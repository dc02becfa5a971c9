"""A serving run with a served token altered where it is produced comes out
not correct, and so does the control: the tokens that the reference in
float8 puts first.  Small cells on the CPU, the cells' own limits."""

import time

import jax

from bench import serve_cell
from bench.tests.small import small_cell

SEED = 2**33 + 202


def _run(name, **kw):
    cell = small_cell(name)
    out = serve_cell.run(cell, SEED, 1.0, False, jax.devices()[:1], time.monotonic(), **kw)
    return out


def _correct(out):
    return not out["problems"] and all(v["ok"] for v in out["compared"].values())


def test_chat_sound_run_is_correct():
    out = _run("mesh-paper.chat")
    assert _correct(out), out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_chat_altered_token_is_not_correct():
    assert not _correct(_run("mesh-paper.chat", fault="token"))


def test_offline_altered_token_is_not_correct():
    assert not _correct(_run("granite-3-8b.offline-long", fault="token"))


def test_control_reads_above_the_limit():
    """The control: at each position of a sequence, the token that the
    reference with float8 matrix products puts first, read against the
    float32 reference.  At a width of 1024 (the smallest at which float8's
    rounding reorders the top logits at all), it reads above each serving
    cell's limit; at the cells' own widths it reads higher still."""
    import numpy as np

    from bench import harness
    from bench.references import dense_transformer as ref
    from bench.weights import make_params
    from repro.models import get_model

    cfg = dict(small_cell("granite-3-8b.offline-long").config, hidden_size=1024,
               num_attention_heads=8, num_key_value_heads=8, intermediate_size=2048,
               vocab_size=8192)
    params = make_params(get_model(harness.arch_config(cfg)).abstract_params(), cfg, SEED)
    toks = np.random.default_rng(5).integers(0, cfg["vocab_size"], 512)
    got = ref.served_gaps(params, toks[:64], list(toks[64:]), cfg, control="fp8", pad_to=128)
    for name in ("mesh-paper.chat", "granite-3-8b.offline-long"):
        assert got["control_gap"] > small_cell(name).limits["served_gap"]["limit"], got
