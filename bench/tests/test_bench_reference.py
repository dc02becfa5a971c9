"""The plain float32 reference against the program, at a small size on the
CPU, both in float32: prefill logits, cached decode through the scheduler,
and the training loss with sigma scrambling.

Tolerances: both sides compute in float32 and differ only in the order of
their sums (the program's GEMMs run the Pallas mesh kernel, interpreted,
over blocks of K), so the logits agree to a few float32 ulps of the logits'
scale: 1e-5 of it, about 100 ulps, leaves room for two layers of such
reorderings.  The loss is a mean of such logits' log-sum-exps: 1e-6
relative.  A served token is the program's argmax; its reference logit must
lie within 1e-4 standard deviations of the position's logits below the
reference's best (the scale is a few standard deviations, so that is the
same few hundred ulps)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.references import dense_transformer as ref
from bench.tests.small import SMALL_CONFIG
from bench.weights import make_params

CASES = {
    # mesh-paper's shape of block: untied head, as many KV heads as heads,
    # the mesh kernel, and sigma scrambling on a 2 x 2 grid of 128-blocks.
    "mesh-paper": dict(SMALL_CONFIG, registry_id="mesh-paper", scramble_privacy=True,
                       gemm_backend="pallas_mesh", tie_word_embeddings=False),
    # granite's: a tied head, grouped-query attention (2 heads on 1 KV head),
    # XLA's dot.
    "granite-3-8b": dict(SMALL_CONFIG, registry_id="granite-3-8b", num_key_value_heads=1,
                         gemm_backend="xla", tie_word_embeddings=True),
}
COMMON = dict(rope_theta=10000.0, rms_norm_eps=1e-5, torch_dtype="float32",
              paged_impl="xla_gather")


def _setup(name):
    cfg = dict(CASES[name], **COMMON)
    from repro.models import get_model

    model = get_model(harness.arch_config(cfg))
    params = make_params(model.abstract_params(), cfg, seed=2**32 + 17)
    return cfg, model, params


@pytest.mark.parametrize("name", sorted(CASES))
def test_prefill_logits_match(name):
    cfg, model, params = _setup(name)
    tok = jnp.asarray(np.random.default_rng(1).integers(0, cfg["vocab_size"], (1, 40)), jnp.int32)
    got, _ = jax.jit(lambda p, t: model.prefill(p, {"tokens": t, "labels": t}))(params, tok)
    want = ref.forward_logits(params, tok, cfg)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale


@pytest.mark.parametrize("name", sorted(CASES))
def test_training_loss_matches_with_scrambling(name):
    cfg, model, params = _setup(name)
    toks = jnp.asarray(np.random.default_rng(2).integers(0, cfg["vocab_size"], (2, 257)), jnp.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    got = float(jax.jit(lambda p, b: model.loss(p, b)[0])(params, batch))
    want = float(ref.token_loss(params, batch["tokens"], batch["labels"], cfg))
    assert got == pytest.approx(want, rel=1e-6)
    if cfg.get("scramble_privacy"):
        # T = D = 256 forms a square grid: without scrambling the loss differs.
        plain = float(ref.token_loss(params, batch["tokens"], batch["labels"],
                                     dict(cfg, scramble_privacy=False)))
        assert abs(plain - want) > 1e-3


@pytest.mark.parametrize("name", sorted(CASES))
def test_cached_decode_through_the_scheduler_matches(name):
    cfg, model, params = _setup(name)
    from repro.launch.scheduler import ContinuousBatchingServer, Request, ServeConfig

    server = ContinuousBatchingServer(model, params, ServeConfig(
        max_slots=3, page_size=8, num_pages=1 + 3 * 6, max_pages_per_seq=6, queue_capacity=8))
    rng = np.random.default_rng(3)
    prompts = {f"q{i}": rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for i, n in enumerate((5, 12, 17, 9))}
    for rid, p in prompts.items():
        server.submit(Request(rid=rid, prompt=p, max_new_tokens=20))
    server.drain()
    for rid, p in prompts.items():
        served = server.results[rid].tokens
        assert len(served) == 20
        g = ref.served_gaps(params, p, served, cfg, pad_to=16)
        assert g["served_gap"] <= 1e-4, rid


def test_sigma_matches_the_papers_table():
    # Kak 2010, the printed n = 4 arrangement: node (i, j) computes c_pq.
    table4 = ["11 22 33 44", "12 31 24 43", "32 14 41 23", "34 42 13 21"]
    perm = ref.sigma_perm(4)
    for i, row in enumerate(table4):
        for j, pq in enumerate(row.split()):
            p, q = int(pq[0]), int(pq[1])
            assert perm[i * 4 + j] == (p - 1) * 4 + (q - 1)
    for n in (2, 3, 16):
        assert sorted(ref.sigma_perm(n)) == list(range(n * n))
