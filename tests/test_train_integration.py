"""End-to-end integration: training convergence, grad accumulation,
generation, and the launch drivers' public APIs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.serve import generate
from repro.launch.train import build_trainer
from repro.models import get_model
from repro.optim import AdamWConfig, constant
from repro.train.train_step import init_train_state, make_train_step


def test_loss_decreases_on_structured_stream():
    """~60 steps on the Markov-ish synthetic corpus: loss must clearly drop."""
    cfg = get_config("qwen2-7b").reduced()
    step_fn, state, data = build_trainer(cfg, batch=8, seq=64, lr=1e-3, total_steps=60)
    first, last = None, None
    for i in range(60):
        state, metrics = step_fn(state, next(data))
        if i == 4:
            first = float(metrics["loss"])
        last = float(metrics["loss"])
    assert last < first - 0.3, (first, last)


def test_grad_accum_matches_full_batch():
    """grad_accum=2 must produce (numerically) the same update as accum=1."""
    cfg = get_config("qwen2-7b").reduced()
    model = get_model(cfg)
    state1 = init_train_state(model, jax.random.PRNGKey(0))
    state2 = jax.tree.map(jnp.copy, state1)
    toks = jax.random.randint(jax.random.PRNGKey(5), (8, 32), 0, cfg.vocab_size).astype(jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    acfg = AdamWConfig(clip_norm=0.0)  # clip uses pre-mean norms; disable for exactness
    s1 = jax.jit(make_train_step(model, constant(1e-3), acfg, grad_accum=1))
    s2 = jax.jit(make_train_step(model, constant(1e-3), acfg, grad_accum=2))
    new1, m1 = s1(state1, batch)
    new2, m2 = s2(state2, batch)
    assert m1["loss"] == pytest.approx(float(jnp.mean(m2["loss"])), rel=1e-5)
    for a, b in zip(jax.tree.leaves(new1["params"]), jax.tree.leaves(new2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("arch", ["qwen2-7b", "rwkv6-1.6b", "zamba2-1.2b", "olmoe-1b-7b"])
def test_generate_runs_all_decode_families(arch):
    cfg = get_config(arch).reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size).astype(jnp.int32)
    out, rate = generate(model, params, prompts, gen_len=5)
    assert out.shape == (2, 5)
    assert rate > 0
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))


def test_generate_greedy_matches_forward_argmax():
    """First generated token == argmax of the forward logits at the last
    prompt position (greedy decoding is exact)."""
    cfg = get_config("granite-3-8b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(2), (3, 10), 0, cfg.vocab_size).astype(jnp.int32)
    out, _ = generate(model, params, prompts, gen_len=2)
    logits, _ = model.forward(params, {"tokens": prompts, "labels": prompts})
    want = jnp.argmax(logits[:, -1, :], axis=-1)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(want))


def test_trainer_cli_smoke(tmp_path):
    """launch.train main(): 6 steps with checkpointing + resume."""
    from repro.launch.train import main

    ckpt_dir = str(tmp_path / "ck")
    main([
        "--arch", "qwen2-7b", "--reduced", "--steps", "4", "--batch", "2",
        "--seq", "16", "--ckpt-dir", ckpt_dir, "--ckpt-every", "2", "--log-every", "2",
    ])
    # resume continues from step 4 to 6
    main([
        "--arch", "qwen2-7b", "--reduced", "--steps", "6", "--batch", "2",
        "--seq", "16", "--ckpt-dir", ckpt_dir, "--ckpt-every", "2",
        "--resume", "auto", "--log-every", "2",
    ])
    from repro.checkpoint.manager import CheckpointManager

    assert CheckpointManager(ckpt_dir).latest_step() == 6


def test_serve_cli_smoke(capsys):
    from repro.launch.serve import main

    main(["--arch", "rwkv6-1.6b", "--reduced", "--batch", "2", "--prompt-len", "8", "--gen", "4"])
    out = capsys.readouterr().out
    assert "decode steps/s" in out


def test_serve_cli_exits_nonzero_when_a_request_is_skipped(capsys):
    from repro.launch.serve import main
    from repro.resilience import faults, ledger

    ledger.clear()
    with faults.inject({"serve.request": faults.FaultSpec(times=1)}):
        with pytest.raises(SystemExit, match="1 of 2 requests failed"):
            main(["--arch", "rwkv6-1.6b", "--reduced", "--batch", "2",
                  "--prompt-len", "8", "--gen", "4", "--requests", "2"])
    out = capsys.readouterr().out
    assert "request 0 FAILED" in out and "req 1: decode steps/s" in out
    ledger.clear()


def test_mesh_kernel_backend_trains():
    """cfg.use_mesh_kernel: the paper's Pallas GEMM backend in a real
    train step (interpret mode on CPU), gradients flowing through the
    custom VJP."""
    import dataclasses

    cfg = dataclasses.replace(get_config("mesh-paper").reduced(), use_mesh_kernel=True)
    model = get_model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, constant(1e-3)))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size).astype(jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    new_state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert float(metrics["grad_norm"]) > 0


def test_scramble_privacy_transform():
    """The paper's scrambling system as an activation privacy transform:
    the stack computes on S-permuted block grids (so logits DIFFER from the
    plain run — that is the point), stays finite, and trains."""
    import dataclasses

    base = get_config("mesh-paper").reduced()
    # (T=256, D=128) -> 2x1 grid is non-square; use T=D=256 for a 2x2 S grid
    cfg_off = dataclasses.replace(base, scramble_privacy=False, d_model=256, head_dim=64)
    cfg_on = dataclasses.replace(base, scramble_privacy=True, d_model=256, head_dim=64)
    m_off, m_on = get_model(cfg_off), get_model(cfg_on)
    params = m_off.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, cfg_off.vocab_size).astype(jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    l_off, _ = m_off.forward(params, batch)
    l_on, _ = m_on.forward(params, batch)
    assert bool(jnp.all(jnp.isfinite(l_on.astype(jnp.float32))))
    # the permutation genuinely re-routes information through the stack
    assert float(jnp.max(jnp.abs(l_on - l_off))) > 1e-4
    # and the scrambled model still trains (gradients flow through S/S^-1)
    state = init_train_state(m_on, jax.random.PRNGKey(2))
    step = jax.jit(make_train_step(m_on, constant(1e-3)))
    _, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"]) and float(metrics["grad_norm"]) > 0
