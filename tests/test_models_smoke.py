"""Per-architecture smoke tests (all 10 assigned archs, the paper demo
config and DeepSeek-V2-Lite's latent attention and leading dense layer).

Each arch instantiates its REDUCED config (same family/code paths, tiny dims)
and runs:
  * forward + loss: output shapes, no NaNs,
  * one real train step: loss/grad-norm finite, params actually change,
  * prefill -> decode consistency: stepwise decode logits must match the
    teacher-forced forward logits at the same positions (validates every
    family's cache/state carry — KV caches, WKV state, SSD state, conv state,
    cross-attention caches).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models import get_model
from repro.optim import AdamWConfig, constant
from repro.train.train_step import init_train_state, make_train_step

ALL_ARCHS = list(ASSIGNED_ARCHS) + ["mesh-paper", "deepseek-v2-lite"]


def _batch_for(cfg, b=2, t=16, seed=0):
    key = jax.random.PRNGKey(seed)
    toks = jax.random.randint(key, (b, t), 0, cfg.vocab_size).astype(jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.family == "audio":
        t_enc = t * cfg.dec_ratio
        batch = {
            "frames": jax.random.normal(key, (b, t_enc, cfg.d_model), cfg.adtype),
            "tokens": toks,
            "labels": jnp.roll(toks, -1, axis=1),
        }
    elif cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (b, cfg.num_stub_patches, cfg.d_model), cfg.adtype
        )
    return batch


@pytest.fixture(scope="module")
def models():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch).reduced()
            model = get_model(cfg)
            params = model.init(jax.random.PRNGKey(1))
            cache[arch] = (cfg, model, params)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch, models):
    cfg, model, params = models(arch)
    batch = _batch_for(cfg)
    logits, aux = model.forward(params, batch)
    b, t = batch["tokens"].shape
    assert logits.shape == (b, t, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    loss, metrics = model.loss(params, batch)
    assert jnp.isfinite(loss)
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch, models):
    cfg, model, params = models(arch)
    state = init_train_state(model, jax.random.PRNGKey(2))
    step = jax.jit(make_train_step(model, constant(1e-3), AdamWConfig()))
    batch = _batch_for(cfg, seed=3)
    new_state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"]) and jnp.isfinite(metrics["grad_norm"])
    assert float(metrics["grad_norm"]) > 0
    # params changed
    diff = jax.tree.reduce(
        lambda acc, x: acc + float(jnp.sum(jnp.abs(x[0] - x[1]))),
        jax.tree.map(lambda a, b: (a, b), new_state["params"], state["params"]),
        0.0,
    )
    assert diff > 0
    assert int(new_state["step"]) == 1


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_consistency(arch, models):
    """Teacher-forced forward logits == prefill+stepwise-decode logits."""
    cfg, model, params = models(arch)
    if arch == "mesh-paper":
        # the demo config scrambles activations (square grids only) — the
        # reduced dims make scrambling a no-op, so the test still applies
        pass
    b, t_pre, t_gen = 2, 8, 4
    batch = _batch_for(cfg, b=b, t=t_pre + t_gen, seed=5)
    full_logits, _ = model.forward(params, batch)

    pre_batch = dict(batch)
    pre_batch["tokens"] = batch["tokens"][:, :t_pre]
    pre_batch["labels"] = batch["labels"][:, :t_pre]
    logits_pre, state = model.prefill(params, pre_batch)
    np.testing.assert_allclose(
        np.asarray(logits_pre, np.float32),
        np.asarray(full_logits[:, :t_pre], np.float32),
        rtol=2e-2, atol=2e-2,
    )

    # grow KV caches for families that carry per-position caches
    offset = cfg.num_stub_patches if cfg.family == "vlm" else 0
    if cfg.family in ("dense", "moe", "vlm"):
        state = jax.tree.map(
            lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, t_gen)] + [(0, 0)] * (c.ndim - 3)),
            state,
        )
    elif cfg.family in ("hybrid", "audio"):
        state = {
            k: (
                jnp.pad(v, [(0, 0), (0, 0), (0, t_gen)] + [(0, 0)] * (v.ndim - 3))
                if k in ("kv_k", "kv_v", "k", "v")
                else v
            )
            for k, v in state.items()
        }
    for i in range(t_gen):
        pos = t_pre + i + offset
        tok = batch["tokens"][:, t_pre + i : t_pre + i + 1]
        logits_i, state = model.decode(params, tok, state, jnp.int32(pos))
        np.testing.assert_allclose(
            np.asarray(logits_i[:, 0], np.float32),
            np.asarray(full_logits[:, t_pre + i], np.float32),
            rtol=2e-2, atol=2e-2,
            err_msg=f"{arch}: decode step {i} diverges from forward",
        )


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "zamba2-1.2b"])
def test_long_context_state_is_constant_size(arch, models):
    """The long_500k families must carry O(1)-per-token decode state."""
    cfg, model, params = models(arch)
    s1 = model.decode_state_specs(2, 64)
    s2 = model.decode_state_specs(2, 128)
    if cfg.family == "ssm":
        assert jax.tree.map(lambda x: x.shape, s1) == jax.tree.map(lambda x: x.shape, s2)
    else:  # hybrid: SSM states constant; only shared-attn KV grows
        assert s1["h"].shape == s2["h"].shape
        assert s1["conv"].shape == s2["conv"].shape


def test_moe_router_aux_losses(models):
    cfg, model, params = models("olmoe-1b-7b")
    batch = _batch_for(cfg, seed=7)
    _, aux = model.forward(params, batch)
    assert float(aux["lb_loss"]) > 0.0  # load-balance loss is active
    loss_with, _ = model.loss(params, batch)
    assert jnp.isfinite(loss_with)


def test_whisper_enc_dec_shapes(models):
    cfg, model, params = models("whisper-medium")
    b, t_dec = 2, 8
    batch = _batch_for(cfg, b=b, t=t_dec)
    logits, _ = model.forward(params, batch)
    assert logits.shape == (b, t_dec, cfg.vocab_size)


def test_vlm_patch_prefix_changes_logits(models):
    """Pixtral: image patches must actually condition the text logits."""
    cfg, model, params = models("pixtral-12b")
    batch = _batch_for(cfg, seed=9)
    logits_a, _ = model.forward(params, batch)
    batch2 = dict(batch)
    batch2["patches"] = batch["patches"] + 1.0
    logits_b, _ = model.forward(params, batch2)
    assert float(jnp.max(jnp.abs(logits_a - logits_b))) > 1e-4


@pytest.mark.parametrize("arch", ["granite-3-8b", "olmoe-1b-7b"])
def test_full_configs_match_assignment(arch):
    """Spot-check the FULL (non-reduced) configs against the assignment table."""
    cfg = get_config(arch)
    if arch == "granite-3-8b":
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads) == (40, 4096, 32, 8)
        assert (cfg.d_ff, cfg.vocab_size) == (12800, 49155)
    else:
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads) == (16, 2048, 16)
        assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.vocab_size) == (64, 8, 50304)


def test_all_ten_archs_registered():
    assert len(ASSIGNED_ARCHS) == 10
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        assert cfg.arch_id == arch
