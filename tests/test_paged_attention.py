"""Paged gather-attention: impl parity, masking, and the capability door.

The load-bearing property is BITWISE parity of the xla_gather impl with the
dense `_sdpa` decode path — the continuous-batching scheduler's correctness
contract (a request served through pages equals legacy `generate()`) rests
on it, and test_scheduler.py builds on the model-level version checked here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import paged_attention as pa
from repro.kernels.api import CapabilityError
from repro.models import ShardCtx, get_model
from repro.models.attention import _sdpa


def _setup(rng, *, s=3, h=4, kvh=2, hd=16, ps=8, n_pages=4):
    pool_pages = 1 + s * n_pages
    q = jnp.asarray(rng.standard_normal((s, h, hd)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((pool_pages, kvh, ps, hd)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((pool_pages, kvh, ps, hd)), jnp.float32)
    # Non-contiguous per-slot page sets, every id >= 1 (0 is scratch).
    tables = rng.permutation(np.arange(1, pool_pages))[: s * n_pages]
    bt = jnp.asarray(tables.reshape(s, n_pages), jnp.int32)
    lengths = jnp.asarray([5, 17, s * n_pages * ps // s], jnp.int32)
    return q, k_pool, v_pool, bt, lengths


def test_xla_gather_bitwise_matches_sdpa(rng):
    q, k_pool, v_pool, bt, lengths = _setup(rng)
    out = pa.paged_attention_xla(q, k_pool, v_pool, bt, lengths)
    # The dense reference: gather the same pages into a contiguous cache and
    # run the legacy decode attention at the same valid lengths.
    k = pa.gather_pages(k_pool, bt)
    v = pa.gather_pages(v_pool, bt)
    ref = _sdpa(q[:, None], k, v, causal=False, kv_valid_len=lengths[:, None])
    assert bool(jnp.all(out == ref[:, 0]))


def test_pallas_interpret_matches_xla(rng):
    q, k_pool, v_pool, bt, lengths = _setup(rng)
    out_x = pa.paged_attention_xla(q, k_pool, v_pool, bt, lengths)
    out_p = pa.paged_attention_pallas(q, k_pool, v_pool, bt, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=2e-6)


@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2), (6, 1)])
def test_gqa_head_ratios(rng, h, kvh):
    q, k_pool, v_pool, bt, lengths = _setup(rng, h=h, kvh=kvh)
    out_x = pa.paged_attention_xla(q, k_pool, v_pool, bt, lengths)
    out_p = pa.paged_attention_pallas(q, k_pool, v_pool, bt, lengths, interpret=True)
    assert out_x.shape == q.shape
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=2e-6)


def test_length_masking_ignores_tail_and_unused_pages(rng):
    """Poisoning every pool row past `lengths` (and page 0) must not change
    the output — the paged masking never reads them."""
    q, k_pool, v_pool, bt, lengths = _setup(rng)
    lengths = jnp.asarray([1, 9, 12], jnp.int32)  # mid-page cutoffs
    base = pa.paged_attention_xla(q, k_pool, v_pool, bt, lengths)

    ps = k_pool.shape[2]
    k2, v2 = np.array(k_pool), np.array(v_pool)
    for slot in range(bt.shape[0]):
        ln = int(lengths[slot])
        for pidx in range(bt.shape[1]):
            page = int(bt[slot, pidx])
            start = pidx * ps
            for off in range(ps):
                if start + off >= ln:
                    k2[page, :, off] = 7e5  # large-but-finite garbage
                    v2[page, :, off] = -7e5
    k2[0] = 9e5  # scratch page
    v2[0] = 9e5
    poisoned = pa.paged_attention_xla(q, jnp.asarray(k2), jnp.asarray(v2), bt, lengths)
    assert bool(jnp.all(base == poisoned))


def test_pallas_skips_pages_past_length(rng):
    q, k_pool, v_pool, bt, lengths = _setup(rng)
    lengths = jnp.asarray([3, 8, 21], jnp.int32)
    out_x = pa.paged_attention_xla(q, k_pool, v_pool, bt, lengths)
    out_p = pa.paged_attention_pallas(q, k_pool, v_pool, bt, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=2e-6)


# Heads wide enough that a block holds fewer pages than the table: f32 pages
# of 16 x 128 are 128 KiB at 16 KV heads (4 pages a block, 10 page slots)
# and 64 KiB at 8 (8 a block, 12 page slots), so the last block is short.
_BLOCK_CASES = {"mha": (16, 16, 10), "gqa": (32, 8, 12)}


@pytest.mark.parametrize("length", ["scratch", "one_block", "one_block_plus_1", "full"])
@pytest.mark.parametrize("heads", sorted(_BLOCK_CASES))
def test_pallas_block_loop_matches_xla(rng, heads, length):
    """Slot lengths on each side of a block boundary, a slot on the scratch
    page between two others (the prefetch crosses slots), pages shuffled."""
    h, kvh, n_pages = _BLOCK_CASES[heads]
    ps, hd, s = 16, 128, 3
    ppb = pa.pages_per_block(kvh, ps, hd, jnp.float32, n_pages)
    assert n_pages % ppb != 0
    n = {
        "scratch": 1,
        "one_block": ppb * ps,
        "one_block_plus_1": ppb * ps + 1,
        "full": n_pages * ps,
    }[length]
    pool_pages = 1 + s * n_pages
    q = jnp.asarray(rng.standard_normal((s, h, hd)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((pool_pages, kvh, ps, hd)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((pool_pages, kvh, ps, hd)), jnp.float32)
    tables = rng.permutation(np.arange(1, pool_pages)).reshape(s, n_pages)
    tables[1] = 0  # an idle slot: length 1 on the scratch page
    if n == 1:
        tables[:, 1:] = 0
    bt = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray([n, 1, n], jnp.int32)
    out_x = pa.paged_attention_xla(q, k_pool, v_pool, bt, lengths)
    out_p = pa.paged_attention_pallas(q, k_pool, v_pool, bt, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=2e-6)


def test_pages_per_block_is_a_function_of_shapes():
    # the serving cells' shapes: mesh-paper chat (KV 16, 64 page slots) and
    # Granite-3 8B offline (KV 8, 200 page slots), bf16 pages of 16 x 128
    chat = pa.pages_per_block(16, 16, 128, jnp.bfloat16, 64)
    granite = pa.pages_per_block(8, 16, 128, jnp.bfloat16, 200)
    assert (chat, granite) == (8, 16)
    assert pa.pages_per_block(8, 16, 128, np.dtype(jnp.bfloat16), 200) == granite
    for kvh, n_pages, ppb in ((16, 64, chat), (8, 200, granite)):
        page_bytes = kvh * 16 * 128 * 2
        assert ppb * page_bytes <= pa._BLOCK_BYTES
    # capped by the table, and never below one page however wide the page
    assert pa.pages_per_block(16, 16, 128, jnp.bfloat16, 3) == 3
    assert pa.pages_per_block(2, 8, 16, jnp.float32, 1) == 1
    assert pa.pages_per_block(256, 64, 256, jnp.float32, 64) == 1


def test_shape_validation(rng):
    q, k_pool, v_pool, bt, lengths = _setup(rng)
    with pytest.raises(ValueError, match="head_dim"):
        pa.paged_attention_pallas(q[..., :8], k_pool, v_pool, bt, lengths, interpret=True)
    with pytest.raises(ValueError, match="k/v pool"):
        pa.paged_attention_pallas(q, k_pool, v_pool[:4], bt, lengths, interpret=True)
    with pytest.raises(ValueError, match="slots"):
        pa.paged_attention_pallas(q, k_pool, v_pool, bt[:2], lengths, interpret=True)


# -- capability door ---------------------------------------------------------


def test_door_resolves_by_capability():
    on_tpu = jax.default_backend() == "tpu"
    assert pa.resolve_paged_impl() == ("pallas_paged" if on_tpu else "xla_gather")
    assert pa.resolve_paged_impl(interpret=True) == "pallas_paged"
    assert pa.resolve_paged_impl("xla_gather") == "xla_gather"


@pytest.mark.skipif(jax.default_backend() == "tpu", reason="pallas runs natively on TPU")
def test_door_explicit_unsupported_raises_capability_error():
    with pytest.raises(CapabilityError):
        pa.resolve_paged_impl("pallas_paged")


def test_door_unknown_impl_raises():
    with pytest.raises(ValueError, match="unknown paged impl"):
        pa.resolve_paged_impl("nope")


def test_door_duplicate_registration_guard():
    with pytest.raises(ValueError, match="already registered"):
        pa.register_paged_impl("xla_gather", pa.paged_attention_xla, interpret=True)
    # override is the explicit escape hatch (re-register the same impl)
    pa.register_paged_impl(
        "xla_gather", pa.paged_attention_xla, interpret=True, override=True
    )


def test_paged_dispatch_entrypoint(rng):
    q, k_pool, v_pool, bt, lengths = _setup(rng)
    out = pa.paged_attention(q, k_pool, v_pool, bt, lengths)  # door-resolved
    ref = pa.paged_attention_xla(q, k_pool, v_pool, bt, lengths)
    if jax.default_backend() != "tpu":
        assert bool(jnp.all(out == ref))


# -- model-level paged decode -----------------------------------------------


@pytest.mark.parametrize("arch", ["mesh-paper", "olmoe-1b-7b"])
def test_lm_decode_paged_bitwise_matches_lm_decode(arch):
    """Full-model paged decode == dense-cache decode, bit for bit, when the
    paged capacity equals the legacy cache capacity (same masked softmax)."""
    cfg = get_config(arch).reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ctx = ShardCtx()
    t, ps, n_pages = 8, 8, 2  # capacity 16 == prompt + 8 decode steps
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (1, t), 0, cfg.vocab_size
    ).astype(jnp.int32)

    logits, caches = model.prefill(params, {"tokens": prompts, "labels": prompts}, ctx)
    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    state = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, n_pages * ps - t)] + [(0, 0)] * (c.ndim - 3)),
        caches,
    )

    # The tracked row sits at slot 1 of a 3-slot batch on BOTH sides: XLA:CPU
    # lowers a dot with one row (the MoE router's (1, D) @ (D, E)) as a
    # vector-matrix product whose summation order differs from the 3-row
    # matrix product, so a 1-row dense step and a 3-slot paged step would
    # differ in the last bit of the router logits before attention is even
    # compared.  Equal batches compare the attention paths alone.
    s_slots = 3
    state = jax.tree.map(lambda c: jnp.repeat(c, s_slots, axis=1), state)
    pool_pages = 1 + s_slots * n_pages
    pools = {
        name: jnp.zeros(sd.shape, sd.dtype)
        for name, sd in model.paged_pool_specs(pool_pages, ps).items()
    }
    pages = jnp.asarray([3, 5], jnp.int32)  # non-contiguous, non-leading
    kvh, hd = cfg.num_kv_heads, cfg.head_dim_
    layers = cfg.num_layers

    def put(pool, c):
        return pool.at[:, pages].set(
            c[:, 0].reshape(layers, 1, ps, kvh, hd).transpose(0, 1, 3, 2, 4).astype(pool.dtype)
        )

    pools = {"k": put(pools["k"], caches["k"]), "v": put(pools["v"], caches["v"])}
    bt = jnp.zeros((s_slots, n_pages), jnp.int32).at[1].set(pages)
    tok_p = tok

    for i in range(8):
        toks_d = jnp.repeat(tok[:, None], s_slots, axis=0)
        lg_d, state = model.decode(params, toks_d, state, jnp.int32(t + i), ctx)
        toks = jnp.zeros((s_slots, 1), jnp.int32).at[1, 0].set(tok_p[0])
        positions = jnp.zeros((s_slots,), jnp.int32).at[1].set(t + i)
        lg_p, pools = model.paged_decode(params, toks, pools, bt, positions, ctx)
        assert bool(jnp.all(lg_p[1, -1] == lg_d[1, -1])), f"step {i} diverged"
        tok = jnp.argmax(lg_d[1:2, -1, :], axis=-1).astype(jnp.int32)
        tok_p = jnp.argmax(lg_p[1:2, -1, :], axis=-1).astype(jnp.int32)


def test_paged_decode_rejects_multi_token():
    cfg = get_config("mesh-paper").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pools = {
        name: jnp.zeros(sd.shape, sd.dtype)
        for name, sd in model.paged_pool_specs(4, 8).items()
    }
    with pytest.raises(ValueError, match="single-token"):
        model.paged_decode(
            params,
            jnp.zeros((2, 3), jnp.int32),
            pools,
            jnp.zeros((2, 2), jnp.int32),
            jnp.zeros((2,), jnp.int32),
        )


def test_unsupported_family_has_no_paged_path():
    cfg = get_config("rwkv6-1.6b").reduced()
    model = get_model(cfg)
    assert not model.supports_paged
    with pytest.raises(NotImplementedError, match="paged"):
        model.paged_pool_specs(4, 8)
