"""GQA attention with RoPE, KV cache, cross-attention, and TP/SP sharding;
multi-head latent attention (MLA, DeepSeek-V2).

Modes:
  * full causal (train / prefill — prefill also writes the cache),
  * single-token decode against a cache (serve_step),
  * bidirectional (whisper encoder), cross-attention (whisper decoder).

MLA caches one latent row a token, `[rmsnorm(c_kv) | rope(k_pe) | 0]`
(kv_lora_rank + qk_rope_head_dim values, zero-padded to whole 128-column
tiles for the paged kernel, `latent_row_width`; one "head"), in place of K
and V.
Train and prefill run the published form: K and V are expanded from the
latent per head, q.k is qk_nope + qk_rope wide and v is v_head_dim wide.
Decode runs the absorbed form: q_nope is taken into the latent through
W_UK^T, scores are q_abs . [c_kv | k_pe] over the cached rows, and the
attention-weighted latent goes out through W_UV, then wo — the same
mathematics, without expanding K or V for the whole cache.

Sharding: activations ('batch','seq','heads','head_dim'); the KV cache uses
('kv_batch','kv_seq','kv_heads','head_dim') so long-context decode can switch
to sequence-parallel rules (kv_seq -> mesh axes) when kv_heads doesn't divide
the 'model' axis — see parallel/sharding.py.  Softmax statistics over a
sequence-sharded cache are handled by XLA SPMD (the (B, H, 1, T) score tensor
for one decode token is small; the collective is a cheap all-reduce).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import latent_row_width
from repro.models.layers import (
    PSpec,
    ShardCtx,
    apply_rope,
    dense,
    rmsnorm,
    rope_inv_freq,
    yarn_mscale,
)

__all__ = [
    "attn_specs",
    "attention",
    "attention_paged_decode",
    "cache_leaves",
    "init_cache_shape",
    "mla_attention",
    "mla_paged_decode",
    "mla_scale",
    "mla_specs",
    "Cache",
]

Cache = Dict[str, jax.Array]  # {"k": (B, T, KV, hd), "v": (B, T, KV, hd)}


def attn_specs(cfg, *, prefix_scale: float = 1.0) -> Dict[str, PSpec]:
    d, hd = cfg.d_model, cfg.head_dim_
    h, kv = cfg.num_heads, cfg.num_kv_heads
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    specs = {
        "wq": PSpec((d, h * hd), ("embed", "heads"), 0.02 * prefix_scale),
        "wk": PSpec((d, kv * hd), ("embed", "kv_heads"), 0.02 * prefix_scale),
        "wv": PSpec((d, kv * hd), ("embed", "kv_heads"), 0.02 * prefix_scale),
        "wo": PSpec((h * hd, d), ("heads", "embed"), out_scale),
    }
    if cfg.qkv_bias:
        specs["bq"] = PSpec((h * hd,), ("heads",), init="zeros")
        specs["bk"] = PSpec((kv * hd,), ("kv_heads",), init="zeros")
        specs["bv"] = PSpec((kv * hd,), ("kv_heads",), init="zeros")
    return specs


def cache_leaves(cfg) -> Dict[str, Tuple[int, int]]:
    """(heads, width) of each per-layer cache leaf: K and V, or MLA's one
    latent row a token."""
    if cfg.is_mla:
        return {"latent": (1, latent_row_width(cfg.kv_lora_rank + cfg.qk_rope_head_dim))}
    kv, hd = cfg.num_kv_heads, cfg.head_dim_
    return {"k": (kv, hd), "v": (kv, hd)}


def init_cache_shape(cfg, batch: int, max_len: int) -> Dict[str, Tuple[int, ...]]:
    return {name: (batch, max_len, h, w) for name, (h, w) in cache_leaves(cfg).items()}


def _sdpa_chunked(
    q: jax.Array,  # (B, Tq, H, hd)
    k: jax.Array,  # (B, Tk, KV, hd)
    v: jax.Array,
    *,
    causal: bool,
    chunk: int,
    unroll: bool = False,
) -> jax.Array:
    """Flash-style attention: online softmax over KV chunks.

    Never materializes the (Tq, Tk) score matrix — the working set per step
    is (Tq, chunk), so HBM traffic drops from O(T^2) to O(T * chunk + T * hd)
    per head.  This is the hillclimb fix for the memory-dominant prefill/train
    cells (EXPERIMENTS.md §Perf).  The chunk loop is a lax.scan whose body is
    jax.checkpoint'd: AD saves only the (m, l, acc) running stats per chunk,
    not the per-chunk probability blocks.

    Equivalent to _sdpa up to fp error; property-tested in
    tests/test_attention.py.
    """
    b, tq, h, hd = q.shape
    tk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    if tk % chunk:
        raise ValueError(f"Tk={tk} not divisible by chunk={chunk}")
    nc = tk // chunk
    q5 = q.reshape(b, tq, kvh, rep, hd)
    scale = hd**-0.5

    kc = jnp.moveaxis(k.reshape(b, nc, chunk, kvh, hd), 1, 0)  # (nc,B,C,KV,hd)
    vc = jnp.moveaxis(v.reshape(b, nc, chunk, kvh, hd), 1, 0)
    qpos = jnp.arange(tq)[:, None]  # (Tq, 1)

    @jax.checkpoint
    def body(carry, inp):
        m, l, acc = carry  # (B,KV,rep,Tq), (B,KV,rep,Tq), (B,Tq,KV,rep,hd) f32
        j, kj, vj = inp
        s = jnp.einsum(
            "btkrd,bskd->bkrts", q5, kj, preferred_element_type=jnp.float32
        ) * scale  # (B,KV,rep,Tq,C)
        if causal:
            kpos = j * chunk + jnp.arange(chunk)[None, :]
            s = jnp.where((kpos <= qpos)[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])  # (B,KV,rep,Tq,C)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkrts,bskd->btkrd", p.astype(q.dtype), vj)
        acc_new = acc * jnp.moveaxis(corr, -1, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, rep, tq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, tq), jnp.float32)
    acc0 = jnp.zeros((b, tq, kvh, rep, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(nc), kc, vc), unroll=unroll
    )
    out = acc / jnp.moveaxis(l, -1, 1)[..., None]
    return out.reshape(b, tq, h, hd).astype(q.dtype)


def _sdpa(
    q: jax.Array,  # (B, Tq, H, hd)
    k: jax.Array,  # (B, Tk, KV, hd)
    v: jax.Array,
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,
    kv_valid_len: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Grouped-query SDPA with f32 softmax; no KV-head materialized repeat.
    V may be narrower than q and K (MLA); `scale` defaults to hd^-0.5."""
    b, tq, h, hd = q.shape
    tk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    q5 = q.reshape(b, tq, kvh, rep, hd)
    scores = jnp.einsum(
        "btkrd,bskd->bkrts", q5, k, preferred_element_type=jnp.float32
    )
    scores = scores / (hd**0.5) if scale is None else scores * scale
    if causal:
        qpos = jnp.arange(tq)[:, None] + q_offset  # (Tq, 1)
        kpos = jnp.arange(tk)[None, :]
        mask = kpos <= qpos  # (Tq, Tk)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    if kv_valid_len is not None:
        valid = jnp.arange(tk)[None, :] < kv_valid_len  # mask unwritten cache
        scores = jnp.where(valid[:, None, None, None] if valid.ndim == 2 else valid[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrts,bskd->btkrd", probs, v)
    return out.reshape(b, tq, h, v.shape[-1])


def _write_slot_rows(
    pools: Dict[str, jax.Array],  # {name: (L, P, KV, page_size, hd)}
    rows: Dict[str, jax.Array],  # {name: (S, KV, hd)} one new row a slot
    layer: jax.Array,  # scalar int32
    block_tables: jax.Array,  # (S, n_pages) int32
    positions: jax.Array,  # (S,) int32
) -> Dict[str, jax.Array]:
    """Each slot's new row of each pool, written in place at `[layer,
    block_tables[s, pos // page_size], :, pos % page_size]`.

    Each slot's page is read, the row put in at its offset, and the S pages
    written back whole: one gather and one scatter a pool, whose window is
    the page's own (page_size, hd) tiles, so XLA keeps the pool's layout
    and updates it in place.  A scatter of the rows alone, or a
    dynamic-update-slice a row, makes XLA on the TPU lay the pool out anew
    and copy it whole around the write.  Idle slots (all-zero table,
    position 0) all write layer `layer`'s scratch page 0; which of them
    lands there is of no account."""
    ps = next(iter(pools.values())).shape[3]
    page = jnp.take_along_axis(block_tables, (positions // ps)[:, None], axis=1)[:, 0]
    at_row = jnp.arange(ps)[None, None, :, None] == (positions % ps)[:, None, None, None]

    def put(pool, row):
        cur = pool[layer, page]  # (S, KV, ps, hd): each slot's page
        new = jnp.where(at_row, row.astype(pool.dtype)[:, :, None, :], cur)
        return pool.at[layer, page].set(new)

    return {name: put(pool, rows[name]) for name, pool in pools.items()}


def _layer_pages(pool: jax.Array, layer: jax.Array, block_tables: jax.Array):
    """A stacked pool (L, P, KV, ps, hd) as one pool of L*P pages, and
    `block_tables` moved to layer `layer`'s pages.  Merging the two leading
    dims is a view (no copy); the kernel and the gather read the layer's
    pages through the shifted tables, and an idle slot's zeros land on the
    layer's own scratch page."""
    n_layers, n_pages = pool.shape[:2]
    return pool.reshape(n_layers * n_pages, *pool.shape[2:]), block_tables + layer * n_pages


def attention_paged_decode(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (S, 1, D) — one new token per sequence slot
    cfg,
    ctx: ShardCtx,
    *,
    pools: Dict[str, jax.Array],  # {"k", "v"}: (L, P, KV, page_size, hd) stacked pools
    layer: jax.Array,  # scalar int32: this layer's index in the stack
    block_tables: jax.Array,  # (S, n_pages) int32
    positions: jax.Array,  # (S,) int32 — each slot's current length
    impl: Optional[str] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token decode against a paged KV pool (DESIGN.md §12).

    The per-slot analogue of the `cache=...` branch of `attention`: the new
    K/V lands in page `block_tables[s, pos // page_size]` of layer `layer`
    at in-page offset `pos % page_size` (`_write_slot_rows`), then the slot
    attends over its pages through `kernels.paged_attention`.  Per-slot
    positions replace the shared scalar `cache_pos`, so every slot can sit
    at a different depth — the property continuous batching needs.
    Op-for-op identical per row to the dense decode path (the xla_gather
    impl mirrors `_sdpa`), so a request served through pages is
    bitwise-equal to `generate()`.

    Inactive slots (all-zero block table, position 0) write into their
    layer's page 0 — the scheduler's reserved scratch page — and their
    output is discarded.  Returns (y (S, 1, D), the stacked pools with the
    token written).
    """
    from repro.kernels.paged_attention import paged_attention

    s, t, _ = x.shape
    if t != 1:
        raise ValueError(f"paged decode is single-token; got T={t}")
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    pos2 = positions[:, None]  # (S, 1) per-row positions for RoPE

    q = dense(x, p["wq"], cfg, p.get("bq"), ctx=ctx).reshape(s, 1, h, hd)
    k = dense(x, p["wk"], cfg, p.get("bk"), ctx=ctx).reshape(s, 1, kvh, hd)
    v = dense(x, p["wv"], cfg, p.get("bv"), ctx=ctx).reshape(s, 1, kvh, hd)
    q = apply_rope(q, pos2, cfg.rope_theta)
    k = apply_rope(k, pos2, cfg.rope_theta)
    q = ctx.c(q, ("batch", "seq", "heads", "head_dim"))

    pools = _write_slot_rows(pools, {"k": k[:, 0], "v": v[:, 0]}, layer,
                            block_tables, positions)
    k_pages, tables = _layer_pages(pools["k"], layer, block_tables)
    v_pages, _ = _layer_pages(pools["v"], layer, block_tables)

    out = paged_attention(
        q.reshape(s, h, hd),
        k_pages,
        v_pages,
        tables,
        positions + 1,  # valid length includes the token just written
        impl=impl,
        interpret=interpret,
    ).reshape(s, 1, h, hd)
    out = ctx.c(out, ("batch", "seq", "heads", "head_dim"))
    y = dense(out.reshape(s, 1, h * hd), p["wo"], cfg, ctx=ctx)
    return ctx.c(y, ("batch", "seq", "embed")), pools


def attention(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, T, D)
    cfg,
    ctx: ShardCtx,
    *,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    use_rope: bool = True,
    cache: Optional[Cache] = None,
    cache_pos: Optional[jax.Array] = None,
    write_cache: bool = False,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Cache]]:
    """Returns (output (B, T, D), updated cache or None).

    Modes:
      cache=None, write_cache=False     train forward (full attention)
      cache=None, write_cache=True      prefill: returns fresh cache = (k, v)
      cache=..., cache_pos=p            decode: T new tokens at position p
      cross_kv=(k, v)                   cross-attention (ignores cache args)
    """
    b, t, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    if positions is None:
        positions = jnp.arange(t)[None, :] + (cache_pos if cache_pos is not None else 0)
        positions = jnp.broadcast_to(positions, (b, t))

    q = dense(x, p["wq"], cfg, p.get("bq"), ctx=ctx).reshape(b, t, h, hd)
    if cross_kv is None:
        k = dense(x, p["wk"], cfg, p.get("bk"), ctx=ctx).reshape(b, t, kvh, hd)
        v = dense(x, p["wv"], cfg, p.get("bv"), ctx=ctx).reshape(b, t, kvh, hd)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = cross_kv
    q = ctx.c(q, ("batch", "seq", "heads", "head_dim"))

    new_cache: Optional[Cache] = None
    kv_valid_len = None
    q_offset: jax.Array | int = 0

    if cross_kv is not None:
        out = _sdpa(q, k, v, causal=False)
    elif cache is not None:
        # Decode: write the T new keys at cache_pos, attend over the prefix.
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, cache_pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, cache_pos, 0, 0))
        ck = ctx.c(ck, ("kv_batch", "kv_seq", "kv_heads", "head_dim"))
        cv = ctx.c(cv, ("kv_batch", "kv_seq", "kv_heads", "head_dim"))
        new_cache = {"k": ck, "v": cv}
        kv_valid_len = cache_pos + t
        q_offset = cache_pos
        out = _sdpa(q, ck, cv, causal=True, q_offset=q_offset, kv_valid_len=kv_valid_len)
    else:
        k = ctx.c(k, ("batch", "seq", "kv_heads", "head_dim"))
        v = ctx.c(v, ("batch", "seq", "kv_heads", "head_dim"))
        chunk = getattr(cfg, "attn_chunk", 0)
        if chunk and t > chunk and t % chunk == 0:
            # Flash-style path; q may additionally be seq-sharded over the TP
            # axis ('seq_attn' rule) when heads don't divide it — context
            # parallelism with replicated KV (see parallel/sharding.py).
            q = ctx.c(q, ("batch", "seq_attn", "heads", "head_dim"))
            out = _sdpa_chunked(
                q, k, v, causal=causal, chunk=chunk, unroll=cfg.scan_unroll
            )
            out = ctx.c(out, ("batch", "seq_attn", "heads", "head_dim"))
        else:
            out = _sdpa(q, k, v, causal=causal)
        if write_cache:
            new_cache = {
                "k": ctx.c(k, ("kv_batch", "kv_seq", "kv_heads", "head_dim")),
                "v": ctx.c(v, ("kv_batch", "kv_seq", "kv_heads", "head_dim")),
            }

    out = ctx.c(out, ("batch", "seq", "heads", "head_dim"))
    y = dense(out.reshape(b, t, h * hd), p["wo"], cfg, ctx=ctx)
    return ctx.c(y, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, no query compression)
# ---------------------------------------------------------------------------


def mla_specs(cfg) -> Dict[str, PSpec]:
    """wq: per head [q_nope | q_pe]; wkv_a: [c_kv | k_pe]; kv_norm on c_kv;
    wkv_b: the latent to per head [k_nope | v] (W_UK, W_UV); wo."""
    d, h = cfg.d_model, cfg.num_heads
    r, nope, rope, dv = (
        cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    )
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    return {
        "wq": PSpec((d, h * (nope + rope)), ("embed", "heads"), 0.02),
        "wkv_a": PSpec((d, r + rope), ("embed", None), 0.02),
        "kv_norm": PSpec((r,), (None,), init="ones"),
        "wkv_b": PSpec((r, h * (nope + dv)), (None, "heads"), 0.02),
        "wo": PSpec((h * dv, d), ("heads", "embed"), out_scale),
    }


def mla_scale(cfg) -> float:
    """Softmax scale: (qk_nope + qk_rope)^-0.5, times YaRN's mscale(factor,
    mscale_all_dim) squared when the context is stretched."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling_factor and cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def _mla_project(p, x, cfg, ctx, positions):
    """(q_nope (B,T,H,nope), q_pe (B,T,H,rope), latent rows (B,T,1,W)):
    the RoPE parts rotated by YaRN's frequencies, pair (2i, 2i+1) by
    frequency i, and scaled by YaRN's cos/sin factor when it is not 1; a
    row is [c_kv | k_pe] and zeros to `latent_row_width`."""
    b, t, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = dense(x, p["wq"], cfg, ctx=ctx).reshape(b, t, h, nope + rope)
    ckv = dense(x, p["wkv_a"], cfg, ctx=ctx)
    c = rmsnorm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    inv = rope_inv_freq(cfg, rope)
    q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta, inv)
    k_pe = apply_rope(ckv[..., None, r:], positions, cfg.rope_theta, inv)
    f = cfg.rope_scaling_factor
    if f:
        m = yarn_mscale(f, cfg.rope_mscale) / yarn_mscale(f, cfg.rope_mscale_all_dim)
        if m != 1.0:
            q_pe, k_pe = q_pe * m, k_pe * m
    return q[..., :nope], q_pe, _pad_latent(jnp.concatenate([c[:, :, None], k_pe], axis=-1))


def _pad_latent(x):
    """Zero columns up to the latent row width."""
    width = x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, latent_row_width(width) - width)])


def _mla_up(p, cfg):
    """W_UK (r, H, nope) and W_UV (r, H, dv) from wkv_b."""
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads, -1)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim :]


def _mla_absorbed(p, q_nope, q_pe, attend, cfg, ctx):
    """Absorbed attention: q_abs = [q_nope W_UK^T | q_pe | 0] (the latent
    row's width), `attend(q_abs)` -> the attention-weighted latent
    (B,T,H,r), then W_UV and wo."""
    b, t, h, _ = q_nope.shape
    w_uk, w_uv = _mla_up(p, cfg)
    q_lat = jnp.einsum(
        "bthn,rhn->bthr", q_nope, w_uk.astype(q_nope.dtype),
        preferred_element_type=jnp.float32,
    ).astype(q_nope.dtype)
    q_abs = _pad_latent(jnp.concatenate([q_lat, q_pe], axis=-1))
    q_abs = ctx.c(q_abs, ("batch", "seq", "heads", None))
    o_lat = attend(q_abs)
    out = jnp.einsum(
        "bthr,rhv->bthv", o_lat, w_uv.astype(o_lat.dtype),
        preferred_element_type=jnp.float32,
    ).astype(q_nope.dtype)
    out = ctx.c(out, ("batch", "seq", "heads", "head_dim"))
    y = dense(out.reshape(b, t, h * cfg.v_head_dim), p["wo"], cfg, ctx=ctx)
    return ctx.c(y, ("batch", "seq", "embed"))


def mla_attention(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, T, D)
    cfg,
    ctx: ShardCtx,
    *,
    cache: Optional[Cache] = None,
    cache_pos: Optional[jax.Array] = None,
    write_cache: bool = False,
) -> Tuple[jax.Array, Optional[Cache]]:
    """`attention`'s causal modes for MLA; the cache is {"latent": (B, T, 1,
    W)}, the rows of `_mla_project`.  Without a cache the published form (K/V expanded from the
    latent); against one, the absorbed form over the cached rows."""
    b, t, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    pos0 = cache_pos if cache_pos is not None else 0
    positions = jnp.broadcast_to(jnp.arange(t)[None, :] + pos0, (b, t))
    q_nope, q_pe, row = _mla_project(p, x, cfg, ctx, positions)
    scale = mla_scale(cfg)

    if cache is not None:
        lat = jax.lax.dynamic_update_slice(
            cache["latent"], row.astype(cache["latent"].dtype), (0, cache_pos, 0, 0)
        )
        lat = ctx.c(lat, ("kv_batch", "kv_seq", "kv_heads", None))

        def attend(q_abs):
            return _sdpa(q_abs, lat, lat[..., :r], causal=True, q_offset=cache_pos,
                         kv_valid_len=cache_pos + t, scale=scale)

        return _mla_absorbed(p, q_nope, q_pe, attend, cfg, ctx), {"latent": lat}

    kv = dense(row[:, :, 0, :r], p["wkv_b"], cfg, ctx=ctx).reshape(b, t, h, nope + dv)
    rope = cfg.qk_rope_head_dim
    k_pe = jnp.broadcast_to(row[:, :, :, r : r + rope], (b, t, h, rope))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    q = ctx.c(q, ("batch", "seq", "heads", "head_dim"))
    k = ctx.c(k, ("batch", "seq", "heads", "head_dim"))
    out = _sdpa(q, k, kv[..., nope:], causal=True, scale=scale)
    out = ctx.c(out, ("batch", "seq", "heads", "head_dim"))
    y = dense(out.reshape(b, t, h * dv), p["wo"], cfg, ctx=ctx)
    new_cache = {"latent": ctx.c(row, ("kv_batch", "kv_seq", "kv_heads", None))} if write_cache else None
    return ctx.c(y, ("batch", "seq", "embed")), new_cache


def mla_paged_decode(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (S, 1, D)
    cfg,
    ctx: ShardCtx,
    *,
    pools: Dict[str, jax.Array],  # {"latent": (L, P, 1, page_size, W)}
    layer: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    impl: Optional[str] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """`attention_paged_decode` for MLA: the slot's latent row lands in its
    page, then the absorbed form attends over the latent pages through
    `kernels.paged_attention`'s latent layout (V = the rows' first r
    columns).  Returns (y (S, 1, D), the stacked pool with the row
    written)."""
    from repro.kernels.paged_attention import paged_attention

    s, t, _ = x.shape
    if t != 1:
        raise ValueError(f"paged decode is single-token; got T={t}")
    h, r = cfg.num_heads, cfg.kv_lora_rank
    q_nope, q_pe, row = _mla_project(p, x, cfg, ctx, positions[:, None])

    pools = _write_slot_rows(pools, {"latent": row[:, 0]}, layer, block_tables, positions)
    pages, tables = _layer_pages(pools["latent"], layer, block_tables)

    def attend(q_abs):
        out = paged_attention(
            q_abs.reshape(s, h, -1), pages, None, tables, positions + 1,
            scale=mla_scale(cfg), v_width=r, impl=impl, interpret=interpret,
        )
        return out.reshape(s, 1, h, r)

    return _mla_absorbed(p, q_nope, q_pe, attend, cfg, ctx), pools
