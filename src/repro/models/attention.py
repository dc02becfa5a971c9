"""GQA attention with RoPE, KV cache, cross-attention, and TP/SP sharding.

Modes:
  * full causal (train / prefill — prefill also writes the cache),
  * single-token decode against a cache (serve_step),
  * bidirectional (whisper encoder), cross-attention (whisper decoder).

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

from repro.models.layers import PSpec, ShardCtx, apply_rope, dense

__all__ = [
    "attn_specs",
    "attention",
    "attention_paged_decode",
    "init_cache_shape",
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


def init_cache_shape(cfg, batch: int, max_len: int) -> Dict[str, Tuple[int, ...]]:
    kv, hd = cfg.num_kv_heads, cfg.head_dim_
    return {"k": (batch, max_len, kv, hd), "v": (batch, max_len, kv, hd)}


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
) -> jax.Array:
    """Grouped-query SDPA with f32 softmax; no KV-head materialized repeat."""
    b, tq, h, hd = q.shape
    tk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    q5 = q.reshape(b, tq, kvh, rep, hd)
    scores = jnp.einsum(
        "btkrd,bskd->bkrts", q5, k, preferred_element_type=jnp.float32
    ) / (hd**0.5)
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
    return out.reshape(b, tq, h, hd)


def attention_paged_decode(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (S, 1, D) — one new token per sequence slot
    cfg,
    ctx: ShardCtx,
    *,
    k_pool: jax.Array,  # (P, KV, page_size, hd) shared page pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (S, n_pages) int32
    positions: jax.Array,  # (S,) int32 — each slot's current length
    impl: Optional[str] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token decode against a paged KV pool (DESIGN.md §12).

    The per-slot analogue of the `cache=...` branch of `attention`: the new
    K/V lands in page `block_tables[s, pos // page_size]` at in-page offset
    `pos % page_size`, then the slot attends over its pages through
    `kernels.paged_attention`.  Per-slot positions replace the shared scalar
    `cache_pos`, so every slot can sit at a different depth — the property
    continuous batching needs.  Op-for-op identical per row to the dense
    decode path (the xla_gather impl mirrors `_sdpa`), so a request served
    through pages is bitwise-equal to `generate()`.

    Inactive slots (all-zero block table, position 0) write into page 0 —
    the scheduler's reserved scratch page — and their output is discarded.
    Returns (y (S, 1, D), (k_pool, v_pool) with the token written).
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

    ps = k_pool.shape[2]
    page = jnp.take_along_axis(block_tables, (positions // ps)[:, None], axis=1)[:, 0]
    off = positions % ps
    # (S,) page ids and (S,) in-page offsets around the kv-head slice: each
    # slot writes its (KV, hd) row into page[s][:, off[s]].
    k_pool = k_pool.at[page, :, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[page, :, off].set(v[:, 0].astype(v_pool.dtype))

    out = paged_attention(
        q.reshape(s, h, hd),
        k_pool,
        v_pool,
        block_tables,
        positions + 1,  # valid length includes the token just written
        impl=impl,
        interpret=interpret,
    ).reshape(s, 1, h, hd)
    out = ctx.c(out, ("batch", "seq", "heads", "head_dim"))
    y = dense(out.reshape(s, 1, h * hd), p["wo"], cfg, ctx=ctx)
    return ctx.c(y, ("batch", "seq", "embed")), (k_pool, v_pool)


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
