"""Decoder-only transformer LM (dense + MoE families).

Covers granite-3-8b, phi3-medium-14b, qwen2-7b, mistral-large-123b (dense),
olmoe-1b-7b, qwen2-moe-a2.7b (MoE), the pixtral-12b backbone, and the
mesh-paper demo config.

Layers are stacked on a leading (L,) axis and executed with `jax.lax.scan`
(compile time ~independent of depth — essential for 88-layer dry-runs) with a
configurable remat policy.  Entry points: `lm_forward` (train), `lm_prefill`,
`lm_decode` (serving, stacked per-layer KV caches carried through the scan).

The paper's scrambling system is integrated as an optional privacy transform:
with cfg.scramble_privacy the embedding-output activation block-grid is
scrambled with S and unscrambled before the head — a zero-FLOP keyed
permutation (examples/scrambling_demo.py; square grids only).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.scramble import scramble_order
from repro.kernels.ops import scramble_blocks
from repro.models.attention import (
    attention,
    attention_paged_decode,
    attn_specs,
    init_cache_shape,
)
from repro.models.layers import PSpec, ShardCtx, gemm, padded_vocab, rmsnorm
from repro.models.moe import moe_block, moe_specs, swiglu, swiglu_specs

__all__ = [
    "lm_specs",
    "lm_forward",
    "lm_prefill",
    "lm_decode",
    "lm_decode_paged",
    "paged_pool_specs",
    "stack_specs",
    "embed_tokens",
    "unembed",
    "block_specs",
    "block_apply",
]


def stack_specs(specs: Any, num: int) -> Any:
    """Prepend a stacked 'layers' dim to every PSpec leaf."""
    return jax.tree.map(
        lambda s: PSpec(
            (num,) + s.shape, ("layers",) + s.axes, s.scale, s.dtype, s.init
        ),
        specs,
        is_leaf=lambda x: isinstance(x, PSpec),
    )


def block_specs(cfg) -> Dict[str, Any]:
    """One transformer block: attn + (SwiGLU | MoE) + 2 norms."""
    specs: Dict[str, Any] = {
        "ln1": PSpec((cfg.d_model,), ("embed",), init="ones"),
        "ln2": PSpec((cfg.d_model,), ("embed",), init="ones"),
        "attn": attn_specs(cfg),
    }
    if cfg.is_moe:
        specs["moe"] = moe_specs(cfg)
    else:
        specs["mlp"] = swiglu_specs(cfg, cfg.d_ff)
    return specs


def block_apply(
    p: Dict[str, Any],
    x: jax.Array,
    cfg,
    ctx: ShardCtx,
    *,
    cache=None,
    cache_pos=None,
    write_cache: bool = False,
) -> Tuple[jax.Array, Any, Dict[str, jax.Array]]:
    """Pre-norm block.  Returns (x, new_cache, aux)."""
    h, new_cache = attention(
        p["attn"],
        rmsnorm(x, p["ln1"], cfg.norm_eps),
        cfg,
        ctx,
        cache=cache,
        cache_pos=cache_pos,
        write_cache=write_cache,
    )
    x = x + h
    aux = {}
    if cfg.is_moe:
        h2, aux = moe_block(p["moe"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, ctx)
    else:
        h2 = swiglu(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, ctx)
    return x + h2, new_cache, aux


def lm_specs(cfg) -> Dict[str, Any]:
    vpad = padded_vocab(cfg)
    specs: Dict[str, Any] = {
        "embed": PSpec((vpad, cfg.d_model), ("vocab", "embed"), 0.02),
        "blocks": stack_specs(block_specs(cfg), cfg.num_layers),
        "final_norm": PSpec((cfg.d_model,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = PSpec((cfg.d_model, vpad), ("embed", "vocab"), 0.02)
    return specs


def embed_tokens(params, tokens: jax.Array, cfg, ctx: ShardCtx) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.adtype)
    return ctx.c(x, ("batch", "seq", "embed"))


def unembed(params, x: jax.Array, cfg, ctx: ShardCtx) -> jax.Array:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = gemm(x, head.astype(x.dtype), cfg, ctx=ctx)
    # Padded vocab rows (vocab_pad_multiple) never win loss/argmax.
    if head.shape[-1] != cfg.vocab_size:
        mask = jnp.arange(head.shape[-1]) < cfg.vocab_size
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    return ctx.c(logits, ("batch", "seq", "vocab"))


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    if policy == "full":
        return jax.checkpoint(fn)
    raise ValueError(f"unknown remat policy {policy!r}")


def _maybe_scramble(
    x: jax.Array, cfg, ctx: ShardCtx, inverse: bool = False
) -> jax.Array:
    """Paper scrambling system on (T, D) activation block grids (square only).

    Under a mesh the Pallas kernel runs on each device's share of the batch
    (GSPMD cannot partition it)."""
    if not cfg.scramble_privacy:
        return x
    t, d = x.shape[-2], x.shape[-1]
    bm, bn = 128, 128
    if t % bm or d % bn or t // bm != d // bn:
        return x  # non-square grid: scrambling skipped (demo feature)
    fn = functools.partial(
        scramble_blocks, block_m=bm, block_n=bn, k=-1 if inverse else 1
    )
    if ctx.mesh is None:
        return fn(x)
    rows = P(ctx.batch_axes(x.shape[0]))
    return jax.shard_map(
        fn, mesh=ctx.mesh, in_specs=rows, out_specs=rows, check_vma=False
    )(x)


def lm_forward(params, tokens: jax.Array, cfg, ctx: ShardCtx = ShardCtx()):
    """Train/eval forward: (B, T) int32 -> (logits (B, T, V), aux dict)."""
    x = embed_tokens(params, tokens, cfg, ctx)
    x = _maybe_scramble(x, cfg, ctx)

    def body(x, lp):
        y, _, aux = block_apply(lp, x, cfg, ctx)
        y = ctx.c(y, ("batch", "seq_sp", "embed"))  # SP remat carrier
        aux_vec = jnp.stack(
            [aux.get("lb_loss", jnp.zeros((), jnp.float32)),
             aux.get("router_z", jnp.zeros((), jnp.float32))]
        )
        return y, aux_vec

    body = _remat(body, cfg.remat_policy)
    x, aux_stack = jax.lax.scan(body, x, params["blocks"], unroll=cfg.scan_unroll)
    x = _maybe_scramble(x, cfg, ctx, inverse=True)
    logits = unembed(params, x, cfg, ctx)
    aux = {"lb_loss": aux_stack[:, 0].mean(), "router_z": aux_stack[:, 1].mean()}
    return logits, aux


def lm_prefill(params, tokens: jax.Array, cfg, ctx: ShardCtx = ShardCtx()):
    """Prefill: returns (logits (B, T, V), stacked caches (L, B, T, KV, hd))."""
    x = embed_tokens(params, tokens, cfg, ctx)

    def body(x, lp):
        y, cache, _ = block_apply(lp, x, cfg, ctx, write_cache=True)
        return ctx.c(y, ("batch", "seq_sp", "embed")), cache

    x, caches = jax.lax.scan(body, x, params["blocks"], unroll=cfg.scan_unroll)
    logits = unembed(params, x, cfg, ctx)
    return logits, caches


def lm_decode(
    params,
    tokens: jax.Array,  # (B, T_new) — usually T_new = 1
    caches,  # stacked (L, B, T_max, KV, hd) pytree {"k","v"}
    pos: jax.Array,  # scalar int32: current length
    cfg,
    ctx: ShardCtx = ShardCtx(),
):
    """One decode step against per-layer KV caches; returns (logits, caches)."""
    x = embed_tokens(params, tokens, cfg, ctx)

    def body(x, layer_in):
        lp, cache = layer_in
        y, new_cache, _ = block_apply(lp, x, cfg, ctx, cache=cache, cache_pos=pos)
        return y, new_cache

    x, new_caches = jax.lax.scan(body, x, (params["blocks"], caches), unroll=cfg.scan_unroll)
    logits = unembed(params, x, cfg, ctx)
    return logits, new_caches


def block_apply_paged(
    p: Dict[str, Any],
    x: jax.Array,  # (S, 1, D)
    cfg,
    ctx: ShardCtx,
    *,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    impl: Optional[str] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """`block_apply`'s decode branch against a paged KV pool (DESIGN.md §12)."""
    h, pools = attention_paged_decode(
        p["attn"],
        rmsnorm(x, p["ln1"], cfg.norm_eps),
        cfg,
        ctx,
        k_pool=k_pool,
        v_pool=v_pool,
        block_tables=block_tables,
        positions=positions,
        impl=impl,
        interpret=interpret,
    )
    x = x + h
    if cfg.is_moe:
        h2, _ = moe_block(p["moe"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, ctx)
    else:
        h2 = swiglu(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, ctx)
    return x + h2, pools


def lm_decode_paged(
    params,
    tokens: jax.Array,  # (S, 1) — one token per sequence slot
    pools,  # {"k","v"}: (L, P, KV, page_size, hd) shared page pools
    block_tables: jax.Array,  # (S, n_pages) int32
    positions: jax.Array,  # (S,) int32 per-slot lengths
    cfg,
    ctx: ShardCtx = ShardCtx(),
    *,
    impl: Optional[str] = None,
    interpret: bool = False,
):
    """One continuous-batching decode step: every slot advances one token
    against its own block-table pages (per-slot positions — slots sit at
    different depths).  Returns (logits (S, 1, V), updated pools)."""
    x = embed_tokens(params, tokens, cfg, ctx)

    def body(x, layer_in):
        lp, kp, vp = layer_in
        y, (nk, nv) = block_apply_paged(
            lp,
            x,
            cfg,
            ctx,
            k_pool=kp,
            v_pool=vp,
            block_tables=block_tables,
            positions=positions,
            impl=impl,
            interpret=interpret,
        )
        return y, (nk, nv)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["blocks"], pools["k"], pools["v"]), unroll=cfg.scan_unroll
    )
    logits = unembed(params, x, cfg, ctx)
    return logits, {"k": ks, "v": vs}


def paged_pool_specs(cfg, num_pages: int, page_size: int):
    """Abstract stacked page pools for the serving scheduler (one per layer)."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim_
    shp = (cfg.num_layers, num_pages, kv, page_size, hd)
    return {
        "k": jax.ShapeDtypeStruct(shp, cfg.adtype),
        "v": jax.ShapeDtypeStruct(shp, cfg.adtype),
    }


def decode_cache_specs(cfg, batch: int, max_len: int):
    """Abstract stacked cache for serve_step lowering (ShapeDtypeStruct tree)."""
    shp = init_cache_shape(cfg, batch, max_len)
    return {
        name: jax.ShapeDtypeStruct((cfg.num_layers,) + s, cfg.adtype)
        for name, s in shp.items()
    }
