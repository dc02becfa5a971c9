"""Decoder-only transformer LM (the dense and MoE families).

Every registered config of family dense or moe runs here, and the
pixtral-12b backbone: GQA or multi-head latent attention (cfg.kv_lora_rank,
DeepSeek-V2), a SwiGLU or MoE feed-forward, and leading dense layers before
the MoE ones (cfg.first_k_dense).

Layers of one kind are stacked on a leading (L,) axis and executed with
`jax.lax.scan` (compile time ~independent of depth — essential for 88-layer
dry-runs) with a configurable remat policy.  A model has one such stack,
`blocks`, or, with leading dense layers, `dense_blocks` then `blocks`, each
scanned in turn (`layer_stacks`).  Entry points: `lm_forward` (train),
`lm_prefill`, `lm_decode` and `lm_decode_paged` (serving: per-layer caches,
stacked per stack and carried through its scan; a dense_blocks stack's
cache leaves are named `dense_<leaf>`).  The serving entry points return
the MoE layers' routing counts of each sequence beside the caches ({} for
a dense model).

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
    cache_leaves,
    init_cache_shape,
    mla_attention,
    mla_paged_decode,
    mla_specs,
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
    "layer_stacks",
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


def layer_stacks(cfg) -> Tuple[Tuple[str, bool, int], ...]:
    """(params key, MoE feed-forward?, layers) of each scanned stack, in
    order: the leading dense layers of an MoE model, then the rest."""
    k = cfg.first_k_dense if cfg.is_moe else 0
    rest = ("blocks", cfg.is_moe, cfg.num_layers - k)
    return (("dense_blocks", False, k), rest) if k else (rest,)


def _cache_prefix(stack: str) -> str:
    return stack[: -len("blocks")]


def block_specs(cfg, moe: Optional[bool] = None) -> Dict[str, Any]:
    """One transformer block: attn + (SwiGLU | MoE) + 2 norms."""
    specs: Dict[str, Any] = {
        "ln1": PSpec((cfg.d_model,), ("embed",), init="ones"),
        "ln2": PSpec((cfg.d_model,), ("embed",), init="ones"),
        "attn": mla_specs(cfg) if cfg.is_mla else attn_specs(cfg),
    }
    if cfg.is_moe if moe is None else moe:
        specs["moe"] = moe_specs(cfg)
    else:
        specs["mlp"] = swiglu_specs(cfg, cfg.d_ff)
    return specs


def _ffn(p, x, cfg, ctx):
    """The block's feed-forward on its normed input: MoE where the block
    holds experts, else SwiGLU.  Returns (output, aux)."""
    y = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        return moe_block(p["moe"], y, cfg, ctx)
    return swiglu(p["mlp"], y, cfg, ctx), {}


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
    h, new_cache = (mla_attention if cfg.is_mla else attention)(
        p["attn"],
        rmsnorm(x, p["ln1"], cfg.norm_eps),
        cfg,
        ctx,
        cache=cache,
        cache_pos=cache_pos,
        write_cache=write_cache,
    )
    x = x + h
    h2, aux = _ffn(p, x, cfg, ctx)
    return x + h2, new_cache, aux


def lm_specs(cfg) -> Dict[str, Any]:
    vpad = padded_vocab(cfg)
    specs: Dict[str, Any] = {
        "embed": PSpec((vpad, cfg.d_model), ("vocab", "embed"), 0.02),
        **{
            name: stack_specs(block_specs(cfg, moe), n)
            for name, moe, n in layer_stacks(cfg)
        },
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
    # The routing losses are the last stack's: the MoE layers follow any
    # leading dense ones.
    for name, _, _ in layer_stacks(cfg):
        x, aux_stack = jax.lax.scan(body, x, params[name], unroll=cfg.scan_unroll)
    x = _maybe_scramble(x, cfg, ctx, inverse=True)
    logits = unembed(params, x, cfg, ctx)
    aux = {"lb_loss": aux_stack[:, 0].mean(), "router_z": aux_stack[:, 1].mean()}
    return logits, aux


def _routed(per_stack) -> Dict[str, jax.Array]:
    """The MoE layers' routing counts of a step per sequence (B,), summed
    over layers and positions ({} for a model without experts)."""
    held = [aux["held_pairs"] for aux in per_stack if "held_pairs" in aux]
    return {"held_pairs": sum(jnp.sum(h, axis=(0, 2)) for h in held)} if held else {}


def _counts(aux) -> Dict[str, jax.Array]:
    return {"held_pairs": aux["held_pairs"]} if "held_pairs" in aux else {}


def lm_prefill(params, tokens: jax.Array, cfg, ctx: ShardCtx = ShardCtx()):
    """Prefill: returns (logits (B, T, V), caches {name: (L, B, T, KV, hd)},
    routing counts)."""
    x = embed_tokens(params, tokens, cfg, ctx)

    def body(x, lp):
        y, cache, aux = block_apply(lp, x, cfg, ctx, write_cache=True)
        return ctx.c(y, ("batch", "seq_sp", "embed")), (cache, _counts(aux))

    caches, per_stack = {}, []
    for name, _, _ in layer_stacks(cfg):
        x, (stacked, aux) = jax.lax.scan(body, x, params[name], unroll=cfg.scan_unroll)
        caches.update({_cache_prefix(name) + k: v for k, v in stacked.items()})
        per_stack.append(aux)
    logits = unembed(params, x, cfg, ctx)
    return logits, caches, _routed(per_stack)


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

    new_caches = {}
    for name, _, _ in layer_stacks(cfg):
        pre = _cache_prefix(name)
        mine = {leaf: caches[pre + leaf] for leaf in cache_leaves(cfg)}
        x, new = jax.lax.scan(body, x, (params[name], mine), unroll=cfg.scan_unroll)
        new_caches.update({pre + k: v for k, v in new.items()})
    logits = unembed(params, x, cfg, ctx)
    return logits, new_caches


def block_apply_paged(
    p: Dict[str, Any],
    x: jax.Array,  # (S, 1, D)
    cfg,
    ctx: ShardCtx,
    *,
    pools: Dict[str, jax.Array],  # the stack's {"k","v"} or {"latent"}, (L, P, ...)
    layer: jax.Array,  # scalar int32: this block's index in the stack
    block_tables: jax.Array,
    positions: jax.Array,
    impl: Optional[str] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array], Dict[str, jax.Array]]:
    """`block_apply`'s decode branch against the stack's paged pools
    (DESIGN.md §12).  Returns (x, pools, aux)."""
    y = rmsnorm(x, p["ln1"], cfg.norm_eps)
    h, pools = (mla_paged_decode if cfg.is_mla else attention_paged_decode)(
        p["attn"], y, cfg, ctx, pools=pools, layer=layer, block_tables=block_tables,
        positions=positions, impl=impl, interpret=interpret,
    )
    x = x + h
    h2, aux = _ffn(p, x, cfg, ctx)
    return x + h2, pools, aux


def lm_decode_paged(
    params,
    tokens: jax.Array,  # (S, 1) — one token per sequence slot
    pools,  # paged_pool_specs: {name: (L, P, KV, page_size, hd)}
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
    different depths).  Returns (logits (S, 1, V), updated pools, routing
    counts).

    Each stack's pools ride the layer scan's carry whole, beside x; the
    scan's inputs are the layer's weights and index.  A layer writes its
    new rows into the stacked pools and reads its pages through a view of
    them, so nothing slices a layer's pool out or stacks it back: with the
    pools donated, the step updates them in place."""
    x = embed_tokens(params, tokens, cfg, ctx)

    def body(carry, layer_in):
        x, stack_pools = carry
        lp, layer = layer_in
        y, stack_pools, aux = block_apply_paged(
            lp,
            x,
            cfg,
            ctx,
            pools=stack_pools,
            layer=layer,
            block_tables=block_tables,
            positions=positions,
            impl=impl,
            interpret=interpret,
        )
        return (y, stack_pools), _counts(aux)

    new_pools, per_stack = {}, []
    for name, _, n in layer_stacks(cfg):
        pre = _cache_prefix(name)
        mine = {leaf: pools[pre + leaf] for leaf in cache_leaves(cfg)}
        (x, mine), aux = jax.lax.scan(
            body, (x, mine), (params[name], jnp.arange(n, dtype=jnp.int32)),
            unroll=cfg.scan_unroll,
        )
        new_pools.update({pre + k: v for k, v in mine.items()})
        per_stack.append(aux)
    logits = unembed(params, x, cfg, ctx)
    return logits, new_pools, _routed(per_stack)


def paged_pool_specs(cfg, num_pages: int, page_size: int):
    """Abstract stacked page pools for the serving scheduler: per stack and
    cache leaf, (layers, num_pages, heads, page_size, width)."""
    return {
        _cache_prefix(name) + leaf: jax.ShapeDtypeStruct(
            (n, num_pages, heads, page_size, width), cfg.adtype
        )
        for name, _, n in layer_stacks(cfg)
        for leaf, (heads, width) in cache_leaves(cfg).items()
    }


def decode_cache_specs(cfg, batch: int, max_len: int):
    """Abstract stacked cache for serve_step lowering (ShapeDtypeStruct tree)."""
    shp = init_cache_shape(cfg, batch, max_len)
    return {
        _cache_prefix(stack) + name: jax.ShapeDtypeStruct((n,) + s, cfg.adtype)
        for stack, _, n in layer_stacks(cfg)
        for name, s in shp.items()
    }
