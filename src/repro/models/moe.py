"""Mixture-of-Experts block: token-choice top-k routing, shared experts, EP.

Expert compute rides the grouped-GEMM planner (DESIGN.md §10): every
(token, choice) pair routed to an expert this layer holds is ranked within
its expert by a sort/segment permutation and scattered into a group-major
buffer, and the two expert projections run as grouped plans
(`layers.grouped_gemm`), ONE ragged kernel per projection.

Routing is drop-free at every token count.  On XLA above _PACK_ABOVE
tokens (prefills, training) the held experts' pairs lie packed end to end
(held expert e owns rows [offset_e, offset_e + size_e)) in a buffer of
n * min(k, held) rows, the most pairs n tokens can send here, and a ragged
dot computes just those rows.  At fewer tokens (a decode tick), and on the
mesh kernel, the layout is the capacity one (held expert e owns rows
[e*rpg, e*rpg + size_e)): a token picks an expert at most once, so rpg is
n rounded up to the row alignment.  XLA's einsum computes all held * rpg
rows, the mesh kernel skips row blocks past each expert's size.

Experts held (guide: a chip's share of a layer's experts).  The router
keeps its published width (cfg.num_experts outputs) and experts per token;
the layer holds the first cfg.experts_held of them and computes only their
part of the result.  Pairs routed elsewhere carry no row here.  Shared
experts, which every share computes alike, are added whole.  With every
expert held (the default) the layer is the whole MoE.

Routing follows the configuration: the top-k softmax weights renormalised
(cfg.norm_topk_prob, OLMoE / Qwen2-MoE) or taken as scored (DeepSeek-V2-Lite,
whose routed_scaling_factor is 1); shared experts behind a sigmoid gate
(cfg.shared_expert_gate, Qwen2-MoE) or ungated (DeepSeek-V2).

EP mapping: the held-expert dim maps to 'model' when divisible (OLMoE 64 %
16 == 0) else the expert hidden dim is TP-sharded (Qwen2-MoE: 60 experts).
The capacity buffer's row dim is expert-major, so the 'expert_rows' rule
shards it the same way (the packed buffer's rows follow the same rule) —
and the planner's `expert` collective schedule (ShardSpec.axis_g, capacity
layout) covers explicit EP meshes.

Aux: Switch load-balance loss + router z-loss, returned for the train loop,
and `held_pairs`, the routed pairs of each token (B, T) that this layer's
held experts computed.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import PSpec, ShardCtx, gemm, grouped_gemm

__all__ = ["moe_specs", "moe_block", "swiglu_specs", "swiglu"]

_ROW_ALIGN = 8  # capacity rows per expert round up so row blocks tile the grid
# Token counts above which XLA packs the held pairs.  Below, the capacity
# einsum is the faster (one DeepSeek-V2-Lite expert projection, 8 held, on
# a v5e: 0.65 ms against the ragged dot's 0.84 at 32 tokens; 2.22 against
# 1.51 at 3,072) and its held * n rows are few.
_PACK_ABOVE = 256


def swiglu_specs(cfg, d_ff: int) -> Dict[str, PSpec]:
    d = cfg.d_model
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    return {
        "wi": PSpec((d, 2 * d_ff), ("embed", "mlp"), 0.02),  # fused gate+up
        "wo": PSpec((d_ff, d), ("mlp", "embed"), out_scale),
    }


def swiglu(p: Dict[str, jax.Array], x: jax.Array, cfg, ctx: ShardCtx) -> jax.Array:
    gate_up = gemm(x, p["wi"], cfg, ctx=ctx)
    gate_up = ctx.c(gate_up, ("batch", "seq", "mlp"))
    gate, up = jnp.split(gate_up, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    y = gemm(h, p["wo"], cfg, ctx=ctx)
    return ctx.c(y, ("batch", "seq", "embed"))


def moe_specs(cfg) -> Dict[str, PSpec]:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    held = cfg.experts_held
    # EP when the held experts divide the TP axis; else shard the expert
    # hidden dim.
    ep_divisible = held % 16 == 0  # production 'model' axis size (DESIGN.md §4)
    eax = "experts" if ep_divisible else None
    fax = None if ep_divisible else "mlp"
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    specs = {
        "router": PSpec((d, e), ("embed", None), 0.02, dtype=jnp.float32),
        "wi": PSpec((held, d, 2 * f), (eax, "embed", fax), 0.02),
        "wo": PSpec((held, f, d), (eax, fax, "embed"), out_scale),
    }
    if cfg.num_shared_experts:
        fs = cfg.moe_d_ff * cfg.num_shared_experts
        specs["shared_wi"] = PSpec((d, 2 * fs), ("embed", "mlp"), 0.02)
        specs["shared_wo"] = PSpec((fs, d), ("mlp", "embed"), out_scale)
        if cfg.shared_expert_gate:
            specs["shared_gate"] = PSpec((d, 1), ("embed", None), 0.02)
    return specs


def moe_block(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, T, D)
    cfg,
    ctx: ShardCtx,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (output, aux) with aux = {'lb_loss', 'router_z', 'held_pairs'}."""
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    held = cfg.experts_held
    n = b * t

    xf = x.reshape(n, d)
    xf = ctx.c(xf, ("batch", "embed"))
    logits = jnp.einsum(
        "nd,de->ne", xf.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)

    topv, topi = jax.lax.top_k(probs, k)  # (n, k)
    if cfg.norm_topk_prob:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    # Sort/segment permutation: rank each (token, choice) pair within its
    # expert (stable sort keeps token order) and scatter the pairs of held
    # experts into the group-major buffer the grouped planner consumes.
    # Pairs routed to experts held elsewhere share one bucket past the held
    # ones, sort last, and are never written.
    flat_e = topi.reshape(-1)  # (n*k,) token-major
    mine = flat_e < held
    flat_e = jnp.minimum(flat_e, held)
    flat_t = jnp.repeat(jnp.arange(n), k)  # token id per pair
    order = jnp.argsort(flat_e)  # stable: pairs grouped by expert
    counts = jnp.bincount(flat_e, length=held + 1)  # (held + 1,) demand
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(n * k) - starts[flat_e[order]]
    rank = jnp.zeros((n * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    gate = topv.reshape(-1) * mine.astype(topv.dtype)

    # XLA packs the held pairs of a long input end to end; otherwise the
    # capacity layout gives each held expert rpg rows.
    packed = n > _PACK_ABOVE and not getattr(cfg, "use_mesh_kernel", False)
    if packed:
        rows = n * min(k, held)
        first_row = starts
    else:
        rpg = -(-n // _ROW_ALIGN) * _ROW_ALIGN  # drop-free static rows-per-group
        rows = held * rpg
        first_row = jnp.arange(held + 1) * rpg
    dest = jnp.where(mine, first_row[flat_e] + rank, rows)  # rows => not held here

    sizes = counts[:held]
    group_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes).astype(jnp.int32)]
    )

    buf = (
        jnp.zeros((rows, d), x.dtype).at[dest].set(xf[flat_t], mode="drop")
    )
    buf = ctx.c(buf, ("expert_rows", "embed"))

    gate_up = grouped_gemm(buf, group_offsets, p["wi"], cfg, packed=packed)  # (rows, 2f)
    gate_h, up_h = jnp.split(gate_up, 2, axis=-1)
    h = jax.nn.silu(gate_h) * up_h
    ex_out = grouped_gemm(h, group_offsets, p["wo"], cfg, packed=packed)  # (rows, d)
    ex_out = ctx.c(ex_out, ("expert_rows", "embed"))

    # Combine: gather each pair's expert output back and weight by its gate
    # (pairs held elsewhere carry gate 0, so the clipped gather never
    # contributes).
    contrib = ex_out[jnp.clip(dest, 0, rows - 1)] * gate.astype(x.dtype)[:, None]
    y = jnp.sum(
        contrib.astype(jnp.float32).reshape(n, k, d), axis=1
    ).astype(x.dtype).reshape(b, t, d)

    if cfg.num_shared_experts:
        gu = gemm(xf, p["shared_wi"], cfg, ctx=ctx)
        g_, u_ = jnp.split(gu, 2, axis=-1)
        shared = gemm(jax.nn.silu(g_) * u_, p["shared_wo"], cfg, ctx=ctx)
        if cfg.shared_expert_gate:
            # shared_gate rides the plan/execute API like every other
            # projection (f32 operands preserve the fp32-router numerics).
            sg = jax.nn.sigmoid(
                gemm(
                    xf.astype(jnp.float32),
                    p["shared_gate"].astype(jnp.float32),
                    cfg,
                    ctx=ctx,
                )
            ).astype(x.dtype)
            shared = shared * sg
        y = y + shared.reshape(b, t, d)

    # Switch load-balance + router z-loss (means over all tokens), from the
    # routing over every expert (top-k indices carry no gradient).
    load = jnp.bincount(topi.reshape(-1), length=e).astype(jnp.float32) / n
    imp = jnp.mean(probs, axis=0)
    lb_loss = e * jnp.sum(load * imp) / k
    router_z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    held_pairs = jnp.sum(mine.reshape(b, t, k), axis=-1, dtype=jnp.int32)
    aux = {"lb_loss": lb_loss, "router_z": router_z, "held_pairs": held_pairs}
    return ctx.c(y, ("batch", "seq", "embed")), aux
