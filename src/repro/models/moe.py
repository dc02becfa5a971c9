"""Mixture-of-Experts block: token-choice top-k routing, shared experts, EP.

Expert compute rides the grouped-GEMM planner (DESIGN.md §10): each group
dispatches its tokens by *sort/segment permutation* — every (token, choice)
pair is ranked within its expert and scattered into a group-major capacity
buffer (expert e owns rows [e*rows_per_group, e*rows_per_group + size_e)) —
and the two expert projections run as grouped plans
(`layers.grouped_gemm`), ONE ragged kernel per projection instead of the
old one-hot dispatch/combine einsum chain over a (G, s, e, cap) tensor.
Capacity scales exactly as before (cap = cf * n * k / e at scale), so
per-device memory stays bounded; small token counts (n or per-group s <=
256: decode steps, smoke tests) use cap = n, i.e. exact drop-free routing —
on those shapes the refactor is output-identical to dense dispatch.

EP mapping: the expert dim maps to 'model' when divisible (OLMoE 64 % 16 ==
0) else the expert hidden dim is TP-sharded (Qwen2-MoE: 60 experts).  The
capacity buffer's row dim is expert-major, so the 'expert_rows' rule shards
it the same way — and the planner's `expert` collective schedule
(ShardSpec.axis_g) covers explicit EP meshes.

Aux: Switch load-balance loss + router z-loss, returned for the train loop.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import PSpec, ShardCtx, gemm, grouped_gemm

__all__ = ["moe_specs", "moe_block", "swiglu_specs", "swiglu"]

_GROUP_SIZE = 1024  # tokens per dispatch group at scale (capacity scaling)
_EXACT_GROUP = 256  # groups this small route exactly (no capacity drops)
_ROW_ALIGN = 8      # capacity rounds up so row blocks tile the ragged grid


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
    # EP when experts divide the TP axis; else shard the expert hidden dim.
    ep_divisible = e % 16 == 0  # production 'model' axis size (DESIGN.md §4)
    eax = "experts" if ep_divisible else None
    fax = None if ep_divisible else "mlp"
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    specs = {
        "router": PSpec((d, e), ("embed", None), 0.02, dtype=jnp.float32),
        "wi": PSpec((e, d, 2 * f), (eax, "embed", fax), 0.02),
        "wo": PSpec((e, f, d), (eax, fax, "embed"), out_scale),
    }
    if cfg.num_shared_experts:
        fs = cfg.moe_d_ff * cfg.num_shared_experts
        specs["shared_wi"] = PSpec((d, 2 * fs), ("embed", "mlp"), 0.02)
        specs["shared_wo"] = PSpec((fs, d), ("mlp", "embed"), out_scale)
        specs["shared_gate"] = PSpec((d, 1), ("embed", None), 0.02)
    return specs


def _capacity(n: int, t: int, e: int, k: int, capacity_factor: float) -> int:
    """Per-expert row capacity, preserving the dense-dispatch scaling: tokens
    notionally split into (n // s) groups of s = min(_GROUP_SIZE, ...), each
    granting cf * s * k / e slots — except small groups, which route exactly
    (cap = n, drop-free)."""
    s = min(_GROUP_SIZE, t) if t > 1 else min(_GROUP_SIZE, n)
    while n % s:
        s //= 2
    if s <= _EXACT_GROUP:
        return n
    return (n // s) * max(1, int(capacity_factor * s * k / e))


def moe_block(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, T, D)
    cfg,
    ctx: ShardCtx,
    *,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (output, aux) with aux = {'lb_loss', 'router_z'}."""
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    n = b * t

    xf = x.reshape(n, d)
    xf = ctx.c(xf, ("batch", "embed"))
    logits = jnp.einsum(
        "nd,de->ne", xf.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)

    topv, topi = jax.lax.top_k(probs, k)  # (n, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    cap = _capacity(n, t, e, k, capacity_factor)
    rpg = -(-cap // _ROW_ALIGN) * _ROW_ALIGN  # static rows-per-group bound
    rows = e * rpg

    # Sort/segment permutation: rank each (token, choice) pair within its
    # expert (stable sort keeps token order), keep the first `cap`, and
    # scatter kept tokens into the group-major capacity buffer the grouped
    # planner consumes.  Replaces the (G, s, e, cap) one-hot dispatch einsum.
    flat_e = topi.reshape(-1)  # (n*k,) expert id per pair, token-major
    flat_t = jnp.repeat(jnp.arange(n), k)  # token id per pair
    order = jnp.argsort(flat_e)  # stable: pairs grouped by expert
    counts = jnp.bincount(flat_e, length=e)  # (e,) demand per expert
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(n * k) - starts[flat_e[order]]
    rank = jnp.zeros((n * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < cap
    gate = topv.reshape(-1) * keep.astype(topv.dtype)
    dest = jnp.where(keep, flat_e * rpg + rank, rows)  # rows => dropped

    sizes = jnp.minimum(counts, cap)
    group_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes).astype(jnp.int32)]
    )

    buf = (
        jnp.zeros((rows, d), x.dtype).at[dest].set(xf[flat_t], mode="drop")
    )
    buf = ctx.c(buf, ("expert_rows", "embed"))

    gate_up = grouped_gemm(buf, group_offsets, p["wi"], cfg)  # (rows, 2f)
    gate_h, up_h = jnp.split(gate_up, 2, axis=-1)
    h = jax.nn.silu(gate_h) * up_h
    ex_out = grouped_gemm(h, group_offsets, p["wo"], cfg)  # (rows, d)
    ex_out = ctx.c(ex_out, ("expert_rows", "embed"))

    # Combine: gather each pair's expert output back and weight by its gate
    # (dropped pairs carry gate 0, so the clipped gather never contributes).
    contrib = ex_out[jnp.clip(dest, 0, rows - 1)] * gate.astype(x.dtype)[:, None]
    y = jnp.sum(
        contrib.astype(jnp.float32).reshape(n, k, d), axis=1
    ).astype(x.dtype).reshape(b, t, d)

    if cfg.num_shared_experts:
        # shared_gate rides the plan/execute API like every other projection
        # (f32 operands preserve the fp32-router numerics of the gate).
        sg = jax.nn.sigmoid(
            gemm(
                xf.astype(jnp.float32),
                p["shared_gate"].astype(jnp.float32),
                cfg,
                ctx=ctx,
            )
        ).astype(x.dtype)
        gu = gemm(xf, p["shared_wi"], cfg, ctx=ctx)
        g_, u_ = jnp.split(gu, 2, axis=-1)
        shared = gemm(jax.nn.silu(g_) * u_, p["shared_wo"], cfg, ctx=ctx)
        y = y + (shared * sg).reshape(b, t, d)

    # Switch load-balance + router z-loss (means over all tokens).  The
    # routing `counts` from dispatch ARE the one-hot load sums (top-k indices
    # carry no gradient either way), so no (n, k, e) tensor materializes.
    load = counts.astype(jnp.float32) / n  # fraction routed per expert
    imp = jnp.mean(probs, axis=0)
    lb_loss = e * jnp.sum(load * imp) / k
    router_z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    aux = {"lb_loss": lb_loss, "router_z": router_z}
    return ctx.c(y, ("batch", "seq", "embed")), aux
