"""Overlapped collective-matmul building blocks (TP comm/compute fusion).

Standard TP layers do `all_gather(x) @ W` or `reduce_scatter(x @ W)` as two
serial phases.  These ring variants fuse the neighbour exchanges with the
local matmuls (Wang et al., "Overlap communication with dependent
computation", and the TPU collective-matmul in XLA).  Each helper has two
selectable dataflows:

  overlap=False   the serial oracle: every ring step's `ppermute` is ordered
                  after the step's kernel call — step time = compute + comm.
  overlap=True    double-buffered: the `ppermute` for shard s+1 is issued
                  first, the kernel runs on shard s against the resident
                  buffer, then the buffers swap — the hop carries NO data
                  dependence on the in-flight kernel, so XLA's latency-hiding
                  scheduler runs them concurrently and the steady-state step
                  time is max(compute, comm).  Outputs are bitwise-equal to
                  the serial path (identical kernel calls in identical
                  accumulation order); the oracle is asserted in tests and
                  the sharded bench.

Used by the hillclimb experiments (EXPERIMENTS.md §Perf) as the beyond-paper
collective schedule, and by the ShardedPlan collective schedules in
`kernels/api.py` (`allgather_a[_overlap]`, `reduce_scatter_k[_overlap]`,
`pipeline`) — the `matmul=` hook is what lets the planner fuse its per-shard
kernel call (Pallas mesh kernel or XLA dot) inside the ring instead of a
hard-wired jnp.dot.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "ring_allgather_matmul",
    "matmul_ring_reducescatter",
    "ring_pipeline_matmul",
    "psum_if_multi",
]

# Per-step local product hook: (chunk, weights) -> f32 partial.  None selects
# the plain XLA dot; ShardedPlan passes its per-shard Plan executor here.
MatmulFn = Optional[Callable[[jax.Array, jax.Array], jax.Array]]


def _default_mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _shift(p: int, by: int = 1):
    return [(s, (s - by) % p) for s in range(p)]


def ring_allgather_matmul(
    x_blk: jax.Array,
    w: jax.Array,
    axis: str,
    *,
    matmul: MatmulFn = None,
    overlap: bool = False,
) -> jax.Array:
    """Computes all_gather(x, axis) @ w without materializing the gather.

    x_blk: local (m_blk, k) shard of a row-sharded X (full X is (p*m_blk, k));
    w: replicated (k, n).  Returns the local (p*m_blk, n) result — i.e. the
    full product, replicated ring-step by ring-step while RESULT chunks
    circulate.

    Each rank computes its own (m_blk, n) partial ONCE and the f32 result
    chunks hop the ring — not the input chunks.  (The input-rotation form
    re-ran the full-K kernel p times per rank for identical bytes moved: p x
    the FLOPs for the same answer, the `allgather_a` pathology the sharded
    bench used to show at 56 ms vs 11 ms.)  SPMD runs the same kernel on the
    same shard values whichever rank executes it, so the result-rotation
    output is bitwise-identical to the input-rotation one.

    overlap=True splits the local product into two column halves and
    double-buffers them: the first half's result chunk starts hopping while
    the second half's kernel is still on the MXU, and the two chains'
    hops/writes interleave — steady state max(compute, comm).  With the
    default dot the halves are bitwise-equal to the full-width product
    (each output element reduces the same K sequence); a `matmul` kernel
    hook receives (m_blk, k) @ (k, n/2) halves, so the planner builds its
    per-shard kernel at the half width.

    `matmul` computes each local product (default: XLA f32 dot).
    """
    from repro.resilience import faults

    sched = "allgather_a_overlap" if overlap else "allgather_a"
    faults.check("collective.step", schedule=sched, axis=axis)
    mm = matmul or _default_mm
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m_blk, n = x_blk.shape[0], w.shape[1]
    out = jnp.zeros((p * m_blk, n), dtype=jnp.promote_types(x_blk.dtype, jnp.float32))

    if not overlap or p == 1 or n < 2:
        cur = mm(x_blk, w)  # the ONE local kernel call
        for t in range(p):
            # chunk `cur` was computed by rank (idx + t) mod p
            src = (idx + t) % p
            out = jax.lax.dynamic_update_slice(out, cur, (src * m_blk, 0))
            if t < p - 1:
                cur = jax.lax.ppermute(cur, axis, _shift(p, 1))
        return out

    n2 = n // 2
    # Half 0's kernel, then its first hop is in flight while half 1's kernel
    # runs — the double buffer.  Both chains then alternate hop/write.
    cur0 = mm(x_blk, w[:, :n2])
    out = jax.lax.dynamic_update_slice(out, cur0, (idx * m_blk, 0))
    cur0 = jax.lax.ppermute(cur0, axis, _shift(p, 1))
    cur1 = mm(x_blk, w[:, n2:])
    out = jax.lax.dynamic_update_slice(out, cur1, (idx * m_blk, n2))
    cur1 = jax.lax.ppermute(cur1, axis, _shift(p, 1))
    for t in range(1, p):
        faults.check("collective.step", schedule=sched, axis=axis, step=t)
        src = (idx + t) % p
        out = jax.lax.dynamic_update_slice(out, cur0, (src * m_blk, 0))
        out = jax.lax.dynamic_update_slice(out, cur1, (src * m_blk, n2))
        if t < p - 1:
            cur0 = jax.lax.ppermute(cur0, axis, _shift(p, 1))
            cur1 = jax.lax.ppermute(cur1, axis, _shift(p, 1))
    return out


def matmul_ring_reducescatter(
    x: jax.Array,
    w_blk: jax.Array,
    axis: str,
    *,
    matmul: MatmulFn = None,
    overlap: bool = False,
) -> jax.Array:
    """Computes reduce_scatter(x @ w_col_shards) with ring accumulation.

    x: local (m, k_blk) shard of a column-sharded X; w_blk: local (k_blk, n).
    Full product rows are reduced around the ring so each rank ends with its
    (m/p, n) slice of sum_k X_k @ W_k.

    overlap=True hoists step t+1's kernel call ahead of step t's accumulator
    hop: the next partial depends only on resident operands, never on the
    in-flight accumulator, so the `ppermute` and the kernel overlap — steady
    state max(compute, comm).  The accumulator receives the same partials in
    the same order either way, so the output is bitwise-equal to the serial
    path unconditionally.  `matmul` computes each (m/p, k_blk) @ (k_blk, n)
    step (default: XLA f32 dot).
    """
    from repro.resilience import faults

    sched = "reduce_scatter_k_overlap" if overlap else "reduce_scatter_k"
    faults.check("collective.step", schedule=sched, axis=axis)
    mm = matmul or _default_mm
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m, n = x.shape[0], w_blk.shape[1]
    if m % p:
        raise ValueError(f"rows {m} not divisible by ring size {p}")
    mb = m // p

    def rows_for(step: int) -> jax.Array:
        # Each accumulation chain is destined for a fixed output rank and
        # moves one hop down the ring per step; the chain that ENDS at rank r
        # is held by rank r + (p-1-t) at step t, so rank `idx` at step t
        # contributes the slice destined for (idx + t + 1) mod p — constant
        # along its chain.
        dst = (idx + step + 1) % p
        return jax.lax.dynamic_slice(x, (dst * mb, 0), (mb, x.shape[1]))

    acc = jnp.zeros((mb, n), dtype=jnp.promote_types(x.dtype, jnp.float32))
    if not overlap:
        for t in range(p):
            acc = acc + mm(rows_for(t), w_blk)
            if t < p - 1:
                acc = jax.lax.ppermute(acc, axis, _shift(p, 1))
        return acc

    part = mm(rows_for(0), w_blk)
    for t in range(p):
        acc = acc + part
        if t < p - 1:
            faults.check("collective.step", schedule=sched, axis=axis, step=t)
            # the hop is in flight while the NEXT partial is on the MXU
            acc = jax.lax.ppermute(acc, axis, _shift(p, 1))
            part = mm(rows_for(t + 1), w_blk)
    return acc


def ring_pipeline_matmul(
    x: jax.Array,
    w_blk: jax.Array,
    axis: str,
    *,
    microbatches: int,
    matmul: MatmulFn = None,
) -> jax.Array:
    """1F1B-microbatched reduce-scatter: the planner-routed pipeline schedule.

    Same contract as `matmul_ring_reducescatter` — x: local (m, k_blk) shard
    of a column-sharded X, w_blk: local (k_blk, n), each rank ends with its
    (m/p, n) row slice of sum_k X_k @ W_k — but the per-rank row block is
    split into `microbatches/p` sub-slices whose accumulator chains flow
    through the stage ring one tick apart (1F1B: at any tick each stage holds
    ONE microbatch's kernel call and ONE in-flight hop; fill = warmup of the
    first chain, steady = one hop overlapping one kernel, drain = the last
    chain's final adds).  In-flight state is one (m/µ, n) accumulator + one
    partial instead of the whole row block — the pipeline's memory shape —
    and every hop is double-buffered against the next tick's kernel exactly
    like `matmul_ring_reducescatter(overlap=True)`.

    `microbatches` must be a multiple of the ring size p and divide m.  Rows
    accumulate in the same ring order as the reduce-scatter, so the output is
    bitwise-equal to both reducescatter dataflows.
    """
    from repro.resilience import faults

    faults.check("collective.step", schedule="pipeline", axis=axis)
    mm = matmul or _default_mm
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m, n = x.shape[0], w_blk.shape[1]
    if microbatches % p or microbatches <= 0:
        raise ValueError(
            f"microbatches {microbatches} must be a positive multiple of the"
            f" ring size {p}"
        )
    if m % microbatches:
        raise ValueError(f"rows {m} not divisible by microbatches {microbatches}")
    f = microbatches // p  # chains per rank (pipeline rounds)
    mb = m // p  # rows this rank ends with
    msb = mb // f  # rows per microbatch chain

    def part_for(rnd: int, step: int) -> jax.Array:
        # Round `rnd` runs the reduce-scatter chain over sub-slice rnd of
        # every rank's destination block, so assembled outputs keep the
        # reduce-scatter row layout (and its bitwise accumulation order).
        dst = (idx + step + 1) % p
        rows = jax.lax.dynamic_slice(
            x, (dst * mb + rnd * msb, 0), (msb, x.shape[1])
        )
        return mm(rows, w_blk)

    outs = []
    part = part_for(0, 0)  # fill: the first microbatch's kernel
    for rnd in range(f):
        acc = jnp.zeros((msb, n), dtype=jnp.promote_types(x.dtype, jnp.float32))
        for t in range(p):
            acc = acc + part
            if rnd == f - 1 and t == p - 1:
                break  # drain: the last chain's final add, nothing in flight
            faults.check(
                "collective.step", schedule="pipeline", axis=axis, step=(rnd, t)
            )
            nrnd, nt = (rnd, t + 1) if t < p - 1 else (rnd + 1, 0)
            if t < p - 1:
                # steady state: this chain's hop overlaps the next kernel
                acc = jax.lax.ppermute(acc, axis, _shift(p, 1))
            part = part_for(nrnd, nt)
        outs.append(acc)
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


def psum_if_multi(x: jax.Array, axis: str) -> jax.Array:
    """psum that is a no-op on a missing/size-1 axis (mesh-shape agnostic)."""
    try:
        size = jax.lax.axis_size(axis)
    except NameError:
        return x
    return jax.lax.psum(x, axis) if size > 1 else x
