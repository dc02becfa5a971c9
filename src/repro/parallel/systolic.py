"""Distributed systolic matmul: the mesh array realized on the TPU ICI torus.

The paper's array is a grid of MACs with nearest-neighbour wires; a TPU pod is
a grid of chips with nearest-neighbour ICI links.  This module runs C = A @ B
with A, B, C block-sharded over a square (p x p) sub-mesh of devices, using
`shard_map` + `jax.lax.ppermute` neighbour rotations (Cannon's schedule, which
is the block-level form of the systolic array).

Hardware adaptation of the paper's step-count claim (DESIGN.md §2):

  * A physical systolic fabric pays the *skew*: hop-by-hop pre-alignment costs
    up to p-1 neighbour steps, so naive aligned Cannon takes ~2p-1 collective
    phases — the analogue of the standard array's 3n-2.
  * ICI is a *switched* torus: an arbitrary permutation is ONE
    collective-permute.  We fold the whole alignment into a single ppermute
    over the flattened 2D axis (row i shifts by i — inexpressible as a uniform
    1D shift, trivial as a 2D permutation).  Total phases: p+1 — the paper's
    2n-1-style saving, delivered by hardware routing instead of output
    scrambling.  (The output-permutation trick itself lives at the kernel
    level, where BlockSpec index_maps play the role of node wiring; block-SPMD
    cannot express per-device feeding schedules — recorded as an adaptation.)
  * Compute/comm overlap: each loop step's ppermutes depend only on the
    *current* buffers, never on the step's matmul, so XLA's latency-hiding
    scheduler overlaps the neighbour exchange with the MXU work
    (double-buffering in dataflow form).  The loop is unrolled (p is a static
    mesh dimension) to give the scheduler full freedom.

`phase_counts()` reports the collective-phase arithmetic for the benchmark
table; `systolic_matmul` is the user-facing jit entry point.

`ring_systolic_kpass` is the 1D-ring form of the same principle and the
backend of the ShardedPlan `ring_k` collective schedule (`kernels/api.py`):
with A column- and B row-sharded over K, p accumulator wavefronts circulate
the ring via `jax.lax.ppermute`, each picking up the resident partial product
as it passes — partial products flow through neighbours instead of returning
to a central psum point, the paper's 2n-1 staggered feed at device
granularity.  This module is consulted by the planner, not just by demos.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel.sharding import shard_map

__all__ = [
    "systolic_matmul",
    "systolic_matmul_shardmap",
    "ring_systolic_kpass",
    "phase_counts",
]


def _shift_perm(p: int, shift: int) -> list[Tuple[int, int]]:
    """Uniform circular shift along one axis: src -> (src - shift) mod p."""
    return [(s, (s - shift) % p) for s in range(p)]


def _alignment_perm_2d(p: int, *, align_a: bool) -> list[Tuple[int, int]]:
    """Cannon pre-alignment as ONE permutation over the flattened (p, p) axes.

    A: device (i, j) must receive A-block (i, (i + j) mod p)  => row i shifts
       left by i.  B: device (i, j) must receive B-block ((i + j) mod p, j)
       => column j shifts up by j.  Flattened index = i * p + j.
    """
    perm = []
    for i in range(p):
        for j in range(p):
            if align_a:
                src = i * p + ((i + j) % p)
            else:
                src = ((i + j) % p) * p + j
            perm.append((src, i * p + j))
    return perm


def phase_counts(p: int) -> dict:
    """Collective-phase accounting, mirroring the paper's step counts.

    naive (hop-by-hop alignment, the 'standard array' analogue):
        (p-1) A-hops + (p-1) B-hops happen concurrently -> p-1 phases,
        then p compute steps with p-1 rotation phases hidden under them.
    switched (this module, the 'mesh array' analogue):
        1 alignment permute phase + p compute steps.
    1D K-pass (the ShardedPlan 'ring_k' / 'reduce_scatter_k' schedules):
        gather-then-compute psums partials through a ring all-reduce,
        2(p-1) phases — partials return to a central point, the 3n-2 regime;
        the ring-systolic pass flows them through neighbours in p-1 phases,
        the 2n-1 regime.
    """
    return {
        "p": p,
        "naive_phases": (p - 1) + p,  # 2p-1  ~ the 3n-2 regime
        "switched_phases": 1 + p,  # p+1  ~ the 2n-1 regime
        "kpass_psum_phases": 2 * (p - 1),  # ring all-reduce of partials
        "kpass_ring_phases": p - 1,  # ring_systolic_kpass wavefronts
        "paper_standard_steps": 3 * p - 2,
        "paper_mesh_steps": 2 * p - 1,
    }


def ring_systolic_kpass(
    a_blk: jax.Array,
    b_blk: jax.Array,
    *,
    axis: str,
    matmul: Optional[Callable[[jax.Array, jax.Array], jax.Array]] = None,
    overlap: bool = False,
) -> jax.Array:
    """K-contraction over a device ring with systolic partial-product flow.

    a_blk: local (m, k/p) column shard of A; b_blk: local (k/p, n) row shard
    of B (shard t holds the K-slice resident on rank t).  Each rank computes
    its partial product ONCE; p accumulator wavefronts then circulate the
    ring (`ppermute`), each adding the resident partial as it passes.  After
    p-1 hops every wavefront has visited all p ranks, so each rank holds the
    full C = sum_t A_t @ B_t — replicated output with no psum tree.

    This is the paper's staggered feed mapped onto collectives: wavefront w
    starts at rank w (the stagger), and partials flow through neighbours
    instead of returning to a central point (2n-1 vs 3n-2; DESIGN.md §9).
    Each rank's sum accumulates in ring order starting from its own partial,
    so cross-rank float32 results can differ in the last ulp (exact for
    integer-valued data); `out_specs` replication is therefore declared, not
    verified (check_vma=False).  `matmul` computes the one local
    (m, k/p) @ (k/p, n) product (default: XLA f32 dot).

    overlap=True splits the partial into two column halves and staggers the
    chains: the first half's accumulator hop is issued while the second
    half's kernel is still running, and each later hop overlaps the other
    chain's add — the explicit double-buffer form of the dataflow the serial
    loop only *permits* the scheduler to overlap.  Per chain the hop/add
    sequence is identical to the serial loop, so XLA-dot results match
    bitwise (a half-width `matmul` kernel hook may retile, so the general
    oracle is exactness on integer-valued data).
    """
    from repro.parallel.collectives import _default_mm, _shift
    from repro.resilience import faults

    sched = "ring_k_overlap" if overlap else "ring_k"
    faults.check("collective.step", schedule=sched, axis=axis)
    mm = matmul or _default_mm
    p = jax.lax.axis_size(axis)
    n = b_blk.shape[1]
    if not overlap or p == 1 or n < 2:
        part = mm(a_blk, b_blk)
        acc = part
        # Unrolled wavefront loop: each hop's ppermute depends only on the
        # previous accumulator, and `part` is loop-invariant, so XLA overlaps
        # the neighbour exchange with the adds (same dataflow as the 2D loop
        # above).
        for _ in range(p - 1):
            acc = jax.lax.ppermute(acc, axis, _shift(p, 1)) + part
        return acc

    n2 = n // 2
    # Chain 0's kernel, then its first hop is in flight while chain 1's
    # kernel runs — the double buffer.
    part0 = mm(a_blk, b_blk[:, :n2])
    acc0 = jax.lax.ppermute(part0, axis, _shift(p, 1)) + part0
    part1 = mm(a_blk, b_blk[:, n2:])
    acc1 = jax.lax.ppermute(part1, axis, _shift(p, 1)) + part1
    for t in range(p - 2):
        faults.check("collective.step", schedule=sched, axis=axis, step=t)
        acc0 = jax.lax.ppermute(acc0, axis, _shift(p, 1)) + part0
        acc1 = jax.lax.ppermute(acc1, axis, _shift(p, 1)) + part1
    return jnp.concatenate([acc0, acc1], axis=1)


def systolic_matmul_shardmap(
    a_blk: jax.Array,
    b_blk: jax.Array,
    *,
    axis_x: str,
    axis_y: str,
    p: int,
    precision=None,
) -> jax.Array:
    """shard_map body: local (m_blk, k_blk) @ (k_blk, n_blk) Cannon loop.

    Call under `shard_map` with a_blk = A[i, j], b_blk = B[i, j] resident and
    returns the resident C[i, j].  Exposed separately so model TP layers can
    embed it inside larger shard_map blocks.
    """
    both = (axis_x, axis_y)

    # Phase 0: single-permute alignment (the switched-torus skew removal).
    a_cur = jax.lax.ppermute(a_blk, both, _alignment_perm_2d(p, align_a=True))
    b_cur = jax.lax.ppermute(b_blk, both, _alignment_perm_2d(p, align_a=False))

    acc = jnp.zeros(
        (a_blk.shape[0], b_blk.shape[1]),
        dtype=jnp.promote_types(a_blk.dtype, jnp.float32),
    )
    # Unrolled systolic loop: matmul(t) and rotate(t->t+1) both read the
    # current buffers, so the exchange overlaps the MXU work.
    for t in range(p):
        partial_prod = jnp.dot(
            a_cur, b_cur, preferred_element_type=jnp.float32, precision=precision
        )
        if t < p - 1:
            a_nxt = jax.lax.ppermute(a_cur, axis_y, _shift_perm(p, 1))
            b_nxt = jax.lax.ppermute(b_cur, axis_x, _shift_perm(p, 1))
            a_cur, b_cur = a_nxt, b_nxt
        acc = acc + partial_prod
    return acc


@functools.partial(jax.jit, static_argnames=("mesh", "axes", "out_dtype"))
def _systolic_jit(a, b, mesh, axes, out_dtype):
    axis_x, axis_y = axes
    p = mesh.shape[axis_x]

    body = functools.partial(
        systolic_matmul_shardmap, axis_x=axis_x, axis_y=axis_y, p=p
    )
    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_x, axis_y), P(axis_x, axis_y)),
        out_specs=P(axis_x, axis_y),
    )
    return mapped(a, b).astype(out_dtype)


def systolic_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    axes: Tuple[str, str] = ("data", "model"),
    out_dtype=None,
) -> jax.Array:
    """C = A @ B with all three matrices block-sharded over a square 2D mesh.

    a: (M, K), b: (K, N); M, K divisible by mesh.shape[axes[0]] and K, N by
    mesh.shape[axes[1]] — and the mesh must be square on these two axes
    (production mesh: data=model=16).
    """
    axis_x, axis_y = axes
    p, p2 = mesh.shape[axis_x], mesh.shape[axis_y]
    if p != p2:
        raise ValueError(f"systolic matmul needs a square mesh, got {p}x{p2}")
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    for dim, div, what in ((m, p, "M"), (k, p, "K"), (n, p, "N")):
        if dim % div:
            raise ValueError(f"{what}={dim} not divisible by mesh dim {div}")
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    return _systolic_jit(a, b, mesh, (axis_x, axis_y), out_dtype)
