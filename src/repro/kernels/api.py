"""Plan/execute operator API: typed GEMM specs + capability-based backends.

This module is the architectural seam between "what GEMM do I need?" and
"which kernel runs it" (DESIGN.md §8).  It separates *planning* — resolve a
backend against declared capabilities, fix block shapes through the autotuner,
precompute the σ/stagger tables host-side — from *execution* — a cached,
reusable, jitted callable that serving and training graphs invoke per request:

    spec = GemmSpec.from_operands(a, b, epilogue=Epilogue(bias=True,
                                                          activation="gelu"))
    p = plan(spec)                  # validate + autotune + build, ONCE
    y = p(a, b, bias=bias)          # reuse forever; p is cached per spec

`GemmSpec.structure` replaces the old `pallas_mesh_scrambled` pseudo-backend:
the *regime* the paper's array supports (general 2n-1-step product, the
3n/2+1 symmetric readout, the scrambling mode) is a property of the problem,
not of the kernel that happens to run it.  Backends declare which structures
(and which other capabilities: fully-batched grids, fused epilogues,
off-TPU interpret execution, autotuned blocks, device-mesh sharding) they
support via `register_backend`, so ref/XLA/Pallas implementations — and test
doubles — register uniformly; `plan` picks a capable backend instead of
string-matching.

The API is sharding-aware end to end (DESIGN.md §9): attach a frozen
`ShardSpec` (device-mesh axes + logical partition of M/K/N/batch, derivable
from `parallel.sharding.ShardingRules`) and `plan(spec, mesh=mesh)` returns a
`ShardedPlan` — the same per-shard Plan lowered through `shard_map` with a
collective schedule (`replicated` | `allgather_a` | `reduce_scatter_k` |
`ring_k`) fused around the kernel call via `parallel/collectives.py` and
`parallel/systolic.py`.  An unsharded spec is just the size-1-axes case of
the same planner path — there is one planner, not two.

The planner also covers **grouped (ragged-batch) GEMMs** (DESIGN.md §10):
attach a `GroupSpec` (num_groups, static rows-per-group bound; K/N shared)
and `plan(spec)` returns a `GroupedPlan` taking `(tokens, group_offsets,
weights_stacked)` — the MoE expert regime, where every layer multiplies many
small ragged row batches against per-expert weight slabs.  Backends declare
the `grouped` capability with a dedicated impl (the Pallas ragged mesh
kernel in `kernels/grouped.py`; segment-masked einsum on xla/ref), and an
`expert` collective schedule shards the group dim over a device mesh (EP).
A packed GroupSpec lays the groups end to end with no padding between them
(a ragged dot on xla; `grouped_packed` capability).

The planner degrades instead of dying (DESIGN.md §11).  `plan()` resolves a
capability-ordered **fallback chain** (`FALLBACK_ORDER`: pallas_mesh → xla →
ref) behind the chosen backend: a failed plan build or a failed execution
falls to the next capable backend instead of raising, recording a
`DegradationEvent` in the plan's own `health` record (`describe()["health"]`)
and in the process-wide `resilience.ledger`.  Sharded plans degrade along the
schedule axis instead — a collective failure falls back to replicated
(unsharded) execution of the same spec.  Spec-level validation errors
(`PlanValidationError`) never trigger fallback: a spec every backend must
reject is a caller bug, not a backend failure.  The opt-in `guard_nonfinite`
plan option samples outputs for NaN/Inf post-epilogue (fused paths stay
fused) with a `raise | fallback | zero_and_record` policy.

`repro.kernels.ops.matmul` remains as a thin compat shim over this module.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, FrozenSet, List, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import autotune as _autotune
from repro.kernels import ref
from repro.kernels.grouped import grouped_mesh_matmul_pallas
from repro.obs import trace as _obs
from repro.resilience import faults as _faults
from repro.resilience import ledger as _rledger
from repro.resilience.policy import (
    NonFiniteError,
    nonfinite_count,
    normalize_policy,
    scrub_nonfinite,
)
from repro.kernels.mesh_matmul import (
    ACTIVATIONS,
    mesh_matmul_pallas,
    mesh_matmul_pallas_batched,
    sigma_block_table,
)

__all__ = [
    "FALLBACK_ORDER",
    "SCHEDULES",
    "STRUCTURES",
    "BackendCapabilities",
    "CapabilityError",
    "Epilogue",
    "PlanValidationError",
    "GemmSpec",
    "GroupSpec",
    "GroupedPlan",
    "Plan",
    "ShardSpec",
    "ShardedGroupedPlan",
    "ShardedPlan",
    "AsyncResult",
    "apply_epilogue",
    "backend_names",
    "clear_plan_cache",
    "default_backend",
    "execute_async",
    "get_capabilities",
    "get_default",
    "plan",
    "plan_cache_info",
    "register_backend",
    "set_default",
    "unregister_backend",
]

STRUCTURES = ("general", "symmetric", "scrambled")

# Collective schedules a ShardedPlan can lower to (DESIGN.md §9, §15):
#   replicated        no collective — M/N/batch partitions are purely local
#                     (each device owns its C tile; all-None axes = the fully
#                     replicated degenerate case unsharded specs route through)
#   allgather_a       A row-sharded on M; each device computes its result
#                     chunk ONCE and the f32 chunks circulate the ring
#                     (collectives.ring_allgather_matmul); output replicated
#   reduce_scatter_k  A/B sharded on K; partial products ring-reduced so each
#                     device ends with its M/p row slice
#                     (collectives.matmul_ring_reducescatter)
#   ring_k            A/B sharded on K; the paper's 2n-1 staggered feed as p
#                     accumulator wavefronts ppermuting around the ring
#                     (systolic.ring_systolic_kpass); output replicated
#   *_overlap         double-buffered twin of the base schedule: every ring
#                     hop is issued while a kernel call runs, so steady-state
#                     step time is max(compute, comm) instead of the sum —
#                     bitwise-equal outputs to the serial twin on the XLA
#                     backend (the serial path is the oracle).  The column-
#                     half variants (allgather_a/ring_k) build the per-shard
#                     kernel at n/2, so they need even N and axis size >= 2.
#   pipeline          A/B sharded on K like reduce_scatter_k, but the per-rank
#                     row block is 1F1B-microbatched: accumulator chains flow
#                     through the stage ring one tick apart with every hop
#                     double-buffered (collectives.ring_pipeline_matmul);
#                     output row-sharded, bitwise-equal to reduce_scatter_k
#   expert            grouped specs only: the group (expert) dim sharded over
#                     axis_g — tokens/weights/sizes reshard at the shard_map
#                     boundary (the EP all-to-all), each device runs the
#                     grouped kernel over its local groups, output rows stay
#                     group-sharded
SCHEDULES = (
    "replicated",
    "allgather_a",
    "allgather_a_overlap",
    "reduce_scatter_k",
    "reduce_scatter_k_overlap",
    "ring_k",
    "ring_k_overlap",
    "pipeline",
    "expert",
)


def _is_overlap_schedule(sched: str) -> bool:
    """True for schedules whose ring hops are double-buffered against kernel
    calls — the cost model prices their collective under max(compute, comm)
    instead of adding it (costmodel.model.predict)."""
    return sched.endswith("_overlap") or sched == "pipeline"


def _pipeline_microbatches(eff_m: int, pk: int) -> int:
    """Microbatch count for the `pipeline` schedule: two chains per stage
    when the per-stage row block splits evenly (so the steady state always
    has one hop in flight behind one kernel), else one."""
    mb = eff_m // pk
    f = 2 if mb >= 2 and mb % 2 == 0 else 1
    return f * pk


# ---------------------------------------------------------------------------
# Typed specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """The fused-epilogue contract (DESIGN.md §3): y = act(AB + bias) + residual.

    Declares *which* epilogue operands exist — the arrays themselves are
    execution-time inputs, so one plan serves every bias/residual value.
    """

    bias: bool = False
    activation: Optional[str] = None
    residual: bool = False

    def __post_init__(self):
        if self.activation not in ACTIVATIONS:
            raise ValueError(
                f"activation must be one of {sorted(k for k in ACTIVATIONS if k)},"
                f" got {self.activation!r}"
            )
        if self.activation == "none":
            object.__setattr__(self, "activation", None)

    @property
    def is_identity(self) -> bool:
        return not (self.bias or self.residual) and self.activation is None


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    """Ragged-batch structure of one grouped GEMM (DESIGN.md §10).

    `num_groups` weight slabs share K/N; tokens arrive concatenated
    group-major in a capacity layout with a STATIC `rows_per_group` bound —
    group g owns rows [g*rows_per_group, g*rows_per_group + size_g), where
    the runtime sizes ride in the `group_offsets` execution operand
    (cumulative counts, (num_groups+1,)).  Rows at or beyond a group's size
    are zero on output.  Hashable and frozen: part of the plan-cache key, so
    blocks are autotuned once per logical group shape.

    With `packed`, the groups instead lie one after another from row 0 —
    group g owns rows [group_offsets[g], group_offsets[g+1]) — in a buffer
    of `rows_per_group` rows, which then bounds the groups' total, not each
    group's: no row is padding between groups, and rows past the last
    group are zero on output.  Only backends with the `grouped_packed`
    capability run it.
    """

    num_groups: int
    rows_per_group: int
    packed: bool = False

    def __post_init__(self):
        object.__setattr__(self, "num_groups", int(self.num_groups))
        object.__setattr__(self, "rows_per_group", int(self.rows_per_group))
        object.__setattr__(self, "packed", bool(self.packed))
        if self.num_groups <= 0 or self.rows_per_group <= 0:
            raise ValueError(
                f"GroupSpec dims must be positive, got num_groups="
                f"{self.num_groups}, rows_per_group={self.rows_per_group}"
            )

    @property
    def rows(self) -> int:
        """Total (static) token rows of the layout."""
        return self.rows_per_group if self.packed else self.num_groups * self.rows_per_group


# Physical mesh axes naming a partition: a single axis name, or (for the
# no-collective dims of the replicated schedule) a tuple of axis names.
Axes = Union[str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Device-mesh partition of one GEMM (DESIGN.md §9).

    `mesh_axes` pins the (name, size) layout of the device mesh the spec was
    built for — the spec stays hashable (it is part of the plan-cache key)
    and `plan(spec, mesh=...)` verifies the live mesh matches.  The four
    axis fields name which mesh axis partitions each LOGICAL dim of
    (batch..., M, K) @ (K, N); None leaves that dim whole.  `schedule` pins a
    collective schedule from SCHEDULES, or "auto" to let the planner choose
    (K sharded -> reduce_scatter_k when M divides the axis, else ring_k;
    otherwise the no-collective replicated schedule).

    `axis_k` must be a single axis name — the K collectives are 1D rings.
    `axis_m`/`axis_n`/`axis_batch` may be axis tuples under the replicated
    schedule, where they only slice the local tile.  `axis_g` (single axis)
    partitions the group dim of a GROUPED spec — the `expert` schedule, EP.
    A ShardSpec whose axes are all None/size-1 (`ShardSpec.unsharded`)
    routes through the identical ShardedPlan path and reproduces the
    unsharded Plan bit for bit.
    """

    mesh_axes: Tuple[Tuple[str, int], ...]
    axis_m: Optional[Axes] = None
    axis_k: Optional[str] = None
    axis_n: Optional[Axes] = None
    axis_batch: Optional[Axes] = None
    axis_g: Optional[str] = None
    schedule: str = "auto"

    def __post_init__(self):
        object.__setattr__(
            self,
            "mesh_axes",
            tuple((str(n), int(s)) for n, s in self.mesh_axes),
        )
        names = [n for n, _ in self.mesh_axes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate mesh axis names in {self.mesh_axes}")
        if self.schedule not in ("auto",) + SCHEDULES:
            raise ValueError(
                f"schedule must be 'auto' or one of {SCHEDULES},"
                f" got {self.schedule!r}"
            )
        seen: List[str] = []
        for field in ("axis_m", "axis_k", "axis_n", "axis_batch", "axis_g"):
            v = getattr(self, field)
            if isinstance(v, list):
                v = tuple(v)
            if isinstance(v, tuple) and len(v) == 1:
                v = v[0]
            if field == "axis_k" and v is not None and not isinstance(v, str):
                raise ValueError(
                    f"axis_k must be a single mesh axis name (the K"
                    f" collectives are 1D rings), got {self.axis_k!r}"
                )
            if field == "axis_g" and v is not None and not isinstance(v, str):
                raise ValueError(
                    f"axis_g must be a single mesh axis name (the group dim"
                    f" shards over one EP axis), got {self.axis_g!r}"
                )
            object.__setattr__(self, field, v)
            for nm in (v,) if isinstance(v, str) else (v or ()):
                if nm not in names:
                    raise ValueError(
                        f"{field}={nm!r} is not a mesh axis; mesh has {names}"
                    )
                if nm in seen:
                    raise ValueError(
                        f"mesh axis {nm!r} partitions more than one GEMM dim"
                    )
                seen.append(nm)

    # -- constructors --------------------------------------------------------

    @classmethod
    def from_mesh(
        cls,
        mesh: Mesh,
        *,
        m: Optional[Axes] = None,
        k: Optional[str] = None,
        n: Optional[Axes] = None,
        batch: Optional[Axes] = None,
        g: Optional[str] = None,
        schedule: str = "auto",
    ) -> "ShardSpec":
        """Partition over a live device mesh by PHYSICAL axis names."""
        return cls(
            mesh_axes=tuple((str(a), int(s)) for a, s in mesh.shape.items()),
            axis_m=m,
            axis_k=k,
            axis_n=n,
            axis_batch=batch,
            axis_g=g,
            schedule=schedule,
        )

    @classmethod
    def from_rules(
        cls,
        mesh: Mesh,
        rules,
        *,
        m: Optional[str] = None,
        k: Optional[str] = None,
        n: Optional[str] = None,
        batch: Optional[str] = None,
        g: Optional[str] = None,
        schedule: str = "auto",
    ) -> "ShardSpec":
        """Partition by LOGICAL axis names (e.g. m='batch', n='mlp',
        g='experts') mapped through a `parallel.sharding.ShardingRules`
        table; rule axes the mesh doesn't carry are dropped, exactly as in
        `named_sharding`."""
        from repro.parallel.sharding import _axes_on_mesh

        def phys(logical):
            return None if logical is None else _axes_on_mesh(mesh, rules.get(logical))

        return cls.from_mesh(
            mesh,
            m=phys(m),
            k=phys(k),
            n=phys(n),
            batch=phys(batch),
            g=phys(g),
            schedule=schedule,
        )

    @classmethod
    def unsharded(cls, mesh: Mesh) -> "ShardSpec":
        """All dims whole: the degenerate ShardSpec that routes an unsharded
        product through the same ShardedPlan planner path."""
        return cls.from_mesh(mesh)

    # -- derived -------------------------------------------------------------

    def axis_size(self, axes: Optional[Axes]) -> int:
        """Product of mesh-axis sizes a partition maps to (1 for None)."""
        sizes = dict(self.mesh_axes)
        out = 1
        for nm in (axes,) if isinstance(axes, str) else (axes or ()):
            out *= sizes[nm]
        return out

    @property
    def is_trivial(self) -> bool:
        """True when every partition has size 1 (numerically unsharded)."""
        return all(
            self.axis_size(a) == 1
            for a in (
                self.axis_m,
                self.axis_k,
                self.axis_n,
                self.axis_batch,
                self.axis_g,
            )
        )


def _dtype_name(dt) -> str:
    return jnp.dtype(dt).name


@dataclasses.dataclass(frozen=True)
class GemmSpec:
    """Logical description of one GEMM: (batch..., M, K) @ (K, N) — or, when
    `batched_b`, (batch..., M, K) @ (batch..., K, N).

    `structure` names the paper regime of the product:
      general    arbitrary C = AB (the 2n-1-step mode)
      symmetric  caller asserts C = Cᵀ (square; the early-readout mode — keys
                 a separate autotune-cache partition, sym1)
      scrambled  output lands in the paper's σ block arrangement (replaces the
                 old `pallas_mesh_scrambled` pseudo-backend)

    `blocks` is an optional (bm, bn, bk) override; entries left None are
    resolved by the autotuner at plan time.  `shard` attaches a device-mesh
    partition (ShardSpec): `plan(spec, mesh=mesh)` then returns a ShardedPlan
    lowering the per-shard product through shard_map with a collective
    schedule.  `group` attaches a GroupSpec, turning the spec into a grouped
    (ragged-batch) GEMM: (num_groups * rows_per_group, K) tokens against
    (num_groups, K, N) stacked weights — `m` is the total row bound and
    `plan` returns a GroupedPlan.  Hashable and frozen — specs are the
    plan-cache key.
    """

    m: int
    k: int
    n: int
    batch: Tuple[int, ...] = ()
    batched_b: bool = False
    dtype_a: str = "float32"
    dtype_b: str = "float32"
    out_dtype: Optional[str] = None
    structure: str = "general"
    epilogue: Epilogue = Epilogue()
    blocks: Optional[Tuple[Optional[int], Optional[int], Optional[int]]] = None
    stagger: bool = True
    shard: Optional[ShardSpec] = None
    group: Optional[GroupSpec] = None
    # Caller hint: how many products this plan will run back-to-back with the
    # SAME B (decode loops, repeated layers).  Per the cross-wired mesh-array
    # analysis (Kak, arXiv:1411.3273) repeated products amortize the fill
    # latency and the resident-operand traffic — the cost model scales its
    # per-call estimate accordingly.  Numerics are unaffected.
    repeats: int = 1

    def __post_init__(self):
        if self.structure not in STRUCTURES:
            raise ValueError(
                f"structure must be one of {STRUCTURES}, got {self.structure!r}"
            )
        if min(self.m, self.k, self.n) <= 0:
            raise ValueError(f"dims must be positive, got {(self.m, self.k, self.n)}")
        if self.batched_b and not self.batch:
            raise ValueError("batched_b requires leading batch dims")
        if self.shard is not None and not isinstance(self.shard, ShardSpec):
            raise TypeError(
                f"shard must be a ShardSpec, got {type(self.shard).__name__}"
            )
        if self.group is not None:
            if not isinstance(self.group, GroupSpec):
                raise TypeError(
                    f"group must be a GroupSpec, got {type(self.group).__name__}"
                )
            if self.structure != "general":
                raise ValueError(
                    f"grouped specs are structure='general' only (the σ and"
                    f" symmetric regimes are defined on one product), got"
                    f" {self.structure!r}"
                )
            if self.batch or self.batched_b:
                raise ValueError(
                    "grouped specs carry their batching in the GroupSpec;"
                    " leading batch dims are not supported"
                )
            if self.m != self.group.rows:
                raise ValueError(
                    f"grouped spec m={self.m} must equal"
                    f" num_groups*rows_per_group={self.group.rows}"
                    f" (use GemmSpec.for_groups)"
                )
        object.__setattr__(self, "repeats", int(self.repeats))
        if self.repeats < 1:
            raise ValueError(f"repeats must be >= 1, got {self.repeats}")
        object.__setattr__(self, "batch", tuple(int(d) for d in self.batch))
        object.__setattr__(self, "dtype_a", _dtype_name(self.dtype_a))
        object.__setattr__(self, "dtype_b", _dtype_name(self.dtype_b))
        if self.out_dtype is not None:
            object.__setattr__(self, "out_dtype", _dtype_name(self.out_dtype))
        if self.blocks is not None:
            if len(self.blocks) != 3:
                raise ValueError(
                    f"blocks must be a (bm, bn, bk) triple, got {self.blocks!r}"
                )
            bks = tuple(None if x in (None, 0) else int(x) for x in self.blocks)
            object.__setattr__(self, "blocks", None if bks == (None,) * 3 else bks)

    @classmethod
    def from_operands(
        cls,
        a: jax.Array,
        b: jax.Array,
        *,
        structure: str = "general",
        epilogue: Optional[Epilogue] = None,
        out_dtype=None,
        blocks=None,
        stagger: bool = True,
        shard: Optional[ShardSpec] = None,
        repeats: int = 1,
    ) -> "GemmSpec":
        """Spec for concrete (or abstract) operands; leading dims of `a` become
        the batch, shared with `b` when `b` carries the same leading dims."""
        if a.ndim < 2 or b.ndim < 2:
            raise ValueError(f"operands must be >= 2D, got {a.shape} @ {b.shape}")
        if a.shape[-1] != b.shape[-2]:
            raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
        batched_b = b.ndim > 2
        if batched_b and a.shape[:-2] != b.shape[:-2]:
            raise ValueError(f"batch dims mismatch: {a.shape} vs {b.shape}")
        return cls(
            m=a.shape[-2],
            k=a.shape[-1],
            n=b.shape[-1],
            batch=a.shape[:-2],
            batched_b=batched_b,
            dtype_a=a.dtype,
            dtype_b=b.dtype,
            out_dtype=out_dtype,
            structure=structure,
            epilogue=epilogue or Epilogue(),
            blocks=blocks,
            stagger=stagger,
            shard=shard,
            repeats=repeats,
        )

    @classmethod
    def for_groups(
        cls,
        group: GroupSpec,
        k: int,
        n: int,
        *,
        dtype_a="float32",
        dtype_b="float32",
        out_dtype=None,
        epilogue: Optional[Epilogue] = None,
        blocks=None,
        stagger: bool = True,
        shard: Optional[ShardSpec] = None,
        repeats: int = 1,
    ) -> "GemmSpec":
        """Spec for a grouped GEMM: (group.rows, k) tokens in the capacity
        layout against (group.num_groups, k, n) stacked weights."""
        return cls(
            m=group.rows,
            k=k,
            n=n,
            dtype_a=dtype_a,
            dtype_b=dtype_b,
            out_dtype=out_dtype,
            epilogue=epilogue or Epilogue(),
            blocks=blocks,
            stagger=stagger,
            shard=shard,
            group=group,
            repeats=repeats,
        )

    # -- derived quantities used at plan time --------------------------------

    @property
    def eff_m(self) -> int:
        """M after folding leading batch dims (b 2D folds batch into M)."""
        if self.batch and not self.batched_b:
            return math.prod(self.batch) * self.m
        return self.m

    @property
    def acc_dtype(self) -> str:
        return _dtype_name(jnp.result_type(self.dtype_a, self.dtype_b))

    def resolved_out_dtype(self) -> str:
        return self.out_dtype or self.acc_dtype

    def flops(self) -> int:
        return 2 * math.prod(self.batch or (1,)) * self.m * self.k * self.n


# ---------------------------------------------------------------------------
# Capability-based backend registry
# ---------------------------------------------------------------------------


class CapabilityError(ValueError):
    """A spec asks for something the (chosen or only) backend cannot do."""


class PlanValidationError(ValueError):
    """The SPEC itself is malformed (misaligned scramble blocks, non-square
    symmetric product, inconsistent ShardSpec, ...).  Subclasses ValueError
    for caller compatibility, but is excluded from the fallback chain: every
    backend must reject the same spec, so degrading would only mask the bug."""


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What a registered backend declares it can execute.

    structures        subset of STRUCTURES the impl can produce
    batching          fully-batched (B, M, K) @ (B, K, N) operands
    epilogue          the DESIGN.md §3 epilogue contract (fused or not)
    epilogue_fusion   the epilogue runs inside the kernel (provenance only)
    interpret         executes off-TPU (natively or via Pallas interpret mode)
    autotune          consumes autotuned (bm, bn, bk) block shapes
    sharding          per-shard kernel composes under shard_map, so specs
                      with a ShardSpec can lower through a ShardedPlan
    grouped           executes ragged-batch specs carrying a GroupSpec
                      (requires a `grouped_impl` at registration)
    grouped_packed    executes them in the packed layout (GroupSpec.packed)
    """

    structures: FrozenSet[str] = frozenset({"general"})
    batching: bool = False
    epilogue: bool = True
    epilogue_fusion: bool = False
    interpret: bool = True
    autotune: bool = False
    sharding: bool = False
    grouped: bool = False
    grouped_packed: bool = False

    def __post_init__(self):
        object.__setattr__(self, "structures", frozenset(self.structures))
        unknown = self.structures - set(STRUCTURES)
        if unknown:
            raise ValueError(
                f"unknown structures {sorted(unknown)}; known: {STRUCTURES}"
            )


_CAP_FIELDS = {f.name for f in dataclasses.fields(BackendCapabilities)}

# impl(plan, a, b, bias, residual) -> array
BackendImpl = Callable[["Plan", jax.Array, jax.Array, Any, Any], jax.Array]
# grouped_impl(plan, tokens, group_offsets, weights, bias, residual) -> array
GroupedImpl = Callable[
    ["Plan", jax.Array, jax.Array, jax.Array, Any, Any], jax.Array
]


@dataclasses.dataclass(frozen=True)
class _Backend:
    name: str
    impl: BackendImpl
    caps: BackendCapabilities
    grouped_impl: Optional[GroupedImpl] = None


_REGISTRY: Dict[str, _Backend] = {}

# Plan cache: one entry per (spec, backend, platform) ever planned (defined
# here because registration evicts from it).
_PLAN_CACHE: Dict[tuple, "Plan"] = {}
_PLAN_STATS = {"hits": 0, "misses": 0}


def _evict_plans(name: str) -> None:
    """Drop cached plans for one backend: a (re|un)registered impl must not
    keep serving stale executables, and plans for OTHER backends stay valid
    (and cached) — no global invalidation, no stranded entries."""
    for key in [k for k in _PLAN_CACHE if k[1] == name]:
        del _PLAN_CACHE[key]


def register_backend(
    name: str,
    impl: BackendImpl,
    capabilities: Union[BackendCapabilities, Mapping[str, Any]],
    *,
    grouped_impl: Optional[GroupedImpl] = None,
    override: bool = False,
) -> None:
    """Register a GEMM backend under `name` with declared capabilities.

    `capabilities` is a BackendCapabilities or a mapping with only its field
    names — unknown capability keys are rejected so typos never silently grant
    an ability.  Declaring the `grouped` capability requires a matching
    `grouped_impl` (the ragged-batch entry point has a different operand
    signature).  Duplicate names are rejected unless `override=True`.
    """
    if not isinstance(capabilities, BackendCapabilities):
        unknown = set(capabilities) - _CAP_FIELDS
        if unknown:
            raise ValueError(
                f"unknown capabilities {sorted(unknown)};"
                f" known: {sorted(_CAP_FIELDS)}"
            )
        capabilities = BackendCapabilities(**capabilities)
    if capabilities.grouped and grouped_impl is None:
        raise ValueError(
            f"backend {name!r} declares the 'grouped' capability but"
            " provides no grouped_impl"
        )
    if name in _REGISTRY and not override:
        raise ValueError(
            f"backend {name!r} already registered (pass override=True to replace)"
        )
    _REGISTRY[name] = _Backend(name, impl, capabilities, grouped_impl)
    _evict_plans(name)


def unregister_backend(name: str) -> None:
    if _REGISTRY.pop(name, None) is not None:
        _evict_plans(name)
    if _DEFAULT_BACKEND[0] == name:
        _DEFAULT_BACKEND[0] = None
        _DEFAULT_EPOCH[0] += 1


def backend_names() -> List[str]:
    return list(_REGISTRY)


def get_capabilities(name: str) -> BackendCapabilities:
    return _require_backend(name).caps


def _require_backend(name: str) -> _Backend:
    be = _REGISTRY.get(name)
    if be is None:
        raise ValueError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        )
    return be


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _check_capabilities(spec: GemmSpec, be: _Backend) -> Optional[str]:
    """None if `be` can run `spec` here; else a human-readable reason."""
    caps = be.caps
    if spec.structure not in caps.structures:
        return (
            f"backend {be.name!r} does not support structure"
            f" {spec.structure!r} (supports {sorted(caps.structures)})"
        )
    if spec.batched_b and not caps.batching:
        return f"backend {be.name!r} does not support fully-batched operands"
    if spec.group is not None and not caps.grouped:
        return (
            f"backend {be.name!r} does not support grouped (ragged-batch)"
            f" specs (no 'grouped' capability)"
        )
    if spec.group is not None and spec.group.packed and not caps.grouped_packed:
        return (
            f"backend {be.name!r} does not support the packed grouped layout"
            f" (no 'grouped_packed' capability)"
        )
    if spec.shard is not None and not caps.sharding:
        return (
            f"backend {be.name!r} does not support device-mesh sharded specs"
            f" (no 'sharding' capability)"
        )
    if not spec.epilogue.is_identity and not caps.epilogue:
        return f"backend {be.name!r} does not support the fused-epilogue contract"
    if not _on_tpu() and not caps.interpret:
        return (
            f"backend {be.name!r} requires TPU and has no interpret mode"
            f" (running on {jax.default_backend()!r})"
        )
    return None


# -- default backend (process default + scoped override) ---------------------

_DEFAULT_BACKEND: List[Optional[str]] = [None]  # None = capability-based choice
_DEFAULT_EPOCH: List[int] = [0]  # bumped on every default change (see ops.py)


def set_default(name: Optional[str]) -> None:
    """Install a process-wide default backend (None restores auto-choice)."""
    if name is not None:
        _require_backend(name)
    _DEFAULT_BACKEND[0] = name
    _DEFAULT_EPOCH[0] += 1


def get_default() -> Optional[str]:
    return _DEFAULT_BACKEND[0]


def default_epoch() -> int:
    """Monotonic counter of default-backend changes — lets the legacy shim
    detect that its recorded default has been superseded by a newer
    set_default/default_backend scope."""
    return _DEFAULT_EPOCH[0]


@contextlib.contextmanager
def default_backend(name: str):
    """Scoped default: `with default_backend("pallas_mesh"): ...` — the
    supported replacement for the mutable `set_default_backend` global."""
    prev = _DEFAULT_BACKEND[0]
    set_default(name)
    try:
        yield
    finally:
        set_default(prev)


def _choose_backend(spec: GemmSpec) -> Tuple[_Backend, Optional[Dict[str, Any]]]:
    """Capability + cost choice, returning (backend, decision provenance).

    A CAPABLE pinned default wins immediately — explicit user intent beats
    any model.  Otherwise the capable set is ranked by the cost model's
    per-backend efficiency (`costmodel.choose.decide_backend`); with the
    shipped coefficients the predicted order IS the legacy xla ->
    pallas_mesh -> registration order on every platform, and the legacy
    order index breaks exact prediction ties, so the choice only shifts
    once calibration says otherwise.  Any cost-model failure degrades to
    the legacy first-capable rule with a ledger record."""
    order: List[str] = []
    for name in (
        *((_DEFAULT_BACKEND[0],) if _DEFAULT_BACKEND[0] is not None else ()),
        "xla",
        "pallas_mesh",
        *_REGISTRY,
    ):
        if name not in order:
            order.append(name)
    reasons = []
    capable: List[Tuple[str, int]] = []
    for idx, name in enumerate(order):
        be = _REGISTRY.get(name)
        if be is None:
            continue
        reason = _check_capabilities(spec, be)
        if reason is not None:
            reasons.append(reason)
            continue
        if name == _DEFAULT_BACKEND[0]:
            return be, None
        capable.append((name, idx))
    if not capable:
        raise CapabilityError(
            "no registered backend can execute this spec: " + "; ".join(reasons)
        )
    if len(capable) == 1:
        return _REGISTRY[capable[0][0]], None
    try:
        from repro.costmodel import choose as _cm_choose

        chosen, dec = _cm_choose.decide_backend(spec, capable)
        return _REGISTRY[chosen], dec.as_dict()
    except Exception as e:  # degraded: legacy first-capable
        _rledger.record(
            "costmodel.decide_backend",
            cause=f"{type(e).__name__}: {e}",
            fallback=capable[0][0],
        )
        return _REGISTRY[capable[0][0]], None


# Capability-ordered degradation ladder (DESIGN.md §11): when a backend's
# plan build or execution fails, the plan falls to the next CAPABLE backend
# in this order (then any other registered backend, registration order).
# ref sits last: slowest, but the oracle that can always run.
FALLBACK_ORDER = ("pallas_mesh", "xla", "ref")


def _fallback_chain(spec: GemmSpec, primary: _Backend) -> List[_Backend]:
    """`primary` plus every other backend capable of `spec`, fallback-ordered."""
    chain = [primary]
    names = {primary.name}
    for name in (*FALLBACK_ORDER, *_REGISTRY):
        be = _REGISTRY.get(name)
        if be is None or be.name in names:
            continue
        if _check_capabilities(spec, be) is None:
            chain.append(be)
            names.add(be.name)
    return chain


# ---------------------------------------------------------------------------
# Shared numerics (moved from ops.py so the shim stays thin)
# ---------------------------------------------------------------------------

# d/dz of each fused activation, as a function of the *pre-activation* z
# (recomputed in the backward pass — remat, not an extra forward output).
_ACT_GRADS = {
    "relu": lambda z: (z > 0).astype(z.dtype),
    "silu": lambda z: jax.nn.sigmoid(z) * (1 + z * (1 - jax.nn.sigmoid(z))),
    "sigmoid": lambda z: jax.nn.sigmoid(z) * (1 - jax.nn.sigmoid(z)),
    "tanh": lambda z: 1 - jnp.tanh(z) ** 2,
    "gelu": lambda z: _gelu_grad(z),
}


def _gelu_grad(z):
    """Analytic derivative of ACTIVATIONS['gelu'] (same GELU_C/GELU_A)."""
    from repro.kernels.mesh_matmul import GELU_A, GELU_C

    u = jnp.tanh(GELU_C * (z + GELU_A * z**3))
    return 0.5 * (1 + u) + 0.5 * z * (1 - u**2) * GELU_C * (1 + 3 * GELU_A * z**2)


def _act_grad(z: jax.Array, activation: str) -> jax.Array:
    return _ACT_GRADS[activation](z)


def _pad_to(x: jax.Array, multiple: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def apply_epilogue(
    z: jax.Array,
    bias: Optional[jax.Array],
    activation: Optional[str],
    residual: Optional[jax.Array],
) -> jax.Array:
    """The epilogue contract as plain jnp ops (f32 in, f32 out) — the single
    unfused reference used by the XLA/ref backends and the unfused A/B lever."""
    if bias is not None:
        z = z + bias.astype(jnp.float32)
    if activation not in (None, "none"):
        z = ACTIVATIONS[activation](z)
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    return z


def _mm_impl(a2, b2, bias, residual, opts) -> jax.Array:
    """Mesh-kernel matmul (2D or fully-batched 3D) with padding to block
    multiples and the fused epilogue."""
    block_m, block_n, block_k, stagger, scramble, out_dtype, interpret, act = opts
    batched = a2.ndim == 3
    m, n = a2.shape[-2], b2.shape[-1]
    ap = _pad_to(_pad_to(a2, block_m, -2), block_k, -1)
    bp = _pad_to(_pad_to(b2, block_k, -2), block_n, -1)
    if scramble and (ap.shape[-2] != m or bp.shape[-1] != n):
        raise ValueError(
            "structure='scrambled' requires block-aligned M and N "
            f"(got M={m}, N={n} with blocks {block_m}x{block_n})"
        )
    bias_p = None if bias is None else _pad_to(bias, block_n, 0)
    res_p = (
        None
        if residual is None
        else _pad_to(_pad_to(residual, block_m, -2), block_n, -1)
    )
    kernel = mesh_matmul_pallas_batched if batched else mesh_matmul_pallas
    out = kernel(
        ap,
        bp,
        bias=bias_p,
        residual=res_p,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        stagger=stagger,
        scramble_out=scramble,
        activation=act,
        out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[..., :m, :n]


# pallas_call has no JVP rule, so training graphs need an explicit VJP.
# Forward: y = act(A @ B + bias) + residual (epilogue fused in-kernel).
# Backward: dresidual = g; dz = g * act'(z) with z recomputed by one plain
# kernel call (remat — no extra forward output); dA = dz Bᵀ and dB = Aᵀ dz are
# two more mesh-kernel matmuls; dbias reduces dz over rows.  For the scrambled
# structure C = S(...), the cotangent is unscrambled (a pure gather — the
# permutation's own transpose) first, putting the whole backward in standard
# arrangement.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _mm(a2, b2, bias, residual, opts) -> jax.Array:
    return _mm_impl(a2, b2, bias, residual, opts)


def _mm_fwd(a2, b2, bias, residual, opts):
    # dresidual only needs residual's DTYPE — save a scalar sentinel, not the
    # full output-sized tensor (it would stay live until the backward pass).
    res_sentinel = None if residual is None else jnp.zeros((), residual.dtype)
    return _mm_impl(a2, b2, bias, residual, opts), (a2, b2, bias, res_sentinel)


def _mm_bwd(opts, res, g):
    a2, b2, bias, res_sentinel = res
    block_m, block_n, block_k, stagger, scramble, _, interpret, act = opts
    if scramble:
        g = ref.unscramble_blocks_ref(g, block_m=block_m, block_n=block_n)
    gf = g.astype(jnp.float32)
    dresidual = None if res_sentinel is None else g.astype(res_sentinel.dtype)

    if act in (None, "none"):
        dz = gf
    else:
        # Remat the pre-activation z = A @ B + bias with a plain (no-epilogue,
        # unscrambled) kernel call, then chain through act'.
        opts_z = (block_m, block_n, block_k, stagger, False, jnp.float32, interpret, None)
        z = _mm_impl(
            a2.astype(jnp.float32), b2.astype(jnp.float32), None, None, opts_z
        )
        if bias is not None:
            z = z + bias.astype(jnp.float32)
        dz = gf * _act_grad(z, act)

    opts_a = (block_m, block_k, block_n, stagger, False, jnp.float32, interpret, None)
    opts_b = (block_k, block_n, block_m, stagger, False, jnp.float32, interpret, None)
    bT = jnp.swapaxes(b2, -1, -2).astype(jnp.float32)
    aT = jnp.swapaxes(a2, -1, -2).astype(jnp.float32)
    da = _mm(dz, bT, None, None, opts_a)
    db = _mm(aT, dz, None, None, opts_b)
    dbias = (
        None
        if bias is None
        else jnp.sum(dz, axis=tuple(range(dz.ndim - 1))).astype(bias.dtype)
    )
    return da.astype(a2.dtype), db.astype(b2.dtype), dbias, dresidual


_mm.defvjp(_mm_fwd, _mm_bwd)


# -- grouped (ragged-batch) numerics ------------------------------------------


def _grouped_valid_mask(sizes: jax.Array, n_groups: int, rpg: int) -> jax.Array:
    """(rows, 1) f32 segment mask: 1 for rows inside their group's size."""
    valid = jnp.arange(rpg)[None, :] < sizes[:, None]
    return valid.reshape(n_groups * rpg, 1).astype(jnp.float32)


def _gmm_impl(tokens, sizes, w, bias, residual, opts) -> jax.Array:
    """Grouped mesh-kernel matmul with K/N padding to block multiples."""
    block_m, block_n, block_k, stagger, out_dtype, interpret, act = opts
    n = w.shape[-1]
    tp = _pad_to(tokens, block_k, -1)
    wp = _pad_to(_pad_to(w, block_k, -2), block_n, -1)
    bias_p = None if bias is None else _pad_to(bias, block_n, -1)
    res_p = None if residual is None else _pad_to(residual, block_n, -1)
    out = grouped_mesh_matmul_pallas(
        tp,
        sizes,
        wp,
        bias=bias_p,
        residual=res_p,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        stagger=stagger,
        activation=act,
        out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[:, :n]


# Like _mm, pallas_call has no JVP rule, so the grouped kernel carries its own
# VJP (MoE training differentiates through every expert GEMM).  Forward:
# y = mask ∘ (act(tokens @ W[g] + bias[g]) + residual).  Backward: the
# cotangent is segment-masked (forward zeroed padding rows), dz = g·act'(z)
# with z rematerialized by one plain grouped call, dtokens = grouped(dz, Wᵀ)
# reuses the ragged kernel with N/K block roles swapped, and dW is the
# capacity layout's free lunch — a single batched einsum over the (G, rpg)
# view, padding rows contributing exact zeros.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gmm(tokens, sizes, w, bias, residual, opts) -> jax.Array:
    return _gmm_impl(tokens, sizes, w, bias, residual, opts)


def _gmm_fwd(tokens, sizes, w, bias, residual, opts):
    res_sentinel = None if residual is None else jnp.zeros((), residual.dtype)
    out = _gmm_impl(tokens, sizes, w, bias, residual, opts)
    return out, (tokens, sizes, w, bias, res_sentinel)


def _gmm_bwd(opts, saved, g):
    tokens, sizes, w, bias, res_sentinel = saved
    block_m, block_n, block_k, stagger, _, interpret, act = opts
    n_groups, _, n = w.shape
    rpg = tokens.shape[0] // n_groups
    mask = _grouped_valid_mask(sizes, n_groups, rpg)
    gf = g.astype(jnp.float32) * mask
    dresidual = None if res_sentinel is None else (gf).astype(res_sentinel.dtype)

    if act in (None, "none"):
        dz = gf
    else:
        opts_z = (block_m, block_n, block_k, stagger, jnp.float32, interpret, None)
        z = _gmm_impl(
            tokens.astype(jnp.float32), sizes, w.astype(jnp.float32), None, None, opts_z
        )
        if bias is not None:
            z = (
                z.reshape(n_groups, rpg, n) + bias[:, None, :].astype(jnp.float32)
            ).reshape(-1, n)
        dz = gf * _act_grad(z, act)  # gf already carries the segment mask

    wT = jnp.swapaxes(w, -1, -2).astype(jnp.float32)
    opts_t = (block_m, block_k, block_n, stagger, jnp.float32, interpret, None)
    dtokens = _gmm(dz, sizes, wT, None, None, opts_t)
    dw = jnp.einsum(
        "grk,grn->gkn",
        (tokens.astype(jnp.float32) * mask).reshape(n_groups, rpg, -1),
        dz.reshape(n_groups, rpg, n),
    )
    dbias = (
        None
        if bias is None
        else dz.reshape(n_groups, rpg, n).sum(axis=1).astype(bias.dtype)
    )
    dsizes = np.zeros(sizes.shape, dtype=jax.dtypes.float0)
    return (
        dtokens.astype(tokens.dtype),
        dsizes,
        dw.astype(w.dtype),
        dbias,
        dresidual,
    )


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


# ---------------------------------------------------------------------------
# Plans
# ---------------------------------------------------------------------------


class AsyncResult:
    """Handle for a dispatched plan execution (DESIGN.md §15).

    jax arrays are futures already — the device computes in the background
    until something reads the value.  This handle makes that contract
    explicit: `out` is the (possibly still computing) array, `block()`
    waits for it and returns it.  Blocking raises whatever the device run
    raised (XLA defers errors to the sync point).
    """

    __slots__ = ("plan", "out")

    def __init__(self, plan: "Plan", out: jax.Array):
        self.plan = plan
        self.out = out

    def block(self) -> jax.Array:
        """Wait for the dispatched execution and return its result."""
        jax.block_until_ready(self.out)
        return self.out


@dataclasses.dataclass
class Plan:
    """A resolved, reusable GEMM executable with provenance.

    Built once by `plan(spec)`; calling it runs the chosen backend with the
    blocks/tables fixed at plan time.  Provenance (backend, blocks, estimated
    FLOPs/VMEM, σ table) is inspectable via the fields or `describe()`.
    """

    spec: GemmSpec
    backend: str
    capabilities: BackendCapabilities
    blocks: Optional[Tuple[int, int, int]]
    out_dtype: str
    interpret: bool
    flops: int
    vmem_bytes: Optional[int]
    sigma_table: Optional[np.ndarray] = None
    stagger_table: Optional[np.ndarray] = None
    # -- resilience state (DESIGN.md §11) --
    # guard: opt-in non-finite output policy; health: DegradationEvents this
    # plan recorded (build-time fallbacks + execution-time degradations);
    # _chain: backend names still available below the active one.
    guard: Optional[str] = None
    guard_sample: Optional[int] = None
    # Cost-model decision provenance (DESIGN.md §13): why this backend /
    # schedule / sharding was picked — per-candidate predicted seconds and
    # the calibration version.  None when every degree of freedom was pinned.
    decision: Optional[Dict[str, Any]] = None
    health: List = dataclasses.field(default_factory=list)
    _chain: List[str] = dataclasses.field(default_factory=list, repr=False)
    _active: Optional[str] = dataclasses.field(default=None, repr=False)
    _fn: Optional[Callable] = dataclasses.field(default=None, repr=False)

    @property
    def activation(self) -> Optional[str]:
        return self.spec.epilogue.activation

    @property
    def active_backend(self) -> str:
        """The backend actually executing: `backend` until an execution-time
        degradation swapped in a fallback."""
        return self._active or self.backend

    @property
    def executor(self) -> Callable:
        """The raw jitted executor `(a, b, bias, residual) -> out`, with no
        per-call Python validation — for benchmarking and trusted hot loops
        where even `__call__`'s shape/dtype checks are measurable."""
        return self._fn

    def describe(self) -> Dict[str, Any]:
        """JSON-able provenance record (benchmarks / serving telemetry)."""
        d = {
            "backend": self.backend,
            "structure": self.spec.structure,
            "mkn": f"{self.spec.eff_m}x{self.spec.k}x{self.spec.n}",
            "dtypes": [self.spec.dtype_a, self.spec.dtype_b],
            "batch": list(self.spec.batch),
            # eff_m in "mkn" folds the batch only when b is 2D; batched_b
            # consumers (roofline) must scale per-element byte counts by batch
            "batched_b": self.spec.batched_b,
            "repeats": self.spec.repeats,
            "blocks": list(self.blocks) if self.blocks else None,
            "epilogue": {
                "bias": self.spec.epilogue.bias,
                "activation": self.activation,
                "residual": self.spec.epilogue.residual,
            },
            "fused_epilogue": self.capabilities.epilogue_fusion,
            "out_dtype": self.out_dtype,
            "interpret": self.interpret,
            "flops": self.flops,
            "vmem_bytes": self.vmem_bytes,
            "health": {
                "active_backend": self.active_backend,
                "degraded": bool(self.health),
                "guard_nonfinite": self.guard,
                "fallback_chain": list(self._chain),
                "events": [e.as_dict() for e in self.health],
            },
        }
        if self.decision is not None:
            d["decision"] = self.decision
        grp = self.spec.group
        if grp is not None:
            ia = jnp.dtype(self.spec.dtype_a).itemsize
            io = jnp.dtype(self.out_dtype).itemsize
            d["grouped"] = {
                "num_groups": grp.num_groups,
                "rows_per_group": grp.rows_per_group,
                "packed": grp.packed,
                # dense per-group compute at the static capacity bound; the
                # ragged steering skips the share past each group's size
                "per_group_flops": 2 * grp.rows_per_group * self.spec.k * self.spec.n,
                # routing traffic: every token row is scattered in (K bytes)
                # and its result gathered back out (N bytes)
                "dispatch_bytes": grp.rows * (self.spec.k * ia + self.spec.n * io),
            }
        return d

    # -- execution -----------------------------------------------------------

    def _check_operands(self, a, b, bias, residual):
        spec = self.spec
        want_a = spec.batch + (spec.m, spec.k)
        want_b = (spec.batch if spec.batched_b else ()) + (spec.k, spec.n)
        if tuple(a.shape) != want_a or tuple(b.shape) != want_b:
            raise ValueError(
                f"operands {a.shape} @ {b.shape} do not match plan spec "
                f"{want_a} @ {want_b}"
            )
        got_dt = (_dtype_name(a.dtype), _dtype_name(b.dtype))
        if got_dt != (spec.dtype_a, spec.dtype_b):
            # out_dtype and the autotuned/VMEM-budgeted blocks were fixed for
            # the spec's dtypes — a silent cast here would mask caller intent
            raise ValueError(
                f"operand dtypes {got_dt} do not match plan spec "
                f"({spec.dtype_a}, {spec.dtype_b}); build a new GemmSpec"
            )
        epi = spec.epilogue
        for name, arr, declared in (
            ("bias", bias, epi.bias),
            ("residual", residual, epi.residual),
        ):
            if (arr is not None) != declared:
                state = "with" if declared else "without"
                raise ValueError(
                    f"plan was built {state} {name}; pass a matching "
                    f"Epilogue in the GemmSpec to change the contract"
                )
        # Epilogue shape validation — identical on every backend (same
        # exception type/message), against the LOGICAL (unpadded) shapes.
        _check_epilogue_shapes(bias, residual, spec)

    def __call__(self, a, b, bias=None, residual=None) -> jax.Array:
        self._check_operands(a, b, bias, residual)
        return self._execute((a, b, bias, residual))

    def dispatch(self, a, b, bias=None, residual=None) -> AsyncResult:
        """Enqueue an execution and return without waiting on the device.

        jax dispatches asynchronously by construction, so this costs what
        `__call__` costs minus any value read; the point is the explicit
        contract: validation and enqueue happen NOW, device work proceeds in
        the background, and `AsyncResult.block()` (or `execute_async` over a
        batch of independent plans) is the single sync point.  No
        `plan.execute` span is opened: its warm spans feed cost-model
        calibration and must measure device walltime, not host enqueue
        time.  Caveat: a plan with a `guard_nonfinite` policy host-syncs
        inside execution to inspect the output, so its dispatch is
        effectively synchronous (the guard wins).
        """
        self._check_operands(a, b, bias, residual)
        return AsyncResult(self, self._execute_impl((a, b, bias, residual)))

    # -- resilience (DESIGN.md §11) ------------------------------------------

    def _record(self, site: str, cause: str, fallback: str, **detail):
        """One DegradationEvent, in the plan's health AND the global ledger."""
        ev = _rledger.record(site, cause=cause, fallback=fallback, **detail)
        self.health.append(ev)
        return ev

    def _degrade(self, args: tuple, *, site: str, cause: str, original=None):
        """Fall to the next capable backend in the chain and run `args` there.

        On success the plan PERMANENTLY swaps its executor — a backend that
        failed (or produced NaN under the `fallback` guard policy) is not
        trusted again for this plan; the hot path recovers to a single
        `_fn` call.  Exhausting the chain re-raises."""
        err = original
        while self._chain:
            name = self._chain.pop(0)
            self._record(site, cause, fallback=name, backend=self.active_backend)
            try:
                fb = plan(self.spec, backend=name, fallback=False)
                _faults.check(site, backend=name)
                out = fb._fn(*args)
            except PlanValidationError:
                raise
            except Exception as e:
                cause = f"{type(e).__name__}: {e}"
                err = e
                continue
            self._fn = fb._fn
            self._active = name
            return out
        raise RuntimeError(
            f"backend {self.active_backend!r} failed ({cause}) and the"
            f" fallback chain is exhausted for this spec"
        ) from err

    def _obs_attrs(self) -> Dict[str, Any]:
        """Span attributes for plan.execute (DESIGN.md §14), computed once
        per plan: backend/blocks/schedule provenance plus the cost-model
        `terms` the obs bridge converts into calibration records.  Cached on
        the instance — the enabled hot path pays one dict splat, not a
        describe() walk."""
        at = getattr(self, "_obs_attrs_cache", None)
        if at is None:
            spec = self.spec
            at = {
                "backend": self.active_backend,
                "structure": spec.structure,
                "mkn": f"{spec.eff_m}x{spec.k}x{spec.n}",
                "key": f"{spec.eff_m}x{spec.k}x{spec.n}|{self.backend}",
                "blocks": list(self.blocks) if self.blocks else None,
                "schedule": getattr(self, "schedule", None),
            }
            try:
                from repro.costmodel.model import terms_from_describe

                at["terms"] = terms_from_describe(self.describe())
            except Exception:
                pass  # spans still carry provenance without cost terms
            self._obs_attrs_cache = at
        return at

    def _execute(self, args: tuple) -> jax.Array:
        # Disabled tracing costs ONE attribute check here (the dispatch
        # microbench rides this path); the span itself is tracer-aware, so
        # a plan called inside an enclosing jit trace records nothing.
        if _obs._STATE.enabled:
            with _obs.span("plan.execute", **self._obs_attrs()):
                return self._execute_impl(args)
        return self._execute_impl(args)

    def _execute_impl(self, args: tuple) -> jax.Array:
        try:
            _faults.check("plan.execute", backend=self.active_backend)
            out = self._fn(*args)
        except (PlanValidationError, CapabilityError):
            raise
        except Exception as e:
            out = self._degrade(
                args,
                site="plan.execute",
                cause=f"{type(e).__name__}: {e}",
                original=e,
            )
        out = _faults.poison("kernel.output", out, backend=self.active_backend)
        if self.guard is not None:
            out = self._apply_guard(out, args)
        return out

    def _apply_guard(self, out: jax.Array, args: tuple) -> jax.Array:
        """The post-epilogue non-finite guard (fused paths stay fused: the
        check wraps the executor's OUTPUT, never reaches into the kernel)."""
        if isinstance(out, jax.core.Tracer):
            # Under an enclosing trace values are unknown: zero_and_record
            # lowers to an unconditional traced scrub; raise/fallback cannot
            # branch on traced values, so the gap is recorded, not hidden.
            if self.guard == "zero_and_record":
                return scrub_nonfinite(out)
            self._record(
                "guard.nonfinite",
                cause="guard bypassed under trace (values unknown)",
                fallback="unchecked",
                backend=self.active_backend,
            )
            return out
        bad = nonfinite_count(out, sample=self.guard_sample)
        if not bad:
            return out
        cause = f"{bad} non-finite output value(s) sampled"
        if self.guard == "zero_and_record":
            self._record(
                "guard.nonfinite", cause, fallback="zero",
                backend=self.active_backend,
            )
            return scrub_nonfinite(out)
        if self.guard == "fallback":
            out = self._degrade(args, site="guard.nonfinite", cause=cause)
            if isinstance(out, jax.core.Tracer) or not nonfinite_count(
                out, sample=self.guard_sample
            ):
                return out
            raise NonFiniteError(
                f"non-finite outputs persist after fallback"
                f" (backend {self.active_backend!r})"
            )
        raise NonFiniteError(
            f"guarded plan produced {bad} non-finite value(s) on backend"
            f" {self.active_backend!r} (structure={self.spec.structure!r},"
            f" mkn={self.spec.eff_m}x{self.spec.k}x{self.spec.n})"
        )


def _check_epilogue_shapes(bias, residual, spec: GemmSpec) -> None:
    """The `_check_epilogue` contract at the dispatch layer: every backend —
    XLA included — rejects malformed bias/residual with the same error.
    Grouped specs carry a PER-GROUP bias (num_groups, N)."""
    n = spec.n
    want_bias = (spec.group.num_groups, n) if spec.group is not None else (n,)
    if bias is not None and tuple(bias.shape) != want_bias:
        raise ValueError(
            f"bias must have shape {want_bias}, got {tuple(bias.shape)}"
        )
    want_res = spec.batch + (spec.m, n)
    if residual is not None and tuple(residual.shape) != want_res:
        raise ValueError(
            f"residual must have shape {want_res}, got {tuple(residual.shape)}"
        )


def _check_grouped_operands(plan: "Plan", tokens, group_offsets, weights,
                            bias, residual) -> None:
    """Operand validation shared by GroupedPlan and ShardedGroupedPlan."""
    spec = plan.spec
    grp = spec.group
    want_t = (grp.rows, spec.k)
    want_w = (grp.num_groups, spec.k, spec.n)
    if tuple(tokens.shape) != want_t or tuple(weights.shape) != want_w:
        raise ValueError(
            f"grouped operands {tokens.shape} / {weights.shape} do not match"
            f" plan spec tokens {want_t} / weights {want_w}"
        )
    if tuple(group_offsets.shape) != (grp.num_groups + 1,):
        raise ValueError(
            f"group_offsets must have shape ({grp.num_groups + 1},) —"
            f" cumulative row counts — got {tuple(group_offsets.shape)}"
        )
    if not jnp.issubdtype(group_offsets.dtype, jnp.integer):
        raise ValueError(
            f"group_offsets must be integer-typed, got {group_offsets.dtype}"
        )
    got_dt = (_dtype_name(tokens.dtype), _dtype_name(weights.dtype))
    if got_dt != (spec.dtype_a, spec.dtype_b):
        raise ValueError(
            f"operand dtypes {got_dt} do not match plan spec "
            f"({spec.dtype_a}, {spec.dtype_b}); build a new GemmSpec"
        )
    epi = spec.epilogue
    for name, arr, declared in (
        ("bias", bias, epi.bias),
        ("residual", residual, epi.residual),
    ):
        if (arr is not None) != declared:
            state = "with" if declared else "without"
            raise ValueError(
                f"plan was built {state} {name}; pass a matching "
                f"Epilogue in the GemmSpec to change the contract"
            )
    _check_epilogue_shapes(bias, residual, spec)


@dataclasses.dataclass
class GroupedPlan(Plan):
    """A Plan for a grouped (ragged-batch) GEMM (DESIGN.md §10).

    Execution takes `(tokens, group_offsets, weights)` — tokens in the
    group-major capacity layout, `group_offsets` the (num_groups+1,)
    cumulative valid-row counts whose diffs are the per-group sizes, weights
    stacked (num_groups, K, N).  Rows at or beyond a group's size come back
    zero.  One plan serves every routing outcome of its logical group shape:
    the offsets are an execution-time operand, not part of the spec.
    """

    def __call__(self, tokens, group_offsets, weights, bias=None, residual=None):
        _check_grouped_operands(self, tokens, group_offsets, weights, bias, residual)
        return self._execute((tokens, group_offsets, weights, bias, residual))


@dataclasses.dataclass
class ShardedPlan(Plan):
    """A Plan lowered over a device mesh (DESIGN.md §9).

    Built by `plan(spec, mesh=...)` for a spec carrying a ShardSpec: the
    per-shard product is the ordinary single-device Plan (`local`, built by
    the same planner), wrapped in `shard_map` with the chosen collective
    schedule fused around the kernel call.  Operands/results are GLOBAL
    arrays with the spec's logical shapes; `__call__` validates them exactly
    like an unsharded Plan.  The epilogue is applied after the collective
    (act(sum) != sum(act) under a K split), so it is never kernel-fused here.

    Extra provenance: the collective `schedule`, per-shard FLOPs/VMEM via
    `local`, and `bytes_moved` — collective link bytes per device per call —
    so roofline/serving tooling can report communication cost.
    """

    mesh: Any = None
    schedule: str = "replicated"
    local: Optional[Plan] = dataclasses.field(default=None, repr=False)
    bytes_moved: int = 0
    collective_phases: int = 0
    # Ring-schedule devices run the local kernel once per ring step, so the
    # per-DEVICE work is local.flops x this (reduce_scatter family: p;
    # column-half overlap variants: 2; pipeline: microbatch count).
    kernel_invocations: int = 1
    # Measured serial_ms / overlap_ms for this plan's schedule vs its serial
    # twin — recorded by benchmarks via `note_overlap_efficiency`, None until
    # something measured it (provenance, never consulted by execution).
    overlap_efficiency: Optional[float] = None

    def note_overlap_efficiency(self, ratio: float) -> None:
        """Record a measured serial/overlap time ratio (>1 means the
        double-buffered schedule won); shows up in describe()["sharding"]."""
        self.overlap_efficiency = float(ratio)

    def describe(self) -> Dict[str, Any]:
        d = super().describe()
        shard = self.spec.shard
        d["fused_epilogue"] = False  # applied post-collective, never in-kernel
        d["sharding"] = {
            "mesh": [[n, s] for n, s in shard.mesh_axes],
            "axes": {
                "m": shard.axis_m,
                "k": shard.axis_k,
                "n": shard.axis_n,
                "batch": shard.axis_batch,
                "g": shard.axis_g,
            },
            "schedule": self.schedule,
            "overlap": _is_overlap_schedule(self.schedule),
            "overlap_efficiency": self.overlap_efficiency,
            "collective_phases": self.collective_phases,
            "bytes_moved": self.bytes_moved,
            "kernel_invocations": self.kernel_invocations,
            "per_shard_mkn": [
                self.local.spec.eff_m,
                self.local.spec.k,
                self.local.spec.n,
            ],
            "per_shard_batch": list(self.local.spec.batch),
            "per_shard_flops": self.local.flops * self.kernel_invocations,
            "per_shard_vmem_bytes": self.local.vmem_bytes,
        }
        return d

    def _degrade(self, args: tuple, *, site: str, cause: str, original=None):
        """Sharded degradation ladder: a failed collective schedule falls back
        to REPLICATED execution of the identical spec — the same global
        operands run through the unsharded planner (its own backend chain
        still applies), so numerics are preserved at the cost of the
        collective's speedup."""
        if self._active == "replicated":  # already degraded once
            raise RuntimeError(
                f"sharded plan failed again after degrading to replicated"
                f" ({cause})"
            ) from original
        self._record(
            site,
            cause,
            fallback="replicated",
            schedule=self.schedule,
            backend=self.active_backend,
        )
        unspec = dataclasses.replace(self.spec, shard=None)
        fb = plan(unspec)
        out = fb._execute(args)
        self._fn = fb._fn
        self._active = "replicated"
        return out


@dataclasses.dataclass
class ShardedGroupedPlan(ShardedPlan):
    """A GroupedPlan lowered over a device mesh: the `expert` schedule.

    The group (expert) dim shards over `ShardSpec.axis_g`; tokens, sizes and
    stacked weights reshard at the shard_map boundary — under a pjit caller
    with data-sharded dispatch buffers this IS the EP all-to-all — and each
    device runs the ordinary per-shard GroupedPlan over its local groups.
    Output rows stay group-sharded (no further collective), and the epilogue
    shards with its operands — per-group bias and group-major residual
    partition on axis_g, so it stays inside the local kernel (fused on the
    Pallas backend), unlike the K-collective schedules.
    """

    __call__ = GroupedPlan.__call__

    def describe(self) -> Dict[str, Any]:
        d = super().describe()
        # ShardedPlan forces fused_epilogue=False (post-collective apply);
        # grouped sharding keeps the epilogue in the local kernel.
        d["fused_epilogue"] = self.capabilities.epilogue_fusion
        return d


# -- built-in backend implementations ----------------------------------------


def _xla_impl(p: Plan, a, b, bias, residual):
    z = jnp.matmul(a, b, preferred_element_type=jnp.float32)
    return apply_epilogue(z, bias, p.activation, residual).astype(p.out_dtype)


def _ref_impl(p: Plan, a, b, bias, residual):
    """Pure-jnp oracle backend: same contract, no Pallas — registered through
    the same capability door as the real kernels (and usable as a test double)."""
    z = jnp.matmul(a, b, preferred_element_type=jnp.float32)
    y = apply_epilogue(z, bias, p.activation, residual)
    if p.spec.structure == "scrambled":
        bm, bn, _ = p.blocks
        y = ref.scramble_blocks_ref(y, block_m=bm, block_n=bn)
    return y.astype(p.out_dtype)


def _pallas_impl(p: Plan, a, b, bias, residual):
    spec = p.spec
    bm, bn, bk = p.blocks
    opts = (
        bm,
        bn,
        bk,
        spec.stagger,
        spec.structure == "scrambled",
        jnp.dtype(p.out_dtype),
        p.interpret,
        spec.epilogue.activation,
    )
    if not spec.batch:
        return _mm(a, b, bias, residual, opts)
    if not spec.batched_b:
        # Fold leading batch dims of `a` into M — still a single 2D kernel.
        a2 = a.reshape(-1, spec.k)
        res2 = None if residual is None else residual.reshape(-1, spec.n)
        out = _mm(a2, b, bias, res2, opts)
        return out.reshape(*spec.batch, spec.m, spec.n)
    # Fully batched: ONE pallas_call with grid (b, i, j, k).
    af = a.reshape(-1, spec.m, spec.k)
    bf = b.reshape(-1, spec.k, spec.n)
    resf = None if residual is None else residual.reshape(-1, spec.m, spec.n)
    out = _mm(af, bf, bias, resf, opts)
    return out.reshape(*spec.batch, spec.m, spec.n)


def _grouped_sizes(p: Plan, group_offsets: jax.Array) -> jax.Array:
    del p
    return (group_offsets[1:] - group_offsets[:-1]).astype(jnp.int32)


def _packed_epilogue(p: Plan, z, group_offsets, bias, residual):
    """The epilogue of a packed grouped product z (rows, N) in float32:
    each row's group's bias, the activation, the residual, and zero past
    the last group."""
    grp = p.spec.group
    r = jnp.arange(grp.rows)
    if bias is not None:
        g = jnp.searchsorted(group_offsets[1:], r, side="right")
        z = z + bias[jnp.minimum(g, grp.num_groups - 1)].astype(jnp.float32)
    if p.activation not in (None, "none"):
        z = ACTIVATIONS[p.activation](z)
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    return jnp.where((r < group_offsets[-1])[:, None], z, 0.0).astype(p.out_dtype)


def _packed_product(tokens, group_offsets, w, out_dtype) -> jax.Array:
    """Product of a packed layout, as float32: a ragged dot on the TPU,
    which computes each row against its own group's slab only and rounds
    to `out_dtype` (its gradients then keep that type too, not float32);
    elsewhere one masked product per group, accumulated in a loop (the
    ragged dot's generic lowering would hold the tokens once per group)."""
    sizes = group_offsets[1:] - group_offsets[:-1]

    def ragged(tokens, group_offsets, w):
        return jax.lax.ragged_dot(tokens, w, sizes, preferred_element_type=out_dtype).astype(jnp.float32)

    def per_group(tokens, group_offsets, w):
        r = jnp.arange(tokens.shape[0])[:, None]

        def body(g, z):
            inside = (group_offsets[g] <= r) & (r < group_offsets[g + 1])
            zg = jnp.matmul(tokens, w[g], preferred_element_type=jnp.float32)
            return z + jnp.where(inside, zg, 0.0)

        z = jnp.zeros((tokens.shape[0], w.shape[-1]), jnp.float32)
        return jax.lax.fori_loop(0, w.shape[0], body, z)

    return jax.lax.platform_dependent(
        tokens, group_offsets, w, tpu=ragged, default=per_group
    )


def _xla_grouped_impl(p: Plan, tokens, group_offsets, w, bias, residual):
    """Segment-masked einsum fallback: the capacity layout makes the ragged
    batch a dense (G, rpg, K) @ (G, K, N) product; the segment mask zeroes
    rows past each group's size (identical contract to the Pallas kernel).
    The packed layout runs `_packed_product`."""
    grp = p.spec.group
    sizes = _grouped_sizes(p, group_offsets)
    if grp.packed:
        # A fused epilogue takes the float32 product, as the capacity
        # layout's does; without one the product rounds once, to out_dtype.
        acc = p.out_dtype if p.spec.epilogue.is_identity else jnp.float32
        z = _packed_product(tokens, group_offsets, w, acc)
        return _packed_epilogue(p, z, group_offsets, bias, residual)
    rpg = grp.rows_per_group
    tg = tokens.reshape(grp.num_groups, rpg, p.spec.k)
    z = jnp.einsum("grk,gkn->grn", tg, w, preferred_element_type=jnp.float32)
    if bias is not None:
        z = z + bias[:, None, :].astype(jnp.float32)
    if p.activation not in (None, "none"):
        z = ACTIVATIONS[p.activation](z)
    if residual is not None:
        z = z + residual.reshape(z.shape).astype(jnp.float32)
    valid = jnp.arange(rpg)[None, :] < sizes[:, None]
    z = jnp.where(valid[..., None], z, 0.0)
    return z.reshape(grp.rows, p.spec.n).astype(p.out_dtype)


def _ref_grouped_impl(p: Plan, tokens, group_offsets, w, bias, residual):
    """Oracle: per-group jnp products in a Python loop (G is static), same
    epilogue + segment-mask contract as every other grouped backend."""
    grp = p.spec.group
    sizes = _grouped_sizes(p, group_offsets)
    if grp.packed:
        r = jnp.arange(grp.rows)[:, None]
        z = sum(
            jnp.where(
                (group_offsets[g] <= r) & (r < group_offsets[g + 1]),
                jnp.matmul(tokens, w[g], preferred_element_type=jnp.float32),
                0.0,
            )
            for g in range(grp.num_groups)
        )
        return _packed_epilogue(p, z, group_offsets, bias, residual)
    rpg = grp.rows_per_group
    outs = []
    for g in range(grp.num_groups):
        z = jnp.matmul(
            tokens[g * rpg : (g + 1) * rpg],
            w[g],
            preferred_element_type=jnp.float32,
        )
        z = apply_epilogue(
            z,
            None if bias is None else bias[g],
            p.activation,
            None if residual is None else residual[g * rpg : (g + 1) * rpg],
        )
        z = jnp.where(jnp.arange(rpg)[:, None] < sizes[g], z, 0.0)
        outs.append(z)
    return jnp.concatenate(outs, axis=0).astype(p.out_dtype)


def _pallas_grouped_impl(p: Plan, tokens, group_offsets, w, bias, residual):
    spec = p.spec
    bm, bn, bk = p.blocks
    opts = (
        bm,
        bn,
        bk,
        spec.stagger,
        jnp.dtype(p.out_dtype),
        p.interpret,
        spec.epilogue.activation,
    )
    return _gmm(tokens, _grouped_sizes(p, group_offsets), w, bias, residual, opts)


register_backend(
    "xla",
    _xla_impl,
    BackendCapabilities(
        structures=frozenset({"general", "symmetric"}),
        batching=True,
        epilogue=True,
        epilogue_fusion=False,  # XLA may fuse, but it is not contractual
        interpret=True,  # native everywhere
        autotune=False,
        sharding=True,
        grouped=True,
        grouped_packed=True,
    ),
    grouped_impl=_xla_grouped_impl,
)
register_backend(
    "pallas_mesh",
    _pallas_impl,
    BackendCapabilities(
        structures=frozenset({"general", "symmetric", "scrambled"}),
        batching=True,
        epilogue=True,
        epilogue_fusion=True,
        interpret=True,  # Pallas interpret mode off-TPU
        autotune=True,
        sharding=True,
        grouped=True,
    ),
    grouped_impl=_pallas_grouped_impl,
)
register_backend(
    "ref",
    _ref_impl,
    BackendCapabilities(
        structures=frozenset({"general", "symmetric", "scrambled"}),
        batching=True,
        epilogue=True,
        epilogue_fusion=False,
        interpret=True,
        autotune=False,
        sharding=True,
        grouped=True,
        grouped_packed=True,
    ),
    grouped_impl=_ref_grouped_impl,
)


# ---------------------------------------------------------------------------
# plan()
# ---------------------------------------------------------------------------


def plan(
    spec: GemmSpec,
    *,
    backend: Optional[str] = None,
    mesh: Optional[Mesh] = None,
    guard_nonfinite: Optional[str] = None,
    guard_sample: Optional[int] = None,
    fallback: bool = True,
) -> Plan:
    """Validate `spec` against backend capabilities and return the cached,
    reusable executable for it.

    Resolution happens ONCE per (spec, backend, mesh, guard) tuple per
    platform: capability checks, autotuned block shapes, σ/stagger tables,
    collective schedule, and the jitted executor are all fixed here; repeated
    calls return the *identical* Plan object.  An explicit `backend` is
    validated strictly (CapabilityError on mismatch); otherwise the first
    capable backend is chosen — the capable set ranked by the cost model
    (DESIGN.md §13; ties reproduce pinned default → xla → pallas_mesh →
    registration order).  A spec carrying a ShardSpec requires the live
    device `mesh` and returns a ShardedPlan; equal meshes (same devices +
    axis names) key the same cache entry, different meshes plan separately.
    `mesh=` WITHOUT a ShardSpec auto-shards: the cost model enumerates axis
    assignments over the live mesh and attaches the cheapest legal
    ShardSpec (decision provenance in `describe()["decision"]`).
    A spec carrying a GroupSpec returns a GroupedPlan taking (tokens,
    group_offsets, weights) — and, with a ShardSpec too, a
    ShardedGroupedPlan (`expert` schedule).

    Resilience (DESIGN.md §11): with `fallback=True` (default) a failed plan
    BUILD falls down the capability-ordered chain (`FALLBACK_ORDER`) to the
    next backend able to run the spec, recording a DegradationEvent in the
    returned plan's `health` and the global `resilience.ledger` instead of
    raising; only when every capable backend fails does the last error
    surface.  Spec-level `PlanValidationError`s always raise — they are
    caller bugs every backend would reject.  `guard_nonfinite` opts the plan
    into the post-epilogue NaN/Inf guard with policy `raise | fallback |
    zero_and_record` (`guard_sample` spot-checks that many strided output
    elements instead of reducing the full array).
    """
    if not isinstance(spec, GemmSpec):
        raise TypeError(f"plan() takes a GemmSpec, got {type(spec).__name__}")
    if spec.shard is not None and mesh is None:
        raise ValueError(
            "spec carries a ShardSpec; pass the device mesh:"
            " plan(spec, mesh=mesh)"
        )
    shard_decision = None
    if spec.shard is None and mesh is not None:
        spec, shard_decision = _auto_shard(spec, mesh)
    if guard_nonfinite is not None:
        guard_nonfinite = normalize_policy(guard_nonfinite)
    backend_decision = None
    if backend is not None:
        be = _require_backend(backend)
        reason = _check_capabilities(spec, be)
        if reason is not None:
            raise CapabilityError(reason)
    else:
        be, backend_decision = _choose_backend(spec)

    key = (
        spec, be.name, jax.default_backend(), mesh, guard_nonfinite, guard_sample
    )
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _PLAN_STATS["hits"] += 1
        return cached
    _PLAN_STATS["misses"] += 1

    chain = _fallback_chain(spec, be) if fallback else [be]
    build_events: List[Any] = []
    p = None
    built_at = 0
    with _obs.span(
        "plan.build",
        backend=be.name,
        structure=spec.structure,
        mkn=f"{spec.eff_m}x{spec.k}x{spec.n}",
        sharded=mesh is not None,
    ) as _bsp:
        for i, cand in enumerate(chain):
            try:
                _faults.check("plan.build", backend=cand.name)
                p = (
                    _build_plan(spec, cand)
                    if mesh is None
                    else _build_sharded_plan(spec, cand, mesh)
                )
                built_at = i
                break
            except (PlanValidationError, CapabilityError):
                raise
            except Exception as e:
                if i + 1 >= len(chain):
                    raise
                build_events.append(
                    _rledger.record(
                        "plan.build",
                        cause=f"{type(e).__name__}: {e}",
                        fallback=chain[i + 1].name,
                        backend=cand.name,
                    )
                )
        _bsp.set("built_backend", chain[built_at].name)
        _bsp.set("blocks", list(p.blocks) if p.blocks else None)
        if getattr(p, "schedule", None) is not None:
            _bsp.set("schedule", p.schedule)
    p.health.extend(build_events)
    if backend_decision is not None or shard_decision is not None:
        # merge with any schedule decision _build_sharded_plan attached
        dec = dict(p.decision or {})
        if backend_decision is not None:
            dec["backend"] = backend_decision
        if shard_decision is not None:
            dec["sharding"] = shard_decision
        p.decision = dec
    # Backends still available below the one that built — the execution-time
    # degradation ladder (Plan._degrade).
    p._chain = [c.name for c in chain[built_at + 1 :]]
    p.guard = guard_nonfinite
    p.guard_sample = guard_sample
    _PLAN_CACHE[key] = p
    return p


def _resolve_blocks_via_costmodel(
    m: int, k: int, n: int, dtype, backend: str, *, symmetry: int = 0
) -> Tuple[int, int, int]:
    """Block resolution through the cost model's chooser: IDENTICAL to
    `autotune.resolve_blocks` (same cache, same analytic ranking) until
    coefficients are CALIBRATED, when the candidate ranking switches to
    `costmodel.model.predict_blocks_ms`.  Any chooser failure degrades to
    the autotuner directly."""
    try:
        from repro.costmodel import choose as _cm_choose

        blocks, _ = _cm_choose.choose_blocks(
            m, k, n, dtype, backend, symmetry=symmetry
        )
        return blocks
    except Exception:
        return _autotune.resolve_blocks(m, k, n, dtype, backend, symmetry=symmetry)


def _grouped_block_m(rpg: int, bm: int) -> int:
    """Largest block_m that both divides rows_per_group and respects the
    tuned bm — the (g, i, j, k) grid needs whole row blocks per group."""
    if rpg % bm == 0:
        return bm
    g = math.gcd(rpg, bm)
    return g if g >= 8 else rpg


def _build_grouped_plan(spec: GemmSpec, be: _Backend) -> GroupedPlan:
    """Grouped planning: autotune ONCE per logical group shape (m = the
    rows-per-group bound), then clamp block_m to divide it."""
    grp = spec.group
    blocks = vmem = stagger_tbl = None
    if be.caps.autotune:
        partial = spec.blocks or (None, None, None)
        if None in partial:
            bm, bn, bk = _resolve_blocks_via_costmodel(
                grp.rows_per_group, spec.k, spec.n, spec.acc_dtype, be.name
            )
            blocks = tuple(p or r for p, r in zip(partial, (bm, bn, bk)))
        else:
            blocks = partial
        bm, bn, bk = blocks
        blocks = (_grouped_block_m(grp.rows_per_group, bm), bn, bk)
        vmem = _autotune.vmem_bytes(
            *blocks,
            spec.acc_dtype,
            has_bias=spec.epilogue.bias,
            has_residual=spec.epilogue.residual,
        )
        if spec.stagger:
            bm, bn, bk = blocks
            nm = grp.rows_per_group // bm
            nn = -(-spec.n // bn)
            nk = -(-spec.k // bk)
            # (g + i + j) mod nk rotation per group tile, recorded for one
            # group (the pattern shifts by g across groups)
            stagger_tbl = np.add.outer(np.arange(nm), np.arange(nn)) % max(nk, 1)
    p = GroupedPlan(
        spec=spec,
        backend=be.name,
        capabilities=be.caps,
        blocks=blocks,
        out_dtype=spec.resolved_out_dtype(),
        interpret=not _on_tpu(),
        flops=spec.flops(),
        vmem_bytes=vmem,
        stagger_table=stagger_tbl,
    )
    impl = be.grouped_impl
    p._fn = jax.jit(
        lambda t, off, w, bias, residual: impl(p, t, off, w, bias, residual)
    )
    return p


def _build_plan(spec: GemmSpec, be: _Backend) -> Plan:
    if spec.group is not None:
        return _build_grouped_plan(spec, be)
    acc_dtype = spec.acc_dtype
    blocks = None
    vmem = None
    if be.caps.autotune or spec.structure == "scrambled":
        partial = spec.blocks or (None, None, None)
        if None in partial:
            # The scrambled σ-table constraint and the symmetric early-readout
            # regime key their own autotune-cache partitions.
            tune_backend = (
                "pallas_mesh_scrambled" if spec.structure == "scrambled" else be.name
            )
            symmetry = 1 if spec.structure == "symmetric" else 0
            bm, bn, bk = _resolve_blocks_via_costmodel(
                spec.eff_m, spec.k, spec.n, acc_dtype, tune_backend, symmetry=symmetry
            )
            blocks = tuple(p or r for p, r in zip(partial, (bm, bn, bk)))
        else:
            blocks = partial
        vmem = _autotune.vmem_bytes(
            *blocks,
            acc_dtype,
            has_bias=spec.epilogue.bias,
            has_residual=spec.epilogue.residual,
        )

    sigma = stagger_tbl = None
    if spec.structure == "symmetric" and spec.m != spec.n:
        raise PlanValidationError(
            f"structure='symmetric' requires a square product, got "
            f"{spec.m}x{spec.n}"
        )
    if spec.structure == "scrambled":
        bm, bn, bk = blocks
        eff_m, n = spec.eff_m, spec.n
        if eff_m % bm or n % bn:
            raise PlanValidationError(
                "structure='scrambled' requires block-aligned M and N "
                f"(got M={eff_m}, N={n} with blocks {bm}x{bn})"
            )
        if eff_m // bm != n // bn:
            raise PlanValidationError(
                f"scramble_out needs square block grid, got {eff_m // bm}x{n // bn}"
            )
        # σ lookup table, host-side numpy, once — the kernel's scalar-prefetch
        # input is an lru_cache hit from here on.
        sigma = sigma_block_table(eff_m // bm)
    if blocks is not None and spec.stagger:
        # Per-cell k-rotation offsets ((i + j) mod nk) — the staggered
        # schedule as a host-side table, recorded for provenance/debug.
        bm, bn, bk = blocks
        nm = -(-spec.eff_m // bm)
        nn = -(-spec.n // bn)
        nk = -(-spec.k // bk)
        stagger_tbl = np.add.outer(np.arange(nm), np.arange(nn)) % max(nk, 1)

    p = Plan(
        spec=spec,
        backend=be.name,
        capabilities=be.caps,
        blocks=blocks,
        out_dtype=spec.resolved_out_dtype(),
        interpret=not _on_tpu(),
        flops=spec.flops(),
        vmem_bytes=vmem,
        sigma_table=sigma,
        stagger_table=stagger_tbl,
    )
    impl = be.impl
    p._fn = jax.jit(lambda a, b, bias, residual: impl(p, a, b, bias, residual))
    return p


# ---------------------------------------------------------------------------
# Sharded planning (DESIGN.md §9)
# ---------------------------------------------------------------------------


def _legacy_auto_schedule(spec: GemmSpec) -> str:
    """The pre-cost-model divisibility heuristic — kept as the degraded
    fallback AND the shape of the model's tie-breaks: a K partition rings
    (scatter when M divides it), anything else replicates."""
    shard = spec.shard
    pk = shard.axis_size(shard.axis_k)
    if pk > 1:
        return "reduce_scatter_k" if spec.eff_m % pk == 0 else "ring_k"
    return "replicated"


def _auto_schedule(spec: GemmSpec) -> Tuple[str, Optional[Dict[str, Any]]]:
    """Resolve schedule='auto' through the cost model (DESIGN.md §13).

    The model legality-trials every schedule with this function's OWN
    validation (pinned-schedule `_resolve_sharding` calls), so it can never
    pick an illegal one.  When no candidate is legal the legacy heuristic
    names the schedule whose validation then raises the precise error the
    caller always saw; any other cost-model failure degrades to the legacy
    choice with a ledger record."""
    try:
        from repro.costmodel import choose as _cm_choose
    except Exception:
        return _legacy_auto_schedule(spec), None
    try:
        sched, dec = _cm_choose.decide_schedule(spec)
        return sched, dec.as_dict()
    except _cm_choose.NoLegalCandidate:
        return _legacy_auto_schedule(spec), None
    except Exception as e:
        _rledger.record(
            "costmodel.decide_schedule",
            cause=f"{type(e).__name__}: {e}",
            fallback="legacy-heuristic",
        )
        return _legacy_auto_schedule(spec), None


def _auto_shard(
    spec: GemmSpec, mesh: Mesh
) -> Tuple[GemmSpec, Optional[Dict[str, Any]]]:
    """plan(spec, mesh=...) with NO ShardSpec: let the cost model pick axes
    AND schedule over the live mesh.  Degraded fallback is the unsharded
    ShardSpec — correct on any mesh — with a ledger record."""
    try:
        from repro.costmodel import choose as _cm_choose

        shard, dec = _cm_choose.decide_sharding(spec, mesh)
        return dataclasses.replace(spec, shard=shard), dec.as_dict()
    except Exception as e:
        _rledger.record(
            "costmodel.decide_sharding",
            cause=f"{type(e).__name__}: {e}",
            fallback="unsharded",
        )
        return dataclasses.replace(spec, shard=ShardSpec.unsharded(mesh)), None


def _resolve_sharding(
    spec: GemmSpec,
) -> Tuple[str, GemmSpec, int, int, Optional[Dict[str, Any]]]:
    """Choose/validate the collective schedule for `spec.shard` and derive
    (schedule, per-shard local spec, bytes_moved per device per call,
    collective phase count, cost-model decision provenance — None unless
    schedule='auto' resolved through the model).

    The local spec is the SAME GemmSpec type the unsharded planner consumes —
    epilogue stripped (applied post-collective) and accumulation pinned to
    f32, structure folded to 'general' (per-shard tiles are rectangular).
    """
    shard = spec.shard
    if spec.group is not None:
        return _resolve_grouped_sharding(spec)
    if shard.axis_g is not None:
        raise PlanValidationError(
            "axis_g partitions the group dim of a GROUPED spec; this spec"
            " carries no GroupSpec"
        )
    if spec.structure == "scrambled":
        raise PlanValidationError(
            "structure='scrambled' does not compose with a ShardSpec: the"
            " σ arrangement is defined on the global block grid"
        )
    if spec.structure == "symmetric" and spec.m != spec.n:
        raise PlanValidationError(
            f"structure='symmetric' requires a square product, got "
            f"{spec.m}x{spec.n}"
        )
    pm = shard.axis_size(shard.axis_m)
    pk = shard.axis_size(shard.axis_k)
    pn = shard.axis_size(shard.axis_n)
    pb = shard.axis_size(shard.axis_batch)
    eff_m = spec.eff_m

    sched = shard.schedule
    decision = None
    if sched == "auto":
        sched, decision = _auto_schedule(spec)
    if sched == "expert":
        raise PlanValidationError(
            "schedule 'expert' shards the group dim of a GROUPED spec;"
            " this spec carries no GroupSpec"
        )

    def div(what: str, dim: int, axes, p: int) -> int:
        if dim % p:
            raise PlanValidationError(
                f"{what}={dim} is not divisible by mesh axes {axes!r}"
                f" (size {p}) required by schedule {sched!r}"
                f" on mesh {shard.mesh_axes}"
            )
        return dim // p

    if spec.batched_b and sched != "replicated":
        raise PlanValidationError(
            f"schedule {sched!r} does not support fully-batched operands;"
            " use the replicated schedule (batch/M/N partitions are local)"
        )
    if shard.axis_batch is not None and not spec.batch:
        raise PlanValidationError("axis_batch given but the spec has no batch dims")
    if not spec.batched_b and pb > 1:
        raise PlanValidationError(
            "axis_batch partitions the leading dim of a fully-batched"
            " product; with 2D b the batch folds into M — shard axis_m"
            " instead"
        )

    lb: Tuple[int, ...] = spec.batch
    if sched == "replicated":
        if pk > 1:
            raise PlanValidationError(
                "schedule 'replicated' cannot shard K (a K partition needs a"
                " collective; use 'reduce_scatter_k' or 'ring_k')"
            )
        if spec.batched_b:
            nb = math.prod(spec.batch)
            lb = (div("batch", nb, shard.axis_batch, pb),)
            lm = div("M", spec.m, shard.axis_m, pm)
        else:
            lm = div("M", eff_m, shard.axis_m, pm)
        lk, ln = spec.k, div("N", spec.n, shard.axis_n, pn)
        bytes_moved, phases = 0, 0
    elif sched in ("allgather_a", "allgather_a_overlap"):
        if not isinstance(shard.axis_m, str):
            raise PlanValidationError(
                f"schedule {sched!r} needs a single mesh axis on M"
                f" (axis_m={shard.axis_m!r}) — the gather is a 1D ring"
            )
        if pk > 1 or pn > 1:
            raise PlanValidationError(
                f"schedule {sched!r} shards only M; drop axis_k/axis_n"
            )
        lm = div("M", eff_m, shard.axis_m, pm)
        lk, ln = spec.k, spec.n
        if sched == "allgather_a_overlap":
            if pm < 2:
                raise PlanValidationError(
                    "schedule 'allgather_a_overlap' double-buffers a ring of"
                    f" size >= 2; axis_m={shard.axis_m!r} has size {pm}"
                )
            if spec.n < 2 or spec.n % 2:
                raise PlanValidationError(
                    "schedule 'allgather_a_overlap' splits the local product"
                    f" into two column halves; N={spec.n} must be even"
                )
            ln = spec.n // 2  # per-shard kernel built at the half width
        # Each device computes its (lm, n) result chunk ONCE; the f32 chunks
        # hop the ring pm-1 times (input rotation would re-run the full-K
        # kernel pm times for the same bytes — the old pathology).
        bytes_moved = (pm - 1) * lm * spec.n * 4
        phases = pm - 1
    elif sched in (
        "reduce_scatter_k",
        "reduce_scatter_k_overlap",
        "ring_k",
        "ring_k_overlap",
        "pipeline",
    ):
        if shard.axis_k is None:
            raise PlanValidationError(f"schedule {sched!r} requires axis_k")
        if pm > 1 or pn > 1:
            if shard.schedule == "auto":
                raise PlanValidationError(
                    "no collective schedule combines a K partition with an"
                    " M/N partition; shard K alone (reduce_scatter_k /"
                    " ring_k) or drop axis_k"
                )
            raise PlanValidationError(
                f"schedule {sched!r} shards only K; drop axis_m/axis_n"
            )
        lk = div("K", spec.k, shard.axis_k, pk)
        ln = spec.n
        if sched in ("reduce_scatter_k", "reduce_scatter_k_overlap"):
            lm = div("M", eff_m, shard.axis_k, pk)
            # f32 accumulator row-chunks hop the ring p-1 times
            bytes_moved = (pk - 1) * lm * spec.n * 4
            phases = pk - 1
        elif sched == "pipeline":
            mb = div("M", eff_m, shard.axis_k, pk)
            micro = _pipeline_microbatches(eff_m, pk)
            lm = eff_m // micro  # one microbatch chain per kernel call
            # same total accumulator bytes as reduce_scatter_k, split over
            # micro/pk chains of (pk-1) hops each
            bytes_moved = (pk - 1) * mb * spec.n * 4
            phases = micro - micro // pk  # (micro/pk chains) x (pk-1) hops
        else:  # ring_k / ring_k_overlap
            lm = eff_m
            if sched == "ring_k_overlap":
                if pk < 2:
                    raise PlanValidationError(
                        "schedule 'ring_k_overlap' double-buffers a ring of"
                        f" size >= 2; axis_k={shard.axis_k!r} has size {pk}"
                    )
                if spec.n < 2 or spec.n % 2:
                    raise PlanValidationError(
                        "schedule 'ring_k_overlap' splits the partial into"
                        f" two column halves; N={spec.n} must be even"
                    )
                ln = spec.n // 2  # per-shard kernel built at the half width
            # full f32 accumulator wavefronts hop the ring p-1 times
            bytes_moved = (pk - 1) * eff_m * spec.n * 4
            phases = pk - 1
    else:  # pragma: no cover — ShardSpec.__post_init__ rejects unknown names
        raise PlanValidationError(f"unknown schedule {sched!r}")

    local = dataclasses.replace(
        spec,
        m=lm,
        k=lk,
        n=ln,
        batch=lb if spec.batched_b else (),
        batched_b=spec.batched_b,
        structure="general",
        epilogue=Epilogue(),
        out_dtype="float32",
        shard=None,
    )
    return sched, local, bytes_moved, phases, decision


def _resolve_grouped_sharding(
    spec: GemmSpec,
) -> Tuple[str, GemmSpec, int, int, Optional[Dict[str, Any]]]:
    """The grouped analogue of `_resolve_sharding`: the only meaningful
    partition is the group (expert) dim over `axis_g` — the `expert`
    schedule.  Tokens/sizes/weights reshard at the shard_map boundary (the
    EP all-to-all); there is no in-body collective, so bytes_moved reports
    the boundary resharding cost."""
    shard = spec.shard
    grp = spec.group
    for field in ("axis_m", "axis_k", "axis_n", "axis_batch"):
        if getattr(shard, field) is not None and shard.axis_size(getattr(shard, field)) > 1:
            raise PlanValidationError(
                f"grouped specs shard only the group dim (axis_g);"
                f" drop {field}"
            )
    pg = shard.axis_size(shard.axis_g)
    sched = shard.schedule
    if sched == "auto":
        sched = "expert" if pg > 1 else "replicated"
    if sched not in ("expert", "replicated"):
        raise PlanValidationError(
            f"schedule {sched!r} does not apply to grouped specs; use"
            " 'expert' (group dim over axis_g) or 'replicated'"
        )
    if sched == "replicated" and pg > 1:
        raise PlanValidationError(
            "schedule 'replicated' cannot shard the group dim; use 'expert'"
        )
    if grp.num_groups % pg:
        raise PlanValidationError(
            f"num_groups={grp.num_groups} is not divisible by mesh axis"
            f" {shard.axis_g!r} (size {pg}) required by schedule 'expert'"
            f" on mesh {shard.mesh_axes}"
        )
    if grp.packed and pg > 1:
        raise PlanValidationError(
            "the packed grouped layout has no per-device group rows; schedule"
            " 'expert' needs the capacity layout"
        )
    local_grp = GroupSpec(grp.num_groups // pg, grp.rows_per_group, grp.packed)
    local = dataclasses.replace(
        spec, m=local_grp.rows, group=local_grp, shard=None
    )
    if pg > 1:
        ia = jnp.dtype(spec.dtype_a).itemsize
        io = jnp.dtype(spec.resolved_out_dtype()).itemsize
        # boundary all-to-all: (p-1)/p of the token rows change device on the
        # way in, and again on the way out
        bytes_moved = (pg - 1) * grp.rows * (spec.k * ia + spec.n * io) // pg
        phases = pg - 1
    else:
        bytes_moved, phases = 0, 0
    # EP has one meaningful partition — no candidate set, no decision record
    return ("expert" if pg > 1 else "replicated"), local, bytes_moved, phases, None


def _grouped_sharded_executor(
    spec: GemmSpec, sched: str, mesh: Mesh, local_plan: Plan
) -> Callable:
    """shard_map executor for grouped specs: group-sharded tokens/sizes/
    weights in, group-sharded output rows out, local GroupedPlan in the body."""
    from repro.parallel.sharding import shard_map as _shard_map

    ag = spec.shard.axis_g if sched == "expert" else None
    epi = spec.epilogue

    def body(t_blk, sz_blk, w_blk, *rest):
        it = iter(rest)
        bias_blk = next(it) if epi.bias else None
        res_blk = next(it) if epi.residual else None
        off = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(sz_blk).astype(jnp.int32)]
        )
        return local_plan._fn(t_blk, off, w_blk, bias_blk, res_blk)

    # The epilogue is per-row / per-group (no cross-device reduction), so it
    # shards with its operands: bias (G, N) and residual (rows, group-major)
    # both partition on the group axis — unlike the K-collective schedules,
    # nothing has to move post-collective.
    in_specs = [P(ag, None), P(ag), P(ag, None, None)]
    if epi.bias:
        in_specs.append(P(ag, None))
    if epi.residual:
        in_specs.append(P(ag, None))
    mapped = _shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(ag, None),
        check_vma=False,
    )

    def run(tokens, group_offsets, weights, bias, residual):
        sizes = (group_offsets[1:] - group_offsets[:-1]).astype(jnp.int32)
        args = [tokens, sizes, weights]
        if epi.bias:
            args.append(bias)
        if epi.residual:
            args.append(residual)
        return mapped(*args)

    return jax.jit(run)


def _sharded_executor(
    spec: GemmSpec, sched: str, mesh: Mesh, local_plan: Plan
) -> Callable:
    """The jitted global-operand executor: shard_map(collective ∘ per-shard
    kernel) with batch folding/unfolding around it."""
    from repro.parallel.collectives import (
        matmul_ring_reducescatter,
        ring_allgather_matmul,
        ring_pipeline_matmul,
    )
    from repro.parallel.sharding import shard_map as _shard_map
    from repro.parallel.systolic import ring_systolic_kpass

    shard = spec.shard
    epi = spec.epilogue
    act = epi.activation
    out_dt = jnp.dtype(spec.resolved_out_dtype())
    am, ak, an, ab = shard.axis_m, shard.axis_k, shard.axis_n, shard.axis_batch
    overlap = sched.endswith("_overlap")
    base = sched[: -len("_overlap")] if overlap else sched

    def local_mm(x, y):
        return local_plan._fn(x, y, None, None)

    if spec.batched_b:  # replicated schedule only (validated upstream)
        in_a, in_b = P(ab, am, None), P(ab, None, an)
        in_bias, in_res = P(an), P(ab, am, an)
        out_spec = P(ab, am, an)
    elif sched == "replicated":
        in_a, in_b = P(am, None), P(None, an)
        in_bias, in_res = P(an), P(am, an)
        out_spec = P(am, an)
    elif base == "allgather_a":
        in_a, in_b, in_bias, in_res = P(am, None), P(), P(), P()
        out_spec = P()
    elif base in ("reduce_scatter_k", "pipeline"):
        in_a, in_b, in_bias = P(None, ak), P(ak, None), P()
        in_res = out_spec = P(ak, None)
    else:  # ring_k / ring_k_overlap
        in_a, in_b, in_bias, in_res = P(None, ak), P(ak, None), P(), P()
        out_spec = P()

    if sched == "pipeline":
        micro = _pipeline_microbatches(spec.eff_m, shard.axis_size(ak))

    def body(*args):
        a_blk, b_blk, *rest = args
        it = iter(rest)
        bias_blk = next(it) if epi.bias else None
        res_blk = next(it) if epi.residual else None
        if sched == "replicated":
            z = local_plan._fn(a_blk, b_blk, None, None)
        elif base == "allgather_a":
            z = ring_allgather_matmul(
                a_blk, b_blk, am, matmul=local_mm, overlap=overlap
            )
        elif base == "reduce_scatter_k":
            z = matmul_ring_reducescatter(
                a_blk, b_blk, ak, matmul=local_mm, overlap=overlap
            )
        elif sched == "pipeline":
            z = ring_pipeline_matmul(
                a_blk, b_blk, ak, microbatches=micro, matmul=local_mm
            )
        else:
            z = ring_systolic_kpass(
                a_blk, b_blk, axis=ak, matmul=local_mm, overlap=overlap
            )
        return apply_epilogue(z, bias_blk, act, res_blk).astype(out_dt)

    in_specs = [in_a, in_b]
    if epi.bias:
        in_specs.append(in_bias)
    if epi.residual:
        in_specs.append(in_res)
    # Ring outputs are replicated by construction, not by a verifiable
    # per-op replication rule — declare specs, skip the rep check.
    mapped = _shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_spec,
        check_vma=False,
    )
    eff_m = spec.eff_m

    def run(a, b, bias, residual):
        if spec.batched_b:
            nb = math.prod(spec.batch)
            af = a.reshape(nb, spec.m, spec.k)
            bf = b.reshape(nb, spec.k, spec.n)
            resf = None if residual is None else residual.reshape(nb, spec.m, spec.n)
            args = [af, bf]
        else:
            # Leading batch dims of `a` fold into M, exactly as in the
            # unsharded pallas path — the M partition shards eff_m.
            af = a.reshape(eff_m, spec.k)
            resf = None if residual is None else residual.reshape(eff_m, spec.n)
            args = [af, b]
        if epi.bias:
            args.append(bias)
        if epi.residual:
            args.append(resf)
        out = mapped(*args)
        return out.reshape(*spec.batch, spec.m, spec.n) if spec.batch else out

    return jax.jit(run)


def _build_sharded_plan(spec: GemmSpec, be: _Backend, mesh: Mesh) -> ShardedPlan:
    """ONE planner: resolve the collective schedule, build the per-shard Plan
    through the ordinary `plan()` path (cached, autotuned at the LOCAL shape),
    and wrap it in the shard_map executor."""
    shard = spec.shard
    live = tuple((str(n), int(s)) for n, s in mesh.shape.items())
    if live != shard.mesh_axes:
        raise PlanValidationError(
            f"ShardSpec was built for mesh axes {shard.mesh_axes} but"
            f" plan() got a mesh with {live}; rebuild it with"
            f" ShardSpec.from_mesh(mesh, ...)"
        )
    sched, local_spec, bytes_moved, phases, sched_decision = _resolve_sharding(spec)
    local_plan = plan(local_spec, backend=be.name)
    # Per-device kernel calls: the reduce-scatter family runs the local
    # kernel once per ring step (p = phases + 1); pipeline runs it once per
    # microbatch chain step; the column-half overlap variants run the
    # half-width kernel twice; allgather_a (result-gather), replicated,
    # ring_k and expert invoke it exactly once.
    if sched in ("reduce_scatter_k", "reduce_scatter_k_overlap"):
        invocations = phases + 1
    elif sched == "pipeline":
        invocations = _pipeline_microbatches(
            spec.eff_m, shard.axis_size(shard.axis_k)
        )
    elif sched in ("allgather_a_overlap", "ring_k_overlap"):
        invocations = 2
    else:
        invocations = 1
    cls = ShardedGroupedPlan if spec.group is not None else ShardedPlan
    p = cls(
        spec=spec,
        backend=be.name,
        capabilities=be.caps,
        blocks=local_plan.blocks,
        out_dtype=spec.resolved_out_dtype(),
        interpret=not _on_tpu(),
        flops=spec.flops(),
        vmem_bytes=local_plan.vmem_bytes,
        sigma_table=None,
        stagger_table=local_plan.stagger_table,
        mesh=mesh,
        schedule=sched,
        local=local_plan,
        bytes_moved=bytes_moved,
        collective_phases=phases,
        kernel_invocations=invocations,
    )
    if sched_decision is not None:
        p.decision = {"schedule": sched_decision}
    executor = (
        _grouped_sharded_executor if spec.group is not None else _sharded_executor
    )
    p._fn = executor(spec, sched, mesh, local_plan)
    return p


def execute_async(items) -> List[jax.Array]:
    """Dispatch independent plan executions back-to-back, sync ONCE at the end.

    `items` is an iterable of `(plan, args)` pairs, `args` the positional
    operand tuple for that plan (`(a, b)`, optionally with bias/residual).
    All executions are enqueued before anything blocks, so the device (and
    XLA's async dispatch queue) overlaps them host-side; the return is the
    list of ready outputs in input order.  This is the batch form of
    `Plan.dispatch` — use it when a serve tick or benchmark has several
    independent GEMMs and per-call `block_until_ready` would serialize them.
    """
    handles = [p.dispatch(*args) for p, args in items]
    outs = [h.out for h in handles]
    jax.block_until_ready(outs)
    return outs


def clear_plan_cache() -> None:
    """Test hook: drop all cached plans and reset the hit/miss counters."""
    _PLAN_CACHE.clear()
    _PLAN_STATS.update(hits=0, misses=0)


def plan_cache_info() -> Dict[str, Any]:
    """Cache telemetry: one entry per (spec, backend, platform, mesh) ever
    planned — the same spec under two different meshes is two entries."""
    return {
        "size": len(_PLAN_CACHE),
        "hits": _PLAN_STATS["hits"],
        "misses": _PLAN_STATS["misses"],
        "plans": [p.describe() for p in _PLAN_CACHE.values()],
    }
