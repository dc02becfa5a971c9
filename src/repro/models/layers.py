"""Shared building blocks: param specs, norms, RoPE, embeddings, MLPs.

Single-source-of-truth parameter system: each model family defines a
`param_specs(cfg)` tree whose leaves are `PSpec(shape, logical_axes, scale,
dtype)`.  From that one tree we derive
  * `init_params`      — real arrays (smoke tests / examples / training),
  * `abstract_params`  — ShapeDtypeStructs (dry-run lowering, no allocation),
  * `logical_axes`     — the sharding tree consumed by parallel/sharding.py.

All GEMMs go through the plan/execute API (`repro.kernels.api`): `gemm`
builds a typed GemmSpec, `api.plan` resolves the backend against declared
capabilities ONCE per logical shape (cfg.use_mesh_kernel selects the Pallas
mesh kernel), and the cached plan executes per call; under pjit the XLA
backend is used and sharding constraints carry the TP layout.  GSPMD cannot
partition a Pallas (Mosaic) kernel, so under a device mesh the kernel paths
run data-parallel through shard_map instead (`ShardCtx.batch_axes`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import api as _api

__all__ = [
    "PSpec",
    "init_params",
    "abstract_params",
    "logical_axes_tree",
    "ShardCtx",
    "dense",
    "rmsnorm",
    "RotaryTable",
    "apply_rope",
    "softmax_xent",
    "gemm",
    "grouped_gemm",
]


@dataclasses.dataclass(frozen=True)
class PSpec:
    """Declarative parameter: shape + logical sharding axes + init scale."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    scale: float = 0.02
    dtype: Any = None  # filled from cfg.param_dtype at materialization
    init: str = "normal"  # normal | zeros | ones

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")


def _is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def init_params(key: jax.Array, specs, dtype) -> Any:
    """Materialize a PSpec tree into real arrays (deterministic per-path keys)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_pspec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, s in zip(keys, leaves):
        dt = s.dtype or dtype
        if s.init == "zeros":
            out.append(jnp.zeros(s.shape, dt))
        elif s.init == "ones":
            out.append(jnp.ones(s.shape, dt))
        else:
            out.append((jax.random.normal(k, s.shape, jnp.float32) * s.scale).astype(dt))
    return treedef.unflatten(out)


def abstract_params(specs, dtype) -> Any:
    """ShapeDtypeStruct tree — dry-run lowering without any allocation."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or dtype),
        specs,
        is_leaf=_is_pspec,
    )


def logical_axes_tree(specs) -> Any:
    """Matching tree of logical-axis tuples for parallel/sharding.py."""
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=_is_pspec)


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Threading (mesh, rules) through model code; None mesh = no constraints."""

    mesh: Any = None
    rules: Any = None

    def c(self, x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
        if self.mesh is None:
            return x
        from repro.parallel.sharding import DEFAULT_RULES, named_sharding

        rules = self.rules or DEFAULT_RULES
        return jax.lax.with_sharding_constraint(
            x, named_sharding(tuple(axes), self.mesh, rules, shape=x.shape)
        )

    def batch_axes(self, rows: int):
        """The mesh axes the rules give 'batch' when they divide `rows` (a
        leading dim), else None: where a Pallas kernel's rows go under
        shard_map."""
        from repro.parallel.sharding import DEFAULT_RULES, _axes_on_mesh

        axes = _axes_on_mesh(self.mesh, (self.rules or DEFAULT_RULES).get("batch"))
        names = (axes,) if isinstance(axes, str) else tuple(axes or ())
        size = math.prod(self.mesh.shape[a] for a in names)
        return axes if names and rows % size == 0 else None


NO_SHARD = ShardCtx()


def padded_vocab(cfg) -> int:
    """Embedding/lm_head row count, padded so the vocab dim divides the TP
    axis (cfg.vocab_pad_multiple; 0 = exact).  Published vocabs like 49155
    (granite) otherwise force the unembed GEMM + logits to REPLICATE over
    'model' — the probe showed that costs ~16x the sharded unembed
    (EXPERIMENTS.md §Perf).  Padded logits are masked out of loss/argmax."""
    m = getattr(cfg, "vocab_pad_multiple", 0)
    if not m:
        return cfg.vocab_size
    return ((cfg.vocab_size + m - 1) // m) * m


def gemm(
    x: jax.Array,
    w: jax.Array,
    cfg,
    *,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    residual: Optional[jax.Array] = None,
    mesh: Any = None,
    shard: Any = None,
    ctx: Optional[ShardCtx] = None,
) -> jax.Array:
    """Config-routed GEMM via plan/execute: XLA dot under pjit, Pallas mesh
    kernel if selected.

    The epilogue (y = act(xW + bias) + residual) rides along: fused into the
    kernel's final-k flush on the Pallas path (cfg.fused_dense_epilogue, the
    A/B lever), applied as plain jnp ops otherwise — one call site, identical
    semantics either way.  Block shapes come from cfg.mesh_block_m/n/k when
    set (> 0); otherwise `kernels/autotune.py` resolves them at plan time.
    Plans are cached process-wide per (spec, backend, mesh) triple, so every
    retrace/request with the same logical shape reuses the same executable.

    With `shard` (a `kernels.api.ShardSpec`) and its live device `mesh`, the
    plan is a ShardedPlan: the same per-shard kernel lowered through
    shard_map with the ShardSpec's collective schedule — operands/results
    stay global arrays, so call sites do not change shape-wise.  A `ctx`
    carrying a mesh gives the Pallas path that ShardSpec itself: rows over
    the batch axes, weights whole (GSPMD cannot partition the kernel).
    """
    backend = "pallas_mesh" if getattr(cfg, "use_mesh_kernel", False) else "xla"
    if shard is None and backend != "xla" and ctx is not None and ctx.mesh is not None:
        mesh = ctx.mesh
        shard = _api.ShardSpec.from_mesh(
            mesh, m=ctx.batch_axes(x.shape[0]), schedule="replicated"
        )
    blocks = (
        getattr(cfg, "mesh_block_m", 0) or None,
        getattr(cfg, "mesh_block_n", 0) or None,
        getattr(cfg, "mesh_block_k", 0) or None,
    )
    if backend != "xla" and not getattr(cfg, "fused_dense_epilogue", True):
        spec = _api.GemmSpec.from_operands(
            x, w, out_dtype=jnp.float32, blocks=blocks, shard=shard
        )
        z = _api.plan(spec, backend=backend, mesh=mesh)(x, w)
        return _api.apply_epilogue(z, bias, activation, residual).astype(x.dtype)
    spec = _api.GemmSpec.from_operands(
        x,
        w,
        epilogue=_api.Epilogue(
            bias=bias is not None,
            activation=activation,
            residual=residual is not None,
        ),
        out_dtype=x.dtype,
        blocks=blocks,
        shard=shard,
    )
    return _api.plan(spec, backend=backend, mesh=mesh)(x, w, bias=bias, residual=residual)


def grouped_gemm(
    tokens: jax.Array,         # (num_groups * rows_per_group, K), group-major
    group_offsets: jax.Array,  # (num_groups + 1,) cumulative valid-row counts
    weights: jax.Array,        # (num_groups, K, N) stacked per-group slabs
    cfg,
    *,
    out_dtype=None,
    mesh: Any = None,
    shard: Any = None,
) -> jax.Array:
    """Config-routed grouped (ragged-batch) GEMM via plan/execute.

    The MoE expert path: row blocks of the capacity-layout `tokens` buffer
    multiply their group's (K, N) weight slab in ONE kernel (the Pallas
    ragged mesh kernel when cfg.use_mesh_kernel, a segment-masked einsum on
    XLA), with rows past each group's size coming back zero.  Plans are
    cached per logical group shape exactly like `gemm` — one autotune, one
    executable, every layer/step reuses it.  With `shard` (a ShardSpec
    carrying axis_g) and the live `mesh`, the plan lowers through the
    `expert` collective schedule (EP).
    """
    backend = "pallas_mesh" if getattr(cfg, "use_mesh_kernel", False) else "xla"
    num_groups, kd, n = weights.shape
    rows = tokens.shape[0]
    blocks = (
        getattr(cfg, "mesh_block_m", 0) or None,
        getattr(cfg, "mesh_block_n", 0) or None,
        getattr(cfg, "mesh_block_k", 0) or None,
    )
    spec = _api.GemmSpec.for_groups(
        _api.GroupSpec(num_groups, rows // num_groups),
        k=kd,
        n=n,
        dtype_a=tokens.dtype,
        dtype_b=weights.dtype,
        out_dtype=out_dtype or tokens.dtype,
        blocks=blocks,
        shard=shard,
    )
    return _api.plan(spec, backend=backend, mesh=mesh)(tokens, group_offsets, weights)


def dense(
    x: jax.Array,
    w: jax.Array,
    cfg,
    b: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    residual: Optional[jax.Array] = None,
    mesh: Any = None,
    shard: Any = None,
    ctx: Optional[ShardCtx] = None,
) -> jax.Array:
    """Dense projection with the fused epilogue: one kernel on the mesh path."""
    return gemm(
        x, w, cfg, bias=b, activation=activation, residual=residual,
        mesh=mesh, shard=shard, ctx=ctx,
    )


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma.astype(x.dtype)


class RotaryTable:
    """Precomputed RoPE angle table; `gather(pos)` works for any position array."""

    def __init__(self, head_dim: int, theta: float, max_len: int):
        self.head_dim = head_dim
        inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
        self.inv_freq = jnp.asarray(inv, jnp.float32)
        self.max_len = max_len

    def angles(self, positions: jax.Array) -> jax.Array:
        # positions: (...,) int -> (..., head_dim/2) f32 angles
        return positions[..., None].astype(jnp.float32) * self.inv_freq


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, hd); positions: (B, T) or (T,).  Rotate pairs (even, odd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * inv  # (B, T, hd/2)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def softmax_xent(
    logits: jax.Array, labels: jax.Array, *, z_loss: float = 0.0
) -> Tuple[jax.Array, jax.Array]:
    """Stable mean token cross-entropy (+optional z-loss).  Returns (loss, acc)."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    loss = jnp.mean(nll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(lse**2)
    acc = jnp.mean((jnp.argmax(lf, axis=-1) == labels).astype(jnp.float32))
    return loss, acc
