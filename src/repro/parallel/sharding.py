"""Logical-axis sharding rules (MaxText-style) for DP / TP / EP / SP.

Models annotate every parameter and activation with *logical* axis names;
this module maps them to physical mesh axes via a rules table, producing
`PartitionSpec`s / `NamedSharding`s consumed by pjit in `launch/dryrun.py`
and `launch/train.py`.

Physical mesh axes (launch/mesh.py):
    single pod:  ('data', 'model')            16 x 16
    multi-pod:   ('pod', 'data', 'model')     2 x 16 x 16  ('pod' = outer DP)

Default logical->physical rules:
    batch    -> ('pod', 'data')     pure DP over pod+data
    seq      -> None                (SP rule available for long-context)
    embed    -> None                activations replicated over 'model'
    heads    -> 'model'             Megatron TP: attention heads
    kv_heads -> 'model'             GQA KV heads (capped by kv count)
    mlp      -> 'model'             Megatron TP: FFN hidden
    experts  -> 'model'             EP: MoE expert dim
    expert_rows -> 'model'          EP: grouped-GEMM dispatch-buffer rows
    vocab    -> 'model'             vocab-sharded embedding + logits
    state    -> None                SSM recurrent state (small)
    kv_seq   -> None                KV-cache length ('data' under SP rules)
    stage    -> 'stage'             PP (only present on PP meshes)
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Mapping, Optional, Sequence, Tuple

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules",
    "DEFAULT_RULES",
    "SP_DECODE_RULES",
    "logical_to_physical",
    "named_sharding",
    "shard_map",
    "tree_shardings",
    "constrain",
]

Rules = Mapping[str, Any]

_DEFAULT: dict = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_sp": None,  # Megatron-SP: layer-boundary activation carriers
    "seq_attn": None,  # context parallelism: q/out seq dim in chunked attention
    # (set to 'model' when num_heads %% TP != 0 — phi3 40H, qwen2 28H)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    # EP: the grouped-GEMM capacity buffer's row dim is expert-major
    # (models/moe.py), so sharding it over 'model' co-locates each expert's
    # token rows with its weight slab — pjit's resharding of the dispatch
    # buffer into this layout IS the EP all-to-all (DESIGN.md §10).
    "expert_rows": "model",
    "vocab": "model",
    "state": None,
    "kv_seq": None,
    "kv_batch": ("pod", "data"),
    "layers": None,
    "stage": "stage",
    "frames": None,
    "patches": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Immutable logical->physical table; `replace` builds variants."""

    table: Tuple[Tuple[str, Any], ...]

    @classmethod
    def make(cls, overrides: Optional[Rules] = None) -> "ShardingRules":
        merged = dict(_DEFAULT)
        if overrides:
            merged.update(overrides)
        return cls(tuple(sorted(merged.items())))

    def get(self, logical: Optional[str]):
        if logical is None:
            return None
        d = dict(self.table)
        if logical not in d:
            raise KeyError(f"unknown logical axis {logical!r}")
        return d[logical]

    def replace(self, **overrides) -> "ShardingRules":
        d = dict(self.table)
        d.update(overrides)
        return ShardingRules(tuple(sorted(d.items())))


DEFAULT_RULES = ShardingRules.make()

# FSDP parameter rules: the 'embed' dim of every weight is additionally
# sharded over the DP axes, so params + optimizer state shard across the FULL
# mesh (TP x DP).  Activations keep DEFAULT_RULES — their 'embed' maps through
# this table too, but the duplicate-axis dedup in logical_to_physical drops it
# wherever 'batch' already owns the data axes.  XLA inserts the per-layer
# weight all-gathers (ZeRO-3/FSDP streaming), which overlap the scanned
# layer compute.  Required: mistral-large-123b params+opt = 1.2 TB.
PARAM_RULES = DEFAULT_RULES.replace(embed=("pod", "data"))

# Megatron sequence parallelism for training: remat-saved layer-boundary
# carriers are stored seq-sharded over 'model' (16x smaller residency).
TRAIN_RULES = DEFAULT_RULES.replace(seq_sp="model")

# Sequence-parallel decode rules: long-context KV caches / recurrent streams
# are sharded along their length over 'data' (batch is tiny in long_500k).
SP_DECODE_RULES = DEFAULT_RULES.replace(
    kv_seq=("pod", "data"), kv_batch=None, batch=None
)


def _axes_on_mesh(mesh: Mesh, axes):
    """Drop rule axes the mesh doesn't have (e.g. 'pod' on single-pod)."""
    if axes is None:
        return None
    if isinstance(axes, str):
        return axes if axes in mesh.shape else None
    present = tuple(a for a in axes if a in mesh.shape)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def logical_to_physical(
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
) -> P:
    """('batch', 'seq', 'embed') -> PartitionSpec(('pod','data'), None, None)."""
    phys = [_axes_on_mesh(mesh, rules.get(ax)) for ax in logical_axes]
    # A physical axis may appear at most once in a spec; later wins -> None.
    seen = set()
    cleaned = []
    for a in phys:
        names = (a,) if isinstance(a, str) else (a or ())
        if any(n in seen for n in names):
            cleaned.append(None)
            continue
        seen.update(names)
        cleaned.append(a)
    return P(*cleaned)


def named_sharding(
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
    shape: Optional[Sequence[int]] = None,
) -> NamedSharding:
    spec = logical_to_physical(logical_axes, mesh, rules)
    if shape is not None:
        spec = _drop_indivisible(spec, shape, mesh)
    return NamedSharding(mesh, spec)


def _axes_size(mesh: Mesh, a) -> int:
    if a is None:
        return 1
    if isinstance(a, str):
        return mesh.shape[a]
    n = 1
    for x in a:
        n *= mesh.shape[x]
    return n


# (spec, shape, mesh-shape) triples already warned about — the fallback is
# per-layer-per-step hot-path code, so each distinct drop warns exactly once.
_WARNED_DROPS: set = set()


def _drop_indivisible(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Replicate any dim whose size doesn't divide by its mapped axes product.

    pjit *arguments* require exact divisibility (XLA pads only internal ops);
    odd published dims (vocab=49155, heads=40 vs TP=16) fall back to
    replicated on that dim — recorded in EXPERIMENTS.md §Dry-run notes.
    The drop is no longer silent: each distinct (spec, shape, mesh) warns
    once, so a mis-sized dim that quietly replicates a 16-way-sharded tensor
    shows up in logs instead of only in the memory profile.
    """
    out, dropped = [], []
    for dim, a in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if a is None or dim % _axes_size(mesh, a) == 0:
            out.append(a)
        else:
            out.append(None)
            dropped.append((dim, a))
    if dropped:
        key = (tuple(spec), tuple(shape), tuple(mesh.shape.items()))
        if key not in _WARNED_DROPS:
            _WARNED_DROPS.add(key)
            detail = ", ".join(
                f"dim {dim} % {_axes_size(mesh, a)} != 0 (axes {a!r})"
                for dim, a in dropped
            )
            warnings.warn(
                f"sharding {spec} of shape {tuple(shape)} fell back to"
                f" replicated on indivisible dim(s): {detail}",
                UserWarning,
                stacklevel=3,
            )
    return P(*out)


def tree_shardings(
    logical_tree,
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
    aval_tree=None,
):
    """Map a pytree of logical-axis tuples to a matching tree of NamedShardings.

    Leaves of `logical_tree` are tuples like ('embed', 'mlp') (or None for
    fully-replicated scalars/vectors).  With `aval_tree` (matching tree of
    arrays/ShapeDtypeStructs) non-divisible dims are dropped to replicated —
    required for pjit argument shardings.
    """
    is_leaf = lambda x: x is None or isinstance(x, tuple)
    if aval_tree is None:
        one = lambda axes: (
            NamedSharding(mesh, P()) if axes is None else named_sharding(axes, mesh, rules)
        )
        return jax.tree.map(one, logical_tree, is_leaf=is_leaf)

    def one_shaped(axes, aval):
        if axes is None:
            return NamedSharding(mesh, P())
        return named_sharding(axes, mesh, rules, shape=aval.shape)

    return jax.tree.map(one_shaped, logical_tree, aval_tree, is_leaf=is_leaf)


def constrain(x: jax.Array, logical_axes, mesh: Mesh, rules: ShardingRules = DEFAULT_RULES):
    """with_sharding_constraint by logical names, divisibility-safe."""
    return jax.lax.with_sharding_constraint(
        x, named_sharding(logical_axes, mesh, rules, shape=x.shape)
    )
