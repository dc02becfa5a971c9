"""Weights made from the seed, on the device, in one jitted call.

The values are the benchmark's: the program is handed them, and the
reference makes the same ones again from the same seed.  Only the tree's
layout (leaf paths, shapes, dtypes) is taken from the program, through
`model.abstract_params()`.  Matrices are normal with the scale of a trained
model's initialisation; norm gains are 1 + N(0, 0.1) so that a gain the
program dropped would show.
"""

from __future__ import annotations

import zlib
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["seed_key", "make_params", "leaf_name"]

_NORMS = ("ln1", "ln2", "final_norm")


def seed_key(seed: int, salt: str = "") -> jax.Array:
    """A key from any non-negative seed, more than 32 bits included."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, zlib.crc32(salt.encode()))


def leaf_name(path) -> str:
    return ".".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _init(name: str, shape, dtype, key, cfg: dict):
    last = name.split(".")[-1]
    if last in _NORMS:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    layers = cfg["num_hidden_layers"]
    # Output projections are scaled down with depth, as GPT-2 style inits do,
    # so the residual stream keeps its scale through the layers.
    out = name.endswith("attn.wo") or name.endswith("mlp.wo")
    scale = 0.02 / max(1.0, (2 * layers) ** 0.5) if out else 0.02
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_params(abstract: Any, cfg: dict, seed: int, shardings: Any = None) -> Any:
    """Arrays shaped like `abstract` (a ShapeDtypeStruct tree), from `seed`."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [leaf_name(p) for p, _ in leaves]

    def build(key):
        out = []
        for name, (_, s) in zip(names, leaves):
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            out.append(_init(name, s.shape, s.dtype, k, cfg))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build, out_shardings=shardings) if shardings is not None else jax.jit(build)
    return fn(seed_key(seed, "weights"))
