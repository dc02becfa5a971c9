"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run forces 512 host
devices via XLA_FLAGS before first jax init, while tests/benches must see 1.
"""

from __future__ import annotations

import math
import os

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "forced_device_env", "PROD_TP"]

PROD_TP = 16  # 'model' axis size on the production meshes


def _make_mesh(shape, axes):
    """jax.make_mesh with Auto axes: sharding constraints inside jit (the
    model's `ShardCtx.c`) are refused on the Explicit axes JAX defaults to."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips multi-pod.

    Axes: ('data', 'model') single-pod, ('pod', 'data', 'model') multi-pod
    ('pod' composes with 'data' as outer DP; PP over 'pod' is available via
    parallel/pipeline.py but the graded dry-runs use DP x TP — DESIGN.md §4).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def forced_device_env(n_devices: int, *, pythonpath=("src",)) -> dict:
    """Environment for a subprocess that must see `n_devices` virtual CPU
    devices (multi-device tests/benches re-exec because the parent process
    already initialized jax at its own device count).

    Pins the child to the CPU backend: on a TPU host the parent may already
    hold the chip, and a child that reaches for it fails or hangs.
    Replaces any existing --xla_force_host_platform_device_count in XLA_FLAGS
    (appending would leave duplicate flags with parser-order semantics) and
    prepends `pythonpath` entries while keeping the inherited PYTHONPATH.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n_devices}"]
    )
    inherited = env.get("PYTHONPATH")
    env["PYTHONPATH"] = os.pathsep.join(
        list(pythonpath) + ([inherited] if inherited else [])
    )
    return env


def make_local_mesh(shape, axes):
    """Small mesh over whatever devices exist (tests / CPU examples).

    Validates the request against the live runtime up front —
    `jax.make_mesh` otherwise fails with an opaque reshape/assignment error
    when the shape doesn't fit the device count.
    """
    shape, axes = tuple(shape), tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(
            f"mesh shape {shape} and axis names {axes} must have equal rank"
        )
    need, have = math.prod(shape), jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh shape {shape} ({'x'.join(map(str, shape))} = {need} devices)"
            f" exceeds the {have} available {jax.default_backend()} device(s);"
            f" for CPU tests set"
            f" XLA_FLAGS=--xla_force_host_platform_device_count={need}"
            f" before the first jax call"
        )
    return _make_mesh(shape, axes)
