"""Cycle-accurate mesh/standard array simulators vs the paper's step counts.

Paper claims validated here:
  * mesh array multiplies n x n in 2n-1 steps (Fig. 1: n=4 -> 7 steps),
  * standard array takes 3n-2 steps (Fig. 2: n=3 -> 7 steps),
  * mesh output is C = AB in the scrambled arrangement sigma_n,
  * node (i, j)'s accumulator is FROZEN after its completion step
    (completion_times is exact, not an upper bound).
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.mesh_array import (
    mesh_completion_times,
    mesh_matmul_reference,
    mesh_start_times,
    simulate_mesh,
    simulate_standard,
    standard_completion_times,
)
from repro.core.scramble import unscramble


def _rand(n, rng, dtype=np.float32):
    return jnp.asarray(rng.normal(size=(n, n)).astype(dtype))


@pytest.mark.parametrize("n", [2, 3, 4, 5, 7, 8, 16])
def test_mesh_steps_and_correctness(n, rng):
    a, b = _rand(n, rng), _rand(n, rng)
    res = simulate_mesh(a, b)
    assert res.steps == 2 * n - 1  # the paper's headline claim
    np.testing.assert_allclose(
        np.asarray(unscramble(res.output)), np.asarray(a @ b), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("n", [2, 3, 4, 5, 8])
def test_standard_steps_and_correctness(n, rng):
    a, b = _rand(n, rng), _rand(n, rng)
    res = simulate_standard(a, b)
    assert res.steps == 3 * n - 2
    np.testing.assert_allclose(np.asarray(res.output), np.asarray(a @ b), rtol=1e-4, atol=1e-4)


def test_fig1_fig2_step_counts():
    """Paper's intro: mesh on 4x4 takes 7 steps = standard on 3x3."""
    assert simulate_mesh(jnp.eye(4), jnp.eye(4)).steps == 7
    assert simulate_standard(jnp.eye(3), jnp.eye(3)).steps == 7


@pytest.mark.parametrize("model", ["antidiagonal", "corner"])
def test_both_start_models_give_2n_minus_1(model, rng):
    for n in (3, 4, 6):
        a, b = _rand(n, rng), _rand(n, rng)
        res = simulate_mesh(a, b, model=model)
        assert int(mesh_completion_times(n, model).max()) == 2 * n - 1
        np.testing.assert_allclose(
            np.asarray(unscramble(res.output)), np.asarray(a @ b), rtol=1e-4, atol=1e-4
        )


def test_node_accumulators_freeze_at_completion(rng):
    """History check: each node's value is final at its completion step and
    every node performs exactly n MACs — the paper's Fig. 3 node semantics."""
    n = 5
    a, b = _rand(n, rng), _rand(n, rng)
    res = simulate_mesh(a, b, record_history=True)
    hist = np.asarray(res.history)  # (steps, n, n)
    comp = res.completion_times  # 1-indexed steps
    final = np.asarray(res.output)
    for i in range(n):
        for j in range(n):
            t = comp[i, j]
            np.testing.assert_allclose(hist[t - 1, i, j], final[i, j], rtol=1e-5)
            if t < res.steps:
                # frozen afterwards
                np.testing.assert_allclose(hist[-1, i, j], final[i, j], rtol=1e-5)


def test_start_times_structure():
    n = 6
    st_anti = mesh_start_times(n, "antidiagonal")
    st_corner = mesh_start_times(n, "corner")
    std = standard_completion_times(n)
    # no-padding feeding: node (1,1) starts at step 1 in both mesh models
    assert st_anti[0, 0] == 1 and st_corner[0, 0] == 1
    # standard array's last node finishes at 3n-2
    assert std.max() == 3 * n - 2
    # mesh completion horizon is 2n-1 under both models
    assert (st_anti + n - 1).max() == 2 * n - 1
    assert (st_corner + n - 1).max() == 2 * n - 1


@given(st.integers(min_value=2, max_value=10))
@settings(max_examples=10, deadline=None)
def test_reference_equals_simulator(n):
    rng = np.random.default_rng(n)
    a = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(mesh_matmul_reference(a, b)),
        np.asarray(simulate_mesh(a, b).output),
        rtol=1e-4,
        atol=1e-4,
    )


def test_reference_batched(rng):
    a = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    out = mesh_matmul_reference(a, b)
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(mesh_matmul_reference(a[i], b[i])), rtol=1e-5
        )


def test_integer_exactness():
    """Integer inputs: simulator must be bit-exact vs the gather reference."""
    n = 6
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.integers(-5, 5, size=(n, n)).astype(np.int32))
    b = jnp.asarray(rng.integers(-5, 5, size=(n, n)).astype(np.int32))
    res = simulate_mesh(a, b)
    assert np.array_equal(np.asarray(unscramble(res.output)), np.asarray(a @ b))
