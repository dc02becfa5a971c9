"""The paper's symmetry claims + the symmetric-product early readout."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.mesh_array import simulate_mesh
from repro.core.scramble import sigma_table
from repro.core.symmetries import (
    check_antidiagonal_structure,
    check_mirror_rows,
    check_row1_diagonal,
    general_readout_steps,
    mirror_cell,
    paper_symmetric_bound,
    symmetric_readout_schedule,
    symmetric_readout_steps,
)


@pytest.mark.parametrize("n", list(range(2, 20)))
def test_row1_carries_diagonal(n):
    assert check_row1_diagonal(n)


@pytest.mark.parametrize("n", list(range(2, 20)))
def test_mirror_rows(n):
    """Rows r and n+2-r are reverse+transpose images (paper's mirror rule);
    covers the even-n middle-row self-symmetry as the r = n/2+1 case."""
    assert check_mirror_rows(n)


@pytest.mark.parametrize("n", list(range(2, 20)))
def test_antidiagonal_fixed_subscript(n):
    assert check_antidiagonal_structure(n)


def test_even_middle_row_self_symmetry():
    """Paper: 'for even n the middle row (n/2+1) has self symmetry'."""
    for n in (4, 6, 8, 10):
        tab = sigma_table(n)
        mid = n // 2  # 0-indexed row n/2+1
        row = tab[mid]
        for j in range(n):
            p, q = row[j]
            mp, mq = row[n - 1 - j]
            assert (p, q) == (mq, mp)


def test_paper_6_to_7_transition_new_cells():
    """The paper derives the 7x7 table from 6x6 'by inspection'; only the
    anti-diagonals d = 8 (length 7) cells are genuinely new.  Check the new
    bold values follow the alternating fixed-subscript + zig-zag rule."""
    tab = sigma_table(7)
    d = 8  # main anti-diagonal, m = 7, fixed value = 7
    cells = [(i, d - i) for i in range(1, 8)]
    got = [tab[i - 1][j - 1] for i, j in cells]
    # d even -> first subscript fixed at 7; zig-zag 7,5,3,1,2,4,6 on the other
    assert got == [(7, 7), (7, 5), (7, 3), (7, 1), (7, 2), (7, 4), (7, 6)]


def test_mirror_cell_involution():
    n = 9
    for i in range(2, n + 1):
        for j in range(1, n + 1):
            mi, mj = mirror_cell(n, i, j)
            assert mirror_cell(n, mi, mj) == (i, j)


# --- symmetric-product early readout ----------------------------------------


@pytest.mark.parametrize("n", list(range(2, 33)))
def test_symmetric_readout_within_paper_bound(n):
    """Paper: all significant values by <= n + 1 + n/2 steps (vs 2n-1)."""
    steps = symmetric_readout_steps(n)
    assert steps <= paper_symmetric_bound(n)
    assert steps <= general_readout_steps(n) == 2 * n - 1
    if n >= 4:  # strict saving kicks in
        assert steps < 2 * n - 1


def test_symmetric_readout_values_correct(rng):
    """Reading c_qp from the mirror cell at its (earlier) completion step
    gives the right value when C is symmetric (Gram product A Aᵀ)."""
    n = 8
    a = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    gram_b = a.T
    res = simulate_mesh(a, gram_b, record_history=True)
    hist = np.asarray(res.history)
    c = np.asarray(a @ gram_b)
    sched = symmetric_readout_schedule(n)
    horizon = symmetric_readout_steps(n)
    for (p, q), ((i, j), t) in sched.items():
        assert t <= horizon
        np.testing.assert_allclose(hist[t - 1, i - 1, j - 1], c[p - 1, q - 1], rtol=1e-4, atol=1e-4)


def test_early_readout_fails_for_general_products(rng):
    """Sanity: the early readout is a *symmetric-product* property — for a
    general product the mirror cell holds c_qp != c_pq."""
    n = 6
    a = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    res = simulate_mesh(a, b, record_history=True)
    hist = np.asarray(res.history)
    c = np.asarray(a @ b)
    sched = symmetric_readout_schedule(n)
    mismatched = 0
    for (p, q), ((i, j), t) in sched.items():
        if not np.allclose(hist[t - 1, i - 1, j - 1], c[p - 1, q - 1], rtol=1e-3):
            mismatched += 1
    assert mismatched > 0


@given(st.integers(min_value=2, max_value=64))
@settings(max_examples=20, deadline=None)
def test_readout_steps_closed_form(n):
    """Empirical law recorded in DESIGN.md: readout horizon == floor(3n/2)
    for n >= 2 under the anti-diagonal start model."""
    assert symmetric_readout_steps(n) == (3 * n) // 2
