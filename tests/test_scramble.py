"""The paper's scrambling transformation S: tables, symmetries, cycles, order.

Every table the paper prints (n = 3..7) is transcribed verbatim below and
checked cell-by-cell against the closed-form sigma_n.  The one known typo
(7x7 cell (2,7), printed `76`, forced to `67` by the paper's own mirror rule)
is asserted AS CORRECTED and flagged in DESIGN.md §Paper-fidelity.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import scramble
from repro.core.scramble import (
    apply_scramble,
    apply_scramble_power,
    cycle_decomposition,
    inverse_perm,
    power_perm,
    scramble_order,
    scramble_perm,
    sigma,
    sigma_table,
    unscramble,
)

# --- the paper's printed tables (1-indexed (p, q) written as "pq") ----------

PAPER_TABLES = {
    4: """
    11 22 33 44
    12 31 24 43
    32 14 41 23
    34 42 13 21
    """,
    5: """
    11 22 33 44 55
    12 31 24 53 45
    32 14 51 25 43
    34 52 15 41 23
    54 35 42 13 21
    """,
    6: """
    11 22 33 44 55 66
    12 31 24 53 46 65
    32 14 51 26 63 45
    34 52 16 61 25 43
    54 36 62 15 41 23
    56 64 35 42 13 21
    """,
    # (2,7) corrected 76 -> 67 (paper typo; see DESIGN.md §Paper-fidelity)
    7: """
    11 22 33 44 55 66 77
    12 31 24 53 46 75 67
    32 14 51 26 73 47 65
    34 52 16 71 27 63 45
    54 36 72 17 61 25 43
    56 74 37 62 15 41 23
    76 57 64 35 42 13 21
    """,
}

# the 3x3 arrangement from the paper's S^1 scrambling figure
PAPER_TABLES[3] = """
    11 22 33
    12 31 23
    32 13 21
    """


def _parse(text):
    rows = [r.split() for r in text.strip().splitlines()]
    return [[(int(c[0]), int(c[1])) for c in row] for row in rows]


@pytest.mark.parametrize("n", sorted(PAPER_TABLES))
def test_sigma_matches_paper_tables(n):
    expect = _parse(PAPER_TABLES[n])
    got = sigma_table(n)
    for i in range(n):
        for j in range(n):
            assert got[i][j] == expect[i][j], (
                f"n={n} cell ({i+1},{j+1}): closed form {got[i][j]} "
                f"!= paper {expect[i][j]}"
            )


# --- cycle structure / order (paper: S has period 7, 7, 20 for n=3,4,5) -----


@pytest.mark.parametrize("n,order", [(3, 7), (4, 7), (5, 20)])
def test_paper_cycle_orders(n, order):
    assert scramble_order(n) == order


def test_paper_n4_cycle_shapes():
    # paper: (11) (42) (12 22 31 32 14 44 21) (13 33 41 34 23 24 43)
    lens = sorted(len(c) for c in cycle_decomposition(4))
    assert lens == [1, 1, 7, 7]


def test_paper_n3_cycle_shapes():
    # paper: (11) (23) (12 22 31 32 13 33 21)
    lens = sorted(len(c) for c in cycle_decomposition(3))
    assert lens == [1, 1, 7]


def test_paper_n5_cycle_shapes():
    # paper: one 20-cycle, one 4-cycle, one fixed point
    lens = sorted(len(c) for c in cycle_decomposition(5))
    assert lens == [1, 4, 20]


def test_paper_n5_four_cycle_members():
    # paper: (13 33 51 54)
    cycles = cycle_decomposition(5)
    four = next(c for c in cycles if len(c) == 4)
    assert set(four) == {(1, 3), (3, 3), (5, 1), (5, 4)}


def test_order_equals_lcm_of_cycles():
    for n in range(2, 12):
        lens = [len(c) for c in cycle_decomposition(n)]
        assert scramble_order(n) == math.lcm(*lens)


# --- permutation algebra (property tests) ------------------------------------


@given(st.integers(min_value=2, max_value=16))
@settings(max_examples=15, deadline=None)
def test_sigma_is_a_bijection(n):
    seen = {sigma(n, i, j) for i in range(1, n + 1) for j in range(1, n + 1)}
    assert len(seen) == n * n


@given(st.integers(min_value=2, max_value=12), st.integers(min_value=-30, max_value=30))
@settings(max_examples=30, deadline=None)
def test_power_perm_matches_repeated_composition(n, k):
    base = scramble_perm(n)
    # repeated composition (k mod order times)
    order = scramble_order(n)
    kk = k % order
    ref = np.arange(n * n)
    for _ in range(kk):
        ref = base[ref]
    assert np.array_equal(power_perm(base, k), ref)


@given(st.integers(min_value=2, max_value=12))
@settings(max_examples=15, deadline=None)
def test_inverse_perm(n):
    p = scramble_perm(n)
    inv = inverse_perm(p)
    assert np.array_equal(p[inv], np.arange(n * n))
    assert np.array_equal(inv[p], np.arange(n * n))


@given(st.integers(min_value=2, max_value=10))
@settings(max_examples=12, deadline=None)
def test_scramble_power_order_is_identity(n):
    x = np.arange(n * n, dtype=np.float32).reshape(n, n)
    out = apply_scramble(jnp.asarray(x), scramble_order(n))
    np.testing.assert_array_equal(np.asarray(out), x)


def test_apply_unscramble_roundtrip():
    rng = np.random.default_rng(1)
    for n in (3, 4, 5, 8):
        x = jnp.asarray(rng.normal(size=(2, n, n)).astype(np.float32))
        np.testing.assert_allclose(np.asarray(unscramble(apply_scramble(x))), np.asarray(x))


def test_apply_scramble_power_traced_key():
    """Keyed scrambler: traced k selects S^k from the precomputed table."""
    n = 5
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    for k in (0, 1, 7, 19, 20, 33):
        got = apply_scramble_power(x, jnp.int32(k), n)
        want = apply_scramble(x, k % scramble_order(n))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_scramble_identity_shows_S():
    """C = A @ I lands in the scrambled arrangement — the paper's Figure 4."""
    from repro.core.mesh_array import simulate_mesh

    n = 4
    a = jnp.asarray(np.arange(n * n, dtype=np.float32).reshape(n, n))
    out = simulate_mesh(a, jnp.eye(n, dtype=jnp.float32)).output
    np.testing.assert_allclose(np.asarray(out), np.asarray(apply_scramble(a)))


def test_scrambled_cell_of_inverts_sigma():
    for n in (3, 4, 7):
        for p in range(1, n + 1):
            for q in range(1, n + 1):
                i, j = scramble.scrambled_cell_of(n, p, q)
                assert sigma(n, i, j) == (p, q)
