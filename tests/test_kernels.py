"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Sweeps shapes/dtypes per the deliverable: every kernel asserts allclose
against repro.kernels.ref for each (shape, dtype, schedule-flag) cell.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.scramble import scramble_order
from repro.kernels import ref
from repro.kernels.mesh_matmul import (
    ACTIVATIONS,
    mesh_matmul_pallas,
    mesh_matmul_pallas_batched,
)
from repro.kernels.ops import matmul, scramble_blocks
from repro.kernels.scramble_kernel import scramble_blocks_pallas

B = 8  # small block for CPU-interpret sweeps


def _mk(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


# --- mesh matmul kernel -------------------------------------------------------

SHAPES = [
    (B, B, B),
    (2 * B, 3 * B, 4 * B),
    (4 * B, 2 * B, B),
    (3 * B, 5 * B, 2 * B),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("stagger", [True, False])
def test_mesh_matmul_vs_oracle(m, k, n, dtype, stagger):
    a = _mk((m, k), dtype, m + k)
    b = _mk((k, n), dtype, k + n)
    got = mesh_matmul_pallas(
        a, b, block_m=B, block_n=B, block_k=B, stagger=stagger, interpret=True
    )
    want = ref.matmul_ref(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("g", [2, 3, 4, 5])
@pytest.mark.parametrize("stagger", [True, False])
def test_mesh_matmul_scrambled_output(g, stagger):
    """Cell-block (i,j) holds standard block sigma(i,j) — zero-cost fusion."""
    m = n = g * B
    k = 2 * B
    a = _mk((m, k), jnp.float32, g)
    b = _mk((k, n), jnp.float32, g + 1)
    got = mesh_matmul_pallas(
        a, b, block_m=B, block_n=B, block_k=B, stagger=stagger,
        scramble_out=True, interpret=True,
    )
    want = ref.mesh_matmul_ref(a, b, block_m=B, block_n=B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_mesh_matmul_rejects_bad_shapes():
    a = jnp.zeros((B + 1, B))
    b = jnp.zeros((B, B))
    with pytest.raises(ValueError):
        mesh_matmul_pallas(a, b, block_m=B, block_n=B, block_k=B, interpret=True)
    with pytest.raises(ValueError):
        mesh_matmul_pallas(
            jnp.zeros((2 * B, B)), jnp.zeros((B, B)),
            block_m=B, block_n=B, block_k=B, scramble_out=True, interpret=True,
        )


@given(
    st.integers(min_value=1, max_value=4),
    st.integers(min_value=1, max_value=4),
    st.integers(min_value=1, max_value=4),
)
@settings(max_examples=12, deadline=None)
def test_mesh_matmul_property_grid(gm, gk, gn):
    """Property: for any block grid, staggered == standard == oracle."""
    rng = np.random.default_rng(gm * 16 + gk * 4 + gn)
    a = jnp.asarray(rng.normal(size=(gm * B, gk * B)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(gk * B, gn * B)).astype(np.float32))
    want = ref.matmul_ref(a, b)
    for stagger in (True, False):
        got = mesh_matmul_pallas(
            a, b, block_m=B, block_n=B, block_k=B, stagger=stagger, interpret=True
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# --- scramble kernel ---------------------------------------------------------


@pytest.mark.parametrize("g", [2, 3, 4, 6])
@pytest.mark.parametrize("k", [1, 2, -1, 5])
def test_scramble_kernel_vs_oracle(g, k):
    x = _mk((g * B, g * B), jnp.float32, g * 10 + k)
    got = scramble_blocks_pallas(x, block_m=B, block_n=B, k=k, interpret=True)
    want = x
    fn = ref.scramble_blocks_ref if k >= 0 else ref.unscramble_blocks_ref
    for _ in range(abs(k)):
        want = fn(want, block_m=B, block_n=B)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scramble_kernel_order_identity():
    g = 4
    x = _mk((g * B, g * B), jnp.float32, 7)
    k = scramble_order(g)
    got = scramble_blocks_pallas(x, block_m=B, block_n=B, k=k, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


def test_scramble_kernel_batched():
    g = 3
    x = _mk((2, 5, g * B, g * B), jnp.float32, 9)
    got = scramble_blocks_pallas(x, block_m=B, block_n=B, k=1, interpret=True)
    want = ref.scramble_blocks_ref(x, block_m=B, block_n=B)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- ops.matmul dispatch layer -------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas_mesh"])
def test_ops_matmul_padding_and_batching(backend):
    """Arbitrary (non-block-multiple) shapes + leading batch dims."""
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.normal(size=(2, 3, 37, 19)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(19, 23)).astype(np.float32))
    got = matmul(a, b, backend=backend, block_m=B, block_n=B, block_k=B)
    want = jnp.einsum("bcmk,kn->bcmn", a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_ops_matmul_fully_batched():
    rng = np.random.default_rng(12)
    a = jnp.asarray(rng.normal(size=(4, 17, 9)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(4, 9, 21)).astype(np.float32))
    got = matmul(a, b, backend="pallas_mesh", block_m=B, block_n=B, block_k=B)
    want = jnp.einsum("bmk,bkn->bmn", a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_ops_matmul_grad_matches_xla():
    """custom_vjp: kernel-backend gradients == XLA-backend gradients."""
    rng = np.random.default_rng(13)
    a = jnp.asarray(rng.normal(size=(2 * B, 3 * B)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(3 * B, B)).astype(np.float32))

    def loss(backend):
        def f(a, b):
            return jnp.sum(
                matmul(a, b, backend=backend, block_m=B, block_n=B, block_k=B) ** 2
            )
        return f

    ga_x, gb_x = jax.grad(loss("xla"), argnums=(0, 1))(a, b)
    ga_p, gb_p = jax.grad(loss("pallas_mesh"), argnums=(0, 1))(a, b)
    np.testing.assert_allclose(np.asarray(ga_p), np.asarray(ga_x), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb_p), np.asarray(gb_x), rtol=1e-4, atol=1e-4)


def test_ops_matmul_grad_scrambled_backend():
    """d/dA sum(S(AB)) == d/dA sum(AB) since S only permutes positions."""
    rng = np.random.default_rng(14)
    g = 3
    a = jnp.asarray(rng.normal(size=(g * B, 2 * B)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(2 * B, g * B)).astype(np.float32))

    def f_scr(a, b):
        return jnp.sum(
            matmul(a, b, backend="pallas_mesh_scrambled", block_m=B, block_n=B, block_k=B)
        )

    def f_xla(a, b):
        return jnp.sum(matmul(a, b, backend="xla"))

    ga_s = jax.grad(f_scr)(a, b)
    ga_x = jax.grad(f_xla)(a, b)
    np.testing.assert_allclose(np.asarray(ga_s), np.asarray(ga_x), rtol=1e-4, atol=1e-4)


def test_ops_scramble_blocks_grad_roundtrip():
    """VJP of S^k is S^-k: grad of sum(S(x) * w) must equal S^-1(w)."""
    rng = np.random.default_rng(15)
    g = 3
    x = jnp.asarray(rng.normal(size=(g * B, g * B)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(g * B, g * B)).astype(np.float32))

    def f(x):
        return jnp.sum(scramble_blocks(x, block_m=B, block_n=B, k=1) * w)

    gx = jax.grad(f)(x)
    want = scramble_blocks(w, block_m=B, block_n=B, k=-1)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_scrambled_backend_equals_core_S():
    """kernel-fused S == core apply_scramble at block granularity."""
    from repro.kernels.ref import scramble_blocks_ref

    rng = np.random.default_rng(16)
    g = 4
    a = jnp.asarray(rng.normal(size=(g * B, g * B)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(g * B, g * B)).astype(np.float32))
    got = matmul(a, b, backend="pallas_mesh_scrambled", block_m=B, block_n=B, block_k=B)
    want = scramble_blocks_ref(ref.matmul_ref(a, b), block_m=B, block_n=B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# --- fused epilogue -----------------------------------------------------------


def _epilogue_ref(a, b, bias=None, activation=None, residual=None):
    z = jnp.dot(a, b, preferred_element_type=jnp.float32)
    if bias is not None:
        z = z + bias.astype(jnp.float32)
    if activation not in (None, "none"):
        z = ACTIVATIONS[activation](z)
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    return z


@pytest.mark.parametrize("activation", [None, "relu", "gelu", "silu", "sigmoid", "tanh"])
@pytest.mark.parametrize("with_residual", [False, True])
def test_fused_epilogue_vs_unfused_reference(activation, with_residual):
    """acceptance: fused bias+activation matches unfused reference @ 1e-4."""
    m, k, n = 2 * B, 3 * B, 2 * B
    a = _mk((m, k), jnp.float32, 21)
    b = _mk((k, n), jnp.float32, 22)
    bias = _mk((n,), jnp.float32, 23)
    res = _mk((m, n), jnp.float32, 24) if with_residual else None
    got = mesh_matmul_pallas(
        a, b, bias=bias, residual=res, activation=activation,
        block_m=B, block_n=B, block_k=B, interpret=True,
    )
    want = _epilogue_ref(a, b, bias, activation, res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_fused_epilogue_scrambled_applies_before_placement():
    """epilogue acts on the standard block, then sigma places it."""
    g = 3
    m = n = g * B
    a = _mk((m, 2 * B), jnp.float32, 25)
    b = _mk((2 * B, n), jnp.float32, 26)
    bias = _mk((n,), jnp.float32, 27)
    got = mesh_matmul_pallas(
        a, b, bias=bias, activation="relu", scramble_out=True,
        block_m=B, block_n=B, block_k=B, interpret=True,
    )
    want = ref.scramble_blocks_ref(
        _epilogue_ref(a, b, bias, "relu"), block_m=B, block_n=B
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_fused_epilogue_rejects_bad_shapes():
    a = jnp.zeros((2 * B, B))
    b = jnp.zeros((B, 2 * B))
    with pytest.raises(ValueError):
        mesh_matmul_pallas(
            a, b, bias=jnp.zeros((B,)),  # wrong bias length
            block_m=B, block_n=B, block_k=B, interpret=True,
        )
    with pytest.raises(ValueError):
        mesh_matmul_pallas(
            a, b, activation="swish-ish",  # unknown activation
            block_m=B, block_n=B, block_k=B, interpret=True,
        )


def test_ops_fused_epilogue_with_padding():
    """Fused path through ops.matmul on non-block-multiple shapes."""
    rng = np.random.default_rng(31)
    a = jnp.asarray(rng.normal(size=(19, 13)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(13, 11)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(11,)).astype(np.float32))
    got = matmul(
        a, b, backend="pallas_mesh", block_m=B, block_n=B, block_k=B,
        bias=bias, activation="gelu",
    )
    want = _epilogue_ref(a, b, bias, "gelu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("activation", ["relu", "gelu", "silu", "sigmoid", "tanh"])
def test_fused_epilogue_grads_match_xla(activation):
    """Extended VJP: grads of act(AB + bias) + residual == XLA-backend grads."""
    rng = np.random.default_rng(32)
    a = jnp.asarray(rng.normal(size=(2 * B, 3 * B)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(3 * B, B)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(B,)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(2 * B, B)).astype(np.float32))

    def loss(backend):
        def f(a, b, bias, res):
            y = matmul(
                a, b, backend=backend, block_m=B, block_n=B, block_k=B,
                bias=bias, activation=activation, residual=res,
            )
            return jnp.sum(y**2)
        return f

    gx = jax.grad(loss("xla"), argnums=(0, 1, 2, 3))(a, b, bias, res)
    gp = jax.grad(loss("pallas_mesh"), argnums=(0, 1, 2, 3))(a, b, bias, res)
    for want, got in zip(gx, gp):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )


def test_fused_epilogue_grads_scrambled_backend():
    rng = np.random.default_rng(33)
    g = 3
    a = jnp.asarray(rng.normal(size=(g * B, 2 * B)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(2 * B, g * B)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(g * B,)).astype(np.float32))

    def f_scr(a, b, bias):
        y = matmul(
            a, b, backend="pallas_mesh_scrambled", block_m=B, block_n=B,
            block_k=B, bias=bias, activation="silu",
        )
        return jnp.sum(y**2)

    def f_xla(a, b, bias):
        return jnp.sum(matmul(a, b, backend="xla", bias=bias, activation="silu") ** 2)

    gs = jax.grad(f_scr, argnums=(0, 1, 2))(a, b, bias)
    gx = jax.grad(f_xla, argnums=(0, 1, 2))(a, b, bias)
    # sum-of-squares is permutation-invariant, so grads agree exactly
    for want, got in zip(gx, gs):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )


# --- batched (b, i, j, k) grid ------------------------------------------------


def test_batched_kernel_vs_oracle():
    nb = 4
    a = _mk((nb, 2 * B, 3 * B), jnp.float32, 41)
    b = _mk((nb, 3 * B, 2 * B), jnp.float32, 42)
    got = mesh_matmul_pallas_batched(
        a, b, block_m=B, block_n=B, block_k=B, interpret=True
    )
    want = jnp.einsum("bmk,bkn->bmn", a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_batched_kernel_fused_epilogue():
    nb = 3
    a = _mk((nb, 2 * B, B), jnp.float32, 43)
    b = _mk((nb, B, 2 * B), jnp.float32, 44)
    bias = _mk((2 * B,), jnp.float32, 45)  # shared across the batch
    res = _mk((nb, 2 * B, 2 * B), jnp.float32, 46)
    got = mesh_matmul_pallas_batched(
        a, b, bias=bias, residual=res, activation="silu",
        block_m=B, block_n=B, block_k=B, interpret=True,
    )
    want = jax.vmap(lambda ai, bi, ri: _epilogue_ref(ai, bi, bias, "silu", ri))(a, b, res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_ops_batched_is_single_pallas_call():
    """acceptance: batched inputs trace to ONE pallas_call with a (b,i,j,k)
    grid — no vmapped per-element launch."""
    import re

    a = _mk((4, 2 * B, B), jnp.float32, 47)
    b = _mk((4, B, B), jnp.float32, 48)
    jaxpr = str(
        jax.make_jaxpr(
            lambda a, b: matmul(a, b, backend="pallas_mesh", block_m=B, block_n=B, block_k=B)
        )(a, b)
    )
    assert len([ln for ln in jaxpr.splitlines() if "pallas_call" in ln]) == 1
    grids = re.findall(r"grid=\(([^)]*)\)", jaxpr)
    assert grids and len(grids[0].split(",")) == 4, grids  # (b, i, j, k)
    assert grids[0].split(",")[0].strip() == "4"  # leading batch axis


def test_batched_grads_match_xla():
    a = _mk((3, 2 * B, B), jnp.float32, 49)
    b = _mk((3, B, 2 * B), jnp.float32, 50)
    bias = _mk((2 * B,), jnp.float32, 51)

    def loss(backend):
        def f(a, b, bias):
            y = matmul(
                a, b, backend=backend, block_m=B, block_n=B, block_k=B,
                bias=bias, activation="gelu",
            )
            return jnp.sum(y**2)
        return f

    gx = jax.grad(loss("xla"), argnums=(0, 1, 2))(a, b, bias)
    gp = jax.grad(loss("pallas_mesh"), argnums=(0, 1, 2))(a, b, bias)
    for want, got in zip(gx, gp):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )
