"""SSD (Mamba2) chunked-vs-sequential equivalence + property tests.

ssd_chunked is the matmul-rich (MXU-friendly) form used for training;
ssd_scan is the sequential oracle.  They must agree for any shapes, chunk
boundaries, and decay magnitudes (the log-space trick keeps every exponent
<= 0, so no overflow for extreme dt/a values).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.ssm import ssd_chunked, ssd_scan, ssd_step


def _inputs(b, t, h, p, n, seed, dt_scale=1.0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, t, h, p)).astype(np.float32))
    dt = jnp.asarray((rng.random((b, t, h)) * dt_scale + 0.01).astype(np.float32))
    a_log = jnp.asarray(rng.normal(size=(h,)).astype(np.float32) * 0.5)
    bmat = jnp.asarray(rng.normal(size=(b, t, n)).astype(np.float32))
    cmat = jnp.asarray(rng.normal(size=(b, t, n)).astype(np.float32))
    d_skip = jnp.asarray(rng.normal(size=(h,)).astype(np.float32))
    h0 = jnp.asarray(rng.normal(size=(b, h, p, n)).astype(np.float32) * 0.1)
    return x, dt, a_log, bmat, cmat, d_skip, h0


@pytest.mark.parametrize("t,chunk", [(16, 4), (16, 16), (20, 8), (7, 4), (64, 16)])
def test_chunked_equals_scan(t, chunk):
    args = _inputs(2, t, 3, 4, 5, seed=t * 31 + chunk)
    y_seq, h_seq = ssd_scan(*args)
    y_chk, h_chk = ssd_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_chk), np.asarray(h_seq), rtol=2e-4, atol=2e-4)


@given(
    b=st.integers(1, 3),
    t=st.integers(1, 24),
    h=st.integers(1, 4),
    p=st.integers(1, 6),
    n=st.integers(1, 6),
    chunk=st.sampled_from([2, 4, 8]),
)
@settings(max_examples=25, deadline=None)
def test_chunked_equals_scan_property(b, t, h, p, n, chunk):
    args = _inputs(b, t, h, p, n, seed=b + t * 7 + h * 11 + p * 13 + n * 17)
    y_seq, h_seq = ssd_scan(*args)
    y_chk, h_chk = ssd_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(h_chk), np.asarray(h_seq), rtol=5e-4, atol=5e-4)


def test_extreme_decay_no_overflow():
    """Large dt * a: decays underflow to 0 but never overflow/NaN."""
    args = _inputs(1, 32, 2, 3, 4, seed=0, dt_scale=50.0)
    y_chk, h_chk = ssd_chunked(*args, chunk=8)
    assert bool(jnp.all(jnp.isfinite(y_chk)))
    assert bool(jnp.all(jnp.isfinite(h_chk)))
    y_seq, h_seq = ssd_scan(*args)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq), rtol=1e-3, atol=1e-3)


def test_step_matches_scan_per_token():
    """Decode path: T applications of ssd_step == one ssd_scan."""
    b, t, h, p, n = 2, 6, 2, 3, 4
    x, dt, a_log, bmat, cmat, d_skip, h0 = _inputs(b, t, h, p, n, seed=5)
    y_seq, h_seq = ssd_scan(x, dt, a_log, bmat, cmat, d_skip, h0)
    hcur = h0
    ys = []
    for i in range(t):
        y_i, hcur = ssd_step(hcur, x[:, i], dt[:, i], a_log, bmat[:, i], cmat[:, i], d_skip)
        ys.append(y_i)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(ys, axis=1)), np.asarray(y_seq), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(np.asarray(hcur), np.asarray(h_seq), rtol=1e-4, atol=1e-4)


def test_state_carry_across_calls():
    """Splitting a sequence across two chunked calls == one call (streaming)."""
    b, t, h, p, n = 1, 24, 2, 4, 3
    x, dt, a_log, bmat, cmat, d_skip, h0 = _inputs(b, t, h, p, n, seed=9)
    y_full, h_full = ssd_chunked(x, dt, a_log, bmat, cmat, d_skip, h0, chunk=8)
    cut = 16
    y1, h1 = ssd_chunked(x[:, :cut], dt[:, :cut], a_log, bmat[:, :cut], cmat[:, :cut], d_skip, h0, chunk=8)
    y2, h2 = ssd_chunked(x[:, cut:], dt[:, cut:], a_log, bmat[:, cut:], cmat[:, cut:], d_skip, h1, chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), rtol=2e-4, atol=2e-4)
