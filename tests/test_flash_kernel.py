"""Pallas flash-attention kernel vs the SDPA oracle (interpret=True).

Sweeps shapes, GQA ratios, block shapes, dtypes, causal on/off; also checks
the jnp chunked path (models/attention._sdpa_chunked) against the same
oracle — three implementations, one semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import flash_attention_pallas
from repro.models.attention import _sdpa, _sdpa_chunked


def _qkv(b, t, h, kv, hd, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, t, h, hd)).astype(np.float32), dtype)
    k = jnp.asarray(rng.normal(size=(b, t, kv, hd)).astype(np.float32), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, kv, hd)).astype(np.float32), dtype)
    return q, k, v


CASES = [
    # b, t, h, kv, hd, bq, bk
    (2, 64, 4, 4, 16, 16, 16),   # MHA
    (2, 64, 4, 2, 16, 16, 32),   # GQA rep=2
    (1, 128, 6, 2, 32, 32, 64),  # GQA rep=3
    (2, 64, 8, 1, 16, 64, 16),   # MQA
]


@pytest.mark.parametrize("b,t,h,kv,hd,bq,bk", CASES)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_vs_oracle(b, t, h, kv, hd, bq, bk, causal):
    q, k, v = _qkv(b, t, h, kv, hd, jnp.float32, seed=t + h)
    got = flash_attention_pallas(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True
    )
    want = _sdpa(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_kernel_bf16():
    q, k, v = _qkv(2, 64, 4, 2, 16, jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    want = _sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=3e-2, atol=3e-2
    )


def test_flash_kernel_rejects_bad_blocks():
    q, k, v = _qkv(1, 64, 2, 2, 16, jnp.float32)
    with pytest.raises(ValueError):
        flash_attention_pallas(q, k, v, block_q=48, block_k=16, interpret=True)


@given(
    t_blocks=st.integers(1, 4),
    h=st.sampled_from([2, 4]),
    kv=st.sampled_from([1, 2]),
    causal=st.booleans(),
)
@settings(max_examples=12, deadline=None)
def test_flash_kernel_property(t_blocks, h, kv, causal):
    if h % kv:
        kv = 1
    t = 32 * t_blocks
    q, k, v = _qkv(1, t, h, kv, 16, jnp.float32, seed=t_blocks * 7 + h)
    got = flash_attention_pallas(
        q, k, v, causal=causal, block_q=32, block_k=32, interpret=True
    )
    want = _sdpa(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_chunked_jnp_path_vs_oracle(chunk):
    q, k, v = _qkv(2, 64, 4, 2, 16, jnp.float32, seed=chunk)
    for causal in (True, False):
        got = _sdpa_chunked(q, k, v, causal=causal, chunk=chunk)
        want = _sdpa(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_three_impls_agree_gqa():
    q, k, v = _qkv(2, 128, 8, 2, 32, jnp.float32, seed=9)
    a = _sdpa(q, k, v, causal=True)
    b = _sdpa_chunked(q, k, v, causal=True, chunk=32)
    c = flash_attention_pallas(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(c), np.asarray(a), rtol=2e-5, atol=2e-5)
