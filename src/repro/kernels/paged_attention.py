"""Paged-KV gather attention for continuous-batching decode (DESIGN.md §12).

The serving scheduler (`launch/scheduler.py`) stores every sequence's KV
cache as fixed-size **pages** inside one shared pool per layer; a sequence
owns an arbitrary, non-contiguous set of pages named by its **block table**.
Decode attention must therefore gather K/V through the block table instead
of slicing a dense per-sequence cache.  Two implementations live behind a
capability door mirroring the GEMM backend registry (`kernels/api.py`):

  pallas_paged  one `pallas_call` whose k/v BlockSpec index_maps read the
                scalar-prefetched block table — page `p` of sequence `s`
                streams pool row `bt[s, p]` straight into VMEM (no gathered
                copy of the context is ever materialized), with the flash
                (m, l, acc) online-softmax recurrence in VMEM scratch and
                pages past the sequence length skipped entirely.
  xla_gather    `pool[block_table]` gather + masked softmax, written
                op-for-op like `models.attention._sdpa` so decode through
                pages is **bitwise equal** to decode against the dense cache
                (the scheduler's correctness contract, tested in
                tests/test_scheduler.py).

The door (`resolve_paged_impl`) applies the same rule as the GEMM registry's
interpret capability: an impl that cannot execute off-TPU is only eligible
on TPU (or when Pallas interpret mode is explicitly requested); asking for
an unavailable impl raises the registry's `CapabilityError`.  On CPU CI the
door resolves to `xla_gather`; on TPU it resolves to `pallas_paged`.

Layout contract (single decode token per sequence slot):

  q             (S, H, hd)           one query token per slot
  k_pool/v_pool (P, KV, page_size, hd)  shared pools; page 0 is the
                                     scheduler's scratch page (inactive
                                     slots write there, never read back).
                                     KV-head-major inside a page, so one
                                     (page, kv-head) block is a full
                                     (page_size, hd) tile and obeys the
                                     TPU's (8, 128) block rule
  block_tables  (S, n_pages) int32   page ids per slot; unallocated -> 0
  lengths       (S,) int32           valid context length INCLUDING the
                                     freshly written token (= pos + 1)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.api import CapabilityError
from repro.kernels.mesh_matmul import _HAVE_PLTPU

if _HAVE_PLTPU:
    from jax.experimental.pallas import tpu as pltpu
else:  # pragma: no cover
    pltpu = None

__all__ = [
    "PAGED_FALLBACK_ORDER",
    "gather_pages",
    "paged_attention",
    "paged_attention_pallas",
    "paged_attention_xla",
    "paged_impl_names",
    "register_paged_impl",
    "resolve_paged_impl",
]

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# XLA gather fallback (bitwise-parity reference)
# ---------------------------------------------------------------------------


def gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(P, KV, ps, hd) pool + (S, n) tables -> (S, n*ps, KV, hd) context."""
    s, n = block_tables.shape
    _, kvh, ps, hd = pool.shape
    pages = jnp.take(pool, block_tables, axis=0)  # (S, n, KV, ps, hd)
    return pages.transpose(0, 1, 3, 2, 4).reshape(s, n * ps, kvh, hd)


def paged_attention_xla(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Gathered-context SDPA, op-for-op `models.attention._sdpa`.

    The op sequence (einsum scaling, -1e30 where-mask, f32 softmax) is kept
    IDENTICAL to the dense decode path so a sequence served through pages
    produces bit-identical logits to the legacy `generate()` loop — pool
    rows past `lengths` (scratch page, unwritten slots) mask to exp -> 0.0
    exactly and contribute nothing.
    """
    del interpret  # native jnp: runs everywhere
    s, h, hd = q.shape
    k = gather_pages(k_pool, block_tables)
    v = gather_pages(v_pool, block_tables)
    kvh = k.shape[2]
    rep = h // kvh
    q5 = q.reshape(s, 1, kvh, rep, hd)
    scores = jnp.einsum(
        "btkrd,bskd->bkrts", q5, k, preferred_element_type=jnp.float32
    ) / (hd**0.5)
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]  # (S, T)
    scores = jnp.where(valid[:, None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrts,bskd->btkrd", probs, v)
    return out.reshape(s, h, hd)


# ---------------------------------------------------------------------------
# Pallas kernel: block-table-steered gather attention
# ---------------------------------------------------------------------------


def _paged_kernel(
    bt_ref,  # SMEM (S, n_pages) block tables (scalar prefetch)
    len_ref,  # SMEM (S,) valid lengths (scalar prefetch)
    q_ref,  # (rep, hd) query rows for this (slot, kv-head)
    k_ref,  # (ps, hd) one page of keys for this kv-head
    v_ref,  # (ps, hd) one page of values for this kv-head
    o_ref,  # (rep, hd)
    m_ref,  # VMEM (rep, 1) running max
    l_ref,  # VMEM (rep, 1) running denominator
    acc_ref,  # VMEM (rep, hd) f32 accumulator
    *,
    page_size: int,
    n_pages: int,
    scale: float,
):
    s = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[s]
    start = p * page_size

    # Pages entirely past the sequence length are skipped — the block table
    # points them at the scratch page and no MXU work is issued (the paged
    # analogue of the grouped kernel's ragged steering).
    @pl.when(start < length)
    def _accumulate():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        sc = jnp.einsum("gd,td->gt", q, k, preferred_element_type=jnp.float32)
        sc = sc * scale  # (rep, ps)
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(kpos < length, sc, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        prob = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(prob, axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jnp.dot(
            prob.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(p == n_pages - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # length >= 1 in practice
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """One pallas_call over grid (slots, kv_heads, pages); the k/v index_maps
    consume the scalar-prefetched block table, so page p of slot s DMAs pool
    row bt[s, p] directly — the gather IS the block placement."""
    if not _HAVE_PLTPU:
        raise NotImplementedError(
            "paged_attention_pallas needs jax.experimental.pallas.tpu"
            " (scalar-prefetch grid specs); use the xla_gather impl"
        )
    s, h, hd = q.shape
    n_pool, kvh, ps, hd2 = k_pool.shape
    if hd != hd2:
        raise ValueError(f"head_dim mismatch: q {q.shape} vs pool {k_pool.shape}")
    if v_pool.shape != k_pool.shape:
        raise ValueError(f"k/v pool mismatch: {k_pool.shape} vs {v_pool.shape}")
    if block_tables.shape[0] != s or lengths.shape != (s,):
        raise ValueError(
            f"block_tables {block_tables.shape} / lengths {lengths.shape}"
            f" do not match {s} slots"
        )
    rep = h // kvh
    n_pages = block_tables.shape[1]
    scale = hd**-0.5

    qf = q.reshape(s, kvh, rep, hd)

    kernel = functools.partial(
        _paged_kernel, page_size=ps, n_pages=n_pages, scale=scale
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, kvh, n_pages),
        in_specs=[
            pl.BlockSpec((None, None, rep, hd), lambda i, j, p, bt, ln: (i, j, 0, 0)),
            pl.BlockSpec(
                (None, None, ps, hd), lambda i, j, p, bt, ln: (bt[i, p], j, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, ps, hd), lambda i, j, p, bt, ln: (bt[i, p], j, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, rep, hd), lambda i, j, p, bt, ln: (i, j, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    compiler_params = None
    if not interpret:  # pragma: no cover — TPU-only path
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, kvh, rep, hd), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qf, k_pool, v_pool)
    return out.reshape(s, h, hd)


# ---------------------------------------------------------------------------
# Capability door (same rules as the GEMM backend registry)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _PagedImpl:
    name: str
    fn: Callable
    # Mirrors BackendCapabilities.interpret: executes off-TPU natively.  An
    # impl without it is only eligible on TPU or under explicit Pallas
    # interpret mode.
    interpret: bool


_PAGED_REGISTRY: Dict[str, _PagedImpl] = {}

# Preference order when no impl is requested (mirrors api.FALLBACK_ORDER:
# the kernel first, the always-runnable gather last).
PAGED_FALLBACK_ORDER = ("pallas_paged", "xla_gather")


def register_paged_impl(
    name: str, fn: Callable, *, interpret: bool, override: bool = False
) -> None:
    if name in _PAGED_REGISTRY and not override:
        raise ValueError(
            f"paged impl {name!r} already registered (pass override=True)"
        )
    _PAGED_REGISTRY[name] = _PagedImpl(name, fn, interpret)


def paged_impl_names() -> List[str]:
    return list(_PAGED_REGISTRY)


def _unavailable_reason(impl: _PagedImpl, interpret: bool) -> Optional[str]:
    if impl.interpret or interpret:
        return None
    if not _HAVE_PLTPU:
        return f"impl {impl.name!r} needs jax.experimental.pallas.tpu"
    if jax.default_backend() != "tpu":
        return (
            f"impl {impl.name!r} requires TPU and interpret mode was not"
            f" requested (running on {jax.default_backend()!r})"
        )
    return None  # pragma: no cover — TPU runtime


def resolve_paged_impl(
    requested: Optional[str] = None, *, interpret: bool = False
) -> str:
    """The capability door: requested impl or the first runnable one.

    Explicitly requesting an impl the runtime cannot execute raises
    `CapabilityError` (never a silent substitution); with no request, the
    preference order degrades from the Pallas kernel to the XLA gather.
    """
    if requested is not None:
        impl = _PAGED_REGISTRY.get(requested)
        if impl is None:
            raise ValueError(
                f"unknown paged impl {requested!r};"
                f" registered: {sorted(_PAGED_REGISTRY)}"
            )
        reason = _unavailable_reason(impl, interpret)
        if reason is not None:
            raise CapabilityError(reason)
        return requested
    reasons = []
    for name in (*PAGED_FALLBACK_ORDER, *_PAGED_REGISTRY):
        impl = _PAGED_REGISTRY.get(name)
        if impl is None:
            continue
        reason = _unavailable_reason(impl, interpret)
        if reason is None:
            return name
        reasons.append(reason)
    raise CapabilityError(
        "no registered paged-attention impl can run here: " + "; ".join(reasons)
    )


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    impl: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """Dispatch through the door; resolution happens at trace time (static)."""
    name = resolve_paged_impl(impl, interpret=interpret)
    return _PAGED_REGISTRY[name].fn(
        q, k_pool, v_pool, block_tables, lengths, interpret=interpret
    )


register_paged_impl("pallas_paged", paged_attention_pallas, interpret=False)
register_paged_impl("xla_gather", paged_attention_xla, interpret=True)
