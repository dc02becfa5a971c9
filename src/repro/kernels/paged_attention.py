"""Paged-KV gather attention for continuous-batching decode (DESIGN.md §12).

The serving scheduler (`launch/scheduler.py`) stores every sequence's KV
cache as fixed-size **pages** inside one shared pool per layer; a sequence
owns an arbitrary, non-contiguous set of pages named by its **block table**.
Decode attention must therefore gather K/V through the block table instead
of slicing a dense per-sequence cache.  Two implementations live behind a
capability door mirroring the GEMM backend registry (`kernels/api.py`):

  pallas_paged  one `pallas_call` over the slots whose steps copy pool
                rows `bt[s, p]` (every KV head of a page at once) from HBM
                straight into VMEM by the scalar-prefetched block table (no
                gathered copy of the context is ever materialized), a block
                of whole pages at a time and only as far as the slot's
                length reaches, with the flash (m, l, acc) online-softmax
                recurrence in VMEM scratch.
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
                                     page of every head is one contiguous
                                     copy and each head's rows are whole
                                     (page_size, hd) tiles
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
    "pages_per_block",
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

# Bytes of K (and as many of V) that one block of pages brings into VMEM.
# K and V are both double-buffered: the kernel's VMEM holds four times this.
_BLOCK_BYTES = 512 * 1024


def pages_per_block(
    kv_heads: int, page_size: int, head_dim: int, dtype, n_pages: int
) -> int:
    """Whole pages (all KV heads of each) one grid step visits at a time.

    As many as fit `_BLOCK_BYTES`, at least one and at most the table's
    `n_pages`: 8 pages of 64 KiB at 16 KV heads of 128 in bf16, 16 of 32 KiB
    at 8 heads.
    """
    page_bytes = kv_heads * page_size * head_dim * jnp.dtype(dtype).itemsize
    return max(1, min(n_pages, _BLOCK_BYTES // page_bytes))


def _paged_kernel(
    bt_ref,  # SMEM (S, n_pages) block tables (scalar prefetch)
    len_ref,  # SMEM (S,) valid lengths (scalar prefetch)
    q_ref,  # VMEM (KV, rep, hd) this slot's query rows
    k_hbm,  # HBM (P, KV, ps, hd) key pool
    v_hbm,  # HBM (P, KV, ps, hd) value pool
    o_ref,  # VMEM (KV, rep, hd)
    k_buf,  # VMEM (2, KV, ppb * ps, hd) double-buffered block of keys
    v_buf,  # VMEM (2, KV, ppb * ps, hd) double-buffered block of values
    k_sem,  # DMA (2,) one semaphore per buffer
    v_sem,  # DMA (2,)
    m_ref,  # VMEM (KV, rep, 1) running max
    l_ref,  # VMEM (KV, rep, 1) running denominator
    acc_ref,  # VMEM (KV, rep, hd) f32 accumulator
    cur_ref,  # SMEM (1,) the buffer the next block lands in
    *,
    page_size: int,
    pages_per_block: int,
    scale: float,
):
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n_pages = bt_ref.shape[1]
    block_len = pages_per_block * page_size

    def live_len(i):
        return jnp.minimum(len_ref[i], n_pages * page_size)

    def n_blocks(i):
        return (live_len(i) + block_len - 1) // block_len

    kv = ((k_hbm, k_buf, k_sem), (v_hbm, v_buf, v_sem))

    def each_page(i, b, buf, pools, op):
        # Only the pages the slot's length reaches are copied; the rest of
        # the buffer keeps older (finite) pages, which the mask zeroes.
        n_live = (live_len(i) + page_size - 1) // page_size
        for j in range(pages_per_block):
            page = b * pages_per_block + j

            @pl.when(page < n_live)
            def _():
                row = bt_ref[i, page]
                dst = pl.ds(j * page_size, page_size)
                for hbm, vmem, sem in pools:
                    copy = pltpu.make_async_copy(
                        hbm.at[row], vmem.at[buf, :, dst], sem.at[buf]
                    )
                    getattr(copy, op)()

    @pl.when(s == 0)
    def _first():
        # Buffer rows no page has reached yet must not hold NaNs for the
        # zero probabilities of masked positions to multiply.
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        cur_ref[0] = 0

    nb = n_blocks(s)
    prev_nb = n_blocks(jnp.maximum(s - 1, 0))

    # The previous step started this slot's first block unless it had none.
    @pl.when((nb > 0) & ((s == 0) | (prev_nb == 0)))
    def _prime():
        each_page(s, 0, cur_ref[0], kv, "start")

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length = len_ref[s]
    q = q_ref[...]

    def block(b, carry):
        cur = cur_ref[0]
        nxt = 1 - cur

        @pl.when(b + 1 < nb)
        def _next_block():
            each_page(s, b + 1, nxt, kv, "start")

        @pl.when((b + 1 == nb) & (s + 1 < n_slots))
        def _next_slot():
            @pl.when(n_blocks(s + 1) > 0)
            def _():
                each_page(s + 1, 0, nxt, kv, "start")

        each_page(s, b, cur, kv[:1], "wait")
        # bf16 products are exact in f32, and are summed in f32.
        sc = jnp.einsum(
            "krd,ktd->krt", q, k_buf[cur], preferred_element_type=jnp.float32
        )
        sc = sc * scale  # (KV, rep, ppb * ps)
        kpos = b * block_len + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 2)
        sc = jnp.where(kpos < length, sc, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        prob = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(prob, axis=-1, keepdims=True)
        m_ref[...] = m_new
        each_page(s, b, cur, kv[1:], "wait")
        pv = jnp.einsum(
            "krt,ktd->krd", prob.astype(v_buf.dtype), v_buf[cur],
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        cur_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, nb, block, 0)
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)  # a slot of length 0 reads zeros
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
    """One pallas_call over grid (slots,): each step loops over the blocks
    of whole pages its slot's length reaches, copying each page (all KV
    heads) from the pools in HBM by the block table while the previous block
    is computed, across the boundary into the next slot's first block."""
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
    ppb = pages_per_block(kvh, ps, hd, k_pool.dtype, n_pages)

    qf = q.reshape(s, kvh, rep, hd)

    kernel = functools.partial(
        _paged_kernel, page_size=ps, pages_per_block=ppb, scale=hd**-0.5
    )
    rows = pl.BlockSpec((None, kvh, rep, hd), lambda i, bt, ln: (i, 0, 0, 0))
    buf = pltpu.VMEM((2, kvh, ppb * ps, hd), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[
            rows,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=rows,
        scratch_shapes=[
            buf,
            buf,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, hd), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    compiler_params = None
    if not interpret:  # pragma: no cover — TPU-only path
        # Sequential: a step starts the next slot's first copies.
        compiler_params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
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
