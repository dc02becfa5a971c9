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
  k_pool/v_pool (P, KV, page_size, hd)  shared pools (the decode step
                                     passes a layer stack's pools as one
                                     of L*P pages); a layer's page 0 is
                                     the scheduler's scratch page (inactive
                                     slots write there, never read back).
                                     KV-head-major inside a page, so one
                                     page of every head is one contiguous
                                     copy and each head's rows are whole
                                     (page_size, hd) tiles
  block_tables  (S, n_pages) int32   page ids per slot; unallocated -> 0
  lengths       (S,) int32           valid context length INCLUDING the
                                     freshly written token (= pos + 1)

Latent layout (multi-head latent attention's absorbed decode): one pool
and no `v_pool`; each row is a latent key of width hd, one KV head serves
every query head, and V is the first `v_width` columns of the K rows
already copied — no second pool and no second copy.  The output is then
(S, H, v_width).  `scale` multiplies the scores (default hd^-0.5).  A row
is `latent_row_width` wide: DeepSeek-V2's 512-wide latent and 64-wide RoPE
key (576) and 64 zero columns.  The TPU lays a 576-wide minor dim out in
640 columns in any case, and the kernel's page copies may cut HBM only at
whole 128-column tiles, so the zeros cost no memory; q carries zeros there
too, so they add nothing to a score.
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
    "latent_row_width",
    "paged_attention",
    "paged_attention_pallas",
    "paged_attention_xla",
    "paged_impl_names",
    "pages_per_block",
    "register_paged_impl",
    "resolve_paged_impl",
]

_NEG_INF = -1e30
_LANES = 128  # the TPU's minor tile: HBM slices start and end on it


def latent_row_width(width: int) -> int:
    """Columns of a latent pool row holding `width` values."""
    return -(-width // _LANES) * _LANES


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
    v_pool: Optional[jax.Array],
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    v_width: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Gathered-context SDPA, op-for-op `models.attention._sdpa`.

    The op sequence (einsum scaling, -1e30 where-mask, f32 softmax) is kept
    IDENTICAL to the dense decode path so a sequence served through pages
    produces bit-identical logits to the legacy `generate()` loop — pool
    rows past `lengths` (scratch page, unwritten slots) mask to exp -> 0.0
    exactly and contribute nothing.  The latent layout (`v_pool` None) reads
    V as the first `v_width` columns of the gathered K.
    """
    del interpret  # native jnp: runs everywhere
    s, h, hd = q.shape
    k = gather_pages(k_pool, block_tables)
    v = k[..., :v_width] if v_pool is None else gather_pages(v_pool, block_tables)
    kvh = k.shape[2]
    rep = h // kvh
    q5 = q.reshape(s, 1, kvh, rep, hd)
    scores = jnp.einsum(
        "btkrd,bskd->bkrts", q5, k, preferred_element_type=jnp.float32
    )
    scores = scores / (hd**0.5) if scale is None else scores * scale
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]  # (S, T)
    scores = jnp.where(valid[:, None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrts,bskd->btkrd", probs, v)
    return out.reshape(s, h, v.shape[-1])


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
    *refs,
    page_size: int,
    pages_per_block: int,
    scale: float,
    v_width: Optional[int],
):
    # With a value pool (v_width None):
    #   k_hbm, v_hbm  HBM (P, KV, ps, hd) key and value pools
    #   o_ref         VMEM (KV, rep, hd)
    #   k_buf, v_buf  VMEM (2, KV, ppb * ps, hd) double-buffered blocks
    #   k_sem, v_sem  DMA (2,) one semaphore per buffer
    # In the latent layout the v_* refs are absent, V is the first v_width
    # columns of k_buf, and o_ref is (KV, rep, v_width).  Then:
    #   m_ref, l_ref  VMEM (KV, rep, 1) running max and denominator
    #   acc_ref       VMEM (KV, rep, dv) f32 accumulator
    #   cur_ref       SMEM (1,) the buffer the next block lands in
    if v_width is None:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, k_sem, v_sem, m_ref, l_ref, acc_ref, cur_ref = refs
        kv = ((k_hbm, k_buf, k_sem), (v_hbm, v_buf, v_sem))
    else:
        k_hbm, o_ref, k_buf, k_sem, m_ref, l_ref, acc_ref, cur_ref = refs
        kv, v_buf = ((k_hbm, k_buf, k_sem),), None
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n_pages = bt_ref.shape[1]
    block_len = pages_per_block * page_size

    def live_len(i):
        return jnp.minimum(len_ref[i], n_pages * page_size)

    def n_blocks(i):
        return (live_len(i) + block_len - 1) // block_len

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
        for _, vmem, _ in kv:
            vmem[...] = jnp.zeros_like(vmem)
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
        v = v_buf[cur] if v_width is None else k_buf[cur, :, :, :v_width]
        pv = jnp.einsum(
            "krt,ktd->krd", prob.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        cur_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, nb, block, 0)
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)  # a slot of length 0 reads zeros
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "v_width", "interpret"))
def paged_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: Optional[jax.Array],
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    v_width: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """One pallas_call over grid (slots,): each step loops over the blocks
    of whole pages its slot's length reaches, copying each page (all KV
    heads) from the pools in HBM by the block table while the previous block
    is computed, across the boundary into the next slot's first block.  In
    the latent layout (`v_pool` None) only K is copied, and V is its first
    `v_width` columns."""
    if not _HAVE_PLTPU:
        raise NotImplementedError(
            "paged_attention_pallas needs jax.experimental.pallas.tpu"
            " (scalar-prefetch grid specs); use the xla_gather impl"
        )
    s, h, hd = q.shape
    n_pool, kvh, ps, hd2 = k_pool.shape
    if hd != hd2:
        raise ValueError(f"head_dim mismatch: q {q.shape} vs pool {k_pool.shape}")
    if v_pool is None:
        if not v_width or v_width > hd:
            raise ValueError(f"latent layout needs 0 < v_width <= {hd}, got {v_width}")
    elif v_pool.shape != k_pool.shape:
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
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
    dv = v_width or hd

    kernel = functools.partial(
        _paged_kernel, page_size=ps, pages_per_block=ppb,
        scale=hd**-0.5 if scale is None else scale, v_width=v_width,
    )
    index = lambda i, bt, ln: (i, 0, 0, 0)  # noqa: E731
    buf = pltpu.VMEM((2, kvh, ppb * ps, hd), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((None, kvh, rep, hd), index),
            *[pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
        ],
        out_specs=pl.BlockSpec((None, kvh, rep, dv), index),
        scratch_shapes=[
            *[buf for _ in pools],
            *[pltpu.SemaphoreType.DMA((2,)) for _ in pools],
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, dv), jnp.float32),
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
        out_shape=jax.ShapeDtypeStruct((s, kvh, rep, dv), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qf, *pools)
    return out.reshape(s, h, dv)


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
    v_pool: Optional[jax.Array],
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    v_width: Optional[int] = None,
    impl: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """Dispatch through the door; resolution happens at trace time (static)."""
    name = resolve_paged_impl(impl, interpret=interpret)
    return _PAGED_REGISTRY[name].fn(
        q, k_pool, v_pool, block_tables, lengths,
        scale=scale, v_width=v_width, interpret=interpret,
    )


register_paged_impl("pallas_paged", paged_attention_pallas, interpret=False)
register_paged_impl("xla_gather", paged_attention_xla, interpret=True)
