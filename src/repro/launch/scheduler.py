"""Continuous-batching serve loop over a paged KV cache (DESIGN.md §12).

`launch/serve.generate` serves one batch at a time: every request in the
batch prefilled together, decoded in lockstep, and the whole batch held
until its slowest member finishes.  This module replaces that with the
serving loop the paper's repeated-product pipelining actually wants: a
fixed set of decode *slots* advances one token per tick, and sequences are
admitted into and retired out of slots **every step** — a finished request
frees its slot (and KV pages) immediately for the next queued request.

KV state is a paged pool per layer (fixed-size pages, per-sequence block
tables, host-side free-list allocator — `PageAllocator`), attended through
`kernels/paged_attention` (Pallas gather kernel on TPU, bitwise `_sdpa`
-mirroring XLA gather elsewhere).  Page 0 is reserved scratch: empty slots
carry an all-zero block table and harmlessly read/write it.

Robustness is the contract, built on PR 6's machinery (DESIGN.md §11):

  admission     bounded queue; overflow and never-fits requests are SHED
                (`serve.shed` ledger events), never queued forever
  deadlines     per-request tick budgets; expired requests — queued or
                running — are evicted and their pages reclaimed
                (`serve.timeout`)
  preemption    page-allocator exhaustion evicts the lowest-priority
                (youngest among ties) running sequence and retries
                (`serve.preempt`); a victimless failure evicts the
                requester itself, so the loop always makes progress
  fault sites   `serve.admit` (fires -> that request is shed),
                `serve.step` (fires -> the tick is skipped, not the
                server), `kv.page_alloc` (fires -> the allocation is
                deferred/stalled one tick and retried) — all wired into
                the `ci-default` chaos plan
  warmup        server start builds a guarded canary GEMM plan (consuming
                any armed plan.build / plan.execute / kernel.output
                triggers outside the serving traces) and pre-traces
                prefill + decode steps so no request pays a compile
  drain         `drain()` / context-manager exit runs the loop until every
                admitted request has retired (graceful shutdown)

Families: dense / moe / vlm serve through the paged path; ssm (rwkv)
carries its O(1) recurrent state stacked per slot — same admission /
deadline / shedding ladder, no pages to allocate.  hybrid / audio are not
schedulable here (enc-dec or mixed state) and are rejected up front.

The decode step is ONE jitted call at a fixed (max_slots,) shape — slot
occupancy changes never retrace.  The pools are the ones the model
declares (`paged_pool_specs`: K and V, or a latent pool per layer stack),
all addressed by the same pages, and the decode step and the admission
scatter DONATE them: each updates the pools in place, writing one row a
slot and layer (or the prompt's pages), and the server keeps the buffers
it returns.  `serve_pool_in_place_total{step="decode"|"scatter"}` counts
the calls after which the pools handed in read deleted.  A `serve.step`
fault fires before the step runs, so a skipped tick keeps its pools.  An
exception raised inside a jitted step propagates as before, but the pools
it was handed are gone with it: the server cannot decode again and must
be rebuilt (no recovery path holds a second copy of the pools).

MoE routing: the prefill and decode steps also return how many routed
(token, expert) pairs landed on the experts this model holds, fetched with
the step's tokens in the sync the step makes anyway.  It is recorded as a
`held_pairs` attribute on `serve.prefill` and `serve.decode` and counted in
`moe_routed_pairs_total{share="held"|"elsewhere"}`.  A decode step counts
the slots that hold a sequence (a block table whose first page is not the
scratch page 0), not the idle slots it computes alongside.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ShardCtx
from repro.obs import metrics as _metrics
from repro.obs import trace as _obs
from repro.resilience import faults, ledger

__all__ = [
    "ContinuousBatchingServer",
    "PageAllocator",
    "PagesExhausted",
    "Request",
    "RequestResult",
    "ServeConfig",
]

_SCHEDULABLE = ("dense", "moe", "vlm", "ssm")


class PagesExhausted(RuntimeError):
    """Free-list is smaller than the requested allocation."""


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Scheduler capacity + policy knobs (all counts, no wall-clock)."""

    max_slots: int = 4  # concurrent decode lanes (the batched step's S)
    page_size: int = 8  # tokens per KV page
    num_pages: int = 64  # pool size INCLUDING the reserved scratch page 0
    max_pages_per_seq: int = 8  # block-table width
    queue_capacity: int = 16  # bounded admission queue
    default_deadline: int = 512  # ticks from submission before eviction
    impl: Optional[str] = None  # paged-attention impl (None = capability door)
    interpret: bool = False  # Pallas interpret mode for the paged kernel
    warmup_prompt_lens: Tuple[int, ...] = ()  # prefill shapes to pre-trace

    def __post_init__(self):
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {self.max_slots}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is reserved scratch), got"
                f" {self.num_pages}"
            )
        if self.max_pages_per_seq < 1 or self.queue_capacity < 1:
            raise ValueError(f"invalid capacities in {self}")


@dataclasses.dataclass(frozen=True)
class Request:
    rid: str
    prompt: np.ndarray  # (T,) int32 token ids
    max_new_tokens: int
    priority: int = 0  # higher survives preemption longer
    deadline: Optional[int] = None  # ticks from submission (None = config)
    arrival: int = 0  # tick at which `run()` submits this request


@dataclasses.dataclass
class RequestResult:
    rid: str
    status: str  # "ok" | "shed" | "timeout" | "preempted"
    tokens: List[int]  # generated tokens (possibly partial on eviction)
    reason: str = ""
    submitted_tick: int = -1
    finished_tick: int = -1
    latency_s: float = 0.0


class PageAllocator:
    """Host-side free-list over pool pages 1..num_pages-1 (0 = scratch).

    `alloc` is a fault site (`kv.page_alloc`): an injected failure surfaces
    exactly like transient exhaustion and the scheduler retries next tick.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"need >= 2 pages (page 0 is scratch), got {num_pages}")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))  # pop() -> 1 first

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int, *, reason: str, rid: str = "") -> List[int]:
        faults.check("kv.page_alloc", reason=reason, rid=rid)
        if n > len(self._free):
            raise PagesExhausted(
                f"need {n} pages, {len(self._free)} free (rid={rid!r}, {reason})"
            )
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if not 0 < p < self.num_pages:
                raise ValueError(f"page {p} out of range (pool {self.num_pages})")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
            self._free.append(p)


@dataclasses.dataclass(eq=False)
class _Seq:
    """One admitted sequence occupying a decode slot.

    Identity semantics (eq=False): membership checks against `_active` must
    mean "this exact sequence object is still live", never field equality.
    """

    req: Request
    slot: int
    pages: List[int]
    pos: int  # next write position == current length (incl. vlm patches)
    tokens: List[int]
    deadline_tick: int
    admit_tick: int
    submitted_tick: int
    submitted_at: float
    first_at: float  # host clock when the first token reached the host
    last_at: float  # ... and when the latest one did
    stalled: bool = False  # page-alloc fault this tick: skip, retry next


class ContinuousBatchingServer:
    """Admit/step/retire serving loop; see the module docstring.

    Typical use::

        server = ContinuousBatchingServer(model, params, ServeConfig(...))
        server.warmup()
        results = server.run(requests)      # or submit() + step() + drain()
    """

    def __init__(self, model, params, cfg: ServeConfig, ctx: ShardCtx = ShardCtx()):
        fam = model.cfg.family
        if fam not in _SCHEDULABLE:
            raise NotImplementedError(
                f"family {fam!r} is not schedulable (supported: {_SCHEDULABLE});"
                " audio is enc-dec (frames batch), hybrid carries mixed"
                " KV+conv state"
            )
        self.model = model
        self.params = params
        self.cfg = cfg
        self.ctx = ctx
        self._paged = model.supports_paged  # dense/moe/vlm; ssm stacks state
        self._patch_offset = (
            model.cfg.num_stub_patches if fam == "vlm" else 0
        )
        self._tick = 0
        self._queue: List[Tuple[Request, int, float]] = []  # (req, tick, t_submit)
        self._active: List[_Seq] = []
        self._free_slots = list(range(cfg.max_slots - 1, -1, -1))
        self.results: Dict[str, RequestResult] = {}
        self.counters = {
            "served": 0, "shed": 0, "timeout": 0, "preempted": 0,
            "ticks": 0, "skipped_ticks": 0, "decode_tokens": 0,
        }
        # Obs instruments (DESIGN.md §14): the typed, label-aware mirror of
        # `self.counters` — process-global (labels aggregate across server
        # instances) where the dict above stays per-instance for tests.
        self._m_requests = _metrics.counter(
            "serve_requests_total", "request outcomes by status",
            labels=("status",),
        )
        self._m_admitted = _metrics.counter(
            "serve_admitted_total", "requests admitted into decode slots")
        self._m_ticks = _metrics.counter(
            "serve_ticks_total", "scheduler ticks by outcome",
            labels=("outcome",),
        )
        self._m_tokens = _metrics.counter(
            "serve_decode_tokens_total", "tokens produced by decode ticks")
        self._m_ttft = _metrics.histogram(
            "serve_ttft_seconds", "submission -> first token on the host")
        self._m_tpot = _metrics.histogram(
            "serve_tpot_seconds",
            "served request's mean gap between tokens after its first")
        self._m_in_place = _metrics.counter(
            "serve_pool_in_place_total",
            "steps after which the KV pools handed in were consumed in place",
            labels=("step",),
        )

        mcfg = model.cfg
        # Routed pairs a token makes over the MoE layers (the leading dense
        # layers route nothing).
        self._pairs_per_token = (
            mcfg.num_experts_per_tok * (mcfg.num_layers - mcfg.first_k_dense)
            if mcfg.is_moe else 0
        )
        if self._pairs_per_token:
            self._m_routed = _metrics.counter(
                "moe_routed_pairs_total",
                "routed (token, expert) pairs, by whether this server holds the expert",
                labels=("share",),
            )

        if self._paged:
            self.alloc = PageAllocator(cfg.num_pages)
            self.pools = {
                name: jnp.zeros(s.shape, s.dtype)
                for name, s in model.paged_pool_specs(
                    cfg.num_pages, cfg.page_size
                ).items()
            }
        else:
            self.alloc = None
            specs = model.decode_state_specs(cfg.max_slots, 0)
            self.state = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), specs
            )

        self._build_steps()

    # -- jitted steps (traced once; shapes never change across ticks) -------

    def _build_steps(self):
        model, ctx, cfg = self.model, self.ctx, self.cfg
        # Prefill shares launch/serve's per-(model, ctx) jitted-step cache:
        # the scheduler and the legacy driver reuse one trace per shape.
        from repro.launch.serve import serving_steps

        self._prefill, _ = serving_steps(model, ctx)

        if self._paged:
            impl, interpret = cfg.impl, cfg.interpret

            def decode(params, tokens, pools, block_tables, positions):
                logits, pools, routed = model.paged_decode_routed(
                    params, tokens, pools, block_tables, positions, ctx,
                    impl=impl, interpret=interpret,
                )
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                live = block_tables[:, 0] > 0  # idle slots read scratch page 0
                routed = {k: jnp.sum(jnp.where(live, v, 0)) for k, v in routed.items()}
                return nxt, pools, routed

            # Donated: the step writes one row a slot and layer into the
            # pools in place (a failure inside it loses them: module doc).
            self._decode = jax.jit(decode, donate_argnums=(2,))

            def scatter(pools, caches, pages):
                # caches: the prefill's {name: (L, 1, T, KV, hd)}, one per
                # pool; pages: (n,) ids.  T is padded up to n*page_size; the
                # zero tail is masked by `lengths` in attention and
                # overwritten as decode proceeds.
                def put(pool, c):
                    layers, _, t, kvh, hd = c.shape
                    n = pages.shape[0]
                    ps = pool.shape[3]
                    c2 = jnp.pad(c[:, 0], [(0, 0), (0, n * ps - t), (0, 0), (0, 0)])
                    c2 = c2.reshape(layers, n, ps, kvh, hd).transpose(0, 1, 3, 2, 4)
                    return pool.at[:, pages].set(c2.astype(pool.dtype))

                return {name: put(pool, caches[name]) for name, pool in pools.items()}

            # One trace per (T, n) pair; pools donated, as for decode.
            self._scatter = jax.jit(scatter, donate_argnums=(0,))
        else:

            def decode_ssm(params, tokens, state):
                # rwkv decode is position-free; state rows are per-slot.
                logits, state = model.decode(params, tokens, state, jnp.int32(0), ctx)
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                return nxt, state, {}

            self._decode = jax.jit(decode_ssm)

            def insert(state, new, slot):
                return jax.tree.map(
                    lambda st, nw: st.at[:, slot].set(nw[:, 0].astype(st.dtype)),
                    state,
                    new,
                )

            self._insert_state = jax.jit(insert)

    # -- capacity arithmetic -------------------------------------------------

    def _prefill_len(self, req: Request) -> int:
        return int(req.prompt.shape[0]) + self._patch_offset

    def _pages_for(self, length: int) -> int:
        return -(-length // self.cfg.page_size)  # ceil

    def _deadline_ticks(self, req: Request) -> int:
        # `is not None`, not truthiness: an explicit deadline=0 means "expire
        # immediately", not "use the default".
        return req.deadline if req.deadline is not None else self.cfg.default_deadline

    def _fits(self, req: Request) -> Optional[str]:
        """None if the request can ever be served, else the shed reason."""
        total = self._prefill_len(req) + req.max_new_tokens
        if not self._paged:
            return None
        if self._pages_for(total) > self.cfg.max_pages_per_seq:
            return "too_long:block_table"
        if self._pages_for(total) > self.cfg.num_pages - 1:
            return "too_long:pool"
        return None

    def _count_routed(self, span, routed, tokens: int) -> None:
        """Record a step's routing counts, already on the host."""
        if not routed:
            return
        held = int(np.sum(routed["held_pairs"]))
        span.set("held_pairs", held)
        self._m_routed.inc(held, share="held")
        self._m_routed.inc(tokens * self._pairs_per_token - held, share="elsewhere")

    # -- lifecycle events ----------------------------------------------------

    def _finish(self, rid: str, status: str, tokens: List[int], *,
                reason: str, submitted_tick: int, submitted_at: float) -> None:
        self.results[rid] = RequestResult(
            rid=rid,
            status=status,
            tokens=tokens,
            reason=reason,
            submitted_tick=submitted_tick,
            finished_tick=self._tick,
            latency_s=time.monotonic() - submitted_at,
        )
        key = {"ok": "served", "shed": "shed", "timeout": "timeout",
               "preempted": "preempted"}[status]
        self.counters[key] += 1
        self._m_requests.inc(status=key)

    def _shed(self, req: Request, reason: str, *, submitted_tick: int,
              submitted_at: float) -> None:
        ledger.record("serve.shed", cause=reason, fallback="shed", rid=req.rid)
        self._finish(req.rid, "shed", [], reason=reason,
                     submitted_tick=submitted_tick, submitted_at=submitted_at)

    def _evict(self, seq: _Seq, status: str, reason: str) -> None:
        if status == "ok" and len(seq.tokens) > 1:
            self._m_tpot.observe((seq.last_at - seq.first_at) / (len(seq.tokens) - 1))
        if self._paged and seq.pages:
            self.alloc.free(seq.pages)
            seq.pages = []  # retired sequences must never grow or double-free
        self._free_slots.append(seq.slot)
        self._active.remove(seq)
        self._finish(seq.req.rid, status, seq.tokens, reason=reason,
                     submitted_tick=seq.submitted_tick,
                     submitted_at=seq.submitted_at)

    # -- submission ----------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Enqueue a request; over-capacity and never-fits are shed NOW."""
        # The span starts with the request's submission time (`now`).
        with _obs.span("serve.submit", rid=req.rid):
            now = time.monotonic()
            if req.rid in self.results or any(
                q.rid == req.rid for q, _, _ in self._queue
            ) or any(s.req.rid == req.rid for s in self._active):
                raise ValueError(f"duplicate request id {req.rid!r}")
            reason = self._fits(req)
            if reason is not None:
                self._shed(req, reason, submitted_tick=self._tick, submitted_at=now)
                return
            if len(self._queue) >= self.cfg.queue_capacity:
                self._shed(req, "queue_full", submitted_tick=self._tick,
                           submitted_at=now)
                return
            self._queue.append((req, self._tick, now))

    # -- the tick ------------------------------------------------------------

    def step(self) -> None:
        """One scheduler tick: expire, admit, grow, decode, retire."""
        self._tick += 1
        self.counters["ticks"] += 1
        # The per-tick span nests everything the tick does, each piece of
        # host work in a child span, and costs one attribute check per span
        # when tracing is off; exports flush at drain/exit, never here.
        with _obs.span("serve.tick", tick=self._tick,
                       active=len(self._active), queued=len(self._queue)):
            try:
                faults.check("serve.step", tick=self._tick)
            except Exception as e:  # injected: skip the tick, keep the server
                ledger.record(
                    "serve.step",
                    cause=f"{type(e).__name__}: {e}",
                    fallback="skip_tick",
                    tick=self._tick,
                )
                self.counters["skipped_ticks"] += 1
                self._m_ticks.inc(outcome="skipped")
                return
            self._m_ticks.inc(outcome="ok")

            with _obs.span("serve.expire"):
                self._expire_deadlines()
            self._admit()
            with _obs.span("serve.grow"):
                self._ensure_pages()
            self._decode_tick()

    def _expire_deadlines(self) -> None:
        for seq in list(self._active):
            if self._tick >= seq.deadline_tick:
                ledger.record(
                    "serve.timeout", cause="deadline", fallback="evict",
                    rid=seq.req.rid, tick=self._tick,
                )
                self._evict(seq, "timeout", "deadline")
        still = []
        for req, tick, t0 in self._queue:
            ddl = tick + self._deadline_ticks(req)
            if self._tick >= ddl:
                ledger.record(
                    "serve.timeout", cause="deadline_queued", fallback="evict",
                    rid=req.rid, tick=self._tick,
                )
                self._finish(req.rid, "timeout", [], reason="deadline_queued",
                             submitted_tick=tick, submitted_at=t0)
            else:
                still.append((req, tick, t0))
        self._queue = still

    def _admit(self) -> None:
        while self._queue and self._free_slots:
            req, submitted_tick, submitted_at = self._queue[0]
            try:
                faults.check("serve.admit", rid=req.rid)
            except Exception as e:  # injected: this request is shed
                self._queue.pop(0)
                self._shed(req, f"{type(e).__name__}: {e}",
                           submitted_tick=submitted_tick,
                           submitted_at=submitted_at)
                continue

            prefill_len = self._prefill_len(req)
            pages: List[int] = []
            if self._paged:
                # Optimistic admission: pages for the prompt plus the first
                # decode token; growth pages are claimed tick by tick (and
                # contended through preemption).
                n0 = self._pages_for(prefill_len + 1)
                try:
                    pages = self.alloc.alloc(n0, reason="admit", rid=req.rid)
                except PagesExhausted:
                    break  # wait for retirements; deadline bounds the wait
                except Exception as e:  # injected: defer one tick
                    ledger.record(
                        "kv.page_alloc",
                        cause=f"{type(e).__name__}: {e}",
                        fallback="defer_admission",
                        rid=req.rid,
                    )
                    break

            self._queue.pop(0)
            slot = self._free_slots.pop()
            with _obs.span("serve.admit", rid=req.rid):
                t_admit = time.monotonic()
                with _obs.span("serve.prefill", rid=req.rid, tokens=prefill_len) as sp_prefill:
                    first_tok, state, routed = self._run_prefill(req)
                self._m_admitted.inc()
                with _obs.span("serve.scatter", rid=req.rid):
                    if self._paged:
                        self._keep_pools("scatter", self._scatter(
                            self.pools, state, jnp.asarray(pages, jnp.int32)
                        ))
                    else:
                        self.state = self._insert_state(
                            self.state, state, jnp.int32(slot)
                        )
                # The prefill emits the first token greedily; it is on the
                # host once this sync returns.
                with _obs.span("serve.first_sync", rid=req.rid):
                    tok, routed = jax.device_get((first_tok[0], routed))
                    tok = int(tok)
                t_first = time.monotonic()
                self._count_routed(sp_prefill, routed, prefill_len)
            self._m_ttft.observe(t_first - submitted_at)
            _obs.record(
                "serve.first_token", submitted_at, t_first, rid=req.rid,
                prompt_len=int(req.prompt.shape[0]), queued_s=t_admit - submitted_at,
            )
            seq = _Seq(
                req=req,
                slot=slot,
                pages=pages,
                pos=prefill_len,
                tokens=[tok],
                deadline_tick=submitted_tick + self._deadline_ticks(req),
                admit_tick=self._tick,
                submitted_tick=submitted_tick,
                submitted_at=submitted_at,
                first_at=t_first,
                last_at=t_first,
            )
            self._active.append(seq)
            if len(seq.tokens) >= req.max_new_tokens:
                self._evict(seq, "ok", "")

    def _run_prefill(self, req: Request):
        cfg = self.model.cfg
        prompts = jnp.asarray(req.prompt, jnp.int32)[None, :]
        batch = {"tokens": prompts, "labels": prompts}
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (1, cfg.num_stub_patches, cfg.d_model), cfg.adtype
            )
        return self._prefill(self.params, batch)

    def _ensure_pages(self) -> None:
        """Every active sequence needs page pos//page_size before decoding."""
        if not self._paged:
            return
        for seq in list(self._active):
            # An earlier sequence's _preempt_for may have evicted this one
            # (identity check: _Seq is eq=False); a retired sequence must not
            # claim fresh pages — they would leak — or preempt live peers.
            if seq not in self._active:
                continue
            seq.stalled = False
            needed = seq.pos // self.cfg.page_size + 1
            while len(seq.pages) < needed:
                try:
                    seq.pages += self.alloc.alloc(1, reason="grow", rid=seq.req.rid)
                except PagesExhausted:
                    if not self._preempt_for(seq):
                        # seq itself was the victim: stop growing IT, but the
                        # remaining active sequences still need their pages
                        # before this tick decodes (a missed growth here would
                        # silently write KV through scratch page 0).
                        break
                except faults.FaultError as e:
                    # Transient (injected) allocator failure: the sequence
                    # sits out this tick and retries, it is NOT evicted.
                    ledger.record(
                        "kv.page_alloc",
                        cause=f"{type(e).__name__}: {e}",
                        fallback="stall",
                        rid=seq.req.rid,
                    )
                    seq.stalled = True
                    break

    def _preempt_for(self, seq: _Seq) -> bool:
        """Evict the lowest-priority (youngest among ties) active sequence to
        free pages for `seq`.  Returns False iff `seq` itself was the victim
        (the caller must stop growing it)."""
        victim = min(self._active, key=lambda s: (s.req.priority, -s.admit_tick))
        ledger.record(
            "serve.preempt",
            cause="pages_exhausted",
            fallback="evict",
            rid=victim.req.rid,
            for_rid=seq.req.rid,
            tick=self._tick,
        )
        self._evict(victim, "preempted", f"pages_exhausted(for={seq.req.rid})")
        return victim is not seq

    def decode_inputs(self):
        """Host arrays for the next decode tick over the non-stalled active
        sequences: (ready, tokens (S, 1), positions (S,), block tables
        (S, max_pages_per_seq) or None when the family has no pages)."""
        ready = [s for s in self._active if not s.stalled]
        s_max = self.cfg.max_slots
        tokens = np.zeros((s_max, 1), np.int32)
        positions = np.zeros((s_max,), np.int32)
        tables = (
            np.zeros((s_max, self.cfg.max_pages_per_seq), np.int32)
            if self._paged
            else None
        )
        for seq in ready:
            tokens[seq.slot, 0] = seq.tokens[-1]
            positions[seq.slot] = seq.pos
            if self._paged:
                tables[seq.slot, : len(seq.pages)] = seq.pages
        return ready, tokens, positions, tables

    def _keep_pools(self, step: str, pools) -> None:
        """Hold the pools a donating step returned; count the step as in
        place when the buffers it was handed read deleted."""
        if all(p.is_deleted() for p in self.pools.values()):
            self._m_in_place.inc(step=step)
        self.pools = pools

    def _decode_tick(self) -> None:
        with _obs.span("serve.inputs"):
            ready, tokens, positions, tables = self.decode_inputs()
        if not ready:
            return
        # The decode span covers the upload, the jitted step AND the host
        # sync (device_get blocks): the tick's decode wall time.
        with _obs.span("serve.decode", slots=len(ready), tick=self._tick) as sp:
            with _obs.span("serve.upload"):
                tokens = jnp.asarray(tokens)
                if self._paged:
                    tables = jnp.asarray(tables)
                    positions = jnp.asarray(positions)
            if self._paged:
                nxt, pools, routed = self._decode(
                    self.params, tokens, self.pools, tables, positions
                )
                self._keep_pools("decode", pools)
            else:
                nxt, self.state, routed = self._decode(self.params, tokens, self.state)
            with _obs.span("serve.decode_sync"):
                nxt, routed = jax.device_get((nxt, routed))
            self._count_routed(sp, routed, len(ready))
        now = time.monotonic()
        self.counters["decode_tokens"] += len(ready)
        self._m_tokens.inc(len(ready))
        with _obs.span("serve.retire"):
            for seq in ready:
                seq.tokens.append(int(nxt[seq.slot]))
                seq.last_at = now
                seq.pos += 1
                if len(seq.tokens) >= seq.req.max_new_tokens:
                    self._evict(seq, "ok", "")

    # -- driving -------------------------------------------------------------

    @property
    def pending(self) -> int:
        return len(self._queue) + len(self._active)

    def warmup(self) -> None:
        """Build the canary plan and pre-trace serving steps (no request
        pays a compile, and any armed plan.build / plan.execute /
        kernel.output fault triggers are consumed OUTSIDE the serving
        traces — a NaN poison lands in the guarded canary, not baked into
        the decode-step jit program)."""
        from repro.kernels import api

        # Pre-resolve the cost model's coefficients (one calibration-file
        # read, memoized) so plan-time auto decisions inside a serving tick
        # never touch the filesystem (DESIGN.md §13).
        try:
            from repro.costmodel import current_coefficients

            current_coefficients()
        except Exception:
            pass  # planner degrades to defaults on its own

        # The canary runs on the backend the model's GEMMs use, so a server
        # whose projections ride the mesh kernel plans nothing else.
        a = jnp.ones((8, 8), jnp.float32)
        canary = api.plan(
            api.GemmSpec.from_operands(a, a, blocks=(8, 8, 8)),
            backend="pallas_mesh" if self.model.cfg.use_mesh_kernel else None,
            guard_nonfinite="zero_and_record",
        )
        # Async dispatch (DESIGN.md §15): the cold compile proceeds in the
        # background while the prefill/decode warmups below build their own
        # traces; the handle is collected after.  The guarded canary
        # host-syncs inside execution anyway (documented dispatch caveat),
        # but the call path exercises `Plan.dispatch` on every serve startup.
        cold = canary.dispatch(a, a)
        cold.block()
        # Second execution is compile-free: when tracing is on, its
        # plan.execute span is the warm sample the obs bridge feeds to
        # cost-model calibration (the cold first call is discarded).
        jax.block_until_ready(canary(a, a))

        for t in self.cfg.warmup_prompt_lens:
            dummy = Request(rid=f"__warmup_{t}", prompt=np.zeros(t, np.int32),
                            max_new_tokens=1)
            self._run_prefill(dummy)
        s_max = self.cfg.max_slots
        tokens = jnp.zeros((s_max, 1), jnp.int32)
        positions = jnp.zeros((s_max,), jnp.int32)
        if self._paged:
            # All-zero tables: the step writes only the scratch pages.  It
            # consumes the pools it is given, so the server keeps the ones
            # it returns.
            tables = jnp.zeros((s_max, self.cfg.max_pages_per_seq), jnp.int32)
            _, self.pools, _ = self._decode(self.params, tokens, self.pools, tables, positions)
        else:
            self._decode(self.params, tokens, self.state)

    def drain(self, *, max_ticks: int = 1_000_000) -> None:
        """Run until every admitted request has retired (graceful shutdown).
        Liveness is deadline-bounded: even permanently stalled sequences are
        evicted when their tick budget runs out."""
        ticks = 0
        while self.pending:
            self.step()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(f"drain exceeded {max_ticks} ticks")
        # Drain is the scheduler's I/O point (DESIGN.md §14): ticks never
        # touch the filesystem, so buffered span->calibration records are
        # folded into the cost-model cache here, after the loop empties.
        from repro.obs import bridge as _bridge

        if _bridge.installed():
            _bridge.flush_calibration()

    def run(self, requests: Sequence[Request]) -> Dict[str, RequestResult]:
        """Submit `requests` at their arrival ticks, drive to completion."""
        todo = sorted(requests, key=lambda r: r.arrival)
        i = 0
        while i < len(todo) or self.pending:
            while i < len(todo) and todo[i].arrival <= self._tick:
                self.submit(todo[i])
                i += 1
            self.step()
        return dict(self.results)

    def __enter__(self) -> "ContinuousBatchingServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.drain()
