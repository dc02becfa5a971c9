"""Structured tracing: nestable spans over a process-wide ring buffer.

The repo's timing story used to be ad-hoc ``time.monotonic()`` pairs
scattered across serve/scheduler/autotune/train; this module replaces them
with *spans* — named, nestable intervals with monotonic wall times and
JSON-able attributes — cheap enough to leave in the hot paths permanently.

Design contract (DESIGN.md §14):

  off by default   tracing is a hard opt-in (`enable()` / the `tracing()`
                   scope).  The DISABLED fast path of `span()` is a single
                   attribute check returning a shared no-op span — the
                   overhead budget (<2% on the 10k-iteration microbench) is
                   asserted in tests and tracked in BENCH_kernels.json["obs"].
  nestable         spans nest via a per-thread stack: `parent` links child
                   spans to the enclosing one, so exports reconstruct a
                   request's life (serve.tick -> serve.decode -> ...).
  bounded          finished spans land in one process-wide ring
                   (deque(maxlen)); old spans are dropped, never grown
                   without bound — `stats()["dropped"]` counts the loss.
  thread-safe      the ring, the seq counter, and the end hooks are guarded
                   by one lock; span stacks are thread-local.
  tracer-aware     a span must never fire inside a jitted trace (the same
                   discipline as the non-finite guard in `kernels/api.py`):
                   under tracing `time.monotonic()` would measure *trace*
                   time and the span would fire once per compile, not per
                   execution.  When jax reports an active trace the span is
                   suppressed (counted in `stats()["suppressed_in_trace"]`).
  one clock        while enabled, every span also opens a
                   `jax.profiler.TraceAnnotation` of the same name (no
                   `#k=v#` metadata), so a running profiler shows the spans
                   on the host line beside the device's ops.
  compiles         the first `enable()` registers one jax.monitoring
                   listener that lands JAX's trace and backend-compile
                   events (persistent-cache loads included) as `jit.compile`
                   spans and counts them in `jit_compiles_total{phase}`.

jax is imported lazily: to ask "are we inside a trace?", for the profiler
annotation and for the compile listener.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from repro.obs import metrics as _metrics

__all__ = [
    "Span",
    "clear",
    "configure",
    "disable",
    "enable",
    "is_enabled",
    "on_span_end",
    "record",
    "remove_span_end",
    "span",
    "spans",
    "stats",
    "tracing",
]

DEFAULT_CAPACITY = 65536


class _State:
    """Process-wide tracer state; `enabled` is THE disabled-path check."""

    __slots__ = ("enabled", "capacity", "epoch")

    def __init__(self) -> None:
        self.enabled = False
        self.capacity = DEFAULT_CAPACITY
        # monotonic origin all span times are relative to (stable within a
        # process; exports use it to produce small, diff-friendly offsets)
        self.epoch = time.monotonic()


_STATE = _State()
_LOCK = threading.Lock()
_RING: "collections.deque" = collections.deque(maxlen=DEFAULT_CAPACITY)
_HOOKS: List[Callable[["Span"], None]] = []
_LOCAL = threading.local()
_SEQ = [0]
_STATS = {"started": 0, "finished": 0, "dropped": 0, "suppressed_in_trace": 0}

# Resolved lazily at first enabled span: () -> bool, True when NOT tracing.
_TRACE_CLEAN: Optional[Callable[[], bool]] = None
# Resolved with it: jax.profiler.TraceAnnotation.
_ANNOTATION: Optional[Callable[[str], Any]] = None
_COMPILE_LISTENER = [False]  # registered with jax.monitoring (once)

# JAX's compile events (jax._src.dispatch) and the phase each is recorded as.
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}


def _resolve_trace_clean() -> Callable[[], bool]:
    # JAX keeps this predicate internal; an import failure after an upgrade
    # must raise here rather than silently switch the in-jit guard off.
    global _TRACE_CLEAN, _ANNOTATION
    if _TRACE_CLEAN is None:
        from jax._src.core import trace_state_clean
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
        _TRACE_CLEAN = trace_state_clean
    return _TRACE_CLEAN


class Span:
    """One finished-or-open interval.  Mutable while open (`set()` adds
    attributes mid-span); append-only once it lands in the ring."""

    __slots__ = ("name", "seq", "parent", "tid", "t0", "t1", "attrs", "annotation")

    def __init__(self, name: str, seq: int, parent: Optional[int], tid: int,
                 t0: float, attrs: Dict[str, Any]):
        self.name = name
        self.seq = seq
        self.parent = parent
        self.tid = tid
        self.t0 = t0
        self.t1 = t0
        self.attrs = attrs
        self.annotation = None  # the open profiler annotation, if any

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def set(self, key: str, value: Any) -> None:
        """Attach an attribute discovered mid-span (e.g. a chosen schedule)."""
        self.attrs[key] = value

    def as_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "seq": self.seq,
            "parent": self.parent,
            "tid": self.tid,
            "t0": self.t0,
            "t1": self.t1,
            "duration_s": self.duration_s,
            "attrs": dict(self.attrs),
        }

    def __repr__(self) -> str:
        return (
            f"Span({self.name!r}, seq={self.seq}, parent={self.parent},"
            f" dur={self.duration_s * 1e3:.3f}ms)"
        )

    # -- context-manager protocol -------------------------------------------

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        _end_span(self, exc)
        return False


class _NullSpan:
    """The disabled/suppressed path: every operation is a no-op."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def set(self, key: str, value: Any) -> None:
        pass


_NULL = _NullSpan()


def _stack() -> List[Span]:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def span(name: str, **attrs: Any):
    """Open a span; use as ``with span("plan.execute", backend="xla"): ...``.

    Disabled tracing returns a shared no-op span after ONE attribute check.
    Enabled tracing inside a jitted trace is suppressed (tracer-aware guard).
    """
    if not _STATE.enabled:
        return _NULL
    return _begin(name, attrs)


def _begin(name: str, attrs: Dict[str, Any]):
    if not _resolve_trace_clean()():
        with _LOCK:
            _STATS["suppressed_in_trace"] += 1
        return _NULL
    st = _stack()
    parent = st[-1].seq if st else None
    with _LOCK:
        _SEQ[0] += 1
        seq = _SEQ[0]
        _STATS["started"] += 1
    sp = Span(name, seq, parent, threading.get_ident(), time.monotonic(), attrs)
    sp.annotation = _ANNOTATION(name)
    sp.annotation.__enter__()
    st.append(sp)
    return sp


def _end_span(sp: Span, exc: Optional[BaseException]) -> None:
    sp.t1 = time.monotonic()
    if sp.annotation is not None:
        sp.annotation.__exit__(None, None, None)
        sp.annotation = None
    if exc is not None:
        sp.attrs["error"] = f"{type(exc).__name__}: {exc}"
    st = _stack()
    # Tolerate out-of-order exits (a span leaked across a raise): pop up to
    # and including this span if present, else leave the stack alone.
    if sp in st:
        while st and st.pop() is not sp:
            pass
    _land(sp)


def record(name: str, t0: float, t1: float, **attrs: Any) -> None:
    """Land an already-finished span [t0, t1] (monotonic seconds) in the
    ring, as a root, without touching the thread's span stack.

    For intervals that no `with` block can hold: a request's wait for its
    first token spans several ticks, and a compile is timed by JAX.  Such a
    span opens no profiler annotation, since it is not one stretch of this
    thread's work.  Disabled tracing records nothing."""
    if not _STATE.enabled:
        return
    with _LOCK:
        _SEQ[0] += 1
        seq = _SEQ[0]
        _STATS["started"] += 1
    sp = Span(name, seq, None, threading.get_ident(), t0, attrs)
    sp.t1 = t1
    _land(sp)


def _land(sp: Span) -> None:
    with _LOCK:
        _STATS["finished"] += 1
        if _RING.maxlen is not None and len(_RING) == _RING.maxlen:
            _STATS["dropped"] += 1
        _RING.append(sp)
        hooks = list(_HOOKS)
    for fn in hooks:
        try:
            fn(sp)
        except Exception:
            pass  # a broken hook must never take the traced path down


# ---------------------------------------------------------------------------
# Switches + introspection
# ---------------------------------------------------------------------------


def enable(capacity: Optional[int] = None) -> None:
    """Turn tracing on (optionally resizing the ring); the first call also
    registers the compile listener."""
    if capacity is not None:
        configure(capacity=capacity)
    _listen_for_compiles()
    _STATE.enabled = True


def _compile_counter() -> "_metrics.Counter":
    return _metrics.counter(
        "jit_compiles_total", "JAX compile events while tracing was on",
        labels=("phase",),
    )


def _listen_for_compiles() -> None:
    _compile_counter()  # present (at 0) wherever compiles are counted
    with _LOCK:
        if _COMPILE_LISTENER[0]:
            return
        _COMPILE_LISTENER[0] = True
    from jax import monitoring

    monitoring.register_event_time_span_listener(_on_compile_event)


def _on_compile_event(event: str, start_time: float, end_time: float, **kwargs: Any) -> None:
    """JAX's compile events (wall-clock `time.time()` stamps) as `jit.compile`
    spans on the ring's monotonic clock, attrs `fun` and `phase`."""
    if not _STATE.enabled:
        return
    phase = _COMPILE_PHASES.get(event)
    if phase is None:
        return
    shift = time.monotonic() - time.time()
    record("jit.compile", start_time + shift, end_time + shift,
           fun=str(kwargs.get("fun_name", "")), phase=phase)
    _compile_counter().inc(phase=phase)


def disable() -> None:
    _STATE.enabled = False


def is_enabled() -> bool:
    return _STATE.enabled


class tracing:
    """Scoped enable: ``with tracing(): ...`` restores the prior state."""

    def __init__(self, capacity: Optional[int] = None):
        self._capacity = capacity
        self._prior = False

    def __enter__(self) -> "tracing":
        self._prior = _STATE.enabled
        enable(self._capacity)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        _STATE.enabled = self._prior
        return False


def configure(*, capacity: int) -> None:
    """Resize the ring (keeps the newest spans that still fit)."""
    global _RING
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    with _LOCK:
        _STATE.capacity = capacity
        _RING = collections.deque(_RING, maxlen=capacity)


def spans(name: Optional[str] = None) -> List[Span]:
    """Snapshot of finished spans, oldest first (optionally filtered)."""
    with _LOCK:
        got = list(_RING)
    return got if name is None else [s for s in got if s.name == name]


def stats() -> Dict[str, int]:
    with _LOCK:
        d = dict(_STATS)
        d["retained"] = len(_RING)
        d["capacity"] = _STATE.capacity
    return d


def clear() -> None:
    """Test hook: drop finished spans and reset counters (keeps `enabled`)."""
    with _LOCK:
        _RING.clear()
        _SEQ[0] = 0
        for k in _STATS:
            _STATS[k] = 0


def on_span_end(fn: Callable[[Span], None]) -> None:
    """Register a finished-span hook (the obs bridge feeds calibration
    through this).  Hooks run outside the lock; exceptions are swallowed."""
    with _LOCK:
        if fn not in _HOOKS:
            _HOOKS.append(fn)


def remove_span_end(fn: Callable[[Span], None]) -> None:
    with _LOCK:
        if fn in _HOOKS:
            _HOOKS.remove(fn)
