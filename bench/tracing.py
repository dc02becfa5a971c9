"""The traced window of a `--trace 1` run: the JAX profiler and the
program's own spans on, and their readings handed to the metric readers.

Off (`--trace 0`), nothing is started and the window runs as it would in a
deployment.  On, the profiler records the device's ops and the host's
`TraceAnnotation` spans on one clock, and `repro.obs.trace` records the
program's spans (`serve.tick`, `serve.prefill`, `serve.decode`, ...) on the
host's monotonic clock.  Python's own tracer stays off: it would slow every
call the host makes.
"""

from __future__ import annotations

import shutil
from pathlib import Path
from typing import Any, Dict, Optional

from bench import trace_reduce as tr
from bench.harness import BENCH

__all__ = ["Tracing", "breakdown", "device_times"]

OUT = BENCH / "out"


class Tracing:
    def __init__(self, enabled: bool, cell: str, out: Path = OUT):
        self.enabled = enabled
        self.dir = out / f"trace-{cell}"
        self.trace: Optional[tr.Trace] = None
        self.spans = []

    def __enter__(self) -> "Tracing":
        if self.enabled:
            import jax
            from jax.profiler import ProfileOptions

            from repro.obs import trace as obs

            shutil.rmtree(self.dir, ignore_errors=True)
            obs.configure(capacity=1 << 20)
            obs.clear()
            obs.enable()
            opts = ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.enabled:
            import jax

            from repro.obs import trace as obs

            jax.profiler.stop_trace()
            obs.disable()
            self.spans = obs.spans()
            if exc_type is None:
                self.trace = tr.load(self.dir)
        return False

    def fill(self, run) -> None:
        """Hand the readings to the run record."""
        if self.trace is not None:
            run.trace = self.trace
            run.trace_window = self.trace.window("bench.window")
            run.spans = self.spans
            shutil.rmtree(self.dir, ignore_errors=True)


def device_times(run) -> Dict[str, float]:
    """busy_s (device seconds with an op running, averaged over the chips)
    and window_s (the traced window's length)."""
    lo, hi = run.trace_window
    busy = [tr.busy_ns(run.trace.device_ops[d], lo, hi) for d in run.devices]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9}


def breakdown(run) -> Dict[str, Any]:
    """The device ops that took most time (seconds per chip) and the longest
    idle gaps of the first chip, labelled by what the host was doing."""
    lo, hi = run.trace_window
    acc: Dict[str, float] = {}
    for d in run.devices:
        for name, s in tr.top_ops(run.trace.device_ops[d], lo, hi, n=10_000):
            acc[name] = acc.get(name, 0.0) + s / len(run.devices)
    ops = sorted(acc.items(), key=lambda kv: -kv[1])[:10]
    d0 = run.devices[0]
    gaps = tr.idle_gaps(run.trace.device_ops[d0], run.trace.host, lo, hi, n=10)
    return {"device_ops": [list(x) for x in ops], "idle_gaps": [list(x) for x in gaps]}
