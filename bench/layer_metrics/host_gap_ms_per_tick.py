"""Host time per scheduler tick with nothing on the device: for each
`serve.tick` span on the profiler's host line (the program opens a
TraceAnnotation per span while tracing) inside the traced window, its
length less the device's busy time inside it (trace_reduce.busy_ns), as a
mean over ticks in ms, averaged over the chips (device trace).  None where
the trace holds no tick or no device."""

import bisect

from bench import trace_reduce as tr
from bench.layer_metrics._common import per_chip_ns


def _idle_ns(events, ticks):
    """Summed idle ns of the ticks; busy_ns reads only the stretches of the
    device's busy union that meet each tick."""
    busy = tr._merged((s, s + d) for _, s, d in events)
    ends = [b for _, b in busy]
    total = 0
    for a, b in ticks:
        i = bisect.bisect_right(ends, a)
        near = []
        while i < len(busy) and busy[i][0] < b:
            near.append(("busy", busy[i][0], busy[i][1] - busy[i][0]))
            i += 1
        total += (b - a) - tr.busy_ns(near, a, b)
    return total


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    ticks = [(s, s + d) for n, s, d in run.trace.host
             if n == "serve.tick" and s >= lo and s + d <= hi]
    if not ticks:
        return None
    ns = per_chip_ns(run, lambda events, _lo, _hi: _idle_ns(events, ticks))
    return None if ns is None else ns / len(ticks) / 1e6
