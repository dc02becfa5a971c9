"""Arithmetic the per-layer readers share (not a metric: no file of a
metric starts with `_`)."""

from bench import flops
from bench import trace_reduce as tr


def per_chip_ns(run, fn):
    """fn(device_ops, lo, hi) averaged over the cell's chips; None where the
    trace holds no device (a run on the CPU)."""
    if not run.trace.device_ops:
        return None
    lo, hi = run.trace_window
    vals = [fn(run.trace.device_ops[d], lo, hi) for d in run.devices]
    return sum(vals) / len(vals)


def kernel_s(run, family):
    """Device seconds per chip of one kernel's leaf ops in the window."""
    ns = per_chip_ns(run, lambda ev, lo, hi: tr.op_time_ns(ev, family, lo, hi))
    return None if ns is None else ns / 1e9


def idle_share(run):
    lo, hi = run.trace_window
    busy = per_chip_ns(run, tr.busy_ns)
    return None if busy is None else 100.0 * (1.0 - busy / (hi - lo))


def gemm_bound_s(cfg, m, peaks, passes=1):
    """Least seconds of every weight GEMM at m rows; passes=3 adds the two
    backward products (dX and dW), which do the forward's work each."""
    f, b = peaks.bf16_flops_per_s, peaks.hbm_bytes_per_s
    total = 0.0
    for _, k, n, count in flops.gemm_shapes(cfg):
        fwd = flops.gemm_time_bound(m, k, n, f, b)
        if passes == 3:
            fwd += flops.gemm_time_bound(m, n, k, f, b) + flops.gemm_time_bound(k, m, n, f, b)
        total += count * fwd
    return total


def spans_in_window(run, name):
    t0, t1 = run.data["window_t0"], run.data["window_t1"]
    return [s for s in run.spans if s.name == name and s.t0 >= t0 and s.t1 <= t1]


def serving_ticks(run):
    return run.data.get("ticks") if run.trace is not None else None
