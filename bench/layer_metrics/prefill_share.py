"""Device time of the batch-1 prefill programs (`jit_prefill_step`) over
the device time of every serving program in the traced window (XLA Modules
line of the profiler trace).  The program's `serve.prefill` span closes when
the prefill is dispatched, before the device has run it, so the device's
own record is the one read here."""

from bench import trace_reduce as tr


def read(run):
    if run.trace is None or not run.trace.device_modules:
        return None
    lo, hi = run.trace_window
    prefill = total = 0
    for d in run.devices:
        t = tr.module_time_ns(run.trace.device_modules.get(d, []), lo, hi)
        prefill += t.get("jit_prefill_step", 0)
        total += sum(t.values())
    return 100.0 * prefill / total if total else None
