"""1 - (the union of the device's op intervals over the traced window),
averaged over the chips (profiler trace)."""

from bench.layer_metrics._common import idle_share


def read(run):
    if run.trace is None:
        return None
    return idle_share(run)
