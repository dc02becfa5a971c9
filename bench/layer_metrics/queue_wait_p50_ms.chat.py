"""Median over requests of the time from when the request was due to the
start of its `serve.prefill` span (host monotonic clock, program span)."""

import numpy as np

from bench.layer_metrics._common import spans_in_window


def read(run):
    due = run.data.get("due")
    spans = spans_in_window(run, "serve.prefill") if run.trace is not None else []
    waits = [s.t0 - due[s.attrs["rid"]] for s in spans if s.attrs.get("rid") in due]
    if not waits:
        return None
    return float(np.median(waits)) * 1e3
