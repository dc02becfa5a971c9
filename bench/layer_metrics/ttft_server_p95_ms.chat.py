"""95th percentile, over every request due in the window, of the program's
own time to first token: the `serve.first_token` span, from the request's
submission to its first token on the host (program span).  A due request
with no first token counts from its submission (the start of its
`serve.submit` span, or its due time if it was never submitted) to the
window's close.  None where the program records no first tokens."""

import numpy as np


def read(run):
    due = run.data.get("due")
    if run.trace is None or not due:
        return None
    t_close = run.data["window_t1"]
    first, submitted = {}, {}
    for s in run.spans:
        rid = s.attrs.get("rid")
        if rid not in due:
            continue
        if s.name == "serve.first_token" and s.t1 <= t_close:
            first[rid] = s.duration_s
        elif s.name == "serve.submit":
            submitted[rid] = s.t0
    if not first:
        return None
    waits = [first[r] if r in first else t_close - submitted.get(r, t_due) for r, t_due in due.items()]
    return float(np.percentile(waits, 95)) * 1e3
