"""95th percentile, over every request due in the window, of the time from
when the request was due to its first token.  A request with no first token
when the window closes counts with the time it had waited (host clock)."""

import numpy as np


def read(run):
    ttft = run.data.get("ttft_s")
    if not ttft:
        return None
    return float(np.percentile(ttft, 95)) * 1e3
