"""95th percentile, over requests with three or more tokens in the window,
of each request's mean gap between output tokens after the first (host
clock; a token is stamped at the end of the tick that made it, and the first
two share their admitting tick, so requests with two tokens say nothing)."""

import numpy as np


def read(run):
    tpot = run.data.get("tpot_s")
    if not tpot:
        return None
    return float(np.percentile(tpot, 95)) * 1e3
