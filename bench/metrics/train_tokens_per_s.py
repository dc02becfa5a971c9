"""Training tokens over the whole window, across all the cell's chips, per
second of the window (host clock, every step waited for)."""


def read(run):
    if "steps" not in run.data:
        return None
    return run.data["tokens"] / run.window_s
