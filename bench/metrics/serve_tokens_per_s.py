"""Generated tokens made in the window, over the window (host clock)."""


def read(run):
    if "ticks" not in run.data:
        return None
    return run.data["tokens"] / run.window_s
