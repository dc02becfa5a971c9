"""Seconds of timed block search in set-up, the program's
`autotune_timed_seconds_total` counter (0 once the autotune cache kept in
the checkout holds every GEMM shape of the cell)."""


def read(run):
    return run.data.get("autotune_s")
