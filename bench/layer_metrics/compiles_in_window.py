"""Backend compilations (loads from the persistent cache included) that
overlap the window: the program's `jit.compile` spans of phase
`backend_compile` (program span).  None where the program counts no
compiles (no `jit_compiles_total` counter)."""

from repro.obs import metrics


def read(run):
    if run.trace is None or "jit_compiles_total" not in metrics.snapshot():
        return None
    t0, t1 = run.data["window_t0"], run.data["window_t1"]
    return sum(
        1 for s in run.spans
        if s.name == "jit.compile" and s.attrs.get("phase") == "backend_compile"
        and s.t1 >= t0 and s.t0 <= t1
    )
