"""Model operations of every prefill and decode token of the window, over
the window times the bf16 peak (host clock)."""

from bench import flops
from bench.layer_metrics._common import serving_ticks


def read(run):
    ticks = serving_ticks(run)
    if not ticks:
        return None
    cfg = run.cell.config
    ops = 0.0
    for _, contexts, prefills in ticks:
        if contexts:
            ops += flops.decode_tick_work(cfg, contexts)["ops"]
        ops += sum(flops.prefill_ops(cfg, p) for p in prefills)
    return 100.0 * ops / run.window_s / run.peaks.bf16_flops_per_s
