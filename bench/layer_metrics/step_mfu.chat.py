"""The decode tick's roofline share: over the ticks of the window, the
least time of each tick (the larger of its model operations over peak and
its weight plus live K/V bytes over HBM bandwidth; memory bounds every tick
of this cell) over the summed `serve.decode` spans of the program (host
clock around the jitted step and its host sync)."""

from bench import flops
from bench.layer_metrics._common import serving_ticks, spans_in_window


def read(run):
    ticks = serving_ticks(run)
    spans = spans_in_window(run, "serve.decode")
    if not ticks or not spans:
        return None
    cfg, pk = run.cell.config, run.peaks
    bound = 0.0
    for _, contexts, _ in ticks:
        if contexts:
            w = flops.decode_tick_work(cfg, contexts)
            bound += max(w["ops"] / pk.bf16_flops_per_s, w["bytes"] / pk.hbm_bytes_per_s)
    return 100.0 * bound / sum(s.duration_s for s in spans)
