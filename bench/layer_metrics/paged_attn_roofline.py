"""Live K/V bytes the decode ticks must read (each slot's cache over every
layer, flops.kv_bytes_per_token) at HBM bandwidth, over the device time of
the paged kernel's ops (`paged_attention_pallas`) in the traced window:
memory bound."""

from bench import flops
from bench.layer_metrics._common import kernel_s, serving_ticks


def read(run):
    ticks = serving_ticks(run)
    if not ticks:
        return None
    t = kernel_s(run, "paged_attention_pallas")
    if not t:
        return None
    per = flops.kv_bytes_per_token(run.cell.config)
    moved = sum(per * sum(contexts) for _, contexts, _ in ticks)
    return 100.0 * moved / run.peaks.hbm_bytes_per_s / t
