"""The serving GEMMs' least time over the device time of the mesh kernel's
ops (`mesh_matmul_pallas`) in the traced window.  Decode ticks count M = the
slots that decoded, not the padded rows; each prefill counts M = its prompt
length."""

from bench.layer_metrics._common import gemm_bound_s, kernel_s, serving_ticks


def read(run):
    ticks = serving_ticks(run)
    if not ticks:
        return None
    t = kernel_s(run, "mesh_matmul_pallas")
    if not t:
        return None
    cfg, peaks = run.cell.config, run.peaks
    bound = 0.0
    for _, contexts, prefills in ticks:
        if contexts:
            bound += gemm_bound_s(cfg, len(contexts), peaks)
        for p in prefills:
            bound += gemm_bound_s(cfg, p, peaks)
    return 100.0 * bound / t
