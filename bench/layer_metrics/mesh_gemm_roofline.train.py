"""The weight GEMMs' least time (forward, dX and dW of every projection and
the head, bf16 operands, flops.gemm_time_bound) over the device time of the
mesh kernel's ops (`mesh_matmul_pallas`) in the traced window, per chip."""

from bench.layer_metrics._common import gemm_bound_s, kernel_s


def read(run):
    if run.trace is None or "steps" not in run.data:
        return None
    t = kernel_s(run, "mesh_matmul_pallas")
    if not t:
        return None
    rows = run.data["batch"] * run.data["seq"] // run.cell.chips
    bound = gemm_bound_s(run.cell.config, rows, run.peaks, passes=3) * run.data["steps"]
    return 100.0 * bound / t
