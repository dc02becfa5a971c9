"""Model operations per step (6 per matmul parameter per token plus
attention, flops.train_step_ops) times steps per second of the window, over
the chips' bf16 peak (host clock)."""


def read(run):
    if "step_ops" not in run.data:
        return None
    ops = run.data["step_ops"]["total"] * run.data["steps"]
    return 100.0 * ops / run.window_s / (run.cell.chips * run.peaks.bf16_flops_per_s)
