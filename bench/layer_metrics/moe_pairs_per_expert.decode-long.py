"""Routed (token, expert) pairs each held expert computed a decode tick:
the `held_pairs` attributes of the program's `serve.decode` spans in the
window, summed, over (those ticks x experts held x MoE layers) (program
span).  The program counts the slots that hold a sequence, so the reading
follows the batch a tick decodes: num_experts_per_tok x live slots /
published experts, 3 at 32 full slots.  A deployment whose every chip
decodes as many slots gives each expert eight times that.  None where the
program records no routing counts."""

from bench.layer_metrics._common import spans_in_window


def read(run):
    if run.trace is None:
        return None
    spans = [s for s in spans_in_window(run, "serve.decode") if "held_pairs" in s.attrs]
    if not spans:
        return None
    cfg = run.cell.config
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    pairs = sum(s.attrs["held_pairs"] for s in spans)
    return pairs / (len(spans) * cfg["n_routed_experts"] * moe_layers)
