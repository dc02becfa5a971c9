"""Small cells for rehearsing the harness on the CPU: the same drivers,
files and comparisons as the chip's cells, at sizes a test run holds."""

from __future__ import annotations

import copy
import json

from bench import harness

SMALL_CONFIG = {
    "hidden_size": 256,
    "num_attention_heads": 2,
    "num_key_value_heads": 2,
    "head_dim": 128,
    "intermediate_size": 512,
    "vocab_size": 512,
    "num_hidden_layers": 2,
}


def small_cell(name: str, **mix_overrides) -> harness.Cell:
    """The BENCHMARK.json cell `name`, shrunk: its configuration cut to
    SMALL_CONFIG in float32, its traffic to a few requests or rows.  A cell
    kept for later (`<configuration>.<mix>`, with its limits file) that
    BENCHMARK.json does not list yet is found the same way."""
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    if name not in {w["name"] for w in spec["workloads"]}:
        config, mix = name.split(".", 1)
        spec["workloads"].append({"name": name, "config": config, "traffic": mix, "chips": 1})
    cell = harness.find_cell(name, spec=spec)
    cfg = copy.deepcopy(cell.config)
    # On the CPU the paged attention resolves to its XLA path, by design.
    cfg.update(SMALL_CONFIG, torch_dtype="float32", paged_impl="xla_gather")
    mix = copy.deepcopy(cell.mix)
    if mix["kind"] == "train":
        mix.update(batch_per_chip=2, seq=256, feed_batches=4)
    else:
        mix.update(prompt_lens=[8, 16], prompt_weights=[0.5, 0.5], slots=4, max_pages_per_seq=8, cycle=8)
        mix["output"] = {"dist": "uniform", "min": 3, "max": 8}
        mix["check"] = {"min_tokens": 16, "max_requests": 3}
        if mix["kind"] == "serve_open":
            mix["rate_per_s"] = 50.0
    mix.update(mix_overrides)
    cell.config, cell.mix = cfg, mix
    return cell
