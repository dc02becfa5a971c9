#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The last line of stdout is one JSON object:
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end metrics,
or with `--trace 1` its per-layer metrics), `device`, with `--trace 1` a
`breakdown`, and last `compared`: each number that decided `correct`, beside
its limit.  The same numbers are the last lines of stderr.

The run exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell needs, or when the checkout holds no program
(`src/repro`).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"no program under {ROOT / 'src'}: nothing to measure")
    sys.path[:1] = [str(ROOT / "src"), str(ROOT)]  # not bench/ itself

    from bench import harness
    from bench.peaks import peaks_for

    cell = harness.find_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.prepare_caches()
    peaks = peaks_for(devices[0].device_kind)

    if cell.mix["kind"] == "train":
        from bench import train_cell as driver
    else:
        from bench import serve_cell as driver
    out = driver.run(cell, args.seed, args.seconds, bool(args.trace), devices, T_START)

    from repro.obs import metrics as obs_metrics

    run = out["run"]
    run.peaks = peaks
    run.data["autotune_s"] = obs_metrics.counter("autotune_timed_seconds_total").total()
    trace_device = breakdown = None
    if args.trace:
        from bench import tracing

        metrics = harness.read_metrics(cell.per_layer, "layer_metrics", run)
        trace_device = tracing.device_times(run)
        breakdown = tracing.breakdown(run)
    else:
        metrics = harness.read_metrics(cell.end_to_end, "metrics", run)
    compared, problems = out["compared"], out["problems"]
    correct = not problems and all(v["ok"] for v in compared.values())
    line = harness.result_line(
        correct=correct,
        attempted=out["attempted"],
        failed=out["failed"],
        metrics=metrics,
        devices=devices,
        memory_peak_bytes=out["memory_peak_bytes"],
        compared=compared,
        problems=problems,
        trace_device=trace_device,
        breakdown=breakdown,
    )
    harness.print_compared(compared, problems)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
