#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and the
faults', at the cell's own size on the chip, one process for many seeds.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --what program,control,faults

The benchmark's own runs never run this.  Training cells need no window:
`program` drives the checked steps and compares them with the float32
reference; `control` puts the reference computed with float8 (e4m3) matrix
products in the program's place; `faults` puts the reference with part of
each batch left out in its place (half the rows; on several chips also each
chip's own rows alone, which is what a step without the gradient exchange
computes on the first chip; and the weights left as they were, which is
what a step that returns its state unchanged computes).  Only `program`
needs the cell's chips; the references run on one.  The unchanged state
reads 1 on grad_gap and update_gap by their definition; its loss_gap is
read.  Serving
cells run a short window at the cell's own load (`--seconds`), and read the
program's and the control's served-token gaps on the same sample.  One JSON
line per reading goes to stdout.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control,faults")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:1] = [str(ROOT / "src"), str(ROOT)]

    from bench import harness

    cell = harness.find_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    what = set(args.what.split(","))
    # The references run on one chip, a block of rows at a time; only the
    # program needs the cell's chips.
    training = cell.mix["kind"] == "train"
    devices = harness.require_chips(cell.chips if "program" in what or not training else 1)
    harness.prepare_caches()

    def emit(**kw):
        print(json.dumps({"workload": cell.name, **kw}), flush=True)

    if training:
        from bench import train_cell as tc

        mix, cfg, chips = cell.mix, cell.config, cell.chips
        from repro.models import get_model

        abstract = get_model(harness.arch_config(cfg)).abstract_params()
        for seed in seeds:
            # The references first, each alone on the chip: the float8
            # control's program needs most of its memory.
            t0 = time.monotonic()
            ref = tc.reference_record(cfg, mix, abstract, seed, chips)
            if "control" in what:
                low = tc.reference_record(cfg, mix, abstract, seed, chips, numerics="fp8")
                emit(seed=seed, kind="control_fp8", **tc.readings(low, ref))
            if "faults" in what:
                batch = mix["batch_per_chip"] * chips
                rows = {"half_batch": batch // 2}
                if chips > 1:
                    rows["no_exchange"] = mix["batch_per_chip"]
                for name, n in rows.items():
                    bad = tc.reference_record(cfg, mix, abstract, seed, chips, rows_used=n)
                    emit(seed=seed, kind=f"fault_{name}", **tc.readings(bad, ref))
                # A step that returns its state unchanged: every loss is the
                # first weights' loss on that step's batch.  Its grad_gap and
                # update_gap read 1 by definition; only its loss_gap is read.
                still = dict(mix, optimizer=dict(mix["optimizer"], lr=0.0))
                bad = tc.reference_record(cfg, still, abstract, seed, chips)
                emit(seed=seed, kind="fault_frozen", loss_gap=tc.readings(bad, ref)["loss_gap"])
            if "program" in what:
                ctx = tc.build(cell, seed)
                prog = tc.checked_steps(cell, seed, ctx)
                del ctx
                gc.collect()
                emit(seed=seed, kind="program", **tc.readings(prog, ref))
            emit(seed=seed, kind="seconds", value=time.monotonic() - t0)
    else:
        from bench import serve_cell as sc

        for seed in seeds:
            out = sc.run(cell, seed, args.seconds, False, devices, time.monotonic(),
                         control="fp8" if "control" in what else None)
            emit(seed=seed, kind="serve", problems=out["problems"],
                 **{k: v["value"] for k, v in out["compared"].items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
