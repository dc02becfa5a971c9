"""Benchmark harness entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only <name>]

Sections:
  stepcounts   paper Figs 1-2 (2n-1 vs 3n-2) + ICI-torus phase analogue
  scramble     cycle structure/orders (7/7/20 + extension) + S^k throughput
  symmetric    symmetric-product early readout (<= n+1+n/2)
  kernels      mesh-matmul BlockSpec structure + allclose gate + GEMM context
  dispatch     plan/execute dispatch overhead (eager matmul vs pre-built Plan)
  moe          grouped-GEMM expert dispatch vs one-hot einsum (ms + bytes)
  sharded      ShardedPlan collective schedules: bytes-moved + step time
  costmodel    cost-model predicted vs measured ms + schedule-ranking accuracy
  obs          tracing overhead: disabled <2% contract + enabled spans/s
  distributed  Cannon phases, pipeline bubbles, ring-overlap wall-time
  serve        continuous-batching Poisson load: throughput + p50/p99 latency
  train        short real training run (loss trajectory) on the demo config
  roofline     renders the dry-run roofline table (artifacts/pod16x16)
"""

import argparse
import json
import platform
import time
import traceback

from benchmarks import (
    bench_costmodel,
    bench_dispatch,
    bench_distributed,
    bench_kernels,
    bench_moe,
    bench_obs,
    bench_roofline,
    bench_scramble,
    bench_serve,
    bench_sharded,
    bench_stepcounts,
    bench_symmetric,
)
from repro.launch.compile_cache import enable_compile_cache


def bench_train():
    """Short training run: the end-to-end sanity number for the harness."""
    from repro.configs import get_config
    from repro.launch.train import build_trainer

    cfg = get_config("mesh-paper").reduced()
    step_fn, state, data = build_trainer(cfg, batch=8, seq=64, lr=1e-3, total_steps=40)
    losses = []
    t0 = time.perf_counter()
    for _ in range(40):
        state, metrics = step_fn(state, next(data))
        losses.append(float(metrics["loss"]))
    dt = time.perf_counter() - t0
    print("# short training run (mesh-paper reduced, 40 steps)")
    print("steps,first_loss,last_loss,steps_per_s")
    print(f"40,{losses[0]:.4f},{losses[-1]:.4f},{40/dt:.2f}")
    assert losses[-1] < losses[0]
    return losses


SECTIONS = {
    "stepcounts": bench_stepcounts.run,
    "scramble": bench_scramble.run,
    "symmetric": bench_symmetric.run,
    "kernels": bench_kernels.run,
    "dispatch": bench_dispatch.run,
    "moe": bench_moe.run,
    "sharded": bench_sharded.run,
    "costmodel": bench_costmodel.run,
    "obs": bench_obs.run,
    "distributed": bench_distributed.run,
    "serve": bench_serve.run,
    "train": bench_train,
    "roofline": bench_roofline.run,
}


def _write_kernels_json(payload: dict, wall_s: float, out_path: str) -> None:
    """BENCH_kernels.json: the cross-PR perf-trajectory artifact (ISSUE 2).

    Structural metrics + host wall-times + the block shapes the autotuner
    chose, with enough provenance (jax version / backend) to compare runs."""
    import jax

    doc = {
        "version": 1,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "host": platform.machine(),
        "wall_s": round(wall_s, 2),
        **payload,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"[kernels] wrote {out_path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(SECTIONS))
    ap.add_argument(
        "--json",
        action="store_true",
        help="write the kernels section's metrics to BENCH_kernels.json",
    )
    ap.add_argument("--json-path", default="BENCH_kernels.json")
    args = ap.parse_args()
    enable_compile_cache()
    names = [args.only] if args.only else list(SECTIONS)
    if args.json and "kernels" not in names:
        names.append("kernels")
    if args.json and "kernels" in names:
        # the kernels --json branch already runs the dispatch/moe/sharded/
        # serve microbenches for its payload — don't time the same calls twice
        for ride_along in ("dispatch", "moe", "sharded", "costmodel", "obs", "serve"):
            if ride_along in names:
                names.remove(ride_along)
    failed = []
    for name in names:
        print(f"\n{'=' * 72}\n== bench: {name}\n{'=' * 72}")
        t0 = time.perf_counter()
        try:
            if name == "kernels" and args.json:
                payload = bench_kernels.run(as_dict=True)
                # dispatch-overhead + moe-dispatch + sharded-schedule
                # microbenches ride along in the same JSON so
                # BENCH_kernels.json tracks the plan-cache win, the grouped
                # vs one-hot dispatch cost, and per-schedule comm cost
                payload["dispatch"] = bench_dispatch.run(as_dict=True)
                payload["moe"] = bench_moe.run(as_dict=True)
                payload["sharded"] = bench_sharded.run(as_dict=True)
                payload["costmodel"] = bench_costmodel.run(as_dict=True)
                payload["obs"] = bench_obs.run(as_dict=True)
                payload["serve"] = bench_serve.run(as_dict=True)
                _write_kernels_json(payload, time.perf_counter() - t0, args.json_path)
            else:
                SECTIONS[name]()
            print(f"[{name}] done in {time.perf_counter() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        raise SystemExit(f"benchmark sections failed: {failed}")
    print("\nALL BENCHES OK")


if __name__ == "__main__":
    main()
