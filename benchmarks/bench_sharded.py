"""ShardedPlan collective-schedule benchmark (ISSUE 4, overlap in ISSUE 10).

In an 8-virtual-CPU-device subprocess: plan one GEMM under every collective
schedule and measure wall time per step next to the plan's own bytes-moved
provenance — the cross-PR artifact (`BENCH_kernels.json` "sharded" section)
that tracks whether schedule choice and the comm model stay sane.  The
unsharded plan runs as the baseline row.

The double-buffered schedules ride the same table: every `*_overlap` /
`pipeline` row is asserted BITWISE-equal to its serial twin (the operands
are integer-valued f32, so accumulation-order differences cannot hide), and
`overlap_efficiency = serial_ms / overlap_ms` is recorded on the row and on
the plan itself (`ShardedPlan.note_overlap_efficiency`).  The fixed
`allgather_a` (compute-once result gather) is asserted within 2x of
`reduce_scatter_k` — the old input-rotation form ran the full-K kernel p
times and sat at ~5x.

CLI: `python -m benchmarks.bench_sharded [--schedule NAME]` — with
`--schedule` only the named schedule (plus its serial twin and the
unsharded baseline) runs: the CI distributed job's overlap smoke.
"""

import argparse
import json
import os
import subprocess
import sys
import textwrap

_PROG = textwrap.dedent(
    """
    import json, os, time
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels import api
    from repro.launch.mesh import make_local_mesh

    M = K = N = 512
    STEPS = 10
    REPS = 4  # best-of-REPS: overlap_efficiency compares two ~10ms numbers,
              # so per-rep noise must not masquerade as a schedule regression
    rng = np.random.default_rng(0)
    # Integer-valued f32 operands: products are exact (max |dot| = 16*512,
    # far below 2^24), so bitwise comparison is meaningful across schedules.
    a = jnp.asarray(rng.integers(-4, 5, size=(M, K)).astype(np.float32))
    b = jnp.asarray(rng.integers(-4, 5, size=(K, N)).astype(np.float32))

    mesh1d = make_local_mesh((8,), ("x",))
    mesh2d = make_local_mesh((4, 2), ("x", "y"))
    # overlap/pipeline rows assert bitwise equality against their serial twin
    TWIN = {
        "allgather_a_overlap": "allgather_a",
        "reduce_scatter_k_overlap": "reduce_scatter_k",
        "ring_k_overlap": "ring_k",
        "pipeline": "reduce_scatter_k",
    }
    cases = [
        ("unsharded", None, None),
        ("replicated_mn", mesh2d, api.ShardSpec.from_mesh(mesh2d, m="x", n="y")),
        ("allgather_a", mesh1d,
         api.ShardSpec.from_mesh(mesh1d, m="x", schedule="allgather_a")),
        ("allgather_a_overlap", mesh1d,
         api.ShardSpec.from_mesh(mesh1d, m="x", schedule="allgather_a_overlap")),
        ("reduce_scatter_k", mesh1d,
         api.ShardSpec.from_mesh(mesh1d, k="x", schedule="reduce_scatter_k")),
        ("reduce_scatter_k_overlap", mesh1d,
         api.ShardSpec.from_mesh(mesh1d, k="x",
                                 schedule="reduce_scatter_k_overlap")),
        ("ring_k", mesh1d,
         api.ShardSpec.from_mesh(mesh1d, k="x", schedule="ring_k")),
        ("ring_k_overlap", mesh1d,
         api.ShardSpec.from_mesh(mesh1d, k="x", schedule="ring_k_overlap")),
        ("pipeline", mesh1d,
         api.ShardSpec.from_mesh(mesh1d, k="x", schedule="pipeline")),
    ]
    only = os.environ.get("REPRO_BENCH_SCHEDULE")
    if only:
        keep = {"unsharded", only, TWIN.get(only, only)}
        cases = [c for c in cases if c[0] in keep]

    rows, outs, times, plans = [], {}, {}, {}
    for name, mesh, shard in cases:
        spec = api.GemmSpec.from_operands(a, b, shard=shard)
        p = api.plan(spec, mesh=mesh)
        out = p(a, b)
        out.block_until_ready()
        ms = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            for _ in range(STEPS):
                out = p(a, b)
            out.block_until_ready()
            ms = min(ms, (time.perf_counter() - t0) / STEPS * 1e3)
        outs[name], times[name], plans[name] = np.asarray(out), ms, p
        sh = p.describe().get("sharding") or {}
        rows.append({
            "case": name,
            "schedule": sh.get("schedule", "-"),
            "overlap": bool(sh.get("overlap", False)),
            "bytes_moved": sh.get("bytes_moved", 0),
            "collective_phases": sh.get("collective_phases", 0),
            "per_shard_flops": sh.get("per_shard_flops", p.flops),
            "ms_per_step": round(ms, 3),
        })

    for r in rows:
        twin = TWIN.get(r["case"])
        if twin is None or twin not in outs:
            continue
        # the serial path is the oracle: outputs must match bit for bit
        assert np.array_equal(outs[r["case"]], outs[twin]), (
            f"{r['case']} output differs from serial twin {twin}")
        eff = times[twin] / times[r["case"]]
        r["overlap_efficiency"] = round(eff, 3)
        plans[r["case"]].note_overlap_efficiency(eff)
    if "allgather_a" in times and "reduce_scatter_k" in times:
        # the compute-once gather must stay in reduce_scatter_k's league
        # (the input-rotation pathology was ~5x)
        assert times["allgather_a"] < 2 * times["reduce_scatter_k"], (
            f"allgather_a {times['allgather_a']:.2f}ms >= 2x reduce_scatter_k "
            f"{times['reduce_scatter_k']:.2f}ms")
    print("SHARDED_JSON " + json.dumps({"mkn": f"{M}x{K}x{N}", "rows": rows}))
    """
)


def _run_subprocess(schedule: str = None) -> dict:
    from repro.launch.mesh import forced_device_env

    env = forced_device_env(8)
    if schedule:
        env["REPRO_BENCH_SCHEDULE"] = schedule
    out = subprocess.run(
        [sys.executable, "-c", _PROG], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=560,
    )
    if out.returncode != 0:
        raise RuntimeError(f"sharded bench subprocess failed: {out.stderr[-2000:]}")
    for line in out.stdout.splitlines():
        if line.startswith("SHARDED_JSON "):
            return json.loads(line[len("SHARDED_JSON "):])
    raise RuntimeError("no SHARDED_JSON line in the sharded bench subprocess output")


def run(as_dict: bool = False, schedule: str = None):
    scope = f", --schedule {schedule}" if schedule else ""
    print(
        "# ShardedPlan collective schedules "
        f"(8 virtual CPU devices, 512^3 GEMM{scope})"
    )
    doc = _run_subprocess(schedule)
    print("case,schedule,bytes_moved,phases,ms_per_step,overlap_efficiency")
    for r in doc["rows"]:
        eff = r.get("overlap_efficiency")
        print(
            f"{r['case']},{r['schedule']},{r['bytes_moved']},"
            f"{r['collective_phases']},{r['ms_per_step']},"
            f"{eff if eff is not None else '-'}"
        )
    return doc if as_dict else True


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--schedule",
        default=None,
        help="run only this schedule (plus its serial twin and the unsharded"
        " baseline); bitwise parity is still asserted",
    )
    args = ap.parse_args()
    run(schedule=args.schedule)
