"""Cost-model prediction quality benchmark (ISSUE 8, DESIGN.md §13).

In an 8-virtual-CPU-device subprocess: plan the BENCH 512^3 GEMM under each
K-collective schedule the planner chooses between, time it, and report
predicted-vs-measured ms plus RANKING accuracy (top-1 + pairwise) — the
number that tells us whether the model orders schedules correctly even when
its absolute scale is off (uncalibrated hosts).  The same run records the
auto-sharding decision for the unsharded spec and asserts the model ranks
reduce_scatter_k ahead of allgather_a (the gather re-runs the full-K kernel
p times for identical bytes moved) — the `BENCH_kernels.json["costmodel"]`
section is the cross-PR artifact tracking both.
"""

import json
import os
import subprocess
import sys
import tempfile
import textwrap

_PROG = textwrap.dedent(
    """
    import itertools, json, time
    import jax, jax.numpy as jnp, numpy as np
    from repro.costmodel import current_coefficients, predict, terms_from_describe
    from repro.costmodel import choose as _choose
    from repro.kernels import api
    from repro.launch.mesh import make_local_mesh
    from repro import obs

    # Tracing + bridge live for the whole bench: every eager p(a, b) below
    # emits a plan.execute span that the bridge converts into a calibration
    # record — this is the multi-device lane ROADMAP 2(a) was missing.
    obs.enable()
    obs.install()

    M = K = N = 512
    STEPS = 10
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))

    mesh = make_local_mesh((8,), ("x",))
    coeffs = current_coefficients()
    cases = [
        ("allgather_a", api.ShardSpec.from_mesh(mesh, m="x", schedule="allgather_a")),
        ("reduce_scatter_k",
         api.ShardSpec.from_mesh(mesh, k="x", schedule="reduce_scatter_k")),
        ("ring_k", api.ShardSpec.from_mesh(mesh, k="x", schedule="ring_k")),
    ]
    rows = []
    for name, shard in cases:
        spec = api.GemmSpec.from_operands(a, b, shard=shard)
        p = api.plan(spec, mesh=mesh)
        p(a, b).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = p(a, b)
        out.block_until_ready()
        ms = (time.perf_counter() - t0) / STEPS * 1e3
        terms = terms_from_describe(p.describe())
        pred = predict(terms, coeffs)
        rows.append({
            "schedule": name,
            "predicted_ms": round(pred["total_s"] * 1e3, 4),
            "measured_ms": round(ms, 3),
            "ratio": round(ms / (pred["total_s"] * 1e3), 2),
        })
        # the bench's own blocked-and-timed number is the highest-quality
        # sample; submit it alongside the bridge's per-execute spans
        obs.submit_calibration([{
            "terms": terms, "ms": ms, "source": "bench_costmodel",
            "key": f"{M}x{K}x{N}|" + terms.get("backend", "?"),
        }])

    # ranking accuracy: does the model ORDER the schedules like the clock?
    by_pred = sorted(rows, key=lambda r: r["predicted_ms"])
    by_meas = sorted(rows, key=lambda r: r["measured_ms"])
    pairs = list(itertools.combinations(range(len(rows)), 2))
    agree = sum(
        1 for i, j in pairs
        if (rows[i]["predicted_ms"] < rows[j]["predicted_ms"])
        == (rows[i]["measured_ms"] < rows[j]["measured_ms"])
    )
    ranking = {
        "top1_predicted": by_pred[0]["schedule"],
        "top1_measured": by_meas[0]["schedule"],
        "top1_correct": by_pred[0]["schedule"] == by_meas[0]["schedule"],
        "pairwise_accuracy": round(agree / len(pairs), 3),
    }

    # the auto-sharding decision for the UNSHARDED spec (pure model, no
    # timing): reduce_scatter_k must outrank allgather_a on this mesh
    spec = api.GemmSpec.from_operands(a, b)
    _, dec = _choose.decide_sharding(spec, mesh)
    d = dec.as_dict()
    order = [c["name"] for c in d["candidates"] if c.get("legal")]
    rs = next(i for i, n in enumerate(order) if n.startswith("reduce_scatter_k"))
    ag = next(i for i, n in enumerate(order) if n.startswith("allgather_a"))
    assert rs < ag, f"model ranked allgather_a over reduce_scatter_k: {order}"
    auto = {
        "chosen": d["chosen"],
        "rank_reduce_scatter_k": rs,
        "rank_allgather_a": ag,
        "rs_before_ag": rs < ag,
        "calibration": d["calibration"],
    }
    # fold the buffered measurements (bench submissions + bridged
    # plan.execute spans) into the scratch calibration cache and refit:
    # link_bytes_per_s / phase_latency_s now come from THIS host's
    # multi-device timings, not shipped defaults
    pre = current_coefficients()
    ingested = obs.flush_calibration()
    post = current_coefficients()
    calibration = {
        "ingested": ingested,
        "source": post.source,
        "link_bytes_per_s": post.link_bytes_per_s,
        "phase_latency_s": post.phase_latency_s,
        "link_moved": post.link_bytes_per_s != pre.link_bytes_per_s,
        "spans": obs.stats()["finished"],
    }
    print("COSTMODEL_JSON " + json.dumps({
        "mkn": f"{M}x{K}x{N}", "rows": rows, "ranking": ranking, "auto": auto,
        "calibration": calibration,
    }))
    """
)


def _run_subprocess() -> dict:
    from repro.launch.mesh import forced_device_env

    env = forced_device_env(8)
    # scratch calibration cache: the bench must neither read a stale repo
    # fit nor leave one behind
    with tempfile.TemporaryDirectory() as td:
        env["REPRO_COSTMODEL_CACHE"] = os.path.join(td, "costmodel.json")
        out = subprocess.run(
            [sys.executable, "-c", _PROG], capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=560,
        )
    if out.returncode != 0:
        raise RuntimeError(f"cost-model bench subprocess failed: {out.stderr[-2000:]}")
    for line in out.stdout.splitlines():
        if line.startswith("COSTMODEL_JSON "):
            return json.loads(line[len("COSTMODEL_JSON "):])
    raise RuntimeError("no COSTMODEL_JSON line in the cost-model bench subprocess output")


def run(as_dict: bool = False):
    print("# Cost model predicted vs measured (8 virtual CPU devices, 512^3 GEMM)")
    doc = _run_subprocess()
    print("schedule,predicted_ms,measured_ms,ratio")
    for r in doc["rows"]:
        print(f"{r['schedule']},{r['predicted_ms']},{r['measured_ms']},{r['ratio']}")
    rk, auto = doc["ranking"], doc["auto"]
    print(
        f"ranking: top1_predicted={rk['top1_predicted']}"
        f" top1_measured={rk['top1_measured']}"
        f" top1_correct={rk['top1_correct']}"
        f" pairwise_accuracy={rk['pairwise_accuracy']}"
    )
    print(
        f"auto-shard: chosen={auto['chosen']}"
        f" rs_rank={auto['rank_reduce_scatter_k']}"
        f" ag_rank={auto['rank_allgather_a']}"
        f" source={auto['calibration']['source']}"
    )
    cal = doc.get("calibration", {})
    if cal:
        print(
            f"calibration: ingested={cal['ingested']} source={cal['source']}"
            f" link_bytes_per_s={cal['link_bytes_per_s']:.3g}"
            f" link_moved={cal['link_moved']} spans={cal['spans']}"
        )
    return doc if as_dict else True


if __name__ == "__main__":
    run()
