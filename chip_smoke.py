"""Drive the main path once on TPU: mesh-paper training and paged serving.

    python chip_smoke.py             # one chip: train + serve at full width
    python chip_smoke.py --chips 4   # four chips: sharded GEMM plans and
                                     # data-parallel training, nothing else

One chip runs `mesh-paper` (configs/mesh_paper.py) at its published width
(4 layers, d_model 2048, 16 heads, d_ff 8192, vocab 32768, bf16, every GEMM
on the Pallas mesh kernel) through the entry points a user calls:

  train   `launch.train.build_trainer`, seq 2048 so the scrambling kernel
          runs on its square 16x16 block grid; a few AdamW steps whose
          losses must be finite.
  serve   `launch.scheduler.ContinuousBatchingServer`: warmup, then 8
          requests (prompt 128, 32 new tokens) that must all end "ok".  On
          one decode tick with every slot busy, the logits of the
          `pallas_paged` kernel are compared with the `xla_gather` path.

Four chips run the `reduce_scatter_k`, `allgather_a` and `ring_k` sharded
plans on `pallas_mesh` against the unsharded plan on device 0 (integer-valued
f32 operands, so equality is exact), and a few train steps under the
`local-dp` mesh against the same batch's loss on one device.  Both check that
the outputs are placed on all four devices.

The run fails (non-zero exit, no result line) when the platform is not TPU,
`REPRO_FAULT_PLAN` is set, the resilience ledger recorded any degradation,
any cached GEMM plan is not on `pallas_mesh` or runs in interpret mode, or
the paged attention did not resolve to `pallas_paged`.  Autotune and
cost-model caches start empty under chiprun_out/chip_smoke/, so the timed
block search is part of set-up.  Every line but the last names the device;
the last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.costmodel import current_coefficients  # noqa: E402
from repro.kernels import api  # noqa: E402
from repro.kernels.paged_attention import resolve_paged_impl  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.launch.scheduler import (  # noqa: E402
    ContinuousBatchingServer,
    Request,
    ServeConfig,
)
from repro.launch.train import build_trainer  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.resilience import faults, ledger  # noqa: E402

# Logits of the two paged paths differ only through the attention output,
# which both round to bf16 before `wo`; a one-ulp (2^-8) change there moves a
# logit by a few bf16 ulps of the logits' own scale.  The bound is 2^-5 of
# the largest logit: eight such ulps.
PAGED_REL_TOL = 2.0**-5


def device_label() -> str:
    d = jax.devices()
    return f"{d[0].platform}/{d[0].device_kind} x{len(d)}"


def log(msg: str) -> None:
    print(f"[chip_smoke {device_label()}] {msg}", flush=True)


def prepare(out_dir: Path) -> None:
    """Fresh autotune and cost-model caches under `out_dir`, so nothing
    cached elsewhere steers the run; refuse an armed fault plan."""
    if os.environ.get(faults.ENV_PLAN):
        raise SystemExit(f"{faults.ENV_PLAN} is set; the smoke run injects no faults")
    out_dir.mkdir(parents=True, exist_ok=True)
    for name, env in (
        ("autotune_cache.json", "REPRO_AUTOTUNE_CACHE"),
        ("costmodel_cache.json", "REPRO_COSTMODEL_CACHE"),
    ):
        path = out_dir / name
        path.unlink(missing_ok=True)
        os.environ[env] = str(path)


def autotune_seconds() -> float:
    """Wall time of the timed block searches so far, compiles included."""
    return obs_metrics.counter("autotune_timed_seconds_total").total()


def train_phase(cfg, *, batch: int, seq: int, steps: int, mesh=None) -> dict:
    """Build the trainer and run `steps` steps; step 0 includes compilation
    and the timed block search.  Losses must be finite."""
    t0 = time.perf_counter()
    step_fn, state, data = build_trainer(
        cfg, batch=batch, seq=seq, mesh=mesh, total_steps=max(steps, 10)
    )
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t0
    tune0 = autotune_seconds()
    losses, step_s, batches = [], [], []
    for _ in range(steps):
        b = next(data)
        batches.append(b)
        t0 = time.perf_counter()
        state, metrics = step_fn(state, b)
        jax.block_until_ready((state, metrics))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite training loss: {losses}")
    return {
        "setup_s": setup_s,
        "first_step_s": step_s[0],
        "autotune_s": autotune_seconds() - tune0,
        "step_s": step_s[1:],
        "losses": losses,
        "tokens_per_step": batch * seq,
        "state": state,
        "step_fn": step_fn,
        "batches": batches,
    }


def serve_phase(
    cfg,
    *,
    n_requests: int = 8,
    prompt_len: int = 128,
    gen: int = 32,
    slots: int = 4,
    page_size: int = 16,
    interpret: bool = False,
    seed: int = 0,
) -> dict:
    """Serve `n_requests` through the scheduler; every status must be "ok".
    On the first tick with every slot busy, compare the paged kernel's
    logits with the XLA gather path's."""
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    pages_per_seq = -(-(prompt_len + gen) // page_size)
    scfg = ServeConfig(
        max_slots=slots,
        page_size=page_size,
        num_pages=1 + slots * pages_per_seq,
        max_pages_per_seq=pages_per_seq,
        queue_capacity=n_requests,
        warmup_prompt_lens=(prompt_len,),
        interpret=interpret,
    )
    paged_impl = resolve_paged_impl(scfg.impl, interpret=interpret)
    server = ContinuousBatchingServer(model, params, scfg)
    t0, tune0 = time.perf_counter(), autotune_seconds()
    server.warmup()
    warmup_s = time.perf_counter() - t0
    tune_s = autotune_seconds() - tune0

    rng = np.random.default_rng(seed + 1)
    for r in range(n_requests):
        prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
        server.submit(Request(rid=f"req{r}", prompt=prompt, max_new_tokens=gen))
    compare = None
    t0 = time.perf_counter()
    while server.pending:
        if compare is None and len(server.decode_inputs()[0]) == slots:
            compare = compare_paged(server, interpret=interpret)
            t0 += compare.pop("seconds")  # keep the comparison out of the rate
        server.step()
    serve_s = time.perf_counter() - t0
    if compare is None:
        raise RuntimeError("no decode tick had every slot busy")
    bad = {rid: r.status for rid, r in server.results.items() if r.status != "ok"}
    if bad or len(server.results) != n_requests:
        raise RuntimeError(f"requests not served: {bad or server.results.keys()}")
    return {
        "paged_impl": paged_impl,
        "warmup_s": warmup_s,
        "autotune_s": tune_s,
        "serve_s": serve_s,
        "requests": n_requests,
        "decode_tokens": server.counters["decode_tokens"],
        "ticks": server.counters["ticks"],
        **compare,
    }


def compare_paged(server, *, interpret: bool) -> dict:
    """Max |logit difference| of one decode tick, pallas_paged vs xla_gather,
    over the busy slots; raises past PAGED_REL_TOL of the logits' scale."""
    t0 = time.perf_counter()
    ready, tokens, positions, tables = server.decode_inputs()
    model, ctx = server.model, server.ctx
    rows = [s.slot for s in ready]
    out = {}
    for impl in ("pallas_paged", "xla_gather"):
        step = jax.jit(
            lambda p, t, pools, bt, pos, impl=impl: model.paged_decode(
                p, t, pools, bt, pos, ctx, impl=impl, interpret=interpret
            )[0]
        )
        lg = step(server.params, tokens, server.pools, tables, positions)
        out[impl] = np.asarray(lg[rows, -1], np.float32)
    scale = float(np.max(np.abs(out["xla_gather"])))
    err = float(np.max(np.abs(out["pallas_paged"] - out["xla_gather"])))
    tol = PAGED_REL_TOL * scale
    if not err <= tol:
        raise RuntimeError(
            f"pallas_paged logits differ from xla_gather by {err} (tolerance {tol})"
        )
    return {
        "paged_max_err": err,
        "paged_tol": tol,
        "paged_logit_scale": scale,
        "seconds": time.perf_counter() - t0,
    }


def health_problems(paged_impl, *, interpret: bool = False) -> list:
    """What would have hidden a failure: ledger events, plans off the mesh
    kernel or in the wrong interpret mode, a paged path other than the kernel."""
    problems = []
    if ledger.count():
        problems.append(ledger.format_summary("ledger:"))
    for p in api.plan_cache_info()["plans"]:
        active = p["health"]["active_backend"]
        if active != "pallas_mesh" or p["interpret"] != interpret:
            problems.append(
                f"plan {p['mkn']} {p['structure']}: active backend {active},"
                f" interpret={p['interpret']}"
            )
    if paged_impl is not None and paged_impl != "pallas_paged":
        problems.append(f"paged attention resolved to {paged_impl}")
    return problems


def sharded_phase(n: int = 4, size: int = 2048, seed: int = 0) -> dict:
    """The three ring schedules on `pallas_mesh` over an n-device mesh,
    each equal to the unsharded plan on device 0 and placed on all n."""
    mesh = make_local_mesh((n,), ("x",))
    rng = np.random.default_rng(seed)
    # Integer-valued operands: every product and partial sum is exact in
    # f32 (|sum| <= 16 * size < 2^24), so the schedules must agree bitwise.
    a = rng.integers(-4, 5, size=(size, size)).astype(np.float32)
    b = rng.integers(-4, 5, size=(size, size)).astype(np.float32)
    dev0 = jax.devices()[0]
    on_tpu = dev0.platform == "tpu"  # interpret mode has no kernel op
    ref_plan = api.plan(api.GemmSpec.from_operands(a, b), backend="pallas_mesh")
    ref = np.asarray(ref_plan(jax.device_put(a, dev0), jax.device_put(b, dev0)))
    # Operands replicated on the mesh once, so a call moves nothing from the host.
    ad, bd = jax.device_put((a, b), jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))
    rows = {}
    for sched, axes in (
        ("reduce_scatter_k", {"k": "x"}),
        ("allgather_a", {"m": "x"}),
        ("ring_k", {"k": "x"}),
    ):
        shard = api.ShardSpec.from_mesh(mesh, schedule=sched, **axes)
        p = api.plan(
            api.GemmSpec.from_operands(a, b, shard=shard),
            backend="pallas_mesh",
            mesh=mesh,
        )
        t0 = time.perf_counter()
        out = jax.block_until_ready(p(ad, bd))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(p(ad, bd))
        call_s = time.perf_counter() - t0
        devices = {s.device.id for s in out.addressable_shards}
        if len(devices) != n or len(out.sharding.device_set) != n:
            raise RuntimeError(f"{sched}: output on devices {sorted(devices)}")
        # A replicated output proves placement, not division of the work:
        # the compiled SPMD program must run the kernel and pass the ring.
        hlo = jax.jit(lambda x, y: p(x, y)).lower(ad, bd).compile().as_text()
        if "collective-permute" not in hlo or (on_tpu and "tpu_custom_call" not in hlo):
            raise RuntimeError(f"{sched}: compiled program lacks the ring or the kernel")
        if not np.array_equal(np.asarray(out), ref):
            raise RuntimeError(f"{sched}: differs from the unsharded plan")
        rows[sched] = {
            "first_call_s": first_s,
            "call_s": call_s,
            "sharding": str(out.sharding.spec),
            "devices": sorted(devices),
            "bytes_moved": p.describe()["sharding"]["bytes_moved"],
        }
    return rows


def dp_train_phase(cfg, *, n: int, batch: int, seq: int, steps: int) -> dict:
    """`train.main --mesh local-dp`'s mesh; the first step's loss must match
    the same batch's loss on one device, and every step's state must live on
    all n devices with the gradient all-reduce in the compiled step."""
    mesh = make_local_mesh((n, 1), ("data", "model"))
    res = train_phase(cfg, batch=batch, seq=seq, steps=steps, mesh=mesh)
    state, step_fn, batch0 = res.pop("state"), res.pop("step_fn"), res.pop("batches")[0]
    placed = {d.id for leaf in jax.tree.leaves(state) for d in leaf.sharding.device_set}
    if len(placed) != n:
        raise RuntimeError(f"train state on devices {sorted(placed)}")
    hlo = step_fn.lower(state, batch0).compile().as_text()
    on_tpu = jax.devices()[0].platform == "tpu"  # interpret mode has no kernel op
    if "all-reduce" not in hlo or (on_tpu and "tpu_custom_call" not in hlo):
        raise RuntimeError("compiled DP step lacks the all-reduce or the mesh kernel")

    # Reference: step 0's loss is the initial parameters' loss on batch0;
    # recompute it on one device, a per-device share at a time (the loss is
    # a mean over equal shares).
    model = get_model(cfg)
    params0 = model.init(jax.random.PRNGKey(0))  # build_trainer's seed-0 init
    loss_fn = jax.jit(lambda p, b: model.loss(p, b)[0])
    share = batch // n
    ref = np.mean([
        float(loss_fn(params0, {k: v[i * share:(i + 1) * share] for k, v in batch0.items()}))
        for i in range(n)
    ])
    err = float(abs(res["losses"][0] - ref))
    # Per-row work is identical on both sides; only the mean's summation
    # order differs, so the losses agree to f32 rounding of a mean of
    # bf16-computed terms.
    if not err <= 1e-3 * abs(ref):
        raise RuntimeError(f"DP loss {res['losses'][0]} vs one device {ref}")
    res.update(devices=sorted(placed), ref_loss=float(ref), loss_err=err)
    return res


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    dev = jax.devices()
    if dev[0].platform != "tpu":
        raise SystemExit(f"chip_smoke runs on TPU only; JAX found {dev[0].platform}")
    if len(dev) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, found {len(dev)}")
    prepare(OUT_DIR)
    cache = enable_compile_cache()
    log(f"compile cache {cache}; autotune/cost-model caches in {OUT_DIR}")
    co = current_coefficients()  # raises for a TPU kind without published peaks
    log(f"cost-model peaks: {co.flops_per_s} FLOP/s, {co.hbm_bytes_per_s} HBM B/s")
    cfg = get_config("mesh-paper")
    summary = {"device": device_label(), "chips": args.chips}

    paged_impl = None
    if args.chips == 1:
        tr = train_phase(cfg, batch=4, seq=2048, steps=4)
        for k in ("state", "step_fn", "batches"):
            tr.pop(k)
        log(
            f"train mesh-paper batch=4 seq=2048: setup {tr['setup_s']}s, first step"
            f" (compile + timed autotune {tr['autotune_s']}s) {tr['first_step_s']}s,"
            f" steps {tr['step_s']}s,"
            f" {tr['tokens_per_step'] / np.median(tr['step_s'])} tokens/s,"
            f" losses {tr['losses']}"
        )
        sv = serve_phase(cfg)
        paged_impl = sv["paged_impl"]
        log(
            f"serve mesh-paper {sv['requests']} requests (prompt 128, gen 32, 4 slots)"
            f" via {paged_impl}: all ok; warmup {sv['warmup_s']}s (timed autotune"
            f" {sv['autotune_s']}s), serve"
            f" {sv['serve_s']}s, {sv['ticks']} ticks,"
            f" {sv['decode_tokens'] / sv['serve_s']} decode tokens/s"
        )
        log(
            f"paged decode logits pallas_paged vs xla_gather: max err"
            f" {sv['paged_max_err']} (bf16 tolerance {sv['paged_tol']},"
            f" logit scale {sv['paged_logit_scale']})"
        )
        summary.update(train=tr, serve=sv)
    else:
        sh = sharded_phase(n=4)
        for sched, r in sh.items():
            log(
                f"sharded {sched} 2048^3 f32 on pallas_mesh: equal to unsharded,"
                f" output {r['sharding']} on devices {r['devices']},"
                f" first call (compile included) {r['first_call_s']}s,"
                f" second call {r['call_s']}s"
            )
        dp = dp_train_phase(cfg, n=4, batch=8, seq=2048, steps=3)
        log(
            f"train mesh-paper local-dp 4x1 batch=8 seq=2048: state on devices"
            f" {dp['devices']}, first step {dp['first_step_s']}s, steps"
            f" {dp['step_s']}s, losses {dp['losses']},"
            f" step-0 loss vs one device {dp['ref_loss']} (|err| {dp['loss_err']})"
        )
        summary.update(sharded=sh, dp_train=dp)

    problems = health_problems(paged_impl)
    summary["problems"] = problems
    n_plans = api.plan_cache_info()["size"]
    (OUT_DIR / f"result_{args.chips}chip.json").write_text(
        json.dumps(summary, indent=1, default=str)
    )
    if problems:
        for p in problems:
            log(f"FAIL {p}")
        raise SystemExit(1)
    log(f"{n_plans} GEMM plans, all pallas_mesh with interpret=False; ledger empty")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev)},
    }))


if __name__ == "__main__":
    main()
