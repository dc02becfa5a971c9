"""Batched serving driver: prefill + greedy decode with a KV/recurrent cache.

The inference-side end-to-end example (the dry-run lowers the same
`prefill_step` / `serve_step` functions at production shapes; this driver
runs them for real at reduced shapes on CPU, or full shapes on a TPU
runtime).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --batch 4 --prompt-len 32 --gen 16

Every projection GEMM routes through the plan/execute API
(`repro.kernels.api`): the first prefill/decode trace *plans* each logical
GEMM shape once (backend choice, autotuned blocks, σ tables, and — for
specs carrying a ShardSpec — the collective schedule), and the process-wide
plan cache serves every subsequent request — `--plan-stats` prints the
cache (one entry per (spec, backend, mesh) triple, however many requests
ran), including per-plan communication cost for sharded plans.  `--mesh
DxM` serves under a local device mesh (sharding constraints active).

Robustness (DESIGN.md §11): `--requests N` serves N independent prompt
batches through `serve_requests`, which isolates each request — one request
raising (poisoned input, injected fault at the `serve.request` site) is
reported, recorded in the resilience ledger, and *skipped*; the remaining
requests still serve, and the process then exits non-zero.  Any degradation
events accumulated during the run (backend fallbacks, guard trips, retries)
are printed at exit.
"""

from __future__ import annotations

import argparse
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels import api as kernel_api
from repro.launch.compile_cache import enable_compile_cache
from repro.models import ShardCtx, get_model
from repro.obs import trace as _obs
from repro.resilience import faults as _faults
from repro.resilience import ledger as _rledger
from repro.train.train_step import make_prefill_step, make_serve_step

__all__ = [
    "generate",
    "main",
    "report_plan_cache",
    "serve_requests",
    "serving_steps",
]


# One jitted prefill/serve step pair per (model, ctx) for the whole process.
# `generate()` used to call jax.jit on a fresh closure per request, so every
# request re-traced even though GEMM plans were cached; now the first request
# traces and the rest replay (asserted trace-flat in tests/test_scheduler.py).
# Keyed on id(model) with the model stored in the entry so a dead id can't
# alias a new model; ShardCtx is frozen/hashable.  The cache is a bounded LRU:
# the jitted closures capture the model strongly (so weakrefs would never
# collect), and a long-lived process cycling through many models must not
# grow memory without bound — least-recently-served pairs are dropped and
# simply re-trace if that model ever comes back.
_STEP_CACHE: "OrderedDict" = OrderedDict()
_STEP_CACHE_MAX = 8


def serving_steps(model, ctx: ShardCtx = ShardCtx()):
    """Return the cached (prefill_step, serve_step) jitted pair for a model.

    The serve step donates its state argument (the KV cache buffer is reused
    across decode steps); the prefill step is shared with the
    continuous-batching scheduler (`launch/scheduler.py`), which admits at
    batch 1 through the same trace.
    """
    key = (id(model), ctx)
    entry = _STEP_CACHE.get(key)
    if entry is not None and entry[0] is model:
        _STEP_CACHE.move_to_end(key)
        return entry[1], entry[2]
    prefill = jax.jit(make_prefill_step(model, ctx))
    serve = jax.jit(make_serve_step(model, ctx), donate_argnums=(2,))
    _STEP_CACHE[key] = (model, prefill, serve)
    while len(_STEP_CACHE) > _STEP_CACHE_MAX:
        _STEP_CACHE.popitem(last=False)
    return prefill, serve


def report_plan_cache(prefix: str = "[serve]") -> dict:
    """Print + return the GEMM plan-cache telemetry for this process.

    Serving wants planning out of the request path: each (spec, backend,
    mesh) triple is planned at most once per process, and this report is the
    observable proof (hits = executions that reused an existing plan).
    Sharded plans additionally report their collective schedule and the
    roofline communication cost derived from bytes-moved provenance;
    grouped plans (MoE expert shapes) report groups x rows-per-group,
    per-group FLOPs, and dispatch (routing) bytes.

    Cost-model provenance (DESIGN.md §13) rides along: every entry prints
    its predicted milliseconds under the current coefficients — plus the
    measured milliseconds when the calibration file holds a record for the
    same shape/backend — and entries whose backend/schedule/sharding the
    cost model chose print the decision (chosen candidate + how many were
    ranked + calibration source).
    """
    from repro.costmodel import current_coefficients, predict, terms_from_describe
    from repro.costmodel.calibrate import default_cache
    from repro.launch.roofline import analyze_plan

    info = kernel_api.plan_cache_info()
    print(
        f"{prefix} GEMM plan cache: {info['size']} plans, "
        f"{info['hits']} hits, {info['misses']} misses"
    )
    coeffs = current_coefficients()
    try:
        measured_ms = {
            rec.get("key"): rec["ms"]
            for rec in default_cache().records(coeffs.platform)
        }
    except Exception:  # a broken calibration file must not break the report
        measured_ms = {}
    # Observed execute latencies from the tracing ring, keyed the same way
    # as the calibration cache ("MxKxN|backend") so each plan row can show
    # predicted vs actually-traced milliseconds side by side (DESIGN.md §14).
    obs_ms: dict = {}
    for sp in _obs.spans("plan.execute"):
        k = sp.attrs.get("key")
        if k:
            obs_ms.setdefault(k, []).append(sp.duration_s * 1e3)
    for p in info["plans"]:
        blocks = "x".join(map(str, p["blocks"])) if p["blocks"] else "-"
        epi = p["epilogue"]
        epi_s = (
            ("+b" if epi["bias"] else "")
            + (f"+{epi['activation']}" if epi["activation"] else "")
            + ("+r" if epi["residual"] else "")
        ) or "-"
        sh = p.get("sharding")
        if sh:
            mesh_s = "x".join(str(s) for _, s in sh["mesh"])
            rl = analyze_plan(p)
            shard_s = (
                f"{sh['schedule']}@{mesh_s} moved={sh['bytes_moved']}B "
                f"t_coll={rl['t_collective_s'] * 1e6:.2f}us"
            )
            if sh.get("overlap"):
                # double-buffered schedule: the collective above is hidden
                # behind kernel calls; show the measured ratio if a bench
                # recorded one (serial_ms / overlap_ms)
                eff = sh.get("overlap_efficiency")
                shard_s += " ov" + (f"={eff:.2f}x" if eff else "")
        else:
            shard_s = "-"
        grp = p.get("grouped")
        grp_s = (
            f"{grp['num_groups']}x{grp['rows_per_group']} "
            f"pgflops={grp['per_group_flops']:.1e} "
            f"dispatch={grp['dispatch_bytes']}B"
            if grp
            else "-"
        )
        pred_ms = predict(terms_from_describe(p), coeffs)["total_s"] * 1e3
        meas = measured_ms.get(f"{p['mkn']}|{p['backend']}")
        cost_s = f"pred={pred_ms:.3f}ms"
        if meas is not None:
            cost_s += f" meas={meas:.3f}ms"
        durs = sorted(obs_ms.get(f"{p['mkn']}|{p['backend']}", ()))
        if durs:
            p50 = durs[len(durs) // 2]
            p99 = durs[min(len(durs) - 1, int(len(durs) * 0.99))]
            cost_s += f" obs[n={len(durs)}]=p50:{p50:.3f}/p99:{p99:.3f}ms"
        dec = p.get("decision") or {}
        dec_bits = []
        for kind in ("backend", "sharding", "schedule"):
            d = dec.get(kind)
            if d:
                dec_bits.append(
                    f"{kind}:{d['chosen']}/{len(d.get('candidates', []))}cand"
                )
        if dec_bits:
            cal = next(iter(dec.values())).get("calibration", {})
            dec_s = " ".join(dec_bits) + f" [{cal.get('source', '?')}]"
        else:
            dec_s = "-"
        print(
            f"{prefix}   {p['backend']:11s} {p['structure']:9s} "
            f"{p['mkn']:>18s} batch={p['batch'] or '-'} blocks={blocks} "
            f"epi={epi_s:12s} flops={p['flops']:.2e} grp={grp_s} shard={shard_s} "
            f"{cost_s} decision={dec_s}"
        )
    return info


def generate(
    model,
    params,
    prompts: jax.Array,  # (B, T_prompt) int32
    *,
    gen_len: int,
    ctx: ShardCtx = ShardCtx(),
    greedy: bool = True,
):
    """Prefill the prompts then decode `gen_len` tokens greedily.

    Returns (tokens (B, gen_len), steps_per_s). Works for every family with a
    decode path (dense/moe/ssm/hybrid/vlm text-only prompts; audio is
    enc-dec and served via its own frames batch — see tests).
    """
    cfg = model.cfg
    b, t_prompt = prompts.shape
    prefill, serve = serving_steps(model, ctx)

    batch = {"tokens": prompts, "labels": prompts}
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros((b, cfg.num_stub_patches, cfg.d_model), cfg.adtype)
    next_tok, state, _ = prefill(params, batch)
    # Grow caches to prompt+gen capacity where the family uses KV caches:
    # prefill returns length-T caches; decode writes at position `pos`, so we
    # pad the cache length dim up front (recurrent families carry O(1) state).
    if cfg.family in ("dense", "moe", "vlm"):
        pad = gen_len
        state = jax.tree.map(
            lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (c.ndim - 3)),
            state,
        )

    toks = [next_tok]
    pos = t_prompt + (cfg.num_stub_patches if cfg.family == "vlm" else 0)
    t0 = time.monotonic()
    for i in range(gen_len - 1):
        next_tok, state = serve(params, toks[-1][:, None], state, jnp.int32(pos + i))
        toks.append(next_tok)
    jax.block_until_ready(toks[-1])
    dt = time.monotonic() - t0
    # Degenerate timings (gen_len == 1, or a clock that didn't advance)
    # report 0.0, never inf — the rate lands in printed stats and
    # BENCH_kernels.json, and inf is invalid JSON.
    steps_per_s = (gen_len - 1) / dt if dt > 0 and gen_len > 1 else 0.0
    return jnp.stack(toks, axis=1), steps_per_s


def serve_requests(
    model,
    params,
    request_prompts,
    *,
    gen_len: int,
    ctx: ShardCtx = ShardCtx(),
    prefix: str = "[serve]",
):
    """Serve a sequence of independent prompt batches, isolating failures.

    Each element of `request_prompts` is a (B, T) int32 prompt batch served
    via `generate`.  A request that raises is reported (one line, with the
    error), recorded in the resilience ledger under the `serve.request`
    site, and skipped — it never takes the other requests down.  Returns a
    list parallel to `request_prompts`: (tokens, steps_per_s) for served
    requests, None for skipped ones.
    """
    results = []
    for i, prompts in enumerate(request_prompts):
        try:
            # span attrs must not assume a well-formed request: the failure
            # path below (and the chaos warmup's probe) serves garbage prompts
            batch = int(getattr(prompts, "shape", (0,))[0] or 0)
            with _obs.span("serve.request", request=i, batch=batch, gen=gen_len):
                _faults.check("serve.request", request=i)
                results.append(
                    generate(model, params, prompts, gen_len=gen_len, ctx=ctx)
                )
        except Exception as e:
            _rledger.record(
                "serve.request",
                cause=f"{type(e).__name__}: {e}",
                fallback="skip",
                request=i,
            )
            print(f"{prefix} request {i} FAILED ({type(e).__name__}: {e}) — skipped")
            results.append(None)
    served = sum(r is not None for r in results)
    if served < len(results):
        print(f"{prefix} served {served}/{len(results)} requests")
    return results


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--requests",
        type=int,
        default=1,
        help="serve N independent prompt batches; a failing request is "
        "reported and skipped, not fatal",
    )
    ap.add_argument(
        "--scheduler",
        action="store_true",
        help="serve through the continuous-batching scheduler (paged KV "
        "cache, admission control, deadlines) instead of one batch per "
        "request — each request becomes one single-prompt scheduler request",
    )
    ap.add_argument(
        "--plan-stats",
        action="store_true",
        help="print the GEMM plan cache after serving (one plan per spec)",
    )
    ap.add_argument(
        "--obs-export",
        default=None,
        metavar="PATH",
        help="enable structured tracing for the run and write a Chrome-trace "
        "timeline to PATH at exit (plus PATH.prom Prometheus metrics and "
        "PATH.jsonl raw spans); also bridges ledger events into metrics and "
        "feeds plan.execute spans to the cost-model calibration cache",
    )
    ap.add_argument(
        "--mesh",
        default=None,
        metavar="DxM",
        help="serve under a local ('data', 'model') device mesh, e.g. 1x1 or"
        " 2x4 (needs that many devices; sharding constraints activate)",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.obs_export:
        # Tracing + both bridge feeds go live BEFORE any model work so the
        # timeline covers warmup, planning, and every request.  Exports are
        # written once, at the end of main — the serving path stays I/O-free.
        from repro.obs import bridge as _bridge

        _obs.enable()
        _bridge.install()

    ctx = ShardCtx()
    if args.mesh:
        from repro.launch.mesh import make_local_mesh

        shape = tuple(int(x) for x in args.mesh.lower().split("x"))
        mesh = make_local_mesh(shape, ("data", "model"))
        ctx = ShardCtx(mesh=mesh)
        print(f"[serve] mesh: {dict(mesh.shape)}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "audio":
        raise SystemExit("audio (whisper) serving is exercised in tests with a frames batch")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    request_prompts = [
        jax.random.randint(
            jax.random.PRNGKey(args.seed + 1 + r),
            (args.batch, args.prompt_len),
            0,
            cfg.vocab_size,
        ).astype(jnp.int32)
        for r in range(max(args.requests, 1))
    ]

    _faults.install_env_plan()
    skipped = 0
    if args.scheduler:
        from repro.launch.scheduler import ContinuousBatchingServer, Request, ServeConfig

        total_len = args.prompt_len + args.gen
        if cfg.family == "vlm":
            total_len += cfg.num_stub_patches
        pages_per_seq = -(-total_len // 8)  # ceil
        scfg = ServeConfig(
            max_slots=args.batch,
            page_size=8,
            num_pages=1 + args.batch * pages_per_seq,
            max_pages_per_seq=pages_per_seq,
            queue_capacity=max(args.requests, 1),
            warmup_prompt_lens=(args.prompt_len,),
        )
        server = ContinuousBatchingServer(model, params, scfg, ctx)
        server.warmup()
        reqs = [
            Request(rid=f"req{r}", prompt=np.asarray(p[0]), max_new_tokens=args.gen)
            for r, p in enumerate(request_prompts)
        ]
        t0 = time.monotonic()
        results_by_rid = server.run(reqs)
        dt = time.monotonic() - t0
        print(
            f"[serve] {args.arch} scheduler slots={scfg.max_slots} "
            f"pages={scfg.num_pages}x{scfg.page_size} prompt={args.prompt_len} "
            f"gen={args.gen} ticks={server.counters['ticks']}"
        )
        for r in reqs:
            res = results_by_rid[r.rid]
            head = res.tokens[:16] if res.tokens else []
            print(
                f"[serve] {res.rid}: {res.status:9s} {len(res.tokens)} tokens "
                f"lat={res.latency_s * 1e3:.1f}ms {head}"
            )
        rate = server.counters["decode_tokens"] / dt if dt > 0 else 0.0
        print(f"[serve] {server.counters}, {rate:.1f} tok/s")
    else:
        results = serve_requests(model, params, request_prompts, gen_len=args.gen, ctx=ctx)
        skipped = sum(r is None for r in results)
        print(f"[serve] {args.arch} batch={args.batch} prompt={args.prompt_len} gen={args.gen}")
        for r, res in enumerate(results):
            if res is None:
                continue
            out, rate = res
            print(
                f"[serve] req {r}: decode steps/s {rate:.2f} "
                f"({rate * args.batch:.1f} tok/s batched), row 0: {np.asarray(out[0])[:16]}"
            )
    if args.plan_stats:
        report_plan_cache()
        if _obs.is_enabled():
            st = _obs.stats()
            print(
                f"[serve] obs: {st['finished']} spans "
                f"({st['retained']} retained, {st['dropped']} dropped, "
                f"{st['suppressed_in_trace']} suppressed-in-jit)"
            )
    if _rledger.count():
        print(_rledger.format_summary("[serve]"))

    if args.obs_export:
        from repro.obs import bridge as _bridge
        from repro.obs import export as _export

        ingested = _bridge.flush_calibration()
        _export.write_chrome_trace(
            args.obs_export,
            metadata={
                "arch": args.arch,
                "requests": max(args.requests, 1),
                "scheduler": bool(args.scheduler),
                "calibration": _bridge.calibration_stamp(),
            },
        )
        _export.write_prometheus(args.obs_export + ".prom")
        _export.write_spans_jsonl(args.obs_export + ".jsonl")
        print(
            f"[serve] obs export: {args.obs_export} (+.prom, +.jsonl), "
            f"{ingested} calibration records ingested"
        )
    if skipped:
        raise SystemExit(f"[serve] {skipped} of {len(request_prompts)} requests failed")


if __name__ == "__main__":
    main()
