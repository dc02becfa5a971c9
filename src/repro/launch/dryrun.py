import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
)

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract roofline inputs from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/]

Per cell this script:
  1. builds make_production_mesh(multi_pod=...),
  2. constructs abstract inputs (ShapeDtypeStructs — zero allocation) and
     NamedShardings from the model's logical-axis trees,
  3. jit(...).lower(...).compile() for the cell's entry point
     (train_step / prefill_step / serve_step per DESIGN.md §6),
  4. prints compiled.memory_analysis() + cost_analysis() and parses collective
     traffic from the HLO (launch/hlo_stats.py),
  5. writes artifacts/<mesh>/<arch>__<shape>.json for launch/roofline.py.

Skip rules (DESIGN.md §5): long_500k only for supports_long_context archs.
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_production_mesh
from repro.models import ShardCtx, get_model
from repro.optim import AdamWConfig, warmup_cosine
from repro.parallel.sharding import DEFAULT_RULES, ShardingRules, tree_shardings
from repro.train.train_step import (
    abstract_train_state,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)

__all__ = ["run_cell", "input_specs"]


def _rules_for(cfg, shape, mesh, tuned: bool = False) -> ShardingRules:
    """Per-cell sharding rules (DESIGN.md §4/§6).

    tuned=True layers on the §Perf winners: Megatron-SP remat carriers for
    train, and sequence-parallel attention wherever heads don't divide TP.
    """
    rules = DEFAULT_RULES
    tp = mesh.shape.get("model", 1)
    if shape.kind in ("decode", "long_decode"):
        if cfg.num_kv_heads % tp:
            # GQA kv heads don't divide TP: shard the cache length instead (SP)
            rules = rules.replace(kv_heads=None, kv_seq="model")
    if shape.kind == "long_decode":
        # B=1: no batch sharding; stream the huge KV/state over DP axes too
        rules = rules.replace(batch=None, kv_batch=None, kv_seq=("pod", "data"))
        if cfg.num_kv_heads % tp == 0:
            rules = rules.replace(kv_heads="model")
    if tuned:
        if shape.kind == "train":
            rules = rules.replace(seq_sp="model")
        if shape.kind in ("train", "prefill") and cfg.num_heads % tp:
            rules = rules.replace(seq_attn="model")
    return rules


def input_specs(arch: str, shape_name: str) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every input of the cell's entry point."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    if shape.kind in ("train", "prefill"):
        batch, axes = model.batch_specs(shape)
        return {"batch": batch, "batch_axes": axes}
    tokens, state, pos, axes = model.decode_input_specs(shape)
    return {"tokens": tokens, "state": state, "pos": pos, "state_axes": axes}


def _cell_applicable(cfg, shape) -> Optional[str]:
    if shape.kind == "long_decode" and not cfg.supports_long_context:
        return (
            "N/A: pure full-attention arch — long_500k requires sub-quadratic "
            "attention (skip recorded per DESIGN.md §5)"
        )
    return None


def build_lowered(cfg, shape, mesh, rules, param_rules=None):
    """Build + lower the cell's entry point for an explicit config (no compile).

    Shared by the baseline dry-run and the cost-probe lowerings (which pass a
    reduced-depth, scan-unrolled variant of the same config).

    param_rules: separate logical->physical table for params + optimizer state
    (e.g. PARAM_RULES for FSDP: 'embed' additionally sharded over DP axes —
    XLA inserts the per-layer all-gathers).  Activations keep `rules`.
    """
    model = get_model(cfg)
    ctx = ShardCtx(mesh, rules)
    prules = param_rules or rules

    if shape.kind == "train":
        state = abstract_train_state(model)
        batch, batch_axes = model.batch_specs(shape)
        p_axes = model.logical_axes()
        params_abs = state["params"]
        state_sh = {
            "params": tree_shardings(p_axes, mesh, prules, params_abs),
            "opt": {
                "m": tree_shardings(p_axes, mesh, prules, params_abs),
                "v": tree_shardings(p_axes, mesh, prules, params_abs),
                "count": NamedSharding(mesh, P()),
            },
            "step": NamedSharding(mesh, P()),
        }
        batch_sh = {k: tree_shardings(batch_axes[k], mesh, rules, batch[k]) for k in batch}
        step_fn = make_train_step(
            model, warmup_cosine(3e-4, 100, 10_000), AdamWConfig(), ctx,
            grad_accum=getattr(cfg, "grad_accum", 1),
        )
        jitted = jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state, batch)
    elif shape.kind == "prefill":
        batch, batch_axes = model.batch_specs(shape)
        params = model.abstract_params()
        params_sh = tree_shardings(model.logical_axes(), mesh, prules, params)
        batch_sh = {k: tree_shardings(batch_axes[k], mesh, rules, batch[k]) for k in batch}
        step_fn = make_prefill_step(model, ctx)
        jitted = jax.jit(step_fn, in_shardings=(params_sh, batch_sh))
        lowered = jitted.lower(params, batch)
    else:  # decode / long_decode
        tokens, dstate, pos, state_axes = model.decode_input_specs(shape)
        params = model.abstract_params()
        params_sh = tree_shardings(model.logical_axes(), mesh, prules, params)
        state_sh = {k: tree_shardings(state_axes[k], mesh, rules, dstate[k]) for k in dstate}
        tok_sh = tree_shardings(("batch", None), mesh, rules, tokens)
        next_sh = tree_shardings(
            ("batch",), mesh, rules, jax.ShapeDtypeStruct(tokens.shape[:1], jnp.int32)
        )
        step_fn = make_serve_step(model, ctx)
        jitted = jax.jit(
            step_fn,
            in_shardings=(params_sh, tok_sh, state_sh, NamedSharding(mesh, P())),
            out_shardings=(next_sh, state_sh),
            donate_argnums=(2,),
        )
        lowered = jitted.lower(params, tokens, dstate, pos)
    return lowered


# --- cost probe ------------------------------------------------------------
# XLA cost_analysis counts a while-loop body ONCE regardless of trip count,
# so a scanned L-layer model under-reports flops/bytes/collectives by ~L x.
# Fix: lower the SAME cell at two reduced depths k1 < k2 with the layer scans
# fully UNROLLED (cfg.scan_unroll), fit the per-depth-unit slope, and
# extrapolate to the full depth.  The full-depth scanned compile is still what
# validates sharding + memory fit; the probe only corrects the cost terms.

PROBE_DEPTHS = (2, 4)


def _probe_cfg(cfg, k: int):
    if cfg.family == "hybrid":
        # depth unit = one (period x mamba + shared-attn) segment
        return dataclasses.replace(
            cfg, num_layers=k * cfg.shared_attn_period, scan_unroll=True
        )
    if cfg.family == "audio":
        # enc and dec scale together (enc_layers == dec_layers for whisper)
        return dataclasses.replace(
            cfg, num_layers=k, enc_layers=k, dec_layers=k, scan_unroll=True
        )
    return dataclasses.replace(cfg, num_layers=k, scan_unroll=True)


def _full_depth_units(cfg) -> float:
    if cfg.family == "hybrid":
        # fractional tail segment approximates `tail` mamba layers (slightly
        # overcounts the shared block: 38 = 6*6 + 2 -> 6.33 units); noted in
        # EXPERIMENTS.md SS-Dry-run.
        return cfg.num_layers / cfg.shared_attn_period
    if cfg.family == "audio":
        return float(cfg.enc_layers)
    return float(cfg.num_layers)


def _cost_triple(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    coll = collective_stats(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_link_bytes": sum(s["link_bytes"] for s in coll.values()),
    }


def probe_corrected_costs(cfg, shape, mesh, rules, param_rules=None) -> Dict[str, Any]:
    """Two reduced-depth unrolled compiles -> per-layer slope -> full-depth cost."""
    k1, k2 = PROBE_DEPTHS
    c1 = _cost_triple(
        build_lowered(_probe_cfg(cfg, k1), shape, mesh, rules, param_rules).compile()
    )
    c2 = _cost_triple(
        build_lowered(_probe_cfg(cfg, k2), shape, mesh, rules, param_rules).compile()
    )
    full = _full_depth_units(cfg)
    out: Dict[str, Any] = {"probe_depths": [k1, k2], "full_depth_units": full}
    # grad_accum wraps the whole microbatch pass in ANOTHER while loop (also
    # counted once) -> scale by the accumulation factor (slightly overcounts
    # the single optimizer update, conservative).
    ga = max(1, getattr(cfg, "grad_accum", 1))
    for key in ("flops", "bytes", "coll_link_bytes"):
        slope = (c2[key] - c1[key]) / (k2 - k1)
        out[key] = (c1[key] + max(0.0, full - k1) * slope) * ga
        out[key + "_per_unit"] = slope
    return out


def recurrence_traffic_analytic(cfg, shape, mesh, rules) -> float:
    """HBM bytes/device of sequential recurrent-state updates NOT visible to
    the probe (the time scans' bodies are also counted once by cost_analysis).

    rwkv6 (ssm): the faithful WKV scan carries a (B_loc, H, K, V) f32 state
    through T per-token steps per layer -> L*T*2*state_bytes (x3 for train:
    fwd + remat-recompute + bwd state grads).
    zamba2 (hybrid): SSD is chunk-parallel; only the inter-chunk carry scan is
    sequential -> L*(T/chunk)*2*state_bytes.
    Transformer families: no sequential recurrence -> 0.
    """
    if cfg.family not in ("ssm", "hybrid"):
        return 0.0
    # local batch after sharding ('batch' -> DP axes unless rules dropped it)
    phys = rules.get("batch")
    dp = 1
    if phys is not None:
        for a in (phys,) if isinstance(phys, str) else phys:
            dp *= mesh.shape.get(a, 1)
    b_loc = max(1, shape.global_batch // dp)
    t_len = shape.seq_len if shape.kind in ("train", "prefill") else 1
    train_mult = 3.0 if shape.kind == "train" else 1.0
    if cfg.family == "ssm":
        h, hd = cfg.num_heads, cfg.head_dim_
        state_bytes = b_loc * h * hd * hd * 4
        if getattr(cfg, "wkv_chunked", False) and t_len > 1:
            # chunk-parallel WKV (models/rwkv._wkv_chunked): per chunk, the
            # state is touched twice and the (C, C, K) decay tensor + (C, C)
            # attention block are materialized once each (r+w).
            c = cfg.wkv_chunk
            nc = max(1, t_len // c)
            d_block = b_loc * c * c * h * hd * 4  # exp(diff) tensor, f32
            a_block = b_loc * c * c * h * 4
            per_chunk = 2 * state_bytes + 2 * (d_block + a_block)
            return float(cfg.num_layers * nc * per_chunk * train_mult)
        steps = t_len
    else:
        d_in = cfg.ssm_expand * cfg.d_model
        state_bytes = b_loc * d_in * cfg.ssm_state_size * 4
        steps = max(1, t_len // 128)  # ssm.py _CHUNK
    return float(cfg.num_layers * steps * 2 * state_bytes * train_mult)


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    rules_override: Optional[ShardingRules] = None,
    param_rules: Optional[ShardingRules] = None,
    remat: Optional[str] = None,
    cfg_overrides: Optional[Dict[str, Any]] = None,
    tuned: bool = False,
    probe: bool = True,
    verbose: bool = True,
) -> Dict[str, Any]:
    """Lower + compile one (arch, shape, mesh) cell; returns the artifact dict."""
    cfg = get_config(arch)
    if tuned:
        cfg = cfg.tuned()
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat_policy=remat)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    skip = _cell_applicable(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    art: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
    }
    if skip:
        art["status"] = "skipped"
        art["reason"] = skip
        if verbose:
            print(f"[{mesh_name}] {arch} x {shape_name}: SKIP ({skip})")
        return art

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    rules = rules_override or _rules_for(cfg, shape, mesh, tuned=tuned)
    if tuned and param_rules is None and shape.kind == "train":
        from repro.parallel.sharding import PARAM_RULES

        param_rules = PARAM_RULES  # FSDP params+opt (fit + §Perf A1/C2)
    model = get_model(cfg)

    t0 = time.monotonic()
    lowered = build_lowered(cfg, shape, mesh, rules, param_rules)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    art.update(
        status="ok",
        n_devices=n_dev,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops_per_device=float(cost.get("flops", 0.0)),
        bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        memory_analysis={
            k: int(getattr(mem, k, 0))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            )
        }
        if mem is not None
        else {},
        collectives=coll,
        collective_link_bytes=sum(s["link_bytes"] for s in coll.values()),
        n_params=model_param_count(model),
        n_active_params=cfg.n_active_params(),
        tokens_per_step=shape.global_batch
        * (shape.seq_len if shape.kind in ("train", "prefill") else 1),
    )
    if probe:
        t0 = time.monotonic()
        pr = probe_corrected_costs(cfg, shape, mesh, rules, param_rules)
        art["probe"] = pr
        art["flops_per_device_corrected"] = pr["flops"]
        art["bytes_per_device_corrected"] = pr["bytes"]
        art["collective_link_bytes_corrected"] = pr["coll_link_bytes"]
        art["recurrence_bytes_analytic"] = recurrence_traffic_analytic(
            cfg, shape, mesh, rules
        )
        art["probe_s"] = round(time.monotonic() - t0, 2)
    if verbose:
        ma = art["memory_analysis"]
        print(
            f"[{mesh_name}] {arch} x {shape_name}: OK "
            f"compile={t_compile:.1f}s flops/dev={art['flops_per_device']:.3e} "
            f"bytes/dev={art['bytes_per_device']:.3e} "
            f"args/dev={ma.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
            f"temp/dev={ma.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
            f"coll_link_bytes/dev={art['collective_link_bytes']:.3e}"
        )
        print(f"  memory_analysis: {ma}")
        ca_keys = {k: v for k, v in sorted(cost.items()) if isinstance(v, float) and v}
        print(f"  cost_analysis: { {k: f'{v:.3e}' for k, v in list(ca_keys.items())[:8]} }")
        print(f"  collectives: { {k: int(v['count']) for k, v in coll.items()} }")
    return art


def model_param_count(model) -> int:
    import numpy as np

    return int(
        sum(np.prod(l.shape) for l in jax.tree.leaves(model.abstract_params()))
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--tuned", action="store_true",
                    help="apply §Perf winners (cfg.tuned() + SP/seq_attn/FSDP rules)")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ASSIGNED_ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for multi_pod in meshes:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        os.makedirs(os.path.join(args.out, mesh_name), exist_ok=True)
        for arch, shape in cells:
            path = os.path.join(args.out, mesh_name, f"{arch}__{shape}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[{mesh_name}] {arch} x {shape}: exists, skip")
                continue
            try:
                # probe corrects cost terms for the (single-pod) roofline table;
                # multi-pod cells only validate sharding/compile -> skip probe.
                art = run_cell(
                    arch, shape, multi_pod=multi_pod, remat=args.remat,
                    tuned=args.tuned, probe=not multi_pod,
                )
            except Exception as e:
                traceback.print_exc()
                art = {
                    "arch": arch,
                    "shape": shape,
                    "mesh": mesh_name,
                    "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                }
                failures.append((mesh_name, arch, shape))
            with open(path, "w") as f:
                json.dump(art, f, indent=1)
    if failures:
        print(f"\nFAILED cells ({len(failures)}):")
        for f3 in failures:
            print("  ", *f3)
        raise SystemExit(1)
    print("\nALL CELLS OK")


if __name__ == "__main__":
    main()
