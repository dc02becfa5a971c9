"""Roofline analysis over dry-run artifacts (EXPERIMENTS.md §Roofline).

Reads artifacts/<mesh>/<arch>__<shape>.json (written by launch/dryrun.py) and
derives, per cell:

    compute term    = HLO_FLOPs_per_device / peak_FLOPs          [s]
    memory term     = HLO_bytes_per_device / HBM_bw              [s]
    collective term = collective_link_bytes_per_device / link_bw [s]

    MODEL_FLOPS  = 6·N·D (train, dense) / 6·N_active·D (train, MoE)
                   2·N(_active)·D for inference steps (fwd only)
    useful ratio = MODEL_FLOPS / (HLO_FLOPs · n_devices)
    roofline fraction = t_model / max(terms)
        where t_model = MODEL_FLOPS / (n_devices · peak) — the step time if
        only useful model FLOPs ran at MXU peak.  This single number is the
        score we hillclimb: <1 means the dominant structural term (wasted
        compute, HBM streaming, or ICI traffic) exceeds useful compute.

TPU v5e constants (per chip): 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link ICI.

Usage:
    PYTHONPATH=src python -m repro.launch.roofline [--dir artifacts/pod16x16]
        [--md artifacts/roofline.md] [--json artifacts/roofline.json]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Any, Dict, List, Optional

from repro.costmodel.model import TPU_PEAKS

__all__ = [
    "PEAK_FLOPS",
    "HBM_BW",
    "LINK_BW",
    "analyze_artifact",
    "analyze_dir",
    "analyze_plan",
    "render_markdown",
]

# The dry-run pod is v5e; its per-chip peaks come from the one device_kind
# table the cost model keeps.
PEAK_FLOPS, HBM_BW, LINK_BW = TPU_PEAKS["TPU v5 lite"]

_HINTS = {
    "compute": "reduce recompute (remat policy) / pick a lower-waste schedule — HLO FLOPs exceed the useful-model floor",
    "memory": "raise arithmetic intensity: fuse ops, larger per-chip tiles, avoid streaming weights/caches more than once",
    "collective": "reshard to cut ICI traffic: different TP axis placement, overlap/ring schedules, gradient compression",
    "collective(hidden)": "collective is the largest term but the schedule double-buffers it behind kernel calls — already hidden; cut link bytes to go faster",
}


def model_flops(art: Dict[str, Any]) -> float:
    """Useful-model FLOPs per step for the cell (whole job, not per device)."""
    n_active = art.get("n_active_params") or art.get("n_params") or 0
    kind = art.get("kind", "train")
    tokens = art.get("tokens_per_step")
    if tokens is None:
        # Reconstruct from the shape registry (artifacts written before the
        # tokens_per_step field was added).
        from repro.configs import SHAPES

        sh = SHAPES[art["shape"]]
        tokens = sh.global_batch * (sh.seq_len if kind in ("train", "prefill") else 1)
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * tokens


def analyze_artifact(art: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Compute roofline terms for one artifact dict; None for skipped cells."""
    if art.get("status") != "ok":
        return None
    n_dev = art["n_devices"]
    # Prefer probe-corrected costs (scan-body undercount fixed; see dryrun.py)
    flops = art.get("flops_per_device_corrected", art["flops_per_device"])
    byts = art.get("bytes_per_device_corrected", art["bytes_per_device"])
    byts += art.get("recurrence_bytes_analytic", 0.0)
    coll = art.get(
        "collective_link_bytes_corrected", art.get("collective_link_bytes", 0.0)
    )
    t_compute = flops / PEAK_FLOPS
    t_memory = byts / HBM_BW
    t_coll = coll / LINK_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(art)
    t_model = mf / (n_dev * PEAK_FLOPS)
    hlo_total = flops * n_dev
    return {
        "arch": art["arch"],
        "shape": art["shape"],
        "mesh": art["mesh"],
        "kind": art["kind"],
        "n_devices": n_dev,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "t_bound_s": terms[dominant],
        "model_flops": mf,
        "useful_ratio": (mf / hlo_total) if hlo_total else 0.0,
        "roofline_fraction": (t_model / terms[dominant]) if terms[dominant] else 0.0,
        "hint": _HINTS[dominant],
    }


def analyze_plan(desc: Dict[str, Any]) -> Dict[str, Any]:
    """Roofline terms for ONE GEMM plan from its `Plan.describe()` record —
    per device, per call, at the TPU v5e constants.

    For a ShardedPlan the sharding provenance supplies per-shard FLOPs and
    the collective's bytes-moved, so the communication cost of a schedule is
    reportable before any profile exists (serve `--plan-stats`, the sharded
    bench).  Unsharded plans get a zero collective term through the same
    arithmetic.  Grouped plans (a "grouped" provenance record) decompose
    into per-group compute terms — rows stream once but every group's
    weight slab streams — plus the dispatch (scatter/gather routing) bytes;
    unknown record shapes degrade to the plain-GEMM arithmetic instead of
    raising.

    The byte/FLOP arithmetic lives in `costmodel.model.terms_from_describe`
    (the machine-usable `terms` dict is echoed back in the result for the
    cost model and calibration); this function adds the fixed TPU v5e
    constants, dominant-term classification, and tuning hints.
    """
    from repro.costmodel.model import terms_from_describe

    sh = desc.get("sharding") or {}
    grp = desc.get("grouped") or {}
    t = terms_from_describe(desc)
    flops, hbm_bytes, coll_bytes = t["flops"], t["hbm_bytes"], t["collective_bytes"]
    terms = {
        "compute": flops / PEAK_FLOPS,
        "memory": hbm_bytes / HBM_BW,
        "collective": coll_bytes / LINK_BW,
    }
    dominant = max(terms, key=terms.get)
    overlap = bool(t.get("overlap"))
    # An overlapped schedule hides the collective behind kernel calls: the
    # bound is max of all three terms (DESIGN.md §15), and a collective-
    # dominant cell gets the "already hidden" hint instead of the reshard one.
    if overlap:
        t_total = max(terms.values())
        hint_key = "collective(hidden)" if dominant == "collective" else dominant
    else:
        t_total = max(terms["compute"], terms["memory"]) + terms["collective"]
        hint_key = dominant
    out = {
        "backend": desc["backend"],
        "mkn": desc["mkn"],
        "schedule": sh.get("schedule"),
        "overlap": overlap,
        "per_shard_flops": flops,
        "hbm_bytes": hbm_bytes,
        "collective_bytes": coll_bytes,
        "terms": t,
        "t_compute_s": terms["compute"],
        "t_memory_s": terms["memory"],
        "t_collective_s": terms["collective"],
        "dominant": dominant,
        "t_bound_s": terms[dominant],
        "t_total_s": t_total,
        "hint": _HINTS[hint_key],
    }
    if grp:
        out["grouped"] = {
            "num_groups": grp.get("num_groups"),
            "rows_per_group": grp.get("rows_per_group"),
            "per_group_flops": grp.get("per_group_flops"),
            "per_group_t_compute_s": grp.get("per_group_flops", 0) / PEAK_FLOPS,
            "dispatch_bytes": grp.get("dispatch_bytes", 0),
            "t_dispatch_s": grp.get("dispatch_bytes", 0) / HBM_BW,
        }
    return out


def analyze_dir(path: str) -> List[Dict[str, Any]]:
    rows, skips = [], []
    for f in sorted(glob.glob(os.path.join(path, "*.json"))):
        art = json.load(open(f))
        if not isinstance(art, dict) or "arch" not in art:
            continue
        r = analyze_artifact(art)
        if r is None:
            skips.append({"arch": art["arch"], "shape": art["shape"],
                          "status": art.get("status"), "reason": art.get("reason", art.get("error", ""))})
        else:
            rows.append(r)
    return rows + [{"skip": True, **s} for s in skips]


def _fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.2f}ms"
    return f"{x * 1e6:.1f}us"


def render_markdown(rows: List[Dict[str, Any]], title: str = "") -> str:
    out = []
    if title:
        out.append(f"### {title}\n")
    out.append("| arch | shape | compute | memory | collective | dominant | useful FLOP ratio | roofline frac |")
    out.append("|---|---|---|---|---|---|---|---|")
    for r in rows:
        if r.get("skip"):
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | {r['status'].upper()} | — | {r.get('reason','')[:60]} |")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {_fmt_s(r['t_compute_s'])} | {_fmt_s(r['t_memory_s'])} "
            f"| {_fmt_s(r['t_collective_s'])} | **{r['dominant']}** | {r['useful_ratio']:.2f} "
            f"| {r['roofline_fraction']:.2f} |"
        )
    return "\n".join(out) + "\n"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="artifacts/pod16x16")
    ap.add_argument("--md", default=None)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    rows = analyze_dir(args.dir)
    md = render_markdown(rows, title=f"Roofline — {args.dir}")
    print(md)
    live = [r for r in rows if not r.get("skip")]
    if live:
        worst = min(live, key=lambda r: r["roofline_fraction"])
        collb = [r for r in live if r["dominant"] == "collective"]
        print(f"worst roofline fraction: {worst['arch']} x {worst['shape']} = {worst['roofline_fraction']:.3f}")
        print(f"collective-bound cells: {[(r['arch'], r['shape']) for r in collb]}")
    if args.md:
        with open(args.md, "w") as f:
            f.write(md)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
