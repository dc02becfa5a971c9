"""Candidate enumeration + cost-ranked choice for the planner (DESIGN.md §13).

`kernels/api.plan()` consults this module whenever a degree of freedom is
left unspecified:

  decide_schedule   ShardSpec.schedule == "auto" with pinned axes — rank
                    every divisibility-LEGAL collective schedule (legality
                    is established by trial `_resolve_sharding` calls with
                    the schedule pinned, so an illegal candidate can never
                    be chosen by construction)
  decide_sharding   plan(spec, mesh=...) with NO ShardSpec — enumerate axis
                    assignments over the live mesh (M-replicated,
                    allgather_a, reduce_scatter_k, ring_k, N-replicated,
                    2D M x N, expert for grouped specs, plus unsharded —
                    and, under CALIBRATED coefficients, the double-buffered
                    `*_overlap`/`pipeline` family) and return the cheapest
                    legal ShardSpec
  decide_backend    rank the capability-legal backends by predicted cost
                    (per-platform `backend_efficiency`); the caller's
                    legacy preference order is the deterministic tie-break
  choose_blocks     block triples stay with `kernels/autotune.py`; once
                    coefficients are CALIBRATED the autotuner's candidate
                    ranking switches to `predict_blocks_ms` (its timed
                    search remains the tie-breaker on TPU)

Every decision returns a JSON-able `Decision` recorded in
`Plan.describe()["decision"]`: the chosen candidate, every candidate's
predicted seconds (and term breakdown), and the calibration provenance —
so `launch/serve.py --plan-stats` and the ledger can show *why*.

Rankings use `calibrate.current_coefficients()` (calibrated numbers when a
`.costmodel_cache.json` fit exists, shipped defaults otherwise) and are
deterministic for a fixed calibration file: pure arithmetic, no timing on
CPU.  On TPU (or under $REPRO_COSTMODEL_TIMED=1) the top-2 schedule
candidates are additionally TIMED through real plan executions and the
measurement wins — the autotuner-style tie-break inside the model's noise.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.costmodel.calibrate import current_coefficients
from repro.costmodel.model import (
    COST_MODEL_VERSION,
    CostCoefficients,
    predict,
    predict_blocks_ms,
    terms_from_describe,
)
from repro.resilience import ledger as _rledger

__all__ = [
    "Decision",
    "NoLegalCandidate",
    "choose_blocks",
    "decide_backend",
    "decide_schedule",
    "decide_sharding",
]

_ENV_TIMED = "REPRO_COSTMODEL_TIMED"

# Deterministic preference among predicted-cost ties (cheap-first philosophy:
# no collective beats a scatter beats a gather beats a full ring wavefront;
# a serial schedule beats its overlap twin at equal prediction — simpler
# dataflow — so overlap only wins when calibrated link terms say it does).
_SCHED_PREF = (
    "replicated",
    "reduce_scatter_k",
    "allgather_a",
    "ring_k",
    "reduce_scatter_k_overlap",
    "allgather_a_overlap",
    "ring_k_overlap",
    "pipeline",
    "expert",
)


def _is_overlap(sched: str) -> bool:
    """Mirror of `api._is_overlap_schedule` (duplicated to avoid the import
    cycle): double-buffered ring schedules priced as max(compute, comm)."""
    return sched.endswith("_overlap") or sched == "pipeline"


class NoLegalCandidate(Exception):
    """No candidate survived legality trials — the caller falls back to its
    legacy resolution (which raises the precise validation error)."""


@dataclasses.dataclass
class Decision:
    """Provenance of one cost-model choice, as recorded in describe()."""

    kind: str  # "schedule" | "sharding" | "backend" | "blocks"
    chosen: str
    candidates: List[Dict[str, Any]]
    calibration: Dict[str, Any]

    def as_dict(self) -> Dict[str, Any]:
        return {
            "kind": self.kind,
            "chosen": self.chosen,
            "candidates": self.candidates,
            "calibration": self.calibration,
        }


def _stamp(coeffs: CostCoefficients) -> Dict[str, Any]:
    return {
        "model_version": COST_MODEL_VERSION,
        "source": coeffs.source,
        "platform": coeffs.platform,
    }


def _best_backend(coeffs: CostCoefficients) -> Optional[str]:
    """The platform's fastest known GEMM path — schedule/sharding rankings
    are backend-relative, so predicting every candidate at the same (best)
    efficiency keeps absolute numbers honest without biasing the order."""
    if not coeffs.backend_efficiency:
        return None
    return max(coeffs.backend_efficiency, key=lambda kv: kv[1])[0]


def _candidate_terms(spec, sched: str, local, bytes_moved: int, phases: int):
    """Synthesize the describe()-shaped record for a candidate that has not
    been planned yet, and derive its cost terms (one arithmetic path:
    `model.terms_from_describe`).  The invocation arithmetic mirrors
    `api._build_sharded_plan` exactly — a drifted copy here would misprice
    candidates against the plans they become."""
    if sched in ("reduce_scatter_k", "reduce_scatter_k_overlap"):
        inv = phases + 1
    elif sched in ("allgather_a_overlap", "ring_k_overlap"):
        inv = 2  # two column-half kernel calls
    elif sched == "pipeline":
        from repro.kernels import api as _api

        inv = _api._pipeline_microbatches(
            spec.eff_m, spec.shard.axis_size(spec.shard.axis_k)
        )
    else:
        inv = 1
    desc: Dict[str, Any] = {
        "backend": None,
        "mkn": f"{spec.eff_m}x{spec.k}x{spec.n}",
        "dtypes": [spec.dtype_a, spec.dtype_b],
        "out_dtype": spec.resolved_out_dtype(),
        "flops": spec.flops(),
        "batch": list(spec.batch),
        "batched_b": spec.batched_b,
        "structure": spec.structure,
        "repeats": getattr(spec, "repeats", 1),
    }
    if spec.group is not None:
        grp = spec.group
        import numpy as _np

        ia = _np.dtype(spec.dtype_a).itemsize
        io = _np.dtype(spec.resolved_out_dtype()).itemsize
        desc["grouped"] = {
            "num_groups": grp.num_groups,
            "rows_per_group": grp.rows_per_group,
            "packed": grp.packed,
            "per_group_flops": 2 * grp.rows_per_group * spec.k * spec.n,
            "dispatch_bytes": grp.rows * (spec.k * ia + spec.n * io),
        }
    shard = spec.shard
    desc["sharding"] = {
        "schedule": sched,
        "overlap": _is_overlap(sched),
        "bytes_moved": bytes_moved,
        "collective_phases": phases,
        "kernel_invocations": inv,
        "per_shard_mkn": [local.eff_m, local.k, local.n],
        "per_shard_batch": list(local.batch),
        "per_shard_flops": local.flops() * inv,
        "mesh": [[n, s] for n, s in shard.mesh_axes],
        "axes": {
            "m": shard.axis_m,
            "k": shard.axis_k,
            "n": shard.axis_n,
            "batch": shard.axis_batch,
            "g": shard.axis_g,
        },
    }
    return terms_from_describe(desc)


def _rank(
    cands: List[Dict[str, Any]], illegal: List[Dict[str, Any]]
) -> List[Dict[str, Any]]:
    def pref(name: str) -> int:
        base = name.split("[", 1)[0]
        return _SCHED_PREF.index(base) if base in _SCHED_PREF else len(_SCHED_PREF)

    cands.sort(key=lambda c: (c["predicted_s"], pref(c["name"]), c["name"]))
    return cands + illegal


def _evaluate(spec, shard, coeffs) -> Tuple[Optional[Dict[str, Any]], Optional[str]]:
    """Legality-trial one (spec, pinned-schedule ShardSpec) candidate.

    Returns (candidate record, None) when `_resolve_sharding` accepts it,
    (None, reason) when it raises PlanValidationError — the trial is the
    same validation the real plan build runs, so legality here IS legality
    there."""
    from repro.kernels import api

    trial = dataclasses.replace(spec, shard=shard)
    try:
        sched, local, bytes_moved, phases, _ = api._resolve_sharding(trial)
    except api.PlanValidationError as e:
        return None, str(e)
    terms = _candidate_terms(trial, sched, local, bytes_moved, phases)
    pred = predict(terms, coeffs, backend=_best_backend(coeffs))
    overlap = bool(terms.get("overlap"))
    return (
        {
            "name": sched,
            "schedule": sched,
            "predicted_s": pred["total_s"],
            "t_compute_s": pred["t_compute_s"],
            "t_memory_s": pred["t_memory_s"],
            "t_collective_s": pred["t_collective_s"],
            "overlap": overlap,
            # how total_s was composed — the §15 pricing, visible in
            # describe()["decision"] provenance
            "pricing": (
                "max(compute,memory,collective)+latency"
                if overlap
                else "max(compute,memory)+collective+latency"
            ),
            "legal": True,
        },
        None,
    )


def _timed_tiebreak(
    spec, mesh, ranked: List[Dict[str, Any]], shards: Dict[str, Any]
) -> List[Dict[str, Any]]:
    """On TPU (or $REPRO_COSTMODEL_TIMED=1): time the top-2 predicted
    candidates through real plan executions and reorder by measurement.
    CPU stays pure-model so auto resolution is deterministic (interpret-mode
    timing measures Python, not the kernel — the autotune.py lesson)."""
    import jax

    if os.environ.get(_ENV_TIMED, "") != "1" and jax.default_backend() != "tpu":
        return ranked
    legal = [c for c in ranked if c.get("legal")]
    if len(legal) < 2 or mesh is None:
        return ranked
    import jax.numpy as jnp

    from repro.kernels import api
    from repro.kernels.autotune import measure_best_ms, outside_trace

    def measure(p) -> float:
        a = jnp.ones(spec.batch + (spec.m, spec.k), spec.dtype_a)
        b_shape = (
            spec.batch + (spec.k, spec.n) if spec.batched_b else (spec.k, spec.n)
        )
        b = jnp.ones(b_shape, spec.dtype_b)
        return measure_best_ms(p, a, b)

    for cand in legal[:2]:
        shard = shards.get(cand["name"])
        if shard is None:
            continue
        try:
            p = api.plan(dataclasses.replace(spec, shard=shard), mesh=mesh)
            # concrete operands even when planning inside a traced step
            cand["measured_ms"] = outside_trace(measure, p)
        except Exception as e:
            _rledger.record(
                "costmodel.tiebreak",
                cause=f"{type(e).__name__}: {e}",
                fallback="model-order",
                candidate=cand["name"],
            )
    timed = [c for c in legal[:2] if "measured_ms" in c]
    if len(timed) == 2 and (
        timed[0]["measured_ms"] > timed[1]["measured_ms"]
    ) != (timed[0]["predicted_s"] > timed[1]["predicted_s"]):
        # the measurement disagrees within the top-2: trust it
        legal[0], legal[1] = legal[1], legal[0]
        return legal + [c for c in ranked if not c.get("legal")]
    return ranked


def decide_schedule(spec, mesh=None) -> Tuple[str, Decision]:
    """Resolve `schedule="auto"` for a spec with PINNED shard axes.

    Candidates are the non-expert SCHEDULES (expert belongs to grouped
    specs, which route `_resolve_grouped_sharding`); each is legality-
    trialed with the schedule pinned and the survivors are ranked by
    predicted cost.  The overlap family (`*_overlap` / `pipeline`) only
    enters the candidate set under CALIBRATED coefficients: with shipped
    defaults (zero latency terms) its max(compute, comm) pricing would
    dominate every serial schedule unconditionally, and auto resolution
    must stay legacy-equivalent until real link measurements justify the
    switch.  Pinning an overlap schedule explicitly always works.  Raises
    NoLegalCandidate when nothing survives so the caller's legacy heuristic
    can produce its precise validation error.
    """
    from repro.kernels import api

    coeffs = current_coefficients()
    overlap_ok = coeffs.source == "calibrated"
    shard = spec.shard
    cands: List[Dict[str, Any]] = []
    illegal: List[Dict[str, Any]] = []
    shards: Dict[str, Any] = {}
    for sched in (s for s in api.SCHEDULES if s != "expert"):
        if _is_overlap(sched) and not overlap_ok:
            continue
        pinned = dataclasses.replace(shard, schedule=sched)
        cand, reason = _evaluate(spec, pinned, coeffs)
        if cand is not None:
            cands.append(cand)
            shards[cand["name"]] = pinned
        else:
            illegal.append(
                {"name": sched, "legal": False, "reason": reason[:120]}
            )
    if not cands:
        raise NoLegalCandidate(
            f"no legal collective schedule for shard axes of {spec!r}"
        )
    ranked = _rank(cands, illegal)
    ranked = _timed_tiebreak(spec, mesh, ranked, shards)
    chosen = ranked[0]["name"]
    return chosen, Decision("schedule", chosen, ranked, _stamp(coeffs))


def _sharding_candidates(
    spec, mesh, *, overlap_ok: bool = False
) -> List[Tuple[str, Any]]:
    """(label, ShardSpec) axis assignments to trial over the live mesh.

    `overlap_ok` admits the double-buffered family — gated on calibrated
    coefficients by the caller, same reasoning as `decide_schedule`."""
    from repro.kernels.api import ShardSpec

    axes = list(mesh.shape.items())
    # schedule pinned so the legality trial never re-enters auto resolution
    out: List[Tuple[str, Any]] = [
        ("unsharded", ShardSpec.from_mesh(mesh, schedule="replicated"))
    ]
    if spec.group is not None:
        for name, size in axes:
            if size > 1:
                out.append(
                    (
                        f"expert[g={name}]",
                        ShardSpec.from_mesh(mesh, g=name, schedule="expert"),
                    )
                )
        return out
    for name, size in axes:
        if size <= 1:
            continue
        out.extend(
            [
                (
                    f"replicated[m={name}]",
                    ShardSpec.from_mesh(mesh, m=name, schedule="replicated"),
                ),
                (
                    f"allgather_a[m={name}]",
                    ShardSpec.from_mesh(mesh, m=name, schedule="allgather_a"),
                ),
                (
                    f"reduce_scatter_k[k={name}]",
                    ShardSpec.from_mesh(mesh, k=name, schedule="reduce_scatter_k"),
                ),
                (
                    f"ring_k[k={name}]",
                    ShardSpec.from_mesh(mesh, k=name, schedule="ring_k"),
                ),
                (
                    f"replicated[n={name}]",
                    ShardSpec.from_mesh(mesh, n=name, schedule="replicated"),
                ),
            ]
        )
        if overlap_ok:
            out.extend(
                [
                    (
                        f"reduce_scatter_k_overlap[k={name}]",
                        ShardSpec.from_mesh(
                            mesh, k=name, schedule="reduce_scatter_k_overlap"
                        ),
                    ),
                    (
                        f"allgather_a_overlap[m={name}]",
                        ShardSpec.from_mesh(
                            mesh, m=name, schedule="allgather_a_overlap"
                        ),
                    ),
                    (
                        f"ring_k_overlap[k={name}]",
                        ShardSpec.from_mesh(mesh, k=name, schedule="ring_k_overlap"),
                    ),
                    (
                        f"pipeline[k={name}]",
                        ShardSpec.from_mesh(mesh, k=name, schedule="pipeline"),
                    ),
                ]
            )
        if spec.batched_b:
            out.append(
                (
                    f"replicated[batch={name}]",
                    ShardSpec.from_mesh(mesh, batch=name, schedule="replicated"),
                )
            )
    if len(axes) >= 2 and not spec.batched_b:
        (a0, _), (a1, _) = axes[0], axes[1]
        out.append(
            (
                f"replicated[m={a0},n={a1}]",
                ShardSpec.from_mesh(mesh, m=a0, n=a1, schedule="replicated"),
            )
        )
    return out


_SHARD_MEMO: Dict[tuple, Tuple[Any, Decision]] = {}


def decide_sharding(spec, mesh) -> Tuple[Any, Decision]:
    """Choose a full ShardSpec (axes AND schedule) for a spec with none.

    This is where reduce_scatter_k outranks allgather_a on the BENCH spec:
    the gather schedule re-runs the FULL-K per-shard kernel p times (8x the
    FLOPs of the scatter's K/p slabs) for identical bytes moved.  Memoized
    per (spec, mesh axes, platform, coefficients) — auto-sharding a cached
    plan's spec costs one dict lookup.
    """
    import jax

    coeffs = current_coefficients()
    memo_key = (spec, tuple(mesh.shape.items()), jax.default_backend(), coeffs)
    got = _SHARD_MEMO.get(memo_key)
    if got is not None:
        return got
    cands: List[Dict[str, Any]] = []
    illegal: List[Dict[str, Any]] = []
    shards: Dict[str, Any] = {}
    overlap_ok = coeffs.source == "calibrated"
    for label, shard in _sharding_candidates(spec, mesh, overlap_ok=overlap_ok):
        cand, reason = _evaluate(spec, shard, coeffs)
        if cand is not None:
            cand["name"] = label
            cands.append(cand)
            shards[label] = shard
        else:
            illegal.append({"name": label, "legal": False, "reason": reason[:120]})
    if not cands:
        raise NoLegalCandidate(
            f"no legal axis assignment for {spec!r} on mesh {dict(mesh.shape)}"
        )
    ranked = _rank(cands, illegal)
    ranked = _timed_tiebreak(spec, mesh, ranked, shards)
    chosen = ranked[0]["name"]
    decision = Decision("sharding", chosen, ranked, _stamp(coeffs))
    got = (shards[chosen], decision)
    _SHARD_MEMO[memo_key] = got
    return got


def decide_backend(
    spec, candidates: Sequence[Tuple[str, int]]
) -> Tuple[str, Decision]:
    """Rank capability-legal backends by predicted cost.

    `candidates` is [(name, legacy_order_index)] — the index is the
    deterministic tie-break, so equal predictions reproduce the legacy
    pinned-default -> xla -> pallas_mesh -> registration order exactly.
    """
    coeffs = current_coefficients()
    desc = {
        "backend": None,
        "mkn": f"{spec.eff_m}x{spec.k}x{spec.n}",
        "dtypes": [spec.dtype_a, spec.dtype_b],
        "out_dtype": spec.resolved_out_dtype(),
        "flops": spec.flops(),
        "batch": list(spec.batch),
        "batched_b": spec.batched_b,
        "structure": spec.structure,
        "repeats": getattr(spec, "repeats", 1),
    }
    terms = terms_from_describe(desc)
    rows = []
    for name, order in candidates:
        pred = predict(terms, coeffs, backend=name)
        rows.append(
            {
                "name": name,
                "predicted_s": pred["total_s"],
                "efficiency": coeffs.efficiency(name),
                "legal": True,
                "_order": order,
            }
        )
    rows.sort(key=lambda r: (r["predicted_s"], r["_order"]))
    for r in rows:
        del r["_order"]
    chosen = rows[0]["name"]
    return chosen, Decision("backend", chosen, rows, _stamp(coeffs))


def choose_blocks(
    m: int, k: int, n: int, dtype, backend: str, *, symmetry: int = 0
):
    """Resolve the block triple, consulting the cost model once calibrated.

    With shipped-default coefficients this IS `autotune.resolve_blocks`
    (identical choice, identical caching) — the analytic `model_score`
    ranking was validated by the autotune bench and stays authoritative
    until measurements say otherwise.  With CALIBRATED coefficients the
    candidate ranking switches to `predict_blocks_ms` under the same cache
    and timed-search tie-break.  Returns (blocks, decision | None).
    """
    from repro.kernels import autotune as _autotune

    coeffs = current_coefficients()
    if coeffs.source != "calibrated":
        return _autotune.resolve_blocks(
            m, k, n, dtype, backend, symmetry=symmetry
        ), None
    blocks = _autotune.autotune(
        m,
        k,
        n,
        dtype,
        backend,
        symmetry=symmetry,
        scorer=lambda blk: predict_blocks_ms(m, k, n, blk, coeffs),
    )
    decision = Decision(
        "blocks",
        "x".join(map(str, blocks)),
        [
            {
                "name": "x".join(map(str, blocks)),
                "predicted_s": predict_blocks_ms(m, k, n, blocks, coeffs) / 1e3,
                "legal": True,
            }
        ],
        _stamp(coeffs),
    )
    return blocks, decision


def clear_decision_memo() -> None:
    """Test hook: drop the per-process sharding-decision memo."""
    _SHARD_MEMO.clear()
