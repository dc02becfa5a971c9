"""Analytical GEMM cost model (DESIGN.md §13).

The planner's brain predicts the wall time of one plan execution from the
same terms `launch/roofline.analyze_plan` reports — compute, memory, and
collective seconds — parameterized by per-platform `CostCoefficients`
instead of the roofline's fixed TPU v5e constants.  Everything here is pure
arithmetic over `Plan.describe()`-shaped records: no jax import, no timing,
no I/O — `costmodel/calibrate.py` owns measurement and persistence, and
`costmodel/choose.py` owns candidate enumeration.

Two ingredients go beyond a plain roofline, both from the paper family:

  * structure_step_factor — a `structure="symmetric"` product reads out in
    `symmetric_readout_steps(n)` ≈ floor(3n/2) mesh steps instead of the
    general 2n-1 (Kak 2010 §symmetries), so its compute term scales by that
    ratio; general and scrambled products pay the full 2n-1 horizon.
  * repeat_amortization — `GemmSpec.repeats` declares that the plan runs r
    times back to back against resident weights (decode loops, MoE layers).
    The cross-wired mesh array computes r pipelined products in r·n + (n-1)
    steps (Kak, arXiv:1411.3273), so the per-product step cost falls from
    2n-1 toward n; the B operand also streams once, not r times.

`predict` combines the terms as `max(compute, memory) + collective +
latency` for the serial schedules: compute and HBM streaming overlap (the
kernels are pipelined) but a gather-then-compute collective is a barrier,
and each collective phase / kernel launch pays a fixed latency the byte
terms can't see (the coefficients calibration actually fits on small
probes).  For the double-buffered schedules (`*_overlap` / `pipeline`,
DESIGN.md §15) every ring hop is issued behind a kernel call, so the
steady state is `max(compute, memory, collective) + latency` — the overlap
pricing that lets a calibrated `schedule="auto"` pick them whenever the
link time would otherwise be exposed.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Mapping, Optional, Tuple

import numpy as np

__all__ = [
    "COST_MODEL_VERSION",
    "CostCoefficients",
    "TPU_PEAKS",
    "default_coefficients",
    "predict",
    "predict_blocks_ms",
    "repeat_amortization",
    "structure_step_factor",
    "terms_from_describe",
    "tpu_peaks",
]

COST_MODEL_VERSION = 1

# Largest n whose symmetric readout horizon is computed exactly from the
# mesh completion times (O(n^2) work, cached); beyond it the empirical
# closed form floor(3n/2) is used (validated against exact in tests).
_EXACT_SYMMETRIC_N = 128


@dataclasses.dataclass(frozen=True)
class CostCoefficients:
    """Per-platform hardware coefficients the prediction is linear in.

    `backend_efficiency` maps backend names to the fraction of
    `flops_per_s` that backend sustains (1.0 = the platform's best GEMM
    path); unknown backends get `default_efficiency`.  `source` records
    whether the numbers are shipped defaults or a measured calibration
    (see calibrate.py); frozen + tuple-typed so coefficients are hashable
    and usable in memo keys.
    """

    flops_per_s: float
    hbm_bytes_per_s: float
    link_bytes_per_s: float
    phase_latency_s: float = 0.0
    launch_overhead_s: float = 0.0
    backend_efficiency: Tuple[Tuple[str, float], ...] = ()
    default_efficiency: float = 0.5
    platform: str = "cpu"
    source: str = "default"

    def efficiency(self, backend: Optional[str]) -> float:
        for name, eff in self.backend_efficiency:
            if name == backend:
                return eff
        return self.default_efficiency

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["backend_efficiency"] = {k: v for k, v in self.backend_efficiency}
        return d

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "CostCoefficients":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in fields}
        be = kw.get("backend_efficiency") or ()
        if isinstance(be, Mapping):
            be = tuple(sorted((str(k), float(v)) for k, v in be.items()))
        else:
            be = tuple((str(k), float(v)) for k, v in be)
        kw["backend_efficiency"] = be
        return cls(**kw)


# Published per-chip peaks, keyed by `jax.Device.device_kind`:
# (bf16 FLOP/s, HBM bytes/s, ICI bytes/s per link).  Source: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect (four links of 50 GB/s).
TPU_PEAKS: Dict[str, Tuple[float, float, float]] = {
    "TPU v5 lite": (197e12, 819e9, 50e9),
}


def tpu_peaks(device_kind: Optional[str]) -> Tuple[float, float, float]:
    """The `TPU_PEAKS` row for a chip; a kind not in the table is an error,
    never a silent v5e default."""
    try:
        return TPU_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for TPU device_kind {device_kind!r};"
            f" known: {sorted(TPU_PEAKS)}"
        ) from None


def default_coefficients(
    platform: Optional[str] = None, device_kind: Optional[str] = None
) -> CostCoefficients:
    """Shipped coefficients: the chip's published peaks on TPU (`TPU_PEAKS`,
    keyed by `device_kind`); CPU numbers anchored to the measured
    `BENCH_kernels.json["xla_gemm"]` series (~105–136 GFLOP/s f32 on the CI
    host).  Latency coefficients default to zero — byte terms alone
    reproduce the legacy auto-schedule heuristic exactly, and calibration
    fits the real fixed costs when asked."""
    if platform is None:
        platform = "cpu"
    if platform == "tpu":
        flops, hbm, link = tpu_peaks(device_kind)
        return CostCoefficients(
            flops_per_s=flops,
            hbm_bytes_per_s=hbm,
            link_bytes_per_s=link,
            backend_efficiency=(("pallas_mesh", 1.0), ("ref", 0.02), ("xla", 0.95)),
            platform="tpu",
        )
    return CostCoefficients(
        flops_per_s=1e11,
        hbm_bytes_per_s=2e10,
        link_bytes_per_s=1e10,
        # interpret-mode Pallas runs the grid in Python; ref materializes
        # rank-1 updates — both orders of magnitude off the XLA dot
        backend_efficiency=(("pallas_mesh", 0.05), ("ref", 0.01), ("xla", 1.0)),
        platform=str(platform),
    )


@functools.lru_cache(maxsize=None)
def _symmetric_steps(n: int) -> int:
    if n <= _EXACT_SYMMETRIC_N:
        from repro.core.symmetries import symmetric_readout_steps

        return symmetric_readout_steps(n)
    return (3 * n) // 2  # empirical closed form (== exact for all tested n)


def structure_step_factor(structure: str, n: int) -> float:
    """Per-product step-count ratio vs the general 2n-1 readout horizon.

    symmetric products finish at `symmetric_readout_steps(n)` (the paper's
    n+1+n/2 bound, empirically floor(3n/2)); general and scrambled pay the
    full horizon (the σ arrangement permutes cells, it doesn't finish
    earlier), factor 1.0.
    """
    n = max(1, int(n))
    if structure != "symmetric" or n == 1:
        return 1.0
    return _symmetric_steps(n) / (2 * n - 1)


def repeat_amortization(repeats: int, n: int) -> float:
    """Per-product step factor for r pipelined products on the cross-wired
    array: r products take r·n + (n-1) steps, so each costs
    (n + (n-1)/r) / (2n-1) of a standalone product — 1.0 at r=1, falling
    toward n/(2n-1) ≈ 1/2 as the pipeline fills."""
    r = max(1, int(repeats))
    n = max(1, int(n))
    return (n + (n - 1) / r) / (2 * n - 1)


def terms_from_describe(desc: Mapping[str, Any]) -> Dict[str, Any]:
    """Machine-usable cost terms for one `Plan.describe()` record.

    This is the single owner of the byte/FLOP arithmetic `roofline
    .analyze_plan` historically computed inline (same conventions: ring
    schedules stream `kernel_invocations` A chunks and output tiles per
    call, batched_b scales per-element traffic by the batch, grouped specs
    stream every group's weight slab plus the dispatch routing bytes, with
    EP scaling both to the per-device share).  Unknown record shapes
    degrade to the plain-GEMM arithmetic instead of raising.
    """
    sh = desc.get("sharding") or {}
    grp = desc.get("grouped") or {}
    flops = sh.get("per_shard_flops", desc["flops"])
    if "per_shard_mkn" in sh:
        m, k, n = (int(x) for x in sh["per_shard_mkn"])
        # batched_b local specs keep their batch dims out of eff_m
        nb = math.prod(sh.get("per_shard_batch") or [1])
    else:
        m, k, n = (int(x) for x in desc["mkn"].split("x"))
        # "mkn" folds batch into M only for 2D b; batched_b products stream
        # per-element A/B/C, so scale bytes to match the batch-inclusive FLOPs
        nb = math.prod(desc.get("batch") or [1]) if desc.get("batched_b") else 1
    dt_a, dt_b = desc.get("dtypes", ["float32", "float32"])
    ia = np.dtype(dt_a).itemsize
    ib = np.dtype(dt_b).itemsize
    io = np.dtype(desc.get("out_dtype") or "float32").itemsize
    # Ring schedules re-invoke the per-shard kernel once per step: the device
    # streams `inv` A chunks and writes `inv` output tiles per call.
    inv = int(sh.get("kernel_invocations", 1))
    dispatch_bytes = 0
    if grp:
        # Grouped: M is the total row bound (rows stream once), but the
        # weight term is per GROUP — every (K, N) slab streams — and the
        # sort/scatter/gather routing traffic rides the memory term too.
        n_groups = grp.get("num_groups", 1)
        dispatch_bytes = grp.get("dispatch_bytes", 0)
        if sh:
            # expert schedule: `m` above is already the per-shard row count
            # (per_shard_mkn); scale group count and dispatch traffic to the
            # per-device share using the group axis size from the record
            mesh_sizes = {nm: s for nm, s in sh.get("mesh", [])}
            pg = mesh_sizes.get((sh.get("axes") or {}).get("g"), 1) or 1
            n_groups = max(1, n_groups // pg)
            dispatch_bytes //= pg
        a_bytes = m * k * ia
        b_bytes = n_groups * k * n * ib
        out_bytes = m * n * io
    else:
        a_bytes = nb * inv * m * k * ia
        b_bytes = nb * k * n * ib
        out_bytes = nb * inv * m * n * io
    return {
        "flops": int(flops),
        "a_bytes": int(a_bytes),
        "b_bytes": int(b_bytes),
        "out_bytes": int(out_bytes),
        "dispatch_bytes": int(dispatch_bytes),
        "hbm_bytes": int(a_bytes + b_bytes + out_bytes + dispatch_bytes),
        "collective_bytes": int(sh.get("bytes_moved", 0)),
        "collective_phases": int(sh.get("collective_phases", 0)),
        "kernel_invocations": inv,
        "overlap": bool(sh.get("overlap", False)),
        "schedule": sh.get("schedule"),
        "structure": desc.get("structure", "general"),
        "readout_n": n,
        "repeats": int(desc.get("repeats", 1)),
        "backend": desc.get("backend"),
    }


def predict(
    terms: Mapping[str, Any],
    coeffs: CostCoefficients,
    *,
    backend: Optional[str] = None,
) -> Dict[str, float]:
    """Predicted seconds for one execution of a plan with these terms.

    total = max(compute, memory) + collective + latency — compute overlaps
    HBM streaming, a serial collective is a barrier, and latency charges
    the per-phase and per-launch fixed costs.  Terms with `overlap` set
    (the double-buffered ring schedules) hide the collective behind the
    kernel calls instead: total = max(compute, memory, collective) +
    latency.  The paper-structure factors scale the compute term
    (symmetric early readout) and amortize launch latency and B streaming
    over `repeats` pipelined products.
    """
    be = backend if backend is not None else terms.get("backend")
    eff = max(coeffs.efficiency(be), 1e-6)
    n = int(terms.get("readout_n", 1))
    r = max(1, int(terms.get("repeats", 1)))
    factor = structure_step_factor(terms.get("structure", "general"), n)
    amort = repeat_amortization(r, n)
    t_compute = terms["flops"] / (coeffs.flops_per_s * eff) * factor * amort
    # With repeats the weights stay resident: B streams once per r products.
    hbm = (
        terms.get("a_bytes", 0)
        + terms.get("out_bytes", 0)
        + terms.get("dispatch_bytes", 0)
        + terms.get("b_bytes", 0) / r
    )
    if not any(k in terms for k in ("a_bytes", "b_bytes", "out_bytes")):
        hbm = terms.get("hbm_bytes", 0)
    t_memory = hbm / coeffs.hbm_bytes_per_s
    t_collective = terms.get("collective_bytes", 0) / coeffs.link_bytes_per_s
    t_latency = (
        terms.get("collective_phases", 0) * coeffs.phase_latency_s
        + terms.get("kernel_invocations", 1) * coeffs.launch_overhead_s * amort
    )
    if terms.get("overlap"):
        # double-buffered ring: hops hidden behind kernel calls (§15)
        total = max(t_compute, t_memory, t_collective) + t_latency
    else:
        total = max(t_compute, t_memory) + t_collective + t_latency
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "t_latency_s": t_latency,
        "total_s": total,
    }


def predict_blocks_ms(
    m: int, k: int, n: int, blocks: Tuple[int, int, int], coeffs: CostCoefficients
) -> float:
    """Predicted milliseconds for one (bm, bn, bk)-blocked GEMM — the cost
    model's block scorer (lower is better, unlike autotune.model_score).

    The padded iteration space sets the compute term (overhang blocks issue
    dead MXU slots) and per-phase streaming sets the memory term; used by
    the autotuner's optional cost-model ranking once coefficients are
    calibrated.
    """
    bm, bn, bk = blocks
    ceil = lambda a, b: -(-a // b)
    pm, pn, pk = ceil(m, bm) * bm, ceil(n, bn) * bn, ceil(k, bk) * bk
    flops = 2 * pm * pn * pk
    # every (i, j) cell streams its A row-block and B col-block per k phase
    phases = ceil(k, bk)
    bytes_streamed = ceil(m, bm) * ceil(n, bn) * phases * (bm * bk + bk * bn) * 4
    t = max(
        flops / coeffs.flops_per_s,
        bytes_streamed / coeffs.hbm_bytes_per_s,
    )
    return t * 1e3
