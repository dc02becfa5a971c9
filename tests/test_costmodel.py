"""Cost-model subsystem tests (DESIGN.md §13): model arithmetic, calibration
cache resilience, decision provenance, and the auto-resolution properties —
legality, determinism for a fixed calibration file, and bitwise parity with
explicitly pinned schedules."""

import dataclasses
import importlib
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the package re-exports the calibrate() FUNCTION, which shadows the
# submodule attribute — import the module itself for its internals
cal = importlib.import_module("repro.costmodel.calibrate")
from repro.costmodel import choose as choose_mod
from repro.costmodel.model import (
    COST_MODEL_VERSION,
    CostCoefficients,
    default_coefficients,
    predict,
    predict_blocks_ms,
    repeat_amortization,
    structure_step_factor,
    terms_from_describe,
)
from repro.kernels import api
from repro.kernels.api import Epilogue, GemmSpec, GroupSpec, ShardSpec

from hypothesis import given, settings, strategies as st

B = 8


@pytest.fixture(autouse=True)
def _isolated_costmodel_cache(tmp_path, monkeypatch):
    """Every test reads/writes a scratch calibration file — the repo-level
    `.costmodel_cache.json` must never be created or consulted by tests."""
    monkeypatch.setenv("REPRO_COSTMODEL_CACHE", str(tmp_path / "costmodel.json"))
    cal.clear_coefficients_memo()
    choose_mod.clear_decision_memo()
    yield
    cal.clear_coefficients_memo()
    choose_mod.clear_decision_memo()


def _axes(p=4):
    return (("x", p),)


# --- model arithmetic ---------------------------------------------------------


def test_coefficients_round_trip_and_unknown_keys():
    co = default_coefficients("cpu")
    d = co.as_dict()
    assert isinstance(d["backend_efficiency"], dict)  # JSON-friendly mapping
    back = CostCoefficients.from_dict({**d, "not_a_field": 1})
    assert back == co
    assert co.efficiency("xla") == 1.0
    assert co.efficiency("never_registered") == co.default_efficiency


def test_structure_step_factor_matches_exact_symmetric_counts():
    from repro.core.symmetries import symmetric_readout_steps

    assert structure_step_factor("general", 64) == 1.0
    assert structure_step_factor("scrambled", 64) == 1.0
    for n in (4, 16, 64, 128):
        assert structure_step_factor("symmetric", n) == (
            symmetric_readout_steps(n) / (2 * n - 1)
        )
    # beyond the exact range: the floor(3n/2) closed form, still < 1
    assert 0 < structure_step_factor("symmetric", 1024) < 0.76


def test_repeat_amortization_limits():
    n = 64
    assert repeat_amortization(1, n) == 1.0
    vals = [repeat_amortization(r, n) for r in (1, 2, 4, 8, 64)]
    assert vals == sorted(vals, reverse=True)  # monotone toward n/(2n-1)
    assert vals[-1] < 0.52


def test_terms_match_real_describe_records_and_roofline():
    from repro.launch.roofline import analyze_plan

    plans = [
        api.plan(GemmSpec(m=2 * B, k=B, n=B)),
        api.plan(GemmSpec(m=B, k=B, n=B, batch=(4,), batched_b=True)),
        api.plan(GemmSpec.for_groups(GroupSpec(4, B), k=B, n=B)),
    ]
    for p in plans:
        d = p.describe()
        t = terms_from_describe(d)
        rl = analyze_plan(d)
        # roofline consumes the SAME terms (single arithmetic owner)
        assert rl["terms"] == t
        assert rl["hbm_bytes"] == t["hbm_bytes"]
        assert rl["per_shard_flops"] == t["flops"]
        json.dumps(t)


def test_predict_prices_paper_structures():
    co = default_coefficients("cpu")
    n = 64
    gen = terms_from_describe(api.plan(GemmSpec(m=n, k=n, n=n)).describe())
    sym = dict(gen, structure="symmetric")
    assert predict(sym, co)["t_compute_s"] < predict(gen, co)["t_compute_s"]
    # repeats amortize compute AND the resident-B stream
    rep = dict(gen, repeats=8)
    assert predict(rep, co)["total_s"] < predict(gen, co)["total_s"]
    # the collective term is additive on top of max(compute, memory)
    coll = dict(gen, collective_bytes=10**9)
    out = predict(coll, co)
    assert out["t_collective_s"] == 10**9 / co.link_bytes_per_s
    assert out["total_s"] == pytest.approx(
        max(out["t_compute_s"], out["t_memory_s"]) + out["t_collective_s"]
    )


def test_predict_blocks_ms_prefers_divisible_blocks():
    co = default_coefficients("cpu")
    # exact tiling beats a pathological overhang (padded dead FLOPs)
    assert predict_blocks_ms(256, 256, 256, (128, 128, 128), co) < predict_blocks_ms(
        256, 256, 256, (129, 129, 129), co
    )


# --- calibration cache resilience --------------------------------------------


def test_calibration_cache_quarantines_corrupt_file(tmp_path):
    path = tmp_path / "cal.json"
    path.write_text("{not json")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cache = cal.CalibrationCache(path)
        assert cache.coefficients("cpu") is None
    assert any("unreadable" in str(x.message) for x in w)
    assert (tmp_path / "cal.json.corrupt").exists()
    # the quarantined store still works
    cache.set_coefficients(default_coefficients("cpu"))
    cache.save()
    assert cal.CalibrationCache(path).coefficients("cpu") is not None


def test_calibration_cache_drops_invalid_records_and_versions(tmp_path):
    path = tmp_path / "cal.json"
    good = {"terms": {"flops": 1000}, "ms": 1.0, "source": "probe"}
    path.write_text(
        json.dumps(
            {
                "version": cal.CALIBRATION_VERSION,
                "model_version": COST_MODEL_VERSION,
                "coefficients": {"cpu": {"flops_per_s": -1}},  # invalid
                "records": {"cpu": [good, {"ms": -3}, "junk"]},
            }
        )
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        cache = cal.CalibrationCache(path)
        assert cache.coefficients("cpu") is None  # invalid coefficients dropped
        assert cache.records("cpu") == [good]
    # unknown version: start clean, never steer plans with stale fits
    path.write_text(json.dumps({"version": 999, "coefficients": {"cpu": {}}}))
    assert cal.CalibrationCache(path).coefficients("cpu") is None


def test_fit_coefficients_is_deterministic_and_reduces_error():
    terms = terms_from_describe(api.plan(GemmSpec(m=64, k=64, n=64)).describe())
    # synthesize measurements from a ground truth 4x slower than defaults
    truth = dataclasses.replace(
        default_coefficients("cpu"), flops_per_s=2.5e10, hbm_bytes_per_s=5e9
    )
    records = []
    for scale in (1, 2, 4, 8):
        t = dict(terms)
        t["flops"] *= scale**3
        t["a_bytes"] *= scale**2
        t["b_bytes"] *= scale**2
        t["out_bytes"] *= scale**2
        t["hbm_bytes"] *= scale**2
        records.append({"terms": t, "ms": predict(t, truth)["total_s"] * 1e3})
    init = default_coefficients("cpu")
    fit1 = cal.fit_coefficients(records, init=init)
    fit2 = cal.fit_coefficients(records, init=init)
    assert fit1 == fit2 and fit1.source == "calibrated"
    assert cal._fit_error(records, fit1) < cal._fit_error(records, init)


def test_calibrate_round_trip_installs_coefficients(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_COSTMODEL_CACHE", str(tmp_path / "cc.json"))
    cal.clear_coefficients_memo()
    assert cal.current_coefficients().source == "default"
    got = cal.calibrate(shapes=((16, 16, 16), (64, 64, 64)))
    assert got.source == "calibrated"
    assert cal.current_coefficients() == got  # memo refreshed
    cal.clear_coefficients_memo()
    assert cal.current_coefficients() == got  # persisted + reloaded


def test_hillclimb_gemm_variant_writes_ingestible_records(tmp_path):
    from repro.launch.hillclimb import run_gemm_variant

    rec = run_gemm_variant("G0_tiny", out_dir=str(tmp_path), reps=1)
    assert cal._valid_record(rec) and rec["source"] == "hillclimb"
    on_disk = json.loads((tmp_path / "gemm__G0_tiny.json").read_text())
    assert cal._valid_record(on_disk)
    assert cal.ingest([rec]) == 1
    assert cal.current_coefficients().source == "calibrated"


# --- decisions ---------------------------------------------------------------


def test_auto_schedule_decision_provenance():
    spec = GemmSpec(m=16, k=32, n=8, shard=ShardSpec(_axes(4), axis_k="x"))
    sched, dec = choose_mod.decide_schedule(spec)
    assert sched == "reduce_scatter_k"
    d = dec.as_dict()
    json.dumps(d)
    assert d["chosen"] == "reduce_scatter_k"
    assert d["calibration"]["model_version"] == COST_MODEL_VERSION
    by_name = {c["name"]: c for c in d["candidates"]}
    assert by_name["reduce_scatter_k"]["legal"]
    assert by_name["ring_k"]["legal"]
    # rs moves 1/p of ring's bytes -> strictly cheaper prediction
    assert (
        by_name["reduce_scatter_k"]["predicted_s"] < by_name["ring_k"]["predicted_s"]
    )
    assert not by_name["replicated"]["legal"]
    assert not by_name["allgather_a"]["legal"]


def test_auto_matches_legacy_heuristic_with_default_coefficients():
    # the shipped zero-latency coefficients reproduce the legacy rule exactly
    cases = [
        (GemmSpec(m=16, k=32, n=8, shard=ShardSpec(_axes(4), axis_k="x")),
         "reduce_scatter_k"),
        (GemmSpec(m=6, k=32, n=8, shard=ShardSpec(_axes(4), axis_k="x")),
         "ring_k"),
        (GemmSpec(m=16, k=32, n=8, shard=ShardSpec(_axes(4), axis_m="x")),
         "replicated"),
        (GemmSpec(m=16, k=32, n=8, shard=ShardSpec(_axes(1))), "replicated"),
    ]
    for spec, want in cases:
        sched, *_ = api._resolve_sharding(spec)
        assert sched == want, (spec, sched, want)


def test_calibrated_latency_steers_auto_schedule(tmp_path, monkeypatch):
    """A calibration file with a large per-launch overhead flips the choice
    to ring_k (1 kernel invocation) over reduce_scatter_k (p invocations) —
    and the resolution is deterministic for the fixed file."""
    spec = GemmSpec(m=16, k=32, n=8, shard=ShardSpec(_axes(4), axis_k="x"))
    path = tmp_path / "steer.json"
    co = dataclasses.replace(default_coefficients("cpu"), launch_overhead_s=1.0)
    cache = cal.CalibrationCache(path)
    cache.set_coefficients(co)
    cache.save()
    monkeypatch.setenv("REPRO_COSTMODEL_CACHE", str(path))
    cal.clear_coefficients_memo()
    choose_mod.clear_decision_memo()
    picks = set()
    for _ in range(3):
        sched, dec = choose_mod.decide_schedule(spec)
        picks.add(sched)
        assert dec.as_dict()["calibration"]["source"] == "calibrated"
    assert picks == {"ring_k"}


def test_calibrated_link_admits_overlap_schedules(tmp_path, monkeypatch):
    """Under shipped (default) coefficients the overlap family never enters
    auto resolution; a CALIBRATED slow link flips the choice to the
    double-buffered twin, with the §15 max(compute, comm) pricing recorded
    in the decision provenance."""
    spec = GemmSpec(m=16, k=32, n=8, shard=ShardSpec(_axes(4), axis_k="x"))
    _, dec = choose_mod.decide_schedule(spec)
    names = [c["name"] for c in dec.as_dict()["candidates"]]
    assert not any(c.endswith("_overlap") or c == "pipeline" for c in names)

    path = tmp_path / "slowlink.json"
    co = dataclasses.replace(default_coefficients("cpu"), link_bytes_per_s=1e6)
    cache = cal.CalibrationCache(path)
    cache.set_coefficients(co)
    cache.save()
    monkeypatch.setenv("REPRO_COSTMODEL_CACHE", str(path))
    cal.clear_coefficients_memo()
    choose_mod.clear_decision_memo()
    sched, dec = choose_mod.decide_schedule(spec)
    # the collective term dominates; hiding it behind the kernel wins, and
    # reduce_scatter's byte model beats ring/pipeline at equal pricing
    assert sched == "reduce_scatter_k_overlap"
    cands = {c["name"]: c for c in dec.as_dict()["candidates"] if c.get("legal")}
    win, serial = cands[sched], cands["reduce_scatter_k"]
    assert win["overlap"] is True and serial["overlap"] is False
    assert win["pricing"] == "max(compute,memory,collective)+latency"
    assert serial["pricing"] == "max(compute,memory)+collective+latency"
    assert win["predicted_s"] < serial["predicted_s"]

    # the planner's auto path records the same chosen schedule
    api.clear_plan_cache()
    got, _, _, _, decision = api._resolve_sharding(spec)
    assert got == "reduce_scatter_k_overlap"
    assert decision["chosen"] == "reduce_scatter_k_overlap"


def test_decide_backend_ranks_capable_set():
    spec = GemmSpec(m=B, k=B, n=B)
    chosen, dec = choose_mod.decide_backend(
        spec, [("xla", 0), ("pallas_mesh", 1), ("ref", 2)]
    )
    assert chosen == "xla"  # efficiency 1.0 beats 0.05 / 0.01 on cpu
    names = [c["name"] for c in dec.as_dict()["candidates"]]
    assert names == ["xla", "pallas_mesh", "ref"]


def test_plan_records_backend_decision():
    api.clear_plan_cache()
    p = api.plan(GemmSpec(m=B, k=B, n=B))
    assert p.backend == "xla"
    d = p.describe()
    json.dumps(d)
    dec = d["decision"]["backend"]
    assert dec["chosen"] == "xla" and len(dec["candidates"]) >= 2
    # explicit backend: no decision to record
    assert api.plan(GemmSpec(m=B, k=B, n=B), backend="ref").decision is None


def test_spec_repeats_validated_and_in_provenance():
    with pytest.raises(ValueError, match="repeats"):
        GemmSpec(m=B, k=B, n=B, repeats=0)
    p = api.plan(GemmSpec(m=B, k=B, n=B, repeats=8), backend="xla")
    d = p.describe()
    assert d["repeats"] == 8
    assert terms_from_describe(d)["repeats"] == 8


# --- auto resolution properties ----------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(min_value=1, max_value=12),
    k_mult=st.integers(min_value=1, max_value=6),
    n=st.integers(min_value=1, max_value=12),
    p=st.sampled_from([2, 3, 4]),
    dim=st.sampled_from(["m", "k", "n"]),
)
def test_auto_never_selects_illegal_schedule(m, k_mult, n, p, dim):
    """Whatever (spec, shard axes) is drawn, auto either resolves to a
    schedule that passes the full divisibility validation when explicitly
    pinned, or raises PlanValidationError itself — it never silently picks
    an illegal schedule."""
    axes = (("x", p),)
    shard = ShardSpec(axes, **{f"axis_{dim}": "x"})
    spec = GemmSpec(m=m, k=k_mult * p, n=n, shard=shard)
    try:
        sched, local, bytes_moved, phases, _ = api._resolve_sharding(spec)
    except api.PlanValidationError:
        return  # no legal schedule for this draw: raising IS the contract
    assert sched in api.SCHEDULES
    pinned = dataclasses.replace(
        spec, shard=dataclasses.replace(shard, schedule=sched)
    )
    got = api._resolve_sharding(pinned)  # must not raise
    assert got[0] == sched and got[1] == local and got[2] == bytes_moved


def test_auto_plan_bitwise_equals_explicit_schedule():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    a = jnp.asarray(
        np.random.default_rng(0).normal(size=(2 * B, B)).astype(np.float32)
    )
    b = jnp.asarray(np.random.default_rng(1).normal(size=(B, B)).astype(np.float32))
    auto_spec = GemmSpec.from_operands(a, b, shard=ShardSpec.from_mesh(mesh, m="x"))
    p_auto = api.plan(auto_spec, mesh=mesh)
    chosen = p_auto.schedule
    explicit = GemmSpec.from_operands(
        a, b, shard=ShardSpec.from_mesh(mesh, m="x", schedule=chosen)
    )
    p_exp = api.plan(explicit, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(p_auto(a, b)), np.asarray(p_exp(a, b)))
    # provenance: the auto plan carries the decision, the pinned one doesn't
    assert (p_auto.describe().get("decision") or {}).get("schedule")
    assert (p_exp.describe().get("decision") or {}).get("schedule") is None


def test_auto_shard_is_deterministic_and_memoized():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    spec = GemmSpec(m=2 * B, k=B, n=B)
    s1, d1 = choose_mod.decide_sharding(spec, mesh)
    s2, d2 = choose_mod.decide_sharding(spec, mesh)
    assert s1 == s2 and d1 is d2  # memo hit
    choose_mod.clear_decision_memo()
    s3, d3 = choose_mod.decide_sharding(spec, mesh)
    assert s3 == s1 and d3.as_dict()["chosen"] == d1.as_dict()["chosen"]


# --- roofline formatting (satellite) -----------------------------------------


def test_fmt_s_unit_ranges():
    from repro.launch.roofline import _fmt_s

    assert _fmt_s(2.5) == "2.50s"
    assert _fmt_s(1.0) == "1.00s"
    assert _fmt_s(0.0042) == "4.20ms"
    assert _fmt_s(1e-3) == "1.00ms"
    assert _fmt_s(2e-5) == "20.0us"
    assert _fmt_s(0.0) == "0.0us"


def test_render_markdown_rows_and_skips():
    from repro.launch.roofline import render_markdown

    rows = [
        {
            "arch": "a1", "shape": "s1", "t_compute_s": 0.5, "t_memory_s": 2e-3,
            "t_collective_s": 3e-6, "dominant": "compute", "useful_ratio": 0.5,
            "roofline_fraction": 0.25,
        },
        {"skip": True, "arch": "a2", "shape": "s2", "status": "oom",
         "reason": "too big"},
    ]
    md = render_markdown(rows, title="T")
    lines = md.strip().splitlines()
    assert lines[0] == "### T"
    assert lines[2].startswith("| arch | shape |")  # blank line after title
    assert "500.00ms" in md and "2.00ms" in md and "3.0us" in md
    assert "**compute**" in md
    assert "OOM" in md and "too big" in md
    # no title -> header first
    assert render_markdown(rows).startswith("| arch ")


def test_tpu_peaks_are_keyed_by_device_kind():
    from repro.costmodel.model import TPU_PEAKS, tpu_peaks

    assert tpu_peaks("TPU v5 lite") == (197e12, 819e9, 50e9)
    co = default_coefficients("tpu", "TPU v5 lite")
    assert (co.flops_per_s, co.hbm_bytes_per_s) == TPU_PEAKS["TPU v5 lite"][:2]
    with pytest.raises(ValueError, match="no published peaks"):
        default_coefficients("tpu", "TPU v9 imaginary")
    with pytest.raises(ValueError, match="no published peaks"):
        default_coefficients("tpu")
