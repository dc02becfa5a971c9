"""launch/: input specs, hlo_stats parsing, roofline math, production mesh.

The 512-device production mesh is exercised in a subprocess (XLA_FLAGS must
be set before jax init; the main test process stays at 1 device).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config
from repro.launch.hlo_stats import collective_stats
from repro.launch.roofline import HBM_BW, LINK_BW, PEAK_FLOPS, analyze_artifact


# --- input_specs ---------------------------------------------------------------


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_input_specs_all_cells(arch):
    """Every applicable (arch x shape) cell produces abstract input specs."""
    from repro.launch.dryrun import _cell_applicable, input_specs

    cfg = get_config(arch)
    for shape_name, shape in SHAPES.items():
        if _cell_applicable(cfg, shape):
            continue  # documented skip
        specs = input_specs(arch, shape_name)
        leaves = jax.tree.leaves(specs)
        assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves if hasattr(l, "shape"))
        if shape.kind in ("train", "prefill"):
            assert specs["batch"]["tokens"].shape[0] == shape.global_batch
        else:
            assert specs["tokens"].shape == (shape.global_batch, 1)


def test_long_500k_skips_exactly_the_full_attention_archs():
    from repro.launch.dryrun import _cell_applicable

    skipped = {
        a for a in ASSIGNED_ARCHS
        if _cell_applicable(get_config(a), SHAPES["long_500k"])
    }
    assert skipped == {
        "olmoe-1b-7b", "qwen2-moe-a2.7b", "granite-3-8b", "phi3-medium-14b",
        "qwen2-7b", "mistral-large-123b", "whisper-medium", "pixtral-12b",
    }
    assert {"rwkv6-1.6b", "zamba2-1.2b"}.isdisjoint(skipped)


# --- hlo_stats ------------------------------------------------------------------

HLO_SAMPLE = """
  %x = f32[16,1024]{1,0} parameter(0)
  %ar = f32[16,1024]{1,0} all-reduce(f32[16,1024]{1,0} %x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = bf16[64,256]{1,0} all-gather(bf16[16,256]{1,0} %y), replica_groups={{0,1},{2,3}}, dimensions={0}
  %rs = f32[4,128]{1,0} reduce-scatter(f32[16,128]{1,0} %z), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %w), source_target_pairs={{0,1},{1,0}}
"""


def test_collective_stats_parsing():
    stats = collective_stats(HLO_SAMPLE)
    assert stats["all-reduce"]["count"] == 1
    # all-reduce: 2 * (n-1)/n * payload, n=4, payload=16*1024*4
    assert stats["all-reduce"]["link_bytes"] == pytest.approx(2 * 0.75 * 16 * 1024 * 4)
    # all-gather result bf16[64,256] -> 2 bytes, n=2 -> 0.5 multiplier
    assert stats["all-gather"]["link_bytes"] == pytest.approx(0.5 * 64 * 256 * 2)
    assert stats["reduce-scatter"]["count"] == 1
    assert stats["collective-permute"]["link_bytes"] == pytest.approx(8 * 8 * 4)


def test_collective_stats_skips_done_ops():
    text = "%d = f32[4]{0} all-reduce-done(f32[4]{0} %s)\n"
    assert collective_stats(text) == {}


# --- roofline math ----------------------------------------------------------------


def test_analyze_artifact_terms():
    art = {
        "status": "ok",
        "arch": "x",
        "shape": "train_4k",
        "mesh": "pod16x16",
        "kind": "train",
        "n_devices": 256,
        "flops_per_device": 1e12,
        "bytes_per_device": 1e11,
        "collective_link_bytes": 5e9,
        "n_active_params": 1e9,
        "n_params": 1e9,
        "tokens_per_step": 1_000_000,
    }
    r = analyze_artifact(art)
    assert r["t_compute_s"] == pytest.approx(1e12 / PEAK_FLOPS)
    assert r["t_memory_s"] == pytest.approx(1e11 / HBM_BW)
    assert r["t_collective_s"] == pytest.approx(5e9 / LINK_BW)
    assert r["dominant"] == "memory"
    assert r["model_flops"] == pytest.approx(6e15)
    assert r["roofline_fraction"] == pytest.approx(
        (6e15 / (256 * PEAK_FLOPS)) / r["t_memory_s"]
    )


def test_analyze_artifact_prefers_corrected():
    art = {
        "status": "ok", "arch": "x", "shape": "decode_32k", "mesh": "m", "kind": "decode",
        "n_devices": 256, "flops_per_device": 1.0, "bytes_per_device": 1.0,
        "collective_link_bytes": 1.0,
        "flops_per_device_corrected": 10.0, "bytes_per_device_corrected": 20.0,
        "collective_link_bytes_corrected": 30.0, "recurrence_bytes_analytic": 5.0,
        "n_active_params": 1, "tokens_per_step": 1,
    }
    r = analyze_artifact(art)
    assert r["t_compute_s"] == pytest.approx(10.0 / PEAK_FLOPS)
    assert r["t_memory_s"] == pytest.approx(25.0 / HBM_BW)
    assert r["t_collective_s"] == pytest.approx(30.0 / LINK_BW)


def test_analyze_artifact_skipped_is_none():
    assert analyze_artifact({"status": "skipped"}) is None


# --- production mesh (512 fake devices, subprocess) ------------------------------


@pytest.mark.slow
def test_production_meshes_subprocess():
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        import jax
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        assert dict(m1.shape) == {"data": 16, "model": 16}, m1.shape
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}, m2.shape
        assert m2.size == 512
        print("OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


@pytest.mark.slow
def test_dryrun_cell_on_small_production_slice():
    """Full dry-run machinery on a 4x4=16-device mesh (fast CI analogue of
    the 256-chip pod): lower + compile + artifact fields present."""
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import json
        import repro.launch.mesh as mesh_mod
        import jax
        real = mesh_mod.make_production_mesh
        mesh_mod.make_production_mesh = lambda multi_pod=False: mesh_mod._make_mesh(
            (4, 4), ("data", "model"))
        from repro.launch.dryrun import run_cell
        art = run_cell("rwkv6-1.6b", "decode_32k", probe=False, verbose=False)
        assert art["status"] == "ok", art
        for k in ("flops_per_device", "bytes_per_device", "collective_link_bytes",
                  "memory_analysis", "tokens_per_step"):
            assert k in art, k
        print("OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=560,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_all_sweep_artifacts_ok_or_documented_skip():
    """If the artifact sweep has been run, every cell must be ok/skipped."""
    base = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "artifacts")
    found = 0
    for mesh_dir in ("pod16x16", "pod2x16x16"):
        d = os.path.join(base, mesh_dir)
        if not os.path.isdir(d):
            continue
        for f in os.listdir(d):
            if not f.endswith(".json"):
                continue
            art = json.load(open(os.path.join(d, f)))
            assert art["status"] in ("ok", "skipped"), (f, art.get("error"))
            found += 1
    if found:
        assert found >= 80  # 40 cells x 2 meshes


@pytest.mark.slow
def test_probe_correction_matches_ground_truth():
    """The scan-cost probe (L=2,4 unrolled -> slope -> extrapolate) must
    reproduce the TRUE cost of a fully-unrolled model at full depth.
    Run on a 4x2=8-device mesh with a 6-layer reduced config."""
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax
        import repro.launch.mesh as mesh_mod
        mesh_mod.make_production_mesh = lambda multi_pod=False: mesh_mod._make_mesh(
            (4, 2), ("data", "model"))
        from repro.configs import SHAPES, get_config
        from repro.launch.dryrun import (
            _cost_triple, _rules_for, build_lowered, probe_corrected_costs)

        cfg = dataclasses.replace(
            get_config("qwen2-7b").reduced(), num_layers=6)
        shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
        mesh = mesh_mod.make_production_mesh()
        rules = _rules_for(cfg, shape, mesh)
        probe = probe_corrected_costs(cfg, shape, mesh, rules)
        truth = _cost_triple(
            build_lowered(
                dataclasses.replace(cfg, scan_unroll=True), shape, mesh, rules
            ).compile()
        )
        rel = abs(probe["flops"] - truth["flops"]) / truth["flops"]
        assert rel < 0.05, (probe["flops"], truth["flops"], rel)
        rel_b = abs(probe["bytes"] - truth["bytes"]) / truth["bytes"]
        assert rel_b < 0.15, (probe["bytes"], truth["bytes"], rel_b)
        print("OK", rel, rel_b)
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


@pytest.mark.slow
def test_multipod_mesh_cell_with_pod_axis():
    """'pod' axis rules compose: lower+compile a decode cell on a tiny
    (pod=2, data=2, model=2) mesh."""
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import repro.launch.mesh as mesh_mod
        mesh_mod.make_production_mesh = lambda multi_pod=False: mesh_mod._make_mesh(
            (2, 2, 2), ("pod", "data", "model"))
        from repro.launch.dryrun import run_cell
        art = run_cell("zamba2-1.2b", "decode_32k", multi_pod=True,
                       probe=False, verbose=False)
        assert art["status"] == "ok", art
        print("OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# --- persistent compilation cache ------------------------------------------------


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only cache; otherwise the
    entry points use <repo>/.jax_cache.  A fresh process, since the helper
    changes process-wide JAX config."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(root, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    prog = (
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "got = enable_compile_cache()\n"
        "print(got, jax.config.jax_compilation_cache_dir)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", prog], cwd=root, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
