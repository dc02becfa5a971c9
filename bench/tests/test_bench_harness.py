"""The harness finds cells, configurations, mixes and metrics by name, and
refuses to measure anywhere but on a TPU."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.harness import BENCH, ROOT


def _digest(root: Path):
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
    }


def test_every_cell_of_the_benchmark_is_found():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.mix["kind"] in ("train", "serve_open", "serve_backlog")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        moved = {m["moves"] for m in cell.per_layer}
        assert moved <= {m["name"] for m in cell.end_to_end}
        for m in cell.end_to_end:
            assert harness.load_reader("metrics", m["name"])
        for m in cell.per_layer:
            assert harness.load_reader("layer_metrics", m["name"])


def test_a_cell_runs_the_programs_values_where_it_departs():
    """The granite file states the published multipliers; the cell carries
    the values the program runs, which the reference follows."""
    raw = json.loads((BENCH / "configs" / "granite-3-8b.json").read_text())
    assert raw["embedding_multiplier"] == 12.0 and raw["reduced"] == ["num_hidden_layers"]
    cfg = harness.find_cell("granite-3-8b.offline-long").config
    assert cfg["embedding_multiplier"] == 1.0
    assert cfg["attention_multiplier"] == pytest.approx(128**-0.5)
    assert (cfg["residual_multiplier"], cfg["logits_scaling"]) == (1.0, 1.0)


def test_a_metric_split_by_kind_reads_its_familys_file(tmp_path):
    bench = tmp_path / "bench"
    (bench / "layer_metrics").mkdir(parents=True)
    (bench / "layer_metrics" / "busy.py").write_text("def read(run):\n    return 1\n")
    (bench / "layer_metrics" / "busy.train.py").write_text("def read(run):\n    return 2\n")
    assert harness.load_reader("layer_metrics", "busy.chat", bench)(None) == 1
    assert harness.load_reader("layer_metrics", "busy.train", bench)(None) == 2


def test_new_files_and_entries_alone_add_a_cell(tmp_path):
    """A configuration, a mix, a per-layer metric and a cell, added as new
    files and new entries of BENCHMARK.json, with no edit to a file that is
    already there."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__", "cache", "out"))
    bench = root / "bench"
    before = _digest(bench)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())

    cfg = json.loads((bench / "configs" / "mesh-paper.json").read_text())
    cfg["num_hidden_layers"] = 2
    (bench / "configs" / "mesh-paper-2l.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "chat.json").read_text())
    mix["rate_per_s"] = 2.0
    (bench / "traffic" / "chat-slow.json").write_text(json.dumps(mix))
    (bench / "limits" / "mesh-paper-2l.chat-slow.json").write_text(
        (bench / "limits" / "mesh-paper.chat.json").read_text()
    )
    (bench / "layer_metrics" / "slots_seen.chat-slow.py").write_text(
        "def read(run):\n    return run.data.get('slots_seen')\n"
    )
    spec["configs"].append(
        {"name": "mesh-paper-2l", "source": "https://arxiv.org/abs/1010.5421",
         "file": "bench/configs/mesh-paper-2l.json", "reduced": ["num_hidden_layers"], "why": "x"}
    )
    spec["workloads"].append(
        {"name": "mesh-paper-2l.chat-slow", "config": "mesh-paper-2l", "traffic": "chat-slow",
         "chips": 1, "why": "x"}
    )
    for m in spec["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            m["workloads"].append("mesh-paper-2l.chat-slow")
    spec["per_layer"].append(
        {"name": "slots_seen.chat-slow", "unit": "slots", "better": "higher",
         "source": "program_counter", "layer": "scheduler", "moves": "tpot_p95_ms",
         "workloads": ["mesh-paper-2l.chat-slow"]}
    )

    cell = harness.find_cell("mesh-paper-2l.chat-slow", spec=spec, bench=bench)
    assert cell.config["num_hidden_layers"] == 2
    assert cell.mix["rate_per_s"] == 2.0
    assert [m["name"] for m in cell.per_layer] == ["slots_seen.chat-slow"]
    run = harness.RunRecord(cell=cell, peaks=None, window_s=1.0, setup_s=1.0,
                            data={"slots_seen": 7, "ttft_s": [0.1], "tpot_s": [0.01]})
    got = harness.read_metrics(cell.per_layer, "layer_metrics", run, bench=bench)
    assert got == {"slots_seen.chat-slow": {"value": 7.0, "unit": "slots"}}
    e2e = harness.read_metrics(cell.end_to_end, "metrics", run, bench=bench)
    assert set(e2e) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def _run(cwd: Path, env_extra=None):
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST")}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mesh-paper.chat", "--seed",
         str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_a_platform_that_is_not_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "TPU only" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__", "cache", "out"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "no program" in r.stderr
    assert r.stdout.strip() == ""
