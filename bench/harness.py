"""What every cell shares: finding its files by name, the caches, the chip
check, the health checks, the metric readers and the result line.

A cell of BENCHMARK.json names a configuration and a traffic mix.  The
configuration is the file its entry names (configs/<name>.json); the mix is
traffic/<mix>.json, whose `kind` picks the driver (train_cell or serve_cell);
the limits of the comparison that decides `correct` are limits/<cell>.json;
each metric is read by metrics/<name>.py (end to end) or
layer_metrics/<name>.py (per layer), or by the file of its family before the
first dot where no file bears its whole name.  Adding a cell, a configuration, a mix
or a metric is adding files and entries; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / "cache"

__all__ = [
    "BENCH",
    "Cell",
    "RunRecord",
    "arch_config",
    "find_cell",
    "health_problems",
    "load_reader",
    "prepare_caches",
    "read_metrics",
    "require_chips",
    "result_line",
]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, Dict[str, float]]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _reports(metric: Dict, cell: str, reported: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def find_cell(name: str, spec: Optional[Dict] = None, bench: Path = BENCH) -> Cell:
    """The cell `name` of BENCHMARK.json, with its files read."""
    from bench.traffic import load_mix

    root = bench.parent
    if spec is None:
        spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    limits_path = bench / "limits" / f"{name}.json"
    config = json.loads((root / conf["file"]).read_text())
    # Where the program departs from the published model, the file states
    # the published values and `as_run` the program's; the reference and
    # the counts follow what runs.
    config.update(config.get("as_run", {}))
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mix=load_mix(w["traffic"], bench / "traffic"),
        limits=json.loads(limits_path.read_text()),
        end_to_end=e2e,
        per_layer=layer,
    )


def prepare_caches(cache: Path = CACHE) -> None:
    """JAX's compile cache, the autotune cache and the cost-model cache, all
    at fixed paths inside the checkout: after a cell's first run, set-up
    reads them and compiles nothing."""
    import jax

    cache.mkdir(parents=True, exist_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(cache / "autotune.json")
    os.environ["REPRO_COSTMODEL_CACHE"] = str(cache / "costmodel.json")
    jax.config.update("jax_compilation_cache_dir", str(cache / "jax"))
    # Cache every program, not only those that take a second to compile:
    # a small program compiled inside the window would be a stall there.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int) -> List[Any]:
    """The devices of a TPU with at least `chips` chips, or SystemExit."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no accelerator: {e}") from None
    if devices[0].platform != "tpu":
        raise SystemExit(f"the benchmark runs on TPU only; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def arch_config(cfg: Dict[str, Any]):
    """The program's configuration for a configuration file: the registry
    entry with every size the file states put in."""
    import dataclasses as dc

    from repro.configs import get_config

    base = get_config(cfg["registry_id"])
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return dc.replace(
        base,
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=hd,
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(cfg.get("attention_bias", False)),
        param_dtype=cfg["torch_dtype"],
        activation_dtype=cfg["torch_dtype"],
        use_mesh_kernel=cfg["gemm_backend"] == "pallas_mesh",
        scramble_privacy=bool(cfg.get("scramble_privacy", False)),
    )


def health_problems(cfg: Dict[str, Any], paged_impl: Optional[str] = None) -> List[str]:
    """What would hide a failure: degradation events in the resilience
    ledger, a GEMM plan on another backend than the configuration states or
    in interpret mode, a paged attention other than the stated one.  Shed,
    timed-out and preempted requests are counted in `failed`, not here."""
    import jax

    from repro.kernels import api
    from repro.resilience import ledger

    # Pallas runs compiled on the TPU and interpreted elsewhere (CPU tests).
    interpret = jax.devices()[0].platform != "tpu"
    problems = []
    counted = ("serve.shed", "serve.timeout", "serve.preempt")
    events = [e for e in ledger.events() if e.site not in counted]
    if events:
        problems.append(f"{len(events)} degradation events, first {events[0]}")
    want = cfg["gemm_backend"]
    for p in api.plan_cache_info()["plans"]:
        if p["health"].get("guard_nonfinite"):
            continue  # the server's warm-up canary, not a model GEMM
        active = p["health"]["active_backend"]
        if active != want or p["interpret"] != interpret:
            problems.append(
                f"GEMM {p['mkn']} on {active} (interpret={p['interpret']}), configuration states {want}"
            )
    if paged_impl is not None and paged_impl != cfg["paged_impl"]:
        problems.append(f"paged attention on {paged_impl}, configuration states {cfg['paged_impl']}")
    return problems


@dataclasses.dataclass
class RunRecord:
    """What a run saw, for the metric readers: the cell, the chip's peaks,
    the window on the host clock, the reduced trace (traced runs), the
    program's spans, and the driver's own counts in `data`."""

    cell: Cell
    peaks: Any
    window_s: float
    setup_s: float
    trace: Any = None
    trace_window: Any = None  # (start_ns, end_ns) on the profiler clock
    spans: List[Any] = dataclasses.field(default_factory=list)
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def devices(self) -> List[int]:
        return sorted(self.trace.device_ops)[: self.cell.chips]


def load_reader(kind: str, name: str, bench: Path = BENCH) -> Callable[[RunRecord], Any]:
    """metrics/<name>.py or layer_metrics/<name>.py, its `read` function.
    A metric split by cell kind (`idle_share.chat`) whose reader is the same
    for every kind reads the family's file (`idle_share.py`)."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        path = bench / kind / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: List[Dict], kind: str, run: RunRecord, bench: Path = BENCH) -> Dict:
    """Each entry's reader applied to the run; a reader that finds nothing
    to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = load_reader(kind, m["name"], bench)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check(readings: Dict[str, float], limits: Dict[str, Dict[str, float]]) -> Dict:
    """Each number compared, beside its limit; a number with no limit, or a
    limit with no number, fails."""
    out = {}
    for name in sorted(set(readings) | set(limits)):
        v = readings.get(name)
        lim = limits.get(name, {}).get("limit")
        ok = v is not None and lim is not None and v <= lim
        out[name] = {"value": v, "limit": lim, "ok": ok}
    return out


def result_line(
    *,
    correct: bool,
    attempted: int,
    failed: int,
    metrics: Dict,
    devices: List[Any],
    memory_peak_bytes: int,
    compared: Dict,
    problems: List[str],
    trace_device: Optional[Dict] = None,
    breakdown: Optional[Dict] = None,
) -> Dict:
    d0 = devices[0]
    device = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory_peak_bytes),
    }
    if trace_device:
        device.update(trace_device)
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {
        **{k: [v["value"], v["limit"]] for k, v in compared.items()},
        **({"health": problems} if problems else {}),
    }
    return line


def memory_peak(devices: List[Any]) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def print_compared(compared: Dict, problems: List[str], stream=sys.stderr) -> None:
    """The numbers compared and their limits, as the last lines of stderr."""
    for p in problems:
        print(f"health: {p}", file=stream)
    for name, v in compared.items():
        state = "ok" if v["ok"] else "FAIL"
        print(f"compared {name} = {v['value']} limit {v['limit']} {state}", file=stream)
    stream.flush()
