"""From a JAX profiler trace to the numbers the per-layer metrics read.

`load(trace_dir)` reads the `.xplane.pb` the profiler wrote, with nothing but
`jax.profiler.ProfileData`, into a `Trace` of plain tuples:

  device_ops[d]  (op, start_ns, dur_ns) of the "XLA Ops" line of plane
                 `/device:TPU:d`; `op` is the HLO instruction's name without
                 its `%` and numeric suffix ("mesh_matmul_pallas",
                 "paged_attention_pallas", "fusion", "all-reduce", ...)
  device_modules[d]  (program, start_ns, dur_ns) of its "XLA Modules" line,
                 one event per execution of a jitted program ("jit_decode",
                 "jit_prefill_step", "jit_train_step", ...)
  host           (name, start_ns, dur_ns) of the host line that holds the
                 harness's `jax.profiler.TraceAnnotation` spans (`bench.*`):
                 those spans and JAX's own dispatch events on that thread,
                 on the same clock

Ops nest: a `while` op holds the ops of its loop body.  An op that holds
another op of its line is a container; device time by op counts leaf ops
only, so nothing is counted twice, while the device's busy time is the union
of every op's interval.  The reductions below are plain interval arithmetic
and are checked against a small trace recorded on a v5e
(tests/data/v5e_train_step.json.gz).
"""

from __future__ import annotations

import collections
import dataclasses
import gzip
import json
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Trace",
    "load",
    "from_json",
    "to_json",
    "op_family",
    "module_name",
    "module_time_ns",
    "union_ns",
    "leaf_ops",
    "busy_ns",
    "op_time_ns",
    "exposed_ns",
    "top_ops",
    "idle_gaps",
]

Event = Tuple[str, int, int]  # (name, start_ns, dur_ns)

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "collective-permute", "all-to-all")
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Trace:
    device_ops: Dict[int, List[Event]]
    host: List[Event]
    # (jitted program, start_ns, dur_ns) of each device's "XLA Modules" line:
    # one event per program execution, named "jit_<function>(<hash>)".
    device_modules: Dict[int, List[Event]] = dataclasses.field(default_factory=dict)

    def window(self, name: str = "bench.window") -> Tuple[int, int]:
        """(start, end) of the harness's window annotation on the profiler
        clock."""
        for n, s, d in self.host:
            if n == name:
                return s, s + d
        raise KeyError(f"no host span {name!r} in the trace")


def op_family(event_name: str) -> str:
    """'%mesh_matmul_pallas.213 = f32[...] custom-call(...)' -> 'mesh_matmul_pallas'."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def module_name(event_name: str) -> str:
    """'jit_prefill_step(604145106575633064)' -> 'jit_prefill_step'."""
    return event_name.split("(", 1)[0]


def module_time_ns(modules: Sequence[Event], lo: int, hi: int) -> Dict[str, int]:
    """Device nanoseconds per jitted program inside [lo, hi)."""
    acc: Dict[str, int] = collections.Counter()
    for a, b, name in _clip(modules, lo, hi):
        acc[name] += b - a
    return dict(acc)


def load(trace_dir: Path) -> Trace:
    """Every `.xplane.pb` under `trace_dir` (the profiler may write the
    device's planes and the host's in files of their own)."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    device_ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host = set()
    for f in files:
        pd = ProfileData.from_file(str(f))
        for plane in pd.planes:
            m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            if m:
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops = [(op_family(e.name), int(e.start_ns), int(e.duration_ns))
                               for e in line.events]
                        if len(ops) > len(device_ops.get(int(m.group(1)), ())):
                            device_ops[int(m.group(1))] = ops
                    elif line.name == "XLA Modules":
                        mods = [(module_name(e.name), int(e.start_ns), int(e.duration_ns))
                                for e in line.events]
                        if len(mods) > len(modules.get(int(m.group(1)), ())):
                            modules[int(m.group(1))] = mods
            elif plane.name == "/host:CPU":
                # The harness's thread: the line that holds its `bench.*`
                # annotations ("python3" on a v5e host, named after the
                # process), whatever the runtime names it.
                for line in plane.lines:
                    events = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
                    if any(n.startswith("bench.") for n, _, _ in events):
                        host.update(events)
    return Trace(device_ops, sorted(host, key=lambda e: e[1]), modules)


def to_json(trace: Trace, path: Path) -> None:
    data = {
        "device_ops": {str(k): v for k, v in trace.device_ops.items()},
        "host": trace.host,
        "device_modules": {str(k): v for k, v in trace.device_modules.items()},
    }
    with gzip.open(path, "wt") as f:
        json.dump(data, f)


def from_json(path: Path) -> Trace:
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return Trace(
        {int(k): [tuple(e) for e in v] for k, v in data["device_ops"].items()},
        [tuple(e) for e in data["host"]],
        {int(k): [tuple(e) for e in v] for k, v in data.get("device_modules", {}).items()},
    )


def _clip(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int, str]]:
    out = []
    for n, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b, n))
    return out


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _merged(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def leaf_ops(events: Sequence[Event]) -> List[Event]:
    """Ops that hold no other op of the line (events sorted by start; a
    container starts no later and ends no earlier than what it holds)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    leaf = [True] * len(evs)
    stack: List[int] = []  # indices of open containers
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and s + d <= evs[stack[-1]][1] + evs[stack[-1]][2]:
            leaf[stack[-1]] = False
        stack.append(i)
    return [e for e, keep in zip(evs, leaf) if keep]


def busy_ns(events: Sequence[Event], lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) in which some op ran."""
    return union_ns((a, b) for a, b, _ in _clip(events, lo, hi))


def op_time_ns(events: Sequence[Event], family: str, lo: int, hi: int) -> int:
    """Device nanoseconds of leaf ops of one family inside [lo, hi)."""
    return sum(b - a for a, b, n in _clip(leaf_ops(events), lo, hi) if n == family)


def _is_collective(op: str) -> bool:
    return any(c in op for c in COLLECTIVES)


def exposed_ns(events: Sequence[Event], lo: int, hi: int) -> Tuple[int, int]:
    """(collective ns, exposed ns) in [lo, hi): the union of collective leaf
    ops, and the part of it during which no other leaf op runs.  An async
    collective's transfer overlaps the ops between its start and its done;
    the start and done ops themselves, and a synchronous collective, are
    the exposed part."""
    leaves = _clip(leaf_ops(events), lo, hi)
    coll = _merged((a, b) for a, b, n in leaves if _is_collective(n))
    compute = _merged((a, b) for a, b, n in leaves if not _is_collective(n))
    total = sum(b - a for a, b in coll)
    hidden, j = 0, 0
    for a, b in coll:
        while j < len(compute) and compute[j][1] <= a:
            j += 1
        k = j
        while k < len(compute) and compute[k][0] < b:
            hidden += min(b, compute[k][1]) - max(a, compute[k][0])
            k += 1
    return total, total - hidden


def top_ops(events: Sequence[Event], lo: int, hi: int, n: int = 10) -> List[Tuple[str, float]]:
    """The n op families with the most leaf device time in [lo, hi), seconds."""
    acc: Dict[str, int] = collections.Counter()
    for a, b, name in _clip(leaf_ops(events), lo, hi):
        acc[name] += b - a
    return [(k, v / 1e9) for k, v in acc.most_common(n)]


def _label(host: Sequence[Event], a: int, b: int) -> str:
    """What the host was doing in [a, b): the outermost harness annotation
    covering the gap's midpoint, and the innermost host event there."""
    mid = (a + b) // 2
    outer: Optional[str] = None
    inner: Optional[Tuple[int, str]] = None
    for name, s, d in host:
        if s > mid:
            break
        if s + d <= mid:
            continue
        if name == "bench.window":
            continue
        if name.startswith("bench.") and outer is None:
            outer = name
        if inner is None or d <= inner[0]:
            inner = (d, name)
    parts = [outer or "outside the harness"]
    if inner is not None and inner[1] != outer:
        parts.append(inner[1])
    return " > ".join(parts)


def idle_gaps(
    events: Sequence[Event], host: Sequence[Event], lo: int, hi: int, n: int = 10
) -> List[Tuple[str, float]]:
    """The n longest stretches of [lo, hi) with no op on the device, in
    seconds, each labelled by what the host was doing then."""
    busy = _merged((a, b) for a, b, _ in _clip(events, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [(_label(host, a, b), (b - a) / 1e9) for a, b in gaps[:n]]
