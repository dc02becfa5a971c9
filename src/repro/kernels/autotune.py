"""Block-shape autotuner for the mesh-matmul dispatch path (DESIGN.md §3).

The Pallas kernel's performance is set almost entirely by its block triple
(block_m, block_n, block_k): it fixes the VMEM working set per grid cell, the
MXU arithmetic intensity, and the HBM padding waste.  This module owns the
choice so `ops.matmul` callers never hard-code 128³ again:

  candidate_blocks   MXU-aligned triples pruned by a VMEM-budget model of the
                     per-cell working set (A-tile + B-tile + f32 accumulator
                     + optional epilogue tiles)
  autotune           cache lookup -> (timed | model-scored) search over the
                     candidates, warm-started from the nearest cached shape
  AutotuneCache      versioned persistent JSON keyed by
                     (M, K, N, dtype, backend, symmetry, platform) —
                     formalizes the legacy flat-dict `.autotune_cache.json`
                     (migrated transparently on load)
  resolve_blocks     process-memoized entry point used by `ops.matmul`
                     whenever block sizes aren't explicitly passed

Search modes: "time" runs the real kernel per candidate (TPU; interpret mode
on CPU is not a measurement), "model" ranks by the analytic score
intensity x padding-utilization, "auto" picks "time" on TPU and "model"
elsewhere.  A cache hit never searches.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import tempfile
import time
import warnings
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as _metrics
from repro.obs import trace as _obs
from repro.resilience import faults as _faults
from repro.resilience import ledger as _rledger
from repro.resilience.policy import retry_call as _retry_call

__all__ = [
    "CACHE_VERSION",
    "AutotuneCache",
    "autotune",
    "cache_key",
    "candidate_blocks",
    "default_cache",
    "measure_best_ms",
    "model_score",
    "outside_trace",
    "resolve_blocks",
    "vmem_bytes",
]

CACHE_VERSION = 2
DEFAULT_CACHE_FILENAME = ".autotune_cache.json"
_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"

_LANE = 128  # MXU tile edge — every candidate dimension is a multiple
# A kernel's default scoped VMEM on v5e is 16 MiB; `vmem_bytes` already
# counts the pipeline's double buffers, so the margin left here is for
# Mosaic's own internal scratch.
DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024

Blocks = Tuple[int, int, int]


def cache_key(
    m: int,
    k: int,
    n: int,
    dtype,
    backend: str,
    *,
    symmetry: int = 0,
    platform: Optional[str] = None,
) -> str:
    """`"MxKxN|dtype|backend|symS|platform"` — the legacy key format, kept."""
    platform = platform or jax.default_backend()
    return f"{m}x{k}x{n}|{jnp.dtype(dtype).name}|{backend}|sym{symmetry}|{platform}"


def vmem_bytes(
    bm: int,
    bn: int,
    bk: int,
    dtype,
    *,
    has_bias: bool = False,
    has_residual: bool = False,
) -> int:
    """Per-grid-cell VMEM working set of the mesh kernel.

    Pallas double-buffers every blocked operand and the output (the next
    block's DMA overlaps this block's compute), so the A, B and output
    tiles — and the bias row / residual tile when fused — count twice; the
    f32 accumulator is one scratch buffer."""
    ds = jnp.dtype(dtype).itemsize
    tiles = (bm * bk + bk * bn + bm * bn) * ds
    if has_bias:
        tiles += 8 * bn * 4  # a (1, bn) f32 row pads to 8 sublanes
    if has_residual:
        tiles += bm * bn * ds
    return 2 * tiles + bm * bn * 4


def _dim_candidates(dim: int, aligns: Tuple[int, ...]) -> List[int]:
    """Aligned block sizes that don't exceed the dim padded up to alignment."""
    ceil_dim = max(dim, aligns[0])
    out = [a for a in aligns if a <= ((ceil_dim + aligns[0] - 1) // aligns[0]) * aligns[0]]
    return out or [aligns[0]]


def candidate_blocks(
    m: int,
    k: int,
    n: int,
    dtype,
    *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    aligns: Tuple[int, ...] = (_LANE, 2 * _LANE, 4 * _LANE),
    has_bias: bool = False,
    has_residual: bool = False,
) -> List[Blocks]:
    """MXU-aligned (bm, bn, bk) triples whose working set fits the budget."""
    cands = [
        (bm, bn, bk)
        for bm in _dim_candidates(m, aligns)
        for bn in _dim_candidates(n, aligns)
        for bk in _dim_candidates(k, aligns)
        if vmem_bytes(bm, bn, bk, dtype, has_bias=has_bias, has_residual=has_residual)
        <= vmem_budget
    ]
    if not cands:  # budget smaller than the minimal tile: fall back anyway
        cands = [(aligns[0], aligns[0], aligns[0])]
    return cands


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def model_score(m: int, k: int, n: int, blocks: Blocks, dtype) -> float:
    """Analytic desirability: MXU intensity x padding utilization.

    intensity   = FLOPs per HBM byte streamed for one (bm, bn, bk) phase —
                  rewards large blocks (the roofline x-axis).
    utilization = useful fraction of the padded iteration space — penalizes
                  blocks that overhang M/N/K (wasted MXU issue slots).
    """
    bm, bn, bk = blocks
    ds = jnp.dtype(dtype).itemsize
    intensity = (2 * bm * bn * bk) / ((bm * bk + bk * bn) * ds)
    padded = (
        _ceil_div(m, bm) * bm * _ceil_div(n, bn) * bn * _ceil_div(k, bk) * bk
    )
    utilization = (m * n * k) / padded
    return intensity * utilization


class AutotuneCache:
    """Versioned persistent JSON cache of chosen block triples.

    On-disk format (v2):
        {"version": 2, "entries": {key: {"blocks": [bm, bn, bk],
                                         "source": "timed|model|seed",
                                         "ms": float|null}}}
    A legacy v1 file (flat {key: [bm, bn, bk]} — the orphaned
    `.autotune_cache.json` this formalizes) is migrated in memory on load and
    rewritten as v2 on the next save.  Any other/unknown version is discarded
    rather than trusted.

    Resilience (DESIGN.md §11): an unreadable/corrupt cache file is
    QUARANTINED — warned about once (with the path), moved aside to
    `<path>.corrupt`, and recorded in the resilience ledger — never crashed
    on and never silently retuned-forever.  Individual entries are validated
    against the VMEM model on load: an entry whose working set cannot fit the
    budget (a corrupt or hand-edited cache) is dropped with a ledger record,
    and the next `autotune` miss rebuilds it.
    """

    def __init__(
        self,
        path: Optional[os.PathLike] = None,
        *,
        vmem_budget: int = DEFAULT_VMEM_BUDGET,
    ):
        self.path = Path(
            path or os.environ.get(_ENV_CACHE, DEFAULT_CACHE_FILENAME)
        )
        self.vmem_budget = vmem_budget
        self._entries: Optional[Dict[str, dict]] = None

    # -- persistence ---------------------------------------------------------

    def _entry_fits_vmem(self, key: str, blocks) -> bool:
        """VMEM-model validation: the (worst-case epilogue) working set of a
        cached triple must fit the budget candidates were pruned by.  Keys
        whose dtype field doesn't parse are conservatively kept."""
        try:
            dtype = jnp.dtype(key.split("|")[1])
        except (IndexError, TypeError):
            return True
        bm, bn, bk = (int(x) for x in blocks)
        return (
            vmem_bytes(bm, bn, bk, dtype, has_bias=True, has_residual=True)
            <= self.vmem_budget
        )

    def _quarantine_file(self, err: BaseException) -> None:
        """Move the unreadable cache aside as `<path>.corrupt` so the bad
        file is diagnosable (and never re-read), then record + warn once."""
        corrupt = Path(str(self.path) + ".corrupt")
        moved = False
        try:
            os.replace(self.path, corrupt)
            moved = True
        except OSError:
            pass
        _warn_once(
            f"autotune cache {self.path} is unreadable"
            f" ({type(err).__name__}: {err});"
            + (f" moved aside to {corrupt};" if moved else "")
            + " retuning from scratch"
        )
        _rledger.record(
            "autotune.cache_load",
            cause=f"{type(err).__name__}: {err}",
            fallback="quarantine",
            path=str(self.path),
            moved_to=str(corrupt) if moved else None,
        )

    def _load(self) -> Dict[str, dict]:
        if self._entries is not None:
            return self._entries
        self._entries = {}
        try:
            _faults.check("autotune.cache_load", path=str(self.path))
            raw = json.loads(self.path.read_text())
        except FileNotFoundError:
            return self._entries  # first run: nothing to load, nothing to warn
        except (OSError, json.JSONDecodeError, _faults.FaultError) as e:
            self._quarantine_file(e)
            return self._entries
        dropped = []
        if isinstance(raw, dict) and "version" not in raw:
            # v1 legacy: flat {key: [bm, bn, bk]}
            for key, blocks in raw.items():
                if _valid_blocks(blocks) and self._entry_fits_vmem(key, blocks):
                    self._entries[key] = {
                        "blocks": [int(x) for x in blocks],
                        "source": "seed",
                        "ms": None,
                    }
                else:
                    dropped.append(key)
        elif isinstance(raw, dict) and raw.get("version") == CACHE_VERSION:
            for key, ent in raw.get("entries", {}).items():
                if (
                    isinstance(ent, dict)
                    and _valid_blocks(ent.get("blocks"))
                    and self._entry_fits_vmem(key, ent["blocks"])
                ):
                    self._entries[key] = ent
                else:
                    dropped.append(key)
        # unknown version: start clean (stale caches must not steer the search)
        if dropped:
            _warn_once(
                f"autotune cache {self.path}: quarantined {len(dropped)}"
                f" invalid entr{'y' if len(dropped) == 1 else 'ies'}"
                f" (failed block/VMEM-model validation); they will be retuned"
            )
            _rledger.record(
                "autotune.cache_load",
                cause=f"{len(dropped)} entries failed validation",
                fallback="retune",
                path=str(self.path),
                keys=dropped[:8],
            )
        return self._entries

    def save(self) -> None:
        """Best-effort persistence with bounded retry: an unwritable
        filesystem must never turn into a matmul-time crash, so after the
        retries the final OSError is still swallowed (each retry is a ledger
        event, so persistent write failure stays visible)."""
        entries = self._load()
        payload = {"version": CACHE_VERSION, "entries": entries}

        def _write_once() -> None:
            tmp = None
            try:
                self.path.parent.mkdir(parents=True, exist_ok=True)
                fd, tmp = tempfile.mkstemp(
                    dir=str(self.path.parent), prefix=self.path.name, suffix=".tmp"
                )
                with os.fdopen(fd, "w") as f:
                    json.dump(payload, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            except OSError:
                if tmp is not None:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                raise

        try:
            _retry_call(
                _write_once,
                retries=2,
                base_delay=0.01,
                retry_on=(OSError,),
                site="autotune.cache_save",
            )
        except OSError:
            pass

    # -- access --------------------------------------------------------------

    def get(self, key: str) -> Optional[Blocks]:
        ent = self._load().get(key)
        return tuple(ent["blocks"]) if ent else None

    def put(
        self, key: str, blocks: Blocks, *, source: str, ms: Optional[float] = None
    ) -> None:
        self._load()[key] = {
            "blocks": [int(x) for x in blocks],
            "source": source,
            "ms": ms,
        }

    def keys(self) -> List[str]:
        return list(self._load())


_WARNED: set = set()


def _warn_once(msg: str) -> None:
    """One warning per distinct message per process — a corrupt cache is
    diagnosable without flooding every subsequent load."""
    if msg not in _WARNED:
        _WARNED.add(msg)
        warnings.warn(msg, stacklevel=3)


def _valid_blocks(blocks) -> bool:
    return (
        isinstance(blocks, (list, tuple))
        and len(blocks) == 3
        and all(isinstance(x, int) and x > 0 for x in blocks)
    )


_DEFAULT_CACHE: Optional[AutotuneCache] = None


def default_cache() -> AutotuneCache:
    """Process-wide cache instance (respects $REPRO_AUTOTUNE_CACHE)."""
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None or _DEFAULT_CACHE.path != Path(
        os.environ.get(_ENV_CACHE, DEFAULT_CACHE_FILENAME)
    ):
        _DEFAULT_CACHE = AutotuneCache()
    return _DEFAULT_CACHE


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------


def _warm_start(
    cache: AutotuneCache, m: int, k: int, n: int, dtype, backend: str, platform: str
) -> Optional[Blocks]:
    """Blocks of the nearest cached shape with the same dtype/backend/platform.

    Distance is L1 in log2 space over (M, K, N) — a 2048³ entry warm-starts a
    4096³ search better than a 512x512x128 one.
    """
    suffix = f"|{jnp.dtype(dtype).name}|{backend}|"
    best, best_d = None, float("inf")
    for key in cache.keys():
        if suffix not in key or not key.endswith(f"|{platform}"):
            continue
        try:
            mm, kk, nn = (int(x) for x in key.split("|", 1)[0].split("x"))
        except ValueError:
            continue
        d = sum(
            abs(np.log2(a) - np.log2(b))
            for a, b in zip((m, k, n), (mm, kk, nn))
        )
        if d < best_d:
            best, best_d = cache.get(key), d
    return best


def outside_trace(fn: Callable, *args):
    """`fn(*args)` on a helper thread.  Plans are built while a model's step
    is being traced, and JAX's trace state belongs to the thread: there the
    call runs eagerly on concrete arrays, where inside the trace it would
    stage tracers (or, under `ensure_compile_time_eval`, fail to evaluate
    an interpret-mode kernel)."""
    with concurrent.futures.ThreadPoolExecutor(1) as ex:
        return ex.submit(fn, *args).result()


def measure_best_ms(fn: Callable, *args, warmup: int = 1, reps: int = 3) -> float:
    """Best-of-`reps` wall time of `fn(*args)` in milliseconds, compile
    excluded (`warmup` untimed calls first).  Results are blocked on when
    they expose `block_until_ready` — the shared timing utility behind the
    autotuner's candidate search and `costmodel/calibrate.py`'s probes."""

    def _run():
        out = fn(*args)
        if hasattr(out, "block_until_ready"):
            out.block_until_ready()
        return out

    for _ in range(warmup):
        _run()
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        _run()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _default_measure(
    m: int, k: int, n: int, dtype, backend: str, blocks: Blocks
) -> float:
    """Wall-time one real kernel launch (compile excluded), in milliseconds."""
    from repro.kernels.mesh_matmul import mesh_matmul_pallas

    bm, bn, bk = blocks
    pad = lambda d, b: _ceil_div(d, b) * b
    a = jnp.zeros((pad(m, bm), pad(k, bk)), dtype)
    b = jnp.zeros((pad(k, bk), pad(n, bn)), dtype)
    kw = dict(
        block_m=bm,
        block_n=bn,
        block_k=bk,
        scramble_out=backend == "pallas_mesh_scrambled",
        interpret=jax.default_backend() != "tpu",
    )
    return measure_best_ms(lambda: mesh_matmul_pallas(a, b, **kw))


def _scramble_compatible(m: int, n: int, blocks: Blocks) -> bool:
    """The scrambled backend needs block-aligned M/N and a square block grid
    (the σ table is defined on g x g cells) — padding is rejected at dispatch,
    so the search must never propose blocks that violate either."""
    bm, bn, _ = blocks
    return m % bm == 0 and n % bn == 0 and m // bm == n // bn


def autotune(
    m: int,
    k: int,
    n: int,
    dtype,
    backend: str = "pallas_mesh",
    *,
    symmetry: int = 0,
    platform: Optional[str] = None,
    cache: Optional[AutotuneCache] = None,
    mode: str = "auto",
    measure: Optional[Callable[..., float]] = None,
    max_timed: int = 8,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    scorer: Optional[Callable[[Blocks], float]] = None,
) -> Blocks:
    """Resolve the block triple for an (M, K, N) GEMM.  Cache hit => no search.

    mode="time": measure `max_timed` candidates (warm-start candidate first,
    then by descending model score) and keep the fastest.  mode="model": pick
    the analytic argmax without running anything.  mode="auto": "time" on TPU,
    "model" elsewhere (CPU interpret timing measures Python, not the kernel).

    The cache key is shape-level only, so candidate pruning budgets for the
    worst-case epilogue working set (bias + residual tiles) — a cached entry
    is valid for every epilogue configuration of that shape.

    `scorer` (optional) replaces the analytic `model_score` ranking with an
    external cost in milliseconds (LOWER is better) — the hook
    `costmodel/choose.py` uses to rank candidates by calibrated-coefficient
    predictions while the timed search stays the tie-breaker on TPU.
    """
    platform = platform or jax.default_backend()
    cache = cache or default_cache()
    key = cache_key(m, k, n, dtype, backend, symmetry=symmetry, platform=platform)
    hit = cache.get(key)
    if hit is not None:
        return hit

    if mode == "auto":
        mode = "time" if platform == "tpu" else "model"
    if mode not in ("time", "model"):
        raise ValueError(f"mode must be auto|time|model, got {mode!r}")

    cands = candidate_blocks(
        m,
        k,
        n,
        dtype,
        vmem_budget=vmem_budget,
        has_bias=True,
        has_residual=True,
    )
    if backend == "pallas_mesh_scrambled":
        cands = [c for c in cands if _scramble_compatible(m, n, c)] or [
            (_LANE, _LANE, _LANE)  # dispatch raises its own clear error if
        ]  # even the default can't tile M/N squarely
    if scorer is not None:
        cands.sort(key=scorer)
    else:
        cands.sort(key=lambda blk: model_score(m, k, n, blk, dtype), reverse=True)

    if mode == "model":
        best, ms, source = cands[0], None, "model"
    else:
        # Warm start: measure the nearest cached shape's blocks first, then
        # the analytically best remainder — the budget (max_timed) goes to
        # the most promising region of the space.
        warm = _warm_start(cache, m, k, n, dtype, backend, platform)
        if warm in cands:
            cands.remove(warm)
            cands.insert(0, warm)
        measure = measure or _default_measure
        timed: List[Tuple[float, Blocks]] = []
        failed = 0
        t_search = time.perf_counter()
        for blk in cands[:max_timed]:
            # A candidate that fails to compile/run is skipped, not fatal —
            # the search degrades toward the analytic model instead of
            # crashing plan construction.
            try:
                with _obs.span("autotune.measure", key=key, blocks=list(blk)):
                    cand_ms = outside_trace(measure, m, k, n, dtype, backend, blk)
                timed.append((cand_ms, blk))
            except Exception as e:
                failed += 1
                _rledger.record(
                    "autotune.measure",
                    cause=f"{type(e).__name__}: {e}",
                    fallback="skip-candidate",
                    blocks=blk,
                )
        # A counter, not the spans above: searches run while a step is being
        # traced, where spans are suppressed.
        _metrics.counter(
            "autotune_timed_seconds_total",
            "wall time of timed block searches, candidate compiles included",
        ).inc(time.perf_counter() - t_search)
        if timed:
            ms, best = min(timed, key=lambda t: t[0])
            source = "timed"
        else:
            # every timed candidate failed: fall back to the model argmax
            best, ms, source = cands[0], None, "model"
            _rledger.record(
                "autotune.measure",
                cause=f"all {failed} timed candidates failed",
                fallback="model",
                key=key,
            )

    cache.put(key, best, source=source, ms=ms)
    cache.save()
    return best


_RESOLVE_MEMO: Dict[tuple, Blocks] = {}


def resolve_blocks(
    m: int, k: int, n: int, dtype, backend: str, *, symmetry: int = 0
) -> Blocks:
    """The dispatch layer's entry point (`kernels/api.plan`): memoized
    per-process, cache-backed, never times on non-TPU hosts (mode="auto").
    `symmetry=1` keys the symmetric-readout regime's own cache partition."""
    memo_key = (
        m, k, n, jnp.dtype(dtype).name, backend, symmetry, jax.default_backend()
    )
    got = _RESOLVE_MEMO.get(memo_key)
    if got is None:
        got = autotune(m, k, n, dtype, backend, symmetry=symmetry)
        _RESOLVE_MEMO[memo_key] = got
    return got


def clear_resolve_memo() -> None:
    """Test hook: drop the per-process memo (not the persistent cache)."""
    _RESOLVE_MEMO.clear()
