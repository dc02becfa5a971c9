"""Autotuner subsystem: candidate pruning, cache round-trip/versioning/legacy
migration, warm start, search modes, and the ops.matmul integration."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (
    CACHE_VERSION,
    AutotuneCache,
    cache_key,
    candidate_blocks,
    model_score,
    vmem_bytes,
)


@pytest.fixture()
def cache(tmp_path):
    return AutotuneCache(tmp_path / "cache.json")


# --- key format / vmem model --------------------------------------------------


def test_cache_key_formalizes_legacy_format():
    key = cache_key(4096, 4096, 4096, jnp.bfloat16, "pallas_mesh", platform="cpu")
    assert key == "4096x4096x4096|bfloat16|pallas_mesh|sym0|cpu"
    key = cache_key(2048, 16384, 2048, "bfloat16", "pallas_mesh", symmetry=1, platform="tpu")
    assert key == "2048x16384x2048|bfloat16|pallas_mesh|sym1|tpu"


def test_vmem_model_counts_tiles_and_acc():
    # double-buffered A, B and output tiles in dtype + one f32 accumulator
    assert vmem_bytes(128, 128, 128, jnp.bfloat16) == 2 * 3 * (128 * 128 * 2) + 128 * 128 * 4
    assert vmem_bytes(128, 128, 128, jnp.float32) == 2 * 3 * (128 * 128 * 4) + 128 * 128 * 4
    plain = vmem_bytes(128, 128, 128, jnp.bfloat16)
    assert vmem_bytes(128, 128, 128, jnp.bfloat16, has_residual=True) > plain
    assert vmem_bytes(128, 128, 128, jnp.bfloat16, has_bias=True) > plain


def test_candidates_are_aligned_and_within_budget():
    cands = candidate_blocks(4096, 4096, 4096, jnp.bfloat16)
    assert cands, "no candidates survived"
    for bm, bn, bk in cands:
        assert bm % 128 == 0 and bn % 128 == 0 and bk % 128 == 0
        assert vmem_bytes(bm, bn, bk, jnp.bfloat16) <= autotune.DEFAULT_VMEM_BUDGET
    # a tight budget prunes the large blocks
    tight = candidate_blocks(4096, 4096, 4096, jnp.bfloat16, vmem_budget=300 * 1024)
    assert max(max(c) for c in tight) <= 256
    assert len(tight) < len(cands)


def test_candidates_never_overhang_small_dims():
    cands = candidate_blocks(100, 4096, 100, jnp.bfloat16)
    for bm, bn, bk in cands:
        assert bm == 128 and bn == 128  # 100 pads to one 128 block at most


def test_model_score_prefers_utilization():
    # A block that exactly tiles the shape beats one that pads 4096 -> 5120.
    fits = model_score(4096, 4096, 4096, (512, 512, 128), jnp.bfloat16)
    pads = model_score(4096 + 128, 4096, 4096, (512, 512, 128), jnp.bfloat16)
    assert fits > pads


# --- cache persistence --------------------------------------------------------


def test_cache_round_trip(cache):
    key = cache_key(512, 512, 512, jnp.bfloat16, "pallas_mesh", platform="cpu")
    assert cache.get(key) is None
    cache.put(key, (256, 256, 128), source="timed", ms=1.25)
    cache.save()
    reloaded = AutotuneCache(cache.path)
    assert reloaded.get(key) == (256, 256, 128)
    raw = json.loads(cache.path.read_text())
    assert raw["version"] == CACHE_VERSION
    assert raw["entries"][key]["source"] == "timed"


def test_cache_migrates_legacy_v1_flat_dict(tmp_path):
    path = tmp_path / "legacy.json"
    legacy_key = "4096x4096x4096|bfloat16|pallas_mesh|sym0|cpu"
    path.write_text(json.dumps({legacy_key: [512, 512, 128]}))
    cache = AutotuneCache(path)
    assert cache.get(legacy_key) == (512, 512, 128)
    cache.save()  # rewritten as v2
    raw = json.loads(path.read_text())
    assert raw["version"] == CACHE_VERSION
    assert raw["entries"][legacy_key]["blocks"] == [512, 512, 128]
    assert raw["entries"][legacy_key]["source"] == "seed"


def test_cache_discards_unknown_version_and_corrupt_files(tmp_path):
    key = "512x512x512|bfloat16|pallas_mesh|sym0|cpu"
    future = tmp_path / "future.json"
    future.write_text(json.dumps({"version": 99, "entries": {key: {"blocks": [64, 64, 64]}}}))
    assert AutotuneCache(future).get(key) is None
    corrupt = tmp_path / "corrupt.json"
    corrupt.write_text("{not json")
    assert AutotuneCache(corrupt).get(key) is None
    bad_blocks = tmp_path / "bad.json"
    bad_blocks.write_text(json.dumps({key: [512, 512]}))  # wrong arity
    assert AutotuneCache(bad_blocks).get(key) is None


# --- search -------------------------------------------------------------------


def test_cache_hit_never_searches(cache):
    key = cache_key(512, 512, 512, jnp.bfloat16, "pallas_mesh")
    cache.put(key, (256, 256, 128), source="timed")

    def explode(*a, **k):  # measure must not be called on a hit
        raise AssertionError("searched despite cache hit")

    got = autotune.autotune(
        512, 512, 512, jnp.bfloat16, "pallas_mesh", cache=cache, mode="time", measure=explode
    )
    assert got == (256, 256, 128)


def test_timed_search_picks_fastest_and_persists(cache):
    fake_ms = {(128, 128, 128): 3.0, (256, 256, 128): 1.0}

    def measure(m, k, n, dtype, backend, blocks):
        return fake_ms.get(blocks, 10.0)

    got = autotune.autotune(
        512,
        512,
        512,
        jnp.bfloat16,
        "pallas_mesh",
        cache=cache,
        mode="time",
        measure=measure,
        max_timed=64,  # cover the full candidate list so the fake times decide
    )
    assert got == (256, 256, 128)
    # persisted: a fresh instance over the same file hits without searching
    reloaded = AutotuneCache(cache.path)
    key = cache_key(512, 512, 512, jnp.bfloat16, "pallas_mesh")
    assert reloaded.get(key) == (256, 256, 128)


def test_timed_search_runs_concretely_inside_a_trace(cache):
    """Plans are built while a model step is traced; the timed search must
    still run each candidate on concrete arrays, not stage tracers."""
    import jax

    concrete = []

    def measure(m, k, n, dtype, backend, blocks):
        x = jnp.zeros((8, 128), dtype) + 1
        concrete.append(not isinstance(x, jax.core.Tracer))
        return float(blocks[0])

    def step(x):
        autotune.autotune(
            512, 512, 512, jnp.bfloat16, "pallas_mesh",
            cache=cache, mode="time", measure=measure, max_timed=3,
        )
        return x + 1

    jax.jit(step)(jnp.ones(2))
    assert concrete and all(concrete)


def test_timed_search_runs_the_kernel_inside_a_trace(cache):
    """The default measure launches the real kernel (interpret mode here)
    from inside a traced step; no candidate may fail there."""
    import jax

    from repro.resilience import ledger

    ledger.clear()

    def step(x):
        autotune.autotune(
            256, 256, 256, jnp.float32, "pallas_mesh",
            cache=cache, mode="time", max_timed=2,
        )
        return x + 1

    jax.jit(step)(jnp.ones(2))
    assert ledger.count() == 0, ledger.format_summary()
    key = cache_key(256, 256, 256, jnp.float32, "pallas_mesh")
    assert cache._load()[key]["source"] == "timed"


def test_warm_start_is_tried_first(cache):
    import jax

    platform = jax.default_backend()
    near = cache_key(1024, 1024, 1024, jnp.bfloat16, "pallas_mesh", platform=platform)
    cache.put(near, (256, 128, 128), source="timed")
    order = []

    def measure(m, k, n, dtype, backend, blocks):
        order.append(blocks)
        return 1.0

    autotune.autotune(
        2048, 2048, 2048, jnp.bfloat16, "pallas_mesh", cache=cache, mode="time", measure=measure
    )
    assert order[0] == (256, 128, 128)


def test_model_mode_runs_nothing_and_caches(cache):
    got = autotune.autotune(4096, 4096, 4096, jnp.bfloat16, "pallas_mesh", cache=cache, mode="model")
    assert all(x % 128 == 0 for x in got)
    key = cache_key(4096, 4096, 4096, jnp.bfloat16, "pallas_mesh")
    assert cache.get(key) == got


# --- ops.matmul integration ---------------------------------------------------


def test_ops_matmul_resolves_blocks_via_autotuner(tmp_path, monkeypatch):
    from repro.kernels.ops import matmul

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "cache.json"))
    autotune._DEFAULT_CACHE = None  # force re-read of the env var
    autotune.clear_resolve_memo()
    try:
        rng = np.random.default_rng(3)
        a = jnp.asarray(rng.normal(size=(48, 32)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(32, 24)).astype(np.float32))
        got = matmul(a, b, backend="pallas_mesh")  # no explicit blocks
        np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b), rtol=1e-4, atol=1e-4)
        cache = autotune.default_cache()
        key = cache_key(48, 32, 24, jnp.float32, "pallas_mesh")
        assert cache.get(key) is not None, "autotuner was not consulted"
        # second call: memo + cache hit, still correct
        got2 = matmul(a, b, backend="pallas_mesh")
        np.testing.assert_allclose(np.asarray(got2), np.asarray(got), rtol=0, atol=0)
    finally:
        autotune._DEFAULT_CACHE = None
        autotune.clear_resolve_memo()


def test_scrambled_backend_candidates_respect_square_grid(cache):
    """Scrambled dispatch rejects padding + non-square grids — the search
    must only propose compatible blocks (regression: 384x384 crashed)."""
    got = autotune.autotune(384, 384, 384, jnp.float32, "pallas_mesh_scrambled",
                            cache=cache, mode="model")
    bm, bn, _ = got
    assert 384 % bm == 0 and 384 % bn == 0 and 384 // bm == 384 // bn

    from repro.kernels.ops import matmul

    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.normal(size=(384, 384)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(384, 384)).astype(np.float32))
    out = matmul(a, b, backend="pallas_mesh_scrambled",
                 block_m=bm, block_n=bn, block_k=got[2])
    assert out.shape == (384, 384)


def test_activation_validated_on_every_backend():
    """Same ValueError for a typo'd activation on xla and pallas backends."""
    from repro.kernels.ops import matmul

    a = jnp.zeros((8, 8))
    for backend in ("xla", "pallas_mesh"):
        with pytest.raises(ValueError, match="activation must be one of"):
            matmul(a, a, backend=backend, block_m=8, block_n=8, block_k=8,
                   activation="swishh")
