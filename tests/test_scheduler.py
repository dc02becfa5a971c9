"""Continuous-batching scheduler: correctness, overload ladder, chaos.

Two contracts (DESIGN.md §12):
  1. Correctness — every request that is not evicted decodes BITWISE equal
     to the legacy single-batch `generate()` path, at any admission order,
     slot occupancy, and page placement.
  2. Robustness — overload and injected faults (serve.admit / serve.step /
     kv.page_alloc, the `ci-default` plan) are absorbed as deterministic
     shed / timeout / preempt ledger events, never a crash.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import api
from repro.launch.scheduler import (
    ContinuousBatchingServer,
    PageAllocator,
    PagesExhausted,
    Request,
    RequestResult,
    ServeConfig,
)
from repro.launch.serve import generate, serving_steps
from repro.models import ShardCtx, get_model
from repro.obs import metrics as obs_metrics
from repro.resilience import faults, ledger


@pytest.fixture(scope="module")
def dense():
    cfg = get_config("mesh-paper").reduced()
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _prompt(i, t=8, vocab=256):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(100 + i), (t,), 0, vocab), np.int32
    )


def _legacy_tokens(model, params, prompt, gen):
    out, _ = generate(model, params, jnp.asarray(prompt)[None], gen_len=gen)
    return [int(x) for x in np.asarray(out[0])]


# -- page allocator ----------------------------------------------------------


def test_allocator_reserves_scratch_page():
    alloc = PageAllocator(4)
    pages = alloc.alloc(3, reason="admit")
    assert sorted(pages) == [1, 2, 3]  # page 0 never handed out
    assert alloc.free_count == 0


def test_allocator_exhaustion_and_reuse():
    alloc = PageAllocator(4)
    pages = alloc.alloc(2, reason="admit")
    with pytest.raises(PagesExhausted):
        alloc.alloc(2, reason="grow")
    alloc.free(pages)
    assert alloc.free_count == 3


def test_allocator_double_free_rejected():
    alloc = PageAllocator(4)
    pages = alloc.alloc(1, reason="admit")
    alloc.free(pages)
    with pytest.raises(ValueError, match="double free"):
        alloc.free(pages)
    with pytest.raises(ValueError, match="out of range"):
        alloc.free([0])


def test_serve_config_validation():
    with pytest.raises(ValueError, match="max_slots"):
        ServeConfig(max_slots=0)
    with pytest.raises(ValueError, match="num_pages"):
        ServeConfig(num_pages=1)


# -- correctness: bitwise parity with generate() -----------------------------


def test_staggered_requests_bitwise_equal_legacy(dense):
    """Five requests arriving one tick apart through two slots: admission
    order, slot reuse, and page placement never change any request's
    tokens relative to the legacy single-batch path."""
    model, params = dense
    prompts = [_prompt(i) for i in range(5)]
    scfg = ServeConfig(
        max_slots=2, page_size=8, num_pages=9, max_pages_per_seq=2, queue_capacity=8
    )
    server = ContinuousBatchingServer(model, params, scfg)
    reqs = [
        Request(rid=f"r{i}", prompt=p, max_new_tokens=8, arrival=i)
        for i, p in enumerate(prompts)
    ]
    results = server.run(reqs)
    assert server.counters["served"] == 5
    for i, p in enumerate(prompts):
        assert results[f"r{i}"].status == "ok"
        assert results[f"r{i}"].tokens == _legacy_tokens(model, params, p, 8)


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "pixtral-12b"])
def test_moe_and_vlm_bitwise_equal_legacy(arch):
    cfg = get_config(arch).reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    t, gen = 8, 6
    total = t + gen + (cfg.num_stub_patches if cfg.family == "vlm" else 0)
    pages = -(-total // 8)
    prompts = [_prompt(i, t=t, vocab=cfg.vocab_size) for i in range(3)]
    scfg = ServeConfig(
        max_slots=2,
        page_size=8,
        num_pages=1 + 2 * pages,
        max_pages_per_seq=pages,
        queue_capacity=4,
    )
    server = ContinuousBatchingServer(model, params, scfg)
    reqs = [
        Request(rid=f"r{i}", prompt=p, max_new_tokens=gen, arrival=i)
        for i, p in enumerate(prompts)
    ]
    results = server.run(reqs)
    for i, p in enumerate(prompts):
        assert results[f"r{i}"].status == "ok"
        assert results[f"r{i}"].tokens == _legacy_tokens(model, params, p, gen)


def test_ssm_stacked_state_bitwise_equal_legacy():
    """Recurrent family: O(1) state rides per slot; no pages involved."""
    cfg = get_config("rwkv6-1.6b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [_prompt(i, vocab=cfg.vocab_size) for i in range(3)]
    server = ContinuousBatchingServer(
        model, params, ServeConfig(max_slots=2, queue_capacity=4)
    )
    server.warmup()
    reqs = [
        Request(rid=f"s{i}", prompt=p, max_new_tokens=6, arrival=i)
        for i, p in enumerate(prompts)
    ]
    results = server.run(reqs)
    for i, p in enumerate(prompts):
        assert results[f"s{i}"].status == "ok"
        assert results[f"s{i}"].tokens == _legacy_tokens(model, params, p, 6)


def test_unschedulable_families_rejected():
    cfg = get_config("zamba2-1.2b").reduced()
    with pytest.raises(NotImplementedError, match="not schedulable"):
        ContinuousBatchingServer(get_model(cfg), None, ServeConfig())


# -- overload ladder: shed / timeout / preempt -------------------------------


def test_queue_overflow_sheds_deterministically(dense):
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=5, max_pages_per_seq=2, queue_capacity=2
    )
    server = ContinuousBatchingServer(model, params, scfg)
    reqs = [
        Request(rid=f"q{i}", prompt=_prompt(i), max_new_tokens=4) for i in range(5)
    ]
    for r in reqs:
        server.submit(r)
    # Admission happens at step(), so the queue (capacity 2) holds q0, q1 and
    # q2..q4 are shed at submit — exactly three deterministic shed events.
    shed = [e for e in ledger.events("serve.shed") if e.cause == "queue_full"]
    assert [dict(e.detail)["rid"] for e in shed] == ["'q2'", "'q3'", "'q4'"]
    server.drain()
    assert {r: server.results[r].status for r in ("q0", "q1")} == {
        "q0": "ok", "q1": "ok"
    }
    assert server.results["q2"].status == "shed"
    assert server.counters["shed"] == 3 and server.counters["served"] == 2


def test_never_fits_request_shed_up_front(dense):
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=5, max_pages_per_seq=2, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    server.submit(Request(rid="big", prompt=_prompt(0), max_new_tokens=64))
    assert server.results["big"].status == "shed"
    assert "too_long" in server.results["big"].reason
    assert server.pending == 0
    assert ledger.count("serve.shed") == 1


def test_deadline_evicts_running_sequence(dense):
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=9, max_pages_per_seq=4, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    server.submit(Request(rid="slow", prompt=_prompt(0), max_new_tokens=24, deadline=5))
    server.drain()
    res = server.results["slow"]
    assert res.status == "timeout" and 0 < len(res.tokens) < 24
    (ev,) = ledger.events("serve.timeout")
    assert dict(ev.detail)["rid"] == "'slow'" and ev.fallback == "evict"
    # pages reclaimed on eviction
    assert server.alloc.free_count == scfg.num_pages - 1


def test_deadline_expires_queued_request(dense):
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=9, max_pages_per_seq=2, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    server.submit(Request(rid="hog", prompt=_prompt(0), max_new_tokens=8))
    server.submit(Request(rid="late", prompt=_prompt(1), max_new_tokens=4, deadline=3))
    server.drain()
    assert server.results["hog"].status == "ok"
    assert server.results["late"].status == "timeout"
    assert server.results["late"].reason == "deadline_queued"


def test_preemption_evicts_lowest_priority(dense):
    """Two sequences growing into a pool that holds only one: the
    lower-priority one is preempted (partial tokens returned), the survivor
    finishes bitwise-correct, and the event is ledgered."""
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=2, page_size=8, num_pages=6, max_pages_per_seq=3, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    reqs = [
        Request(rid=f"p{i}", prompt=_prompt(i), max_new_tokens=16, priority=i)
        for i in range(2)
    ]
    results = server.run(reqs)
    assert results["p0"].status == "preempted" and 0 < len(results["p0"].tokens) < 16
    assert results["p1"].status == "ok"
    assert results["p1"].tokens == _legacy_tokens(model, params, _prompt(1), 16)
    (ev,) = ledger.events("serve.preempt")
    assert dict(ev.detail)["rid"] == "'p0'" and ev.cause == "pages_exhausted"
    assert server.alloc.free_count == scfg.num_pages - 1  # all pages returned


def test_self_preemption_when_requester_is_lowest_priority(dense):
    """When the sequence requesting growth IS the lowest-priority one (the
    higher-priority peer grabbed the last page first), it evicts itself —
    the loop can never deadlock waiting for pages it cannot take."""
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=2, page_size=8, num_pages=6, max_pages_per_seq=3, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    reqs = [
        Request(rid=f"v{i}", prompt=_prompt(i), max_new_tokens=16, priority=1 - i)
        for i in range(2)  # v0 outranks v1; v0 also grows first
    ]
    results = server.run(reqs)
    assert results["v1"].status == "preempted" and 0 < len(results["v1"].tokens) < 16
    assert results["v0"].status == "ok"
    assert results["v0"].tokens == _legacy_tokens(model, params, _prompt(0), 16)
    (ev,) = ledger.events("serve.preempt")
    detail = dict(ev.detail)
    assert detail["rid"] == "'v1'" and detail["for_rid"] == "'v1'"  # self-evict
    assert server.alloc.free_count == scfg.num_pages - 1


def test_self_preemption_first_in_order_still_grows_survivors(dense):
    """Regression: three sequences hit a page turn on the same tick with the
    pool exhausted; the FIRST one in iteration order self-evicts (it is the
    lowest priority).  The two survivors must still claim their growth pages
    before the tick decodes — an early exit here would let them write the
    boundary token's KV through scratch page 0 and silently diverge."""
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=3, page_size=8, num_pages=7, max_pages_per_seq=3, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)

    # Deterministic invariant spy: at every decode, each non-stalled active
    # sequence must already hold the page its next write lands in.  (The
    # token-level assertion below can pass by luck — a zeroed KV entry does
    # not always flip the argmax in a reduced random model — this cannot.)
    orig_decode_tick = server._decode_tick

    def checked_decode_tick():
        for s in server._active:
            if not s.stalled:
                assert len(s.pages) >= s.pos // scfg.page_size + 1, (
                    f"{s.req.rid} decoding at pos={s.pos} with only "
                    f"{len(s.pages)} pages: KV would land in scratch page 0"
                )
        orig_decode_tick()

    server._decode_tick = checked_decode_tick

    # w0 admitted first (iterates first) and lowest priority -> self-evicts.
    reqs = [
        Request(rid=f"w{i}", prompt=_prompt(i), max_new_tokens=16,
                priority=0 if i == 0 else 1)
        for i in range(3)
    ]
    results = server.run(reqs)
    assert results["w0"].status == "preempted" and 0 < len(results["w0"].tokens) < 16
    for i in (1, 2):
        assert results[f"w{i}"].status == "ok"
        assert results[f"w{i}"].tokens == _legacy_tokens(model, params, _prompt(i), 16)
    (ev,) = ledger.events("serve.preempt")
    detail = dict(ev.detail)
    assert detail["rid"] == "'w0'" and detail["for_rid"] == "'w0'"
    assert server.alloc.free_count == scfg.num_pages - 1


def test_preemption_victim_later_in_snapshot_does_not_leak_pages(dense):
    """Regression: the victim evicted for an earlier sequence's growth also
    appears later in the iteration snapshot.  A retired sequence must be
    skipped there — processing it would alloc fresh pages onto a dead
    sequence (leaked forever) and, with the pool dry, preempt a LIVE one."""
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=3, page_size=8, num_pages=7, max_pages_per_seq=3, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    # u2 is lowest priority but iterates LAST; u0's growth evicts it first.
    reqs = [
        Request(rid=f"u{i}", prompt=_prompt(i), max_new_tokens=16,
                priority=0 if i == 2 else 1)
        for i in range(3)
    ]
    results = server.run(reqs)
    assert results["u2"].status == "preempted" and 0 < len(results["u2"].tokens) < 16
    for i in (0, 1):
        assert results[f"u{i}"].status == "ok"
        assert results[f"u{i}"].tokens == _legacy_tokens(model, params, _prompt(i), 16)
    # exactly ONE preemption: the dead victim never preempted a live peer
    (ev,) = ledger.events("serve.preempt")
    assert dict(ev.detail)["rid"] == "'u2'"
    assert server.counters["preempted"] == 1
    # and no pages leaked onto the retired sequence
    assert server.alloc.free_count == scfg.num_pages - 1


def test_explicit_zero_deadline_expires_immediately(dense):
    """Regression: deadline=0 is an explicit 'expire now', not falsy sugar
    for the 512-tick default."""
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=9, max_pages_per_seq=2, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    server.submit(Request(rid="z0", prompt=_prompt(0), max_new_tokens=4, deadline=0))
    server.step()
    res = server.results["z0"]
    assert res.status == "timeout" and res.reason == "deadline_queued"
    assert res.tokens == []
    assert server.pending == 0


# -- fault sites (the ci-default triggers) ----------------------------------


def test_serve_admit_fault_sheds_exactly_one_request(dense):
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=2, page_size=8, num_pages=9, max_pages_per_seq=2, queue_capacity=8
    )
    server = ContinuousBatchingServer(model, params, scfg)
    reqs = [
        Request(rid=f"a{i}", prompt=_prompt(i), max_new_tokens=4) for i in range(3)
    ]
    with faults.inject({"serve.admit": faults.FaultSpec(times=1)}):
        results = server.run(reqs)
    assert results["a0"].status == "shed"  # first admission attempt fired
    assert results["a1"].status == "ok" and results["a2"].status == "ok"
    assert results["a1"].tokens == _legacy_tokens(model, params, _prompt(1), 4)
    shed = ledger.events("serve.shed")
    assert len(shed) == 1 and "injected fault" in shed[0].cause


def test_serve_step_fault_skips_tick_not_server(dense):
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=5, max_pages_per_seq=2, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    with faults.inject({"serve.step": faults.FaultSpec(times=1)}):
        results = server.run(
            [Request(rid="s0", prompt=_prompt(0), max_new_tokens=4)]
        )
    assert results["s0"].status == "ok"
    assert results["s0"].tokens == _legacy_tokens(model, params, _prompt(0), 4)
    assert server.counters["skipped_ticks"] == 1
    (ev,) = ledger.events("serve.step")
    assert ev.fallback == "skip_tick"


def test_page_alloc_fault_at_admission_defers_one_tick(dense):
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=5, max_pages_per_seq=2, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    with faults.inject(
        {"kv.page_alloc": faults.FaultSpec(times=1, match={"reason": "admit"})}
    ):
        results = server.run(
            [Request(rid="d0", prompt=_prompt(0), max_new_tokens=4)]
        )
    assert results["d0"].status == "ok"  # deferred, then admitted and served
    assert results["d0"].tokens == _legacy_tokens(model, params, _prompt(0), 4)
    (ev,) = ledger.events("kv.page_alloc")
    assert ev.fallback == "defer_admission"


def test_page_alloc_fault_at_growth_stalls_not_evicts(dense):
    model, params = dense
    ledger.clear()
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=5, max_pages_per_seq=3, queue_capacity=4
    )
    server = ContinuousBatchingServer(model, params, scfg)
    with faults.inject(
        {"kv.page_alloc": faults.FaultSpec(times=1, match={"reason": "grow"})}
    ):
        results = server.run(
            [Request(rid="g0", prompt=_prompt(0), max_new_tokens=10)]
        )
    assert results["g0"].status == "ok"  # stalled one tick at the page turn
    assert results["g0"].tokens == _legacy_tokens(model, params, _prompt(0), 10)
    (ev,) = ledger.events("kv.page_alloc")
    assert ev.fallback == "stall"
    assert server.counters["preempted"] == 0


def test_ci_default_oversubscribed_run_survives(dense):
    """The acceptance scenario: the full ci-default plan armed and more work
    than the pool can hold — the run completes, overload lands in the
    ledger, and every non-evicted request is bitwise-equal to legacy."""
    model, params = dense
    ledger.clear()
    api.clear_plan_cache()  # fresh process semantics: warmup builds the canary
    scfg = ServeConfig(
        max_slots=2,
        page_size=8,
        num_pages=7,
        max_pages_per_seq=2,
        queue_capacity=3,
        default_deadline=60,
        warmup_prompt_lens=(8,),
    )
    server = ContinuousBatchingServer(model, params, scfg)
    prompts = [_prompt(i) for i in range(6)]
    reqs = [
        Request(rid=f"c{i}", prompt=p, max_new_tokens=8, arrival=0)
        for i, p in enumerate(prompts)
    ]
    with faults.inject(dict(faults.CANNED_PLANS["ci-default"])):
        server.warmup()
        results = server.run(reqs)

    assert len(results) == 6  # nobody vanished
    statuses = {r.status for r in results.values()}
    assert statuses <= {"ok", "shed", "timeout", "preempted"}
    assert any(r.status == "ok" for r in results.values())
    assert any(r.status != "ok" for r in results.values())  # overload was real
    for i, p in enumerate(prompts):
        if results[f"c{i}"].status == "ok":
            assert results[f"c{i}"].tokens == _legacy_tokens(model, params, p, 8)
    # the serve-side triggers all fired and were absorbed
    assert ledger.count("serve.step") == 1
    assert ledger.count("serve.shed") >= 1
    assert ledger.count("kv.page_alloc") == 1


# -- warmup, tracing, drain --------------------------------------------------


def test_warmup_consumes_poison_outside_serving_traces(dense):
    """An armed kernel.output NaN poison lands in the guarded warmup canary,
    never inside the decode-step trace: served tokens stay legacy-equal."""
    model, params = dense
    ledger.clear()
    api.clear_plan_cache()
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=5, max_pages_per_seq=2,
        queue_capacity=4, warmup_prompt_lens=(8,),
    )
    server = ContinuousBatchingServer(model, params, scfg)
    with faults.inject(
        {"kernel.output": faults.FaultSpec(times=1, poison="nan")}
    ):
        server.warmup()
        results = server.run(
            [Request(rid="w0", prompt=_prompt(0), max_new_tokens=6)]
        )
    assert results["w0"].tokens == _legacy_tokens(model, params, _prompt(0), 6)
    assert ledger.count("guard.nonfinite") == 1  # the canary absorbed it


def test_decode_step_traced_once_across_occupancy(dense):
    """Slot occupancy changes every tick of a staggered run; the fixed
    (max_slots,) batch shape means ONE decode trace serves them all."""
    model, params = dense
    scfg = ServeConfig(
        max_slots=2, page_size=8, num_pages=9, max_pages_per_seq=2, queue_capacity=8
    )
    server = ContinuousBatchingServer(model, params, scfg)
    reqs = [
        Request(rid=f"t{i}", prompt=_prompt(i), max_new_tokens=6, arrival=2 * i)
        for i in range(4)
    ]
    server.run(reqs)
    assert server._decode._cache_size() == 1


@pytest.mark.parametrize("impl", ["xla_gather", "pallas_paged"])
def test_steps_consume_the_pools_in_place(dense, impl):
    """The admission scatter and the decode step are handed the pools and
    consume them: the buffers handed in read deleted after each call, the
    server holds the ones returned, and `serve_pool_in_place_total` counts
    one per call of each step.  Warm-up keeps the pools its decode returns."""
    model, params = dense
    scfg = ServeConfig(
        max_slots=2, page_size=8, num_pages=9, max_pages_per_seq=2,
        impl=impl, interpret=impl == "pallas_paged",
    )
    server = ContinuousBatchingServer(model, params, scfg)
    in_place = obs_metrics.counter("serve_pool_in_place_total", labels=("step",))
    base = {step: in_place.value(step=step) for step in ("scatter", "decode")}

    def counted():
        return {step: in_place.value(step=step) - base[step] for step in base}

    def deleted(pools):
        return [p.is_deleted() for p in pools.values()]

    first = server.pools
    server.warmup()
    assert all(deleted(first)) and not any(deleted(server.pools))
    assert counted() == {"scatter": 0, "decode": 0}

    server.submit(Request(rid="a", prompt=_prompt(0), max_new_tokens=3))
    held = server.pools
    server._admit()
    assert all(deleted(held)) and not any(deleted(server.pools))
    assert counted() == {"scatter": 1, "decode": 0}

    server._ensure_pages()
    held = server.pools
    server._decode_tick()
    assert all(deleted(held)) and not any(deleted(server.pools))
    assert counted() == {"scatter": 1, "decode": 1}

    held = server.pools
    server.step()  # a tick that decodes and admits nothing
    assert all(deleted(held))
    assert counted() == {"scatter": 1, "decode": 2}
    assert server.results["a"].tokens == _legacy_tokens(model, params, _prompt(0), 3)


def test_generate_trace_count_flat_across_requests(dense):
    """Satellite: the per-(model, ctx) step cache means request N replays
    request 0's traces — trace counts stay at one per shape."""
    model, params = dense
    ctx = ShardCtx()
    prefill, serve = serving_steps(model, ctx)
    generate(model, params, jnp.asarray(_prompt(0))[None], gen_len=4, ctx=ctx)
    base_p, base_s = prefill._cache_size(), serve._cache_size()
    for i in range(1, 4):
        generate(model, params, jnp.asarray(_prompt(i))[None], gen_len=4, ctx=ctx)
    assert serving_steps(model, ctx) == (prefill, serve)  # cache hit, same objects
    assert prefill._cache_size() == base_p
    assert serve._cache_size() == base_s


def test_step_cache_is_bounded():
    """Regression: the per-(model, ctx) step cache is a bounded LRU.  The
    jitted closures capture their model strongly, so an unbounded cache in a
    long-lived process that keeps constructing models grows memory forever;
    least-recently-served entries must be dropped instead."""
    from repro.launch import serve as serve_mod

    saved = dict(serve_mod._STEP_CACHE)
    try:
        serve_mod._STEP_CACHE.clear()
        ctx = ShardCtx()
        models = [object() for _ in range(serve_mod._STEP_CACHE_MAX + 3)]
        for m in models:
            serving_steps(m, ctx)  # steps are built lazily; never traced here
        assert len(serve_mod._STEP_CACHE) == serve_mod._STEP_CACHE_MAX
        # the most recent model is still cached: a hit returns the same pair
        pair = serving_steps(models[-1], ctx)
        assert serving_steps(models[-1], ctx) == pair
        # the oldest was evicted: its key is gone from the cache
        assert all(entry[0] is not models[0]
                   for entry in serve_mod._STEP_CACHE.values())
    finally:
        serve_mod._STEP_CACHE.clear()
        serve_mod._STEP_CACHE.update(saved)


def test_generate_degenerate_timing_reports_zero(dense):
    """Satellite: gen_len=1 decodes zero steps; the rate must be 0.0 (a
    finite, JSON-safe value), never inf."""
    model, params = dense
    _, rate = generate(model, params, jnp.asarray(_prompt(0))[None], gen_len=1)
    assert rate == 0.0


def test_duplicate_rid_rejected(dense):
    model, params = dense
    server = ContinuousBatchingServer(
        model, params,
        ServeConfig(max_slots=1, page_size=8, num_pages=5, max_pages_per_seq=2),
    )
    server.submit(Request(rid="dup", prompt=_prompt(0), max_new_tokens=4))
    with pytest.raises(ValueError, match="duplicate"):
        server.submit(Request(rid="dup", prompt=_prompt(1), max_new_tokens=4))
    server.drain()


def test_context_manager_drains_on_exit(dense):
    model, params = dense
    scfg = ServeConfig(
        max_slots=1, page_size=8, num_pages=5, max_pages_per_seq=2, queue_capacity=4
    )
    with ContinuousBatchingServer(model, params, scfg) as server:
        server.submit(Request(rid="cm", prompt=_prompt(0), max_new_tokens=4))
    assert server.results["cm"].status == "ok"
    assert server.pending == 0
