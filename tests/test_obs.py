"""Observability subsystem (ISSUE 9, DESIGN.md §14).

Contracts under test:
  1. Overhead — a disabled `span()` adds <2% to a ~10us workload (the
     single-attribute-check fast path), so tracing can stay in hot paths.
  2. Correctness — nesting/parent links, thread safety, bounded ring,
     tracer-aware suppression (a span can NEVER fire inside a jitted trace).
  3. Exports — Chrome-trace documents load (schema), Prometheus text parses
     (format + cumulative-bucket invariants), JSONL sinks own their handle.
  4. Bridge — ledger events mirror into the degradation counter EXACTLY
     (the chaos CI job asserts the same equality under fault injection),
     and warm plan.execute spans become cost-model calibration records.
"""

import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import bridge as obs_bridge
from repro.obs import trace as obs_trace
from repro.resilience import ledger


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with obs fully off and empty."""
    obs.uninstall()
    obs.disable()
    obs.clear_spans()
    obs.reset_metrics()
    yield
    obs.uninstall()
    obs.disable()
    obs.clear_spans()
    obs.reset_metrics()


# -- overhead contract -------------------------------------------------------


def test_disabled_overhead_under_2pct():
    """Contract: a disabled span adds <2% to the cheapest realistically
    traced body (~tens of µs: a scheduler tick, a plan-cache hit).
    Measured as direct-per-call cost over body-per-iteration — differencing
    two long loops drowns a ~200ns effect in scheduler noise on a loaded
    test runner."""

    def workload():
        return sum(range(5000))

    def bare(iters=10_000):
        for _ in range(iters):
            workload()

    def spans_only(iters=10_000):
        for _ in range(iters):
            with obs.span("t.overhead", i=0):
                pass

    assert not obs.is_enabled()
    bare(), spans_only()  # warm both paths
    best = lambda fn: min(_timed(fn) for _ in range(5))
    per_call = best(spans_only) / 10_000  # incl. loop + with overhead
    body = best(bare) / 10_000
    overhead = per_call / body
    assert overhead < 0.02, (
        f"disabled span costs {per_call * 1e9:.0f}ns per call = "
        f"{overhead:.2%} of a {body * 1e6:.0f}us body (contract: <2%)"
    )
    assert obs.stats()["finished"] == 0  # nothing recorded while disabled


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# -- span mechanics ----------------------------------------------------------


def test_span_nesting_and_attrs():
    obs.enable()
    with obs.span("outer.op", a=1) as outer:
        with obs.span("inner.op") as inner:
            inner.set("found", "x")
        outer.set("late", True)
    got = {s.name: s for s in obs.spans()}
    assert set(got) == {"outer.op", "inner.op"}
    assert got["inner.op"].parent == got["outer.op"].seq
    assert got["outer.op"].parent is None
    assert got["outer.op"].attrs == {"a": 1, "late": True}
    assert got["inner.op"].attrs == {"found": "x"}
    assert got["inner.op"].duration_s <= got["outer.op"].duration_s


def test_span_records_error_and_unwinds():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("t.fail"):
            raise ValueError("boom")
    (sp,) = obs.spans("t.fail")
    assert "ValueError: boom" in sp.attrs["error"]
    # the stack unwound: a new span is a root again
    with obs.span("t.after"):
        pass
    assert obs.spans("t.after")[0].parent is None


def test_record_lands_a_finished_span_off_the_stack():
    assert obs.record("t.rec", 1.0, 2.0, k=1) is None
    assert obs.spans("t.rec") == []  # disabled: nothing
    obs.enable()
    with obs.span("t.open") as open_:
        obs.record("t.rec", 1.0, 2.5, k=1)
        with obs.span("t.child"):
            pass
    (rec,) = obs.spans("t.rec")
    assert (rec.t0, rec.t1, rec.duration_s, rec.attrs) == (1.0, 2.5, 1.5, {"k": 1})
    assert rec.parent is None and rec.annotation is None
    # the open span's stack was left alone: the child still nests under it
    assert obs.spans("t.child")[0].parent == open_.seq


def test_fresh_jit_records_one_compile_span_per_phase():
    def fresh_compile_probe(x):
        return jax.lax.sin(x)

    obs.enable()
    f = jax.jit(fresh_compile_probe)
    t_before = time.monotonic()
    f(jnp.ones(3)).block_until_ready()
    t_after = time.monotonic()
    mine = [s for s in obs.spans("jit.compile") if "fresh_compile_probe" in s.attrs["fun"]]
    assert sorted(s.attrs["phase"] for s in mine) == ["backend_compile", "trace"]
    for s in mine:  # JAX's wall-clock stamps, moved to the ring's clock
        assert t_before - 0.05 <= s.t0 <= s.t1 <= t_after + 0.05
    c = obs.counter("jit_compiles_total", labels=("phase",))
    assert c.value(phase="backend_compile") >= 1 and c.value(phase="trace") >= 1
    n = len(obs.spans("jit.compile"))
    f(jnp.ones(3)).block_until_ready()  # cached: no compile
    assert len(obs.spans("jit.compile")) == n


def test_no_compile_span_while_disabled():
    obs.enable()
    obs.disable()  # the listener stays registered, and records nothing

    def disabled_compile_probe(x):
        return jax.lax.cos(x)

    jax.jit(disabled_compile_probe)(jnp.ones(3)).block_until_ready()
    assert obs.spans("jit.compile") == []
    assert obs.counter("jit_compiles_total", labels=("phase",)).total() == 0


def test_spans_open_profiler_annotations_only_while_enabled():
    with obs.span("t.off") as sp:
        assert sp is obs_trace._NULL
    obs.enable()
    with obs.span("t.on") as sp:
        assert sp.annotation is not None
    assert sp.annotation is None  # closed with the span


def test_ring_is_bounded_and_counts_drops():
    obs.enable(capacity=8)
    try:
        for i in range(20):
            with obs.span("t.ring", i=i):
                pass
        st = obs.stats()
        assert st["retained"] == 8 and st["dropped"] == 12
        # newest survive
        assert [s.attrs["i"] for s in obs.spans("t.ring")] == list(range(12, 20))
    finally:
        obs.configure(capacity=obs_trace.DEFAULT_CAPACITY)


def test_threads_get_independent_stacks():
    obs.enable()
    errs = []

    def worker(k):
        try:
            for i in range(50):
                with obs.span(f"t.outer{k}"):
                    with obs.span(f"t.inner{k}", i=i):
                        pass
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert not errs
    assert obs.stats()["finished"] == 4 * 50 * 2
    for k in range(4):
        inners = obs.spans(f"t.inner{k}")
        outers = {s.seq: s for s in obs.spans(f"t.outer{k}")}
        assert len(inners) == 50
        for sp in inners:  # every inner's parent is one of ITS thread's outers
            assert sp.parent in outers and outers[sp.parent].tid == sp.tid


def test_no_span_inside_jit():
    """The tracer-aware guard: a span in jitted code must not record (it
    would measure trace time and fire per-compile, not per-execution)."""
    obs.enable()

    @jax.jit
    def f(x):
        with obs.span("t.in_jit"):
            return x * 2

    np.testing.assert_allclose(np.asarray(f(jnp.ones(4))), 2.0)
    f(jnp.ones(4))  # cached-trace call: no python at all
    assert obs.spans("t.in_jit") == []
    assert obs.stats()["suppressed_in_trace"] >= 1


def test_tracing_scope_restores_prior_state():
    assert not obs.is_enabled()
    with obs.tracing():
        assert obs.is_enabled()
        with obs.span("t.scoped"):
            pass
    assert not obs.is_enabled()
    assert len(obs.spans("t.scoped")) == 1


# -- metrics -----------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    c = obs.counter("t_total", "help", labels=("site",))
    c.inc(site="a"), c.inc(2, site="a"), c.inc(site="b")
    assert c.value(site="a") == 3 and c.total() == 4
    with pytest.raises(ValueError):
        c.inc(-1, site="a")
    with pytest.raises(ValueError):
        c.inc(site="a", extra="x")  # undeclared label

    g = obs.gauge("t_gauge")
    g.set(5), g.inc(-2)
    assert g.value() == 3

    h = obs.histogram("t_lat_seconds")
    for v in (1e-5, 1e-5, 1e-3, 0.1):
        h.observe(v)
    assert h.count() == 4 and h.sum() == pytest.approx(0.10102)
    q50 = h.quantile(0.5)
    assert 1e-6 < q50 < 1e-3
    assert h.quantile(1.0) >= 0.05


def test_registry_is_idempotent_and_kind_checked():
    a = obs.counter("t_same", labels=("x",))
    assert obs.counter("t_same", labels=("x",)) is a
    with pytest.raises(TypeError):
        obs.gauge("t_same", labels=("x",))
    with pytest.raises(TypeError):
        obs.counter("t_same", labels=("y",))


# -- exports -----------------------------------------------------------------


def test_chrome_trace_schema(tmp_path):
    obs.enable()
    with obs.span("outer.op", k="v"):
        with obs.span("inner.op"):
            pass
    path = tmp_path / "trace.json"
    obs.write_chrome_trace(str(path), metadata={"run": "test"})
    doc = json.loads(path.read_text())  # must round-trip as strict JSON
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M"  # process_name metadata event
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"outer.op", "inner.op"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0  # µs offsets from the epoch
        assert e["cat"] == e["name"].split(".")[0]
        assert isinstance(e["args"]["seq"], int)
    inner = next(e for e in xs if e["name"] == "inner.op")
    outer = next(e for e in xs if e["name"] == "outer.op")
    assert inner["args"]["parent"] == outer["args"]["seq"]
    assert doc["otherData"]["run"] == "test"


_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) .*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? ([0-9.e+-]+|\+Inf))$"
)


def test_prometheus_text_format():
    obs.counter("t_req_total", "requests", labels=("status",)).inc(status="ok")
    h = obs.histogram("t_dur_seconds", "durations")
    h.observe(0.001), h.observe(0.5)
    text = obs.prometheus_text()
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line), f"malformed exposition line: {line!r}"
    assert 't_req_total{status="ok"} 1' in text
    # cumulative buckets: +Inf bucket equals _count, buckets never decrease
    bucket_vals = [
        float(m.group(1))
        for m in re.finditer(r't_dur_seconds_bucket\{le="[^"]+"\} (\S+)', text)
    ]
    assert bucket_vals == sorted(bucket_vals)
    count = float(re.search(r"t_dur_seconds_count (\S+)", text).group(1))
    assert bucket_vals[-1] == count == 2


def test_jsonl_sink_owns_handle(tmp_path):
    path = tmp_path / "m.jsonl"
    with obs.JsonlSink(str(path)) as sink:
        sink.write({"a": 1})
        assert not sink.closed
    assert sink.closed
    with pytest.raises(ValueError):
        sink.write({"b": 2})
    assert json.loads(path.read_text()) == {"a": 1}


def test_metrics_logger_closes_sink(tmp_path):
    from repro.train.metrics import MetricsLogger

    path = tmp_path / "train.jsonl"
    with MetricsLogger(str(path)) as lg:
        lg.log(1, {"loss": 2.5})
        lg.summary({"final_step": 1})
        assert not lg.closed
    assert lg.closed
    recs = [json.loads(x) for x in path.read_text().splitlines()]
    assert recs[0]["step"] == 1 and recs[0]["loss"] == 2.5
    assert recs[1] == {"summary": {"final_step": 1}}
    MetricsLogger().close()  # pathless logger: close is a no-op


# -- bridge: ledger -> counter ----------------------------------------------


def test_ledger_events_mirror_to_counter_exactly():
    ledger.clear()
    try:
        ledger.record("t.site_a", cause="ValueError: x", fallback="skip")
        ledger.record("t.site_a", cause="ValueError: y", fallback="skip")
        obs.install()  # backfills the two pre-install events
        ledger.record("t.site_b", cause="KeyError: z", fallback="retry")
        c = obs_bridge.degradation_counter()
        assert c.total() == ledger.count() == 3
        assert c.value(site="t.site_a", cause="ValueError") == 2
        assert c.value(site="t.site_b", cause="KeyError") == 1
        # per-site sums match the ledger summary (the chaos CI assertion)
        per_site = {}
        for (site, _), v in c.series().items():
            per_site[site] = per_site.get(site, 0) + v
        want = {s: sum(d.values()) for s, d in ledger.summary().items()}
        assert per_site == want
    finally:
        ledger.clear()


def test_install_is_idempotent():
    ledger.clear()
    try:
        obs.install()
        obs.install()  # second install must not double-subscribe
        ledger.record("t.once", cause="E: e", fallback="f")
        assert obs_bridge.degradation_counter().value(site="t.once", cause="E") == 1
    finally:
        ledger.clear()


# -- bridge: spans -> calibration --------------------------------------------


def test_plan_execute_spans_feed_calibration(tmp_path, monkeypatch):
    from repro.costmodel.calibrate import CalibrationCache, clear_coefficients_memo
    from repro.kernels import api

    cache_path = tmp_path / "costmodel.json"
    monkeypatch.setenv("REPRO_COSTMODEL_CACHE", str(cache_path))
    clear_coefficients_memo()
    obs.enable()
    obs.install()
    try:
        a = jnp.ones((16, 16), jnp.float32)
        p = api.plan(api.GemmSpec.from_operands(a, a, blocks=(16, 16, 16)))
        jax.block_until_ready(p(a, a))  # cold: compile-inclusive, discarded
        jax.block_until_ready(p(a, a))  # warm: becomes a calibration record
        pend = obs.pending_calibration_records()
        assert len(pend) == 1
        assert pend[0]["source"] == "obs" and pend[0]["ms"] > 0
        assert pend[0]["terms"]["flops"] == 2 * 16**3
        n = obs.flush_calibration(refit=False)
        assert n == 1 and obs.pending_calibration_records() == []
        recs = CalibrationCache(str(cache_path)).records(jax.default_backend())
        assert len(recs) == 1 and recs[0]["source"] == "obs"
        stamp = obs.calibration_stamp()
        assert stamp["cache_path"] == str(cache_path)
    finally:
        clear_coefficients_memo()


def test_flush_of_invalid_records_never_raises():
    ledger.clear()
    try:
        obs.submit_calibration([{"terms": "not-a-dict", "ms": -1}])
        assert obs.flush_calibration() == 0  # invalid batch: dropped, no raise
        assert obs.pending_calibration_records() == []
    finally:
        ledger.clear()


# -- scheduler + serve integration -------------------------------------------


@pytest.fixture(scope="module")
def dense():
    from repro.configs import get_config
    from repro.models import get_model

    cfg = get_config("mesh-paper").reduced()
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _mk_server(dense, slots=2):
    from repro.launch.scheduler import ContinuousBatchingServer, ServeConfig

    model, params = dense
    cfg = ServeConfig(
        max_slots=slots, page_size=8, num_pages=1 + slots * 4,
        max_pages_per_seq=4, queue_capacity=8, warmup_prompt_lens=(8,),
    )
    return ContinuousBatchingServer(model, params, cfg)


def test_scheduler_ticks_emit_spans_and_metrics(dense, tmp_path, monkeypatch):
    from repro.launch.scheduler import Request

    # drain() flushes bridged calibration records; keep the persist off the
    # repo's calibration cache
    monkeypatch.setenv("REPRO_COSTMODEL_CACHE", str(tmp_path / "cal.json"))
    obs.enable()
    obs.install()
    server = _mk_server(dense)
    server.warmup()
    prompt = np.zeros(8, np.int32)
    for r in range(3):
        server.submit(Request(rid=f"r{r}", prompt=prompt, max_new_tokens=4))
    server.drain()
    ticks = obs.spans("serve.tick")
    assert len(ticks) == server.counters["ticks"]
    tick_seqs = {s.seq for s in ticks}
    decodes = obs.spans("serve.decode")
    assert decodes and all(s.parent in tick_seqs for s in decodes)
    prefills = obs.spans("serve.prefill")
    assert {s.attrs["rid"] for s in prefills} >= {"r0", "r1", "r2"}
    # metrics agree with the scheduler's own accounting
    assert obs.counter("serve_requests_total", labels=("status",)).value(
        status="served"
    ) == 3
    assert obs.counter("serve_decode_tokens_total").value() == float(
        server.counters["decode_tokens"]
    )
    # ...and once per tick, not per token
    assert obs.counter("serve_decode_tokens_total").value() == 3 * 3
    h = obs.histogram("serve_ttft_seconds")
    assert h.count() == 3 and h.quantile(0.5) > 0
    # one mean gap after the first token per served request, not per tick
    tpot = obs.histogram("serve_tpot_seconds")
    assert tpot.count() == 3 and 0 < tpot.sum() / 3 < max(s.duration_s for s in ticks) * 2
    # the tick's host work sits in child spans of the tick
    for name in ("serve.expire", "serve.grow", "serve.inputs", "serve.retire"):
        assert {s.parent for s in obs.spans(name)} <= tick_seqs, name
    admits = {s.seq for s in obs.spans("serve.admit")}
    for name in ("serve.prefill", "serve.scatter", "serve.first_sync"):
        assert {s.parent for s in obs.spans(name)} == admits, name
    decode_seqs = {s.seq for s in decodes}
    for name in ("serve.upload", "serve.decode_sync"):
        assert {s.parent for s in obs.spans(name)} == decode_seqs, name


def test_scheduler_records_first_token_per_admission(dense):
    from repro.launch.scheduler import Request

    obs.enable()
    server = _mk_server(dense)
    server.warmup()
    for r in range(3):
        server.submit(Request(rid=f"r{r}", prompt=np.zeros(8 + r, np.int32), max_new_tokens=3))
    server.drain()
    firsts = {s.attrs["rid"]: s for s in obs.spans("serve.first_token")}
    assert set(firsts) == {"r0", "r1", "r2"}
    submits = {s.attrs["rid"]: s for s in obs.spans("serve.submit")}
    syncs = {s.attrs["rid"]: s for s in obs.spans("serve.first_sync")}
    for rid, sp in firsts.items():
        assert sp.attrs["prompt_len"] == 8 + int(rid[1])
        assert 0 <= sp.attrs["queued_s"] <= sp.duration_s
        assert submits[rid].t0 <= sp.t0 <= submits[rid].t1  # from the submission
        assert syncs[rid].t1 <= sp.t1  # to the first token on the host
    # the TTFT histogram observes exactly these durations
    h = obs.histogram("serve_ttft_seconds")
    assert h.count() == 3
    assert h.sum() == pytest.approx(sum(s.duration_s for s in firsts.values()), rel=1e-12)


def test_serve_main_obs_export_end_to_end(tmp_path, capsys, monkeypatch):
    from repro.launch import serve

    # the exit-time calibration flush persists; keep it off the repo's cache
    monkeypatch.setenv("REPRO_COSTMODEL_CACHE", str(tmp_path / "cal.json"))
    out = tmp_path / "trace.json"
    serve.main([
        "--arch", "mesh-paper", "--reduced", "--batch", "1",
        "--prompt-len", "8", "--gen", "2", "--requests", "2",
        "--plan-stats", "--obs-export", str(out),
    ])
    text = capsys.readouterr().out
    assert "obs export:" in text
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    # plan() here only runs inside the jitted step traces, where spans are
    # correctly suppressed — so the timeline holds the request spans (the
    # scheduler path, exercised above and in CI, adds tick/plan spans)
    assert "serve.request" in names
    assert sum(e["name"] == "serve.request" for e in evs) == 2
    st = obs.stats()
    assert st["suppressed_in_trace"] > 0  # the in-jit plan spans were suppressed
    assert "source" in doc["otherData"]["calibration"]
    # the .prom and .jsonl sidecars parse
    (tmp_path / "trace.json.prom").read_text()
    lines = (tmp_path / "trace.json.jsonl").read_text().splitlines()
    assert lines and all(json.loads(x)["name"] for x in lines)
