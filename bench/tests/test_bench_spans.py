"""The program's spans on the profiler's host line, and the per-layer
metrics that read them: host gaps per tick, compilations in the window and
the program's own time to first token."""

import time

import jax
import pytest

from bench import harness
from bench import trace_reduce as tr
from bench.tests.small import small_cell
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs

SEED = 2**33 + 303


@pytest.fixture
def obs_off():
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def _reader(name):
    return harness.load_reader("layer_metrics", name)


def _run(trace=None, spans=(), **data):
    cell = small_cell("mesh-paper.chat")
    run = harness.RunRecord(cell=cell, peaks=None, window_s=1.0, setup_s=1.0,
                            trace=trace, spans=list(spans), data=data)
    if trace is not None:
        run.trace_window = trace.window()
    return run


def _span(name, t0, t1, **attrs):
    sp = obs.Span(name, 0, None, 0, t0, attrs)
    sp.t1 = t1
    return sp


def test_span_shows_on_the_host_line_that_load_keeps(tmp_path, obs_off):
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.x"):
            with obs.span("serve.off"):  # disabled: no annotation
                pass
            obs.enable()
            with obs.span("serve.on", k=1):
                jax.numpy.ones(8).sum().block_until_ready()
            obs.disable()
    jax.profiler.stop_trace()
    host = tr.load(tmp_path).host
    names = [n for n, _, _ in host]
    assert "serve.on" in names  # the name alone, no #k=v# metadata
    assert not any(n.startswith("serve.off") for n in names)
    (x,) = [(s, d) for n, s, d in host if n == "bench.x"]
    (on,) = [(s, d) for n, s, d in host if n == "serve.on"]
    assert x[0] <= on[0] and on[0] + on[1] <= x[0] + x[1]  # one clock


def test_host_gap_per_tick():
    dev = [("fusion", 10, 20), ("fusion", 35, 5), ("while", 60, 30), ("fusion", 62, 10)]
    host = [
        ("bench.window", 0, 200),
        ("serve.tick", 5, 40),  # [5, 45): busy 20 + 5, idle 15
        ("serve.tick", 50, 50),  # [50, 100): busy 30, idle 20
        ("serve.tick", 190, 20),  # ends after the window: left out
    ]
    trace = tr.Trace({0: dev}, host)
    got = _reader("host_gap_ms_per_tick.chat")(_run(trace))
    assert got == pytest.approx((15 + 20) / 2 / 1e6)
    assert _reader("host_gap_ms_per_tick.offline")(_run(trace)) == got
    # None without a trace, without ticks, without a device
    assert _reader("host_gap_ms_per_tick.chat")(_run()) is None
    no_ticks = tr.Trace({0: dev}, [("bench.window", 0, 200)])
    assert _reader("host_gap_ms_per_tick.chat")(_run(no_ticks)) is None
    assert _reader("host_gap_ms_per_tick.chat")(_run(tr.Trace({}, host))) is None


def test_host_gap_matches_busy_ns_over_many_ticks():
    import random

    rnd = random.Random(7)
    dev, t = [], 0
    for _ in range(400):
        t += rnd.randint(0, 30)
        d = rnd.randint(1, 40)
        dev.append(("op", t, d))
        if rnd.random() < 0.1:
            dev.append(("inner", t + 1, max(1, d - 2)))
        t += d
    ticks = [("serve.tick", a, rnd.randint(5, 200)) for a in range(0, t - 300, 250)]
    trace = tr.Trace({0: dev}, [("bench.window", 0, t)] + ticks)
    want = sum(d - tr.busy_ns(dev, s, s + d) for _, s, d in ticks) / len(ticks) / 1e6
    assert _reader("host_gap_ms_per_tick.chat")(_run(trace)) == pytest.approx(want)


def test_compiles_in_window(monkeypatch):
    trace = tr.Trace({}, [("bench.window", 0, 10)])
    spans = [
        _span("jit.compile", 0.5, 1.5, fun="a", phase="backend_compile"),  # overlaps the open
        _span("jit.compile", 2.0, 2.1, fun="b", phase="trace"),  # not a backend compile
        _span("jit.compile", 3.0, 3.5, fun="b", phase="backend_compile"),
        _span("jit.compile", 9.0, 9.5, fun="c", phase="backend_compile"),  # after the close
        _span("serve.tick", 2.0, 3.0),
    ]
    read = _reader("compiles_in_window.chat")
    obs_metrics.counter("jit_compiles_total", labels=("phase",))
    assert read(_run(trace, spans, window_t0=1.0, window_t1=4.0)) == 2
    assert read(_run(trace, spans[1:2], window_t0=1.0, window_t1=4.0)) == 0
    assert read(_run(None, spans, window_t0=1.0, window_t1=4.0)) is None
    # a program that counts no compiles reads nothing, not 0
    monkeypatch.setattr(obs_metrics, "snapshot", lambda: {})
    assert read(_run(trace, spans, window_t0=1.0, window_t1=4.0)) is None


def test_ttft_server_p95():
    trace = tr.Trace({}, [("bench.window", 0, 10)])
    due = {f"r{i}": 1.0 + i for i in range(22)}
    spans = [_span("serve.submit", 1.01 + i, 1.02 + i, rid=f"r{i}") for i in range(21)]
    spans += [_span("serve.first_token", 1.01 + i, 1.03 + i, rid=f"r{i}") for i in range(20)]
    spans.append(_span("serve.first_token", 0.0, 5.0, rid="warm-8"))  # not due: left out
    run = _run(trace, spans, due=due, window_t1=30.0)
    # 20 requests at 20 ms; r20 submitted at 21.01 and waiting at the close
    # (8.99 s); r21 never submitted, due at 22.0 (8.0 s)
    import numpy as np

    want = np.percentile([0.02] * 20 + [30.0 - 21.01, 30.0 - 22.0], 95) * 1e3
    assert _reader("ttft_server_p95_ms.chat")(run) == pytest.approx(want)
    # None without a trace, or where the program records no first token
    assert _reader("ttft_server_p95_ms.chat")(_run(None, spans, due=due, window_t1=30.0)) is None
    no_first = [s for s in spans if s.name != "serve.first_token"]
    assert _reader("ttft_server_p95_ms.chat")(_run(trace, no_first, due=due, window_t1=30.0)) is None


def test_traced_serving_run_reads_the_program_spans(obs_off, tmp_path, monkeypatch):
    """A traced chat run on the CPU: the scheduler's spans are on the host
    line, the program's first tokens come before the harness's, and the
    compile count reads (the device metrics need a TPU)."""
    from bench import serve_cell, tracing

    class InTmp(tracing.Tracing):  # the profile goes to the test's directory
        def __init__(self, enabled, cell):
            super().__init__(enabled, cell, out=tmp_path)

    monkeypatch.setattr(tracing, "Tracing", InTmp)
    cell = small_cell("mesh-paper.chat")
    out = serve_cell.run(cell, SEED, 1.0, True, jax.devices()[:1], time.monotonic())
    run = out["run"]
    names = {n for n, _, _ in run.trace.host}
    for name in ("serve.tick", "serve.admit", "serve.prefill", "serve.first_sync",
                 "serve.inputs", "serve.decode", "serve.decode_sync", "serve.retire",
                 "serve.submit"):
        assert name in names, name
    firsts = [s for s in run.spans if s.name == "serve.first_token"]
    assert firsts
    read = lambda kind, name: harness.load_reader(kind, name)(run)  # noqa: E731
    server = read("layer_metrics", "ttft_server_p95_ms.chat")
    assert 0 < server < read("metrics", "ttft_p95_ms")
    assert read("layer_metrics", "compiles_in_window.chat") >= 0
    assert read("layer_metrics", "host_gap_ms_per_tick.chat") is None  # no device ops
    assert read("layer_metrics", "queue_wait_p50_ms.chat") is not None
