"""The reduction from a profiler trace to metrics, on a recorded v5e trace
and on small hand-made ones."""

from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_leaf_ops():
    assert tr.union_ns([(0, 10), (5, 15), (20, 30), (21, 22)]) == 25
    ev = [("while", 0, 100), ("a", 10, 20), ("b", 40, 10), ("fusion", 40, 5), ("c", 120, 5)]
    # `while` holds a and b; b holds the fusion; so the leaves are a, fusion, c.
    assert [e[0] for e in tr.leaf_ops(ev)] == ["a", "fusion", "c"]
    assert tr.busy_ns(ev, 0, 200) == 105
    assert tr.busy_ns(ev, 50, 125) == 55  # clipped to the window
    assert tr.op_time_ns(ev, "a", 0, 200) == 20
    assert tr.op_time_ns(ev, "a", 0, 15) == 5


def test_op_family_strips_hlo_text():
    name = "%mesh_matmul_pallas.213 = f32[16384,2048]{1,0:T(8,128)} custom-call(f32[16384,16384] %b)"
    assert tr.op_family(name) == "mesh_matmul_pallas"
    assert tr.op_family("%all-reduce-start.3 = (f32[8]) all-reduce-start(%x)") == "all-reduce-start"


def test_exposed_collective_time():
    # Ops of one line run one at a time, so a collective op on the line is
    # exposed for its whole length; what overlap there is shows as the gap
    # between a collective's start and its done, where compute ops run.
    ev = [
        ("fusion", 0, 40),
        ("all-reduce-start", 40, 2),
        ("fusion", 42, 30),
        ("all-reduce-done", 72, 8),
        ("all-reduce", 100, 10),
    ]
    total, exposed = tr.exposed_ns(ev, 0, 200)
    assert total == 20
    assert exposed == 20
    assert tr.exposed_ns(ev, 0, 75) == (5, 5)


def test_idle_gaps_are_labelled_by_the_host():
    dev = [("fusion", 0, 10), ("fusion", 50, 10), ("fusion", 65, 35)]
    host = [
        ("bench.window", 0, 100),
        ("bench.step", 5, 50),
        ("np.asarray(jax.Array)", 20, 30),
        ("bench.submit", 60, 5),
    ]
    gaps = tr.idle_gaps(dev, host, 0, 100, n=5)
    assert gaps[0] == ("bench.step > np.asarray(jax.Array)", pytest.approx(40e-9))
    assert gaps[1] == ("bench.submit", pytest.approx(5e-9))


def test_recorded_v5e_training_step():
    """One mesh-paper training step (batch 8 x 2048) traced on a v5e: the
    device is busy all but a few ms of the 672 ms step, and the mesh kernel
    takes half of it."""
    trace = tr.from_json(DATA / "v5e_train_step.json.gz")
    lo, hi = trace.window()
    assert (hi - lo) / 1e6 == pytest.approx(672.0, abs=1.0)
    ev = trace.device_ops[0]
    busy = tr.busy_ns(ev, lo, hi)
    assert 0.99 < busy / (hi - lo) <= 1.0
    mesh = tr.op_time_ns(ev, "mesh_matmul_pallas", lo, hi)
    assert mesh / 1e6 == pytest.approx(338.14, abs=0.01)
    top = tr.top_ops(ev, lo, hi, n=3)
    assert top[0][0] == "mesh_matmul_pallas"
    leaf_total = sum(s for _, s in tr.top_ops(ev, lo, hi, n=10_000))
    assert leaf_total * 1e9 <= busy * 1.001  # leaves never count a nanosecond twice
    gaps = tr.idle_gaps(ev, trace.host, lo, hi, n=10)
    assert gaps[0][0].startswith("bench.train_step")
    assert sum(s for _, s in gaps) == pytest.approx((hi - lo - busy) / 1e9, rel=0.05)


def test_load_reads_a_profiler_trace(tmp_path):
    """`load` reads what jax.profiler writes: here a CPU trace, which has
    no TPU plane but has the host's annotations."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load(tmp_path)
    lo, hi = trace.window()
    assert hi > lo
    assert trace.device_ops == {}
    back = tmp_path / "t.json.gz"
    tr.to_json(trace, back)
    assert tr.from_json(back).host == trace.host


def test_module_time_splits_programs():
    mods = [("jit_prefill_step", 0, 40), ("jit_decode", 50, 30), ("jit_prefill_step", 90, 20)]
    assert tr.module_name("jit_prefill_step(604145106575633064)") == "jit_prefill_step"
    assert tr.module_time_ns(mods, 0, 100) == {"jit_prefill_step": 50, "jit_decode": 30}
