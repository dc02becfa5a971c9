"""Training cells: `launch.train.build_trainer`'s jitted step, driven from
the seed, with the window timed on the host clock.

Set-up builds the one compiled step and its state, hands the state the
benchmark's weights, and drives the first `checked_steps` steps through the
same call and feed as the window, on rows that all differ.  Those steps are
what the plain reference follows once the window has closed:

  loss_gap    the largest relative gap of a step's loss,
  grad_gap    the worst leaf's gap between the norms of the first clipped
              gradient, the program's worked out from AdamW's first moment
              after one step (m1 = (1 - b1) g1),
  update_gap  the worst leaf's gap between the norms of the parameters'
              change over the checked steps,
  grad_diff   the worst leaf's norm of the difference between the program's
              first clipped gradient and the reference's, on a sample of
              each leaf's elements drawn from the seed (the same elements
              on both sides),

each taken against the reference's norm of that leaf or of the median
leaf, whichever is larger.  The gaps between norms are blind to rounding
that leaves a norm alone: a mean over many tokens averages it out of the
loss, and Adam's first step, m / sqrt(v), takes each element's sign and
not its size.  grad_diff sees it element by element.

Leaves whose reference gradient is under a thousandth of the median leaf's
move under Adam by round-off alone and are left out of update_gap.
"""

from __future__ import annotations

import collections
import gc
import time
import zlib
from typing import Any, Dict, List

import numpy as np

from bench import harness
from bench.weights import leaf_name, make_params, seed_key

__all__ = ["run", "leaf_gaps", "readings"]

# Steps queued on the device ahead of the host's wait: with two, a stall of
# the host shorter than a step leaves the device busy.
IN_FLIGHT = 2
# Elements of each leaf that grad_diff compares.
SAMPLES = 1 << 16


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in t])(
        [x for _, x in leaves]
    )
    return {leaf_name(p): float(n) for (p, _), n in zip(leaves, norms)}


def _delta_norms(a, b) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree.leaves(b)
    norms = jax.jit(
        lambda x, y: [
            jnp.sqrt(jnp.sum(jnp.square(u.astype(jnp.float32) - v.astype(jnp.float32))))
            for u, v in zip(x, y)
        ]
    )([x for _, x in la], lb)
    return {leaf_name(p): float(n) for (p, _), n in zip(la, norms)}


def _leaf_samples(tree, seed: int, scale: float = 1.0) -> Dict[str, np.ndarray]:
    """SAMPLES elements of each leaf (all of a smaller one), at indices drawn
    from the seed and the leaf's name, in float32 on the host."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    idx = []
    for p, x in leaves:
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, zlib.crc32(leaf_name(p).encode())])
        idx.append(np.arange(x.size) if x.size <= SAMPLES else np.sort(rng.integers(0, x.size, SAMPLES)))
    got = jax.jit(lambda t: [x.reshape(-1)[i].astype(jnp.float32) for x, i in zip(t, idx)])(
        [x for _, x in leaves]
    )
    return {leaf_name(p): np.asarray(g, np.float64) * scale for (p, _), g in zip(leaves, got)}


def sample_diff(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> float:
    """Worst leaf of |prog - ref| / max(|ref|, median leaf of |ref|)."""
    norms = {n: float(np.linalg.norm(r)) for n, r in ref.items()}
    median = float(np.median(list(norms.values())))
    return max(
        float(np.linalg.norm(prog[n] - ref[n])) / max(norms[n], median, 1e-30) for n in ref
    )


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], keep=None) -> float:
    """Worst leaf of |prog - ref| / max(ref, median leaf of ref)."""
    names = [n for n in ref if keep is None or n in keep]
    median = float(np.median([ref[n] for n in ref]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30) for n in names)


def make_batches(cfg: Dict, mix: Dict, seed: int, chips: int, sharding=None):
    """`feed_batches` batches of uniform random tokens, made on the device in
    one call; rows all differ with probability one."""
    import jax
    import jax.numpy as jnp

    b = mix["batch_per_chip"] * chips
    t = mix["seq"]
    n = mix["feed_batches"]

    def build(key):
        toks = jax.random.randint(key, (n, b, t + 1), 0, cfg["vocab_size"], jnp.int32)
        return [{"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:]} for i in range(n)]

    fn = jax.jit(build, out_shardings=sharding) if sharding is not None else jax.jit(build)
    return fn(seed_key(seed, "train-batches"))


def readings(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared, from the program's and the reference's records
    (losses, first-gradient norms and samples, update norms per leaf)."""
    loss_gap = max(
        abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"])
    )
    g_ref = ref["grad_norms"]
    median_g = float(np.median(list(g_ref.values())))
    moving = {n for n, v in g_ref.items() if v >= 1e-3 * median_g}
    return {
        "loss_gap": loss_gap,
        "grad_gap": leaf_gaps(prog["grad_norms"], g_ref),
        "update_gap": leaf_gaps(prog["update_norms"], ref["update_norms"], keep=moving),
        "grad_diff": sample_diff(prog["grad_samples"], ref["grad_samples"]),
    }


def reference_record(cfg: Dict, mix: Dict, abstract, seed: int, chips: int, numerics="f32",
                     rows_used=None) -> Dict[str, Any]:
    """The reference's record, made from the seed alone: its own weights and
    batches, `checked_steps` AdamW steps in float32 (or the control's
    numerics), one row at a time.  `rows_used` keeps only the first rows of
    each batch (a fault planted in the reference: part of the batch left out)."""
    import jax

    from bench.references import dense_transformer as ref

    p0 = make_params(abstract, cfg, seed)
    batches = make_batches(cfg, mix, seed, chips)[: mix["checked_steps"]]
    if rows_used is not None:
        batches = [{k: v[:rows_used] for k, v in b.items()} for b in batches]
    out = ref.train_reference(p0, batches, cfg, mix["optimizer"], numerics=numerics)
    rec = {
        "losses": out["losses"],
        "grad_norms": _leaf_norms(out["first_grad"]),
        "grad_samples": _leaf_samples(out["first_grad"], seed),
        "update_norms": _delta_norms(out["params"], p0),
    }
    del p0, batches, out
    gc.collect()
    jax.clear_caches()
    return rec


def build(cell: harness.Cell, seed: int, *, fault: str = "") -> Dict[str, Any]:
    """The compiled step and its state with the seed's weights, and the
    feed.  `fault` plants a fault in the timed path for the benchmark's own
    tests ("frozen": the step returns its state unchanged; "half_batch":
    half the rows are left out and the mean taken over the rest;
    "no_exchange": every chip steps with the first chip's rows alone, which
    is what the first chip applies when the gradient exchange is left out)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import build_trainer

    cfg, mix, chips = cell.config, cell.mix, cell.chips
    opt = mix["optimizer"]
    mesh = None
    if mix["mesh"] == "local-dp":
        mesh = make_local_mesh((chips, 1), ("data", "model"))
    batch = mix["batch_per_chip"] * chips
    step_fn, state, _ = build_trainer(
        harness.arch_config(cfg), batch=batch, seq=mix["seq"], mesh=mesh, lr=opt["lr"],
        total_steps=opt["total_steps"],
    )
    p_shard = jax.tree.map(lambda x: x.sharding, state["params"])
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state["params"])
    state["params"] = None  # the program's own init, replaced by the seed's weights
    state["params"] = make_params(abstract, cfg, seed, p_shard)
    b_shard = None
    if mesh is not None:
        b_shard = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    feed = make_batches(cfg, mix, seed, chips, b_shard)
    keep = {"half_batch": batch // 2, "no_exchange": mix["batch_per_chip"]}.get(fault)
    if keep:
        feed = [{k: jnp.concatenate([v[:keep]] * (batch // keep)) for k, v in b.items()} for b in feed]
    if fault == "frozen":
        inner = step_fn

        def step_fn(s, b):
            _, m = inner(jax.tree.map(jnp.copy, s), b)
            return s, m

    return {"step_fn": step_fn, "state": state, "feed": feed, "abstract": abstract,
            "p_shard": p_shard, "batch": batch}


def checked_steps(cell: harness.Cell, seed: int, ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Drive the first `checked_steps` steps through the window's own call
    and feed; the program's record for the comparison.  Leaves the state
    after those steps in `ctx`."""
    from jax.profiler import TraceAnnotation

    mix, cfg = cell.mix, cell.config
    b1 = mix["optimizer"]["b1"]
    state, step_fn, feed = ctx["state"], ctx["step_fn"], ctx["feed"]
    losses, prog = [], {}
    for i in range(mix["checked_steps"]):
        with TraceAnnotation("bench.train_step"):
            state, met = step_fn(state, feed[i])
        losses.append(met["loss"])
        if i == 0:
            m1 = _leaf_norms(state["opt"]["m"])
            prog["grad_norms"] = {n: v / (1.0 - b1) for n, v in m1.items()}
            prog["grad_samples"] = _leaf_samples(state["opt"]["m"], seed, 1.0 / (1.0 - b1))
    p0 = make_params(ctx["abstract"], cfg, seed, ctx["p_shard"])
    prog["update_norms"] = _delta_norms(state["params"], p0)
    del p0
    prog["losses"] = [float(x) for x in losses]
    ctx["state"] = state
    return prog


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, devices: List[Any],
        t_start: float, *, fault: str = "") -> Dict[str, Any]:
    """One run of a training cell; returns the result line's fields."""
    from jax.profiler import TraceAnnotation

    cfg, mix, chips = cell.config, cell.mix, cell.chips
    ctx = build(cell, seed, fault=fault)
    prog = checked_steps(cell, seed, ctx)
    state, step_fn, feed = ctx["state"], ctx["step_fn"], ctx["feed"]
    abstract, batch = ctx["abstract"], ctx["batch"]
    del ctx
    setup_s = time.monotonic() - t_start

    from bench.tracing import Tracing

    tracer = Tracing(trace, cell.name)
    k = mix["checked_steps"]
    steps = 0
    with tracer:
        with TraceAnnotation("bench.window"):
            t0 = time.monotonic()
            pending = collections.deque()
            while True:
                with TraceAnnotation("bench.train_step"):
                    state, met = step_fn(state, feed[k % len(feed)])
                k += 1
                pending.append(met["loss"])
                if len(pending) > IN_FLIGHT:
                    with TraceAnnotation("bench.wait"):
                        pending.popleft().block_until_ready()
                    steps += 1
                if time.monotonic() - t0 >= seconds:
                    break
            with TraceAnnotation("bench.wait"):
                losses = [float(x) for x in pending]
            steps += len(losses)
            last_loss = losses[-1]
            t1 = time.monotonic()
    window_s = t1 - t0
    mem = harness.memory_peak(devices)
    problems = harness.health_problems(cfg)
    if not np.isfinite(last_loss):
        problems.append(f"non-finite loss {last_loss} in the window")
    del state, feed, met, pending, step_fn
    gc.collect()

    tokens = steps * batch * mix["seq"]
    from bench import flops

    run_rec = harness.RunRecord(
        cell=cell,
        peaks=None,
        window_s=window_s,
        setup_s=setup_s,
        data={
            "tokens": tokens,
            "steps": steps,
            "batch": batch,
            "seq": mix["seq"],
            "step_ops": flops.train_step_ops(cfg, batch, mix["seq"]),
        },
    )
    tracer.fill(run_rec)

    ref = reference_record(cfg, mix, abstract, seed, chips)
    compared = harness.check(readings(prog, ref), cell.limits)
    return {
        "run": run_rec,
        "attempted": steps,
        "failed": 0 if np.isfinite(last_loss) else 1,
        "memory_peak_bytes": mem,
        "compared": compared,
        "problems": problems,
    }
