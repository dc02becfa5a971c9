"""MoE expert-dispatch benchmark (ISSUE 5): grouped plan vs one-hot einsum.

The grouped-GEMM planner replaced the Switch-style dense dispatch — a
(groups, s, e, cap) one-hot tensor driving dispatch/combine einsums — with a
sort/segment permutation feeding ONE ragged kernel per expert projection
(models/moe.py, DESIGN.md §10).  This section times both expert paths on the
same routing decisions at a reduced shape and reports, per layer:

  grouped_ms       sort + scatter + two grouped plans + gather/combine
  onehot_ms        one-hot dispatch einsum + two dense einsums + combine
  dispatch bytes   routing traffic each path streams: the one-hot path
                   materializes the (n, e, cap) dispatch/combine tensors;
                   the grouped path scatters rows in and gathers them out
                   (the GroupedPlan's own dispatch_bytes provenance)

`run(as_dict=True)` rides into BENCH_kernels.json under "moe" via
`benchmarks/run.py --json`, tracking the dispatch win across PRs.  CPU
numbers are structural (XLA backend either way); the kernel-level win is a
TPU measurement.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import api
from repro.models.layers import NO_SHARD, init_params
from repro.models.moe import moe_block, moe_specs

BATCH = 4
SEQ = 256  # <= _EXACT_GROUP: exact drop-free routing on both paths
N_TOKENS = BATCH * SEQ
D_MODEL = 64
N_EXPERTS = 8
TOP_K = 2
D_FF = 128
STEPS = 20


class _Cfg:
    """Just enough config surface for moe_specs/moe_block."""

    d_model = D_MODEL
    num_experts = N_EXPERTS
    experts_held = N_EXPERTS
    num_experts_per_tok = TOP_K
    norm_topk_prob = True
    moe_d_ff = D_FF
    num_layers = 2
    num_shared_experts = 0
    use_mesh_kernel = False
    mesh_block_m = mesh_block_n = mesh_block_k = 0
    param_dtype = "float32"
    fused_dense_epilogue = True

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)


def onehot_moe_reference(p, x, cfg, ctx=None, capacity_factor=1.25):
    """The pre-refactor dense one-hot dispatch (PR 4 models/moe.py),
    preserved verbatim as the SINGLE in-tree oracle: the benchmark baseline
    here and the drop-free equivalence oracle in tests/test_grouped.py.
    Returns (y, aux) exactly like moe_block; `ctx` is ignored (the old
    sharding constraints don't change CPU numerics)."""
    del ctx
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    n = b * t
    s = min(1024, t) if t > 1 else min(1024, n)
    while n % s:
        s //= 2
    g = n // s
    cap = s if s <= 256 else max(1, int(capacity_factor * s * k / e))

    xg = x.reshape(g, s, d)
    logits = jnp.einsum(
        "gsd,de->gse", xg.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)
    flat = onehot.reshape(g, s * k, e)
    pos = (jnp.cumsum(flat, axis=1) - 1.0).reshape(g, s, k, e)
    pos = jnp.sum(pos * onehot, axis=-1)
    keep = pos < cap
    gate = topv * keep.astype(topv.dtype)
    cap_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=xg.dtype)
    onehot_keep = onehot.astype(xg.dtype) * keep[..., None].astype(xg.dtype)
    disp = jnp.einsum("gske,gskc->gsec", onehot_keep, cap_oh)
    ex_in = jnp.einsum("gsec,gsd->gecd", disp, xg)
    gate_up = jnp.einsum("gecd,edf->gecf", ex_in, p["wi"])
    gate_h, up_h = jnp.split(gate_up, 2, axis=-1)
    h = jax.nn.silu(gate_h) * up_h
    ex_out = jnp.einsum("gecf,efd->gecd", h, p["wo"])
    combine = jnp.einsum(
        "gske,gskc->gsec", onehot_keep * gate.astype(xg.dtype)[..., None], cap_oh
    )
    y = jnp.einsum("gsec,gecd->gsd", combine, ex_out).reshape(b, t, d)

    if cfg.num_shared_experts:
        xf = x.reshape(n, d)
        sg = jax.nn.sigmoid(
            jnp.einsum(
                "nd,do->no",
                xf.astype(jnp.float32),
                p["shared_gate"].astype(jnp.float32),
            )
        ).astype(x.dtype)
        gu = jnp.einsum("nd,df->nf", xf, p["shared_wi"])
        g_, u_ = jnp.split(gu, 2, axis=-1)
        shared = jnp.einsum("nf,fd->nd", jax.nn.silu(g_) * u_, p["shared_wo"])
        y = y + (shared * sg).reshape(b, t, d)

    load = jnp.mean(onehot.sum(2), axis=(0, 1))
    imp = jnp.mean(probs, axis=(0, 1))
    lb_loss = e * jnp.sum(load * imp) / k
    router_z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return y, {"lb_loss": lb_loss, "router_z": router_z}


def _time_ms(fn, *args):
    fn(*args).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / STEPS * 1e3


def run(as_dict: bool = False):
    cfg = _Cfg()
    rng = np.random.default_rng(0)
    params = init_params(jax.random.PRNGKey(0), moe_specs(cfg), cfg.pdtype)
    x = jnp.asarray(
        rng.normal(size=(BATCH, SEQ, D_MODEL)).astype(np.float32)
    )
    cap = N_TOKENS  # drop-free at this shape (both paths route exactly)

    grouped = jax.jit(lambda pp, xx: moe_block(pp, xx, cfg, NO_SHARD)[0])
    onehot = jax.jit(lambda pp, xx: onehot_moe_reference(pp, xx, cfg)[0])

    y_g = grouped(params, x)
    y_o = onehot(params, x)
    np.testing.assert_allclose(
        np.asarray(y_g), np.asarray(y_o), rtol=1e-5, atol=1e-5
    )  # same routing semantics before any timing claims

    grouped_ms = _time_ms(grouped, params, x)
    onehot_ms = _time_ms(onehot, params, x)

    # Dispatch-traffic provenance: grouped from the plan's own record;
    # one-hot from the (groups, s, e, cap) dispatch+combine tensors the
    # baseline actually materializes (cap derived exactly as the reference
    # does — per notional group, not globally).
    grouped_plans = [
        p
        for p in api.plan_cache_info()["plans"]
        if p.get("grouped")
        and p["grouped"]["num_groups"] == N_EXPERTS
        and p["grouped"]["rows_per_group"] >= cap
    ]
    disp_grouped = sum(p["grouped"]["dispatch_bytes"] for p in grouped_plans)
    itemsize = np.dtype(np.float32).itemsize
    cap_pg = SEQ if SEQ <= 256 else max(1, int(1.25 * SEQ * TOP_K / N_EXPERTS))
    disp_onehot = 2 * N_TOKENS * N_EXPERTS * cap_pg * itemsize
    # ...and the FLOPs those tensors cost: dispatch + combine einsums contract
    # over d per (token, expert, slot); the sort/scatter path moves rows
    # without multiplying anything.
    disp_flops_onehot = 4 * N_TOKENS * N_EXPERTS * cap_pg * D_MODEL

    payload = {
        "shape": {
            "tokens": N_TOKENS,
            "d_model": D_MODEL,
            "experts": N_EXPERTS,
            "top_k": TOP_K,
            "d_ff": D_FF,
            "capacity": cap,
        },
        "grouped_ms_per_layer": round(grouped_ms, 3),
        "onehot_ms_per_layer": round(onehot_ms, 3),
        "dispatch_bytes_grouped": disp_grouped,
        "dispatch_bytes_onehot": disp_onehot,
        "dispatch_flops_onehot": disp_flops_onehot,
        "dispatch_flops_grouped": 0,  # sort/scatter/gather: no MACs
        "grouped_plans": len(grouped_plans),
    }
    print("# MoE expert dispatch: grouped plan vs one-hot einsum (drop-free)")
    print("path,ms_per_layer,dispatch_bytes,dispatch_flops")
    print(f"grouped,{grouped_ms:.3f},{disp_grouped},0")
    print(f"onehot,{onehot_ms:.3f},{disp_onehot},{disp_flops_onehot}")
    print(
        f"routing overhead removed: {disp_flops_onehot:.2e} dispatch-einsum"
        f" FLOPs/layer; ms ratio {onehot_ms / max(grouped_ms, 1e-9):.1f}x"
    )
    if as_dict:
        return payload


if __name__ == "__main__":
    run()
