"""Grouped-GEMM planner tests (ISSUE 5, DESIGN.md §10).

Covers: grouped plans vs the dense one-hot / per-group oracles on every
backend (bitwise on drop-free configs), empty-group and single-expert edge
cases, plan-cache keying on GroupSpec, capability rejection for backends
that don't declare `grouped`, gradients through the Pallas ragged kernel,
the `expert` collective schedule, and the MoE refactor's drop-free
equivalence with the pre-refactor dense dispatch.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import api
from repro.kernels.ref import grouped_matmul_ref
from repro.models.layers import NO_SHARD, init_params
from repro.models.moe import moe_block, moe_specs

BACKENDS = ("xla", "ref", "pallas_mesh")


def _case(g=4, rpg=16, k=24, n=20, seed=0, sizes=None):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.normal(size=(g * rpg, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(g, k, n)).astype(np.float32))
    if sizes is None:
        sizes = rng.integers(0, rpg + 1, size=g)
    sizes = jnp.asarray(np.asarray(sizes), jnp.int32)
    # contract: padding rows are zero (the MoE scatter produces exactly this)
    valid = (jnp.arange(rpg)[None, :] < sizes[:, None]).reshape(-1, 1)
    tokens = tokens * valid
    off = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)]).astype(
        jnp.int32
    )
    return tokens, sizes, off, w


@pytest.fixture(autouse=True)
def _fresh_cache():
    api.clear_plan_cache()
    yield
    api.clear_plan_cache()


@pytest.mark.parametrize("backend", BACKENDS)
def test_grouped_plan_matches_oracle(backend):
    tokens, sizes, off, w = _case()
    spec = api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20)
    p = api.plan(spec, backend=backend)
    assert isinstance(p, api.GroupedPlan)
    out = p(tokens, off, w)
    want = grouped_matmul_ref(tokens, sizes, w)
    # drop-free of reduction-order ambiguity at K <= one block: bitwise
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("backend", BACKENDS)
def test_grouped_epilogue_parity(backend):
    tokens, sizes, off, w = _case(seed=1)
    rng = np.random.default_rng(2)
    bias = jnp.asarray(rng.normal(size=(4, 20)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(64, 20)).astype(np.float32))
    spec = api.GemmSpec.for_groups(
        api.GroupSpec(4, 16), 24, 20,
        epilogue=api.Epilogue(bias=True, activation="gelu", residual=True),
    )
    out = api.plan(spec, backend=backend)(tokens, off, w, bias=bias, residual=res)
    # reference: per-group epilogue then the segment mask (contract: padding
    # rows are zero even when a residual is attached)
    z = jnp.einsum(
        "grk,gkn->grn", tokens.reshape(4, 16, 24), w,
        preferred_element_type=jnp.float32,
    )
    z = api.ACTIVATIONS["gelu"](z + bias[:, None, :]) + res.reshape(4, 16, 20)
    valid = jnp.arange(16)[None, :] < sizes[:, None]
    want = jnp.where(valid[..., None], z, 0.0).reshape(64, 20)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_grouped_empty_groups(backend):
    """All-empty and partially-empty groups produce zero rows."""
    tokens, sizes, off, w = _case(sizes=[0, 0, 0, 0])
    spec = api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20)
    out = api.plan(spec, backend=backend)(tokens, off, w)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((64, 20), np.float32))
    tokens, sizes, off, w = _case(sizes=[16, 0, 3, 0], seed=3)
    out = api.plan(spec, backend=backend)(tokens, off, w)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(grouped_matmul_ref(tokens, sizes, w))
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_grouped_single_group(backend):
    """num_groups=1 degenerates to a plain (masked) GEMM."""
    tokens, sizes, off, w = _case(g=1, rpg=32, sizes=[20], seed=4)
    spec = api.GemmSpec.for_groups(api.GroupSpec(1, 32), 24, 20)
    out = api.plan(spec, backend=backend)(tokens, off, w)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(grouped_matmul_ref(tokens, sizes, w))
    )


def test_grouped_plan_cache_keys_on_groupspec():
    spec_a = api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20)
    spec_b = api.GemmSpec.for_groups(api.GroupSpec(8, 8), 24, 20)  # same m!
    assert spec_a.m == spec_b.m
    p_a = api.plan(spec_a)
    p_b = api.plan(spec_b)
    assert p_a is not p_b  # GroupSpec is part of the cache key
    assert api.plan(spec_a) is p_a  # identical object on reuse
    info = api.plan_cache_info()
    assert info["size"] == 2 and info["hits"] == 1 and info["misses"] == 2
    assert all(p["grouped"] for p in info["plans"])


def test_grouped_capability_rejection():
    """Backends that don't declare `grouped` reject grouped specs; declaring
    it without a grouped_impl is rejected at registration."""
    api.register_backend(
        "nogrouped_double",
        lambda p, a, b, bias, res: jnp.matmul(a, b),
        {"structures": {"general"}},
    )
    try:
        spec = api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20)
        with pytest.raises(api.CapabilityError, match="grouped"):
            api.plan(spec, backend="nogrouped_double")
        with pytest.raises(ValueError, match="grouped_impl"):
            api.register_backend(
                "half_grouped",
                lambda p, a, b, bias, res: jnp.matmul(a, b),
                {"structures": {"general"}, "grouped": True},
            )
    finally:
        api.unregister_backend("nogrouped_double")


def test_grouped_spec_validation():
    with pytest.raises(ValueError, match="for_groups"):
        api.GemmSpec(m=65, k=24, n=20, group=api.GroupSpec(4, 16))
    with pytest.raises(ValueError, match="general"):
        api.GemmSpec(
            m=64, k=24, n=20, group=api.GroupSpec(4, 16), structure="scrambled"
        )
    with pytest.raises(ValueError, match="batch"):
        api.GemmSpec(
            m=64, k=24, n=20, group=api.GroupSpec(4, 16), batch=(2,)
        )
    with pytest.raises(ValueError, match="positive"):
        api.GroupSpec(0, 16)


def test_grouped_operand_validation():
    tokens, sizes, off, w = _case()
    p = api.plan(api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20))
    with pytest.raises(ValueError, match="group_offsets"):
        p(tokens, off[:-1], w)
    with pytest.raises(ValueError, match="integer"):
        p(tokens, off.astype(jnp.float32), w)
    with pytest.raises(ValueError, match="do not match"):
        p(tokens[:, :-1], off, w)
    with pytest.raises(ValueError, match="without bias"):
        p(tokens, off, w, bias=jnp.zeros((4, 20)))


def test_grouped_grads_match_reference():
    """The custom VJP through the Pallas ragged kernel equals autodiff
    through the pure-jnp oracle — tokens AND stacked weights."""
    tokens, sizes, off, w = _case(seed=5)
    spec = api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20)
    p = api.plan(spec, backend="pallas_mesh")

    def loss_kernel(t, ww):
        return jnp.sum(p(t, off, ww) ** 2)

    def loss_ref(t, ww):
        return jnp.sum(grouped_matmul_ref(t, sizes, ww) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1))(tokens, w)
    gr = jax.grad(loss_ref, argnums=(0, 1))(tokens, w)
    # Each gradient entry is an f32 sum of ~K products whose terms reach the
    # size of the largest gradient (~90 here); the two paths block those sums
    # differently, so an entry that cancels to near zero carries an absolute
    # error of a few ulps of the largest term, not of itself.  The bound is
    # therefore K * eps32 * max|grad| (~2.6e-4 here), with rtol left at 1e-6.
    for a, b in zip(gk, gr):
        b = np.asarray(b)
        atol = spec.k * np.finfo(np.float32).eps * float(np.max(np.abs(b)))
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-6, atol=atol)


def test_grouped_autotuned_block_m_divides_rows():
    """block_m is clamped to divide the rows_per_group bound (the ragged
    grid needs whole row blocks per group)."""
    spec = api.GemmSpec.for_groups(api.GroupSpec(4, 24), 32, 32)
    p = api.plan(spec, backend="pallas_mesh")
    bm = p.blocks[0]
    assert 24 % bm == 0
    tokens, sizes, off, w = _case(g=4, rpg=24, k=32, n=32, sizes=[24, 5, 0, 17])
    np.testing.assert_allclose(
        np.asarray(p(tokens, off, w)),
        np.asarray(grouped_matmul_ref(tokens, sizes, w)),
        rtol=1e-6,
        atol=1e-6,
    )


# ---------------------------------------------------------------------------
# Sharded grouped plans: the `expert` schedule
# ---------------------------------------------------------------------------


def test_grouped_sharded_trivial_mesh_bitwise():
    """A size-1 axis_g routes through the ShardedGroupedPlan path and
    reproduces the unsharded GroupedPlan bit for bit."""
    from repro.launch.mesh import make_local_mesh

    tokens, sizes, off, w = _case(seed=6)
    mesh = make_local_mesh((1,), ("model",))
    base = api.plan(api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20))(
        tokens, off, w
    )
    spec = api.GemmSpec.for_groups(
        api.GroupSpec(4, 16), 24, 20,
        shard=api.ShardSpec.from_mesh(mesh, g="model"),
    )
    p = api.plan(spec, mesh=mesh)
    assert isinstance(p, api.ShardedGroupedPlan)
    assert p.schedule == "replicated" and p.bytes_moved == 0
    np.testing.assert_array_equal(np.asarray(p(tokens, off, w)), np.asarray(base))

    # the epilogue shards with its operands, so a sharded grouped plan with
    # bias+activation reproduces the unsharded one bit for bit too
    epi = api.Epilogue(bias=True, activation="gelu")
    bias = jnp.ones((4, 20), jnp.float32)
    base_e = api.plan(api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20, epilogue=epi))(
        tokens, off, w, bias=bias
    )
    spec_e = api.GemmSpec.for_groups(
        api.GroupSpec(4, 16), 24, 20, epilogue=epi,
        shard=api.ShardSpec.from_mesh(mesh, g="model"),
    )
    p_e = api.plan(spec_e, mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(p_e(tokens, off, w, bias=bias)), np.asarray(base_e)
    )


@pytest.mark.skipif(
    jax.device_count() < 8, reason="expert schedule needs 8 devices in-process"
)
@pytest.mark.parametrize("backend", ["xla", "pallas_mesh"])
def test_grouped_expert_schedule_bitwise(backend):
    """Group dim sharded over 8 devices: same bits as the unsharded plan,
    bytes-moved provenance populated."""
    from repro.launch.mesh import make_local_mesh

    tokens, sizes, off, w = _case(g=8, rpg=16, seed=7)
    mesh = make_local_mesh((8,), ("model",))
    base = api.plan(
        api.GemmSpec.for_groups(api.GroupSpec(8, 16), 24, 20), backend=backend
    )(tokens, off, w)
    spec = api.GemmSpec.for_groups(
        api.GroupSpec(8, 16), 24, 20,
        shard=api.ShardSpec.from_mesh(mesh, g="model"),
    )
    p = api.plan(spec, backend=backend, mesh=mesh)
    assert p.schedule == "expert"
    assert p.bytes_moved > 0 and p.collective_phases == 7
    np.testing.assert_array_equal(np.asarray(p(tokens, off, w)), np.asarray(base))
    rl = _roofline_record(p)
    assert rl["grouped"]["per_group_flops"] > 0


def _roofline_record(p):
    from repro.launch.roofline import analyze_plan

    return analyze_plan(p.describe())


def test_roofline_understands_grouped_plans():
    spec = api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20)
    p = api.plan(spec)
    rec = _roofline_record(p)
    assert rec["grouped"]["num_groups"] == 4
    assert rec["grouped"]["per_group_flops"] == 2 * 16 * 24 * 20
    assert rec["grouped"]["dispatch_bytes"] == p.describe()["grouped"]["dispatch_bytes"]
    assert rec["t_compute_s"] > 0 and rec["dominant"] in (
        "compute", "memory", "collective",
    )


# ---------------------------------------------------------------------------
# The packed layout: groups end to end, no padding between them
# ---------------------------------------------------------------------------


def _packed_case(sizes=(5, 0, 9, 3), rows=24, k=24, n=20, seed=6):
    rng = np.random.default_rng(seed)
    # rows past the last group hold garbage: the contract zeroes them
    tokens = jnp.asarray(rng.normal(size=(rows, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)).astype(np.float32))
    off = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    return tokens, off, w


@pytest.mark.parametrize("backend", ["xla", "ref"])
def test_grouped_packed_plan_matches_a_loop_over_groups(backend):
    """Row r of group g is epilogue(tokens[r] @ w[g] + bias[g]); rows past
    the last group are zero, empty groups take no rows."""
    tokens, off, w = _packed_case()
    rng = np.random.default_rng(7)
    bias = jnp.asarray(rng.normal(size=(4, 20)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(24, 20)).astype(np.float32))
    spec = api.GemmSpec.for_groups(
        api.GroupSpec(4, 24, packed=True), 24, 20,
        epilogue=api.Epilogue(bias=True, activation="gelu", residual=True),
    )
    assert spec.m == 24
    out = api.plan(spec, backend=backend)(tokens, off, w, bias=bias, residual=res)
    want = np.zeros((24, 20), np.float32)
    o = np.asarray(off)
    for g in range(4):
        z = tokens[o[g]:o[g + 1]] @ w[g] + bias[g]
        want[o[g]:o[g + 1]] = np.asarray(api.ACTIVATIONS["gelu"](z) + res[o[g]:o[g + 1]])
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(out)[o[-1]:])


def test_grouped_packed_grads_match_the_capacity_layout():
    """The packed layout's gradients are the capacity layout's, row for row."""
    sizes = [5, 0, 9, 3]
    tokens, off, w = _packed_case(sizes)
    o = np.asarray(off)
    cap = jnp.zeros((4 * 16, 24), jnp.float32)
    for g in range(4):
        cap = cap.at[16 * g:16 * g + sizes[g]].set(tokens[o[g]:o[g + 1]])
    packed = api.plan(api.GemmSpec.for_groups(api.GroupSpec(4, 24, packed=True), 24, 20),
                      backend="xla")
    capacity = api.plan(api.GemmSpec.for_groups(api.GroupSpec(4, 16), 24, 20), backend="xla")
    gp = jax.grad(lambda t, ww: jnp.sum(packed(t, off, ww) ** 2), argnums=(0, 1))(tokens, w)
    gc = jax.grad(lambda t, ww: jnp.sum(capacity(t, off, ww) ** 2), argnums=(0, 1))(cap, w)
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gc[1]), rtol=1e-5, atol=1e-4)
    for g in range(4):
        np.testing.assert_allclose(np.asarray(gp[0][o[g]:o[g + 1]]),
                                   np.asarray(gc[0][16 * g:16 * g + sizes[g]]),
                                   rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(gp[0])[o[-1]:])


def test_grouped_packed_is_refused_without_its_capability():
    spec = api.GemmSpec.for_groups(api.GroupSpec(4, 24, packed=True), 24, 20)
    with pytest.raises(api.CapabilityError, match="packed"):
        api.plan(spec, backend="pallas_mesh")
    assert api.GroupSpec(4, 24, packed=True).rows == 24
    assert api.GroupSpec(4, 24).rows == 96


# ---------------------------------------------------------------------------
# MoE on the grouped planner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "qwen2-moe-a2.7b"])
def test_moe_block_matches_onehot_reference_dropfree(arch):
    """At a drop-free (_EXACT_GROUP) shape the grouped-planner moe_block
    reproduces the dense one-hot dispatch — outputs and aux losses — to f32
    reduction-order precision (the computation graphs reduce in different
    orders, so agreement is ulp-level, not bitwise)."""
    # the single in-tree copy of the pre-refactor dense dispatch lives next
    # to the benchmark that times it
    from benchmarks.bench_moe import onehot_moe_reference

    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), moe_specs(cfg), cfg.pdtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), cfg.adtype)
    y_ref, aux_ref = onehot_moe_reference(params, x, cfg)
    y_new, aux_new = moe_block(params, x, cfg, NO_SHARD)
    np.testing.assert_allclose(
        np.asarray(y_new, np.float32), np.asarray(y_ref, np.float32),
        rtol=1e-6, atol=1e-8,
    )
    for key in aux_ref:
        np.testing.assert_allclose(
            float(aux_new[key]), float(aux_ref[key]), rtol=1e-5
        )


def test_moe_block_one_grouped_plan_per_expert_shape():
    """One grouped plan per logical expert shape (wi and wo), however many
    layers/calls run — the acceptance-criteria cache check."""
    cfg = get_config("olmoe-1b-7b").reduced()
    params = init_params(jax.random.PRNGKey(0), moe_specs(cfg), cfg.pdtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), cfg.adtype)
    for _ in range(3):  # repeated layers/steps reuse the same two plans
        moe_block(params, x, cfg, NO_SHARD)
    grouped = [p for p in api.plan_cache_info()["plans"] if p.get("grouped")]
    assert len(grouped) == 2  # wi: d -> 2f, wo: f -> d
    shapes = {p["mkn"] for p in grouped}
    assert len(shapes) == 2


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "qwen2-moe-a2.7b"])
@pytest.mark.parametrize("seq", [40, 160])
def test_moe_block_packed_layout_matches_the_capacity_layout(arch, seq):
    """Above 256 tokens XLA's packed buffer holds n * min(k, held) rows, at
    fewer its capacity layout held * n, as the mesh kernel's always does:
    the same pairs, the same outputs."""
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), moe_specs(cfg), cfg.pdtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, cfg.d_model), cfg.adtype)
    y_packed, aux_packed = moe_block(params, x, cfg, NO_SHARD)
    n = 2 * seq
    grouped = [p for p in api.plan_cache_info()["plans"] if p.get("grouped")]
    assert {p["grouped"]["packed"] for p in grouped} == {n > 256}
    rows = {int(p["mkn"].split("x")[0]) for p in grouped}
    bound = n * min(cfg.num_experts_per_tok, cfg.experts_held) if n > 256 else n * cfg.experts_held
    assert rows == {bound}
    mesh_cfg = dataclasses.replace(cfg, use_mesh_kernel=True)
    y_cap, aux_cap = moe_block(params, x, mesh_cfg, NO_SHARD)
    np.testing.assert_allclose(np.asarray(y_packed), np.asarray(y_cap), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(aux_packed["held_pairs"]),
                                  np.asarray(aux_cap["held_pairs"]))


def test_moe_block_grouped_trains():
    """Gradients flow through sort/scatter/grouped-plan/gather end to end,
    on the Pallas backend too."""
    cfg = dataclasses.replace(
        get_config("olmoe-1b-7b").reduced(), use_mesh_kernel=True
    )
    params = init_params(jax.random.PRNGKey(0), moe_specs(cfg), cfg.pdtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), cfg.adtype)

    def loss(pp):
        y, aux = moe_block(pp, x, cfg, NO_SHARD)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux["lb_loss"]

    grads = jax.grad(loss)(params)
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    assert float(jnp.max(jnp.abs(grads["wi"]))) > 0
    assert float(jnp.max(jnp.abs(grads["wo"]))) > 0
