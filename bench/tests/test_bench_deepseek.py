"""DeepSeek-V2-Lite: the program against the plain float32 reference at a
small size on the CPU (prefill logits, prefill then absorbed paged decode
through the scheduler, one chip's share of the experts against the whole
layer, drop-free routing), the cell's files and counts, and the routing
counts the scheduler records.

Tolerances: both sides compute in float32.  The program differs from the
reference in the order of its sums (its own GEMMs, the absorbed form's
W_UK and W_UV applied to the query and the attended latent where the
reference expands K and V, RoPE rotated in place where the reference first
gathers each vector's pairs into halves, a sort-and-scatter of the routed
pairs), so logits agree to a few float32 ulps of their scale: 1e-5 of it,
about 100 ulps, leaves room for three layers of such reorderings; the same
1e-5 holds for one MoE layer's output.  A served token is the program's
argmax; its reference logit must lie within 1e-4 standard deviations of
the position's logits below the reference's best (a few hundred ulps).
Products rounded to float8 (the control) move the logits by a hundredth of
their scale or more, far past 1e-5."""

import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness
from bench.harness import BENCH
from bench.references import deepseek_v2 as ref
from bench.weights import make_params

CELL = "deepseek-v2-lite.decode-long"
TOL = 1e-5


def _small(**rope):
    """The cell's configuration at CPU widths: 3 layers (the dense one and
    two MoE layers), 16 routed experts of which 4 are held, 3 a token, 2
    shared; YaRN stretching 64 positions by 4, so that its ramp falls
    inside the 4 rotated pairs."""
    cfg = copy.deepcopy(harness.find_cell(CELL).config)
    rope_scaling = dict(cfg["rope_scaling"], factor=4, original_max_position_embeddings=64, **rope)
    cfg.update(
        num_hidden_layers=3, hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4,
        num_experts_per_tok=3, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, vocab_size=256, rope_scaling=rope_scaling, torch_dtype="float32",
        paged_impl="xla_gather", published={"n_routed_experts": 16},
    )
    cfg["program"] = dict(
        cfg["program"], num_experts=16, num_experts_held=4, num_experts_per_tok=3, moe_d_ff=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling_factor=4.0, rope_original_max_position=64,
        rope_mscale=float(rope_scaling["mscale"]),
        rope_mscale_all_dim=float(rope_scaling["mscale_all_dim"]),
    )
    return cfg


# The published mscales are equal, so YaRN's cos/sin factor is 1; the
# second case makes it 1.10 (and the softmax's factor 1), and so checks
# that both sides apply each.
ROPES = {"published": {}, "cos_sin_factor": {"mscale_all_dim": 0.0, "mscale": 0.707}}


def _setup(rope, seed=2**32 + 19):
    from repro.models import get_model

    cfg = _small(**ROPES[rope])
    model = get_model(harness.arch_config(cfg))
    params = make_params(model.abstract_params(), cfg, seed=seed)
    return cfg, model, params


@pytest.mark.parametrize("rope", sorted(ROPES))
def test_prefill_logits_match_the_reference(rope):
    cfg, model, params = _setup(rope)
    tok = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, 40)), jnp.int32)
    got, _, routed = jax.jit(lambda p, t: model.prefill_routed(p, {"tokens": t, "labels": t}))(params, tok)
    want = ref.forward_logits(params, tok, cfg)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= TOL * scale
    # Two MoE layers route 40 tokens x 3 experts each; about a quarter of
    # the pairs land on the 4 held experts of 16.
    assert routed["held_pairs"].shape == (1,)
    assert 0 < int(routed["held_pairs"][0]) < 2 * 40 * 3
    low = ref.forward_logits(params, tok, cfg, numerics="fp8")
    assert float(jnp.max(jnp.abs(low - want))) > TOL * scale


@pytest.mark.parametrize("impl", ["xla_gather", "pallas_paged"])
def test_absorbed_paged_decode_through_the_scheduler_matches(impl):
    cfg, model, params = _setup("published")
    from repro.launch.scheduler import ContinuousBatchingServer, Request, ServeConfig

    server = ContinuousBatchingServer(model, params, ServeConfig(
        max_slots=3, page_size=8, num_pages=1 + 3 * 6, max_pages_per_seq=6, queue_capacity=8,
        impl=impl, interpret=impl == "pallas_paged"))
    rng = np.random.default_rng(3)
    prompts = {f"q{i}": rng.integers(0, 256, n).astype(np.int32)
               for i, n in enumerate((5, 12, 17, 9))}
    for rid, p in prompts.items():
        server.submit(Request(rid=rid, prompt=p, max_new_tokens=20))
    # Each tick's logits, from the server's own pools and inputs, against
    # the reference's full forward pass over the slot's sequence so far.
    step = jax.jit(lambda p, t, pools, bt, pos: model.paged_decode(
        p, t, pools, bt, pos, impl=impl, interpret=impl == "pallas_paged")[0])
    checked = 0
    while server.pending:
        ready, tokens, positions, tables = server.decode_inputs()
        if ready and checked < 6:
            logits = step(params, jnp.asarray(tokens), server.pools, jnp.asarray(tables),
                          jnp.asarray(positions))
            for seq in ready:
                seq_tokens = np.concatenate([seq.req.prompt, seq.tokens])[None]
                want = ref.forward_logits(params, jnp.asarray(seq_tokens), cfg)[0, -1]
                err = float(jnp.max(jnp.abs(logits[seq.slot, 0] - want)))
                assert err <= TOL * float(jnp.max(jnp.abs(want))), (seq.req.rid, len(seq.tokens))
            checked += 1
        server.step()
    assert checked == 6
    for rid, p in prompts.items():
        served = server.results[rid].tokens
        assert len(served) == 20
        g = ref.served_gaps(params, p, served, cfg, pad_to=16)
        assert g["served_gap"] <= 1e-4, rid


def test_eight_shares_of_the_experts_add_up_to_the_whole_layer():
    """At 8 shares of 8 of 64 experts (6 a token), each share's routed part,
    and the shared experts once, sum to the reference's uncut layer.  A
    layer holds the router's first 8 experts, so share s sees the router's
    columns rolled by -8s; softmax and top-k do not depend on the order."""
    from repro.configs import get_config
    from repro.models.layers import NO_SHARD, init_params
    from repro.models.moe import moe_block, moe_specs

    base = dataclasses.replace(
        get_config("deepseek-v2-lite"), d_model=64, moe_d_ff=32, num_layers=3,
        param_dtype="float32", activation_dtype="float32",
    )
    whole = init_params(jax.random.PRNGKey(0), moe_specs(base), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64), jnp.float32)
    total, held = 0.0, 0
    for share in range(8):
        cfg = dataclasses.replace(base, num_experts_held=8,
                                  num_shared_experts=2 if share == 0 else 0)
        mine = slice(8 * share, 8 * share + 8)
        p = {"router": jnp.roll(whole["router"], -8 * share, axis=1),
             "wi": whole["wi"][mine], "wo": whole["wo"][mine]}
        if share == 0:  # the shared experts, which every share computes alike
            p.update(shared_wi=whole["shared_wi"], shared_wo=whole["shared_wo"])
        y, aux = moe_block(p, x, cfg, NO_SHARD)
        total, held = total + y, held + int(jnp.sum(aux["held_pairs"]))
    rcfg = {"num_experts_per_tok": 6, "norm_topk_prob": False, "routed_scaling_factor": 1}
    want = ref._experts(x, whole, rcfg, "f32")
    assert held == 2 * 24 * 6  # every pair is held by exactly one share
    assert float(jnp.max(jnp.abs(total - want))) <= TOL * float(jnp.max(jnp.abs(want)))


def test_routing_is_drop_free_past_256_tokens_on_one_expert():
    """300 tokens that all pick expert 3 first (and, the other scores tied,
    expert 0 second): every pair is computed, as the reference computes
    each expert for every token."""
    from repro.configs import get_config
    from repro.models.layers import NO_SHARD, init_params
    from repro.models.moe import moe_block, moe_specs

    cfg = dataclasses.replace(
        get_config("deepseek-v2-lite").reduced(), num_experts_per_tok=2, num_experts_held=0,
    )
    p = init_params(jax.random.PRNGKey(0), moe_specs(cfg), jnp.float32)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 3].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 300, cfg.d_model), jnp.float32))
    y, aux = moe_block(p, x, cfg, NO_SHARD)
    assert int(jnp.sum(aux["held_pairs"])) == 300 * 2
    rcfg = {"num_experts_per_tok": 2, "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": 1}
    want = ref._experts(x, p, rcfg, "f32")
    assert float(jnp.max(jnp.abs(y - want))) <= TOL * float(jnp.max(jnp.abs(want)))


def test_yarn_frequencies_and_scale_at_the_published_sizes():
    """The ramp runs between pairs floor(10.47) = 10 and ceil(22.51) = 23 of
    32; the softmax scale is 192^-0.5 m^2, m = 0.1 x 0.707 x ln 40 + 1."""
    from repro.models.attention import mla_scale
    from repro.models.layers import rope_inv_freq

    cfg = harness.find_cell(CELL).config
    arch = harness.arch_config(cfg)
    inv = rope_inv_freq(arch, 64)
    want = ref.yarn_inv_freq(cfg)
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    extra = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-12)
    assert extra[11] / 40 < inv[11] < extra[11]
    m = 0.1 * 0.707 * np.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert mla_scale(arch) == pytest.approx(192**-0.5 * m * m, rel=1e-12)


def test_the_cell_and_its_configuration_load():
    cell = harness.find_cell(CELL)
    assert cell.chips == 1 and cell.mix["kind"] == "serve_backlog"
    assert (cell.mix["slots"], cell.mix["page_size"], cell.mix["max_pages_per_seq"]) == (32, 16, 224)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    raw = json.loads((BENCH / "configs" / "deepseek-v2-lite.json").read_text())
    assert raw["reduced"] == ["n_routed_experts"] and raw["published"] == {"n_routed_experts": 64}
    arch = harness.arch_config(cell.config)
    assert (arch.num_experts, arch.experts_held, arch.num_experts_per_tok) == (64, 8, 6)
    assert (arch.first_k_dense, arch.norm_topk_prob, arch.shared_expert_gate) == (1, False, False)
    assert (arch.d_ff, arch.moe_d_ff, arch.norm_eps) == (10944, 1408, 1e-6)


def test_the_program_holds_one_chips_share_at_published_widths():
    """3,110,989,312 parameters: layer 0 (81.0 M), 26 MoE layers of 100.4 M
    (8 of 64 experts), the embedding and the head (419.4 M); a latent pool
    of one row a token per layer."""
    from repro.models import get_model, transformer

    arch = harness.arch_config(harness.find_cell(CELL).config)
    abstract = get_model(arch).abstract_params()
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abstract)) == 3_110_989_312
    assert abstract["blocks"]["moe"]["router"].shape == (26, 2048, 64)
    assert abstract["blocks"]["moe"]["wi"].shape == (26, 8, 2048, 2816)
    pools = transformer.paged_pool_specs(arch, 7169, 16)
    assert {k: v.shape for k, v in pools.items()} == {
        "dense_latent": (1, 7169, 1, 16, 640), "latent": (26, 7169, 1, 16, 640)}


GOLDEN = {
    "matmul_params": 2_901_147_648,
    "weight_bytes": 5_809_363_968,
    "kv_bytes_per_token": 31_104,
    "attn_ops_per_key": 276_480,
    "prefill_ops": 9110855614464.0,
    "decode_tick_work": {"ops": 13401262080.0, "bytes": 6000560256.0,
                         "kv_bytes": 191196288.0, "slots": 3},
}
CALLS = {
    "matmul_params": flops.matmul_params,
    "weight_bytes": flops.weight_bytes,
    "kv_bytes_per_token": flops.kv_bytes_per_token,
    "attn_ops_per_key": flops.attn_ops_per_key,
    "prefill_ops": lambda cfg: flops.prefill_ops(cfg, 3072),
    "decode_tick_work": lambda cfg: flops.decode_tick_work(cfg, [1025, 2049, 3073]),
}


@pytest.mark.parametrize("count", sorted(GOLDEN))
def test_counts_are_pinned(count):
    """27 layers x 576 latent values x 2 bytes = 31,104 bytes a token (dense
    MHA counting would say 221,184); the matmul weights are the parameters
    less the embedding and the norms."""
    cfg = harness.find_cell(CELL).config
    assert CALLS[count](cfg) == GOLDEN[count]


@pytest.mark.parametrize("router", ["seeded", "all_held"])
def test_routing_counts_are_recorded_on_the_spans_and_the_counter(router):
    """`all_held`: a zero router ties every score, top-k takes experts 0-2,
    all held, so each span counts exactly 2 layers x 3 pairs of each token
    it computed for a sequence, and none for an idle slot."""
    from repro.launch.scheduler import ContinuousBatchingServer, Request, ServeConfig
    from repro.obs import metrics
    from repro.obs import trace as obs

    cfg, model, params = _setup("published")
    if router == "all_held":
        moe = dict(params["blocks"]["moe"], router=jnp.zeros_like(params["blocks"]["moe"]["router"]))
        params = dict(params, blocks=dict(params["blocks"], moe=moe))
    counter = metrics.counter("moe_routed_pairs_total", labels=("share",))
    before = {s: counter.value(share=s) for s in ("held", "elsewhere")}
    server = ContinuousBatchingServer(model, params, ServeConfig(
        max_slots=2, page_size=8, num_pages=13, max_pages_per_seq=6, queue_capacity=4))
    rng = np.random.default_rng(5)
    with obs.tracing():
        obs.clear()
        for i, n in enumerate((7, 11)):
            server.submit(Request(rid=f"r{i}", prompt=rng.integers(0, 256, n).astype(np.int32),
                                  max_new_tokens=4 + 3 * i))
        server.drain()
        decode, prefill = obs.spans("serve.decode"), obs.spans("serve.prefill")
    held = sum(s.attrs["held_pairs"] for s in decode + prefill)
    assert decode and all(0 <= s.attrs["held_pairs"] <= 2 * 3 * s.attrs["slots"] for s in decode)
    assert counter.value(share="held") - before["held"] == held
    # 2 MoE layers x 3 pairs a token: a prefill routes its prompt, a decode
    # step the slots that hold a sequence (one of the two, once the other
    # has finished), not the idle ones.
    slots = [s.attrs["slots"] for s in decode]
    assert 1 in slots
    routed = 2 * 3 * (7 + 11 + sum(slots))
    moved = sum(counter.value(share=s) - before[s] for s in before)
    assert moved == routed
    if router == "all_held":
        assert held == routed
        assert all(s.attrs["held_pairs"] == 2 * 3 * s.attrs["slots"] for s in decode)


def test_the_pairs_reader_divides_by_ticks_experts_and_layers():
    from types import SimpleNamespace

    reader = harness.load_reader("layer_metrics", "moe_pairs_per_expert.decode-long")
    cell = harness.find_cell(CELL)
    span = lambda t, n: SimpleNamespace(name="serve.decode", t0=t, t1=t + 0.01,  # noqa: E731
                                        attrs={"held_pairs": n})
    run = harness.RunRecord(cell=cell, peaks=None, window_s=1.0, setup_s=1.0, trace=object(),
                            spans=[span(0.1, 624), span(0.2, 600), span(5.0, 999)],
                            data={"window_t0": 0.0, "window_t1": 1.0})
    assert reader(run) == pytest.approx((624 + 600) / (2 * 8 * 26))
    run.spans = [SimpleNamespace(name=s.name, t0=s.t0, t1=s.t1, attrs={}) for s in run.spans]
    assert reader(run) is None  # a program without routing counts


def _small_cell():
    """The cell at the widths of `_small`, with the small serving mix of
    the other cells' rehearsals (bench/tests/small.py)."""
    from bench.tests.small import small_cell

    cell = small_cell(CELL)
    cell.config = _small()
    return cell


def _correct(out):
    return not out["problems"] and all(v["ok"] for v in out["compared"].values())


def test_a_sound_run_of_the_cell_is_correct_and_an_altered_token_is_not():
    import time

    from bench import serve_cell

    for fault, want in (("", True), ("token", False)):
        out = serve_cell.run(_small_cell(), 2**33 + 404, 1.0, False, jax.devices()[:1],
                             time.monotonic(), fault=fault)
        assert _correct(out) is want, out["compared"]
        assert out["attempted"] > 0 and out["failed"] == 0


def test_a_traced_run_of_the_cell_reads_the_routing_counts(tmp_path, monkeypatch):
    """On the CPU the device metrics read nothing; the held pairs a tick
    come from the program's spans."""
    import time

    from bench import serve_cell, tracing
    from repro.obs import trace as obs

    class InTmp(tracing.Tracing):  # the profile goes to the test's directory
        def __init__(self, enabled, cell):
            super().__init__(enabled, cell, out=tmp_path)

    monkeypatch.setattr(tracing, "Tracing", InTmp)
    try:
        out = serve_cell.run(_small_cell(), 2**33 + 405, 1.0, True, jax.devices()[:1],
                             time.monotonic())
    finally:
        obs.disable()
        obs.clear()
    run = out["run"]
    from bench.peaks import peaks_for

    run.peaks = peaks_for("TPU v5 lite")  # as run.py sets them on the chip
    got = harness.read_metrics(run.cell.per_layer, "layer_metrics", run)
    assert "step_mfu.decode-long" in got
    # 4 slots x 3 pairs a token over 16 experts: 0.75 pairs a held expert a
    # tick when every slot decodes.
    assert 0 < got["moe_pairs_per_expert.decode-long"]["value"] <= 4 * 3 / 4 + 1e-9
    assert "paged_attn_roofline.decode-long" not in got  # no device ops on the CPU
