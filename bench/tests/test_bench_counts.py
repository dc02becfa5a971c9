"""The work counts and the peaks table against hand-worked numbers."""

import json

import pytest

from bench import flops
from bench.harness import BENCH
from bench.peaks import PEAKS, peaks_for


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_mesh_paper_training_step_ops():
    # 4 layers x (4 x 2048^2 attention + 3 x 2048 x 8192 MLP) + 2048 x 32768
    # head = 335,544,320 matmul parameters; 6 per token at 4 x 2048 tokens is
    # 16.49 TFLOP, and attention 12 x 4 x 2048 x 2048 per token is 1.65 TFLOP.
    cfg = _config("mesh-paper")
    assert flops.matmul_params(cfg) == 335_544_320
    ops = flops.train_step_ops(cfg, batch=4, seq=2048)
    assert ops["gemm"] == pytest.approx(16.4927e12, rel=1e-4)
    assert ops["attention"] == pytest.approx(1.6493e12, rel=1e-4)
    assert ops["total"] == pytest.approx(18.1419e12, rel=1e-4)


def test_granite_stage_sizes():
    # 10 layers x (4096 x (4096 + 2 x 1024) + 4096^2 + 3 x 4096 x 12800)
    # + 4096 x 49155 tied head = 2.19 B parameters, 4.39 GB of bf16 weights.
    cfg = _config("granite-3-8b")
    assert flops.matmul_params(cfg) == 2_193_633_280
    assert flops.weight_bytes(cfg) == 2 * (2_193_633_280 + 2 * 10 * 4096 + 4096)
    # K and V of 8 heads of 128 over 10 layers: 40 KiB a token.
    assert flops.kv_bytes_per_token(cfg) == 2 * 10 * 8 * 128 * 2
    # A 2,048-token prefill: 2 x 2.19e9 x 2048 + 4 x 10 x 32 x 128 x 2048 x 2049 / 2.
    assert flops.prefill_ops(cfg, 2048) == pytest.approx(9.3289e12, rel=1e-4)


def test_decode_tick_work_counts_each_slot_context():
    cfg = _config("mesh-paper")
    w = flops.decode_tick_work(cfg, [100, 300])
    assert w["slots"] == 2
    assert w["kv_bytes"] == flops.kv_bytes_per_token(cfg) * 400
    assert w["ops"] == 2 * flops.matmul_params(cfg) * 2 + flops.attn_ops_per_key(cfg) * 400
    assert w["bytes"] == flops.weight_bytes(cfg) + w["kv_bytes"]


def test_gemm_time_bound_takes_the_larger_bound():
    peak, bw = 197e12, 819e9
    # 4096^3: 137 GFLOP, 0.70 ms of compute against 0.12 ms of bytes.
    assert flops.gemm_time_bound(4096, 4096, 4096, peak, bw) == pytest.approx(2 * 4096**3 / peak)
    # One row against a 4096 x 4096 weight: memory bound, 33.6 MB in 41 us.
    one = flops.gemm_time_bound(1, 4096, 4096, peak, bw)
    assert one == pytest.approx(2 * (4096 + 4096 * 4096 + 4096) / bw)


def test_v5e_peaks_and_unknown_kind():
    p = peaks_for("TPU v5 lite")
    assert p.bf16_flops_per_s == 197e12
    assert p.hbm_bytes_per_s == 819e9
    assert p.hbm_bytes == 16 * 2**30
    assert set(PEAKS) >= {"TPU v5 lite"}
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v4")
