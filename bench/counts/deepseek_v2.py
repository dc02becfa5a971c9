"""Work counts of DeepSeek-V2's decoder as one chip serves it: latent
attention, leading dense layers, and the chip's held share of each MoE
layer's routed experts (configuration keys as in config.json;
`n_routed_experts` is the number held here, `published` the router's).

Attention is counted in the form each phase runs: prefill the published
form (q.k over qk_nope + qk_rope, p.v over v_head_dim, per head), decode
the absorbed form (q_abs.[c_kv | k_pe] over kv_lora_rank + qk_rope and p.c
over kv_lora_rank, per head).  Applying W_UK to the query and W_UV to the
attended latent costs what applying wkv_b to one latent row does, so the
weight GEMMs count alike in both.  A token's routed experts are counted as
the expected share that lands on held experts, num_experts_per_tok x held /
published; weights are read whole, every held expert each decode tick (at
32 slots each is hit with probability 1 - (58/64)^32 = 0.96).  The router
is float32.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

BF16, F32 = 2, 4

__all__ = [
    "attn_ops_per_key",
    "decode_tick_work",
    "gemm_shapes",
    "kv_bytes_per_token",
    "matmul_params",
    "prefill_ops",
    "weight_bytes",
]


def _layers(cfg: Dict) -> Tuple[int, int]:
    """(dense layers, MoE layers)."""
    k = cfg["first_k_dense_replace"]
    return k, cfg["num_hidden_layers"] - k


def gemm_shapes(cfg: Dict) -> List[Tuple[str, int, int, int]]:
    """(name, K, N, count) of every weight GEMM: per layer wq, wkv_a, wkv_b
    and wo; the dense layers' fused gate+up and down; per MoE layer the
    router, each held expert's gate+up and down, the shared experts'; the
    head.  `count` is how many times a model holds that shape."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    held = cfg["n_routed_experts"]
    dense, moe = _layers(cfg)
    layers = dense + moe
    return [
        ("wq", d, h * (nope + rope), layers),
        ("wkv_a", d, r + rope, layers),
        ("wkv_b", r, h * (nope + dv), layers),
        ("wo", h * dv, d, layers),
        ("wi", d, 2 * ff, dense),
        ("w_down", ff, d, dense),
        ("router", d, cfg["published"]["n_routed_experts"], moe),
        ("expert_wi", d, 2 * fe, moe * held),
        ("expert_wo", fe, d, moe * held),
        ("shared_wi", d, 2 * fs, moe),
        ("shared_wo", fs, d, moe),
        ("head", d, cfg["vocab_size"], 1),
    ]


def matmul_params(cfg: Dict) -> int:
    """Parameters that take part in a matrix product (every held expert,
    the head; the embedding lookup not)."""
    return sum(k * n * c for _, k, n, c in gemm_shapes(cfg))


def _norm_params(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] * (2 * cfg["hidden_size"] + cfg["kv_lora_rank"]) + cfg["hidden_size"]


def weight_bytes(cfg: Dict) -> int:
    """Bytes of weights a forward pass reads: the matmul weights (bf16, the
    router float32, every held expert) and the norms."""
    shapes = {name: k * n * c for name, k, n, c in gemm_shapes(cfg)}
    router = shapes.pop("router")
    return BF16 * (sum(shapes.values()) + _norm_params(cfg)) + F32 * router


def _active_params(cfg: Dict) -> float:
    """Matmul parameters one token passes through: its routed experts at
    the share that lands on held experts."""
    experts = sum(k * n * c for name, k, n, c in gemm_shapes(cfg) if name.startswith("expert_"))
    share = cfg["num_experts_per_tok"] / cfg["published"]["n_routed_experts"]
    return matmul_params(cfg) - experts + experts * share


def attn_ops_per_key(cfg: Dict) -> int:
    """Operations per (query, key) pair summed over layers in the published
    form: q.k over qk_nope + qk_rope and p.v over v_head_dim, per head."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"])


def _absorbed_ops_per_key(cfg: Dict) -> int:
    """The absorbed form's: q_abs.[c_kv | k_pe] and p.c_kv, per head."""
    r = cfg["kv_lora_rank"]
    return 2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * (2 * r + cfg["qk_rope_head_dim"])


def kv_bytes_per_token(cfg: Dict) -> int:
    """Bytes of the cached latent row ([c_kv | k_pe]) one token holds, over
    all layers."""
    return cfg["num_hidden_layers"] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16


def prefill_ops(cfg: Dict, prompt_len: int) -> float:
    """Forward operations of a batch-1 prefill: every position through the
    weight GEMMs (logits included) plus causal attention, published form."""
    pairs = prompt_len * (prompt_len + 1) // 2
    return 2.0 * _active_params(cfg) * prompt_len + attn_ops_per_key(cfg) * pairs


def decode_tick_work(cfg: Dict, contexts: Iterable[int]) -> Dict[str, float]:
    """One decode tick over the active slots, each given the number of keys
    it attends over: the operations (absorbed attention), the bytes that
    must move (every weight once, each slot's live latent rows) and the
    latent bytes alone."""
    ctx = list(contexts)
    keys = sum(ctx)
    ops = 2.0 * _active_params(cfg) * len(ctx) + _absorbed_ops_per_key(cfg) * keys
    kv = float(kv_bytes_per_token(cfg) * keys)
    return {"ops": ops, "bytes": weight_bytes(cfg) + kv, "kv_bytes": kv, "slots": len(ctx)}
