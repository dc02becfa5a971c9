"""Operations and bytes of the algorithm, from the model's shapes alone.

These count what the mathematics needs, not what an implementation pads or
recomputes, so a kernel's roofline share reads the same work whatever
implements it.  `cfg` is a configuration file's dict (configs/<name>.json).

Conventions:
  * A matrix product of (m, k) by (k, n) is 2·m·k·n operations.
  * Training counts forward + backward as 3× the forward (6 per matmul
    parameter per token) and attention at full sequence length, 12·L·T·H·hd
    per token: the PaLM convention (Chowdhery et al. 2022, appendix B).
    Recomputation under remat does not count.
  * Serving counts causal attention: a token at position p attends over p+1
    keys, 4·H·hd operations per key per layer.
  * Bytes are bf16 (2 bytes an element) unless a dtype is given.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

__all__ = [
    "attn_ops_per_key",
    "decode_tick_work",
    "gemm_shapes",
    "gemm_time_bound",
    "kv_bytes_per_token",
    "matmul_params",
    "prefill_ops",
    "train_step_ops",
    "weight_bytes",
]

BF16 = 2


def _dims(cfg: Dict) -> Tuple[int, int, int, int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return cfg["num_hidden_layers"], d, h, kv, hd, cfg["intermediate_size"], cfg["vocab_size"]


def gemm_shapes(cfg: Dict) -> List[Tuple[str, int, int, int]]:
    """(name, K, N, count) of every weight GEMM one token passes through:
    per layer q, k, v, o, the fused gate+up and the down projection, then
    the head.  `count` is how many layers run that shape."""
    L, d, h, kv, hd, ff, v = _dims(cfg)
    return [
        ("wq", d, h * hd, L),
        ("wk", d, kv * hd, L),
        ("wv", d, kv * hd, L),
        ("wo", h * hd, d, L),
        ("wi", d, 2 * ff, L),
        ("w_down", ff, d, L),
        ("head", d, v, 1),
    ]


def matmul_params(cfg: Dict) -> int:
    """Parameters that take part in a matrix product (head included, the
    embedding lookup not)."""
    return sum(k * n * c for _, k, n, c in gemm_shapes(cfg))


def weight_bytes(cfg: Dict) -> int:
    """Bytes of weights a forward pass reads: the matmul weights and the
    norms (bf16).  A tied head reads the embedding once as the head."""
    L, d, *_ = _dims(cfg)
    return BF16 * (matmul_params(cfg) + 2 * L * d + d)


def attn_ops_per_key(cfg: Dict) -> int:
    """Operations per (query, key) pair summed over layers: q·k and p·v."""
    L, d, h, kv, hd, ff, v = _dims(cfg)
    return 4 * L * h * hd


def kv_bytes_per_token(cfg: Dict) -> int:
    """Bytes of cached K and V that one token holds, over all layers."""
    L, d, h, kv, hd, ff, v = _dims(cfg)
    return 2 * L * kv * hd * BF16


def train_step_ops(cfg: Dict, batch: int, seq: int) -> Dict[str, float]:
    """Model operations of one training step (forward + backward)."""
    tokens = batch * seq
    gemm = 6.0 * matmul_params(cfg) * tokens
    attn = 3.0 * attn_ops_per_key(cfg) * seq * tokens
    return {"gemm": gemm, "attention": attn, "total": gemm + attn}


def prefill_ops(cfg: Dict, prompt_len: int) -> float:
    """Forward operations of a batch-1 prefill: every position through the
    weight GEMMs (logits included) plus causal attention."""
    pairs = prompt_len * (prompt_len + 1) // 2
    return 2.0 * matmul_params(cfg) * prompt_len + attn_ops_per_key(cfg) * pairs


def decode_tick_work(cfg: Dict, contexts: Iterable[int]) -> Dict[str, float]:
    """One decode tick over the active slots, each given the number of keys
    it attends over (its length including the new token).  Returns the
    operations, the bytes that must move (weights once, each slot's live
    K/V), and the K/V bytes alone."""
    ctx = list(contexts)
    s = len(ctx)
    keys = sum(ctx)
    ops = 2.0 * matmul_params(cfg) * s + attn_ops_per_key(cfg) * keys
    kv = float(kv_bytes_per_token(cfg) * keys)
    return {"ops": ops, "bytes": weight_bytes(cfg) + kv, "kv_bytes": kv, "slots": s}


def gemm_time_bound(m: int, k: int, n: int, flops_per_s: float, bytes_per_s: float) -> float:
    """Least seconds a bf16 (m,k)x(k,n) product can take on the chip: the
    larger of its operations over peak and its operand and result bytes over
    HBM bandwidth."""
    ops = 2.0 * m * k * n
    moved = BF16 * (m * k + k * n + m * n)
    return max(ops / flops_per_s, moved / bytes_per_s)
