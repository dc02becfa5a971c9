"""Architecture config system.

One frozen dataclass covers every registered architecture (dense / MoE /
SSM / hybrid / enc-dec audio / VLM; latent attention and leading dense
layers for DeepSeek-V2).  Every published config file under
`repro/configs/` instantiates `ArchConfig` with the exact paper/HF numbers and
registers it; `reduced()` derives the CPU-smoke variant used by per-arch tests
(same family and code paths, tiny dims).

Shapes are separate (`ShapeSpec`): the four assigned input-shape cells plus
smoke shapes.  `launch/dryrun.py` iterates CONFIGS x SHAPES.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

__all__ = ["ArchConfig", "ShapeSpec", "SHAPES", "register", "get_config", "CONFIGS"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    # identity
    arch_id: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    source: str  # citation tag from the assignment table

    # transformer backbone
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5

    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0  # expert hidden dim (d_ff above = dense fallback/shared)
    router_aux_coef: float = 0.01
    first_k_dense: int = 0  # leading layers with a dense SwiGLU of width d_ff
    norm_topk_prob: bool = True  # renormalise the top-k routing weights
    shared_expert_gate: bool = True  # sigmoid gate on the shared experts
    # Expert parallelism's share of a layer: this chip holds the first
    # num_experts_held of the router's num_experts and computes only their
    # part (0 = all held).
    num_experts_held: int = 0

    # multi-head latent attention (DeepSeek-V2): K and V come from one
    # kv_lora_rank-wide latent plus a qk_rope_head_dim-wide shared RoPE key
    kv_lora_rank: int = 0  # > 0 selects MLA
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN RoPE scaling (rope_scaling type "yarn"; factor 0 = plain RoPE)
    rope_scaling_factor: float = 0.0
    rope_original_max_position: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # SSM / RWKV / hybrid
    ssm_state_size: int = 0
    ssm_conv_dim: int = 4
    ssm_num_heads: int = 0  # mamba2 heads (d_inner / head_p)
    ssm_expand: int = 2
    shared_attn_period: int = 0  # zamba2: shared attn block after every k SSM layers

    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    enc_layers: int = 0
    dec_layers: int = 0
    dec_ratio: int = 8  # decoder len = enc len // dec_ratio for assigned shapes

    # VLM (pixtral)
    num_stub_patches: int = 0  # stub ViT frontend: precomputed patch embeddings

    # capability flags (drive which shape cells lower — DESIGN.md §5/§6)
    supports_long_context: bool = False  # sub-quadratic path for long_500k
    has_decode: bool = True

    # numerics / schedule levers (hillclimb knobs)
    param_dtype: str = "bfloat16"
    activation_dtype: str = "bfloat16"
    remat_policy: str = "dots"  # none | dots | full
    use_mesh_kernel: bool = False  # route GEMMs through the Pallas mesh kernel
    mesh_block_m: int = 0  # kernel block shape overrides; 0 = resolve via the
    mesh_block_n: int = 0  # persistent autotune cache (kernels/autotune.py,
    mesh_block_k: int = 0  # DESIGN.md §3)
    fused_dense_epilogue: bool = True  # bias+activation+residual inside the
    # kernel's final-k flush (DESIGN.md §3); False = separate XLA ops (A/B lever)
    scramble_privacy: bool = False  # apply S to activations (scrambling system)
    scan_unroll: bool = False  # unroll layer scans (cost-probe lowering only:
    # XLA cost_analysis counts a while body ONCE, so roofline probes lower
    # reduced-depth UNROLLED variants and fit the per-layer slope — launch/dryrun.py)
    attn_chunk: int = 0  # >0: flash-style chunked attention (KV-chunk online
    # softmax) for train/prefill — kills the O(S^2) score materialization
    vocab_pad_multiple: int = 0  # pad embedding/lm_head rows so the vocab dim
    # divides the TP axis (padded logits are masked out of loss/argmax)
    wkv_chunked: bool = False  # rwkv6: chunk-parallel GEMM-form WKV (exact)
    # instead of the faithful per-token scan — see models/rwkv._wkv_chunked
    wkv_chunk: int = 16
    grad_accum: int = 1  # microbatch gradient accumulation (train_step scan)
    # — bounds activation/remat residency per pass; used to FIT large cells

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def adtype(self):
        return jnp.dtype(self.activation_dtype)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def experts_held(self) -> int:
        return self.num_experts_held or self.num_experts

    def n_params_dense_blocks(self) -> int:
        """Rough parameter count (reported in DESIGN/EXPERIMENTS tables)."""
        d, L = self.d_model, self.num_layers
        hd = self.head_dim_
        attn = d * hd * (self.num_heads + 2 * self.num_kv_heads) + self.num_heads * hd * d
        if self.is_moe:
            ff = 3 * d * self.moe_d_ff * self.num_experts
            ff += 3 * d * self.d_ff * self.num_shared_experts
        else:
            ff = 3 * d * self.d_ff
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return L * (attn + ff) + emb

    def n_active_params(self) -> int:
        """Active-per-token params (MoE: only routed top-k + shared)."""
        if not self.is_moe:
            return self.n_params_dense_blocks()
        d, L = self.d_model, self.num_layers
        hd = self.head_dim_
        attn = d * hd * (self.num_heads + 2 * self.num_kv_heads) + self.num_heads * hd * d
        ff = 3 * d * self.moe_d_ff * self.num_experts_per_tok
        ff += 3 * d * self.d_ff * self.num_shared_experts
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return L * (attn + ff) + emb

    def tuned(self, tp: int = 16) -> "ArchConfig":
        """Beyond-paper production tuning (EXPERIMENTS.md §Perf, applied
        across the board): flash-style chunked attention, vocab padding when
        the vocab doesn't divide TP, chunk-parallel WKV for rwkv.  Sharding
        rule upgrades (FSDP/SP/seq_attn) live in launch/dryrun._rules_for."""
        kw: dict = {}
        if self.family != "ssm":  # every attention-bearing family
            kw["attn_chunk"] = 1024
        if self.vocab_size % tp:
            kw["vocab_pad_multiple"] = 256
        if self.family == "ssm":
            kw["wkv_chunked"] = True
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ArchConfig":
        """CPU-smoke variant: same family/code paths, tiny dims."""
        kv = max(1, min(self.num_kv_heads, 2))
        heads = max(kv, min(self.num_heads, 4))
        return dataclasses.replace(
            self,
            num_layers=min(self.num_layers, 2),
            d_model=64,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=16,
            d_ff=128,
            vocab_size=256,
            num_experts=min(self.num_experts, 8) if self.is_moe else 0,
            num_experts_per_tok=min(self.num_experts_per_tok, 2) if self.is_moe else 0,
            num_shared_experts=min(self.num_shared_experts, 1),
            moe_d_ff=64 if self.is_moe else 0,
            num_experts_held=(
                min(8, max(2, 8 * self.num_experts_held // self.num_experts))
                if self.num_experts_held else 0
            ),
            kv_lora_rank=32 if self.is_mla else 0,
            qk_nope_head_dim=16 if self.is_mla else 0,
            qk_rope_head_dim=8 if self.is_mla else 0,
            v_head_dim=16 if self.is_mla else 0,
            ssm_state_size=min(self.ssm_state_size, 16) if self.ssm_state_size else 0,
            ssm_num_heads=min(self.ssm_num_heads, 4) if self.ssm_num_heads else 0,
            shared_attn_period=2 if self.shared_attn_period else 0,
            enc_layers=min(self.enc_layers, 2),
            dec_layers=min(self.dec_layers, 2),
            num_stub_patches=min(self.num_stub_patches, 8),
            param_dtype="float32",
            activation_dtype="float32",
            remat_policy="none",
        )


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode | long_decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "long_decode"),
}

CONFIGS: Dict[str, Callable[[], ArchConfig]] = {}


def register(fn: Callable[[], ArchConfig]) -> Callable[[], ArchConfig]:
    cfg = fn()
    CONFIGS[cfg.arch_id] = fn
    return fn


def get_config(arch_id: str) -> ArchConfig:
    import repro.configs  # noqa: F401  (triggers registration of all archs)

    if arch_id not in CONFIGS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(CONFIGS)}")
    return CONFIGS[arch_id]()
