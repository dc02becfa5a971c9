"""Plain float32 reference of DeepSeek-V2's decoder (multi-head latent
attention, a leading dense layer, routed and shared experts).

Written from the published description (DeepSeek-AI 2024, arXiv:2405.04434,
and the model's config.json and modeling code), in straightforward
`jax.numpy`, and independent of the program:

  * pre-norm blocks of RMSNorm;
  * attention in its published form, with no query compression: per head
    q = [q_nope | q_pe] from one projection; one projection of the input to
    [c_kv | k_pe]; c_kv normed and expanded per head to [k_nope | v]; keys
    [k_nope | k_pe], k_pe shared by the heads; a causal softmax scaled by
    (qk_nope + qk_rope)^-0.5 times YaRN's mscale(factor, mscale_all_dim)^2;
  * RoPE on q_pe and k_pe as the modeling code applies it: each vector's
    interleaved pairs are gathered into halves and the halves rotated
    against each other, pair i at YaRN's frequency i (the frequencies of
    the pairs that turn fast kept, the slow ones' divided by the factor, a
    linear ramp between), cos and sin scaled by mscale(factor, mscale) /
    mscale(factor, mscale_all_dim);
  * the first `first_k_dense_replace` layers a SwiGLU of `intermediate_size`
    (gate and up one fused matrix, gate first); the rest a softmax router
    over the published count of routed experts, the top `num_experts_per_tok`
    weights renormalised only with `norm_topk_prob` (else scaled by
    `routed_scaling_factor`), and each token's output the weighted sum of
    its experts' SwiGLUs of `moe_intermediate_size`, plus the shared experts
    (one SwiGLU `n_shared_experts` times as wide, ungated);
  * a final RMSNorm and an untied head.

The chip's share of a layer's experts (the configuration's
`n_routed_experts`, against its `published` count): the router keeps its
published width, and only experts 0 .. n_routed_experts - 1 are computed,
each for every token and weighted by the routing weight the token gives it
(zero where it did not pick it); what the other experts would add is left
out, as on the chip.

Weights come as the program's tree of leaves (`embed`, `lm_head`,
`final_norm`, `dense_blocks` and `blocks` stacked over layers, each with
`ln1`, `ln2`, `attn.{wq, wkv_a, kv_norm, wkv_b, wo}` and `mlp.{wi, wo}` or
`moe.{router, wi, wo, shared_wi, shared_wo}`) in the type they are served
in; each layer is cast to float32 inside the layer scan.  Matrix products
run at `precision=HIGHEST`.

`numerics="fp8"` is the control: every matrix product's operands are
rounded to float8 e4m3 with one scale per tensor.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["forward_logits", "served_gaps", "yarn_inv_freq"]

HIGHEST = jax.lax.Precision.HIGHEST


def _round8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with a per-tensor scale (max |x| -> 448)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ein(numerics: str, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if numerics == "fp8":
        a, b = _round8(a), _round8(b)
    elif numerics != "f32":
        raise ValueError(f"unknown numerics {numerics!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: Dict) -> np.ndarray:
    """The rope_head_dim / 2 inverse frequencies under YaRN."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    factor, ctx = rs["factor"], rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor

    def pair(rotations):  # the pair that turns `rotations` times over ctx
        return dim * math.log(ctx / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair(rs["beta_fast"])), 0)
    high = min(math.ceil(pair(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def _rope(x, positions, cfg):
    """x (B, T, H, d): pairs (x[2i], x[2i+1]) gathered into halves, then
    x * cos + rotate_half(x) * sin with angles positions * inv_freq[i]."""
    rs = cfg["rope_scaling"]
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]  # (1, T, 1, d)
    m = _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"], rs["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    rot = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)
    return x * cos + rot * sin


def _swiglu(numerics, y, wi, wo):
    gate, up = jnp.split(_ein(numerics, "btd,dn->btn", y, wi), 2, axis=-1)
    return _ein(numerics, "btf,fd->btd", jax.nn.silu(gate) * up, wo)


def _attention(x, a, cfg, numerics, positions):
    b, t, _ = x.shape
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = _ein(numerics, "btd,dn->btn", x, a["wq"]).reshape(b, t, h, nope + rope)
    ckv = _ein(numerics, "btd,dn->btn", x, a["wkv_a"])
    c = _rmsnorm(ckv[..., :r], a["kv_norm"], cfg["rms_norm_eps"])
    kv = _ein(numerics, "btr,rn->btn", c, a["wkv_b"]).reshape(b, t, h, nope + dv)
    q_pe = _rope(q[..., nope:], positions, cfg)
    k_pe = _rope(ckv[:, :, None, r:], positions, cfg)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, t, h, rope))], axis=-1)
    rs = cfg["rope_scaling"]
    scale = (nope + rope) ** -0.5 * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    s = _ein(numerics, "bqhd,bkhd->bhqk", q, k) * scale
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = _ein(numerics, "bhqk,bkhd->bqhd", p, kv[..., nope:]).reshape(b, t, h * dv)
    return _ein(numerics, "btn,nd->btd", o, a["wo"])


def _experts(y, m, cfg, numerics):
    """The held routed experts' part of the layer plus the shared experts."""
    k = cfg["num_experts_per_tok"]
    logits = _ein(numerics, "btd,de->bte", y, m["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # over the published experts
    topv, topi = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    else:
        topv = topv * cfg["routed_scaling_factor"]
    held = m["wi"].shape[0]
    # weight[b, t, e]: the routing weight token (b, t) gives held expert e.
    weight = jnp.sum(jax.nn.one_hot(topi, held) * topv[..., None], axis=-2)

    def one(acc, e):
        out = _swiglu(numerics, y, m["wi"][e], m["wo"][e])
        return acc + weight[..., e, None] * out, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), jnp.arange(held))
    return out + _swiglu(numerics, y, m["shared_wi"], m["shared_wo"])


def _block(x, lp, cfg, numerics, positions):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rmsnorm(x, lp["ln1"], eps), lp["attn"], cfg, numerics, positions)
    y = _rmsnorm(x, lp["ln2"], eps)
    if "moe" in lp:
        return x + _experts(y, lp["moe"], cfg, numerics)
    return x + _swiglu(numerics, y, lp["mlp"]["wi"], lp["mlp"]["wo"])


def forward_logits(
    params,
    tokens: jax.Array,
    cfg: Dict,
    *,
    numerics: str = "f32",
    training: bool = False,
) -> jax.Array:
    """(B, T) tokens -> (B, T, V) float32 logits."""
    if training:
        raise NotImplementedError("the DeepSeek-V2 reference serves; it has no training form")
    if cfg["n_routed_experts"] != params["blocks"]["moe"]["wi"].shape[1]:
        raise ValueError("the weights hold another number of experts than the configuration")
    if cfg["published"]["n_routed_experts"] != params["blocks"]["moe"]["router"].shape[-1]:
        raise ValueError("the router's width is not the published number of experts")
    t = tokens.shape[1]
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    positions = jnp.arange(t)

    def body(x, lp):
        lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
        return _block(x, lp, cfg, numerics, positions), None

    for stack in ("dense_blocks", "blocks"):
        x, _ = jax.lax.scan(body, x, params[stack])
    x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _ein(numerics, "btd,dv->btv", x, params["lm_head"])


@functools.lru_cache(maxsize=None)
def _forward_fn(cfg_json: str, numerics: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda p, t: forward_logits(p, t, cfg, numerics=numerics)[0])


def served_gaps(
    params,
    prompt: np.ndarray,
    served: Sequence[int],
    cfg: Dict,
    *,
    control: Optional[str] = None,
    pad_to: int = 1,
) -> Dict[str, float]:
    """As `dense_transformer.served_gaps`: one pass over prompt + served
    tokens; the widest gap of a served token's reference logit below the
    reference's best at its position, in standard deviations of that
    position's logits, and with `control` the same for the tokens the
    control's numerics put first.  Padding at the end changes no position
    that is read (causal)."""
    seq = np.concatenate([np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)])
    n = len(seq)
    padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
    padded[:n] = seq
    key = json.dumps(cfg, sort_keys=True)
    lg = _forward_fn(key, "f32")(params, jnp.asarray(padded)[None])
    pos = jnp.arange(len(prompt) - 1, n)
    rows = lg[pos]
    best = jnp.max(rows, axis=-1)
    spread = jnp.std(rows, axis=-1)

    def widest(tokens):
        got = jnp.take_along_axis(rows, tokens[:, None], axis=-1)[:, 0]
        return float(jnp.max((best - got) / spread))

    out = {"served_gap": widest(jnp.asarray(served, jnp.int32)), "tokens": len(served)}
    if control is not None:
        low = _forward_fn(key, control)(params, jnp.asarray(padded)[None])[pos]
        out["control_gap"] = widest(jnp.argmax(low, axis=-1).astype(jnp.int32))
    return out
