"""Plain float32 reference of the dense decoder-only transformer.

Written from the published description, in straightforward `jax.numpy`, and
independent of the program: pre-norm blocks of RMSNorm, grouped-query
attention with interleaved RoPE and a causal mask, and a SwiGLU MLP whose
gate and up projections are one fused matrix (gate first); a final RMSNorm
and a head that is the embedding's transpose when tied.  Matrix products run
at `precision=HIGHEST`, so a float32 product on the TPU is not silently done
in bfloat16.

Weights come as the program's tree of leaves (`embed`, `lm_head`,
`final_norm`, `blocks.{ln1,ln2,attn.{wq,wk,wv,wo},mlp.{wi,wo}}`, stacked over
layers) in the type they are served in; each layer is cast to float32 inside
the layer scan, so the reference holds one float32 layer at a time.

The paper's scrambling system (Kak 2010): where the configuration turns it
on and a training sequence's (T, D) activation forms a square grid of
128 x 128 blocks, the embedding output is permuted block-wise by S (block at
cell (i, j) := block at sigma(i, j)) and the last block's output by S^-1
before the final norm.  sigma is the closed form of the paper's tables,
written out again here.

`numerics="fp8"` is the control: every matrix product's operands are
rounded to float8 e4m3 with one scale per tensor, the precision below the
configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "forward_logits",
    "token_loss",
    "train_reference",
    "served_gaps",
    "sigma_perm",
]

HIGHEST = jax.lax.Precision.HIGHEST
SCRAMBLE_BLOCK = 128


# -- numerics ----------------------------------------------------------------


def _round8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with a per-tensor scale (max |x| -> 448)."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x: jax.Array) -> jax.Array:
    """A matrix product's operand in float8, as float8 training runs it: the
    forward rounds the operand, the backward rounds the gradient that flows
    back through it, each with its own scale.  (Differentiating the cast
    itself would round the unscaled gradient, which underflows to zero.)"""
    return _round8(x)


_fp8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def _ein(numerics: str, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if numerics == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif numerics != "f32":
        raise ValueError(f"unknown numerics {numerics!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# -- the paper's sigma, block-wise --------------------------------------------


def _sigma(n: int, i: int, j: int):
    """sigma_n at 1-indexed cell (i, j) -> 1-indexed (p, q) (Kak 2010)."""
    d = i + j
    if d <= n + 1:
        m, f, r = d - 1, d - 1, i
    else:
        m, f, r = 2 * n + 1 - d, 2 * n + 2 - d, i - (d - n) + 1
    h = (m + 1) // 2
    if r <= h:
        v = m - 2 * (r - 1)
    elif m % 2:
        v = 2 * (r - h)
    else:
        v = 2 * (r - h) - 1
    return (f, v) if d % 2 == 0 else (v, f)


@functools.lru_cache(maxsize=None)
def sigma_perm(n: int) -> np.ndarray:
    """perm[cell] = the flat index of sigma(cell), cells in row-major order."""
    perm = np.empty(n * n, np.int32)
    for i in range(1, n + 1):
        for j in range(1, n + 1):
            p, q = _sigma(n, i, j)
            perm[(i - 1) * n + j - 1] = (p - 1) * n + q - 1
    return perm


def _block_permute(x: jax.Array, perm: np.ndarray) -> jax.Array:
    """Block at cell c of the trailing (T, D) grid := block at perm[c]."""
    b, t, d = x.shape
    g = t // SCRAMBLE_BLOCK
    blocks = x.reshape(b, g, SCRAMBLE_BLOCK, g, SCRAMBLE_BLOCK).transpose(0, 1, 3, 2, 4)
    blocks = blocks.reshape(b, g * g, SCRAMBLE_BLOCK, SCRAMBLE_BLOCK)[:, perm]
    blocks = blocks.reshape(b, g, g, SCRAMBLE_BLOCK, SCRAMBLE_BLOCK).transpose(0, 1, 3, 2, 4)
    return blocks.reshape(b, t, d)


def _scrambles(cfg: Dict, t: int, d: int) -> bool:
    if not cfg.get("scramble_privacy"):
        return False
    bs = SCRAMBLE_BLOCK
    return t % bs == 0 and d % bs == 0 and t // bs == d // bs


# -- the model -----------------------------------------------------------------


def _rmsnorm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma.astype(
        jnp.float32
    )


def _rope(x, positions, theta):
    """x (B, T, H, hd); rotate interleaved pairs (x[2i], x[2i+1])."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def _block(x, lp, cfg, numerics, positions):
    b, t, d = x.shape
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    eps = cfg["rms_norm_eps"]
    a = lp["attn"]
    y = _rmsnorm(x, lp["ln1"], eps)
    q = _ein(numerics, "btd,dn->btn", y, a["wq"]).reshape(b, t, h, hd)
    k = _ein(numerics, "btd,dn->btn", y, a["wk"]).reshape(b, t, kvh, hd)
    v = _ein(numerics, "btd,dn->btn", y, a["wv"]).reshape(b, t, kvh, hd)
    q = _rope(q, positions, cfg["rope_theta"])
    k = _rope(k, positions, cfg["rope_theta"])
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=2)  # query head i reads kv head i // rep
    v = jnp.repeat(v, rep, axis=2)
    s = _ein(numerics, "bqhd,bkhd->bhqk", q, k) * cfg.get("attention_multiplier", hd**-0.5)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _ein(numerics, "bhqk,bkhd->bqhd", p, v).reshape(b, t, h * hd)
    x = x + cfg.get("residual_multiplier", 1.0) * _ein(numerics, "btn,nd->btd", o, a["wo"])
    y = _rmsnorm(x, lp["ln2"], eps)
    gate_up = _ein(numerics, "btd,dn->btn", y, lp["mlp"]["wi"])
    gate, up = jnp.split(gate_up, 2, axis=-1)
    hmid = jax.nn.silu(gate) * up
    return x + cfg.get("residual_multiplier", 1.0) * _ein(numerics, "btf,fd->btd", hmid, lp["mlp"]["wo"])


def forward_logits(
    params,
    tokens: jax.Array,
    cfg: Dict,
    *,
    numerics: str = "f32",
    training: bool = False,
) -> jax.Array:
    """(B, T) tokens -> (B, T, V) float32 logits.  `training` selects the
    training forward, the only one the scrambling system rides."""
    b, t = tokens.shape
    emb = params["embed"]
    x = jnp.take(emb, tokens, axis=0).astype(jnp.float32) * cfg.get("embedding_multiplier", 1.0)
    d = x.shape[-1]
    scramble = training and _scrambles(cfg, t, d)
    if scramble:
        x = _block_permute(x, sigma_perm(t // SCRAMBLE_BLOCK))
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

    def body(x, lp):
        lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
        return _block(x, lp, cfg, numerics, positions), None

    if training:
        # The backward pass keeps each layer's input and recomputes the
        # rest, so a float32 (or float8-rounded) layer's intermediates live
        # one layer at a time.
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    if scramble:
        perm = sigma_perm(t // SCRAMBLE_BLOCK)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.size, dtype=perm.dtype)
        x = _block_permute(x, inv)
    x = _rmsnorm(x, params["final_norm"], cfg["rms_norm_eps"])
    head = params["embed"].T if cfg["tie_word_embeddings"] else params["lm_head"]
    return _ein(numerics, "btd,dv->btv", x, head) / cfg.get("logits_scaling", 1.0)


def token_loss(params, tokens, labels, cfg, *, numerics="f32", training=True):
    """Mean next-token cross-entropy over every position."""
    logits = forward_logits(params, tokens, cfg, numerics=numerics, training=training)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# -- training ------------------------------------------------------------------


def _lr(count, opt: Dict):
    """Linear warm-up to the peak, then cosine to final_frac of it."""
    peak, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    step = jnp.asarray(count, jnp.float32)
    prog = jnp.clip((step - warm) / max(1, total - warm), 0.0, 1.0)
    cos = peak * (opt["final_frac"] + (1 - opt["final_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < warm, peak * step / max(1, warm), cos)


def _grads(params, tokens, labels, cfg, numerics, rows):
    """Loss and float32 gradient of the batch mean, one block of `rows` rows
    at a time (the batch mean is the mean of equal row blocks)."""
    b = tokens.shape[0]
    f = jax.value_and_grad(
        lambda p, t, l: token_loss(p, t, l, cfg, numerics=numerics, training=True)
    )
    tb = tokens.reshape(b // rows, rows, -1)
    lb = labels.reshape(b // rows, rows, -1)

    def body(acc, xs):
        loss, g = f(params, *xs)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, g), _ = jax.lax.scan(body, zero, (tb, lb))
    n = b // rows
    return loss / n, jax.tree.map(lambda x: x / n, g)


def _adamw(params, grads, m, v, count, opt):
    """AdamW with global-norm clipping; weight decay on leaves of two or
    more dimensions.  Parameters are stored back in `store_dtype`."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (norm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    c = count + 1
    b1c = 1.0 - opt["b1"] ** c
    b2c = 1.0 - opt["b2"] ** c
    lr = _lr(count, opt)
    m = jax.tree.map(lambda m_, g: opt["b1"] * m_ + (1 - opt["b1"]) * g, m, grads)
    v = jax.tree.map(lambda v_, g: opt["b2"] * v_ + (1 - opt["b2"]) * g * g, v, grads)

    def upd(p, m_, v_):
        step = (m_ / b1c) / (jnp.sqrt(v_ / b2c) + opt["eps"])
        pf = p.astype(jnp.float32)
        if p.ndim >= 2:
            step = step + opt["weight_decay"] * pf
        return (pf - lr * step).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), m, v, grads


def train_reference(
    params,
    batches: Sequence[Dict[str, jax.Array]],
    cfg: Dict,
    opt: Dict,
    *,
    numerics: str = "f32",
    rows: int = 1,
):
    """Run len(batches) AdamW steps from `params` (kept in their stored type).
    Returns the loss before each step, the clipped gradient of the first step
    and the parameters after the last, all per leaf."""

    @jax.jit
    def step(p, m, v, count, tokens, labels):
        loss, g = _grads(p, tokens, labels, cfg, numerics, rows)
        p, m, v, gc = _adamw(p, g, m, v, count, opt)
        return p, m, v, loss, gc

    p = params
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    losses, first_grad = [], None
    for i, bt in enumerate(batches):
        p, m, v, loss, gc = step(p, m, v, jnp.float32(i), bt["tokens"], bt["labels"])
        losses.append(float(loss))
        if first_grad is None:
            first_grad = gc
    return {"losses": losses, "first_grad": first_grad, "params": p}


# -- serving -------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _forward_fn(cfg_json: str, numerics: str):
    import json

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
    """Run the reference once over prompt + served tokens.  At each position
    that produced a served token, the gap by which that token's reference
    logit lies below the reference's best, in units of the standard
    deviation of the reference's logits at that position (so the number
    reads alike at any width or logit scale); the widest such gap.  With
    `control`, also the widest gap of the token that the control's numerics
    put first at each position.  The sequence is padded at its end to a
    multiple of `pad_to`; attention is causal, so the padding changes no
    position that is read."""
    import json

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
