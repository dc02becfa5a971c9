"""Uniform model API over all families — the layer launch/train/serve talk to.

`get_model(cfg)` returns a `Model` with a family-independent interface:
  init / abstract_params / logical_axes      parameter trees (1 source: PSpec)
  forward(params, batch, ctx)                train/eval logits
  loss(params, batch, ctx)                   scalar loss + metrics
  prefill / decode + decode_state_specs      serving path
  batch_specs(shape) / decode_input_specs    ShapeDtypeStructs + logical axes
                                             for dry-run lowering (no alloc)

Batch conventions (DESIGN.md §6):
  LM (dense/moe/ssm/hybrid/vlm): {"tokens": (B,S), "labels": (B,S)}
      vlm adds {"patches": (B,P,D)}   (stub ViT frontend)
  whisper:  {"frames": (B,S,D), "tokens": (B,S//r), "labels": (B,S//r)}
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import rwkv, ssm, transformer, vlm, whisper
from repro.models.layers import (
    ShardCtx,
    abstract_params,
    init_params,
    logical_axes_tree,
    softmax_xent,
)

__all__ = ["Model", "get_model"]


@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    _specs: Callable
    _forward: Callable
    _prefill: Callable
    _decode: Callable
    _state_specs: Callable  # (batch, max_len) -> abstract decode state
    # Paged-KV decode for the continuous-batching scheduler (DESIGN.md §12).
    # None for families whose decode state is not a KV cache: recurrent
    # families (ssm) carry O(1) state and are batched by stacking it per
    # slot instead; hybrid/audio are not schedulable (see launch/scheduler).
    _paged_decode: Optional[Callable] = None

    # -- parameters ---------------------------------------------------------
    def specs(self):
        return self._specs(self.cfg)

    def init(self, key: jax.Array):
        return init_params(key, self.specs(), self.cfg.pdtype)

    def abstract_params(self):
        return abstract_params(self.specs(), self.cfg.pdtype)

    def logical_axes(self):
        return logical_axes_tree(self.specs())

    # -- compute ------------------------------------------------------------
    def forward(self, params, batch: Dict[str, jax.Array], ctx: ShardCtx = ShardCtx()):
        return self._forward(params, batch, self.cfg, ctx)

    def loss(self, params, batch, ctx: ShardCtx = ShardCtx()):
        logits, aux = self.forward(params, batch, ctx)
        loss, acc = softmax_xent(logits, batch["labels"])
        if aux.get("lb_loss") is not None and self.cfg.is_moe:
            loss = loss + self.cfg.router_aux_coef * aux["lb_loss"]
            loss = loss + 1e-3 * aux["router_z"]
        metrics = {"loss": loss, "accuracy": acc, **aux}
        return loss, metrics

    def prefill(self, params, batch, ctx: ShardCtx = ShardCtx()):
        return self._prefill(params, batch, self.cfg, ctx)

    def decode(self, params, tokens, state, pos, ctx: ShardCtx = ShardCtx()):
        return self._decode(params, tokens, state, pos, self.cfg, ctx)

    def decode_state_specs(self, batch: int, max_len: int):
        return self._state_specs(self.cfg, batch, max_len)

    # -- paged serving (continuous batching) ---------------------------------
    @property
    def supports_paged(self) -> bool:
        return self._paged_decode is not None

    def paged_decode(
        self,
        params,
        tokens,  # (S, 1)
        pools,  # {"k","v"}: (L, P, KV, page_size, hd)
        block_tables,  # (S, n_pages)
        positions,  # (S,)
        ctx: ShardCtx = ShardCtx(),
        *,
        impl: Optional[str] = None,
        interpret: bool = False,
    ):
        """One continuous-batching decode step against paged KV pools."""
        if self._paged_decode is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no paged decode path"
            )
        return self._paged_decode(
            params,
            tokens,
            pools,
            block_tables,
            positions,
            self.cfg,
            ctx,
            impl=impl,
            interpret=interpret,
        )

    def paged_pool_specs(self, num_pages: int, page_size: int):
        if self._paged_decode is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no paged decode path"
            )
        return transformer.paged_pool_specs(self.cfg, num_pages, page_size)

    # -- dry-run input specs --------------------------------------------------
    def batch_specs(self, shape: ShapeSpec) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Training/prefill inputs as ShapeDtypeStructs + logical axes."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if cfg.family == "audio":
            dec = s // cfg.dec_ratio
            specs = {
                "frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), cfg.adtype),
                "tokens": jax.ShapeDtypeStruct((b, dec), i32),
                "labels": jax.ShapeDtypeStruct((b, dec), i32),
            }
            axes = {
                "frames": ("batch", "frames", "embed"),
                "tokens": ("batch", "seq"),
                "labels": ("batch", "seq"),
            }
        elif cfg.family == "vlm":
            specs = {
                "patches": jax.ShapeDtypeStruct((b, cfg.num_stub_patches, cfg.d_model), cfg.adtype),
                "tokens": jax.ShapeDtypeStruct((b, s), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32),
            }
            axes = {
                "patches": ("batch", "patches", "embed"),
                "tokens": ("batch", "seq"),
                "labels": ("batch", "seq"),
            }
        else:
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, s), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32),
            }
            axes = {"tokens": ("batch", "seq"), "labels": ("batch", "seq")}
        return specs, axes

    def decode_input_specs(self, shape: ShapeSpec):
        """serve_step inputs: (tokens, state, pos) specs + state logical axes."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        if cfg.family == "audio":
            state = whisper.whisper_cache_specs(cfg, b, s, s // cfg.dec_ratio)
            axes = {
                "enc_out": ("kv_batch", "kv_seq", "embed"),
                "k": ("layers", "kv_batch", "kv_seq", "kv_heads", "head_dim"),
                "v": ("layers", "kv_batch", "kv_seq", "kv_heads", "head_dim"),
            }
        elif cfg.family == "ssm":
            state = rwkv.rwkv_state_specs(cfg, b)
            axes = {
                "wkv": ("layers", "batch", "heads", None, None),
                "tm_shift": ("layers", "batch", "embed"),
                "cm_shift": ("layers", "batch", "embed"),
            }
        elif cfg.family == "hybrid":
            state = ssm.zamba_state_specs(cfg, b, s)
            axes = {
                "h": ("layers", "batch", "heads", None, "state"),
                "conv": ("layers", "batch", None, "mlp"),
                "kv_k": ("layers", "kv_batch", "kv_seq", "kv_heads", "head_dim"),
                "kv_v": ("layers", "kv_batch", "kv_seq", "kv_heads", "head_dim"),
            }
        else:
            max_len = s + (cfg.num_stub_patches if cfg.family == "vlm" else 0)
            state = transformer.decode_cache_specs(cfg, b, max_len)
            axes = {
                "k": ("layers", "kv_batch", "kv_seq", "kv_heads", "head_dim"),
                "v": ("layers", "kv_batch", "kv_seq", "kv_heads", "head_dim"),
            }
        return tokens, state, pos, axes


def _lm_forward(params, batch, cfg, ctx):
    return transformer.lm_forward(params, batch["tokens"], cfg, ctx)


def _lm_prefill(params, batch, cfg, ctx):
    return transformer.lm_prefill(params, batch["tokens"], cfg, ctx)


def _rwkv_forward(params, batch, cfg, ctx):
    return rwkv.rwkv_forward(params, batch["tokens"], cfg, ctx)


def _rwkv_prefill(params, batch, cfg, ctx):
    return rwkv.rwkv_prefill(params, batch["tokens"], cfg, ctx)


def _zamba_forward(params, batch, cfg, ctx):
    return ssm.zamba_forward(params, batch["tokens"], cfg, ctx)


def _zamba_prefill(params, batch, cfg, ctx):
    return ssm.zamba_prefill(params, batch["tokens"], cfg, ctx)


def get_model(cfg: ArchConfig) -> Model:
    fam = cfg.family
    if fam in ("dense", "moe"):
        return Model(
            cfg,
            transformer.lm_specs,
            _lm_forward,
            _lm_prefill,
            transformer.lm_decode,
            lambda c, b, m: transformer.decode_cache_specs(c, b, m),
            _paged_decode=transformer.lm_decode_paged,
        )
    if fam == "ssm":
        return Model(
            cfg,
            rwkv.rwkv_specs,
            _rwkv_forward,
            _rwkv_prefill,
            rwkv.rwkv_decode,
            lambda c, b, m: rwkv.rwkv_state_specs(c, b),
        )
    if fam == "hybrid":
        return Model(
            cfg,
            ssm.zamba_specs,
            _zamba_forward,
            _zamba_prefill,
            ssm.zamba_decode,
            ssm.zamba_state_specs,
        )
    if fam == "audio":
        return Model(
            cfg,
            whisper.whisper_specs,
            whisper.whisper_forward,
            whisper.whisper_prefill,
            whisper.whisper_decode,
            lambda c, b, m: whisper.whisper_cache_specs(c, b, m, m // c.dec_ratio),
        )
    if fam == "vlm":
        return Model(
            cfg,
            vlm.vlm_specs,
            vlm.vlm_forward,
            vlm.vlm_prefill,
            vlm.vlm_decode,
            lambda c, b, m: vlm.vlm_cache_specs(c, b, m + c.num_stub_patches),
            # vlm decode is structurally lm_decode (patches only affect
            # prefill); the scheduler offsets positions by num_stub_patches.
            _paged_decode=transformer.lm_decode_paged,
        )
    raise ValueError(f"unknown family {fam!r}")
