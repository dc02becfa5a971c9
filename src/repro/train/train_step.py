"""Train/serve step factories — the functions the launcher lowers with pjit.

`make_train_step(model, ...)` returns a pure (state, batch) -> (state, metrics)
function: value_and_grad over `model.loss`, global-norm clipping, AdamW with a
schedule.  State = {"params", "opt", "step"}.  Under pjit the DP gradient
all-reduce is implicit in the sharded loss mean; the int8 error-feedback
variant (`make_dp_train_step_compressed`) expresses the data-parallel outer
loop with shard_map so the compressed all-reduce is explicit (used by
examples/tests; see parallel/compression.py).

`make_serve_step(model)` returns (params, tokens, state, pos) ->
(next_tokens, state): one greedy decode step — the function behind the
decode_32k / long_500k dry-run cells.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import ShardCtx
from repro.models.registry import Model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.parallel.compression import compressed_pmean_tree, init_error_state
from repro.parallel.sharding import shard_map

__all__ = [
    "init_train_state",
    "make_train_step",
    "make_serve_step",
    "make_prefill_step",
    "make_dp_train_step_compressed",
]


def init_train_state(model: Model, key: jax.Array) -> Dict[str, Any]:
    params = model.init(key)
    return {"params": params, "opt": adamw_init(params), "step": jnp.zeros((), jnp.int32)}


def abstract_train_state(model: Model) -> Dict[str, Any]:
    """ShapeDtypeStruct state tree for dry-run lowering (no allocation)."""
    params = model.abstract_params()
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    return {
        "params": params,
        "opt": {
            "m": jax.tree.map(f32, params),
            "v": jax.tree.map(f32, params),
            "count": jax.ShapeDtypeStruct((), jnp.int32),
        },
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def make_train_step(
    model: Model,
    schedule: Callable[[jax.Array], jax.Array],
    adamw_cfg: AdamWConfig = AdamWConfig(),
    ctx: ShardCtx = ShardCtx(),
    grad_accum: int = 1,
) -> Callable:
    """grad_accum > 1: microbatch gradient accumulation — the global batch is
    split on its leading dim and scanned, with an f32 grad accumulator sharded
    like the params (FSDP).  This bounds both the attention-score working set
    and the remat-carrier residency per microbatch (DESIGN.md §4)."""

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch, ctx
        )
        del loss
        return grads, metrics

    def train_step(state, batch):
        params = state["params"]
        if grad_accum == 1:
            grads, metrics = grads_of(params, batch)
        else:
            micro = jax.tree.map(
                lambda t: t.reshape(grad_accum, t.shape[0] // grad_accum, *t.shape[1:]),
                batch,
            )

            def body(acc, mb):
                g, m = grads_of(params, mb)
                acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc, g)
                return acc, m

            acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            gsum, mstack = jax.lax.scan(body, acc0, micro)
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            metrics = jax.tree.map(lambda m: m.mean(), mstack)
        lr = schedule(state["opt"]["count"])
        new_params, new_opt, gnorm = adamw_update(
            grads, state["opt"], params, lr, adamw_cfg
        )
        metrics = {**metrics, "grad_norm": gnorm, "lr": lr}
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        return new_state, metrics

    return train_step


def make_prefill_step(model: Model, ctx: ShardCtx = ShardCtx()) -> Callable:
    def prefill_step(params, batch):
        logits, state, routed = model.prefill_routed(params, batch, ctx)
        # next-token from the last position — the serving handoff artifact
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok, state, routed

    return prefill_step


def make_serve_step(model: Model, ctx: ShardCtx = ShardCtx()) -> Callable:
    def serve_step(params, tokens, state, pos):
        logits, new_state = model.decode(params, tokens, state, pos, ctx)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok, new_state

    return serve_step


def make_dp_train_step_compressed(
    model: Model,
    schedule: Callable,
    mesh,
    adamw_cfg: AdamWConfig = AdamWConfig(),
    dp_axes: Tuple[str, ...] = ("data",),
) -> Callable:
    """Data-parallel train step with explicit int8 error-feedback all-reduce.

    State additionally carries {"err": residual tree}.  Params/opt replicated;
    batch sharded over dp_axes.  For pure-DP meshes (examples/tests).
    """

    def local_grads(params, batch):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
        return grads, metrics

    def step_fn(state, batch):
        def body(params, opt, step, err, batch):
            # err leaves carry a leading per-device axis (dp, *param_shape),
            # sharded over dp_axes -> each rank sees its own (1, ...) residual.
            err_local = jax.tree.map(lambda e: e[0], err)
            grads, metrics = local_grads(params, batch)
            mean_grads, new_err = compressed_pmean_tree(grads, err_local, dp_axes)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, dp_axes), metrics)
            lr = schedule(opt["count"])
            new_params, new_opt, gnorm = adamw_update(mean_grads, opt, params, lr, adamw_cfg)
            new_err = jax.tree.map(lambda e: e[None], new_err)
            return new_params, new_opt, step + 1, new_err, {**metrics, "grad_norm": gnorm}

        pspec_rep = jax.tree.map(lambda _: P(), state["params"])
        opt_rep = jax.tree.map(lambda _: P(), state["opt"])
        err_spec = jax.tree.map(lambda _: P(dp_axes), state["err"])
        batch_spec = jax.tree.map(lambda _: P(dp_axes), batch)
        mapped = shard_map(
            body,
            mesh=mesh,
            in_specs=(pspec_rep, opt_rep, P(), err_spec, batch_spec),
            out_specs=(pspec_rep, opt_rep, P(), err_spec, jax.tree.map(lambda _: P(), {"loss": 0, "accuracy": 0, "lb_loss": 0, "router_z": 0, "grad_norm": 0})),
            check_vma=False,
        )
        new_params, new_opt, new_step, new_err, metrics = mapped(
            state["params"], state["opt"], state["step"], state["err"], batch
        )
        return {"params": new_params, "opt": new_opt, "step": new_step, "err": new_err}, metrics

    return step_fn


def init_dp_train_state_compressed(
    model: Model, key: jax.Array, mesh=None, dp_axes: Tuple[str, ...] = ("data",)
) -> Dict[str, Any]:
    """State with per-rank error residuals: err leaves are (dp, *param_shape)."""
    state = init_train_state(model, key)
    dp = 1
    if mesh is not None:
        for a in dp_axes:
            dp *= mesh.shape.get(a, 1)
    err = init_error_state(state["params"])
    state["err"] = jax.tree.map(
        lambda e: jnp.broadcast_to(e[None], (dp,) + e.shape), err
    )
    return state
