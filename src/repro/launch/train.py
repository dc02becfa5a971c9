"""End-to-end training driver.

Runs any assigned architecture (full config on a production mesh under pjit,
or `--reduced` on whatever devices exist — the CPU path used by tests and
examples), with the full fault-tolerance stack: atomic/async checkpoints,
`--resume auto`, deterministic resumable data, straggler logging.

Examples
--------
  # CPU: train the paper demo config for 200 steps
  PYTHONPATH=src python -m repro.launch.train --arch mesh-paper-demo \
      --steps 200 --batch 8 --seq 128

  # CPU: reduced olmoe with checkpointing + crash-resume
  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --reduced \
      --steps 100 --ckpt-dir /tmp/ckpt --resume auto
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.checkpoint.manager import CheckpointManager
from repro.checkpoint.async_writer import AsyncCheckpointer
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import ShardCtx, get_model
from repro.optim import AdamWConfig, warmup_cosine
from repro.parallel.sharding import DEFAULT_RULES, tree_shardings
from repro.train.loop import LoopConfig, train_loop
from repro.train.metrics import MetricsLogger
from repro.train.train_step import init_train_state, make_train_step

__all__ = ["main", "build_trainer"]


def build_trainer(
    cfg,
    *,
    batch: int,
    seq: int,
    mesh=None,
    lr: float = 3e-4,
    total_steps: int = 1000,
    grad_accum: int = 1,
    seed: int = 0,
):
    """Construct (train_step_fn, state, data_iter) for a config.

    With `mesh`, the step is jitted with NamedShardings from the model's
    logical axes (the same path the dry-run lowers); without, plain jit.
    """
    model = get_model(cfg)
    key = jax.random.PRNGKey(seed)
    schedule = warmup_cosine(lr, min(100, total_steps // 10 + 1), total_steps)
    ctx = ShardCtx(mesh, DEFAULT_RULES) if mesh is not None else ShardCtx()
    step_fn = make_train_step(model, schedule, AdamWConfig(), ctx, grad_accum=grad_accum)

    state = init_train_state(model, key)
    if mesh is not None:
        p_axes = model.logical_axes()
        state_sh = {
            "params": tree_shardings(p_axes, mesh, DEFAULT_RULES, state["params"]),
            "opt": {
                "m": tree_shardings(p_axes, mesh, DEFAULT_RULES, state["params"]),
                "v": tree_shardings(p_axes, mesh, DEFAULT_RULES, state["params"]),
                "count": jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            },
            "step": jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        }
        state = jax.device_put(state, state_sh)
        step_fn = jax.jit(step_fn, in_shardings=(state_sh, None), out_shardings=(state_sh, None), donate_argnums=(0,))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch, seed=seed))
    return step_fn, state, data


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="CPU-smoke dims")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--resume", default=None, choices=(None, "auto"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="none", choices=("none", "local-dp", "prod"),
                    help="'prod' requires a 256-device runtime (dry-run covers it offline)")
    ap.add_argument("--step-deadline-s", type=float, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family in ("audio", "vlm"):
        raise SystemExit(f"{args.arch}: synthetic LM trainer covers token-LM families; "
                         "see tests/test_models_smoke.py for audio/vlm train steps")

    mesh = None
    if args.mesh == "local-dp":
        mesh = make_local_mesh((jax.device_count(), 1), ("data", "model"))
    elif args.mesh == "prod":
        mesh = make_production_mesh()

    step_fn, state, data = build_trainer(
        cfg, batch=args.batch, seq=args.seq, mesh=mesh, lr=args.lr,
        total_steps=args.steps, grad_accum=args.grad_accum, seed=args.seed,
    )

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    writer = AsyncCheckpointer(ckpt) if (ckpt and args.async_ckpt) else None
    if ckpt and args.resume == "auto":
        latest = ckpt.latest_step()
        if latest is not None:
            print(f"[resume] restoring step {latest} from {args.ckpt_dir}")
            state = ckpt.restore(latest, state)
            data.restore(ckpt.meta(latest)["data_step"])

    loop_cfg = LoopConfig(
        total_steps=args.steps,
        ckpt_every=args.ckpt_every,
        step_deadline_s=args.step_deadline_s,
        log_every=args.log_every,
    )
    logger = MetricsLogger()
    state = train_loop(step_fn, state, data, loop_cfg, ckpt=ckpt, logger=logger, checkpointer=writer)
    if writer is not None:
        writer.close()
    final_loss = logger.history[-1]["loss"] if logger.history else float("nan")
    print(f"[done] {args.arch} steps={args.steps} final_loss={final_loss:.4f}")


if __name__ == "__main__":
    main()
