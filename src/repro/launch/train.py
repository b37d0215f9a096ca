"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-7b \
        --smoke --steps 100 --batch 8 --seq 256 --checkpoint-dir /tmp/ckpt

On this CPU box you train the ``--smoke`` (reduced) configs; on a real
pod the same entrypoint takes ``--mesh single|multi`` and the full
configs.  Fault tolerance (checkpoint/restart, straggler watermark) is
always on via the TrainSupervisor.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", type=str, default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "single", "multi"],
                    default="none")
    ap.add_argument("--compress-pod-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_config, get_smoke
    from repro.data import DataConfig, TokenStream
    from repro.distributed import TrainStepConfig, make_train_step
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model
    from repro.optim import AdamWConfig, init_adamw

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = None
    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    print(f"[train] arch={cfg.name} family={cfg.family} "
          f"params={cfg.num_params()/1e6:.1f}M "
          f"active={cfg.num_active_params()/1e6:.1f}M")

    params = model.init(jax.random.PRNGKey(args.seed))
    opt = init_adamw(params)
    opt_cfg = AdamWConfig(peak_lr=args.lr, warmup_steps=args.steps // 10,
                          decay_steps=args.steps)
    step_cfg = TrainStepConfig(microbatches=args.microbatches,
                               compress_pod_grads=args.compress_pod_grads)
    train_step = jax.jit(make_train_step(model, opt_cfg, mesh=mesh,
                                         step_cfg=step_cfg),
                         donate_argnums=(0, 1))

    stream = TokenStream(DataConfig(vocab=cfg.vocab,
                                    global_batch=args.batch,
                                    seq_len=args.seq, seed=args.seed))

    def make_batch(step):
        b = {k: jnp.asarray(v) for k, v in stream.make_batch(step).items()}
        if cfg.frontend == "frames":
            key = jax.random.fold_in(jax.random.PRNGKey(args.seed), step)
            b["frames"] = jax.random.normal(
                key, (args.batch, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        return b

    state = {"params": params, "opt": opt, "step": 0}
    if args.checkpoint_dir:
        from repro.checkpoint import CheckpointManager
        from repro.runtime import FaultPolicy, TrainSupervisor
        sup = TrainSupervisor(
            CheckpointManager(args.checkpoint_dir, keep=3),
            FaultPolicy(checkpoint_every=args.checkpoint_every))
        state = sup.run(train_step, state, make_batch, args.steps,
                        log_every=args.log_every)
        print(f"[train] done at step {state['step']}")
        return

    losses = []
    t0 = time.perf_counter()
    for step in range(args.steps):
        batch = make_batch(step)
        state["params"], state["opt"], metrics = train_step(
            state["params"], state["opt"], batch)
        losses.append(float(metrics["loss"]))
        if args.log_every and (step + 1) % args.log_every == 0:
            dt = (time.perf_counter() - t0) / (step + 1)
            print(f"[train] step={step+1} loss={losses[-1]:.4f} "
                  f"({dt*1e3:.0f} ms/step)", flush=True)
    print(f"[train] loss {losses[0]:.4f} -> {losses[-1]:.4f} over "
          f"{args.steps} steps")


if __name__ == "__main__":
    main()
