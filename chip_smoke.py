#!/usr/bin/env python3
"""Bring-up smoke test: serve hymba-1.5b at full width on one TPU chip.

    python3 chip_smoke.py               # one chip: the tuned serving path
    python3 chip_smoke.py --four-chips  # four chips: sharded train steps

One process drives the chip; nothing here starts a child that needs it.
The one-chip run serves hymba-1.5b (all 32 layers, published widths,
seeded random weights) through `repro.launch.serve.serve` with tuned
ops and a graph pretune (batch 4, prompt 1024, 16 decode steps), and
checks, one printed line per phase:

  (a) the device (exits non-zero unless JAX reports a TPU),
  (b) compile seconds of prefill and decode, apart from the runs,
  (c) prefill ms and decode ms/token after warm-up,
  (d) the compiled prefill holds Pallas kernels (``tpu_custom_call``)
      and they are not run in interpret mode,
  (e) dispatch: 100% frozen, 0 live, 0 fallback, 0 runtime tunes,
  (f) tuned-path prefill logits against the jnp layer path (same
      params, same batch): relative Frobenius error <= LOGIT_BOUND,
  (g) the device's peak bytes in use.

``--four-chips`` runs only the sharded path: a (data=2, model=2) mesh,
three train steps of hymba-1.5b at full width, step 1's loss against
the unsharded loss of the same batch on one device, and the parameter
bytes each device holds.

Any failed phase exits non-zero before the last line.  The last line
of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "hymba-1.5b"
SEED = 0
BATCH, PROMPT_LEN, GEN = 4, 1024, 16
# Tuned Pallas kernels vs the jnp layers, both bf16.  The two round in
# different places, and 32 layers of random weights amplify it: at
# smoke width with all 32 layers, on the CPU, the gap is 0.057 while
# each path is 0.081 from a float32 "highest"-precision forward pass.
LOGIT_BOUND = 1e-1
# Four-chip train shape, sized by compiling the sharded step for a
# described v5e:2x2 (memory_analysis per device, see CHANGES.md).
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 3
# Step-1 loss, sharded over 4 chips vs one device: same math, other
# reduction order and collectives, bf16 activations.
LOSS_BOUND = 1e-2


def fail(msg: str) -> None:
    raise SystemExit(f"[smoke] FAILED: {msg}")


def device_phase(want_count: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices)}
    print(f"[smoke] (a) device: {json.dumps(dev)}")
    if d.platform != "tpu":
        fail(f"JAX found no TPU (platform {d.platform!r})")
    if len(devices) < want_count:
        fail(f"need {want_count} chips, JAX sees {len(devices)}")
    return dev


def rel_frobenius(got, want) -> float:
    import jax.numpy as jnp
    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def one_chip() -> dict:
    dev = device_phase(1)
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.distributed import make_serve_fns
    from repro.kernels.common import default_interpret
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import frozen_audit_problems, serve
    from repro.models.layers import use_tuned_layers

    enable_compile_cache()
    cfg = get_config(ARCH)
    print(f"[smoke] model {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads (kv {cfg.n_kv}), d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}; batch {BATCH} x prompt "
          f"{PROMPT_LEN}, {GEN} decode steps, seed {SEED}")
    run = serve(cfg, batch=BATCH, prompt_len=PROMPT_LEN, gen=GEN,
                seed=SEED, tuned_ops=True, pretune=True)
    print(f"[smoke] (b) compile s: prefill {run['compile_s']['prefill']}, "
          f"decode {run['compile_s']['decode']}")
    print(f"[smoke] (c) prefill {run['prefill_ms']} ms, decode "
          f"{run['decode_ms_per_token']} ms/token")

    n_custom = run["prefill_compiled"].as_text().count("tpu_custom_call")
    interpret = default_interpret()
    print(f"[smoke] (d) compiled prefill: {n_custom} tpu_custom_call, "
          f"default_interpret() = {interpret}")
    if n_custom == 0 or interpret:
        fail("the tuned kernels are not compiled for the chip")

    st = run["dispatch"]
    print(f"[smoke] (e) dispatch: {st['frozen']}/{st['total']} frozen, "
          f"{st['live']} live, {st['fallback']} fallback, "
          f"{run['runtime_tunes']} runtime tunes")
    problems = frozen_audit_problems(run)
    if problems:
        fail("; ".join(problems))

    tuned = run["prefill_logits"]
    want_shape = (BATCH, PROMPT_LEN, cfg.vocab)
    if tuned.shape != want_shape or not bool(jnp.isfinite(tuned).all()):
        fail(f"tuned prefill logits: shape {tuned.shape} (want "
             f"{want_shape}) or non-finite values")
    prefill_jnp, _ = make_serve_fns(run["model"])
    with use_tuned_layers(False):
        ref, _ = jax.jit(prefill_jnp)(run["params"], run["batch"])
    err = rel_frobenius(tuned, ref)
    print(f"[smoke] (f) prefill logits, tuned vs jnp layers: relative "
          f"Frobenius error {err} (bound {LOGIT_BOUND})")
    if not err <= LOGIT_BOUND:
        fail(f"tuned path disagrees with the jnp path: {err}")

    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"[smoke] (g) peak_bytes_in_use: {peak}")
    return dev


def four_chips() -> dict:
    dev = device_phase(4)
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.distributed import make_train_step
    from repro.distributed.sharding import Sharder
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import (sharded_batch, sharded_opt_state,
                                    sharded_params)
    from repro.models import build_model
    from repro.models.config import ShapeSpec
    from repro.optim import AdamWConfig, init_adamw

    enable_compile_cache()
    cfg = get_config(ARCH)
    model = build_model(cfg)
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    shape = ShapeSpec("four_chip_train", TRAIN_SEQ, TRAIN_BATCH, "train")
    print(f"[smoke] model {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}; mesh data=2 x model=2; batch {TRAIN_BATCH} x "
          f"seq {TRAIN_SEQ}, {TRAIN_STEPS} train steps, seed {SEED}")

    key = jax.random.PRNGKey(SEED)
    tokens = jax.random.randint(key, (TRAIN_BATCH, TRAIN_SEQ), 0, cfg.vocab)
    # unsharded reference loss on one device, before the params move
    params = jax.jit(model.init)(key)
    loss_1dev = float(jax.jit(lambda p, b: model.loss(p, b, Sharder())[0])(
        params, {"tokens": tokens}))
    print(f"[smoke] one-device loss of the batch: {loss_1dev}")

    p_sds = sharded_params(model, mesh)
    shardings = lambda tree: jax.tree.map(lambda s: s.sharding, tree)
    p_shd = shardings(p_sds)
    o_shd = shardings(sharded_opt_state(p_sds, mesh))
    b_shd = shardings(sharded_batch(cfg, shape, mesh))
    params = jax.device_put(params, p_shd)
    opt = jax.jit(init_adamw, out_shardings=o_shd)(params)
    batch = jax.device_put({"tokens": tokens}, b_shd)
    step = jax.jit(make_train_step(model, AdamWConfig(), mesh=mesh),
                   in_shardings=(p_shd, o_shd, b_shd),
                   out_shardings=(p_shd, o_shd, None),
                   donate_argnums=(0, 1))
    t0 = time.perf_counter()
    step_c = step.lower(params, opt, batch).compile()
    print(f"[smoke] sharded train step compiled in "
          f"{time.perf_counter() - t0} s")
    losses, gnorms = [], []
    for _ in range(TRAIN_STEPS):
        params, opt, metrics = step_c(params, opt, batch)
        losses.append(float(metrics["loss"]))
        gnorms.append(float(metrics["grad_norm"]))
    print(f"[smoke] sharded losses over {TRAIN_STEPS} steps: {losses}; "
          f"grad norms {gnorms}")
    rel = abs(losses[0] - loss_1dev) / abs(loss_1dev)
    print(f"[smoke] step-1 loss vs one device: relative difference {rel} "
          f"(bound {LOSS_BOUND})")
    if not (np.isfinite(losses + gnorms).all() and rel <= LOSS_BOUND):
        fail(f"sharded losses {losses}, grad norms {gnorms}; step-1 loss "
             f"vs one-device {loss_1dev}")

    per_device = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per_device[shard.device] += shard.data.nbytes
    total = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree.leaves(params))
    held = [per_device[d] for d in mesh.devices.flat]
    print(f"[smoke] parameter bytes per device {held} of {total} total")
    if min(held) == 0 or max(held) > total // 2:
        fail("the parameters are not spread over the four devices")
    return dev


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip train phase")
    args = ap.parse_args()
    dev = four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
