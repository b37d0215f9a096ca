"""Distributed training step factory.

Builds a jit-able ``train_step(params, opt_state, batch) -> (params,
opt_state, metrics)`` for any Model:

* microbatched gradient accumulation (scan over microbatches — the
  pipeline-depth knob on TPU pods where FSDP+TP replaces inter-stage
  PP),
* f32 master params + f32 Adam moments, global-norm clip,
* optional int8 + error-feedback gradient compression across the
  ``pod`` axis (the slow DCN/inter-pod tier) via shard_map,
* donation-friendly signature (params/opt_state donated by the caller's
  jit).

Gradient reduction across data/pod axes is otherwise implicit in SPMD:
the loss is the global-batch mean, so XLA inserts the all-reduce.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from typing import TYPE_CHECKING

from repro import obs
from repro.distributed.sharding import (ACT_RULES, CACHE_RULES, Rules,
                                        Sharder, WEIGHT_RULES)
from repro.optim.adamw import (AdamWConfig, adamw_update, global_norm,
                               init_adamw)

if TYPE_CHECKING:  # avoid models<->distributed import cycle
    from repro.models.model import Model

__all__ = ["TrainStepConfig", "make_train_step", "make_serve_fns"]


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    microbatches: int = 1
    compress_pod_grads: bool = False
    act_rules: Rules = ACT_RULES
    cache_rules: Rules = CACHE_RULES
    weight_rules: Rules = WEIGHT_RULES


def _split_microbatches(batch: Dict, k: int, shd: Sharder) -> Dict:
    def split(x):
        b = x.shape[0]
        assert b % k == 0, (b, k)
        xx = x.reshape(k, b // k, *x.shape[1:])
        # keep the microbatch axis unsharded (it is scanned) and the
        # per-microbatch batch dim on (pod, data).
        return shd.act(xx, (None, "batch") + (None,) * (xx.ndim - 2))
    return jax.tree.map(split, batch)


def recommended_microbatches(cfg, shape, mesh,
                             act_budget_bytes: float = 4e9) -> int:
    """Gradient-accumulation depth that keeps the scan-boundary
    activations (L x B_loc x S x D bf16 — the dominant live set under
    full remat) inside ``act_budget_bytes`` per device."""
    import numpy as np
    if mesh is None or shape.kind != "train":
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_shards = sizes.get("pod", 1) * sizes.get("data", 1)
    b_loc = max(shape.global_batch // max(data_shards, 1), 1)
    layers = cfg.n_layers + getattr(cfg, "enc_layers", 0)
    boundary = layers * b_loc * shape.seq_len * cfg.d_model * 2.0
    k = int(np.ceil(boundary / act_budget_bytes))
    if k <= 1:
        return 1
    divs = [d for d in range(1, b_loc + 1) if b_loc % d == 0]
    for d in divs:
        if d >= k:
            return d
    return b_loc


def make_train_step(model: "Model", opt_cfg: AdamWConfig,
                    mesh=None, step_cfg: TrainStepConfig = TrainStepConfig()
                    ) -> Callable:
    shd = Sharder(mesh, act_rules=step_cfg.act_rules,
                  cache_rules=step_cfg.cache_rules,
                  weight_rules=step_cfg.weight_rules)

    def loss_fn(params, mb):
        loss, metrics = model.loss(params, mb, shd)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        k = step_cfg.microbatches
        if k <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        mbs = _split_microbatches(batch, k, shd)

        def body(carry, mb):
            acc, loss_acc = carry
            (loss, metrics), g = grad_fn(params, mb)
            acc = jax.tree.map(jnp.add, acc, g)
            return (acc, loss_acc + loss), metrics

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gacc, loss_sum), metrics = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)), mbs)
        grads = jax.tree.map(lambda g: g / k, gacc)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss_sum / k, metrics, grads

    def train_step(params, opt_state, batch):
        loss, metrics, grads = compute_grads(params, batch)
        if step_cfg.compress_pod_grads and mesh is not None \
                and "pod" in mesh.axis_names:
            from repro.distributed.compression import ef_compress_grads
            grads, opt_state = ef_compress_grads(grads, opt_state, mesh)
        params, opt_state, om = adamw_update(params, grads, opt_state,
                                             opt_cfg)
        out_metrics = {"loss": loss, **{k: v for k, v in metrics.items()},
                       **om}
        return params, opt_state, out_metrics

    return train_step


def make_serve_fns(model: "Model", mesh=None,
                   step_cfg: TrainStepConfig = TrainStepConfig()
                   ) -> Tuple[Callable, Callable]:
    """(prefill, decode_step) closures with the Sharder bound.

    ``prefill(params, batch, max_len=0)`` sizes the decode cache for
    ``max_len`` positions (at least the prompt): a server that decodes
    ``gen`` tokens passes ``prompt_len + gen``, so no decode step
    writes past the cache.  Bind it before ``jax.jit`` (it is a shape).
    """
    shd = Sharder(mesh, act_rules=step_cfg.act_rules,
                  cache_rules=step_cfg.cache_rules,
                  weight_rules=step_cfg.weight_rules)

    # these bodies run only while JAX traces them: the spans time the
    # Python trace of the model (trace-time dispatch included)
    def prefill(params, batch, max_len: int = 0):
        with obs.span("trace.prefill"):
            return model.prefill(params, batch, shd, max_len=max_len)

    def decode_step(params, cache, token):
        with obs.span("trace.decode"):
            return model.decode_step(params, cache, token, shd)

    return prefill, decode_step
