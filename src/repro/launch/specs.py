"""Sharded ShapeDtypeStruct builders for the dry-run.

The same pattern shannon/kernels uses: weak-type-correct, shardable
stand-ins for every model input — params, optimizer state, decode
caches, token batches — with shardings resolved from the logical-dim
rule tables.  No device allocation anywhere.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (ACT_RULES, CACHE_RULES, Rules,
                                        WEIGHT_RULES, named_sharding)
from repro.models import batch_shapes
from repro.models.config import ModelConfig, ShapeSpec
from repro.models.model import Model
from repro.models.params import Param, map_params
from repro.models.transformer import cache_dims

__all__ = ["sharded_params", "sharded_opt_state", "sharded_batch",
           "sharded_cache", "cell_inputs", "tree_bytes_per_device"]


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _shard_params(tree, mesh: Mesh, rules: Rules):
    """A Param tree of abstract values, each sharded by its dims."""
    def attach(p: Param):
        s = named_sharding(p.dims, p.value.shape, rules, mesh)
        return Param(_sds(p.value.shape, p.value.dtype, s), p.dims)

    return map_params(attach, tree)


def sharded_params(model: Model, mesh: Mesh,
                   rules: Rules = WEIGHT_RULES):
    return _shard_params(model.abstract_params(), mesh, rules)


def sharded_opt_state(params_sds, mesh: Mesh):
    """Adam moments share the param shardings; count is replicated."""
    def moment(p: Param):
        return Param(_sds(p.value.shape, jnp.float32, p.value.sharding),
                     p.dims)
    rep = NamedSharding(mesh, P())
    return {
        "m": map_params(moment, params_sds),
        "v": map_params(moment, params_sds),
        "count": _sds((), jnp.int32, rep),
    }


def sharded_batch(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                  rules: Rules = ACT_RULES) -> Dict:
    out = {}
    for name, sds in batch_shapes(cfg, shape).items():
        if name in ("tokens", "token"):
            dims = ("batch", "seq")
        elif name == "frames":
            dims = ("batch", "seq", "embed")
        else:
            dims = tuple([None] * len(sds.shape))
        s = named_sharding(dims, sds.shape, rules, mesh)
        out[name] = _sds(sds.shape, sds.dtype, s)
    return out


# the attention leaves' dims follow their stored axis order
# (`transformer.cache_dims`); latent attention's latent and shared rope
# key have no head axis, so every model shard reads them whole
_CACHE_DIMS = {
    "ek": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
    "ev": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
    "ssm": ("layers", "batch", "ssm_inner", None, None),
    "conv": ("layers", "batch", None, "ssm_inner"),
    "pos": (),
}


def sharded_cache(model: Model, shape: ShapeSpec, mesh: Mesh,
                  rules: Rules = CACHE_RULES,
                  weight_rules: Rules = WEIGHT_RULES) -> Dict:
    """The decode state; the layer weights it carries are sharded as
    the weights they copy."""
    acache = model.abstract_cache(shape.global_batch, shape.seq_len)
    known = {**_CACHE_DIMS, **cache_dims(model.cfg)}
    out = {}
    for name, sds in acache.items():
        if name == "weights":
            out[name] = _shard_params(sds, mesh, weight_rules)
            continue
        dims = known.get(name, tuple([None] * len(sds.shape)))
        s = named_sharding(dims, sds.shape, rules, mesh)
        out[name] = _sds(sds.shape, sds.dtype, s)
    return out


def cell_inputs(model: Model, shape: ShapeSpec, mesh: Mesh,
                weight_rules: Rules = WEIGHT_RULES,
                act_rules: Rules = ACT_RULES,
                cache_rules: Rules = CACHE_RULES) -> Tuple:
    """Args tuple for the cell's step function:
    train  -> (params, opt_state, batch)
    prefill-> (params, batch)
    decode -> (params, cache, token_batch)"""
    params = sharded_params(model, mesh, weight_rules)
    if shape.kind == "train":
        opt = sharded_opt_state(params, mesh)
        batch = sharded_batch(model.cfg, shape, mesh, act_rules)
        return (params, opt, batch)
    if shape.kind == "prefill":
        batch = sharded_batch(model.cfg, shape, mesh, act_rules)
        return (params, batch)
    if shape.kind == "decode":
        cache = sharded_cache(model, shape, mesh, cache_rules, weight_rules)
        batch = sharded_batch(model.cfg, shape, mesh, act_rules)
        return (params, cache, batch["token"])
    raise ValueError(shape.kind)


def tree_bytes_per_device(tree, mesh: Mesh) -> int:
    """Analytic per-device bytes of a sharded SDS tree (fallback when
    the backend's memory_analysis is unavailable on CPU)."""
    n = 0
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def leaf_bytes(l):
        nonlocal n
        if not isinstance(l, jax.ShapeDtypeStruct):
            return
        total = int(np.prod(l.shape)) * l.dtype.itemsize if l.shape else \
            l.dtype.itemsize
        shards = 1
        sh = getattr(l, "sharding", None)
        if sh is not None and hasattr(sh, "spec"):
            for entry in sh.spec:
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                for a in axes:
                    shards *= mesh_sizes.get(a, 1)
        n += total // max(shards, 1)

    jax.tree.map(leaf_bytes, tree,
                 is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return n
