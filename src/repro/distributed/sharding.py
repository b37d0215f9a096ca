"""Logical-axis sharding rules with divisibility fallback.

Every parameter and constrained activation in the model zoo carries a
tuple of *logical* dimension names (``("embed", "heads", "head_dim")``).
A rule table maps logical names to mesh-axis candidates; the resolver
assigns, per tensor, the first candidate whose mesh-axis product divides
the dimension size, never reusing a mesh axis within one tensor, and
falls back to replication otherwise.

This gives the production behaviours for free:

* FSDP/ZeRO-3: ``embed -> data`` on weights,
* TP: ``heads / mlp / experts / vocab -> model``,
* graceful degradation: 60 experts or 25 heads on a 16-way model axis
  replicate (and the next dim in the tensor picks the freed axis up —
  e.g. starcoder2's 24 q-heads fail but head_dim=128 takes "model"),
* DP over pods: ``batch -> ("pod", "data")`` groups both axes.

Rule tables are plain tuples so hillclimb variants (e.g. sequence-
sharded decode caches) are one-line swaps (`repro.launch.dryrun`
variants).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["Rules", "WEIGHT_RULES", "ACT_RULES", "CACHE_RULES",
           "CACHE_RULES_SEQSHARD", "logical_spec", "named_sharding",
           "Sharder", "tree_shardings"]

AxisCand = Union[str, Tuple[str, ...]]
Rule = Tuple[str, Tuple[AxisCand, ...]]
Rules = Tuple[Rule, ...]

# -- default rule tables -----------------------------------------------------

WEIGHT_RULES: Rules = (
    ("vocab", ("model",)),
    ("embed", ("data",)),          # FSDP / ZeRO-3 weight sharding
    ("heads", ("model",)),
    ("kv_heads", ("model",)),
    ("head_dim", ("model",)),      # TP fallback when heads indivisible
    ("mlp", ("model",)),
    ("experts", ("model",)),       # expert parallelism
    ("expert_mlp", ("model",)),    # within-expert TP fallback
    ("ssm_inner", ("model",)),
    ("state", ()),
    ("conv", ()),
)

ACT_RULES: Rules = (
    ("batch", (("pod", "data"), "data")),
    ("seq", ()),
    ("embed", ()),
    ("heads", ("model",)),
    # kv activations stay replicated over model: they broadcast up to
    # the TP-sharded q-head axis locally (Megatron GQA recipe); sharding
    # them over head_dim would force per-layer logit all-reduces.
    ("kv_heads", ()),
    ("head_dim", ()),
    ("mlp", ("model",)),
    ("experts", ("model",)),
    ("expert_mlp", ("model",)),
    ("moe_capacity", (("pod", "data"), "data")),
    ("vocab", ("model",)),
    ("ssm_inner", ("model",)),
    ("state", ()),
    ("residual_seq", ()),          # block-boundary residual stream
)

# Megatron-style sequence parallelism: the residual stream between
# blocks is sharded over the model axis (16x smaller scan-boundary
# saves under remat; GSPMD all-gathers at attention/MLP entry and
# reduce-scatters after).  Dry-run variant ``sp`` (`launch/dryrun.py`).
ACT_RULES_SP: Rules = tuple(
    (("residual_seq", ("model",)) if name == "residual_seq"
     else (name, cands))
    for name, cands in ACT_RULES)

# Decode caches: baseline shards kv-heads (head_dim fallback);
# the seq-sharded variant is the split-KV/flash-decoding layout used in
# the hillclimb.
CACHE_RULES: Rules = (
    ("batch", (("pod", "data"), "data")),
    ("kv_heads", ("model",)),
    ("head_dim", ("model",)),
    ("cache_seq", ()),
    ("state", ()),
    ("ssm_inner", ("model",)),
    ("layers", ()),
)

CACHE_RULES_SEQSHARD: Rules = (
    ("batch", (("pod", "data"), "data")),
    ("cache_seq", ("model",)),
    ("kv_heads", ()),
    ("head_dim", ()),
    ("state", ()),
    ("ssm_inner", ("model",)),
    ("layers", ()),
)


def _axes_of(c: AxisCand) -> Tuple[str, ...]:
    return c if isinstance(c, tuple) else (c,)


def logical_spec(dims: Sequence[Optional[str]],
                 shape: Sequence[int],
                 rules: Rules,
                 mesh: Mesh) -> P:
    """Resolve logical dims -> PartitionSpec for a concrete shape."""
    assert len(dims) == len(shape), (dims, shape)
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used: set = set()
    out = []
    for dname, size in zip(dims, shape):
        assigned = None
        if dname is not None:
            for ld, cands in rules:
                if ld != dname:
                    continue
                for cand in cands:
                    axs = _axes_of(cand)
                    if any(a in used or a not in mesh_sizes for a in axs):
                        continue
                    n = int(np.prod([mesh_sizes[a] for a in axs]))
                    if n > 1 and size % n == 0:
                        assigned = cand
                        used.update(axs)
                        break
                break  # first matching rule only
        out.append(assigned)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(dims: Sequence[Optional[str]], shape: Sequence[int],
                   rules: Rules, mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(dims, shape, rules, mesh))


@dataclasses.dataclass
class Sharder:
    """Threaded through model code; no-op when mesh is None (CPU smoke)."""

    mesh: Optional[Mesh] = None
    act_rules: Rules = ACT_RULES
    cache_rules: Rules = CACHE_RULES
    weight_rules: Rules = WEIGHT_RULES

    def act(self, x: jax.Array, dims: Sequence[Optional[str]]) -> jax.Array:
        if self.mesh is None:
            return x
        s = named_sharding(dims, x.shape, self.act_rules, self.mesh)
        return jax.lax.with_sharding_constraint(x, s)

    def cache(self, x: jax.Array, dims: Sequence[Optional[str]]) -> jax.Array:
        if self.mesh is None:
            return x
        s = named_sharding(dims, x.shape, self.cache_rules, self.mesh)
        return jax.lax.with_sharding_constraint(x, s)

    def weight_sharding(self, dims, shape) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return named_sharding(dims, shape, self.weight_rules, self.mesh)


def tree_shardings(mesh: Mesh, tree_shapes, tree_dims, rules: Rules):
    """Map a pytree of shapes + a matching pytree of dim-tuples to
    NamedShardings (for in_shardings / eval_shape dry-runs)."""
    return jax.tree.map(
        lambda shp, dims: named_sharding(dims, shp.shape, rules, mesh),
        tree_shapes, tree_dims,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, jax.Array)),
    )
