"""Mixture-of-experts layer: top-k routing, capacity-bounded sort-based
dispatch, batched expert GEMMs, shared experts.

Dispatch is the scatter/gather (MegaBlocks-style) formulation rather
than the GShard one-hot einsum: tokens are replicated k ways, ranked
within their expert by a stable sort, dropped beyond ``capacity =
cf * T * k / E``, scattered into an (E, C, D) buffer, pushed through a
batched GEMM ``ecd,edf->ecf`` (MXU-friendly), and gathered back with
router-probability weighting.  FLOPs stay proportional to *active*
parameters, which is what the 6·N_active·D roofline accounting assumes.

Expert parallelism: the (E, C, D) buffer and (E, D, F) weights carry the
"experts" logical dim -> the ``model`` mesh axis when divisible (64
experts / 16-way TP for moonshot); qwen2-moe's 60 experts fall back per
the sharding rules to within-expert TP over ``expert_mlp``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import Sharder
from repro.models.layers import cast
from repro.models.params import Param, param

__all__ = ["init_moe", "moe_layer", "moe_capacity"]


def moe_capacity(tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    c = int(math.ceil(capacity_factor * tokens * top_k / n_experts))
    # multiple of 32: sublane-aligned AND divisible by the (pod, data)
    # axes so the capacity dim of the dispatch buffer can shard.
    return max(32, ((c + 31) // 32) * 32)


def init_moe(key, d_model: int, d_ff: int, n_experts: int,
             n_shared: int = 0, act: str = "silu_glu",
             pad_to: int = 0) -> Dict:
    """``pad_to``: physically allocate max(n_experts, pad_to) experts so
    the expert dim divides the TP axis (e.g. 60 -> 64); the router only
    ever routes to the first n_experts (padding rows are dead weight,
    ~6% memory for qwen2-moe, traded for expert shards that need no
    dispatch collectives to line up with the TP axis)."""
    ks = jax.random.split(key, 5)
    e = max(n_experts, pad_to) if pad_to else n_experts
    d, f = d_model, d_ff
    p = {
        "router": param(ks[0], (d, n_experts), ("embed", "experts"),
                        scale=0.02),
        "w_gate": param(ks[1], (e, d, f), ("experts", "embed",
                                           "expert_mlp")),
        "w_up": param(ks[2], (e, d, f), ("experts", "embed", "expert_mlp")),
        "w_down": param(ks[3], (e, f, d), ("experts", "expert_mlp",
                                           "embed")),
    }
    if n_shared > 0:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], d_model, n_shared * d_ff, act=act)
    return p


def _exclusive_cumsum(x):
    return jnp.cumsum(x) - x


def _rank_in_expert(flat_e: jax.Array, n: int, e: int) -> jax.Array:
    """Position of each routed token within its expert (stable order)."""
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = _exclusive_cumsum(counts)
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_e]
    return jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)


def _dispatch_combine(xt, top_p, top_i, wg, wu, wd, act, e, e_pad, cap,
                      shd):
    """Flat dispatch: scatter (T,D) tokens -> (E_pad, C, D) with global
    capacity, expert GEMMs, gather back."""
    from repro.models.layers import _ACTS
    t, d = xt.shape
    k = top_i.shape[-1]
    flat_e = top_i.reshape(-1)
    pos = _rank_in_expert(flat_e, t * k, e)
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e_pad * cap)   # drop->sink

    tok_idx = jnp.tile(jnp.arange(t, dtype=jnp.int32)[:, None],
                       (1, k)).reshape(-1)
    xin = xt[tok_idx]                                          # (T*k, D)
    buf = jnp.zeros((e_pad * cap + 1, d), xt.dtype).at[slot].add(
        jnp.where(keep[:, None], xin, 0))
    buf = buf[:-1].reshape(e_pad, cap, d)
    buf = shd.act(buf, ("experts", "moe_capacity", None))

    a = _ACTS[act.replace("_glu", "")]
    hid = a(jnp.einsum("ecd,edf->ecf", buf, wg)) \
        * jnp.einsum("ecd,edf->ecf", buf, wu)
    hid = shd.act(hid, ("experts", "moe_capacity", "expert_mlp"))
    out_buf = jnp.einsum("ecf,efd->ecd", hid, wd)              # (E, C, D)
    out_buf = shd.act(out_buf, ("experts", "moe_capacity", None))

    flat_out = out_buf.reshape(e_pad * cap, d)
    safe_slot = jnp.minimum(slot, e_pad * cap - 1)
    y_rep = jnp.where(keep[:, None], flat_out[safe_slot], 0)   # (T*k, D)
    w = top_p.reshape(-1)[:, None].astype(xt.dtype)
    return jnp.zeros((t, d), xt.dtype).at[tok_idx].add(y_rep * w)


def moe_layer(p: Dict, x: jax.Array, *, n_experts: int, top_k: int,
              capacity_factor: float, act: str, shd: Sharder,
              router_dtype=jnp.float32, pad_to: int = 0,
              dispatch: str = "flat") -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss).

    ``dispatch='flat'``: global capacity over all B*S tokens (best load
    balance; the scatter crosses data shards -> buffer collectives).
    ``dispatch='grouped'``: GShard-style per-sequence groups — routing
    capacity is per group, the scatter is group-local, and the
    (B, E, C, D) buffer is (batch x expert)-sharded with no resharding
    before the GEMM.  Trades a little capacity headroom for less
    dispatch traffic (compare the two with `repro.launch.dryrun`).
    """
    b, s, d = x.shape
    t = b * s
    e = n_experts
    e_pad = max(e, pad_to) if pad_to else e

    logits = jnp.einsum("bsd,de->bse", x.astype(router_dtype),
                        p["router"].value.astype(router_dtype))
    probs = jax.nn.softmax(logits, axis=-1)                    # (B, S, E)
    top_p, top_i = jax.lax.top_k(probs, top_k)                 # (B, S, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    me = probs.reshape(t, e).mean(axis=0)
    ce = jnp.zeros((e,), router_dtype).at[top_i.reshape(-1)].add(
        1.0 / (t * top_k))
    aux = e * jnp.sum(me * ce)

    wg = cast(p["w_gate"], x.dtype)
    wu = cast(p["w_up"], x.dtype)
    wd = cast(p["w_down"], x.dtype)

    if dispatch == "grouped":
        from repro.models.layers import _ACTS
        a = _ACTS[act.replace("_glu", "")]
        cap = moe_capacity(s, e, top_k, capacity_factor)

        def scatter_group(xg, ig):                  # (S, D), (S, k)
            flat_e = ig.reshape(-1)
            pos = _rank_in_expert(flat_e, s * top_k, e)
            keep = pos < cap
            slot = jnp.where(keep, flat_e * cap + pos, e_pad * cap)
            tok = jnp.tile(jnp.arange(s, dtype=jnp.int32)[:, None],
                           (1, top_k)).reshape(-1)
            bufg = jnp.zeros((e_pad * cap + 1, d), xg.dtype).at[slot].add(
                jnp.where(keep[:, None], xg[tok], 0))
            return bufg[:-1].reshape(e_pad, cap, d), slot, keep, tok

        buf, slot, keep, tok = jax.vmap(scatter_group)(x, top_i)
        buf = shd.act(buf, ("batch", "experts", None, None))
        hid = a(jnp.einsum("gecd,edf->gecf", buf, wg)) \
            * jnp.einsum("gecd,edf->gecf", buf, wu)
        hid = shd.act(hid, ("batch", "experts", None, "expert_mlp"))
        out_buf = jnp.einsum("gecf,efd->gecd", hid, wd)
        out_buf = shd.act(out_buf, ("batch", "experts", None, None))

        def gather_group(og, slotg, keepg, tokg, pg):
            flat = og.reshape(e_pad * cap, d)
            safe = jnp.minimum(slotg, e_pad * cap - 1)
            y_rep = jnp.where(keepg[:, None], flat[safe], 0)
            w = pg.reshape(-1)[:, None].astype(og.dtype)
            return jnp.zeros((s, d), og.dtype).at[tokg].add(y_rep * w)

        y = jax.vmap(gather_group)(out_buf, slot, keep, tok, top_p)
        y = shd.act(y, ("batch", "residual_seq", "embed"))
    else:
        cap = moe_capacity(t, e, top_k, capacity_factor)
        y = _dispatch_combine(x.reshape(t, d), top_p.reshape(t, top_k),
                              top_i.reshape(t, top_k), wg, wu, wd, act,
                              e, e_pad, cap, shd)
        y = y.reshape(b, s, d)

    if "shared" in p:
        from repro.models.layers import mlp
        y = y + mlp(p["shared"], x, act, shd)

    return shd.act(y, ("batch", "residual_seq", "embed")), \
        aux.astype(jnp.float32)
