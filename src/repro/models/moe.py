"""Mixture-of-experts layer: routing over all of the router's experts,
the part of the result that the experts held here give, shared experts.

Routing (`route`) scores every token against all of the router's
experts: softmax, or DeepSeek-V3's sigmoid ``noaux_tc`` rule, where the
scores plus ``e_score_correction_bias`` pick the top k and the unbiased
scores of the picked experts weigh them.  The weights are normalised to
sum 1, then scaled by ``routed_scaling``.

Held experts: a program holds the weights of a contiguous range
``[first, stop)`` of the router's experts (one chip's share under expert
parallelism; all of them by default) and computes, for every token
routed to one of them, that expert's weighted output.  What the other
experts give is not computed here.

Dispatch, chosen by a property of the mesh:

- no mesh, or one that does not shard the experts axis: dropless.  A
  token's k choices are k rows; the rows routed to held experts are
  sorted by expert and gathered, run through `jax.lax.ragged_dot`
  grouped by expert, and weighted back onto their tokens.  No row is
  dropped.  T tokens make at most T k rows, so tokens go through in
  chunks of at most ``GROUP_ROWS`` rows, which bounds the temporaries.
  A program of at most ``DENSE_TOKENS`` tokens (a decode step) instead
  runs every held expert on every token, weighted by the routing (zero
  where the token picked another expert): the products are bound by
  reading each held expert's weights once, as the grouped ones are when
  every expert is picked, and their time does not depend on the routing.
- a mesh that shards the experts axis (training, `launch/dryrun`): the
  capacity dispatch (MegaBlocks-style scatter into an (E, C, D) buffer,
  batched GEMM ``ecd,edf->ecf``, gather), which keeps each expert's rows
  on its shard and drops rows beyond ``capacity = cf * T * k / E``.
  ``dispatch='grouped'`` is its GShard-style per-sequence variant.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.distributed.sharding import Sharder, named_sharding
from repro.models.layers import _ACTS, cast, mlp, traced_count
from repro.models.params import param

__all__ = ["init_moe", "moe_layer", "moe_capacity", "route",
           "GROUP_ROWS", "DENSE_TOKENS"]

GROUP_ROWS = 65536   # most rows one grouped product takes (a token chunk)
# Most tokens for which every held expert runs on every token: below the
# rows at which a chip's matrix unit outruns its memory (about 240 on a
# v5e, 167 on a v5p), so the dense products cost the weight reads alone.
DENSE_TOKENS = 128


def moe_capacity(tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    c = int(math.ceil(capacity_factor * tokens * top_k / n_experts))
    # multiple of 32: sublane-aligned AND divisible by the (pod, data)
    # axes so the capacity dim of the dispatch buffer can shard.
    return max(32, ((c + 31) // 32) * 32)


def init_moe(key, d_model: int, d_ff: int, n_experts: int,
             n_shared: int = 0, act: str = "silu_glu",
             pad_to: int = 0, held: Optional[Tuple[int, int]] = None,
             scoring: str = "softmax") -> Dict:
    """The router over all ``n_experts`` (and, for ``sigmoid`` scoring,
    its ``e_score_correction_bias``), the weights of the ``held`` range
    of experts (all by default), and the shared experts.

    ``pad_to``: physically allocate max(held, pad_to) experts so the
    expert dim divides the TP axis (e.g. 60 -> 64); the router never
    routes to the padding (dead weight, ~6% memory for qwen2-moe,
    traded for expert shards that line up with the TP axis)."""
    ks = jax.random.split(key, 5)
    first, stop = held or (0, n_experts)
    e = max(stop - first, pad_to)
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
    if scoring == "sigmoid":
        p["e_score_correction_bias"] = param(ks[0], (n_experts,),
                                             ("experts",), init="zeros")
    if n_shared > 0:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], d_model, n_shared * d_ff, act=act)
    return p


def route(p: Dict, x: jax.Array, *, top_k: int, scoring: str = "softmax",
          routed_scaling: float = 1.0, router_dtype=jnp.float32):
    """x: (..., D) -> (weights (..., k) float32, experts (..., k) int32,
    Switch-style load-balancing loss)."""
    logits = jnp.einsum("...d,de->...e", x.astype(router_dtype),
                        p["router"].value.astype(router_dtype))
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_i = jax.lax.top_k(
            scores + p["e_score_correction_bias"].value, top_k)
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        probs = scores / scores.sum(-1, keepdims=True)
    elif scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, top_k)
    else:
        raise ValueError(f"router scoring {scoring!r}")
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    top_p = top_p * routed_scaling
    e = logits.shape[-1]
    me = probs.reshape(-1, e).mean(axis=0)
    ce = jnp.zeros((e,), router_dtype).at[top_i.reshape(-1)].add(
        1.0 / top_i.size)
    return top_p, top_i, e * jnp.sum(me * ce)


def _grouped_chunk(xt, local, w, wg, wu, wd, act):
    """Dropless expert part of T tokens: (T, D) float32.

    ``local`` (T, k) holds each choice's index among the held experts
    (outside [0, E_held) where it went elsewhere), ``w`` its weight."""
    t, d = xt.shape
    k = local.shape[-1]
    e = wg.shape[0]
    with obs.scope("group"):
        flat = local.reshape(-1)
        held = (flat >= 0) & (flat < e)
        key = jnp.where(held, flat, e)                 # elsewhere: last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
        rows = xt[order // k]                          # (T k, D)
    with obs.scope("experts"):
        a = _ACTS[act.replace("_glu", "")]
        hid = a(jax.lax.ragged_dot(rows, wg, sizes)) \
            * jax.lax.ragged_dot(rows, wu, sizes)
        out = jax.lax.ragged_dot(hid, wd, sizes)       # 0 past sum(sizes)
    with obs.scope("combine"):
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.size, dtype=order.dtype))
        out = out[back].reshape(t, k, d)
        wk = jnp.where(held.reshape(t, k), w, 0.0).astype(out.dtype)
        return jnp.einsum("tkd,tk->td", out, wk,
                          preferred_element_type=jnp.float32)


def _dense(xt, local, w, wg, wu, wd, act):
    """Every held expert on each of T tokens, weighted by the routing:
    (T, D) float32, as `_grouped_chunk` gives it."""
    e = wg.shape[0]
    with obs.scope("group"):
        picked = local[..., None] == jnp.arange(e, dtype=local.dtype)
        we = jnp.sum(jnp.where(picked, w[..., None], 0.0), axis=1)  # (T, E)
    with obs.scope("experts"):
        a = _ACTS[act.replace("_glu", "")]
        hid = a(jnp.einsum("td,edf->etf", xt, wg)) \
            * jnp.einsum("td,edf->etf", xt, wu)
        out = jnp.einsum("etf,efd->etd", hid, wd)
    with obs.scope("combine"):
        return jnp.einsum("etd,te->td", out, we.astype(out.dtype),
                          preferred_element_type=jnp.float32)


def _grouped(xt, local, w, wg, wu, wd, act):
    """`_dense` at most ``DENSE_TOKENS`` tokens, else `_grouped_chunk`
    over chunks of at most ``GROUP_ROWS`` rows."""
    t, k = local.shape
    if t <= DENSE_TOKENS:
        return _dense(xt, local, w, wg, wu, wd, act)
    traced_count("moe.grouped_rows", t * k)
    n = max(1, math.ceil(t * k / GROUP_ROWS))
    while t % n:
        n += 1
    if n == 1:
        return _grouped_chunk(xt, local, w, wg, wu, wd, act)
    c = t // n
    chunks = (xt.reshape(n, c, -1), local.reshape(n, c, k),
              w.reshape(n, c, k))
    y = jax.lax.map(lambda a: _grouped_chunk(*a, wg, wu, wd, act), chunks)
    return y.reshape(t, -1)


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


def _scatter(xg, flat, cap, e_pad):
    """Rows of ``xg`` (S, D) into an (E_pad, C, D) buffer by expert
    ``flat`` (S k,; E_pad = held elsewhere); (buffer, slot, keep, token)."""
    s, d = xg.shape
    n = flat.shape[0]
    k = n // s
    pos = _rank_in_expert(flat, n, e_pad + 1)
    keep = (pos < cap) & (flat < e_pad)
    slot = jnp.where(keep, flat * cap + pos, e_pad * cap)      # drop->sink
    tok = jnp.tile(jnp.arange(s, dtype=jnp.int32)[:, None],
                   (1, k)).reshape(-1)
    buf = jnp.zeros((e_pad * cap + 1, d), xg.dtype).at[slot].add(
        jnp.where(keep[:, None], xg[tok], 0))
    return buf[:-1].reshape(e_pad, cap, d), slot, keep, tok


def _gather(out_buf, slot, keep, tok, w, s):
    """Weighted rows of the (E_pad, C, D) buffer back onto ``s`` tokens."""
    e_pad, cap, d = out_buf.shape
    flat_out = out_buf.reshape(e_pad * cap, d)
    safe_slot = jnp.minimum(slot, e_pad * cap - 1)
    y_rep = jnp.where(keep[:, None], flat_out[safe_slot], 0)
    return jnp.zeros((s, d), out_buf.dtype).at[tok].add(
        y_rep * w.reshape(-1)[:, None].astype(out_buf.dtype))


def _capacity(x, local, w, wg, wu, wd, act, cap, shd, dispatch):
    """Capacity dispatch of (B, S, D) tokens: flat over all B S tokens
    (best balance; the scatter crosses data shards), or ``grouped`` per
    sequence (capacity per group, group-local scatter, the (B, E, C, D)
    buffer batch- and expert-sharded with no resharding)."""
    b, s, d = x.shape
    e_pad = wg.shape[0]
    flat = jnp.where((local >= 0) & (local < e_pad), local, e_pad)
    a = _ACTS[act.replace("_glu", "")]
    if dispatch == "grouped":
        buf, slot, keep, tok = jax.vmap(
            lambda xg, fg: _scatter(xg, fg.reshape(-1), cap, e_pad))(x, flat)
        buf = shd.act(buf, ("batch", "experts", None, None))
        hid = a(jnp.einsum("gecd,edf->gecf", buf, wg)) \
            * jnp.einsum("gecd,edf->gecf", buf, wu)
        hid = shd.act(hid, ("batch", "experts", None, "expert_mlp"))
        out = jnp.einsum("gecf,efd->gecd", hid, wd)
        out = shd.act(out, ("batch", "experts", None, None))
        return jax.vmap(lambda o, sl, kp, tk, wg_: _gather(o, sl, kp, tk,
                                                           wg_, s))(
            out, slot, keep, tok, w)
    buf, slot, keep, tok = _scatter(x.reshape(b * s, d), flat.reshape(-1),
                                    cap, e_pad)
    buf = shd.act(buf, ("experts", "moe_capacity", None))
    hid = a(jnp.einsum("ecd,edf->ecf", buf, wg)) \
        * jnp.einsum("ecd,edf->ecf", buf, wu)
    hid = shd.act(hid, ("experts", "moe_capacity", "expert_mlp"))
    out = jnp.einsum("ecf,efd->ecd", hid, wd)
    out = shd.act(out, ("experts", "moe_capacity", None))
    return _gather(out, slot, keep, tok, w, b * s).reshape(b, s, d)


def _experts_sharded(shd: Sharder, n: int) -> bool:
    if shd.mesh is None:
        return False
    spec = named_sharding(("experts",), (n,), shd.weight_rules,
                          shd.mesh).spec
    return len(spec) > 0 and spec[0] is not None


def moe_layer(p: Dict, x: jax.Array, *, n_experts: int, top_k: int,
              capacity_factor: float, act: str, shd: Sharder,
              router_dtype=jnp.float32, pad_to: int = 0,
              dispatch: str = "flat", scoring: str = "softmax",
              routed_scaling: float = 1.0,
              held: Optional[Tuple[int, int]] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss): the held experts' part of the
    routed output for every token, plus the shared experts.

    ``capacity_factor`` and ``dispatch`` apply to the capacity dispatch
    alone, which runs where the mesh shards the experts axis."""
    b, s, d = x.shape
    with obs.scope("router"):
        top_p, top_i, aux = route(p, x, top_k=top_k, scoring=scoring,
                                  routed_scaling=routed_scaling,
                                  router_dtype=router_dtype)
        local = top_i - (held[0] if held else 0)
    wg = cast(p["w_gate"], x.dtype)
    wu = cast(p["w_up"], x.dtype)
    wd = cast(p["w_down"], x.dtype)
    if _experts_sharded(shd, wg.shape[0]):
        cap = moe_capacity(b * s if dispatch != "grouped" else s,
                           n_experts, top_k, capacity_factor)
        y = _capacity(x, local, top_p, wg, wu, wd, act, cap, shd, dispatch)
    else:
        y = _grouped(x.reshape(b * s, d), local.reshape(b * s, top_k),
                     top_p.reshape(b * s, top_k), wg, wu, wd, act)
        y = y.astype(x.dtype).reshape(b, s, d)

    if "shared" in p:
        with obs.scope("shared"):
            y = y + mlp(p["shared"], x, act, shd)

    return shd.act(y, ("batch", "residual_seq", "embed")), \
        aux.astype(jnp.float32)
