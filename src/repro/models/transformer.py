"""Decoder-only LM covering the dense / moe / ssm / hybrid families.

One scan-over-layers with stacked Params keeps the HLO size O(1 layer)
for every assigned arch (80-layer qwen1.5-110b compiles in seconds);
per-layer heterogeneity (hymba's sliding-vs-global windows, moonlight's
leading dense layer) is expressed as scanned per-layer scalars or a
small prefix stack, never as unrolled layers.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.distributed.sharding import Sharder
from repro.models.config import ModelConfig
from repro.models.layers import (FLOAT32_READS, AttnConfig, attention,
                                 attention_decode, cache_seq_axis, cast,
                                 count_cast_bytes, stored_order,
                                 init_attention, init_mla, init_mlp,
                                 mla_attention, mla_decode, mlp, read_layer,
                                 repeated, rms_norm)
from repro.models.moe import init_moe, moe_layer
from repro.models.params import Param, param, stack_dims, tree_param_bytes
from repro.models.ssd import (SsdConfig, init_ssd, init_ssd_state,
                              ssd_block, ssd_decode)

__all__ = ["attn_config", "ssd_config", "init_lm", "lm_logits", "lm_loss",
           "lm_prefill", "lm_decode_step", "init_lm_cache", "decode_weights",
           "hybrid_windows"]


def attn_config(cfg: ModelConfig) -> AttnConfig:
    return AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
        head_dim=cfg.qk_nope_head_dim if cfg.kv_lora_rank else cfg.hd,
        qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta, kv_repeat=cfg.kv_repeat,
        window=0,  # per-layer windows flow through window_override
        kv_lora_rank=cfg.kv_lora_rank, rope_dim=cfg.qk_rope_head_dim,
        v_dim=cfg.v_head_dim, norm_eps=cfg.norm_eps,
    )


def attn_state(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    """Name -> per-position shape of what the decode cache holds for one
    attention layer: the latent ``c_kv`` and the shared rotated key
    ``k_rope`` for latent attention, else each KV head's ``k`` and
    ``v``."""
    if cfg.kv_lora_rank:
        return {"c_kv": (cfg.kv_lora_rank,),
                "k_rope": (cfg.qk_rope_head_dim,)}
    kv = cfg.n_kv * max(cfg.kv_repeat, 1)
    return {"k": (kv, cfg.hd), "v": (kv, cfg.hd)}


def cache_dims(cfg: ModelConfig) -> Dict[str, Tuple[Optional[str], ...]]:
    """Name -> logical dims of each stacked attention leaf of the decode
    cache, in its stored axis order (`cache_seq_axis`)."""
    per = (None,) if cfg.kv_lora_rank else ("kv_heads", "head_dim")
    out = {}
    for name, shape in attn_state(cfg).items():
        dims = stored_order(("layers", "batch", "cache_seq") + per,
                           cache_seq_axis(shape[-1]))
        out[name] = out[name + "_pre"] = dims
    return out


def ssd_config(cfg: ModelConfig) -> SsdConfig:
    return SsdConfig(d_model=cfg.d_model, ssm_state=cfg.ssm_state,
                     ssm_conv=cfg.ssm_conv, expand=cfg.ssm_expand,
                     head_dim=cfg.ssm_head_dim, chunk=cfg.ssm_chunk)


def hybrid_windows(cfg: ModelConfig, seq_len: int, n_layers: int
                   ) -> jnp.ndarray:
    """Per-layer attention window scalars (traced through the layer
    scan).  A window >= seq_len acts as full causal attention — NOTE:
    these are traced values, so the "0 means no window" static
    convention does not apply; full attention is encoded as seq_len."""
    full = max(int(seq_len), 1)
    if cfg.family != "hybrid" or cfg.swa_window <= 0:
        return jnp.full((n_layers,), full, jnp.int32)
    glb = {0, n_layers // 2, n_layers - 1}
    w = [full if i in glb else min(cfg.swa_window, full)
         for i in range(n_layers)]
    return jnp.asarray(w, jnp.int32)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, moe: bool) -> Dict:
    ks = jax.random.split(key, 8)
    blk: Dict = {"ln1": param(ks[0], (cfg.d_model,), ("embed",),
                              init="ones")}
    fam = cfg.family
    if fam in ("dense", "moe", "hybrid", "encdec"):
        init_attn = init_mla if cfg.kv_lora_rank else init_attention
        blk["attn"] = init_attn(ks[1], attn_config(cfg))
        blk["ln2"] = param(ks[2], (cfg.d_model,), ("embed",), init="ones")
        if moe:
            blk["moe"] = init_moe(ks[3], cfg.d_model, cfg.d_ff_expert,
                                  cfg.n_experts, cfg.n_shared, cfg.act,
                                  pad_to=cfg.pad_experts_to,
                                  held=cfg.experts_held,
                                  scoring=cfg.router_scoring)
        else:
            blk["mlp"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.act)
    if fam == "ssm":
        blk["ssd"] = init_ssd(ks[4], ssd_config(cfg))
    if fam == "hybrid":
        blk["ssd"] = init_ssd(ks[4], ssd_config(cfg))
        blk["norm_a"] = param(ks[5], (cfg.d_model,), ("embed",),
                              init="ones")
        blk["norm_m"] = param(ks[6], (cfg.d_model,), ("embed",),
                              init="ones")
        blk["beta_a"] = param(ks[7], (cfg.d_model,), ("embed",),
                              init="ones")
        blk["beta_m"] = param(ks[7], (cfg.d_model,), ("embed",),
                              init="ones")
    return blk


def _stacked_blocks(key, cfg: ModelConfig, n: int, moe: bool):
    keys = jax.random.split(key, n)
    blocks = jax.vmap(lambda k: _init_block(k, cfg, moe))(keys)
    return stack_dims(blocks)


def init_lm(key, cfg: ModelConfig) -> Dict:
    """Parameters for a decoder-only LM (all non-encdec families)."""
    ks = jax.random.split(key, 5)
    p: Dict = {
        "embed": param(ks[0], (cfg.vocab, cfg.d_model), ("vocab", "embed"),
                       init="embed"),
        "final_norm": param(ks[1], (cfg.d_model,), ("embed",), init="ones"),
        "lm_head": param(ks[2], (cfg.d_model, cfg.vocab),
                         ("embed", "vocab"),
                         scale=1.0 / math.sqrt(cfg.d_model)),
    }
    n_moe = 0
    if cfg.family == "moe":
        n_moe = cfg.n_layers - cfg.first_dense_layers
        if cfg.first_dense_layers:
            p["prefix_blocks"] = _stacked_blocks(
                ks[3], cfg, cfg.first_dense_layers, moe=False)
        p["blocks"] = _stacked_blocks(ks[4], cfg, n_moe, moe=True)
    else:
        p["blocks"] = _stacked_blocks(ks[4], cfg, cfg.n_layers, moe=False)
    return p


# ---------------------------------------------------------------------------
# blocks (train / prefill path)
# ---------------------------------------------------------------------------


def _full_attention(cfg: ModelConfig) -> bool:
    """True when every layer statically runs full (unwindowed)
    attention.  `hybrid_windows` then encodes "full" as a *traced*
    window >= seq — semantically a no-op, but it defeats the static
    window==0 gate that lets `attention` route through the tuned
    flash_attention kernel.  Drop the override entirely in that case
    so the jnp and tuned paths both see the static full-causal mask."""
    return cfg.family != "hybrid" or cfg.swa_window <= 0


def _block_apply(blk: Dict, h: jax.Array, window, cfg: ModelConfig,
                 shd: Sharder, moe: bool, collect_kv: bool = False):
    """One layer; returns (h, aux_loss, (kv, ssm_state)) — the last two
    are None unless ``collect_kv`` (prefill handoff)."""
    acfg = attn_config(cfg)
    if _full_attention(cfg):
        window = None               # static full attention (cfg.window=0)
    aux = jnp.zeros((), jnp.float32)
    kv = sstate = None
    fam = cfg.family
    if fam == "ssm":
        x = rms_norm(h, blk["ln1"], cfg.norm_eps)
        with obs.scope("ssm"):
            if collect_kv:
                y, sstate = ssd_block(blk["ssd"], x, ssd_config(cfg), shd,
                                      return_state=True)
            else:
                y = ssd_block(blk["ssd"], x, ssd_config(cfg), shd)
        return h + y, aux, (kv, sstate)
    x = rms_norm(h, blk["ln1"], cfg.norm_eps)
    with obs.scope("attn"):
        if cfg.kv_lora_rank:
            a = mla_attention(blk["attn"], x, acfg, shd,
                              return_kv=collect_kv)
        else:
            a = attention(blk["attn"], x, acfg, shd,
                          window_override=window, return_kv=collect_kv)
        if collect_kv:
            a, kv = a
    if fam == "hybrid":
        with obs.scope("ssm"):
            if collect_kv:
                m, sstate = ssd_block(blk["ssd"], x, ssd_config(cfg), shd,
                                      return_state=True)
            else:
                m = ssd_block(blk["ssd"], x, ssd_config(cfg), shd)
        h = h + _mix(blk, a, m, h.dtype)
    else:
        h = h + a
    y, aux = _ffn(blk, rms_norm(h, blk["ln2"], cfg.norm_eps), cfg, shd, moe)
    return h + y, aux, (kv, sstate)


def _ffn(blk: Dict, x: jax.Array, cfg: ModelConfig, shd: Sharder,
         moe: bool):
    """The layer's MLP or expert layer: (y, aux_loss)."""
    with obs.scope("mlp"):
        if moe:
            return moe_layer(blk["moe"], x, n_experts=cfg.n_experts,
                             top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor,
                             act=cfg.act, shd=shd,
                             pad_to=cfg.pad_experts_to,
                             dispatch=cfg.moe_dispatch,
                             scoring=cfg.router_scoring,
                             routed_scaling=cfg.routed_scaling,
                             held=cfg.experts_held)
        return mlp(blk["mlp"], x, cfg.act, shd), jnp.zeros((), jnp.float32)


def _mix(blk: Dict, a: jax.Array, m: jax.Array, dtype) -> jax.Array:
    """Hybrid layer: the normed, scaled mean of the attention and SSM
    heads' outputs."""
    from repro.models.layers import _rms
    with obs.scope("mix"):
        return 0.5 * (_rms(a, blk["norm_a"].value)
                      * cast(blk["beta_a"], dtype)
                      + _rms(m, blk["norm_m"].value)
                      * cast(blk["beta_m"], dtype))


def _scan_blocks(blocks, h, windows, cfg: ModelConfig, shd: Sharder,
                 moe: bool, collect_kv: bool = False):
    """lax.scan over stacked layer params (+ per-layer window scalars)."""

    def body(carry, xs):
        hh, aux = carry
        blk, win = xs
        with obs.scope("layer"):
            hh, aux_l, ys = _block_apply(blk, hh, win, cfg, shd, moe,
                                         collect_kv)
        return (hh, aux + aux_l), ys

    if cfg.remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif cfg.remat == "dots":
        # selective: save matmul outputs, recompute only elementwise —
        # trades activation memory for less backward recompute traffic.
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_saveable)
    with repeated(windows.shape[0]):
        (h, aux), kvs = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                     (blocks, windows))
    return h, aux, kvs


def _embed(params, tokens, cfg: ModelConfig, shd: Sharder, dtype,
           rows_first: bool = False) -> jax.Array:
    """The tokens' embedding rows in ``dtype``.  ``rows_first`` gathers
    the rows before converting them (the same numbers), so a decode step
    converts a few rows and not the whole table."""
    with obs.scope("embed"):
        if rows_first:
            h = params["embed"].value[tokens]
            with obs.scope("cast"):
                h = h.astype(dtype)
        else:
            h = cast(params["embed"], dtype)[tokens]
        return shd.act(h, ("batch", "residual_seq", "embed"))


def _head(params, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm, then the logits over the vocabulary."""
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    with obs.scope("head"):
        return jnp.einsum("bsd,dv->bsv", h, cast(params["lm_head"], h.dtype))


def lm_logits(params: Dict, tokens: jax.Array, cfg: ModelConfig,
              shd: Sharder, collect_kv: bool = False,
              inputs_embeds: Optional[jax.Array] = None):
    """Forward pass.  tokens: (B, S) int32 -> logits (B, S, V)."""
    dtype = jnp.dtype(cfg.dtype)
    h = (inputs_embeds.astype(dtype) if inputs_embeds is not None
         else _embed(params, tokens, cfg, shd, dtype))
    b, s, _ = h.shape
    aux_total = jnp.zeros((), jnp.float32)
    kvs = None
    if "prefix_blocks" in params:
        nl = cfg.first_dense_layers
        h, aux, kv_pre = _scan_blocks(
            params["prefix_blocks"], h,
            jnp.full((nl,), s, jnp.int32), cfg, shd, moe=False,
            collect_kv=collect_kv)
        aux_total += aux
    else:
        kv_pre = None
    n_main = (cfg.n_layers - cfg.first_dense_layers
              if cfg.family == "moe" else cfg.n_layers)
    windows = hybrid_windows(cfg, s, n_main)
    h, aux, kvs = _scan_blocks(params["blocks"], h, windows, cfg, shd,
                               moe=(cfg.family == "moe"),
                               collect_kv=collect_kv)
    aux_total += aux
    logits = shd.act(_head(params, h, cfg), ("batch", "seq", "vocab"))
    if collect_kv:
        return logits, aux_total, (kv_pre, kvs)
    return logits, aux_total


def lm_loss(params: Dict, batch: Dict, cfg: ModelConfig, shd: Sharder
            ) -> Tuple[jax.Array, Dict]:
    """Next-token cross entropy (f32 logsumexp), plus MoE aux loss."""
    tokens = batch["tokens"]
    # forward the full sequence (keeps S a chunk multiple); the last
    # position has no target and is sliced off the logits.
    logits, aux = lm_logits(params, tokens, cfg, shd,
                            inputs_embeds=batch.get("frames"))
    targets = tokens[:, 1:]
    lf = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = (lse - gold).mean()
    loss = nll + 0.01 * aux
    return loss, {"nll": nll, "aux": aux, "loss": loss}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def _cache_len(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.family == "hybrid":
        return min(seq_len, cfg.decode_cache_cap)
    return seq_len


def _narrowed_leaves(params: Dict, dtype):
    """(path, Param) of each layer-weight leaf that the layers read
    through `cast` to ``dtype`` and that is stored wider than it."""
    for name in ("prefix_blocks", "blocks"):
        flat, _ = jax.tree_util.tree_flatten_with_path(
            params.get(name, {}), is_leaf=lambda x: isinstance(x, Param))
        for path, p in flat:
            key = path[-1].key
            if key not in FLOAT32_READS and \
                    p.value.dtype.itemsize > dtype.itemsize:
                yield (name,) + tuple(k.key for k in path), p


def decode_weights(params: Dict, cfg: ModelConfig) -> Dict:
    """The stacked layer weights that the layers read in ``cfg.dtype``,
    converted once: the leaves of ``params["blocks"]`` (and
    ``"prefix_blocks"``) stored wider than ``cfg.dtype``, under the same
    paths and dims; empty when no leaf is.  Prefill hands it to the
    decode state, and each decode step reads it in place of converting
    the stored stack again."""
    dtype = jnp.dtype(cfg.dtype)
    out: Dict = {}
    for path, p in _narrowed_leaves(params, dtype):
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = Param(cast(p, dtype), p.dims)
    return out


def _carried(params: Dict, cfg: ModelConfig) -> Dict:
    """The decode state's entry for `decode_weights`, if it has one."""
    weights = decode_weights(params, cfg)
    return {"weights": weights} if weights else {}


def _overlay(stored, carried):
    """``stored`` with each leaf that ``carried`` holds in its place."""
    if carried is None:
        return stored
    if isinstance(carried, Param):
        return carried
    return {k: _overlay(v, carried.get(k)) for k, v in stored.items()}


def init_lm_cache(cfg: ModelConfig, batch: int, seq_len: int,
                  dtype=None, params: Optional[Dict] = None) -> Dict:
    """Decode cache: ring/linear KV per attention layer + SSM states;
    given the ``params`` it serves, also the compute-precision layer
    weights that prefill hands over (`decode_weights`)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    n_main = (cfg.n_layers - cfg.first_dense_layers
              if cfg.family == "moe" else cfg.n_layers)
    cache: Dict = {"pos": jnp.zeros((), jnp.int32)}
    sc = _cache_len(cfg, seq_len)
    if cfg.family in ("dense", "moe", "hybrid"):
        per_position = 0
        for name, shape in attn_state(cfg).items():
            seq = cache_seq_axis(shape[-1])
            cache[name] = jnp.zeros(
                stored_order((n_main, batch, sc) + shape, seq), dtype)
            if cfg.first_dense_layers:
                cache[name + "_pre"] = jnp.zeros(stored_order(
                    (cfg.first_dense_layers, batch, sc) + shape, seq), dtype)
            per_position += int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        obs.gauge("serve.cache_bytes_per_token",
                  per_position * (n_main + cfg.first_dense_layers))
    if cfg.family in ("ssm", "hybrid"):
        scfg = ssd_config(cfg)
        st = init_ssd_state(batch, scfg, dtype)
        n_l = cfg.n_layers
        cache["ssm"] = jnp.tile(st["ssm"][None], (n_l, 1, 1, 1, 1))
        cache["conv"] = jnp.tile(st["conv"][None], (n_l, 1, 1, 1))
    if params is not None:
        cache.update(_carried(params, cfg))
    return cache


def _cache_positions(cfg: ModelConfig, cache: Dict) -> int:
    """The positions the decode cache's attention leaves hold (0 without
    attention)."""
    for name, shape in attn_state(cfg).items():
        if name in cache:
            return cache[name].shape[cache_seq_axis(shape[-1])]
    return 0


def _block_decode(blk, h, win, state, layer, pos, cfg: ModelConfig,
                  shd: Sharder, moe: bool):
    """One layer of a decode step over the stacked decode ``state``
    (attention state under `attn_state`'s names, ``ssm``, ``conv``):
    returns h and the state with layer ``layer`` updated in place."""
    acfg = attn_config(cfg)
    fam = cfg.family
    state = dict(state)

    def ssm(x):
        with obs.scope("ssm"):
            rows = {n: read_layer(state[n], layer) for n in ("ssm", "conv")}
            y, rows = ssd_decode(blk["ssd"], x, rows, ssd_config(cfg), shd)
            for n, row in rows.items():
                state[n] = jax.lax.dynamic_update_index_in_dim(
                    state[n], row.astype(state[n].dtype), layer, 0)
        return y

    x = rms_norm(h, blk["ln1"], cfg.norm_eps)
    if fam == "ssm":
        return h + ssm(x), state
    kn, vn = attn_state(cfg)
    with obs.scope("attn"):
        if cfg.kv_lora_rank:
            a, (state[kn], state[vn]) = mla_decode(
                blk["attn"], x, state[kn], state[vn], layer, pos, acfg, shd)
        else:
            a, (state[kn], state[vn]) = attention_decode(
                blk["attn"], x, state[kn], state[vn], layer, pos, acfg, shd,
                window_override=win, rolling=(fam == "hybrid"))
    if fam == "hybrid":
        h = h + _mix(blk, a, ssm(x), h.dtype)
    else:
        h = h + a
    y, _ = _ffn(blk, rms_norm(h, blk["ln2"], cfg.norm_eps), cfg, shd, moe)
    return h + y, state


def lm_decode_step(params: Dict, cache: Dict, token: jax.Array,
                   cfg: ModelConfig, shd: Sharder):
    """One decode step.  token: (B, 1) int32 -> (logits (B, 1, V), cache).

    The layers read the compute-precision weights that the cache
    carries (``cache["weights"]``, from `lm_prefill`) where the stored
    ones are wider; a cache without them is refused."""
    with obs.scope("decode"), \
            count_cast_bytes("serve.decode_weight_cast_bytes"):
        return _decode_step(params, cache, token, cfg, shd)


def _decode_step(params: Dict, cache: Dict, token: jax.Array,
                 cfg: ModelConfig, shd: Sharder):
    dtype = jnp.dtype(cfg.dtype)
    carried = cache.get("weights", {})
    if not carried and next(_narrowed_leaves(params, dtype), None):
        raise ValueError(
            f"{cfg.name}: the layer weights are stored wider than "
            f"{dtype.name} but the decode state carries no {dtype.name} "
            "copy of them; build it with lm_prefill, or init_lm_cache "
            "given the params")
    pos = cache["pos"]
    h = _embed(params, token, cfg, shd, dtype, rows_first=True)
    new_cache = dict(cache)
    fam = cfg.family

    def scan_stack(blocks, h, windows, state, moe):
        """The layer scan: the carry holds h, the layer index and the
        stacked ``state``, which each layer updates in place; the scanned
        inputs are the layer weights and windows alone."""
        obs.count("decode.cache_in_place", len(state))

        def body(carry, xs):
            hh, layer, st = carry
            blk, win = xs
            with obs.scope("layer"):
                hh, st = _block_decode(blk, hh, win, st, layer, pos, cfg,
                                       shd, moe)
            return (hh, layer + 1, st), None

        n = windows.shape[0]
        with repeated(n):
            (h, _, state), _ = jax.lax.scan(
                body, (h, jnp.zeros((), jnp.int32), state), (blocks, windows))
        return h, state

    n_main = (cfg.n_layers - cfg.first_dense_layers
              if fam == "moe" else cfg.n_layers)
    names = [n for n in attn_state(cfg) if n in cache]
    sc = _cache_positions(cfg, cache)
    if "prefix_blocks" in params:
        npre = cfg.first_dense_layers
        h, pre = scan_stack(_overlay(params["prefix_blocks"],
                                     carried.get("prefix_blocks")), h,
                            jnp.full((npre,), max(sc, 1), jnp.int32),
                            {n: cache[n + "_pre"] for n in names},
                            moe=False)
        new_cache.update({n + "_pre": x for n, x in pre.items()})
    windows = hybrid_windows(cfg, max(sc, 1), n_main)
    h, state = scan_stack(
        _overlay(params["blocks"], carried.get("blocks")), h, windows,
        {n: cache[n] for n in names + ["ssm", "conv"] if n in cache},
        moe=(fam == "moe"))
    new_cache.update(state)
    new_cache["pos"] = pos + 1
    return _head(params, h, cfg), new_cache


def lm_prefill(params: Dict, tokens: jax.Array, cfg: ModelConfig,
               shd: Sharder, max_len: Optional[int] = None,
               inputs_embeds: Optional[jax.Array] = None):
    """Prefill: full forward collecting per-layer KV + SSM states ->
    (logits, cache) ready for ``lm_decode_step`` at position s.  The
    cache also carries the compute-precision layer weights
    (`decode_weights`) where the stored ones are wider.

    ``max_len`` sizes the cache for subsequent decode steps (default:
    exactly the prompt length — the dry-run decode-shape convention)."""
    with obs.scope("prefill"):
        b, s = (tokens.shape if inputs_embeds is None
                else inputs_embeds.shape[:2])
        logits, _aux, (pre_ys, main_ys) = lm_logits(
            params, tokens, cfg, shd, collect_kv=True,
            inputs_embeds=inputs_embeds)
        with obs.scope("kv_fill"):
            cache = _fill_cache(cfg, b, s, max(s, max_len or 0), pre_ys,
                                main_ys)
        with obs.scope("weights"):
            carried = _carried(params, cfg)
        if carried:
            obs.count("serve.carried_weight_bytes",
                      tree_param_bytes(carried))
        cache.update(carried)
        return logits, cache


def _fill_cache(cfg: ModelConfig, b: int, s: int, max_len: int, pre_ys,
                main_ys) -> Dict:
    """The decode cache, sized for ``max_len``, holding the attention and
    SSM states that a prefill of ``s`` positions collected."""
    cache = init_lm_cache(cfg, b, max_len)

    def fill(name, x, width):
        """x (L, B, s, ..., width) from prefill into leaf ``name``, in its
        stored order."""
        seq = cache_seq_axis(width)
        sc = cache[name].shape[seq]
        if sc < s:
            # capped ring cache: position p lives at slot p % sc; the
            # last sc positions land at roll(linear_tail, s % sc).
            x = jnp.roll(x[:, :, -sc:], s % sc, axis=2)
        x = jnp.moveaxis(x.astype(cache[name].dtype), 2, seq)
        if sc > s:
            idx = [slice(None)] * x.ndim
            idx[seq] = slice(0, s)
            x = cache[name].at[tuple(idx)].set(x)
        cache[name] = x

    for suffix, ys in (("", main_ys), ("_pre", pre_ys)):
        kvs = ys[0] if ys is not None else None
        for (name, shape), x in zip(attn_state(cfg).items(), kvs or ()):
            if name + suffix in cache:
                fill(name + suffix, x, shape[-1])
    sstates = main_ys[1] if main_ys is not None else None
    if sstates is not None and "ssm" in cache:
        for name in ("ssm", "conv"):
            cache[name] = sstates[name].astype(cache[name].dtype)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return cache
