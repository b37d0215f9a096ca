"""Encoder-decoder LM (whisper-tiny backbone).

Per the assignment the audio frontend is a STUB: ``input_specs()``
provides precomputed frame embeddings (B, enc_seq, d_model); the conv
stem exists in the codebase for completeness (``conv_frontend``) but is
not part of the dry-run path.  The transformer backbone is real:
bidirectional encoder, causal decoder with cross-attention, scan over
layers in both stacks.  RMSNorm replaces Whisper's LayerNorm (recorded
in DESIGN.md §8 — no pretrained weights are loaded, so parity of norm
flavour is immaterial).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import Sharder
from repro.models.config import ModelConfig
from repro.models.layers import (AttnConfig, attention, attention_decode,
                                 cache_seq_axis, init_attention, init_mlp,
                                 mlp, rms_norm, stored_order, _sdpa)
from repro.models.params import Param, param, stack_dims

__all__ = ["init_encdec", "encdec_loss", "encdec_prefill",
           "encdec_decode_step", "init_encdec_cache", "conv_frontend"]


def _acfg(cfg: ModelConfig, causal: bool) -> AttnConfig:
    return AttnConfig(d_model=cfg.d_model, n_heads=cfg.n_heads,
                      n_kv=cfg.n_kv, head_dim=cfg.hd,
                      rope_theta=cfg.rope_theta, causal=causal)


# ---------------------------------------------------------------------------
# optional conv stem (completeness only; stubbed in input_specs)
# ---------------------------------------------------------------------------


def conv_frontend(params: Dict, mel: jax.Array) -> jax.Array:
    """(B, T, n_mels) -> (B, T//2, d_model): two 1-D convs, GELU, stride 2."""
    x = mel
    for i, name in enumerate(("conv1", "conv2")):
        w = params[name].value.astype(x.dtype)      # (k, cin, cout)
        stride = 1 if i == 0 else 2
        x = jax.lax.conv_general_dilated(
            x, w, window_strides=(stride,), padding="SAME",
            dimension_numbers=("NTC", "TIO", "NTC"))
        x = jax.nn.gelu(x, approximate=True)
    return x


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_enc_block(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 4)
    return {
        "ln1": param(ks[0], (cfg.d_model,), ("embed",), init="ones"),
        "attn": init_attention(ks[1], _acfg(cfg, causal=False)),
        "ln2": param(ks[2], (cfg.d_model,), ("embed",), init="ones"),
        "mlp": init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.act),
    }


def _init_dec_block(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 6)
    return {
        "ln1": param(ks[0], (cfg.d_model,), ("embed",), init="ones"),
        "attn": init_attention(ks[1], _acfg(cfg, causal=True)),
        "ln_x": param(ks[2], (cfg.d_model,), ("embed",), init="ones"),
        "xattn": init_attention(ks[3], _acfg(cfg, causal=False)),
        "ln2": param(ks[4], (cfg.d_model,), ("embed",), init="ones"),
        "mlp": init_mlp(ks[5], cfg.d_model, cfg.d_ff, cfg.act),
    }


def init_encdec(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 8)
    enc_keys = jax.random.split(ks[0], cfg.enc_layers)
    dec_keys = jax.random.split(ks[1], cfg.n_layers)
    return {
        "enc_pos": param(ks[2], (cfg.enc_seq, cfg.d_model),
                         (None, "embed"), scale=0.02),
        "enc_blocks": stack_dims(jax.vmap(
            lambda k: _init_enc_block(k, cfg))(enc_keys)),
        "enc_norm": param(ks[3], (cfg.d_model,), ("embed",), init="ones"),
        "embed": param(ks[4], (cfg.vocab, cfg.d_model), ("vocab", "embed"),
                       init="embed"),
        "dec_blocks": stack_dims(jax.vmap(
            lambda k: _init_dec_block(k, cfg))(dec_keys)),
        "final_norm": param(ks[5], (cfg.d_model,), ("embed",),
                            init="ones"),
        "lm_head": param(ks[6], (cfg.d_model, cfg.vocab),
                         ("embed", "vocab"),
                         scale=1.0 / math.sqrt(cfg.d_model)),
    }


# ---------------------------------------------------------------------------
# cross attention
# ---------------------------------------------------------------------------


def _cross_kv(p: Dict, ctx: jax.Array):
    k = jnp.einsum("bsd,dhk->bshk", ctx, p["wk"].value.astype(ctx.dtype))
    v = jnp.einsum("bsd,dhk->bshk", ctx, p["wv"].value.astype(ctx.dtype))
    return k, v


def _cross_attention(p: Dict, x: jax.Array, ek: jax.Array, ev: jax.Array,
                     cfg: ModelConfig) -> jax.Array:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].value.astype(x.dtype))
    scale = 1.0 / math.sqrt(cfg.hd)
    out = _sdpa(q, ek, ev, jnp.zeros((), jnp.float32), scale)
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype),
                      p["wo"].value.astype(x.dtype))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def encode(params: Dict, frames: jax.Array, cfg: ModelConfig,
           shd: Sharder) -> jax.Array:
    """frames: (B, T_enc, d_model) stub embeddings -> encoder output."""
    h = frames.astype(jnp.dtype(cfg.dtype))
    h = h + params["enc_pos"].value.astype(h.dtype)[None, :h.shape[1]]
    h = shd.act(h, ("batch", "residual_seq", "embed"))

    def body(hh, blk):
        a = attention(blk["attn"], rms_norm(hh, blk["ln1"]),
                      _acfg(cfg, causal=False), shd)
        hh = hh + a
        hh = hh + mlp(blk["mlp"], rms_norm(hh, blk["ln2"]), cfg.act, shd)
        return hh, None

    if cfg.remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(body, h, params["enc_blocks"])
    return rms_norm(h, params["enc_norm"])


def _decode_stack(params, h, enc_out, cfg, shd, collect_kv=False):
    def body(carry, blk):
        hh, aux = carry
        a_in = rms_norm(hh, blk["ln1"])
        if collect_kv:
            a, kv = attention(blk["attn"], a_in, _acfg(cfg, True), shd,
                              return_kv=True)
        else:
            a = attention(blk["attn"], a_in, _acfg(cfg, True), shd)
            kv = None
        hh = hh + a
        x_in = rms_norm(hh, blk["ln_x"])
        ek, ev = _cross_kv(blk["xattn"], enc_out)
        hh = hh + _cross_attention(blk["xattn"], x_in, ek, ev, cfg)
        hh = hh + mlp(blk["mlp"], rms_norm(hh, blk["ln2"]), cfg.act, shd)
        ys = (kv, (ek, ev)) if collect_kv else None
        return (hh, aux), ys

    if cfg.remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    (h, _), ys = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                              params["dec_blocks"])
    return h, ys


def encdec_logits(params: Dict, frames: jax.Array, tokens: jax.Array,
                  cfg: ModelConfig, shd: Sharder, collect_kv=False):
    enc_out = encode(params, frames, cfg, shd)
    h = params["embed"].value.astype(jnp.dtype(cfg.dtype))[tokens]
    h = shd.act(h, ("batch", "residual_seq", "embed"))
    h, ys = _decode_stack(params, h, enc_out, cfg, shd, collect_kv)
    h = rms_norm(h, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", h,
                        params["lm_head"].value.astype(h.dtype))
    logits = shd.act(logits, ("batch", "seq", "vocab"))
    return (logits, ys) if collect_kv else logits


def encdec_loss(params: Dict, batch: Dict, cfg: ModelConfig, shd: Sharder
                ) -> Tuple[jax.Array, Dict]:
    tokens = batch["tokens"]
    logits = encdec_logits(params, batch["frames"], tokens, cfg, shd)
    targets = tokens[:, 1:]
    lf = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = (lse - gold).mean()
    return nll, {"nll": nll, "loss": nll,
                 "aux": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def init_encdec_cache(cfg: ModelConfig, batch: int, seq_len: int,
                      dtype=None) -> Dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    kv, hd = cfg.n_kv, cfg.hd
    self_kv = stored_order((cfg.n_layers, batch, seq_len, kv, hd),
                           cache_seq_axis(hd))
    return {
        "pos": jnp.zeros((), jnp.int32),
        "k": jnp.zeros(self_kv, dtype),
        "v": jnp.zeros(self_kv, dtype),
        "ek": jnp.zeros((cfg.n_layers, batch, cfg.enc_seq, kv, hd), dtype),
        "ev": jnp.zeros((cfg.n_layers, batch, cfg.enc_seq, kv, hd), dtype),
    }


def encdec_prefill(params: Dict, frames: jax.Array, tokens: jax.Array,
                   cfg: ModelConfig, shd: Sharder, max_len: int = 0):
    b, s = tokens.shape
    (logits, ys) = encdec_logits(params, frames, tokens, cfg, shd,
                                 collect_kv=True)
    kvs, enc_kvs = ys
    cache = init_encdec_cache(cfg, b, max(s, max_len))
    seq = cache_seq_axis(cfg.hd)
    for name, x in zip(("k", "v"), kvs):
        x = jnp.moveaxis(x.astype(cache[name].dtype), 2, seq)
        idx = [slice(None)] * x.ndim
        idx[seq] = slice(0, s)
        cache[name] = cache[name].at[tuple(idx)].set(x)
    cache["ek"] = enc_kvs[0].astype(cache["ek"].dtype)
    cache["ev"] = enc_kvs[1].astype(cache["ev"].dtype)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return logits, cache


def encdec_decode_step(params: Dict, cache: Dict, token: jax.Array,
                       cfg: ModelConfig, shd: Sharder):
    dtype = jnp.dtype(cfg.dtype)
    pos = cache["pos"]
    h = params["embed"].value.astype(dtype)[token]

    def body(carry, xs):
        hh, layer, ck, cv = carry
        blk, ek, ev = xs
        a_in = rms_norm(hh, blk["ln1"])
        a, (ck, cv) = attention_decode(blk["attn"], a_in, ck, cv, layer,
                                       pos, _acfg(cfg, True), shd)
        hh = hh + a
        x_in = rms_norm(hh, blk["ln_x"])
        hh = hh + _cross_attention(blk["xattn"], x_in,
                                   ek.astype(hh.dtype),
                                   ev.astype(hh.dtype), cfg)
        hh = hh + mlp(blk["mlp"], rms_norm(hh, blk["ln2"]), cfg.act, shd)
        return (hh, layer + 1, ck, cv), None

    (h, _, k_new, v_new), _ = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.int32), cache["k"], cache["v"]),
        (params["dec_blocks"], cache["ek"], cache["ev"]))
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = k_new, v_new
    new_cache["pos"] = pos + 1
    h = rms_norm(h, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", h,
                        params["lm_head"].value.astype(h.dtype))
    return logits, new_cache
