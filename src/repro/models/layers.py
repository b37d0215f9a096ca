"""Shared neural layers (pure functions over Param trees).

Everything computes in ``compute_dtype`` (bf16 by default) with f32
norms/softmax and f32 residual-safe accumulations, matching the mixed-
precision recipe the assigned checkpoints train with.

Tuned-op routing (DESIGN.md §15): when tuned layers are enabled —
``use_tuned_layers()`` / ``set_tuned_layers(True)`` / env
``REPRO_TUNED_LAYERS=1`` — ``rms_norm``, the gated ``mlp`` front half,
and full-attention ``attention`` dispatch through the variant-aware
``repro.kernels.ops`` registry (statically-ranked Pallas schedules,
frozen-table lookup at trace time).  Disabled (the default) every
layer runs the original jnp path, so the flag is a pure routing
switch with no numeric surprises outside the documented kernel
tolerances.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
from contextvars import ContextVar
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro import obs
from repro.distributed.sharding import Sharder
from repro.models.params import Param, param

__all__ = ["cast", "count_cast_bytes", "repeated", "traced_count",
           "FLOAT32_READS", "rms_norm",
           "make_rope", "apply_rope", "init_attention", "attention",
           "attention_decode", "cache_seq_axis", "stored_order",
           "write_position", "read_layer", "init_mla", "mla_attention",
           "mla_decode",
           "init_mlp", "mlp", "causal_mask_bias", "AttnConfig",
           "set_tuned_layers", "use_tuned_layers", "tuned_layers_enabled"]


# ---------------------------------------------------------------------------
# tuned-op routing flag
# ---------------------------------------------------------------------------

_TUNED_LAYERS: "ContextVar[Optional[bool]]" = ContextVar(
    "repro_tuned_layers", default=None)


def tuned_layers_enabled() -> bool:
    """True when layers should dispatch through `repro.kernels.ops`.

    Explicit `set_tuned_layers` / `use_tuned_layers` state wins; with
    neither set, the env var ``REPRO_TUNED_LAYERS`` decides (off by
    default)."""
    v = _TUNED_LAYERS.get()
    if v is not None:
        return v
    return os.environ.get("REPRO_TUNED_LAYERS", "0").lower() \
        not in ("", "0", "false", "no")


def set_tuned_layers(on: bool) -> None:
    """Process-wide (well: context-wide) switch; `use_tuned_layers`
    is the scoped variant."""
    _TUNED_LAYERS.set(bool(on))


@contextlib.contextmanager
def use_tuned_layers(on: bool = True):
    """Scope in which layers route through the tuned kernel registry."""
    tok = _TUNED_LAYERS.set(bool(on))
    try:
        yield
    finally:
        _TUNED_LAYERS.reset(tok)


def _ops():
    # deferred: repro.kernels imports every kernel module on first use
    from repro.kernels import ops
    return ops


# Weight leaves the layers read at float32 whatever the compute dtype:
# norm scales (through `.value`), the SSD's per-head scalars (cast to
# float32) and the MoE router.  Every other leaf is read through
# `cast(w, compute dtype)`.
FLOAT32_READS = frozenset({"ln1", "ln2", "norm_a", "norm_m", "q_norm",
                           "k_norm", "kv_norm", "norm_w", "a_log", "dt_bias",
                           "d_skip", "router", "e_score_correction_bias"})

_CAST_COUNTER: "ContextVar[Optional[str]]" = ContextVar(
    "repro_cast_counter", default=None)
_TRIPS: "ContextVar[int]" = ContextVar("repro_trace_trips", default=1)


@contextlib.contextmanager
def repeated(times: int):
    """A loop traced once for all of its ``times`` trips (a layer scan)
    traces its body inside: each `traced_count` made there stands for
    every trip."""
    tok = _TRIPS.set(_TRIPS.get() * int(times))
    try:
        yield
    finally:
        _TRIPS.reset(tok)


def traced_count(name: str, n: int) -> None:
    """Add to the `repro.obs` counter ``name``, at trace time, what one
    run of the traced program does: ``n`` times the trips of each loop
    around."""
    obs.count(name, n * _TRIPS.get())


@contextlib.contextmanager
def count_cast_bytes(counter: str):
    """While a program is traced inside, `cast` adds to the `repro.obs`
    counter ``counter`` the stored bytes of each weight it converts."""
    tok = _CAST_COUNTER.set(counter)
    try:
        yield
    finally:
        _CAST_COUNTER.reset(tok)


def cast(w: Param, dtype) -> jax.Array:
    """A weight in ``dtype``, converted under scope ``cast`` (so a
    per-call weight conversion is named on the device trace); the
    stored array itself when it is in ``dtype`` already."""
    v = w.value
    if v.dtype == jnp.dtype(dtype):
        return v
    counter = _CAST_COUNTER.get()
    if counter is not None:
        traced_count(counter, v.size * v.dtype.itemsize)
    with obs.scope("cast"):
        return v.astype(dtype)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: Param, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with gamma stored directly (init ones); f32 math.

    Tuned route: flatten to (tokens, D) rows and dispatch through the
    ``rms_norm`` registry op — same f32 mean/rsqrt/scale discipline, so
    the two paths agree to float associativity."""
    with obs.scope("norm"):
        if tuned_layers_enabled():
            d = x.shape[-1]
            out = _ops().rms_norm(x.reshape(-1, d), w.value, eps=eps)
            return out.reshape(x.shape)
        return _rms(x, w.value, eps)


def _rms(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def make_rope(head_dim: int, theta: float = 1e4):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    return inv  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array
               ) -> jax.Array:
    """x: (..., S, head_dim); positions: (..., S) int32 (broadcastable)."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (...,S,hd/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    window: int = 0              # 0 = full attention
    causal: bool = True          # False: bidirectional (encoder)
    chunk_q: int = 1024          # chunked path q-block for long seqs
    dense_below: int = 4096      # use dense logits for S < this
    kv_repeat: int = 1           # replicate KV heads in the decode cache
                                 # so kv*r divides the TP axis (vLLM-
                                 # style; exact GQA semantics preserved)
    # latent attention (MLA): head_dim is then the q/k width without
    # position; kv_lora_rank 0 = ordinary heads
    kv_lora_rank: int = 0
    rope_dim: int = 0            # q/k width with RoPE (k's part shared)
    v_dim: int = 0               # value width per head
    norm_eps: float = 1e-6       # of the latent's RMSNorm


def init_attention(key, cfg: AttnConfig) -> Dict:
    ks = jax.random.split(key, 6)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    p = {
        "wq": param(ks[0], (d, h, hd), ("embed", "heads", "head_dim")),
        "wk": param(ks[1], (d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": param(ks[2], (d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": param(ks[3], (h, hd, d), ("heads", "head_dim", "embed"),
                    scale=1.0 / math.sqrt(h * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = param(ks[4], (h, hd), ("heads", "head_dim"), init="zeros")
        p["bk"] = param(ks[5], (kv, hd), ("kv_heads", "head_dim"),
                        init="zeros")
        p["bv"] = param(ks[5], (kv, hd), ("kv_heads", "head_dim"),
                        init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = param(key, (hd,), ("head_dim",), init="ones")
        p["k_norm"] = param(key, (hd,), ("head_dim",), init="ones")
    return p


def _project_qkv(p: Dict, x: jax.Array, cfg: AttnConfig, positions):
    with obs.scope("qkv"):
        q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], x.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, cast(p["wk"], x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, cast(p["wv"], x.dtype))
        if cfg.qkv_bias:
            q = q + cast(p["bq"], x.dtype)
            k = k + cast(p["bk"], x.dtype)
            v = v + cast(p["bv"], x.dtype)
        if cfg.qk_norm:
            q = _rms(q, p["q_norm"].value)
            k = _rms(k, p["k_norm"].value)
    with obs.scope("rope"):
        inv = make_rope(cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, positions[:, :, None], inv)
        k = apply_rope(k, positions[:, :, None], inv)
    return q, k, v


def causal_mask_bias(q_pos: jax.Array, k_pos: jax.Array, window=0
                     ) -> jax.Array:
    """Additive bias (0 / -inf) of shape broadcastable to (..., Sq, Sk).

    ``window`` may be a static int (0 = full causal) or a traced scalar
    (per-layer sliding windows in the hybrid family; window >= seq acts
    as full attention)."""
    ok = k_pos[None, :] <= q_pos[:, None]
    static = isinstance(window, int)
    if not static or window > 0:
        ok &= (q_pos[:, None] - k_pos[None, :]) < window
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _sdpa(q, k, v, bias, scale):
    """q: (B,S,H,hd), k/v: (B,Sk,KV,hd) — GQA dense attention.

    KV heads are broadcast up to H before the einsum so the head axis
    stays cleanly TP-sharded (the Megatron GQA recipe).  XLA does not
    fuse the broadcast into the matmul: the broadcast K/V (K in float32)
    are written out whole, which is why decode has its own grouped core
    (`_sdpa_decode`).  Softmax in f32, PV in the value dtype.
    """
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             (b, k.shape[1], kvh, rep, hd)
                             ).reshape(b, k.shape[1], h, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             (b, v.shape[1], kvh, rep, hd)
                             ).reshape(b, v.shape[1], h, hd)
    logits = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = logits + bias  # bias: (q, s) broadcast
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, v)
    return out.astype(q.dtype)


def _sdpa_decode(q, cache_k, cache_v, bias, scale, seq: int):
    """q: (B,1,H,hd), cache_k/v: one layer's cache as stored, (B,KV,S,hd)
    or (B,KV,hd,S) (the sequence at ``seq``, `cache_seq_axis`) — one-
    token GQA attention that reads the cache as stored.

    Each KV head scores its group of H//KV query heads in one grouped
    einsum over the cache in its own dtype, accumulated in f32: no
    broadcast up to H, no cast of the cache (a product of two bf16
    values is exact in f32, so only the summation order can differ from
    `_sdpa`).  The KV axis stays outermost in the group split, so a
    heads-sharded q meets the kv-head-sharded cache group for group.
    Bias and softmax in f32, PV in the value dtype with f32
    accumulation.  The cache is each dot's first operand, in either
    stored order the form in which the CPU backend too takes bf16
    operands to an f32 result."""
    b, sq, h, hd = q.shape
    kvh = cache_k.shape[1]
    c = "".join(stored_order("bskd", seq, at=1))
    traced_count("attn.decode_grouped", 1)
    qg = q.reshape(b, sq, kvh, h // kvh, hd)
    logits = jnp.einsum(f"{c},bqkgd->bkgqs", cache_k, qg,
                        preferred_element_type=jnp.float32) * scale
    logits = logits + bias  # bias: (q, s) broadcast
    probs = jax.nn.softmax(logits, axis=-1).astype(cache_v.dtype)
    out = jnp.einsum(f"{c},bkgqs->bqkgd", cache_v, probs,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def _sdpa_chunked(q, k, v, q_positions, k_positions, window, scale,
                  chunk: int):
    """Streaming over query chunks: O(S * chunk) logits memory.

    q length is padded up to a chunk multiple (pad rows sliced off)."""
    b, s, h, hd = q.shape
    pad = (-s) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)))
    sp = s + pad
    nchunk = sp // chunk
    qc = q.reshape(b, nchunk, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(b, nchunk, chunk).transpose(1, 0, 2)

    def body(_, qq):
        qi, qpi = qq
        bias = causal_mask_bias(qpi[0], k_positions[0], window)
        return None, _sdpa(qi, k, v, bias, scale)

    _, out = jax.lax.scan(body, None, (qc, qp))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, sp, h, v.shape[-1])
    return out[:, :s]


def _attention_tuned(q, k, v, causal: bool):
    """Dispatch full attention through the ``flash_attention`` registry
    op: broadcast GQA KV heads up to H (as `_sdpa` does), transpose
    (B,S,H,hd) -> (B,H,S,hd) for the kernel layout, and back.

    Only exact for the standard prefill mask (positions = arange, no
    sliding window) — `attention` gates on that before routing.  The
    kernel scales by 1/sqrt(hd), matching the jnp path."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             (b, k.shape[1], kvh, rep, hd)
                             ).reshape(b, k.shape[1], h, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             (b, v.shape[1], kvh, rep, hd)
                             ).reshape(b, v.shape[1], h, hd)
    out = _ops().flash_attention(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3), causal)
    return out.transpose(0, 2, 1, 3)


def attention(p: Dict, x: jax.Array, cfg: AttnConfig, shd: Sharder,
              positions: Optional[jax.Array] = None,
              return_kv: bool = False, window_override=None):
    """Full-sequence (training / prefill) attention.  x: (B, S, D)."""
    b, s, d = x.shape
    window = cfg.window if window_override is None else window_override
    # the tuned kernel implements exactly the standard prefill mask:
    # positions = arange, full causal (or fully bidirectional) — gate
    # on those *statically* so traced windows fall back to jnp.
    tuned = (tuned_layers_enabled() and positions is None
             and isinstance(window, int) and window == 0)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = shd.act(q, ("batch", "seq", "heads", "head_dim"))
    k = shd.act(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = shd.act(v, ("batch", "seq", "kv_heads", "head_dim"))
    scale = 1.0 / math.sqrt(cfg.head_dim)
    with obs.scope("core"):
        if tuned:
            out = _attention_tuned(q, k, v, cfg.causal)
        elif not cfg.causal:
            out = _sdpa(q, k, v, jnp.zeros((), jnp.float32), scale)
        elif s < cfg.dense_below:
            bias = causal_mask_bias(positions[0], positions[0], window)
            out = _sdpa(q, k, v, bias, scale)
        else:
            out = _sdpa_chunked(q, k, v, positions, positions, window,
                                scale, cfg.chunk_q)
    with obs.scope("out"):
        out = out.astype(x.dtype)
        y = jnp.einsum("bshk,hkd->bsd", out, cast(p["wo"], x.dtype))
        y = shd.act(y, ("batch", "residual_seq", "embed"))
    if return_kv:
        # the cache copy lives in the decode-cache layout (kv-head /
        # head_dim sharded), not the activation layout; kv_repeat
        # replicates heads so kv*r divides the TP axis.
        if cfg.kv_repeat > 1:
            k = jnp.repeat(k, cfg.kv_repeat, axis=2)
            v = jnp.repeat(v, cfg.kv_repeat, axis=2)
        kc = shd.cache(k, ("batch", "cache_seq", "kv_heads", "head_dim"))
        vc = shd.cache(v, ("batch", "cache_seq", "kv_heads", "head_dim"))
        return y, (kc, vc)
    return y


LANES = 128   # the minor axis of a TPU vector tile


def cache_seq_axis(width: int) -> int:
    """Where the sequence axis sits in a stored decode-cache leaf whose
    positions each hold ``width`` values per head, counted from the end.

    Second from last where ``width`` fills whole lane tiles: (L, B,
    *heads, S, width), each position one row of its tiles.  Else last:
    (L, B, *heads, width, S), so that no tile pads a narrow row out to
    the lanes.  The device lays either out row-major, as prefill hands
    it over; the decode step writes one position into it and reads a
    layer's slice of it in that layout (`write_position`)."""
    return -2 if width % LANES == 0 else -1


def stored_order(axes, seq: int, at: int = 2) -> tuple:
    """``axes`` (a shape, logical dims or einsum letters) of a cache
    whose sequence axis is ``at``, with that axis moved to ``seq``
    (`cache_seq_axis`): the stored order."""
    rest = list(axes[:at]) + list(axes[at + 1:])
    rest.insert(len(rest) + 1 + seq, axes[at])
    return tuple(rest)


def write_position(stack: jax.Array, new: jax.Array, layer, slot,
                   seq: int, shd: Sharder, dims) -> jax.Array:
    """``stack`` (L, B, ...) in its stored order with ``new`` (B, 1,
    *per-position) written at layer ``layer``, position ``slot`` of the
    sequence axis ``seq``: an update in place of one position where the
    stack is a loop's carry or a donated buffer.  ``dims`` are the
    stack's logical dims as (L, B, S, *per-position).

    The result keeps the stack's row-major layout: left free, XLA lays
    a carried stack out for this write (the written axes minor) and
    copies the whole stack into and out of the loop.  Only where the
    program is not partitioned: XLA's partitioner replicates a layout
    constraint, gathering the whole stack at every layer."""
    new = jnp.moveaxis(new, 1, seq)[None].astype(stack.dtype)
    idx = [0] * stack.ndim
    idx[0], idx[stack.ndim + seq] = layer, slot
    out = jax.lax.dynamic_update_slice(stack, new, idx)
    if shd.mesh is None:
        out = with_layout_constraint(
            out, Layout(major_to_minor=tuple(range(stack.ndim))))
    return shd.cache(out, stored_order(dims, seq))


def read_layer(stack: jax.Array, layer) -> jax.Array:
    """Layer ``layer`` of a stacked state (L, B, ...)."""
    return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def attention_decode(p: Dict, x: jax.Array, cache_k: jax.Array,
                     cache_v: jax.Array, layer, pos: jax.Array,
                     cfg: AttnConfig, shd: Sharder, window_override=None,
                     rolling: bool = False):
    """One-token decode.  x: (B, 1, D); cache_k/v: the stacked caches
    (L, B, KV, S, hd) or (L, B, KV, hd, S) (`cache_seq_axis`), of which
    layer ``layer`` is written at one position and read; ``pos``: scalar
    int32 current position.  Returns y and the updated stacks.

    ``rolling=True`` treats the cache as a mod-S_cache ring buffer
    (windowed layers / capped long-context decode); the effective
    attention span is ``min(window, S_cache)``.  ``window_override`` may
    be traced (per-layer windows in the hybrid family)."""
    b = x.shape[0]
    seq = cache_seq_axis(cfg.head_dim)
    s_cache = cache_k.shape[seq]
    window = cfg.window if window_override is None else window_override
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    with obs.scope("cache_write"):
        if cfg.kv_repeat > 1:
            k_new = jnp.repeat(k_new, cfg.kv_repeat, axis=2)
            v_new = jnp.repeat(v_new, cfg.kv_repeat, axis=2)
        slot = (pos % s_cache) if rolling else pos
        dims = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
        cache_k = write_position(cache_k, k_new, layer, slot, seq, shd,
                                 dims)
        cache_v = write_position(cache_v, v_new, layer, slot, seq, shd,
                                 dims)
    with obs.scope("core"):
        idx = jnp.arange(s_cache, dtype=jnp.int32)
        if rolling:
            # ring buffer: entry i holds absolute position p ≡ i (mod
            # S_c), valid if it was written (p <= pos) and inside the
            # window.
            age = (pos - idx) % s_cache
            span = jnp.minimum(jnp.asarray(
                window if not isinstance(window, int) or window > 0
                else s_cache, jnp.int32), s_cache)
            valid = (age < span) & (age <= pos)
            bias = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)[None, :]
        else:
            ok = idx <= pos
            static = isinstance(window, int)
            if not static or window > 0:
                ok &= (pos - idx) < window
            bias = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[None, :]
        scale = 1.0 / math.sqrt(cfg.head_dim)
        out = _sdpa_decode(q, read_layer(cache_k, layer),
                           read_layer(cache_v, layer), bias, scale, seq)
    with obs.scope("out"):
        y = jnp.einsum("bshk,hkd->bsd", out, cast(p["wo"], x.dtype))
    return y, (cache_k, cache_v)


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2/V3 with q_lora_rank null)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: AttnConfig) -> Dict:
    """q_proj (per head: head_dim without position, then rope_dim with
    it), kv_a_proj_with_mqa (the latent, then the one shared rope key),
    kv_a_layernorm, kv_b_proj held as its key half ``wk_b`` and value
    half ``wv_b``, and o_proj."""
    ks = jax.random.split(key, 5)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.head_dim, cfg.rope_dim, cfg.v_dim
    return {
        "wq": param(ks[0], (d, h, nope + rope), ("embed", "heads",
                                                  "head_dim")),
        "wkv_a": param(ks[1], (d, r + rope), ("embed", "kv_lora")),
        "kv_norm": param(ks[1], (r,), ("kv_lora",), init="ones"),
        "wk_b": param(ks[2], (r, h, nope), ("kv_lora", "heads",
                                            "head_dim")),
        "wv_b": param(ks[3], (r, h, vd), ("kv_lora", "heads", "head_dim")),
        "wo": param(ks[4], (h, vd, d), ("heads", "head_dim", "embed"),
                    scale=1.0 / math.sqrt(h * vd)),
    }


def _mla_project(p: Dict, x: jax.Array, cfg: AttnConfig, positions):
    """q (B, S, H, head_dim + rope_dim) with its rope part rotated, the
    normed latent c_kv (B, S, kv_lora_rank) and the rotated shared key
    k_rope (B, S, rope_dim): what the latent cache holds."""
    r, nope = cfg.kv_lora_rank, cfg.head_dim
    with obs.scope("q"):
        q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], x.dtype))
    with obs.scope("kv_down"):
        kv = jnp.einsum("bsd,dc->bsc", x, cast(p["wkv_a"], x.dtype))
    with obs.scope("kv_norm"):
        c_kv = _rms(kv[..., :r], p["kv_norm"].value, cfg.norm_eps)
    with obs.scope("rope"):
        inv = make_rope(cfg.rope_dim, cfg.rope_theta)
        q = jnp.concatenate(
            [q[..., :nope], apply_rope(q[..., nope:], positions[:, :, None],
                                       inv)], axis=-1)
        k_rope = apply_rope(kv[..., r:], positions, inv)
    return q, c_kv, k_rope


def mla_attention(p: Dict, x: jax.Array, cfg: AttnConfig, shd: Sharder,
                  return_kv: bool = False):
    """Causal latent attention over the whole sequence (prefill), with the
    heads decompressed: k = [c_kv wk_b, k_rope], v = c_kv wv_b.  The
    tuned kernel takes one head width, so v is zero-padded to q/k's
    (exact: the padding's output columns are dropped) and the kernel's
    1/sqrt(width) is the published scale.  With ``return_kv``, also the
    latent cache's entries (c_kv, k_rope)."""
    b, s, _ = x.shape
    h, nope, rope, vd = cfg.n_heads, cfg.head_dim, cfg.rope_dim, cfg.v_dim
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, c_kv, k_rope = _mla_project(p, x, cfg, positions)
    with obs.scope("kv_up"):
        k = jnp.einsum("bsc,chk->bshk", c_kv, cast(p["wk_b"], x.dtype))
        v = jnp.einsum("bsc,chk->bshk", c_kv, cast(p["wv_b"], x.dtype))
        k = jnp.concatenate(
            [k, jnp.broadcast_to(k_rope[:, :, None], (b, s, h, rope))], -1)
    q = shd.act(q, ("batch", "seq", "heads", "head_dim"))
    k = shd.act(k, ("batch", "seq", "heads", "head_dim"))
    v = shd.act(v, ("batch", "seq", "heads", "head_dim"))
    scale = 1.0 / math.sqrt(nope + rope)
    with obs.scope("core"):
        if tuned_layers_enabled():
            vp = jnp.pad(v, ((0, 0),) * 3 + ((0, nope + rope - vd),))
            out = _attention_tuned(q, k, vp, True)[..., :vd]
        elif s < cfg.dense_below:
            out = _sdpa(q, k, v, causal_mask_bias(positions[0],
                                                  positions[0]), scale)
        else:
            out = _sdpa_chunked(q, k, v, positions, positions, 0, scale,
                                cfg.chunk_q)
    with obs.scope("out"):
        y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype),
                       cast(p["wo"], x.dtype))
        y = shd.act(y, ("batch", "residual_seq", "embed"))
    if return_kv:
        return y, (shd.cache(c_kv, ("batch", "cache_seq", None)),
                   shd.cache(k_rope, ("batch", "cache_seq", None)))
    return y


def mla_decode(p: Dict, x: jax.Array, c_cache: jax.Array,
               r_cache: jax.Array, layer, pos: jax.Array, cfg: AttnConfig,
               shd: Sharder):
    """One-token latent attention in the absorbed form.  x: (B, 1, D);
    c_cache (L, B, S, kv_lora_rank) and r_cache (L, B, S, rope_dim), each
    in its stored order (`cache_seq_axis`), of which layer ``layer`` is
    written and read; ``pos`` the scalar position written.  The query's
    position-free part is taken through ``wk_b`` into the latent, scored
    against the cached latent (and its rope part against the shared
    rope key); the weighted sum of latents goes through ``wv_b``
    afterwards: no head is decompressed."""
    c_seq = cache_seq_axis(cfg.kv_lora_rank)
    r_seq = cache_seq_axis(cfg.rope_dim)
    b, s_cache = x.shape[0], c_cache.shape[c_seq]
    nope = cfg.head_dim
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, c_new, r_new = _mla_project(p, x, cfg, positions)
    with obs.scope("cache_write"):
        dims = ("layers", "batch", "cache_seq", None)
        c_cache = write_position(c_cache, c_new, layer, pos, c_seq, shd,
                                 dims)
        r_cache = write_position(r_cache, r_new, layer, pos, r_seq, shd,
                                 dims)
    with obs.scope("absorb"):
        q_lat = jnp.einsum("bshk,chk->bshc", q[..., :nope],
                           cast(p["wk_b"], x.dtype))
    with obs.scope("core"):
        c_layer, r_layer = read_layer(c_cache, layer), read_layer(r_cache,
                                                                  layer)
        c = "".join(stored_order("btc", c_seq, at=1))
        r = "".join(stored_order("btr", r_seq, at=1))
        f32 = jnp.float32
        logits = (jnp.einsum(f"{c},bshc->bhst", c_layer, q_lat,
                             preferred_element_type=f32)
                  + jnp.einsum(f"{r},bshr->bhst", r_layer, q[..., nope:],
                               preferred_element_type=f32))
        logits = logits / math.sqrt(nope + cfg.rope_dim)
        ok = jnp.arange(s_cache, dtype=jnp.int32) <= pos
        logits = jnp.where(ok, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(c_layer.dtype)
        o_lat = jnp.einsum(f"bhst,{c}->bshc", probs, c_layer)
    with obs.scope("unabsorb"):
        o = jnp.einsum("bshc,chk->bshk", o_lat, cast(p["wv_b"], x.dtype))
    with obs.scope("out"):
        y = jnp.einsum("bshk,hkd->bsd", o, cast(p["wo"], x.dtype))
    return y, (c_cache, r_cache)


# ---------------------------------------------------------------------------
# mlp
# ---------------------------------------------------------------------------

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def init_mlp(key, d_model: int, d_ff: int, act: str = "silu_glu") -> Dict:
    ks = jax.random.split(key, 3)
    gated = act.endswith("_glu")
    p = {"w_up": param(ks[0], (d_model, d_ff), ("embed", "mlp")),
         "w_down": param(ks[1], (d_ff, d_model), ("mlp", "embed"))}
    if gated:
        p["w_gate"] = param(ks[2], (d_model, d_ff), ("embed", "mlp"))
    return p


def mlp(p: Dict, x: jax.Array, act: str, shd: Sharder) -> jax.Array:
    a = _ACTS[act.replace("_glu", "")]
    b, s, d = x.shape
    if tuned_layers_enabled() and "w_gate" in p:
        # gated front half act(x@w_gate) * (x@w_up) as one registry op
        # (variant-arbitrated fused/stream/split schedule), then the
        # down-projection through the tuned matmul.
        x2 = x.reshape(b * s, d)
        h = _ops().mlp_matmul(x2, cast(p["w_gate"], x.dtype),
                              cast(p["w_up"], x.dtype),
                              act.replace("_glu", ""))
        h = shd.act(h.reshape(b, s, -1), ("batch", "seq", "mlp"))
        f = h.shape[-1]
        y = _ops().matmul(h.reshape(b * s, f), cast(p["w_down"], x.dtype))
        return shd.act(y.reshape(b, s, d), ("batch", "residual_seq",
                                            "embed"))
    up = jnp.einsum("bsd,df->bsf", x, cast(p["w_up"], x.dtype))
    if "w_gate" in p:
        gate = jnp.einsum("bsd,df->bsf", x, cast(p["w_gate"], x.dtype))
        h = a(gate) * up
    else:
        h = a(up)
    h = shd.act(h, ("batch", "seq", "mlp"))
    y = jnp.einsum("bsf,fd->bsd", h, cast(p["w_down"], x.dtype))
    return shd.act(y, ("batch", "residual_seq", "embed"))
