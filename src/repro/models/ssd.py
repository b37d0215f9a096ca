"""Mamba-2 SSD (state-space duality) block.

Chunked block decomposition (Dao & Gu, arXiv:2405.21060 §6): the
sequence is split into chunks of length L; within a chunk the output is
the quadratic "attention-like" term, across chunks an associative scan
carries the (H, N, P) state with exponential decay.  O(T·L) memory,
matmul-dominated — maps onto the MXU.  Decode is the O(1) recurrence
``S <- exp(dt·A)·S + dt·B⊗x``.

Layout: d_inner = expand * d_model, heads H = d_inner / head_dim P,
single B/C group (G=1), state size N = cfg.ssm_state, short causal
conv (k = cfg.ssm_conv) over the x/B/C channels.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import Sharder
from repro.models.params import Param, param

__all__ = ["SsdConfig", "init_ssd", "ssd_block", "ssd_decode",
           "init_ssd_state"]


@dataclasses.dataclass(frozen=True)
class SsdConfig:
    d_model: int
    ssm_state: int = 128       # N
    ssm_conv: int = 4
    expand: int = 2
    head_dim: int = 64         # P
    chunk: int = 128

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        assert self.d_inner % self.head_dim == 0
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_state


def init_ssd(key, cfg: SsdConfig) -> Dict:
    ks = jax.random.split(key, 8)
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.n_heads
    # in_proj packs [z, x, B, C, dt]
    return {
        "w_in": param(ks[0], (d, 2 * di + 2 * n + h), ("embed", "ssm_inner")),
        "conv_w": param(ks[1], (cfg.ssm_conv, cfg.conv_dim),
                        ("conv", "ssm_inner"), scale=0.5),
        "conv_b": param(ks[2], (cfg.conv_dim,), ("ssm_inner",),
                        init="zeros"),
        "a_log": param(ks[3], (h,), (None,), init="zeros"),
        "dt_bias": param(ks[4], (h,), (None,), init="zeros"),
        "d_skip": param(ks[5], (h,), (None,), init="ones"),
        "norm_w": param(ks[6], (di,), ("ssm_inner",), init="ones"),
        "w_out": param(ks[7], (di, d), ("ssm_inner", "embed"),
                       scale=1.0 / math.sqrt(di)),
    }


def _split_in(p, x, cfg: SsdConfig):
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.n_heads
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["w_in"].value.astype(x.dtype))
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + cfg.conv_dim]
    dt = zxbcdt[..., di + cfg.conv_dim:]
    return z, xbc, dt


def _causal_conv(xbc, w, b, k):
    """Depthwise causal conv via k shifted adds.  xbc: (B, S, C)."""
    out = jnp.zeros_like(xbc)
    for i in range(k):
        shifted = xbc if i == 0 else jnp.pad(
            xbc[:, :-i, :], ((0, 0), (i, 0), (0, 0)))
        out = out + shifted * w[k - 1 - i]
    return jax.nn.silu(out + b)


def _ssd_chunked(xh, dt, a, b_in, c_in, cfg: SsdConfig):
    """xh: (B,T,H,P); dt: (B,T,H); b_in/c_in: (B,T,N).  Returns (B,T,H,P)."""
    bsz, t, h, pdim = xh.shape
    n = b_in.shape[-1]
    l = min(cfg.chunk, t)
    t_orig = t
    pad = (-t) % l
    if pad:
        # zero-pad the tail; dt=0 on pads makes them state-neutral
        # (decay exp(0)=1, update dt·B⊗x = 0) so return_state is exact.
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))
        t = t + pad
    nc = t // l

    # reshape into chunks
    xc = xh.reshape(bsz, nc, l, h, pdim).astype(jnp.float32)
    dtc = dt.reshape(bsz, nc, l, h).astype(jnp.float32)
    bc = b_in.reshape(bsz, nc, l, n).astype(jnp.float32)
    cc = c_in.reshape(bsz, nc, l, n).astype(jnp.float32)

    da = dtc * a  # (B,NC,L,H)  negative decays
    cum = jnp.cumsum(da, axis=2)                     # inclusive cumsum
    seg_total = cum[:, :, -1:, :]                    # (B,NC,1,H)

    # ---- intra-chunk (quadratic within chunk) -----------------------------
    # decay matrix Λ[i,j] = exp(cum_i - cum_j) for i >= j else 0.  Mask
    # before the exp: above the diagonal cum_i - cum_j > 0 grows with
    # the chunk and overflows, and exp's gradient there (inf * 0) is NaN.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,NC,L,L,H)
    tri = jnp.tril(jnp.ones((l, l), bool))
    lam = jnp.exp(jnp.where(tri[None, None, :, :, None], diff, -jnp.inf))
    scores = jnp.einsum("bcin,bcjn->bcij", cc, bc)         # (B,NC,L,L)
    w = scores[..., None] * lam * dtc[:, :, None, :, :]    # (B,NC,L,L,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", w, xc)

    # ---- chunk states ------------------------------------------------------
    # S_c = sum_j exp(total - cum_j) * dt_j * B_j ⊗ x_j  -> (B,NC,H,N,P)
    decay_to_end = jnp.exp(seg_total - cum)                # (B,NC,L,H)
    wgt = decay_to_end * dtc                               # (B,NC,L,H)
    s_chunk = jnp.einsum("bcln,bclh,bclhp->bchnp", bc, wgt, xc)

    # ---- inter-chunk associative scan -------------------------------------
    # carry: (decay_product a_c, state b_c); combine: (a1a2, b1*a2 + b2)
    a_c = jnp.exp(seg_total[:, :, 0, :])                   # (B,NC,H)

    def combine(left, right):
        al, bl = left
        ar, br = right
        return al * ar, bl * ar[..., None, None] + br

    a_scan, s_scan = jax.lax.associative_scan(
        combine, (a_c, s_chunk), axis=1)
    # state entering chunk c = scanned state of chunk c-1 (zero for c=0)
    s_prev = jnp.pad(s_scan[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0),
                                      (0, 0)))

    # ---- inter-chunk contribution -----------------------------------------
    decay_in = jnp.exp(cum)                                # (B,NC,L,H)
    y_inter = jnp.einsum("bcln,bclh,bchnp->bclhp", cc, decay_in, s_prev)

    y = y_intra + y_inter
    y = y.reshape(bsz, t, h, pdim)
    if pad:
        y = y[:, :t_orig]
    return y, (a_scan, s_scan)


def ssd_block(p: Dict, x: jax.Array, cfg: SsdConfig, shd: Sharder,
              return_state: bool = False):
    """Full-sequence SSD block.  x: (B, S, D) -> (B, S, D).

    ``return_state=True`` additionally returns the decode handoff state
    {"ssm": (B,H,N,P), "conv": (B,k-1,C)} after the last position."""
    from repro.models.layers import _rms
    bsz, t, _ = x.shape
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.n_heads
    z, xbc_raw, dt = _split_in(p, x, cfg)
    xbc = _causal_conv(xbc_raw, p["conv_w"].value.astype(x.dtype),
                       p["conv_b"].value.astype(x.dtype), cfg.ssm_conv)
    xin = xbc[..., :di]
    b_in = xbc[..., di:di + n]
    c_in = xbc[..., di + n:]
    xh = xin.reshape(bsz, t, h, cfg.head_dim)
    xh = shd.act(xh, ("batch", "seq", "ssm_inner", None))
    a = -jnp.exp(p["a_log"].value.astype(jnp.float32))       # (H,)
    dtp = jax.nn.softplus(dt.astype(jnp.float32)
                          + p["dt_bias"].value.astype(jnp.float32))
    y, (_a_scan, s_scan) = _ssd_chunked(xh, dtp, a, b_in, c_in, cfg)
    y = y + xc_skip(p, xh)
    y = y.reshape(bsz, t, di).astype(x.dtype)
    y = _rms(y * jax.nn.silu(z), p["norm_w"].value)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"].value.astype(x.dtype))
    out = shd.act(out, ("batch", "residual_seq", "embed"))
    if return_state:
        k = cfg.ssm_conv
        pad = max(0, (k - 1) - t)
        tail = xbc_raw[:, max(0, t - (k - 1)):, :]
        if pad:
            tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
        state = {"ssm": s_scan[:, -1], "conv": tail}
        return out, state
    return out


def xc_skip(p, xh):
    return xh.astype(jnp.float32) * p["d_skip"].value.astype(
        jnp.float32)[None, None, :, None]


def init_ssd_state(bsz: int, cfg: SsdConfig, dtype=jnp.float32):
    return {
        "ssm": jnp.zeros((bsz, cfg.n_heads, cfg.ssm_state, cfg.head_dim),
                         jnp.float32),
        "conv": jnp.zeros((bsz, cfg.ssm_conv - 1, cfg.conv_dim), dtype),
    }


def ssd_decode(p: Dict, x: jax.Array, state: Dict, cfg: SsdConfig,
               shd: Sharder) -> Tuple[jax.Array, Dict]:
    """One-token decode.  x: (B, 1, D)."""
    from repro.models.layers import _rms
    bsz = x.shape[0]
    di, n, h, k = cfg.d_inner, cfg.ssm_state, cfg.n_heads, cfg.ssm_conv
    z, xbc, dt = _split_in(p, x, cfg)                       # (B,1,*)
    window = jnp.concatenate([state["conv"], xbc], axis=1)  # (B,k,C)
    w = p["conv_w"].value.astype(x.dtype)
    conv_out = jnp.einsum("bkc,kc->bc", window, w) \
        + p["conv_b"].value.astype(x.dtype)
    conv_out = jax.nn.silu(conv_out)[:, None, :]            # (B,1,C)
    new_conv = window[:, 1:, :]

    xin = conv_out[..., :di].reshape(bsz, h, cfg.head_dim)
    b_in = conv_out[..., di:di + n].reshape(bsz, n)
    c_in = conv_out[..., di + n:].reshape(bsz, n)
    a = -jnp.exp(p["a_log"].value.astype(jnp.float32))
    dtp = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                          + p["dt_bias"].value.astype(jnp.float32))  # (B,H)
    decay = jnp.exp(dtp * a)                                # (B,H)
    s = state["ssm"]                                        # (B,H,N,P)
    upd = jnp.einsum("bn,bh,bhp->bhnp", b_in.astype(jnp.float32), dtp,
                     xin.astype(jnp.float32))
    s_new = s * decay[..., None, None] + upd
    y = jnp.einsum("bn,bhnp->bhp", c_in.astype(jnp.float32), s_new)
    y = y + xin.astype(jnp.float32) * p["d_skip"].value.astype(
        jnp.float32)[None, :, None]
    y = y.reshape(bsz, 1, di).astype(x.dtype)
    y = _rms(y * jax.nn.silu(z), p["norm_w"].value)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"].value.astype(x.dtype))
    return out, {"ssm": s_new, "conv": new_conv}
