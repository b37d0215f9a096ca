"""Plain float32 pieces of the references: straightforward jnp, no kernels,
no cache, no batching tricks, and nothing imported from the program.

Every matmul runs at float32 "highest" precision (on a TPU a float32
matmul otherwise runs in bfloat16 passes).  `Num` carries the one switch
the control needs: ``fp8=True`` rounds both operands of every matmul to
float8 e4m3 first, the precision below the bfloat16 the configurations
compute in.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Num:
    fp8: bool = False

    def q(self, x):
        if not self.fp8:
            return x
        x = jnp.clip(x, -F8_MAX, F8_MAX)
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def mm(self, eq, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b), precision=HIGHEST)


# ---------------------------------------------------------------- weights

def normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def near_one(key, shape):
    """Gains drawn around 1, so that a gain the program drops shows."""
    return 1.0 + normal(key, shape, 0.1)


class Keys:
    """Independent keys, one per leaf, drawn in a fixed order."""

    def __init__(self, key):
        self.key = key

    def __call__(self):
        self.key, sub = jax.random.split(self.key)
        return sub


# ---------------------------------------------------------------- layers

def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate-half RoPE.  x: (B, T, H, hd); pos: (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, m, window, num: Num):
    """Causal GQA attention over the whole sequence.  x: (B, T, D).

    Query i sees key j where j <= i and i - j < window (a window of at
    least T is full causal attention)."""
    b, t, _ = x.shape
    h, kv = m["n_heads"], m["n_kv"]
    hd = m["head_dim"] or m["d_model"] // h
    q = num.mm("btd,dhk->bthk", x, p["wq"])
    k = num.mm("btd,dhk->bthk", x, p["wk"])
    v = num.mm("btd,dhk->bthk", x, p["wv"])
    if m["qkv_bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    pos = jnp.arange(t)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)      # query head i reads KV head i // (h/kv)
    v = jnp.repeat(v, h // kv, axis=2)
    s = num.mm("bihk,bjhk->bhij", q, k) / math.sqrt(hd)
    i, j = pos[:, None], pos[None, :]
    ok = (j <= i) & (i - j < window)
    s = jnp.where(ok, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = num.mm("bhij,bjhk->bihk", w, v)
    return num.mm("bthk,hkd->btd", o, p["wo"])


def head_logits(params, h, m, rows, num: Num):
    """Logits of the positions `rows` (static slice) after the final norm."""
    x = rms(h[:, rows], params["final_norm"], m["norm_eps"])
    return num.mm("btd,dv->btv", x, params["lm_head"])
