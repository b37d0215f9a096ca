"""Plain float32 reference of hymba-1.5b as configs/hymba-1.5b.json states it.

`init` makes the weights from a key, in one jitted call, for both the
reference and (repacked by the runner) the program.  `forward` is the
whole-sequence pass: attention and the state-space heads side by side
in every layer, the SSM as its sequential recurrence S_t = exp(dt A)
S_{t-1} + dt B_t x_t (not the program's chunked dual form).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import plain


def _sizes(m):
    d, h, kv = m["d_model"], m["n_heads"], m["n_kv"]
    hd = m["head_dim"] or d // h
    di = m["ssm_expand"] * d
    sh = di // m["ssm_head_dim"]
    conv = di + 2 * m["ssm_state"]
    return d, h, kv, hd, di, sh, conv


def init(key, m):
    """Weights, float32, in the layout {embed, lm_head, final_norm, blocks}."""
    d, h, kv, hd, di, sh, conv = _sizes(m)
    L, f, v, n, k = (m["n_layers"], m["d_ff"], m["vocab"], m["ssm_state"],
                     m["ssm_conv"])
    r = plain.Keys(key)
    nrm, one = plain.normal, plain.near_one
    dt = jnp.exp(jax.random.uniform(r(), (L, sh), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    blocks = {
        "ln1": one(r(), (L, d)), "ln2": one(r(), (L, d)),
        "attn": {"wq": nrm(r(), (L, d, h, hd), d ** -0.5),
                 "wk": nrm(r(), (L, d, kv, hd), d ** -0.5),
                 "wv": nrm(r(), (L, d, kv, hd), d ** -0.5),
                 "wo": nrm(r(), (L, h, hd, d), (h * hd) ** -0.5)},
        "mlp": {"w_gate": nrm(r(), (L, d, f), d ** -0.5),
                "w_up": nrm(r(), (L, d, f), d ** -0.5),
                "w_down": nrm(r(), (L, f, d), f ** -0.5)},
        "ssd": {"w_in": nrm(r(), (L, d, 2 * di + 2 * n + sh), d ** -0.5),
                "conv_w": nrm(r(), (L, k, conv), 0.5),
                "conv_b": nrm(r(), (L, conv), 0.1),
                "a_log": jnp.log(jax.random.uniform(r(), (L, sh), jnp.float32,
                                                    1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "d_skip": one(r(), (L, sh)),
                "norm_w": one(r(), (L, di)),
                "w_out": nrm(r(), (L, di, d), di ** -0.5)},
        "norm_a": one(r(), (L, d)), "norm_m": one(r(), (L, d)),
        "beta_a": one(r(), (L, d)), "beta_m": one(r(), (L, d)),
    }
    return {"embed": nrm(r(), (v, d), 0.02), "lm_head": nrm(r(), (d, v), d ** -0.5),
            "final_norm": one(r(), (d,)), "blocks": blocks}


def windows(m, t):
    """Per-layer attention window: full on the first, middle and last
    layers, `swa_window` elsewhere."""
    L = m["n_layers"]
    glob = {0, L // 2, L - 1}
    return jnp.asarray([t if i in glob else m["swa_window"] for i in range(L)],
                       jnp.int32)


def ssm(p, x, m, num):
    """Mamba-2 heads by their recurrence.  x: (B, T, D) -> (B, T, D)."""
    b, t, _ = x.shape
    _, _, _, _, di, sh, conv = _sizes(m)
    n, k, hp = m["ssm_state"], m["ssm_conv"], m["ssm_head_dim"]
    zxbcdt = num.mm("btd,de->bte", x, p["w_in"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + conv],
                  zxbcdt[..., di + conv:])
    # causal depthwise conv: out_t = sum_j xbc_{t-j} w_{k-1-j} + b
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(padded[:, k - 1 - j:k - 1 - j + t] * p["conv_w"][k - 1 - j]
              for j in range(k))
    xbc = jax.nn.silu(out + p["conv_b"])
    xs = xbc[..., :di].reshape(b, t, sh, hp)
    bs, cs = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (B, T, SH)
    a = -jnp.exp(p["a_log"])                                # (SH,)

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, :, None, None] * s
             + dt_t[:, :, None, None] * b_t[:, None, :, None] * x_t[:, :, None, :])
        y = jnp.einsum("bn,bhnp->bhp", c_t, s, precision=plain.HIGHEST)
        return s, y + p["d_skip"][None, :, None] * x_t

    s0 = jnp.zeros((b, sh, n, hp), jnp.float32)
    seq = lambda u: jnp.moveaxis(u, 1, 0)
    _, ys = jax.lax.scan(step, s0, (seq(xs), seq(bs), seq(cs), seq(dt)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, t, di)
    y = plain.rms(y * jax.nn.silu(z), p["norm_w"], m["norm_eps"])
    return num.mm("bte,ed->btd", y, p["w_out"])


def forward(params, tokens, m, rows, num=plain.Num()):
    """Logits (B, len(rows), V) of `tokens` (B, T) at positions `rows`."""
    eps = m["norm_eps"]
    h = params["embed"][tokens]

    def layer(h, xs):
        p, window = xs
        x = plain.rms(h, p["ln1"], eps)
        a = plain.attention(p["attn"], x, m, window, num)
        s = ssm(p["ssd"], x, m, num)
        h = h + 0.5 * (plain.rms(a, p["norm_a"], eps) * p["beta_a"]
                       + plain.rms(s, p["norm_m"], eps) * p["beta_m"])
        x2 = plain.rms(h, p["ln2"], eps)
        mp = p["mlp"]
        g = jax.nn.silu(num.mm("btd,df->btf", x2, mp["w_gate"]))
        u = num.mm("btd,df->btf", x2, mp["w_up"])
        return h + num.mm("btf,fd->btd", g * u, mp["w_down"]), None

    h, _ = jax.lax.scan(layer, h, (params["blocks"],
                                   windows(m, tokens.shape[1])))
    return plain.head_logits(params, h, m, rows, num)
