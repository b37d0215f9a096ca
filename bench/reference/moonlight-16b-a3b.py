"""Plain float32 reference of moonlight-16b-a3b as configs/moonlight-16b-a3b.json states it.

`init` makes the weights from a key in one jitted call, in the
program's parameter layout; `forward` is the whole-sequence pass with
latent attention decompressed into heads (never the absorbed form, and
no cache), and every held expert computed for every token, weighted by
the routing: sigmoid scores, the top k picked on scores plus
`e_score_correction_bias`, the picked scores normalised to sum 1 and
scaled.  What the experts outside the held range give is left out, as
the program leaves it out (one chip's share of the layer).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import plain


def _attn(r, m, L, nrm, one):
    d, h = m["d_model"], m["n_heads"]
    c, nope = m["kv_lora_rank"], m["qk_nope_head_dim"]
    rope, vd = m["qk_rope_head_dim"], m["v_head_dim"]
    return {"wq": nrm(r(), (L, d, h, nope + rope), d ** -0.5),
            "wkv_a": nrm(r(), (L, d, c + rope), d ** -0.5),
            "kv_norm": one(r(), (L, c)),
            "wk_b": nrm(r(), (L, c, h, nope), c ** -0.5),
            "wv_b": nrm(r(), (L, c, h, vd), c ** -0.5),
            "wo": nrm(r(), (L, h, vd, d), (h * vd) ** -0.5)}


def _mlp(r, d, f, L, nrm):
    return {"w_gate": nrm(r(), (L, d, f), d ** -0.5),
            "w_up": nrm(r(), (L, d, f), d ** -0.5),
            "w_down": nrm(r(), (L, f, d), f ** -0.5)}


def init(key, m):
    """Weights, float32: {embed, lm_head, final_norm, prefix_blocks (the
    dense first layers), blocks (the expert layers)}."""
    d, v = m["d_model"], m["vocab"]
    n_pre = m["first_dense_layers"]
    L = m["n_layers"] - n_pre
    first, stop = m["experts_held"]
    e, f = stop - first, m["d_ff_expert"]
    r = plain.Keys(key)
    nrm, one = plain.normal, plain.near_one
    prefix = {"ln1": one(r(), (n_pre, d)), "ln2": one(r(), (n_pre, d)),
              "attn": _attn(r, m, n_pre, nrm, one),
              "mlp": _mlp(r, d, m["d_ff"], n_pre, nrm)}
    blocks = {"ln1": one(r(), (L, d)), "ln2": one(r(), (L, d)),
              "attn": _attn(r, m, L, nrm, one),
              "moe": {"router": nrm(r(), (L, d, m["n_experts"]), d ** -0.5),
                      "e_score_correction_bias": nrm(
                          r(), (L, m["n_experts"]), 0.1),
                      "w_gate": nrm(r(), (L, e, d, f), d ** -0.5),
                      "w_up": nrm(r(), (L, e, d, f), d ** -0.5),
                      "w_down": nrm(r(), (L, e, f, d), f ** -0.5),
                      "shared": _mlp(r, d, m["n_shared"] * f, L, nrm)}}
    return {"embed": nrm(r(), (v, d), 0.02),
            "lm_head": nrm(r(), (d, v), d ** -0.5),
            "final_norm": one(r(), (d,)), "prefix_blocks": prefix,
            "blocks": blocks}


def attention(p, x, m, num):
    """Causal latent attention with decompressed heads.  x: (B, T, D)."""
    t = x.shape[1]
    h, c = m["n_heads"], m["kv_lora_rank"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    q = num.mm("btd,dhk->bthk", x, p["wq"])
    kv = num.mm("btd,dc->btc", x, p["wkv_a"])
    latent = plain.rms(kv[..., :c], p["kv_norm"], m["norm_eps"])
    pos = jnp.arange(t)
    q = jnp.concatenate([q[..., :nope],
                         plain.rope(q[..., nope:], pos, m["rope_theta"])], -1)
    k_rope = plain.rope(kv[..., None, c:], pos, m["rope_theta"])
    k = num.mm("btc,chk->bthk", latent, p["wk_b"])
    k = jnp.concatenate([k, jnp.broadcast_to(
        k_rope, k.shape[:-1] + (rope,))], -1)
    v = num.mm("btc,chk->bthk", latent, p["wv_b"])
    s = num.mm("bihk,bjhk->bhij", q, k) / math.sqrt(nope + rope)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = num.mm("bhij,bjhk->bihk", jax.nn.softmax(s, axis=-1), v)
    return num.mm("bthk,hkd->btd", o, p["wo"])


def mlp(p, x, num):
    g = jax.nn.silu(num.mm("btd,df->btf", x, p["w_gate"]))
    return num.mm("btf,fd->btd", g * num.mm("btd,df->btf", x, p["w_up"]),
                  p["w_down"])


def routing(p, x, m, num):
    """(weights (B, T, k), experts (B, T, k)) by the noaux_tc rule."""
    scores = jax.nn.sigmoid(num.mm("btd,de->bte", x, p["router"]))
    _, top = jax.lax.top_k(scores + p["e_score_correction_bias"], m["top_k"])
    w = jnp.take_along_axis(scores, top, axis=-1)
    return w / w.sum(-1, keepdims=True) * m["routed_scaling"], top


def experts(p, x, m, num):
    """Every held expert on every token, weighted by the routing, plus
    the shared experts."""
    w, top = routing(p, x, m, num)
    first, stop = m["experts_held"]
    held = jnp.arange(first, stop)
    weight = jnp.sum(w[..., None] * (top[..., None] == held), axis=-2)
    g = jax.nn.silu(num.mm("btd,edf->btef", x, p["w_gate"]))
    u = num.mm("btd,edf->btef", x, p["w_up"])
    y = num.mm("btef,efd->bted", g * u, p["w_down"])
    y = jnp.einsum("bted,bte->btd", y, weight, precision=plain.HIGHEST)
    return y + mlp(p["shared"], x, num)


def forward(params, tokens, m, rows, num=plain.Num()):
    """Logits (B, len(rows), V) of `tokens` (B, T) at positions `rows`."""
    eps = m["norm_eps"]
    h = params["embed"][tokens]

    def layer(ffn):
        def apply(h, p):
            h = h + attention(p["attn"], plain.rms(h, p["ln1"], eps), m, num)
            return h + ffn(p, plain.rms(h, p["ln2"], eps)), None
        return apply

    h, _ = jax.lax.scan(layer(lambda p, x: mlp(p["mlp"], x, num)), h,
                        params["prefix_blocks"])
    h, _ = jax.lax.scan(layer(lambda p, x: experts(p["moe"], x, m, num)), h,
                        params["blocks"])
    return plain.head_logits(params, h, m, rows, num)
