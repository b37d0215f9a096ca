"""Plain float32 reference of starcoder2-3b as configs/starcoder2-3b.json states it.

`init` makes the weights from a key in one jitted call; `forward` is the
whole-sequence pass: pre-norm blocks of causal GQA attention with q/k/v
biases and a non-gated tanh-GELU MLP.
"""
from __future__ import annotations

import jax

from bench.reference import plain


def init(key, m):
    """Weights, float32, in the layout {embed, lm_head, final_norm, blocks}."""
    d, h, kv = m["d_model"], m["n_heads"], m["n_kv"]
    hd = m["head_dim"] or d // h
    L, f, v = m["n_layers"], m["d_ff"], m["vocab"]
    r = plain.Keys(key)
    nrm, one = plain.normal, plain.near_one
    blocks = {
        "ln1": one(r(), (L, d)), "ln2": one(r(), (L, d)),
        "attn": {"wq": nrm(r(), (L, d, h, hd), d ** -0.5),
                 "wk": nrm(r(), (L, d, kv, hd), d ** -0.5),
                 "wv": nrm(r(), (L, d, kv, hd), d ** -0.5),
                 "wo": nrm(r(), (L, h, hd, d), (h * hd) ** -0.5),
                 "bq": nrm(r(), (L, h, hd), 0.1),
                 "bk": nrm(r(), (L, kv, hd), 0.1),
                 "bv": nrm(r(), (L, kv, hd), 0.1)},
        "mlp": {"w_up": nrm(r(), (L, d, f), d ** -0.5),
                "w_down": nrm(r(), (L, f, d), f ** -0.5)},
    }
    return {"embed": nrm(r(), (v, d), 0.02), "lm_head": nrm(r(), (d, v), d ** -0.5),
            "final_norm": one(r(), (d,)), "blocks": blocks}


def forward(params, tokens, m, rows, num=plain.Num()):
    """Logits (B, len(rows), V) of `tokens` (B, T) at positions `rows`."""
    eps = m["norm_eps"]
    t = tokens.shape[1]
    h = params["embed"][tokens]

    def layer(h, p):
        h = h + plain.attention(p["attn"], plain.rms(h, p["ln1"], eps), m,
                                t, num)
        x2 = plain.rms(h, p["ln2"], eps)
        u = jax.nn.gelu(num.mm("btd,df->btf", x2, p["mlp"]["w_up"]),
                        approximate=True)
        return h + num.mm("btf,fd->btd", u, p["mlp"]["w_down"]), None

    h, _ = jax.lax.scan(layer, h, params["blocks"])
    return plain.head_logits(params, h, m, rows, num)
