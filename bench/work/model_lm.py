"""Required work of a decoder LM step, dense or hybrid, from its sizes.

Counted is what the step needs, not what the program happens to do:
- FLOPs: 2 per weight of every matmul per token, attention's two
  matmuls over the query-key pairs the config's causal and window mask
  keeps, the SSM recurrence (about 6 H N P per token and layer: decay,
  input outer product, output contraction) and its short conv, and the
  head only where a token is sampled (the last position of a prompt);
- bytes (decode): every weight once at bfloat16, the KV cache at its
  filled length within the window, the SSM state read and written in
  float32 and the conv state in bfloat16.
"""
from __future__ import annotations


def _dims(m):
    d, h, kv = m["d_model"], m["n_heads"], m["n_kv"]
    hd = m["head_dim"] or d // h
    return d, h, kv, hd


def layer_weights(m) -> int:
    """Matmul weights of one layer."""
    d, h, kv, hd = _dims(m)
    n = d * hd * (h + 2 * kv) + h * hd * d
    n += (3 if m["act"].endswith("_glu") else 2) * d * m["d_ff"]
    if m["family"] == "hybrid":
        di = m["ssm_expand"] * d
        sh = di // m["ssm_head_dim"]
        n += d * (2 * di + 2 * m["ssm_state"] + sh) + di * d
    return n


def windows(m, t: int):
    """Attention span of each layer when the sequence is t long."""
    L = m["n_layers"]
    if m["family"] != "hybrid" or m["swa_window"] <= 0:
        return [t] * L
    glob = {0, L // 2, L - 1}
    return [t if i in glob else min(m["swa_window"], t) for i in range(L)]


def _pairs(t: int, w: int) -> int:
    """Query-key pairs of a causal mask of span w over t positions."""
    full = min(t, w)
    return full * (full + 1) // 2 + (t - full) * w


def _ssm(m):
    """(FLOPs per token and layer, state bytes per sequence and layer)."""
    if m["family"] != "hybrid":
        return 0, 0
    di = m["ssm_expand"] * m["d_model"]
    sh = di // m["ssm_head_dim"]
    n, p, k = m["ssm_state"], m["ssm_head_dim"], m["ssm_conv"]
    conv = di + 2 * n
    return 6 * sh * n * p + 2 * k * conv, 4 * sh * n * p + 2 * (k - 1) * conv


def prefill(m, batch: int, prompt: int) -> dict:
    d, h, kv, hd = _dims(m)
    L = m["n_layers"]
    flops = 2.0 * batch * prompt * L * layer_weights(m)
    flops += 4.0 * batch * h * hd * sum(_pairs(prompt, w)
                                         for w in windows(m, prompt))
    flops += batch * prompt * L * _ssm(m)[0]
    flops += 2.0 * batch * d * m["vocab"]
    return {"flops": flops}


def decode_step(m, batch: int, pos: int) -> dict:
    """One decode step whose input token sits at position `pos`."""
    d, h, kv, hd = _dims(m)
    L = m["n_layers"]
    span = sum(min(pos + 1, w) for w in windows(m, pos + 1))
    ssm_flops, ssm_bytes = _ssm(m)
    flops = 2.0 * batch * (L * layer_weights(m) + d * m["vocab"])
    flops += 4.0 * batch * h * hd * span + batch * L * ssm_flops
    weights = L * layer_weights(m) + d * m["vocab"]
    nb = 2.0 * weights
    nb += 2.0 * 2 * kv * hd * batch * (span + L)   # read span, write 1
    nb += 2.0 * batch * L * ssm_bytes              # state read and written
    return {"flops": flops, "bytes": nb}
