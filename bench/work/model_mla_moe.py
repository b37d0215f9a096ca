"""Required work of a decoder step with latent attention and routed experts
(DeepSeek-V3 style, as configs/moonlight-16b-a3b.json states it), from its
sizes.

Counted is what the step needs, not what the program happens to do:
- FLOPs: 2 per weight of every matmul per token, where a token's routed
  experts count as top_k x held / n_experts experts (its average share of
  the held ones), the dense first layers' MLP in place of the expert
  layer; attention's matmuls over the causal query-key pairs, with heads
  decompressed in prefill (q.k over qk_nope + qk_rope, p.v over v) and
  in the absorbed form in decode (scores over the latent and the rope
  key, the weighted sum over the latent); the head only where a token is
  sampled (the last position of a prompt);
- bytes (decode): every weight once at bfloat16, each held expert's
  whole (every one is routed to at these batches), the router and the
  head included; the latent cache (kv_lora_rank + qk_rope per position,
  bfloat16) read at its filled length and written at one position.
"""
from __future__ import annotations


def _attn_weights(m) -> int:
    d, h, c = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    return (d * h * (nope + rope) + d * (c + rope) + c * h * (nope + v)
            + h * v * d)


def _held(m) -> int:
    first, stop = m["experts_held"] or (0, m["n_experts"])
    return stop - first


def _layers(m):
    """(dense layers, expert layers)."""
    n_pre = m["first_dense_layers"]
    return n_pre, m["n_layers"] - n_pre


def token_weights(m) -> float:
    """Matmul weights that one token goes through, summed over layers."""
    d, f = m["d_model"], m["d_ff_expert"]
    n_pre, n_moe = _layers(m)
    expert = 3 * d * f
    moe = (d * m["n_experts"] + m["n_shared"] * expert
           + m["top_k"] * _held(m) / m["n_experts"] * expert)
    return (m["n_layers"] * _attn_weights(m) + n_pre * 3 * d * m["d_ff"]
            + n_moe * moe)


def stored_weights(m) -> int:
    """Matmul weights that the layers hold (every held expert)."""
    d, f = m["d_model"], m["d_ff_expert"]
    n_pre, n_moe = _layers(m)
    moe = d * m["n_experts"] + (m["n_shared"] + _held(m)) * 3 * d * f
    return (m["n_layers"] * _attn_weights(m) + n_pre * 3 * d * m["d_ff"]
            + n_moe * moe)


def prefill(m, batch: int, prompt: int) -> dict:
    h = m["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    pairs = prompt * (prompt + 1) // 2
    flops = 2.0 * batch * prompt * token_weights(m)
    flops += 2.0 * batch * h * (qk + m["v_head_dim"]) * pairs * m["n_layers"]
    flops += 2.0 * batch * m["d_model"] * m["vocab"]
    return {"flops": flops}


def decode_step(m, batch: int, pos: int) -> dict:
    """One decode step whose input token sits at position `pos`."""
    c, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    L, span = m["n_layers"], pos + 1
    head = m["d_model"] * m["vocab"]
    flops = 2.0 * batch * (token_weights(m) + head)
    flops += 2.0 * batch * m["n_heads"] * (2 * c + rope) * span * L
    nb = 2.0 * (stored_weights(m) + head)
    nb += 2.0 * (c + rope) * batch * (span + 1) * L   # read span, write 1
    return {"flops": flops, "bytes": nb}
