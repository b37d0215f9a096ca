"""Required work of one causal attention call over q, k, v (..., s, hd).

Both matmuls (q k^T and p v) over the query-key pairs that the causal
mask keeps, s (s + 1) / 2 per head: 4 hd s (s + 1) / 2 FLOPs per head;
q, k, v read once and the output written once.  The serving path calls
this kernel only for causal prefill, and the mask is not in the call's
shapes, so causal is taken as given.
"""
from bench.work._shapes import nbytes

PALLAS_NAMES = ("flash_attention_pallas", "blocked_attention_pallas")


def work(out, ins):
    q = ins[0][1]
    s, hd = q[-2], q[-1]
    heads = 1
    for x in q[:-2]:
        heads *= x
    flops = 4.0 * hd * heads * s * (s + 1) / 2
    return flops, sum(nbytes(x) for x in ins[:3]) + nbytes(out[0])
