"""Required work of one gated-MLP front half, act(x @ w_gate) * (x @ w_up).

One logical call reads x (m, d) and both weights (d, f) once and writes
(m, f): 4 m d f FLOPs.  The `split` variant runs it as two custom calls
of one weight each; each is counted as half of the logical call, and the
elementwise combine it leaves to XLA is not part of the kernel's time.
"""
from bench.work._shapes import nbytes

PALLAS_NAMES = ("mlp_matmul_fused_pallas", "mlp_matmul_stream_pallas",
                "mlp_matmul_split_pallas")


def work(out, ins):
    """(FLOPs, bytes) from the call's result and operand shapes."""
    (_, (m, d)), (_, (_, f)) = ins[0], ins[1]
    dt = ins[0][0]
    flops = 4.0 * m * d * f
    nb = nbytes(ins[0]) + 2 * nbytes((dt, (d, f))) + nbytes((dt, (m, f)))
    if len(ins) == 2:          # one pass of the split variant
        return flops / 2, nb / 2
    return flops, nb
