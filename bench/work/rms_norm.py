"""Required work of one RMSNorm over (m, d) rows: about 4 m d FLOPs (square,
sum, scale twice); x and the gain read once, the result written once."""
from bench.work._shapes import nbytes

PALLAS_NAMES = ("rms_norm_pallas",)


def work(out, ins):
    (_, (m, d)) = ins[0]
    return 4.0 * m * d, sum(nbytes(s) for s in ins) + nbytes(out[0])
