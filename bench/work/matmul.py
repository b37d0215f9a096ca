"""Required work of one matmul (m, k) @ (k, n): 2 m k n FLOPs, each operand
read once and the result written once."""
from bench.work._shapes import nbytes

PALLAS_NAMES = ("matmul_pallas",)


def work(out, ins):
    (_, (m, k)), (_, (_, n)) = ins[0], ins[1]
    return 2.0 * m * k * n, nbytes(ins[0]) + nbytes(ins[1]) + nbytes(out[0])
