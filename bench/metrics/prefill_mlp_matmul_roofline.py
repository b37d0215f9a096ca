"""Roofline share of the tuned `mlp_matmul` kernel in the prefill program
(device trace), in %: the summed required time of its calls
(work/mlp_matmul.py at each call's shapes, against bench/peaks.json) over
their summed device time."""


def read(rec):
    tr = rec["trace"]
    k = tr and tr["kernels"]["prefill"].get("mlp_matmul")
    if not k or k[0] <= 0:
        return None
    return 100.0 * k[1] / k[0]
