"""Roofline share of the tuned `flash_attention` kernel in the prefill program
(device trace), in %: the summed required time of its calls
(work/flash_attention.py at each call's shapes, against bench/peaks.json) over
their summed device time."""


def read(rec):
    tr = rec["trace"]
    k = tr and tr["kernels"]["prefill"].get("flash_attention")
    if not k or k[0] <= 0:
        return None
    return 100.0 * k[1] / k[0]
