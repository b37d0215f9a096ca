"""Share of the decode programs' periods in which no op ran on the device
(device trace), in %.  A prefill's period starts with the host span that
dispatched it, a decode step's at the end of the program before it."""


def read(rec):
    tr = rec["trace"]
    v = tr and tr["idle_share"]["decode"]
    return None if v is None else 100.0 * v
