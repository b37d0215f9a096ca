"""Seconds of tracing and compiling the timed programs in set-up (host
clock); served from the persistent cache after a checkout's first run."""


def read(rec):
    return rec.get("compile_s")
