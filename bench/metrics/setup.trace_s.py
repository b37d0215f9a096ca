"""Seconds of Python tracing of the serving programs in set-up, dispatch
at trace time included: the program's root `trace.prefill` and
`trace.decode` spans (those under `pretune` are its enumeration)."""
from bench import program_spans


def read(rec):
    return program_spans.seconds(rec, ("trace.prefill", "trace.decode"),
                                 root=True)
