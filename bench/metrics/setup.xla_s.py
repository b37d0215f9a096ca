"""Seconds of backend compiles or persistent-cache loads in set-up: the
program's `compile` spans (one per program JAX compiled, from its
compile listener) that ended before the window, the weights' init
included."""
from bench import program_spans


def read(rec):
    return program_spans.seconds(rec, ("compile",))
