"""Seconds of graph pretune and freeze in set-up, from the program's own
`pretune` span (the root one) and `freeze` span: the inside twin of
`pretune_s`, which the runner times around the same calls."""
from bench import program_spans


def read(rec):
    pretune = program_spans.seconds(rec, ("pretune",), root=True)
    freeze = program_spans.seconds(rec, ("freeze",))
    if pretune is None or freeze is None:
        return None
    return pretune + freeze
