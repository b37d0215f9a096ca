"""Seconds of graph pretune and freeze in set-up (host clock)."""


def read(rec):
    return rec.get("pretune_s")
