"""Bytes of decode state that one cached position takes, summed over the
layers: the program's `serve.cache_bytes_per_token` counter, set when it
traces its decode state.  None where the program keeps no such counter."""


def read(rec):
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.counters().get("serve.cache_bytes_per_token")
