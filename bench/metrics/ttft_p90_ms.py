"""90th percentile, over every request started in the window, of the time
from its round's start to its first token on the host (host clock)."""
from bench import stats


def read(rec):
    v = stats.percentile(stats.ttfts(rec), 90)
    return None if v is None else v * 1e3
