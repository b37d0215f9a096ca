"""95th percentile, over every gap that closed in the window, of the time
between successive tokens of one request on the host (host clock)."""
from bench import stats


def read(rec):
    v = stats.percentile(stats.gaps(rec), 95)
    return None if v is None else v * 1e3
