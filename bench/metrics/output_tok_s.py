"""Output tokens read back in the window per second of window (host clock)."""
from bench import stats


def read(rec):
    return stats.rate(rec)
