"""Tail and rate arithmetic over a run's requests: every request, the whole window."""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation; None when empty."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttfts(rec: Dict) -> List[float]:
    """Seconds from each request's round start to its first token, for
    every request started in the window (the serving window closes at a
    round boundary, so that is every request of the run)."""
    t0, t1 = rec["window"]
    return [r["times"][0] - r["start"] for r in rec["requests"]
            if t0 <= r["start"] < t1 and r["times"]]


def gaps(rec: Dict) -> List[float]:
    """Seconds between successive tokens of one request, for every gap
    that closed inside the window."""
    t0, t1 = rec["window"]
    out = []
    for r in rec["requests"]:
        ts = r["times"]
        out += [b - a for a, b in zip(ts, ts[1:]) if t0 <= b <= t1]
    return out


def tokens_in_window(rec: Dict) -> int:
    t0, t1 = rec["window"]
    return sum(1 for r in rec["requests"] for t in r["times"] if t0 <= t <= t1)


def rate(rec: Dict) -> float:
    """Output tokens read back inside the window per second of window."""
    t0, t1 = rec["window"]
    return tokens_in_window(rec) / (t1 - t0)
