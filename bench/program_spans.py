"""The program's own spans (`repro.obs`) from before the window opened.

The store lives in the process that ran the cell, on the same
`time.perf_counter` clock as the runner's ``rec["window"]``.  A program
without `repro.obs` keeps no spans: the readers then give no value.
"""
from __future__ import annotations

from typing import Dict, List, Optional


def before_window(rec: Dict) -> Optional[List[Dict]]:
    """Closed spans that ended before the window's start, or None where
    the program keeps no store."""
    try:
        from repro import obs
    except ImportError:
        return None
    t0_ns = rec["window"][0] * 1e9
    return [s for s in obs.spans()
            if s["end_ns"] is not None and s["end_ns"] <= t0_ns]


def seconds(rec: Dict, names, root: bool = False) -> Optional[float]:
    """Summed seconds of the set-up spans named in ``names`` (only those
    opened inside no other span, with ``root``); None where there are
    none."""
    spans = before_window(rec)
    picked = [s for s in spans or ()
              if s["name"] in names and not (root and s["parent"] is not None)]
    if not picked:
        return None
    return sum(s["end_ns"] - s["start_ns"] for s in picked) * 1e-9
