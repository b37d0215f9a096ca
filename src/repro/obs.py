"""The program's spans, counters and device-side names, in one store.

- `span(name, **attrs)` times a block of host work on the
  `time.perf_counter_ns` clock (the clock a serving runner reads for its
  own window), records the span it nests in as ``parent``, and sits on
  the profiler's host timeline too (a `jax.profiler.TraceAnnotation` of
  the same name) whenever a profiler is running.
- `count(name, n)` adds to a counter; `gauge(name, value)` sets one to
  a property of the program being traced, the same on every trace (the
  last value set is kept).
- `spans()`, `counters()` read the store; `reset()` clears it and
  `reset_counters(prefix)` clears one family of counters.  Nothing is
  written out unless a caller asks.
- `scope(name)` is the program's one way to name device work: a
  `jax.named_scope`, so the name lands in each op's metadata
  (``op_name``) and in no instruction of the executable.
- One `jax.monitoring` listener, installed on import, records every
  backend compile (or persistent-cache load) as a ``compile`` span with
  the ``fun_name`` JAX gives, ending when JAX reports it, and counts
  ``compile.cache_hits`` / ``compile.cache_misses`` of the persistent
  compilation cache.

Span records are dicts: ``name``, ``start_ns``, ``end_ns`` (None while
open), ``index`` (its place in `spans()`), ``parent`` (the enclosing
span's index, or None) and ``attrs``.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import jax

__all__ = ["span", "count", "gauge", "spans", "counters", "reset", "reset_counters",
           "scope", "seconds", "self_ns", "COMPILE_EVENT"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_lock = threading.Lock()
_spans: List[Dict[str, Any]] = []
_counters: Dict[str, int] = collections.defaultdict(int)
_open: "contextvars.ContextVar[Optional[int]]" = contextvars.ContextVar(
    "repro_obs_open_span", default=None)


def _add(name: str, start_ns: int, end_ns: Optional[int],
         attrs: Dict[str, Any]) -> Dict[str, Any]:
    rec = {"name": name, "start_ns": start_ns, "end_ns": end_ns,
           "parent": _open.get(), "attrs": attrs}
    with _lock:
        rec["index"] = len(_spans)
        _spans.append(rec)
    return rec


@contextlib.contextmanager
def span(name: str, **attrs: Any) -> Iterator[Dict[str, Any]]:
    """Time the block as one span; yields its record (callers may add
    to ``rec["attrs"]`` inside).  Spans opened inside are its children."""
    with jax.profiler.TraceAnnotation(name):
        rec = _add(name, time.perf_counter_ns(), None, attrs)
        tok = _open.set(rec["index"])
        try:
            yield rec
        finally:
            rec["end_ns"] = time.perf_counter_ns()
            _open.reset(tok)


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] += n


def gauge(name: str, value: int) -> None:
    with _lock:
        _counters[name] = value


def spans() -> List[Dict[str, Any]]:
    """Every span recorded since the last `reset`, in opening order."""
    with _lock:
        return list(_spans)


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Clear every span and counter (not while a span is open)."""
    with _lock:
        _spans.clear()
        _counters.clear()


def reset_counters(prefix: str) -> None:
    """Clear the counters whose names start with ``prefix``."""
    with _lock:
        for k in [k for k in _counters if k.startswith(prefix)]:
            del _counters[k]


def seconds(rec: Dict[str, Any]) -> float:
    """A closed span's duration in seconds."""
    return (rec["end_ns"] - rec["start_ns"]) * 1e-9


def self_ns(recs: List[Dict[str, Any]]) -> List[int]:
    """Each closed span's duration less its direct children's, for a
    list as `spans()` returns it."""
    pos = {r["index"]: i for i, r in enumerate(recs)}
    own = [r["end_ns"] - r["start_ns"] for r in recs]
    for r in recs:
        if r["parent"] in pos:
            own[pos[r["parent"]]] -= r["end_ns"] - r["start_ns"]
    return own


def scope(name: str):
    """Name the device work traced inside (op metadata only)."""
    return jax.named_scope(name)


def _listen(event: str, duration: Optional[float] = None, **kw: Any) -> None:
    """The program's one `jax.monitoring` listener, for events and
    durations alike."""
    if event == COMPILE_EVENT and duration is not None:
        end = time.perf_counter_ns()
        _add("compile", end - int(duration * 1e9), end,
             {"fun_name": kw.get("fun_name")})
    elif duration is None and event in _CACHE_COUNTERS:
        count(_CACHE_COUNTERS[event])


_installed = False


def _install() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    jax.monitoring.register_event_listener(_listen)
    jax.monitoring.register_event_duration_secs_listener(_listen)


_install()
