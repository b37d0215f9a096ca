"""repro.tuning_cache — the persistent tuning database + dispatch registry.

The paper's thesis (near-optimal launch parameters from static analysis,
zero program runs) implies tuning results are pure functions of
``(kernel, shapes/dtype, hardware, tuner mode, model version)`` — so we
compute them once and reuse them everywhere:

* `keys`      content-addressed cache keys + the MODEL_VERSION stamp
* `store`     TuningRecord, in-process LRU, on-disk JSON, JSONL interchange
* `registry`  trace-time dispatch: kernels resolve launch params via
              `lookup_or_tune` instead of hard-coded defaults
* `cli`       ``python -m repro.tuning_cache export|import|show|tune``

The process-wide default database is memory-only unless the
``REPRO_TUNING_CACHE_DIR`` environment variable points at a directory;
it is warmed at first use from the pre-tuned JSONL shipped for the
active hardware target under ``tuning_cache/pretuned/`` (one
``<target>.jsonl`` per chip), so common shapes dispatch warm out of the
box.  Dispatching under another target (`repro.core.target.use_target`
or ``REPRO_TUNING_TARGET``) lazily warms that target's file on first
use.

See DESIGN.md §6-§7 for the key schema and invalidation rules.
"""
from __future__ import annotations

import os
from typing import Optional

from repro.tuning_cache.keys import (CacheKey, MODEL_VERSION, canonical_json,
                                     fingerprint_spec, make_key)
from repro.tuning_cache.store import (CacheStats, DiskStore, TuningDatabase,
                                      TuningRecord)
from repro.tuning_cache import registry
from repro.tuning_cache.registry import (ENV_MODEL, MODEL_KINDS,
                                         TuningProblem, clear_dispatch_memo,
                                         default_model_kind,
                                         dispatch_key, freeze, frozen_lookup,
                                         frozen_table, get_problem,
                                         invalidate_kernel, is_frozen,
                                         lookup_or_tune,
                                         normalize_signature,
                                         on_dispatch_memo_clear, rank_space,
                                         register, register_entry,
                                         registered, set_default_model,
                                         thaw, unregister)

__all__ = [
    "CacheKey", "MODEL_VERSION", "canonical_json", "fingerprint_spec",
    "make_key", "CacheStats", "DiskStore", "TuningDatabase", "TuningRecord",
    "TuningProblem", "clear_dispatch_memo", "get_problem", "lookup_or_tune",
    "normalize_signature", "on_dispatch_memo_clear", "rank_space",
    "register", "register_entry", "registered", "unregister",
    "invalidate_kernel", "dispatch_key",
    "ENV_MODEL", "MODEL_KINDS", "default_model_kind", "set_default_model",
    "freeze", "thaw", "is_frozen", "frozen_lookup", "frozen_table",
    "get_default_db", "set_default_db", "reset_default_db", "pretuned_dir",
    "pretuned_path", "warm_pretuned",
]

ENV_DB_DIR = "REPRO_TUNING_CACHE_DIR"

_default_db: Optional[TuningDatabase] = None


def pretuned_dir() -> str:
    """Directory of pre-tuned JSONL databases shipped with the package."""
    return os.path.join(os.path.dirname(__file__), "pretuned")


def pretuned_path(target=None) -> str:
    """Shipped JSONL for one hardware target: ``pretuned/<name>.jsonl``
    (canonical name, '-' -> '_'; e.g. tpu-v5p -> tpu_v5p.jsonl)."""
    from repro.core.hw import resolve_target
    name = resolve_target(target).name.replace("-", "_")
    return os.path.join(pretuned_dir(), f"{name}.jsonl")


def warm_pretuned(db: TuningDatabase, target=None) -> int:
    """Fold the target's shipped pretuned records into ``db`` (memory
    only), once per (database, target) — repeat calls are a set probe.
    Missing file (a target we ship no database for) warms nothing."""
    from repro.core.hw import resolve_target
    spec = resolve_target(target)
    return _warm_pretuned_spec(db, spec)


def _warm_pretuned_spec(db: TuningDatabase, spec) -> int:
    # check-then-add under the database lock: two threads taking their
    # first dispatch for the same target must not double-import (and
    # double-bump the generation, spuriously invalidating the memo)
    with db.lock:
        if spec.name in db.warmed_targets:
            return 0
        db.warmed_targets.add(spec.name)
        path = pretuned_path(spec)
        if os.path.isfile(path):
            return db.warm_jsonl(path)
        return 0


def get_default_db() -> TuningDatabase:
    """Process-wide database: LRU + optional env-configured disk root,
    warmed from the pre-tuned JSONL shipped for the default target
    (other targets warm lazily at first dispatch)."""
    global _default_db
    if _default_db is None:
        _default_db = TuningDatabase(root=os.environ.get(ENV_DB_DIR))
        warm_pretuned(_default_db)
    return _default_db


def set_default_db(db: Optional[TuningDatabase]) -> None:
    global _default_db
    _default_db = db
    # the dispatch memo shadows the default database; a new default
    # must not serve another database's answers
    clear_dispatch_memo()


def reset_default_db() -> None:
    """Drop the process default (tests; env-var changes)."""
    set_default_db(None)
