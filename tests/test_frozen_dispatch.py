"""Frozen warm-dispatch tier tests (ISSUE 6 acceptance).

Covers: live/frozen parity for every registered kernel under every
shipped target (including kwarg-order-permuted and default-elided
signature spellings, scoped-target overrides, and explicit-spec
probes), freeze priming from database-resident records (the serve.py
startup posture), the full invalidation matrix (db clear / import /
default-db swap / memo clear / default-target change / unregister),
mutation safety of frozen-path results, the unhashable-signature
fallback regression, and binder exclusion of non-compilable
declarations.
"""
import json
import os
import subprocess
import sys

import pytest

from repro import tuning_cache
from repro.core import (default_target, resolve_target, set_default_target,
                        use_target)
from repro.core.search import SearchSpace
from repro.tuning_cache import TuningDatabase
from repro.tuning_cache import registry as registry_mod
from repro.tuning_cache.binder import MISSING, compile_binder, schema_of
from repro.tuning_cache.cli import SHIPPED_TARGETS

import repro.kernels  # noqa: F401  (registers dispatch problems)
from repro.kernels import api


@pytest.fixture(autouse=True)
def _fresh_state():
    set_default_target(None)
    tuning_cache.set_default_db(TuningDatabase())
    yield
    tuning_cache.thaw()
    set_default_target(None)
    tuning_cache.reset_default_db()


# One representative signature per registered kernel — small shapes so
# the cold ranks across 6 targets stay cheap.  dtype (and causal) ride
# on declared defaults, giving every kernel an elidable key.
_SIGS = {
    "matmul": dict(m=256, n=256, k=256, dtype="float32"),
    "flash_attention": dict(b=2, h=4, sq=512, skv=512, d=64, causal=True,
                            dtype="float32"),
    "atax": dict(m=512, n=512, dtype="float32"),
    "bicg": dict(m=512, n=512, dtype="float32"),
    "matvec": dict(m=512, n=512, dtype="float32"),
    "jacobi3d": dict(z=32, y=32, x=32, dtype="float32"),
    "stencil2d": dict(y=512, x=512, dtype="float32"),
    "rms_norm": dict(m=256, d=256, dtype="float32"),
    "mlp_matmul": dict(m=256, d=256, f=512, act="silu", dtype="float32"),
}


def _spellings(sig):
    """Exact, kwarg-order-permuted, and default-elided spellings."""
    permuted = dict(reversed(list(sig.items())))
    elided = {k: v for k, v in sig.items() if k not in ("dtype", "causal")}
    return [sig, permuted, elided]


# ---------------------------------------------------------------------------
# Parity: every kernel x every shipped target, every spelling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("target", SHIPPED_TARGETS)
def test_frozen_parity_all_kernels(target):
    assert set(_SIGS) == set(api.registered_kernels()), (
        "update _SIGS: the registered kernel set changed")
    set_default_target(target)
    live = {kid: tuning_cache.lookup_or_tune(kid, **sig)
            for kid, sig in _SIGS.items()}
    n = tuning_cache.freeze()
    assert n >= len(_SIGS)
    for kid, sig in _SIGS.items():
        for spelling in _spellings(sig):
            assert tuning_cache.frozen_lookup(kid, spelling) == live[kid]
            # the public dispatch entry takes the same frozen fast path
            assert tuning_cache.lookup_or_tune(kid, **spelling) == live[kid]
        # explicit-spec probe (name and resolved spec) hits the same table
        assert tuning_cache.frozen_lookup(kid, sig, spec=target) == live[kid]
        assert tuning_cache.frozen_lookup(
            kid, sig, spec=resolve_target(target)) == live[kid]


# ---------------------------------------------------------------------------
# The disk store: a fresh database on the same root answers without a tune
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("target", SHIPPED_TARGETS)
@pytest.mark.parametrize("kid", sorted(_SIGS))
def test_disk_store_answers_a_fresh_db(kid, target, tmp_path):
    """A cold rank written through to a `DiskStore` answers a second
    database on the same root (another process's view) with the same
    params and no tune of its own."""
    root = str(tmp_path / "db")
    cold = TuningDatabase(root=root)
    params = tuning_cache.lookup_or_tune(kid, spec=target, db=cold,
                                         **_SIGS[kid])
    assert cold.stats.tunes == 1
    fresh = TuningDatabase(root=root)
    assert tuning_cache.lookup_or_tune(kid, spec=target, db=fresh,
                                       **_SIGS[kid]) == params
    assert fresh.stats.tunes == 0 and fresh.stats.hits == 1


_COLD_DISPATCH = """
import json, sys
import repro.kernels  # noqa: F401  (registers dispatch problems)
from repro.tuning_cache import TuningDatabase, lookup_or_tune
db = TuningDatabase(root=sys.argv[1])
print(json.dumps(lookup_or_tune("matmul", spec="tpu-v5e", db=db,
                                m=320, n=320, k=320)))
"""


def test_processes_sharing_a_disk_store_agree_on_a_cold_key(tmp_path):
    """Two processes dispatch one cold key into one disk root at once:
    both answer the same params, and the store ends holding one whole
    record (atomic publish; a race costs at most a duplicate rank)."""
    root = str(tmp_path / "db")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    procs = [subprocess.Popen([sys.executable, "-c", _COLD_DISPATCH, root],
                              stdout=subprocess.PIPE, env=env, text=True)
             for _ in range(2)]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert [p.returncode for p in procs] == [0, 0]
    answers = [json.loads(o.strip().splitlines()[-1]) for o in outs]
    assert answers[0] == answers[1]
    reader = TuningDatabase(root=root)
    recs = list(reader.records())
    assert len(recs) == 1 and recs[0].params == answers[0]
    assert reader.disk.corrupt_seen == 0
    # no temp file or quarantined copy left beside the writers' lock
    assert set(os.listdir(root)) - {".lock"} == {
        f"{recs[0].key.digest}.json"}


def test_frozen_respects_scoped_target_override():
    """A `use_target` scope must route the frozen probe to that chip's
    subtable, never the freeze-time default's."""
    sig = _SIGS["atax"]
    p_default = tuning_cache.lookup_or_tune("atax", **sig)
    with use_target("tpu-v5p"):
        p_v5p = tuning_cache.lookup_or_tune("atax", **sig)
    tuning_cache.freeze()
    assert tuning_cache.frozen_lookup("atax", sig) == p_default
    with use_target("tpu-v5p"):
        assert tuning_cache.frozen_lookup("atax", sig) == p_v5p
        assert tuning_cache.lookup_or_tune("atax", **sig) == p_v5p
    # winners genuinely differ across these chips for this shape family
    # in general; parity above is what matters either way
    assert tuning_cache.frozen_lookup("atax", sig) == p_default


def test_frozen_misses_cleanly():
    tuning_cache.lookup_or_tune("matmul", **_SIGS["matmul"])
    tuning_cache.freeze()
    # unknown signature key / missing required key / un-frozen kernel id
    assert tuning_cache.frozen_lookup(
        "matmul", dict(_SIGS["matmul"], bogus=1)) is None
    assert tuning_cache.frozen_lookup("matmul", dict(m=256, n=256)) is None
    assert tuning_cache.frozen_lookup("nonexistent", dict(m=1)) is None
    # a signature never dispatched is a miss, and falls through to a
    # correct live tune via the public path
    cold = dict(m=320, n=320, k=320, dtype="float32")
    assert tuning_cache.frozen_lookup("matmul", cold) is None
    assert tuning_cache.lookup_or_tune("matmul", **cold)["bm"] >= 8


def test_freeze_primes_from_db_resident_records():
    """serve.py freezes right after warming the database, before any
    dispatch has populated the memo — frozen tables must compile from
    the database records themselves."""
    sig = dict(m=1024, n=1024, k=1024, dtype="float32")
    tuning_cache.lookup_or_tune("matmul", **sig)   # warms shipped v5e JSONL
    tuning_cache.clear_dispatch_memo()             # memo empty, db warm
    n = tuning_cache.freeze()
    assert n > 1
    # a pretuned signature never dispatched in this process is frozen
    st_sig = dict(y=1024, x=1024, dtype="float32")
    frozen = tuning_cache.frozen_lookup("stencil2d", st_sig)
    assert frozen is not None
    tuning_cache.thaw()
    assert frozen == tuning_cache.lookup_or_tune("stencil2d", **st_sig)


def test_freeze_is_idempotent_until_invalidated():
    tuning_cache.lookup_or_tune("matmul", **_SIGS["matmul"])
    n1 = tuning_cache.freeze()
    state = registry_mod._FROZEN
    n2 = tuning_cache.freeze()
    assert n1 == n2 and registry_mod._FROZEN is state   # reused, not rebuilt
    tuning_cache.thaw()
    assert tuning_cache.freeze() == n1


# ---------------------------------------------------------------------------
# Invalidation matrix
# ---------------------------------------------------------------------------


def test_invalidated_by_db_clear_and_import(tmp_path):
    sig = _SIGS["stencil2d"]
    params = tuning_cache.lookup_or_tune("stencil2d", **sig)
    db = tuning_cache.get_default_db()

    tuning_cache.freeze()
    db.clear()
    assert not tuning_cache.is_frozen()
    # post-thaw dispatch re-tunes rather than serving the dropped record
    assert tuning_cache.lookup_or_tune("stencil2d", **sig) == params
    assert db.stats.tunes == 1

    # import_jsonl with doctored params: thaw + new answer served
    rec = next(r for r in db.snapshot()
               if r.key.kernel_id == "stencil2d")
    doctored = rec.to_dict()
    new_by = 8 if params["by"] != 8 else 16
    doctored["params"] = {"by": new_by}
    path = tmp_path / "doctored.jsonl"
    path.write_text(json.dumps(doctored) + "\n")
    tuning_cache.freeze()
    assert tuning_cache.frozen_lookup("stencil2d", sig) == params
    assert db.import_jsonl(str(path)) == 1
    assert not tuning_cache.is_frozen()
    assert tuning_cache.lookup_or_tune("stencil2d", **sig) == {"by": new_by}


def test_invalidated_by_memo_clear_db_swap_target_change_unregister():
    sig = _SIGS["matmul"]
    tuning_cache.lookup_or_tune("matmul", **sig)

    tuning_cache.freeze()
    tuning_cache.clear_dispatch_memo()
    assert not tuning_cache.is_frozen()

    tuning_cache.freeze()
    tuning_cache.set_default_db(TuningDatabase())
    assert not tuning_cache.is_frozen()

    tuning_cache.lookup_or_tune("matmul", **sig)
    tuning_cache.freeze()
    set_default_target("tpu-v5p")       # fast path specialization stale
    assert not tuning_cache.is_frozen()
    set_default_target(None)
    assert not tuning_cache.is_frozen()

    tuning_cache.lookup_or_tune("matmul", **sig)
    tuning_cache.freeze()
    spec = api.get_spec("matmul")
    try:
        api.unregister("matmul")
        assert not tuning_cache.is_frozen()
    finally:
        api.register_spec(spec)


def test_invalidated_by_variant_register_and_unregister():
    """Frozen tables bind each kernel's variant-set digest: removing or
    (re-)adding a variant must thaw, and a refreeze after the variant
    set changed excludes the now-stale records (fresh dispatch re-ranks
    under the new digest rather than serving the old winner)."""
    fsig = _SIGS["flash_attention"]
    tuning_cache.lookup_or_tune("flash_attention", **fsig)

    tuning_cache.freeze()
    v = api.unregister_variant("flash_attention", "blocked")
    try:
        assert not tuning_cache.is_frozen()
        # refreeze under the reduced set: the record ranked under the
        # full set carries the old digest and must NOT be frozen in
        tuning_cache.freeze()
        assert tuning_cache.frozen_lookup("flash_attention", fsig) is None
        p_reduced = tuning_cache.lookup_or_tune("flash_attention", **fsig)
        assert p_reduced["variant"] == "flash"
    finally:
        api.register_variant("flash_attention", v)
    # re-registering thawed again, and the original digest is warm in
    # the database: dispatch serves the full-set winner without a tune
    assert not tuning_cache.is_frozen()
    db = tuning_cache.get_default_db()
    tunes = db.stats.tunes
    p_full = tuning_cache.lookup_or_tune("flash_attention", **fsig)
    assert db.stats.tunes == tunes
    assert p_full["variant"] in api.get_spec("flash_attention").variant_ids()


def test_op_wrapper_picks_up_thaw_and_refreeze():
    """The generated op caches its frozen probe; the cache must follow
    thaw/re-freeze by identity, never serving a stale table."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((64, 1)), jnp.float32)
    expect = np.asarray(ref.atax_ref(a, x))

    def run():
        np.testing.assert_allclose(np.asarray(ops.atax(a, x)), expect,
                                   rtol=2e-4, atol=2e-4)

    run()                               # live path
    tuning_cache.freeze()
    assert tuning_cache.is_frozen()
    run()                               # frozen path
    tuning_cache.thaw()
    run()                               # back to live
    tuning_cache.freeze()
    run()                               # re-frozen


# ---------------------------------------------------------------------------
# Mutation safety (frozen mirrors the live snapshot-as-items guarantee)
# ---------------------------------------------------------------------------


def test_frozen_result_is_mutation_safe():
    sig = _SIGS["matmul"]
    original = dict(tuning_cache.lookup_or_tune("matmul", **sig))
    tuning_cache.freeze()

    got = tuning_cache.frozen_lookup("matmul", sig)
    got["bm"] = -1
    got["injected"] = "poison"
    assert tuning_cache.frozen_lookup("matmul", sig) == original

    got2 = tuning_cache.lookup_or_tune("matmul", **sig)   # frozen fast path
    got2.clear()
    assert tuning_cache.lookup_or_tune("matmul", **sig) == original

    probe = tuning_cache.frozen_table("matmul")
    got3 = probe(sig)
    got3.update(bm=0, bn=0, bk=0)
    assert probe(sig) == original

    # ... and thawing back to the live tiers still serves clean params
    tuning_cache.thaw()
    assert tuning_cache.lookup_or_tune("matmul", **sig) == original


# ---------------------------------------------------------------------------
# Unhashable-signature fallback (the registry TypeError branch)
# ---------------------------------------------------------------------------


def test_unhashable_signature_bypasses_memo_and_freeze():
    """An unhashable signature value must bypass both the memo and the
    frozen tables, still tune correctly, and poison neither cache."""

    @tuning_cache.register("unhash_regress")
    def _factory(*, dims, dtype="float32"):
        return tuning_cache.get_problem("atax", m=dims[0], n=dims[1],
                                        dtype=dtype)

    try:
        dims = [512, 512]               # list: valid signature, unhashable
        db = tuning_cache.get_default_db()
        p1 = tuning_cache.lookup_or_tune("unhash_regress", dims=dims)
        expect = tuning_cache.lookup_or_tune("atax", m=512, n=512,
                                             db=TuningDatabase(),
                                             spec=default_target())
        assert p1 == expect             # tuned correctly despite the bypass
        # repeat call: served from the database, not re-tuned
        tunes = db.stats.tunes
        assert tuning_cache.lookup_or_tune("unhash_regress", dims=dims) == p1
        assert db.stats.tunes == tunes
        # the memo shard holds nothing for it
        assert not any(k[0] == "unhash_regress"
                       for k in registry_mod.dispatch_memo_keys())
        # freeze skips it (its db record carries the unhashable value)
        tuning_cache.freeze()
        assert tuning_cache.frozen_lookup("unhash_regress",
                                          dict(dims=dims)) is None
        assert tuning_cache.frozen_table("unhash_regress") is None
        # ... and keeps serving other kernels from the frozen tier
        msig = _SIGS["matmul"]
        tuning_cache.thaw()
        m_live = tuning_cache.lookup_or_tune("matmul", **msig)
        tuning_cache.freeze()
        assert tuning_cache.frozen_lookup("matmul", msig) == m_live
        # dispatch with the unhashable value still works while frozen
        assert tuning_cache.lookup_or_tune("unhash_regress",
                                           dims=dims) == p1
    finally:
        tuning_cache.unregister("unhash_regress")


# ---------------------------------------------------------------------------
# Binder: declaration-time normalization building blocks
# ---------------------------------------------------------------------------


def test_binder_canonicalizes_spellings():
    import inspect

    def schema(*, m, n, dtype="float32"):
        ...

    b = compile_binder(schema_of(
        inspect.signature(schema).parameters.values()))
    full = b.key(dict(m=1, n=2, dtype="bf16"))
    assert full == (1, 2, "bf16")
    assert b.key(dict(dtype="bf16", n=2, m=1)) == full      # permuted
    assert b.key(dict(m=1, n=2)) == (1, 2, "float32")       # elided
    assert b.key(dict(m=1)) is None                         # missing req
    assert b.key(dict(m=1, n=2, bogus=3)) is None           # unknown key
    assert b.key(dict(m=1, n=2, dtype="x", bogus=3)) is None
    assert b.normalized(dict(n=2, m=1)) == dict(m=1, n=2, dtype="float32")
    assert b.names == ("m", "n", "dtype")
    assert b.schema[0] == ("m", MISSING)


def test_binder_rejects_uncompilable_schemas():
    import inspect

    def var_kw(**sig): ...
    def var_pos(*sig): ...
    def unhashable_default(*, m, opts=[1, 2]): ...          # noqa: B006

    for fn in (var_kw, var_pos, unhashable_default):
        assert schema_of(inspect.signature(fn).parameters.values()) is None
    assert compile_binder(None) is None


def test_binderless_registration_uses_raw_memo_and_skips_freeze():
    """A legacy ``**kwargs`` factory cannot be compiled: it must keep
    dispatching through the raw-keyed live memo and be excluded from
    frozen tables."""

    @tuning_cache.register("rawkw_kernel")
    def _factory(**sig):
        return tuning_cache.get_problem("stencil2d", **sig)

    try:
        sig = dict(y=256, x=256, dtype="float32")
        p = tuning_cache.lookup_or_tune("rawkw_kernel", **sig)
        assert p["by"] >= 8
        raw = [k for k in registry_mod.dispatch_memo_keys()
               if k[0] == "rawkw_kernel"]
        assert raw and raw[0][3][0] == "#raw"
        tuning_cache.freeze()
        assert tuning_cache.frozen_table("rawkw_kernel") is None
        assert tuning_cache.lookup_or_tune("rawkw_kernel", **sig) == p
    finally:
        tuning_cache.unregister("rawkw_kernel")


def test_sharded_memo_canonicalizes_spellings():
    """Permuted/elided spellings of one signature share one live memo
    entry (the binder keys the shard), where the old raw-spelling memo
    stored three."""
    sig = _SIGS["jacobi3d"]
    for spelling in _spellings(sig):
        tuning_cache.lookup_or_tune("jacobi3d", **spelling)
    keys = [k for k in registry_mod.dispatch_memo_keys()
            if k[0] == "jacobi3d"]
    assert len(keys) == 1
