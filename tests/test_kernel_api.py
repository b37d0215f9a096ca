"""`@tuned_kernel` declarative API tests (ISSUE 4 acceptance).

Covers: the decorator round-trip (declare -> registry ->
`lookup_or_tune` -> params applied to the pallas call), signature
normalization parity with the old per-kernel factories, KernelSpec
misuse (duplicate kernel_id, missing space) raising clear errors, the
Orio-annotation space bridge, the derived fallback params, the
generated `ops` re-exports, the thread-safe dispatch-failure log, and
the stencil2d openness proof (cold rank -> shipped pretuned record ->
warm memo hit from one decorated module).
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tuning_cache
from repro.core import KernelTuner
from repro.core.annotations import annotate_kernel
from repro.kernels import api, ops
from repro.kernels.api import divisors, tuned_kernel
from repro.kernels.common import cdiv, pick_divisor_candidates, tile_legal
from repro.tuning_cache import TuningDatabase


@pytest.fixture(autouse=True)
def _fresh_default_db():
    """Isolate every test from the process-wide default database."""
    tuning_cache.set_default_db(TuningDatabase())
    yield
    tuning_cache.reset_default_db()


def _toy_analysis_for(kernel_id):
    def analysis(p, *, m: int, dtype: str = "float32"):
        bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
        return dict(in_blocks=[(bm, 128)], out_blocks=[(bm, 128)],
                    in_dtypes=[dtype], out_dtypes=[dtype],
                    flops_per_step=2.0 * bm * 128,
                    grid_steps=cdiv(m, bm))
    return analysis


def _declare_toy(kernel_id, **overrides):
    """A minimal decorated kernel: row-blocked doubling of an (m, 128)
    array (the pallas layer is plain jnp so the test stays instant)."""
    decl = dict(
        space={"bm": divisors("m", (8, 16, 32, 64))},
        signature=lambda a, **_: dict(m=a.shape[0], dtype=str(a.dtype)),
        static_info=_toy_analysis_for(kernel_id),
        make_inputs=lambda key, *, m, dtype="float32": (
            jax.random.normal(key, (m, 128), np.dtype(dtype)),),
        reference=lambda a: a * 2.0,
    )
    decl.update(overrides)

    @tuned_kernel(kernel_id, **decl)
    def toy_pallas(a, *, bm: int = 32, interpret=None):
        if a.shape[0] % bm:
            raise ValueError(f"toy: bm={bm} !| m={a.shape[0]}")
        return a * 2.0

    return toy_pallas


# ---------------------------------------------------------------------------
# decorator round-trip
# ---------------------------------------------------------------------------


def test_roundtrip_declare_registry_tune_apply():
    kid = "toy_roundtrip"
    fn = _declare_toy(kid)
    try:
        # declaration registered everywhere
        assert kid in tuning_cache.registered()
        assert kid in api.registered_kernels()
        assert fn.spec is api.get_spec(kid)

        # cold: lookup_or_tune ranks the declared space
        db = TuningDatabase()
        params = tuning_cache.lookup_or_tune(kid, db=db, m=64,
                                             dtype="float32")
        assert params["bm"] in (8, 16, 32, 64)
        assert db.stats.tunes == 1

        # warm: pure hit, identical params
        again = tuning_cache.lookup_or_tune(kid, db=db, m=64,
                                            dtype="float32")
        assert again == params and db.stats.hits == 1

        # the derived op applies the resolved params to the pallas call
        a = jnp.ones((64, 128), jnp.float32)
        out = api.get_spec(kid).op(a)
        np.testing.assert_allclose(out, a * 2.0)
        # ... and the generated ops re-export is the same wrapper
        assert getattr(ops, kid) is api.get_spec(kid).op
    finally:
        api.unregister(kid)


def test_roundtrip_through_kernel_tuner():
    kid = "toy_tuner"
    _declare_toy(kid)
    try:
        tk = api.get_spec(kid).tunable(m=64, dtype="float32")
        rep = KernelTuner(tk, repeats=1, db=None).tune(mode="static")
        assert rep.empirical_evals == 0
        assert rep.best_params["bm"] in (8, 16, 32, 64)
        # hybrid mode exercises build()/make_inputs() derivation
        rep_h = KernelTuner(tk, repeats=1, db=None).tune(
            mode="hybrid", empirical_budget=1)
        assert rep_h.best_measured_s is not None
    finally:
        api.unregister(kid)


def test_tuned_params_bypass_and_fallback():
    kid = "toy_bypass"
    _declare_toy(kid)
    try:
        spec = api.get_spec(kid)
        # 48: candidates (8, 16) divide it, neither is lane-aligned, so
        # the full extent joins them: (8, 16, 48)
        a = jnp.ones((48, 128), jnp.float32)
        np.testing.assert_allclose(spec.op(a, tuned_params={"bm": 8}),
                                   a * 2.0)
        # derived fallback: the largest candidate
        assert spec.fallback_params(m=48) == {"bm": 48}
        assert spec.fallback_params(m=64) == {"bm": 64}
        # no candidate divides -> the dimension itself (never crashes)
        assert spec.fallback_params(m=13) == {"bm": 13}
    finally:
        api.unregister(kid)


def test_failed_resolve_counts_as_fallback_and_fails_the_audit(monkeypatch):
    """A dispatch whose database lookup raises still runs (fallback
    params), but it is counted as a fallback, so the serving audit
    `chip_smoke.py` gates on cannot pass it as tuned."""
    from repro.launch.serve import frozen_audit_problems
    kid = "toy_resolve_fails"
    _declare_toy(kid)

    def broken(*a, **k):
        raise RuntimeError("database unavailable")
    monkeypatch.setattr(tuning_cache, "lookup_or_tune", broken)
    api.reset_dispatch_stats()          # the counters are process-global
    try:
        a = jnp.ones((64, 128), jnp.float32)
        np.testing.assert_allclose(api.get_spec(kid).op(a), a * 2.0)
        st = api.dispatch_stats()
        assert (st["fallback"], st["total"]) == (1, 1)
        problems = frozen_audit_problems({"dispatch": st,
                                          "runtime_tunes": 0})
        assert any("fallback=1" in p for p in problems), problems
    finally:
        api.unregister(kid)
        api.reset_dispatch_stats()


def test_fallback_params_stay_vmem_feasible():
    """The failure path must never emit a launch the chip rejects: the
    derived fallback backs off the largest divisor until the kernel's
    own static analysis fits VMEM (matching the old hand-capped
    fallback lists)."""
    for kid, sig in [("jacobi3d", dict(z=64, y=256, x=256)),
                     ("atax", dict(m=4096, n=4096)),
                     ("matmul", dict(m=4096, n=4096, k=4096)),
                     ("flash_attention",
                      dict(b=1, h=8, sq=4096, skv=4096, d=128)),
                     # d = n = 1600 has no lane-aligned divisor
                     ("mlp_matmul", dict(m=4096, d=1600, f=5504,
                                         dtype="bfloat16")),
                     ("matmul", dict(m=4096, n=1600, k=5504,
                                     dtype="bfloat16"))]:
        spec = api.get_spec(kid)
        fb = spec.fallback_params(**sig)
        assert spec.static_info(fb, **sig).feasible(), (kid, fb)
    # the old conservative caps are reproduced where VMEM binds
    assert api.get_spec("jacobi3d").fallback_params(
        z=64, y=256, x=256) == {"bz": 8}


@pytest.mark.parametrize("block,array,dtype,legal", [
    ((512, 128), (4096, 5504), "bfloat16", True),
    ((128, 64), (5504, 1600), "bfloat16", False),   # lane dim 64 of 1600
    ((128, 1600), (5504, 1600), "bfloat16", True),  # full extent
    ((8, 1600), (4096, 1600), "float32", True),
    ((8, 1600), (4096, 1600), "bfloat16", False),   # bf16 rows pack by 16
    ((4, 1600), (4, 1600), "bfloat16", True),       # full extent rows
    ((1, 512, 128), (32, 1024, 128), "float32", True),
])
def test_tile_legal_is_the_tpu_block_rule(block, array, dtype, legal):
    assert bool(tile_legal(block, array, dtype)) is legal
    # the batched form agrees row by row
    rows = np.array([block[-2]] * 3)
    assert tile_legal((rows, block[-1]), array, dtype).tolist() == \
        [legal] * 3


def test_full_extent_joins_divisors_without_a_lane_aligned_one():
    sizes = (8, 16, 32, 64, 128, 256, 512, 1024)
    assert pick_divisor_candidates(1600, sizes) == (8, 16, 32, 64, 1600)
    assert pick_divisor_candidates(5504, sizes) == (8, 16, 32, 64, 128)
    assert pick_divisor_candidates(4096, sizes) == sizes
    assert pick_divisor_candidates(4, sizes) == (4,)


@pytest.mark.parametrize("arch", ["hymba-1.5b", "starcoder2-3b"])
def test_graph_pretune_picks_obey_the_tpu_block_rule(arch):
    """Every pick the static model makes for a served config at full
    width (batch 4, prompt 1024, prefill and decode) is a block the
    TPU compiler accepts: misaligned tiles are infeasible, not merely
    slower."""
    from repro.configs import get_config
    from repro.core import GraphTuner
    rep = GraphTuner.tune_config(get_config(arch), batch=4, prompt_len=1024,
                                 db=TuningDatabase())
    assert rep["tuned"] == len(rep["instances"]) > 0
    for inst in rep["instances"]:
        spec = api.get_spec(inst["kernel"])
        info = spec.static_info(inst["params"], **inst["signature"])
        assert info.tiles_legal and info.feasible(), inst


def test_unregister_evicts_memoized_ops_attr():
    """Replacing a declaration (unregister + re-declare) must not keep
    dispatching through the stale wrapper ops memoized into globals."""
    kid = "toy_evict"
    _declare_toy(kid)
    try:
        first = getattr(ops, kid)           # memoized into ops globals
        api.unregister(kid)
        _declare_toy(kid)
        assert getattr(ops, kid) is not first
        assert getattr(ops, kid) is api.get_spec(kid).op
    finally:
        api.unregister(kid)


def test_flash_attention_op_accepts_positional_causal():
    """Pre-redesign public signature was flash_attention(q, k, v,
    causal=True, ...); the generated op must keep accepting it."""
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    np.testing.assert_array_equal(ops.flash_attention(q, q, q, False),
                                  ops.flash_attention(q, q, q,
                                                      causal=False))


def test_op_survives_registry_failure(monkeypatch):
    """A broken database layer must degrade to fallback params, not
    break a numerically-correct call — and log only once."""
    kid = "toy_broken"
    _declare_toy(kid)
    try:
        def boom(*a, **k):
            raise RuntimeError("database down")
        monkeypatch.setattr(tuning_cache, "lookup_or_tune", boom)
        api.reset_dispatch_failure_log()
        a = jnp.ones((64, 128), jnp.float32)
        np.testing.assert_allclose(api.get_spec(kid).op(a), a * 2.0)
        assert kid in api._logged_dispatch_failures
        # clear_dispatch_memo resets the failure log too (test hygiene)
        tuning_cache.clear_dispatch_memo()
        assert kid not in api._logged_dispatch_failures
    finally:
        api.unregister(kid)


def test_failure_log_is_thread_safe():
    """Concurrent dispatch failures racing resets must neither raise
    nor corrupt the once-per-kernel log (check-then-act is locked)."""
    api.reset_dispatch_failure_log()
    errors = []

    def hammer(kid):
        try:
            for _ in range(200):
                # unregistered kernel -> lookup fails -> logged failure
                assert api._resolve(kid, dict(m=1)) == {}
                api.reset_dispatch_failure_log()
        except Exception as e:          # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(f"toy_missing_{i}",))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    api.reset_dispatch_failure_log()
    assert not api._logged_dispatch_failures


# ---------------------------------------------------------------------------
# signature normalization parity with the old per-kernel factories
# ---------------------------------------------------------------------------


def test_normalize_applies_defaults_like_old_factories():
    # omitted dtype binds the declared default, exactly as the old
    # inspect-bound factories did: CLI-written records == trace-time keys
    got = tuning_cache.normalize_signature("matmul",
                                           dict(m=256, n=256, k=256))
    assert got == dict(m=256, n=256, k=256, dtype="float32")
    full = tuning_cache.normalize_signature(
        "matmul", dict(m=256, n=256, k=256, dtype="float32"))
    assert got == full
    flash = tuning_cache.normalize_signature(
        "flash_attention", dict(b=1, h=2, sq=256, skv=256, d=128))
    assert flash["causal"] is True and flash["dtype"] == "float32"


def test_normalize_rejects_missing_and_unknown_keys():
    with pytest.raises(TypeError):
        tuning_cache.normalize_signature("matmul", dict(m=256, n=256))
    with pytest.raises(TypeError):
        tuning_cache.normalize_signature(
            "matmul", dict(m=256, n=256, k=256, bogus=1))


def test_normalized_and_explicit_signatures_share_one_record():
    db = TuningDatabase()
    p1 = tuning_cache.lookup_or_tune("stencil2d", db=db, y=256, x=256)
    p2 = tuning_cache.lookup_or_tune("stencil2d", db=db, y=256, x=256,
                                     dtype="float32")
    assert p1 == p2
    assert db.stats.tunes == 1 and db.stats.hits == 1


# ---------------------------------------------------------------------------
# KernelSpec misuse
# ---------------------------------------------------------------------------


def test_duplicate_kernel_id_raises():
    kid = "toy_dup"
    _declare_toy(kid)
    try:
        with pytest.raises(ValueError, match="already registered"):
            _declare_toy(kid)
    finally:
        api.unregister(kid)


def test_missing_or_bad_space_raises():
    with pytest.raises(ValueError, match="space"):
        _declare_toy("toy_nospace", space={})
    with pytest.raises(ValueError, match="space"):
        _declare_toy("toy_nonespace", space=None)
    with pytest.raises(ValueError, match="axis"):
        _declare_toy("toy_badaxis", space={"bm": 32})   # not a sequence
    # a failed declaration must leave nothing registered
    for kid in ("toy_nospace", "toy_nonespace", "toy_badaxis"):
        assert kid not in api.registered_kernels()
        assert kid not in tuning_cache.registered()


def test_divisor_axis_tied_to_unknown_dim_fails_clearly():
    kid = "toy_baddim"
    _declare_toy(kid, space={"bm": divisors("zz", (8, 16))})
    try:
        with pytest.raises(KeyError, match="zz"):
            api.get_spec(kid).problem(m=64)
    finally:
        api.unregister(kid)


# ---------------------------------------------------------------------------
# Orio-annotation bridge
# ---------------------------------------------------------------------------


def test_annotation_string_space_bridge():
    kid = "toy_annotated"
    spec_str = """
    /*@ begin PerfTuning (
     def performance_params {
     param bm[] = [8, 16, 32];
     }
    ) @*/
    """

    @annotate_kernel(
        kid, spec_str,
        signature=lambda a, **_: dict(m=a.shape[0], dtype=str(a.dtype)),
        static_info=_toy_analysis_for(kid))
    def toy_pallas(a, *, bm: int = 8, interpret=None):
        return a * 2.0

    try:
        prob = tuning_cache.get_problem(kid, m=64)
        assert prob.space.axes == {"bm": (8, 16, 32)}
        params = tuning_cache.lookup_or_tune(kid, db=TuningDatabase(),
                                             m=64, dtype="float32")
        assert params["bm"] in (8, 16, 32)
    finally:
        api.unregister(kid)


def test_annotation_bridge_rejects_empty_spec():
    with pytest.raises(ValueError):
        annotate_kernel("toy_badspec", "def performance_params { }",
                        signature=lambda a, **_: {},
                        static_info=_toy_analysis_for("x"))


# ---------------------------------------------------------------------------
# stencil2d: the openness proof
# ---------------------------------------------------------------------------


def test_stencil2d_cold_rank_pretuned_and_warm_memo():
    from repro.core import default_target
    from repro.tuning_cache.registry import dispatch_memo_keys

    # cold: full-space rank through the derived problem
    db = TuningDatabase()
    sig = dict(y=1024, x=1024, dtype="float32")
    params = tuning_cache.lookup_or_tune("stencil2d", db=db, **sig)
    assert params["by"] in (8, 16, 32, 64, 128, 256)
    assert db.stats.tunes == 1

    # shipped per-target pretuned record exists and matches a re-rank
    path = tuning_cache.pretuned_path(default_target())
    shipped = [json.loads(l) for l in open(path)
               if json.loads(l)["key"]["kernel_id"] == "stencil2d"]
    assert shipped, "stencil2d missing from the shipped pretuned grid"
    match = [r for r in shipped if '"y":1024' in r["key"]["signature"]
             and "float32" in r["key"]["signature"]]
    assert match and match[0]["params"] == params

    # warm: default-db dispatch is served from the shipped grid and
    # memoized (zero tunes, memo entry present)
    default = tuning_cache.get_default_db()
    p2 = tuning_cache.lookup_or_tune("stencil2d", **sig)
    p3 = tuning_cache.lookup_or_tune("stencil2d", **sig)
    assert p2 == p3 == params
    assert default.stats.tunes == 0          # shipped-db hit, no rank
    assert any(k[0] == "stencil2d" for k in dispatch_memo_keys())


def test_stencil2d_numerics_and_boundary():
    from repro.kernels.stencil2d import stencil2d_pallas, stencil2d_ref
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((32, 128)), jnp.float32)
    for by in (8, 16, 32):
        out = stencil2d_pallas(u, by=by)
        np.testing.assert_allclose(out, stencil2d_ref(u), rtol=1e-5,
                                   atol=1e-5)
    out = np.asarray(stencil2d_pallas(u, by=8))
    ua = np.asarray(u)
    np.testing.assert_array_equal(out[0], ua[0])
    np.testing.assert_array_equal(out[-1], ua[-1])
    np.testing.assert_array_equal(out[:, 0], ua[:, 0])
    np.testing.assert_array_equal(out[:, -1], ua[:, -1])


def test_stencil2d_dispatches_via_generated_op():
    rng = np.random.default_rng(1)
    from repro.kernels.stencil2d import stencil2d_ref
    u = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    np.testing.assert_allclose(ops.stencil2d(u), stencil2d_ref(u),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# generated ops module
# ---------------------------------------------------------------------------


def test_ops_exposes_exactly_the_registered_kernels():
    assert set(ops.__all__) == set(api.registered_kernels())
    for kid in api.registered_kernels():
        assert callable(getattr(ops, kid))
    with pytest.raises(AttributeError, match="no attribute"):
        ops.not_a_kernel
