"""`repro.obs`: spans nest and time on the host clock, counters add up,
the compile listener records what JAX compiles, and the dispatch tier
counters are views of the store."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import api


@pytest.fixture
def store():
    obs.reset()
    yield
    obs.reset()


def _names(recs):
    return [r["name"] for r in recs]


def test_spans_nest_and_record_their_parent(store):
    with obs.span("a", k=1) as a:
        with obs.span("b"):
            time.sleep(0.002)
        with obs.span("c") as c:
            c["attrs"]["n"] = 3
    with obs.span("d"):
        pass
    recs = obs.spans()
    assert _names(recs) == ["a", "b", "c", "d"]
    assert [r["parent"] for r in recs] == [None, 0, 0, None]
    assert a["attrs"] == {"k": 1} and recs[2]["attrs"] == {"n": 3}
    for r in recs:
        assert r["start_ns"] <= r["end_ns"]
    assert recs[0]["start_ns"] <= recs[1]["start_ns"]
    assert recs[2]["end_ns"] <= recs[0]["end_ns"] <= recs[3]["start_ns"]
    assert obs.seconds(recs[1]) >= 0.002


def test_self_time_is_duration_less_children(store):
    with obs.span("outer"):
        with obs.span("inner"):
            time.sleep(0.001)
            with obs.span("leaf"):
                time.sleep(0.001)
        time.sleep(0.001)
    recs = obs.spans()
    dur = [r["end_ns"] - r["start_ns"] for r in recs]
    own = obs.self_ns(recs)
    assert own == [dur[0] - dur[1], dur[1] - dur[2], dur[2]]
    assert all(t > 0 for t in own)


def test_a_span_closes_when_its_block_raises(store):
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError
    with obs.span("after"):
        pass
    recs = obs.spans()
    assert recs[0]["end_ns"] is not None
    assert recs[1]["parent"] is None


def test_counters_add_and_reset(store):
    obs.count("x")
    obs.count("x", 4)
    obs.count("y.a")
    obs.count("y.b", 2)
    assert obs.counters() == {"x": 5, "y.a": 1, "y.b": 2}
    obs.reset_counters("y.")
    assert obs.counters() == {"x": 5}
    with obs.span("s"):
        pass
    obs.reset()
    assert obs.spans() == [] and obs.counters() == {}


def test_compile_listener_records_a_span_per_program(store):
    with obs.span("setup"):
        f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
        f(jnp.arange(7.0)).block_until_ready()
    recs = obs.spans()
    comp = [r for r in recs if r["name"] == "compile"]
    assert comp, _names(recs)
    assert any("lambda" in (r["attrs"]["fun_name"] or "") for r in comp)
    for r in comp:
        assert r["parent"] == 0
        assert recs[0]["start_ns"] <= r["start_ns"] <= r["end_ns"]
        assert r["end_ns"] <= recs[0]["end_ns"]


def test_the_listener_is_installed_once():
    from jax._src import monitoring
    obs._install()
    obs._install()
    assert monitoring.get_event_duration_listeners().count(obs._listen) == 1
    assert monitoring.get_event_listeners().count(obs._listen) == 1


def test_cache_events_are_counted(store):
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert obs.counters() == {"compile.cache_hits": 2,
                              "compile.cache_misses": 1}


def test_dispatch_stats_are_the_stores_dispatch_counters(store):
    a = jnp.ones((64, 128), jnp.float32)
    w = jnp.ones((128,), jnp.float32)
    api.reset_dispatch_stats()
    obs.count("other")
    from repro.kernels import ops
    np.testing.assert_allclose(np.asarray(ops.rms_norm(a, w)), 1.0,
                               rtol=1e-5)
    with api.collect_dispatches():
        ops.rms_norm(a, w)
    st = api.dispatch_stats()
    c = obs.counters()
    tiers = ("frozen", "live", "fallback", "explicit", "collected")
    assert {k: st[k] for k in tiers} == {
        k: c.get(f"dispatch.{k}", 0) for k in tiers}
    assert st["total"] == 1 and st["collected"] == 1
    assert st["total"] == st["frozen"] + st["live"] + st["fallback"] \
        + st["explicit"]
    api.reset_dispatch_stats()
    assert api.dispatch_stats() == dict.fromkeys(tiers + ("total",), 0)
    assert obs.counters() == {"other": 1}


def test_counts_from_many_threads_are_not_lost(store):
    import os
    import sys
    import threading
    workers, per = 4 * (os.cpu_count() or 2), 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda: [
            obs.count("dispatch.frozen") for _ in range(per)])
            for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert api.dispatch_stats()["frozen"] == workers * per


def test_serve_reports_its_own_spans(store):
    from repro import tuning_cache
    from repro.configs import get_smoke
    from repro.launch.serve import serve
    from repro.tuning_cache import TuningDatabase
    tuning_cache.thaw()
    tuning_cache.set_default_db(TuningDatabase())
    try:
        run = serve(get_smoke("starcoder2-3b"), batch=2, prompt_len=16, gen=3,
                    tuned_ops=True, pretune=True)
    finally:
        tuning_cache.thaw()
        tuning_cache.reset_default_db()
    recs = {r["name"]: r for r in obs.spans()}
    assert {"pretune", "freeze", "trace.prefill", "trace.decode",
            "serve.compile.prefill", "serve.compile.decode", "serve.prefill",
            "serve.decode"} <= set(recs)
    assert run["compile_s"] == {
        "prefill": obs.seconds(recs["serve.compile.prefill"]),
        "decode": obs.seconds(recs["serve.compile.decode"])}
    assert run["prefill_ms"] == obs.seconds(recs["serve.prefill"]) * 1e3
    assert run["decode_ms_per_token"] == pytest.approx(
        obs.seconds(recs["serve.decode"]) / 3 * 1e3)
    assert recs["serve.decode"]["attrs"] == {"batch": 2, "steps": 3}
    assert run["dispatch"]["total"] > 0
    assert run["dispatch"]["frozen"] == run["dispatch"]["total"]
