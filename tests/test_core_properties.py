"""Property-based tests (hypothesis) on the analyzer's invariants.

Skips cleanly when `hypothesis` is not installed.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import (GPU_TABLE, InstructionMix, SearchSpace,
                        StaticPrunedSearch, cuda_occupancy,
                        default_tpu_model, intensity, predict_time,
                        tpu_occupancy, spearman)
from repro.core.search import (ExhaustiveSearch, GeneticSearch,
                               NelderMeadSearch, RandomSearch,
                               SimulatedAnnealing)
from repro.distributed.sharding import (ACT_RULES, WEIGHT_RULES,
                                        logical_spec)

GPUS = list(GPU_TABLE.values())


# ---------------------------------------------------------------------------
# occupancy
# ---------------------------------------------------------------------------


@given(t=st.integers(1, 1024), r=st.integers(0, 255),
       s=st.integers(0, 49152), g=st.sampled_from(GPUS))
@settings(max_examples=200, deadline=None)
def test_cuda_occupancy_bounds(t, r, s, g):
    occ = cuda_occupancy(t, r, s, g)
    assert 0.0 <= occ.occupancy <= 1.0
    assert occ.active_blocks >= 0
    assert occ.active_warps <= g.warps_per_mp


@given(t=st.integers(1, 1024), r=st.integers(1, 200),
       s=st.integers(1, 40000), g=st.sampled_from(GPUS))
@settings(max_examples=100, deadline=None)
def test_cuda_occupancy_monotone_in_resources(t, r, s, g):
    """More registers / shared memory per block never increases the
    number of active blocks."""
    base = cuda_occupancy(t, r, s, g)
    more_r = cuda_occupancy(t, min(r + 16, 255), s, g)
    more_s = cuda_occupancy(t, r, s + 4096, g)
    assert more_r.active_blocks <= base.active_blocks
    assert more_s.active_blocks <= base.active_blocks


@given(bi=st.lists(st.integers(1024, 2 ** 22), min_size=1, max_size=3),
       bo=st.lists(st.integers(1024, 2 ** 22), min_size=1, max_size=2),
       f=st.floats(0, 1e12), steps=st.integers(1, 10000))
@settings(max_examples=200, deadline=None)
def test_tpu_occupancy_bounds(bi, bo, f, steps):
    occ = tpu_occupancy(bi, bo, f, grid_steps=steps)
    assert 0.0 <= occ.occupancy <= 1.0
    assert occ.predicted_step_time > 0
    assert occ.fits_vmem == (occ.vmem_bytes <= 16 * 1024 ** 2)
    if not occ.fits_vmem:
        assert occ.occupancy == 0.0


# ---------------------------------------------------------------------------
# predictive model
# ---------------------------------------------------------------------------


def _mix(mxu, vpu, hbm, ctrl=0.0):
    return InstructionMix(mxu_flops=mxu, vpu_flops=vpu, hbm_bytes=hbm,
                          mem_ops=hbm / 4.0, ctrl_ops=ctrl)


@given(mxu=st.floats(0, 1e15), vpu=st.floats(0, 1e12),
       hbm=st.floats(0, 1e13))
@settings(max_examples=200, deadline=None)
def test_predict_nonnegative_and_monotone(mxu, vpu, hbm):
    for mode in ("sum", "max"):
        model = default_tpu_model(mode=mode)
        base = model.time(_mix(mxu, vpu, hbm))
        assert base >= 0
        assert model.time(_mix(mxu * 2 + 1, vpu, hbm)) >= base
        assert model.time(_mix(mxu, vpu, hbm * 2 + 1)) >= base
    # sum-composition upper-bounds max-composition
    assert default_tpu_model(mode="sum").time(_mix(mxu, vpu, hbm)) >= \
        default_tpu_model(mode="max").time(_mix(mxu, vpu, hbm)) - 1e-12


@given(a=st.floats(1, 1e9), b=st.floats(1, 1e9))
@settings(max_examples=50, deadline=None)
def test_mix_additive(a, b):
    m1, m2 = _mix(a, a / 2, a * 4), _mix(b, b / 3, b * 2)
    s = m1 + m2
    assert s.mxu_flops == m1.mxu_flops + m2.mxu_flops
    assert s.hbm_bytes == m1.hbm_bytes + m2.hbm_bytes
    model = default_tpu_model(mode="sum")
    assert model.time(s) == pytest.approx(model.time(m1) + model.time(m2),
                                          rel=1e-9)


def test_intensity_definition():
    m = _mix(400.0, 0.0, 400.0)  # 100 mem ops
    assert intensity(m) == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

SPACES = st.builds(
    lambda a, b, c: SearchSpace({"x": tuple(sorted(set(a))),
                                 "y": tuple(sorted(set(b))),
                                 "z": tuple(sorted(set(c)))}),
    st.lists(st.integers(1, 64), min_size=1, max_size=4),
    st.lists(st.integers(1, 8), min_size=1, max_size=3),
    st.lists(st.integers(1, 4), min_size=1, max_size=2),
)


@given(space=SPACES, seed=st.integers(0, 100))
@settings(max_examples=50, deadline=None)
def test_static_pruned_subset_and_zero_evals(space, seed):
    cost = lambda p: p["x"] * 2.0 + p["y"] + 0.1 * p["z"]
    calls = []
    pruner = StaticPrunedSearch(cost, keep_frac=0.25, seed=seed)
    res = pruner.minimize(lambda p: calls.append(p) or 0.0, space,
                          empirical_budget=0)
    assert calls == []                      # zero executions
    assert res.evaluations == 0
    assert res.search_space_reduction == 1.0
    # returns the true argmin of the static cost
    best = min(space.enumerate(), key=cost)
    assert cost(res.best_params) == pytest.approx(cost(best))


@given(space=SPACES, seed=st.integers(0, 20))
@settings(max_examples=20, deadline=None)
def test_strategies_respect_budget_and_find_feasible(space, seed):
    objective = lambda p: float(p["x"]) + 0.5 * p["y"]
    budget = max(3, space.size // 3)
    for strat in (RandomSearch(seed), SimulatedAnnealing(seed),
                  GeneticSearch(seed, pop=4, elite=2),
                  NelderMeadSearch(seed)):
        res = strat.minimize(objective, space, budget=budget)
        assert res.evaluations <= budget + 1
        assert res.best_params in space.enumerate()


def test_exhaustive_finds_optimum():
    space = SearchSpace({"x": (1, 2, 3, 4), "y": (10, 20)})
    res = ExhaustiveSearch().minimize(
        lambda p: abs(p["x"] - 3) + abs(p["y"] - 20), space)
    assert res.best_params == {"x": 3, "y": 20}
    assert res.evaluations == space.size


@given(xs=st.lists(st.floats(-1e3, 1e3), min_size=3, max_size=30,
                   unique=True))
@settings(max_examples=50, deadline=None)
def test_spearman_self_correlation(xs):
    assert spearman(xs, xs) == pytest.approx(1.0)
    assert spearman(xs, [-v for v in xs]) == pytest.approx(-1.0)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


@given(dims=st.lists(
    st.sampled_from(["batch", "embed", "heads", "kv_heads", "mlp",
                     "experts", "vocab", None]),
    min_size=1, max_size=4),
    shape=st.lists(st.sampled_from([1, 3, 5, 8, 16, 24, 60, 64, 128]),
                   min_size=1, max_size=4))
@settings(max_examples=200, deadline=None)
def test_logical_spec_always_valid(dims, shape):
    """Whatever the dims/shape, the resolved spec is consistent: each
    mesh axis used at most once and every sharded dim divisible."""
    if len(dims) != len(shape):
        shape = (shape * 4)[:len(dims)]

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.zeros((4, 8))
    spec = logical_spec(dims, shape, WEIGHT_RULES, FakeMesh())
    sizes = {"data": 4, "model": 8}
    used = []
    for entry, size in zip(tuple(spec) + (None,) * len(shape), shape):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            assert a not in used
            used.append(a)
            n *= sizes[a]
        assert size % n == 0
