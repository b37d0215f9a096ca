"""End-to-end behaviour tests for the paper's system.

1. The autotuner stack: static mode never executes, hybrid beats naive
   picks, calibration tightens the model, Spearman(static, measured) is
   positive on a real kernel sweep.
2. Training end-to-end: loss decreases on the synthetic stream.
3. Multi-device SPMD: an 8-device sub-mesh lowers the sharded train
   step, the HLO contains collectives, and the loop-aware analyzer sees
   them (runs in a subprocess so this process keeps 1 device).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (KernelTuner, calibrate, default_tpu_model,
                        spearman)
from repro.kernels import make_tunable_atax, make_tunable_matmul

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# autotuner system behaviour
# ---------------------------------------------------------------------------


def test_static_mode_runs_nothing_and_prunes_everything():
    tk = make_tunable_atax(m=512, n=256)
    calls = []
    orig_build = tk.build
    tk.build = lambda p: calls.append(p) or orig_build(p)
    rep = KernelTuner(tk, repeats=1).tune(mode="static")
    assert calls == []
    assert rep.empirical_evals == 0
    assert rep.search_space_reduction == 1.0
    assert rep.best_params in tk.space.enumerate()


def test_hybrid_measures_only_shortlist():
    tk = make_tunable_matmul(m=512, n=512, k=512)   # 27-point space
    rep = KernelTuner(tk, repeats=1, keep_frac=0.25).tune(
        mode="hybrid", empirical_budget=2)
    assert rep.empirical_evals == 2
    assert rep.best_measured_s is not None
    assert rep.search_space_reduction > 0.5


def test_static_rank_correlates_with_measurement():
    tk = make_tunable_matmul(m=512, n=512, k=512)
    tuner = KernelTuner(tk, repeats=3)
    rep = tuner.tune(mode="empirical", empirical_budget=10)
    assert rep.spearman_static_vs_measured is not None
    assert rep.spearman_static_vs_measured > 0.3, rep.summary()


def test_calibration_reduces_error():
    tk = make_tunable_atax(m=512, n=256)
    tuner = KernelTuner(tk, repeats=2)
    pts = [(p, tuner._info(p).mix) for p in tk.space.enumerate()]
    from benchmarks.common import median_time
    inputs = tk.make_inputs()
    times = [median_time(tk.build(p), inputs, 2) for p, _ in pts]
    mixes = [m for _, m in pts]
    base = default_tpu_model(mode="sum")
    fit = calibrate(mixes, times, mode="sum")
    err_base = np.mean([abs(base.time(m) - t) / t
                        for m, t in zip(mixes, times)])
    err_fit = np.mean([abs(fit.time(m) - t) / t
                       for m, t in zip(mixes, times)])
    assert err_fit <= err_base + 1e-9


# ---------------------------------------------------------------------------
# end-to-end training
# ---------------------------------------------------------------------------


def test_training_loss_decreases():
    from repro.data import DataConfig, TokenStream
    from repro.distributed import make_train_step
    from repro.models import ModelConfig, build_model
    from repro.optim import AdamWConfig, init_adamw

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv=2, d_ff=128, vocab=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = init_adamw(params)
    step = jax.jit(make_train_step(
        model, AdamWConfig(peak_lr=1e-2, warmup_steps=5, decay_steps=100)),
        donate_argnums=(0, 1))
    stream = TokenStream(DataConfig(vocab=512, global_batch=8, seq_len=64))
    losses = []
    for s in range(25):
        b = {k: jnp.asarray(v) for k, v in stream.make_batch(s).items()}
        params, opt, m = step(params, opt, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses[::6]
    assert all(np.isfinite(l) for l in losses)


# ---------------------------------------------------------------------------
# multi-device SPMD (subprocess: needs its own device count)
# ---------------------------------------------------------------------------

_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke
    from repro.core.hlo import collective_stats, module_mix, parse_hlo
    from repro.distributed import make_train_step, TrainStepConfig
    from repro.launch.specs import cell_inputs
    from repro.models import build_model
    from repro.models.config import ShapeSpec
    from repro.optim import AdamWConfig

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_smoke("gemma-7b")
    model = build_model(cfg)
    shape = ShapeSpec("tiny_train", 64, 8, "train")
    args = cell_inputs(model, shape, mesh)
    step = make_train_step(model, AdamWConfig(), mesh=mesh,
                           step_cfg=TrainStepConfig(microbatches=2))
    with mesh:
        compiled = jax.jit(step).lower(*args).compile()
        text = compiled.as_text()
    mod = parse_hlo(text)
    coll = collective_stats(mod)
    mix = module_mix(mod)
    print(json.dumps({
        "collective_bytes": coll.total_bytes,
        "kinds": sorted(coll.by_kind_bytes),
        "flops": mix.mxu_flops,
    }))
""")


@pytest.mark.slow
def test_spmd_submesh_lowering_and_collectives():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", _SPMD_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["collective_bytes"] > 0
    assert "all-reduce" in rec["kinds"] or "all-gather" in rec["kinds"]
    assert rec["flops"] > 0


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_is_placed_from_outside(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` places the persistent compile cache;
    without it the cache lives at one fixed path inside the checkout."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = str(tmp_path / "cc")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "path = enable_compile_cache()\n"
            "print(path, jax.config.jax_compilation_cache_dir,\n"
            "      jax.config.jax_enable_compilation_cache)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want, "True"]


def test_warm_tuning_db_reports_and_strict_exits(tmp_path, capsys):
    from repro import tuning_cache
    from repro.core.hw import TPU_V5E
    from repro.launch.serve import _warm_tuning_db
    from repro.tuning_cache import TuningDatabase
    rec = tuning_cache.TuningRecord(
        key=tuning_cache.make_key("matvec", spec=TPU_V5E, m=128, n=128,
                                  dtype="float32"),
        params={"bm": 64})
    path = tmp_path / "mix.jsonl"
    path.write_text(json.dumps(rec.to_dict()) + "\n"
                    + "corrupt line one\n" + '{"params": {}}\n')
    db = TuningDatabase()
    assert _warm_tuning_db(db, str(path)) == (1, 2)
    assert "2 corrupt lines skipped" in capsys.readouterr().out
    with pytest.raises(SystemExit):
        _warm_tuning_db(TuningDatabase(), str(path), strict=True)
    with pytest.raises(SystemExit):         # unreadable + strict: loud
        _warm_tuning_db(TuningDatabase(), str(tmp_path / "absent.jsonl"),
                        strict=True)
    assert _warm_tuning_db(TuningDatabase(),
                           str(tmp_path / "absent.jsonl")) == (0, 0)
