"""Checkpoint manager + fault-tolerant supervisor tests."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, TokenStream
from repro.distributed import make_train_step
from repro.models import ModelConfig, build_model
from repro.optim import AdamWConfig, init_adamw
from repro.runtime import FaultPolicy, TrainSupervisor
from repro.runtime.fault import FaultSchedule, scheduled_fault

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv=2, d_ff=64, vocab=128)


def _setup():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    opt = init_adamw(params)
    step = jax.jit(make_train_step(model, AdamWConfig(peak_lr=1e-3,
                                                      warmup_steps=2,
                                                      decay_steps=50)))
    stream = TokenStream(DataConfig(vocab=128, global_batch=4, seq_len=32))
    make_batch = lambda s: {k: jnp.asarray(v)
                            for k, v in stream.make_batch(s).items()}
    return model, params, opt, step, make_batch


def test_roundtrip_and_retention(tmp_path):
    _, params, opt, _, _ = _setup()
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in (10, 20, 30):
        mgr.save(s, {"params": params, "opt": opt, "step": s})
    assert mgr.all_steps() == [20, 30]          # retention
    back = mgr.restore()
    assert int(np.asarray(back["step"])) == 30
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(back["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Param dims metadata survives the roundtrip
    from repro.models.params import Param, map_params
    dims_orig, dims_back = [], []
    map_params(lambda p: dims_orig.append(p.dims), params)
    map_params(lambda p: dims_back.append(p.dims), back["params"])
    assert dims_orig == dims_back


def test_async_save_and_wait(tmp_path):
    _, params, opt, _, _ = _setup()
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
    mgr.save(1, {"params": params, "opt": opt, "step": 1})
    mgr.wait()
    assert mgr.latest_step() == 1
    mgr.close()


def test_partial_tmp_dir_is_ignored(tmp_path):
    _, params, opt, _, _ = _setup()
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(5, {"params": params, "opt": opt, "step": 5})
    # simulate an interrupted save
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert mgr.latest_step() == 5


def test_supervisor_restarts_after_fault(tmp_path):
    _, params, opt, step, make_batch = _setup()
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    boom = {"armed": True}

    def inject(s):
        if s == 7 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected node failure")

    sup = TrainSupervisor(mgr, FaultPolicy(checkpoint_every=5,
                                           max_restarts=2),
                          inject_fault=inject)
    state = sup.run(step, {"params": params, "opt": opt, "step": 0},
                    make_batch, num_steps=12)
    assert state["step"] == 12
    assert mgr.latest_step() in (10, 12)


def test_supervisor_exceeds_restarts(tmp_path):
    _, params, opt, step, make_batch = _setup()
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)

    def always_fail(s):
        if s >= 6:
            raise RuntimeError("persistent failure")

    sup = TrainSupervisor(mgr, FaultPolicy(checkpoint_every=5,
                                           max_restarts=2),
                          inject_fault=always_fail)
    with pytest.raises(RuntimeError, match="max_restarts"):
        sup.run(step, {"params": params, "opt": opt, "step": 0},
                make_batch, num_steps=12)


def test_resume_is_deterministic(tmp_path):
    """Train 10 straight vs train 5 + checkpoint + resume 5: identical."""
    _, params, opt, step, make_batch = _setup()

    p1, o1 = params, opt
    for s in range(10):
        p1, o1, _ = step(p1, o1, make_batch(s))

    p2, o2 = params, opt
    for s in range(5):
        p2, o2, _ = step(p2, o2, make_batch(s))
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(5, {"params": p2, "opt": o2, "step": 5})
    back = mgr.restore()
    p3, o3 = back["params"], back["opt"]
    for s in range(5, 10):
        p3, o3, _ = step(p3, o3, make_batch(s))

    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p3)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-6)


def test_data_stream_resumable_and_deterministic():
    cfgd = DataConfig(vocab=977, global_batch=4, seq_len=64, seed=3)
    s1 = TokenStream(cfgd)
    s2 = TokenStream(cfgd)
    np.testing.assert_array_equal(s1.make_batch(17)["tokens"],
                                  s2.make_batch(17)["tokens"])
    assert not np.array_equal(s1.make_batch(17)["tokens"],
                              s1.make_batch(18)["tokens"])


def test_fault_schedule_arithmetic():
    s = FaultSchedule(after=2, every=3, times=2)
    fired = 0
    hits = [h for h in range(1, 12)
            if s.fires_at(h, fired) and (fired := fired + 1)]
    assert hits == [2, 5]                   # after=2, stride 3, budget 2
    once = FaultSchedule(after=4, every=0)
    assert [h for h in range(1, 8) if once.fires_at(h, 0)] == [4]
    always = FaultSchedule()
    assert all(always.fires_at(h, h - 1) for h in range(1, 5))


def test_scheduled_fault_adapts_to_train_supervisor_hook():
    inject = scheduled_fault(FaultSchedule(after=3, every=0),
                             exc=lambda step: OSError(f"step {step}"))
    inject(10)
    inject(11)
    with pytest.raises(OSError, match="step 12"):
        inject(12)
    inject(13)                              # budget-less after=3,every=0:
    #                                         fires exactly once
