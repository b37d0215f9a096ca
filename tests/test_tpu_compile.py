"""Compile the serving path's Pallas kernels for a TPU v5e, with no chip.

Interpret mode runs every kernel body on the CPU but cannot see what
the TPU compiler refuses: blocks that break the (8, 128) tiling rule,
more VMEM than a kernel may use.  These tests hand the tuner's own
picks — taken from a graph pretune of each config at full width, never
hard-coded — to the TPU compiler for a described (not attached) v5e
chip, and check that each program holds the kernel
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import GraphTuner
from repro.kernels import api
from repro.tuning_cache import TuningDatabase

BATCH, PROMPT_LEN = 4, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def picks():
    """(arch, kernel, m-or-sq) -> (signature, params) from a fresh
    graph pretune of each served config."""
    out = {}
    for arch in ("hymba-1.5b", "starcoder2-3b"):
        rep = GraphTuner.tune_config(get_config(arch), batch=BATCH,
                                     prompt_len=PROMPT_LEN,
                                     db=TuningDatabase())
        for inst in rep["instances"]:
            sig = inst["signature"]
            rows = sig["m"] if "m" in sig else sig["sq"]
            out[(arch, inst["kernel"], rows)] = (sig, inst["params"])
    return out


def _compile_pick(kernel_id, sig, params, sharding):
    """Lower the op with the tuner's params for the described chip."""
    spec = api.get_spec(kernel_id)
    shapes = jax.eval_shape(lambda k: spec.make_inputs(k, **sig),
                            jax.random.PRNGKey(0))
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
            for s in shapes]
    extra = {k: sig[k] for k in ("act", "causal") if k in sig}

    def run(*a):
        # this process's backend is the CPU, where kernels default to
        # interpret mode: ask for the TPU lowering explicitly
        return spec.op(*a, tuned_params=params, interpret=False, **extra)

    return jax.jit(run).lower(*args).compile()


@pytest.mark.parametrize("arch,kernel,rows", [
    ("hymba-1.5b", "rms_norm", BATCH * PROMPT_LEN),
    ("hymba-1.5b", "mlp_matmul", BATCH * PROMPT_LEN),
    ("hymba-1.5b", "matmul", BATCH * PROMPT_LEN),
    ("hymba-1.5b", "rms_norm", BATCH),
    ("hymba-1.5b", "mlp_matmul", BATCH),
    ("hymba-1.5b", "matmul", BATCH),
    ("starcoder2-3b", "flash_attention", PROMPT_LEN),
])
def test_tuner_pick_compiles_for_v5e(one_chip, picks, arch, kernel, rows):
    sig, params = picks[(arch, kernel, rows)]
    compiled = _compile_pick(kernel, sig, params, one_chip)
    assert "tpu_custom_call" in compiled.as_text(), (kernel, sig, params)


def test_tuned_prefill_compiles_for_one_v5e(one_chip, monkeypatch):
    """The whole jitted hymba-1.5b prefill of `chip_smoke.py` (tuned ops
    after a graph pretune, batch 4 x prompt 1024, cache sized for 16
    more tokens) compiles for one v5e, holds the Pallas kernels, and
    its program fits the chip's HBM."""
    from repro import tuning_cache
    from repro.core.hw import TPU_V5E
    from repro.distributed import make_serve_fns
    from repro.models import build_model
    from repro.models.layers import use_tuned_layers

    cfg = get_config("hymba-1.5b")
    model = build_model(cfg)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((BATCH, PROMPT_LEN),
                                                    jax.numpy.int32)})
    prefill, _ = make_serve_fns(model)
    step = jax.jit(lambda p, b: prefill(p, b, max_len=PROMPT_LEN + 16))
    # the kernels ask the backend whether to interpret; this process's
    # backend is the CPU, so tell them it is the TPU being compiled for,
    # and drop kernel traces made under either answer on the way in and
    # out (a kernel's jit keys on its arguments, not on the backend)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    db = TuningDatabase()
    tuning_cache.thaw()
    tuning_cache.set_default_db(db)
    try:
        GraphTuner.tune_config(cfg, batch=BATCH, prompt_len=PROMPT_LEN, db=db)
        tuning_cache.freeze()
        with use_tuned_layers():
            compiled = step.lower(params, batch).compile()
    finally:
        tuning_cache.thaw()
        tuning_cache.reset_default_db()
        monkeypatch.undo()
        jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < TPU_V5E.hbm_bytes, used
