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


def test_moonlight_picks_compile_for_v5e(one_chip):
    """Every kernel instance of moonlight-16b-a3b's serving path at its
    cell's shapes (a 64 x 1024 prefill, 64-row decode) compiles for one
    v5e with the tuner's pick.  At 65,536 rows the ``stream`` MLP's
    float32 tiles, beside its double-buffered panels, once put a pick
    past the compiler's VMEM limit."""
    import dataclasses
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), n_layers=3,
                              vocab=40960, experts_held=(0, 16))
    rep = GraphTuner.tune_config(cfg, batch=64, prompt_len=1024,
                                 db=TuningDatabase())
    kernels = {inst["kernel"] for inst in rep["instances"]}
    assert {"rms_norm", "flash_attention", "mlp_matmul", "matmul"} <= kernels
    for inst in rep["instances"]:
        compiled = _compile_pick(inst["kernel"], inst["signature"],
                                 inst["params"], one_chip)
        assert "tpu_custom_call" in compiled.as_text(), inst


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


@pytest.mark.parametrize("arch", ["hymba-1.5b", "starcoder2-3b"])
def test_scopes_leave_the_v5e_program_unchanged(one_chip, monkeypatch, arch):
    """The tuned smoke-size serving programs, compiled for one v5e with
    the named scopes live and turned off, are the same program up to
    metadata and instruction numbering, and each Pallas kernel keeps the
    ``<function>_pallas.N`` instruction name a device trace is read by."""
    import contextlib
    import functools
    import re
    from repro import obs, tuning_cache
    from repro.configs import get_smoke
    from repro.distributed import make_serve_fns
    from repro.models import build_model
    from repro.models.layers import use_tuned_layers

    cfg = get_smoke(arch)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    model = build_model(cfg)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((2, 128),
                                                    jax.numpy.int32)})

    def compiled():
        prefill, _ = make_serve_fns(model)
        step = jax.jit(functools.partial(prefill, max_len=136))
        with use_tuned_layers():
            return step.lower(params, batch).compile().as_text()

    def program(text):
        text = re.sub(r",? metadata=\{[^}]*\}", "",
                      text.split("\n\nFileNames")[0])
        names = {}
        return re.sub(r"%[A-Za-z0-9_.\-]+", lambda m: names.setdefault(
            m.group(0), f"%v{len(names)}"), text)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    db = TuningDatabase()
    tuning_cache.thaw()
    tuning_cache.set_default_db(db)
    try:
        GraphTuner.tune_config(cfg, batch=2, prompt_len=128, db=db)
        tuning_cache.freeze()
        live = compiled()
        with monkeypatch.context() as m:
            m.setattr(obs, "scope", lambda name: contextlib.nullcontext())
            jax.clear_caches()
            off = compiled()
    finally:
        tuning_cache.thaw()
        tuning_cache.reset_default_db()
        monkeypatch.undo()
        jax.clear_caches()
    assert program(live) == program(off)
    kernels = set(re.findall(r"%([A-Za-z0-9_]+_pallas)\.\d+ = .*custom-call",
                             live))
    assert kernels, "no Pallas kernel in the program"
    assert kernels == set(re.findall(
        r"%([A-Za-z0-9_]+_pallas)\.\d+ = .*custom-call", off))


@pytest.mark.parametrize("arch,n_layers,batch,prompt_len,gen", [
    ("hymba-1.5b", 32, 4, 2048, 16),
    ("starcoder2-3b", 15, 2, 3840, 32),
])
def test_decode_reads_the_weights_prefill_hands_over_on_one_v5e(
        one_chip, monkeypatch, arch, n_layers, batch, prompt_len, gen):
    """The tuned serving programs at full width and a long-prompt shape,
    compiled for one v5e as a server runs them (greedy token out, the
    decode state donated): prefill returns the bf16 layer weights and
    decode reads them where prefill left them.  The decode step converts
    no stacked weight, neither program copies or transposes a whole
    carried weight, decode takes each carried weight in the layout
    prefill gives it, and each program fits the chip's HBM."""
    import dataclasses
    import functools
    import re
    import jax.numpy as jnp
    from repro import tuning_cache
    from repro.core.hw import TPU_V5E
    from repro.distributed import make_serve_fns
    from repro.models import build_model
    from repro.models.layers import use_tuned_layers

    cfg = dataclasses.replace(get_config(arch), n_layers=n_layers)
    model = build_model(cfg)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = on_chip(model.abstract_params())
    tokens = on_chip(jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32))
    prefill, decode = make_serve_fns(model)
    prefill = functools.partial(prefill, max_len=prompt_len + gen)

    def greedy(logits):
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]

    def serve_prefill(params, tokens):
        logits, cache = prefill(params, {"tokens": tokens})
        return greedy(logits), cache

    def serve_decode(params, cache, tok):
        logits, cache = decode(params, cache, tok)
        return greedy(logits), cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    db = TuningDatabase()
    tuning_cache.thaw()
    tuning_cache.set_default_db(db)
    try:
        GraphTuner.tune_config(cfg, batch=batch, prompt_len=prompt_len, db=db)
        tuning_cache.freeze()
        with use_tuned_layers():
            pc = jax.jit(serve_prefill).lower(params, tokens).compile()
            tok, cache = on_chip(jax.eval_shape(serve_prefill, params, tokens))
            dc = jax.jit(serve_decode, donate_argnums=(1,)).lower(
                params, cache, tok).compile()
    finally:
        tuning_cache.thaw()
        tuning_cache.reset_default_db()
        monkeypatch.undo()
        jax.clear_caches()

    carried = jax.tree.leaves(cache["weights"])
    assert len(carried) > 0
    shapes = {",".join(map(str, x.shape)) for x in carried}
    for name, c in (("prefill", pc), ("decode", dc)):
        text = c.as_text()
        moved = [shape for shape, _ in re.findall(
            r"= bf16\[([\d,]*)\]\{[^}]*\} (copy|transpose)\(", text)
            if shape in shapes]
        assert moved == [], (name, moved)
        mem = c.memory_analysis()
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < TPU_V5E.hbm_bytes, (name, used)
    converts = [shape for shape in re.findall(
        r"= bf16\[([\d,]*)\]\{[^}]*\} convert\(", dc.as_text())
        if shape in shapes]
    assert converts == [], converts
    handed = jax.tree.leaves(pc.output_formats[1]["weights"])
    taken = jax.tree.leaves(dc.input_formats[0][1]["weights"])
    assert [f.layout for f in taken] == [f.layout for f in handed]


@pytest.mark.parametrize("arch,changes,batch,prompt_len,gen,leaves", [
    ("hymba-1.5b", {}, 32, 256, 512, 4),
    ("starcoder2-3b", {"n_layers": 15}, 2, 3840, 32, 2),
    # the prefix layer and two expert layers: every cache leaf's shape but
    # the layer count, at the cell's batch and length
    ("moonlight-16b-a3b", {"n_layers": 3, "experts_held": (0, 16),
                           "vocab": 40960}, 64, 1024, 512, 4),
])
def test_decode_updates_the_cache_in_place_on_one_v5e(
        one_chip, monkeypatch, arch, changes, batch, prompt_len, gen,
        leaves):
    """The served decode step at a cell's cache shapes, compiled for one
    v5e with the state donated, updates the stacked cache in place: no
    copy or transpose has the shape of a stacked cache leaf or of one
    layer's slice of it, decode takes each leaf in the layout prefill
    hands it over in, its temporaries stay under the stacked cache's
    bytes, and ``decode.cache_in_place`` counts every leaf it carries."""
    import dataclasses
    import functools
    import re
    import jax.numpy as jnp
    from repro import obs, tuning_cache
    from repro.distributed import make_serve_fns
    from repro.models import build_model
    from repro.models.layers import use_tuned_layers

    cfg = dataclasses.replace(get_config(arch), **changes)
    model = build_model(cfg)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = on_chip(model.abstract_params())
    tokens = on_chip(jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32))
    prefill, decode = make_serve_fns(model)
    prefill = functools.partial(prefill, max_len=prompt_len + gen)

    def greedy(logits):
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]

    def serve_prefill(params, tokens):
        logits, cache = prefill(params, {"tokens": tokens})
        return greedy(logits), cache

    def serve_decode(params, cache, tok):
        logits, cache = decode(params, cache, tok)
        return greedy(logits), cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    db = TuningDatabase()
    tuning_cache.thaw()
    tuning_cache.set_default_db(db)
    try:
        GraphTuner.tune_config(cfg, batch=batch, prompt_len=prompt_len, db=db)
        tuning_cache.freeze()
        with use_tuned_layers():
            pc = jax.jit(serve_prefill).lower(params, tokens).compile()
            tok, cache = on_chip(jax.eval_shape(serve_prefill, params, tokens))
            obs.reset_counters("decode.")
            dc = jax.jit(serve_decode, donate_argnums=(1,)).lower(
                params, cache, tok).compile()
            in_place = obs.counters().get("decode.cache_in_place")
    finally:
        tuning_cache.thaw()
        tuning_cache.reset_default_db()
        monkeypatch.undo()
        jax.clear_caches()

    names = [n for n in cache if n not in ("pos", "weights")]
    assert len(names) == leaves == in_place
    shapes = set()
    for n in names:
        shape = cache[n].shape
        shapes |= {shape, shape[1:], (1,) + shape[1:]}
    shapes = {",".join(map(str, s)) for s in shapes}
    text = dc.as_text()
    moved = [shape for shape, _ in re.findall(
        r"= \w+\[([\d,]*)\]\{[^}]*\} (copy|transpose)\(", text)
        if shape in shapes]
    assert moved == [], moved
    handed = pc.output_formats[1]
    taken = dc.input_formats[0][1]
    assert [taken[n].layout for n in names] \
        == [handed[n].layout for n in names]
    stacked = sum(cache[n].size * cache[n].dtype.itemsize for n in names)
    assert dc.memory_analysis().temp_size_in_bytes < stacked
