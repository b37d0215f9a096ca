"""The decode state carries the layer weights in compute precision.

Where the parameters are stored wider than ``cfg.dtype`` (float32 under
bf16 compute), prefill hands decode a bf16 copy of the stacked layer
weights, and every decode step reads it in place of converting the
float32 stack again.  These tests hold the served numbers to the bit
against the program before the copy was carried (greedy tokens and
every step's logits, recorded in ``data/decode_parent.npz``), check
that the copy engaged (no convert of a stacked weight in the decode
step, the two ``serve.*`` counters), and that every function that
builds a decode state agrees on its structure and sharding.

``python tests/test_decode_weights.py --record`` rewrites the data file
from the program as it stands; it was written at the commit before the
carried copy and is not to be rewritten after it.  The
``moonlight-16b-a3b`` entries were recorded when that configuration
came in (latent attention and the held-experts layer), from the program
that introduced it, with the carried copy.
"""
import ast
import functools
import json
import os
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.distributed import make_serve_fns
from repro.models import build_model

ARCHS = ["hymba-1.5b", "starcoder2-3b", "moonlight-16b-a3b", "mamba2-1.3b"]
STORED = ["float32", "bfloat16"]
BATCH, PROMPT, STEPS, SEED = 2, 12, 8, 1234
DATA = os.path.join(os.path.dirname(__file__), "data", "decode_parent.npz")


def stored_params(model, stored: str):
    """Seeded parameters, every leaf moved off its initial value (norm
    scales of ones and zero biases included, so a leaf read in the wrong
    precision shows), stored in ``stored``."""
    params = model.init(jax.random.PRNGKey(SEED))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), len(leaves))
    moved = [x * (1 + 0.25 * jax.random.normal(k, x.shape))
             + 0.05 * jax.random.normal(jax.random.fold_in(k, 1), x.shape)
             for x, k in zip(leaves, keys)]
    return jax.tree.unflatten(tree, [x.astype(stored) for x in moved])


def structure(tree) -> list:
    """Each leaf's path, shape and dtype, in order."""
    return [f"{jax.tree_util.keystr(path)} {tuple(x.shape)} {x.dtype}"
            for path, x in jax.tree_util.tree_leaves_with_path(tree)]


def serve(arch: str, stored: str):
    """Prefill, then greedy decode steps: (tokens (B, STEPS + 1), the
    prefill's last logits and each step's logits (STEPS + 1, B, V) as
    bf16 bit patterns, the cache that prefill returns)."""
    model = build_model(get_smoke(arch))
    params = stored_params(model, stored)
    prefill, decode = make_serve_fns(model)
    prefill = jax.jit(functools.partial(prefill, max_len=PROMPT + STEPS))
    decode = jax.jit(decode)
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 2),
                                (BATCH, PROMPT), 0, model.cfg.vocab)
    logits, cache = prefill(params, {"tokens": tokens})
    first = cache
    steps, toks = [logits[:, -1]], []
    for _ in range(STEPS):
        tok = jnp.argmax(steps[-1], axis=-1).astype(jnp.int32)[:, None]
        toks.append(tok)
        logits, cache = decode(params, cache, tok)
        steps.append(logits[:, -1])
    toks.append(jnp.argmax(steps[-1], axis=-1).astype(jnp.int32)[:, None])
    bits = np.stack([np.asarray(s.astype(jnp.bfloat16)).view(np.uint16)
                     for s in steps])
    return np.concatenate([np.asarray(t) for t in toks], axis=1), bits, first


def record(path: str = DATA) -> None:
    out, layout = {}, {}
    for arch in ARCHS:
        for stored in STORED:
            toks, bits, cache = serve(arch, stored)
            out[f"{arch}/{stored}/tokens"] = toks
            out[f"{arch}/{stored}/logits"] = bits
            layout[f"{arch}/{stored}"] = structure(cache)
    out["cache_structure"] = np.array(json.dumps(layout))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    np.savez_compressed(path, **out)


@pytest.fixture(scope="module")
def parent():
    with np.load(DATA) as f:
        data = {k: f[k] for k in f.files}
    data["cache_structure"] = json.loads(str(data["cache_structure"]))
    return data


@pytest.mark.parametrize("stored", STORED)
@pytest.mark.parametrize("arch", ARCHS)
def test_served_numbers_equal_the_parent_to_the_bit(parent, arch, stored):
    toks, bits, _ = serve(arch, stored)
    np.testing.assert_array_equal(toks, parent[f"{arch}/{stored}/tokens"])
    np.testing.assert_array_equal(bits, parent[f"{arch}/{stored}/logits"])


def lowered_state(arch: str, stored: str, max_len: int = 0):
    """(model, abstract params, the abstract state prefill returns),
    traced by fresh closures with the ``serve.*`` counters cleared."""
    model = build_model(get_smoke(arch))
    params = jax.eval_shape(lambda: stored_params(model, stored))
    prefill, _ = make_serve_fns(model)
    batch = {"tokens": jax.ShapeDtypeStruct((BATCH, PROMPT), jnp.int32)}
    obs.reset_counters("serve.")
    _, cache = jax.eval_shape(
        functools.partial(prefill, max_len=max_len), params, batch)
    return model, params, cache


ATTENTION_LEAVES = {"k", "v", "c_kv", "k_rope"}


def in_stored_order(recorded: list) -> list:
    """The recorded structure with each attention leaf of the decode
    cache, recorded as (L, B, S, *per-position), in its stored axis
    order: the sequence after the heads, second from last where a
    head's width fills whole 128-lane tiles, else last."""
    out = []
    for line in recorded:
        path, rest = line.split(" ", 1)
        name = path.strip("[]'").removesuffix("_pre")
        if name in ATTENTION_LEAVES:
            shape = rest[:rest.index(")") + 1]
            dims = list(ast.literal_eval(shape))
            s = dims.pop(2)
            dims.insert(len(dims) - (dims[-1] % 128 == 0), s)
            line = f"{path} {tuple(dims)}{rest[len(shape):]}"
        out.append(line)
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_bf16_stored_params_carry_no_copy(parent, arch):
    """Stored in the compute dtype, the state and its structure are the
    parent's, each attention leaf in its stored axis order, and prefill
    hands nothing over."""
    _, _, cache = lowered_state(arch, "bfloat16", PROMPT + STEPS)
    assert "weights" not in cache
    assert structure(cache) == in_stored_order(
        parent["cache_structure"][f"{arch}/bfloat16"])
    assert obs.counters().get("serve.carried_weight_bytes", 0) == 0


@pytest.mark.parametrize("arch", ARCHS)
def test_float32_stored_params_carry_the_bf16_layer_weights(parent, arch):
    """Stored in float32, the state is the parent's (each attention leaf
    in its stored axis order) plus a bf16 copy of every layer weight the
    layers read in bf16, under its own path; the leaves read at float32
    stay out of it."""
    from repro.models.layers import FLOAT32_READS
    from repro.models.params import Param, tree_param_bytes
    model, params, cache = lowered_state(arch, "float32", PROMPT + STEPS)
    rest = {k: v for k, v in cache.items() if k != "weights"}
    assert structure(rest) == in_stored_order(
        parent["cache_structure"][f"{arch}/float32"])
    carried = cache["weights"]
    assert obs.counters()["serve.carried_weight_bytes"] \
        == tree_param_bytes(carried) > 0
    flat, _ = jax.tree_util.tree_flatten_with_path(
        carried, is_leaf=lambda x: isinstance(x, Param))
    for path, p in flat:
        stored = params
        for k in path:
            stored = stored[k.key]
        assert p.dims == stored.dims and p.shape == stored.shape, path
        assert p.dtype == jnp.bfloat16, path
        assert path[-1].key not in FLOAT32_READS, path
    n_carried = len(flat)
    n_bf16_reads = sum(
        1 for name in ("blocks", "prefix_blocks")
        for path, _ in jax.tree_util.tree_flatten_with_path(
            params.get(name, {}), is_leaf=lambda x: isinstance(x, Param))[0]
        if path[-1].key not in FLOAT32_READS)
    assert n_carried == n_bf16_reads


@pytest.mark.parametrize("arch", ARCHS)
def test_abstract_cache_is_the_state_prefill_returns(arch):
    for stored in STORED:
        model, params, cache = lowered_state(arch, stored)
        assert structure(model.abstract_cache(BATCH, PROMPT, params)) \
            == structure(cache), stored
    assert structure(model.abstract_cache(BATCH, PROMPT)) \
        == structure(lowered_state(arch, "float32")[2])


def _layer_weight_converts(jaxpr, weights):
    """f32 -> bf16 converts of a layer weight: of one of ``weights`` (the
    jaxpr's variables that hold one), or of what a scan or call passes
    on of one."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "convert_element_type"
                and eqn.invars[0] in weights
                and eqn.invars[0].aval.dtype == jnp.float32
                and eqn.params["new_dtype"] == jnp.bfloat16):
            found.append(eqn.invars[0].aval)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            inner = set()
            if len(sub.invars) == len(eqn.invars):
                inner = {s for s, v in zip(sub.invars, eqn.invars)
                         if not isinstance(v, jax.extend.core.Literal)
                         and v in weights}
            found += _layer_weight_converts(sub, inner)
    return found


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_converts_no_stacked_weight(arch):
    """The mechanism engaged: the decode step converts no layer weight
    (neither a stored stack nor a layer's slice of one inside the scan),
    and of the stored weights it converts only the head (the norms are
    read as stored, the embedding's rows after the gather)."""
    model, params, cache = lowered_state(arch, "float32", PROMPT + STEPS)
    _, decode = make_serve_fns(model)
    tok = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32)
    obs.reset_counters("serve.")
    jaxpr = jax.make_jaxpr(decode)(params, cache, tok).jaxpr
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path((params, cache, tok))]
    weights = {v for v, path in zip(jaxpr.invars, paths)
               if path.startswith(("[0]['blocks']", "[0]['prefix_blocks']",
                                   "[1]['weights']"))}
    assert len(weights) > 0
    assert _layer_weight_converts(jaxpr, weights) == []
    head = params["lm_head"]
    assert obs.counters()["serve.decode_weight_cast_bytes"] \
        == head.value.size * head.dtype.itemsize


def test_decode_refuses_a_state_without_the_copy():
    model, params, cache = lowered_state("hymba-1.5b", "float32")
    _, decode = make_serve_fns(model)
    tok = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32)
    del cache["weights"]
    with pytest.raises(ValueError, match="carries no bfloat16 copy"):
        jax.eval_shape(decode, params, cache, tok)


_SHARDED = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.configs import get_smoke
from repro.distributed import make_serve_fns
from repro.launch.mesh import make_mesh
from repro.launch.specs import cell_inputs, sharded_params
from repro.models import build_model
from repro.models.config import ShapeSpec
from repro.models.params import Param

mesh = make_mesh((2, 4), ("data", "model"))
out = {}
for arch in ("hymba-1.5b", "moonlight-16b-a3b"):
    model = build_model(get_smoke(arch))
    params, cache, tok = cell_inputs(model, ShapeSpec("d", 32, 8, "decode"),
                                     mesh)
    weights = sharded_params(model, mesh)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        cache["weights"], is_leaf=lambda x: isinstance(x, Param))
    pairs = []
    for path, p in flat:
        w = weights
        for k in path:
            w = w[k.key]
        pairs.append([str(p.value.sharding.spec), str(w.value.sharding.spec)])
    _, decode = make_serve_fns(model, mesh=mesh)
    with mesh:
        jax.jit(decode, donate_argnums=(1,)).lower(params, cache, tok).compile()
    out[arch] = pairs
print(json.dumps(out))
"""


def test_sharded_cache_shards_the_carried_weights_as_the_weights():
    """`specs.sharded_cache` gives each carried leaf its weight's
    sharding, and the decode step compiles on a (data, model) mesh."""
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    for arch, pairs in out.items():
        assert pairs, arch
        for got, want in pairs:
            assert got == want, (arch, got, want)
        assert any("model" in got for got, _ in pairs), arch


if __name__ == "__main__":
    if sys.argv[1:] != ["--record"]:
        sys.exit(__doc__)
    record()
