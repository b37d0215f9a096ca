"""Per-architecture smoke tests (deliverable f).

Each assigned arch instantiates its REDUCED config (same family: same
bias/norm/act/MoE/SSM structure, tiny widths) and runs one forward +
one train step + one prefill->decode step on CPU, asserting output
shapes and finiteness.  The FULL configs are exercised only via the
dry-run (ShapeDtypeStruct, no allocation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_smoke
from repro.distributed import make_train_step
from repro.distributed.sharding import Sharder
from repro.models import build_model
from repro.optim import AdamWConfig, init_adamw

SHD = Sharder()
B, S = 2, 64


def _batch(cfg, key):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.frontend == "frames":
        batch["frames"] = jax.random.normal(
            key, (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    opt = init_adamw(params)
    step = jax.jit(make_train_step(model, AdamWConfig(warmup_steps=1,
                                                      decay_steps=10)))
    params, opt, metrics = step(params, opt, _batch(cfg, key))
    loss = float(metrics["loss"])
    assert np.isfinite(loss), (arch, loss)
    assert loss > 0
    # params updated and finite
    leaf = np.asarray(jax.tree.leaves(params)[0], np.float32)
    assert np.isfinite(leaf).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    batch = _batch(cfg, key)
    loss, metrics = jax.jit(
        lambda p, b: model.loss(p, b, SHD))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(2)
    params = model.init(key)
    batch = _batch(cfg, key)
    logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, SHD))(params, batch)
    assert logits.shape == (B, S, cfg.vocab), (arch, logits.shape)
    tok = batch["tokens"][:, -1:]
    logits2, cache2 = jax.jit(
        lambda p, c, t: model.decode_step(p, c, t, SHD))(
            params, cache, tok)
    assert logits2.shape == (B, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits2, np.float32)).all()
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("arch", ["gemma-7b", "hymba-1.5b",
                                  "moonlight-16b-a3b"])
def test_decode_past_prompt_matches_longer_prefill(arch):
    """Serving sizes the cache for prompt + generated tokens: decoding
    G tokens after a P-token prefill gives the last logits of a prefill
    of all P + G tokens (dense: linear cache; hybrid: ring cache whose
    global layers must keep the prompt's head; moonlight: the latent
    caches of the dense first layer and of the expert layers).  The
    hybrid bound covers the bf16 chunked-scan vs recurrent SSM state
    update."""
    from repro.distributed import make_serve_fns
    p_len, gen = 16, 4
    cfg = get_smoke(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(3)
    params = model.init(key)
    full = jax.random.randint(key, (B, p_len + gen), 0, cfg.vocab)
    prefill, decode_step = make_serve_fns(model)
    want = jax.jit(prefill)(params, {"tokens": full})[0][:, -1]
    _, cache = jax.jit(lambda p, b: prefill(p, b, max_len=p_len + gen))(
        params, {"tokens": full[:, :p_len]})
    decode_step = jax.jit(decode_step)
    for i in range(gen):
        logits, cache = decode_step(params, cache,
                                    full[:, p_len + i:p_len + i + 1])
    got = np.asarray(logits[:, -1], np.float32)
    want = np.asarray(want, np.float32)
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err <= 5e-2, (arch, err)


# name -> (arch, config changes): the hybrid ring cache of 8 slots written
# past its wrap, latent attention with its prefix layer (a latent that
# fills the 128 lanes beside a narrow rope key, so both stored orders),
# the ssm family, and a dense cache whose head fills the lanes
IN_PLACE = {
    "hybrid-ring-past-wrap": ("hymba-1.5b", {"decode_cache_cap": 8}),
    "latent-with-prefix": ("moonlight-16b-a3b", {"kv_lora_rank": 128}),
    "ssm": ("mamba2-1.3b", {}),
    "dense-head-128": ("starcoder2-3b", {"head_dim": 128}),
}


@pytest.mark.parametrize("case", list(IN_PLACE))
def test_in_place_decode_matches_a_longer_prefill(monkeypatch, case):
    """Decode steps that update the stacked cache in place give, at each
    position, the logits and greedy token of a prefill over every
    position, and leave every cache leaf as that prefill does (float32,
    so the only differences are summation orders: SSD's chunked scan
    against its recurrence, attention over a masked cache).  In the ring
    case every layer's window is the ring's 8 slots, so that a prefill
    over every position attends to what the ring holds."""
    import dataclasses
    from repro.distributed import make_serve_fns
    from repro.models import transformer
    arch, changes = IN_PLACE[case]
    if "decode_cache_cap" in changes:
        cap = changes["decode_cache_cap"]
        monkeypatch.setattr(transformer, "hybrid_windows",
                            lambda cfg, s, n: jnp.full((n,), cap, jnp.int32))
    p_len, gen = 12, 6
    cfg = dataclasses.replace(get_smoke(arch), dtype="float32", **changes)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    full = jax.random.randint(jax.random.PRNGKey(5), (B, p_len + gen), 0,
                              cfg.vocab)
    prefill, decode_step = make_serve_fns(model)
    want, want_cache = jax.jit(prefill)(params, {"tokens": full})
    _, cache = jax.jit(lambda p, b: prefill(p, b, max_len=p_len + gen))(
        params, {"tokens": full[:, :p_len]})
    decode_step = jax.jit(decode_step)
    for i in range(gen):
        logits, cache = decode_step(params, cache,
                                    full[:, p_len + i:p_len + i + 1])
        ref = np.asarray(want[:, p_len + i])
        np.testing.assert_allclose(np.asarray(logits[:, 0]), ref,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.argmax(logits[:, 0], -1),
                                      np.argmax(ref, -1))
    assert set(cache) == set(want_cache)
    for name in cache:
        np.testing.assert_allclose(np.asarray(cache[name]),
                                   np.asarray(want_cache[name]),
                                   rtol=1e-4, atol=1e-4, err_msg=name)
    if case == "hybrid-ring-past-wrap":
        assert int(cache["pos"]) > cache["k"].shape[-1] == 8


@pytest.mark.parametrize("arch,leaves", [
    ("hymba-1.5b", 4), ("starcoder2-3b", 2), ("moonlight-16b-a3b", 4),
    ("mamba2-1.3b", 2)])
def test_decode_carries_every_cache_leaf(arch, leaves):
    """``decode.cache_in_place`` reads the stacked cache leaves one traced
    decode step updates in its layer scans' carry (hymba k, v, ssm,
    conv; starcoder2 k, v; moonlight c_kv, k_rope and their prefix
    stacks; mamba2 ssm, conv), and no scan takes a cache leaf as a
    scanned input or gives one back as a scanned output."""
    from repro import obs
    from repro.distributed import make_serve_fns
    model = build_model(get_smoke(arch))
    params = model.abstract_params()
    prefill, decode = make_serve_fns(model)
    _, cache = jax.eval_shape(lambda p, b: prefill(p, b, max_len=12), params,
                              {"tokens": jax.ShapeDtypeStruct((B, 8),
                                                              jnp.int32)})
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    obs.reset_counters("decode.")
    jaxpr = jax.make_jaxpr(decode)(params, cache, tok).jaxpr
    assert obs.counters().get("decode.cache_in_place") == leaves
    stacks = {tuple(x.shape) for n, x in cache.items()
              if n not in ("pos", "weights")}
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == (2 if model.cfg.first_dense_layers else 1)
    for eqn in scans:
        n_in = eqn.params["num_consts"] + eqn.params["num_carry"]
        carried = [tuple(v.aval.shape) for v in
                   eqn.invars[eqn.params["num_consts"]:n_in]]
        scanned = [tuple(v.aval.shape) for v in eqn.invars[n_in:]]
        ys = [tuple(v.aval.shape) for v in
              eqn.outvars[eqn.params["num_carry"]:]]
        assert stacks & set(carried)
        assert not stacks & set(scanned + ys), (scanned, ys)
