"""Decode attention reads the KV cache grouped by KV head, as stored.

`attention_decode` scores each KV head's group of query heads against
the cache in its own dtype (`layers._sdpa_decode`), where `_sdpa`
broadcasts K/V up to every query head and casts K to float32.  These
tests hold the grouped core to `_sdpa` on seeded inputs (GQA, MHA and a
replicated cache; rolling and linear caches under a traced window;
float32 and bf16; a head width that fills the lanes, stored with the
sequence second from last, and one that does not, stored with it
last), check that the decode program broadcasts no cache up to the
query heads and casts none to float32, and that the
``attn.decode_grouped`` counter reads one per attention layer of a
traced decode step.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.distributed import make_serve_fns
from repro.distributed.sharding import Sharder
from repro.models import build_model, layers
from repro.models.layers import (AttnConfig, attention_decode,
                                 cache_seq_axis, init_attention,
                                 stored_order)

SHD = Sharder()
B, S_CACHE, HD, D = 2, 16, 16, 32
HDS = [16, 128]   # stored (L, B, KV, hd, S) and (L, B, KV, S, hd)
SEED = 1616

# (query heads, KV heads, kv_repeat): hymba-1.5b's 25 over 5,
# starcoder2-3b's 24 over 2, plain MHA, and a cache whose 2 KV heads
# are each held twice
HEADS = {"gqa25x5": (25, 5, 1), "gqa24x2": (24, 2, 1), "mha4x4": (4, 4, 1),
         "repeat8x2r2": (8, 2, 2)}
# (rolling, position written, traced window): a linear cache, and a ring
# buffer already wrapped round
CACHES = {"linear": (False, 11, 6), "rolling": (True, 21, 9)}
# float32 to float32 rounding; bf16 to one or two bf16 steps
TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def decode_inputs(h, kv, r, dtype, hd=HD):
    """Config, weights, x, and a one-layer stack of K and V in their
    stored order."""
    cfg = AttnConfig(d_model=D, n_heads=h, n_kv=kv, head_dim=hd,
                     kv_repeat=r)
    k = jax.random.split(jax.random.PRNGKey(SEED), 4)
    p = init_attention(k[0], cfg)
    x = jax.random.normal(k[1], (B, 1, D)).astype(dtype)
    shape = stored_order((1, B, S_CACHE, kv * r, hd), cache_seq_axis(hd))
    ck = jax.random.normal(k[2], shape).astype(dtype)
    cv = jax.random.normal(k[3], shape).astype(dtype)
    return cfg, p, x, ck, cv


def run_decode(cfg, p, x, ck, cv, pos, window, rolling):
    fn = jax.jit(lambda p, x, ck, cv, pos, win: attention_decode(
        p, x, ck, cv, 0, pos, cfg, SHD, window_override=win,
        rolling=rolling))
    return fn(p, x, ck, cv, jnp.int32(pos), jnp.int32(window))


def broadcast_core(q, k, v, bias, scale, seq):
    """`_sdpa` over one layer's stored cache, its sequence moved back
    next to the batch."""
    return layers._sdpa(q, jnp.moveaxis(k, seq, 1), jnp.moveaxis(v, seq, 1),
                        bias, scale)


@pytest.mark.parametrize("hd", HDS)
@pytest.mark.parametrize("dtype", list(TOL))
@pytest.mark.parametrize("cache", list(CACHES))
@pytest.mark.parametrize("heads", list(HEADS))
def test_grouped_core_matches_the_broadcast_core(monkeypatch, heads, cache,
                                                 dtype, hd):
    h, kv, r = HEADS[heads]
    rolling, pos, window = CACHES[cache]
    cfg, p, x, ck, cv = decode_inputs(h, kv, r, jnp.dtype(dtype), hd)
    y, (k1, v1) = run_decode(cfg, p, x, ck, cv, pos, window, rolling)
    with monkeypatch.context() as m:
        m.setattr(layers, "_sdpa_decode", broadcast_core)
        y_ref, (k0, v0) = run_decode(cfg, p, x, ck, cv, pos, window,
                                     rolling)
    assert y.dtype == y_ref.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k0))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v0))
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def cache_broadcasts_and_casts(h, kv):
    """(broadcasts of the cache up to the query heads, float32 casts of
    the whole cache or of such a broadcast) in the jaxpr of a bf16
    `attention_decode`."""
    cfg, p, x, ck, cv = decode_inputs(h, kv, 1, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, x, ck, cv, pos, win: attention_decode(
        p, x, ck, cv, 0, pos, cfg, SHD, window_override=win, rolling=True))(
        p, x, ck, cv, jnp.int32(3), jnp.int32(8)).jaxpr
    up = {(B, S_CACHE, kv, h // kv, HD), (B, S_CACHE, h, HD)}
    cache_shapes = up | {ck.shape, ck.shape[1:]}
    broadcasts, casts = [], []
    for eqn in _eqns(jaxpr):
        out = eqn.outvars[0].aval
        if eqn.primitive.name == "broadcast_in_dim" and out.shape in up:
            broadcasts.append(out)
        if (eqn.primitive.name == "convert_element_type"
                and eqn.params["new_dtype"] == jnp.float32
                and eqn.invars[0].aval.shape in cache_shapes):
            casts.append(out)
    return broadcasts, casts


def test_decode_reads_the_cache_unbroadcast_and_uncast(monkeypatch):
    """No op of the decode core widens the cache to every query head or
    to float32; the same check finds both in the broadcast core."""
    assert cache_broadcasts_and_casts(25, 5) == ([], [])
    with monkeypatch.context() as m:
        m.setattr(layers, "_sdpa_decode", broadcast_core)
        broadcasts, casts = cache_broadcasts_and_casts(25, 5)
    assert len(broadcasts) == 2 and len(casts) == 1


@pytest.mark.parametrize("arch", ["hymba-1.5b", "starcoder2-3b",
                                  "moonlight-16b-a3b"])
def test_grouped_core_counted_once_per_attention_layer(arch):
    """``attn.decode_grouped`` reads the attention layers of one traced
    decode step (the scan's trips counted), and 0 for latent attention,
    whose decode never calls the grouped core."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    prefill, decode = make_serve_fns(model)
    params = model.abstract_params()
    _, cache = jax.eval_shape(functools.partial(prefill, max_len=12),
                              params, {"tokens": jax.ShapeDtypeStruct(
                                  (B, 8), jnp.int32)})
    obs.reset_counters("attn.")
    jax.eval_shape(decode, params, cache,
                   jax.ShapeDtypeStruct((B, 1), jnp.int32))
    want = 0 if cfg.kv_lora_rank else cfg.n_layers
    assert obs.counters().get("attn.decode_grouped", 0) == want
