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
