"""Latent attention and the held-experts layer against plain references.

The program runs the moonlight-16b-a3b smoke configuration (latent
attention, sigmoid routing, a dense first layer, 4 of the router's 8
experts held) at float32 with seeded random weights from the benchmark's
plain reference (`bench/reference/moonlight-16b-a3b.py`), so that both
sides route every token to the same experts and differ by float32
rounding alone.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from repro import obs
from repro.configs import get_config, get_smoke
from repro.distributed import make_serve_fns
from repro.distributed.sharding import Sharder
from repro.models import build_model, moe
from repro.models.layers import cache_seq_axis, mla_attention, mla_decode
from repro.models.params import Param
from repro.models.transformer import attn_config

SHD = Sharder()
ARCH = "moonlight-16b-a3b"
# Prefill then decode through the latent cache against the reference's
# whole-sequence pass, both at float32: they differ by rounding order
# alone (the absorbed decode, the grouped expert products), 5e-6 on
# logits of about 3.6 here.  The same program computing in bfloat16
# misses it by nearly three orders of magnitude (0.09).
TOL = 1e-4


def reference():
    return harness.code("reference", ARCH)


def model_dict(cfg):
    m = dataclasses.asdict(cfg)
    m["experts_held"] = list(cfg.experts_held or (0, cfg.n_experts))
    return m


def weights(cfg, seed=3):
    return jax.jit(functools.partial(reference().init, m=model_dict(cfg)))(
        jax.random.PRNGKey(seed))


def program_params(cfg, w):
    runner = harness.code("runners", "serve")
    return runner.program_params(w, build_model(cfg).abstract_params())


def served_logits(cfg, params, tokens, p):
    """Prefill of tokens[:, :p], then each later token decoded through
    the cache: logits at positions p-1 .. T-1."""
    prefill, decode = make_serve_fns(build_model(cfg))
    t = tokens.shape[1]
    logits, cache = jax.jit(functools.partial(prefill, max_len=t))(
        params, {"tokens": tokens[:, :p]})
    out = [logits[:, -1]]
    step = jax.jit(decode)
    for k in range(p, t):
        logits, cache = step(params, cache, tokens[:, k:k + 1])
        out.append(logits[:, -1])
    return jnp.stack(out, axis=1).astype(jnp.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_matches_the_reference(dtype):
    cfg = dataclasses.replace(get_smoke(ARCH), dtype=dtype)
    w = weights(cfg)
    p, g = 72, 8              # 144 prompt tokens: grouped experts in prefill
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (2, p + g)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = served_logits(cfg, program_params(cfg, w), tokens, p)
        want = reference().forward(w, tokens, model_dict(cfg),
                                   slice(p - 1, p + g))
    assert got.shape == want.shape == (2, g + 1, cfg.vocab)
    assert float(jnp.abs(want).max()) > 1.0
    err = float(jnp.abs(got - want).max())
    if dtype == "float32":
        assert err <= TOL, err
    else:
        assert err > 10 * TOL, err     # the tolerance tells the precisions apart


def test_absorbed_decode_matches_the_decompressed_form():
    """One layer: the absorbed decode at position s over the latent cache
    of an s-token prefill equals row s of the decompressed whole-sequence
    attention over s + 1 tokens."""
    cfg = dataclasses.replace(get_smoke(ARCH), dtype="float32")
    acfg = attn_config(cfg)
    w = weights(cfg)
    p = {k: Param(v[0], ()) for k, v in w["blocks"]["attn"].items()}
    s = 11
    x = jax.random.normal(jax.random.PRNGKey(5), (2, s + 1, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        full = mla_attention(p, x, acfg, SHD)
        _, (c_kv, k_rope) = mla_attention(p, x[:, :s], acfg, SHD,
                                          return_kv=True)
        pad = ((0, 0), (0, 5), (0, 0))          # cache longer than filled
        c_seq = cache_seq_axis(acfg.kv_lora_rank)
        r_seq = cache_seq_axis(acfg.rope_dim)
        y, (c2, r2) = mla_decode(
            p, x[:, s:], jnp.moveaxis(jnp.pad(c_kv, pad), 1, c_seq)[None],
            jnp.moveaxis(jnp.pad(k_rope, pad), 1, r_seq)[None], 0,
            jnp.int32(s), acfg, SHD)
        # back from the stored one-layer stacks to (B, S, width)
        c2 = jnp.moveaxis(c2[0], c_seq, 1)
        r2 = jnp.moveaxis(r2[0], r_seq, 1)
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(full[:, s]),
                               atol=1e-5, rtol=1e-5)
    # the step wrote its own latent and rope key at position s, and only there
    _, (c_all, r_all) = mla_attention(p, x, acfg, SHD, return_kv=True)
    np.testing.assert_allclose(np.asarray(c2[:, :s + 1]), np.asarray(c_all),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(r2[:, :s + 1]), np.asarray(r_all),
                               atol=1e-5)
    assert not np.asarray(c2[:, s + 1:]).any()


def test_routing_follows_noaux_tc():
    """Sigmoid scores; the bias picks the top k and does not weigh them:
    the weights are the picked experts' unbiased scores, normalised to
    sum 1 and scaled."""
    d, e, k = 16, 8, 2
    key = jax.random.PRNGKey(7)
    router = jax.random.normal(key, (d, e)) * d ** -0.5
    bias = jnp.zeros((e,)).at[3].set(5.0)       # expert 3 always picked
    p = {"router": Param(router, ()),
         "e_score_correction_bias": Param(bias, ())}
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 5, d))
    w, top, _ = moe.route(p, x, top_k=k, scoring="sigmoid",
                          routed_scaling=2.446)
    scores = np.asarray(jax.nn.sigmoid(
        jnp.einsum("bsd,de->bse", x, router, precision="highest")))
    want_top = np.argsort(-(scores + np.asarray(bias)), axis=-1)[..., :k]
    np.testing.assert_array_equal(np.sort(np.asarray(top), -1),
                                  np.sort(want_top, -1))
    assert (np.asarray(top) == 3).any(-1).all()
    picked = np.take_along_axis(scores, np.asarray(top), -1)
    np.testing.assert_allclose(np.asarray(w),
                               picked / picked.sum(-1, keepdims=True) * 2.446,
                               rtol=1e-5)
    # without the bias the top choice differs somewhere: the bias picks
    _, top0, _ = moe.route({"router": p["router"],
                            "e_score_correction_bias": Param(0 * bias, ())},
                           x, top_k=k, scoring="sigmoid")
    assert not np.array_equal(np.asarray(top0), np.asarray(top))


def _layer(cfg, held=None):
    return dict(n_experts=cfg.n_experts, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, act=cfg.act, shd=SHD,
                scoring=cfg.router_scoring,
                routed_scaling=cfg.routed_scaling, held=held)


def _full_layer_params(cfg):
    """One expert layer holding all of the router's experts, with a bias
    that moves the picks."""
    full = dataclasses.replace(cfg, experts_held=None, dtype="float32")
    w = weights(full, seed=9)["blocks"]["moe"]
    w = jax.tree.map(lambda v: v[0], w)
    w["e_score_correction_bias"] = jax.random.normal(
        jax.random.PRNGKey(2), (cfg.n_experts,)) * 0.1
    return w


def _as_params(tree):
    return jax.tree.map(lambda v: Param(v, ()), tree)


@pytest.mark.parametrize("dense_tokens", [moe.DENSE_TOKENS, 0],
                         ids=["dense", "grouped"])
def test_expert_shares_add_up_to_the_whole_layer(dense_tokens, monkeypatch):
    """Four programs each holding a quarter of the experts: their routed
    parts, with the shared experts counted once, add up to the uncut
    layer, with every held expert run on every token or the rows grouped
    by expert."""
    monkeypatch.setattr(moe, "DENSE_TOKENS", dense_tokens)
    cfg = get_smoke(ARCH)
    w = _full_layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, _ = moe.moe_layer(_as_params(w), x, **_layer(cfg))
        shared = moe.mlp(_as_params(w["shared"]), x, cfg.act, SHD)
        n = cfg.n_experts // 4
        parts = []
        for first in range(0, cfg.n_experts, n):
            share = {k: (v[first:first + n]
                         if k in ("w_gate", "w_up", "w_down") else v)
                     for k, v in w.items() if k != "shared"}
            y, _ = moe.moe_layer(_as_params(share), x,
                                 **_layer(cfg, (first, first + n)))
            parts.append(y)
    assert all(float(jnp.abs(y).max()) > 1e-3 for y in parts)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dense_tokens,group_rows",
                         [(moe.DENSE_TOKENS, moe.GROUP_ROWS),
                          (0, moe.GROUP_ROWS), (0, 8)],
                         ids=["dense", "grouped", "grouped-chunks"])
def test_no_token_is_dropped(dense_tokens, group_rows, monkeypatch):
    """All experts held and a bias that sends every token to expert 0
    (far past any capacity): every token's routed weights reach the
    output, with every expert run on every token, in one grouped product
    or in chunks of ``group_rows``."""
    monkeypatch.setattr(moe, "DENSE_TOKENS", dense_tokens)
    monkeypatch.setattr(moe, "GROUP_ROWS", group_rows)
    cfg = get_smoke(ARCH)
    w = _full_layer_params(cfg)
    w["e_score_correction_bias"] = w["e_score_correction_bias"].at[0].set(9.)
    del w["shared"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, _ = moe.moe_layer(_as_params(w), x, **_layer(cfg))
        wt, top, _ = moe.route(_as_params(w), x, top_k=cfg.top_k,
                               scoring="sigmoid",
                               routed_scaling=cfg.routed_scaling)
    assert (np.asarray(top) == 0).any(-1).all()
    xt = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    top = np.asarray(top).reshape(len(xt), -1)
    wt = np.asarray(wt, np.float64).reshape(len(xt), -1)
    g, u, dn = (np.asarray(w[k], np.float64)
                for k in ("w_gate", "w_up", "w_down"))
    want = np.zeros_like(xt)
    for t in range(len(xt)):
        for j, e in enumerate(top[t]):
            a = xt[t] @ g[e]
            want[t] += wt[t, j] * ((a / (1 + np.exp(-a))) * (xt[t] @ u[e])
                                   @ dn[e])
    np.testing.assert_allclose(np.asarray(y).reshape(len(xt), -1), want,
                               atol=2e-5, rtol=2e-5)


def test_dense_and_grouped_experts_agree(monkeypatch):
    """Every held expert on every token, weighted by the routing, gives
    what the grouped products give, on a share of the experts."""
    cfg = get_smoke(ARCH)
    w = _full_layer_params(cfg)
    share = {k: (v[2:6] if k in ("w_gate", "w_up", "w_down") else v)
             for k, v in w.items()}
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 8, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        dense, _ = moe.moe_layer(_as_params(share), x, **_layer(cfg, (2, 6)))
        monkeypatch.setattr(moe, "DENSE_TOKENS", 0)
        grouped, _ = moe.moe_layer(_as_params(share), x,
                                   **_layer(cfg, (2, 6)))
    np.testing.assert_allclose(np.asarray(dense), np.asarray(grouped),
                               atol=1e-5, rtol=1e-5)


def test_grouped_rows_and_cache_bytes_are_counted_at_trace_time():
    """``moe.grouped_rows``: rows handed to the grouped products of one
    program (tokens x top_k x expert layers; none in a decode step, whose
    few tokens run every held expert); the
    ``serve.cache_bytes_per_token`` gauge: decode-state bytes of one
    cached position over all layers, 9 x (512 + 64) x 2 B for the
    benchmark's cut of moonlight-16b-a3b."""
    cfg = get_smoke(ARCH)
    model = build_model(cfg)
    prefill, decode = make_serve_fns(model)
    params = model.abstract_params()
    b, s = 2, 80
    n_moe = cfg.n_layers - cfg.first_dense_layers
    obs.reset_counters("moe.")
    _, cache = jax.eval_shape(functools.partial(prefill, max_len=s + 4),
                              params, {"tokens": jax.ShapeDtypeStruct(
                                  (b, s), jnp.int32)})
    assert obs.counters()["moe.grouped_rows"] == b * s * cfg.top_k * n_moe
    obs.reset_counters("moe.")
    jax.eval_shape(decode, params, cache,
                   jax.ShapeDtypeStruct((b, 1), jnp.int32))
    assert obs.counters().get("moe.grouped_rows", 0) == 0

    full = dataclasses.replace(get_config(ARCH), n_layers=9, vocab=40960,
                               experts_held=(0, 16))
    jax.eval_shape(lambda: build_model(full).init_cache(64, 1536))
    assert obs.counters()["serve.cache_bytes_per_token"] == 10368
