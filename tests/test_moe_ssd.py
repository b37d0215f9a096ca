"""Numerical correctness of the MoE dispatch and the SSD scan against
naive references, plus prefill->decode parity per family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.sharding import Sharder
from repro.models import ModelConfig, build_model
from repro.models.moe import init_moe, moe_layer
from repro.models.ssd import (SsdConfig, init_ssd, init_ssd_state,
                              ssd_block, ssd_decode)

SHD = Sharder()


def test_moe_matches_dense_loop_reference():
    """Capacity large enough that nothing drops -> the sort-based
    dispatch must equal the explicit per-token loop."""
    key = jax.random.PRNGKey(0)
    d, f, e, k = 16, 32, 4, 2
    p = init_moe(key, d, f, e, n_shared=0)
    x = jax.random.normal(key, (2, 8, d), jnp.float32)
    y, aux = moe_layer(p, x, n_experts=e, top_k=k, capacity_factor=8.0,
                       act="silu_glu", shd=SHD)

    # reference: route each token through its top-k experts explicitly
    xt = x.reshape(-1, d)
    logits = xt @ np.asarray(p["router"].value)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    wg = np.asarray(p["w_gate"].value)
    wu = np.asarray(p["w_up"].value)
    wd = np.asarray(p["w_down"].value)
    want = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        for j in range(k):
            ei = int(top_i[t, j])
            h = jax.nn.silu(xt[t] @ wg[ei]) * (xt[t] @ wu[ei])
            want[t] += float(top_p[t, j]) * np.asarray(h @ wd[ei])
    np.testing.assert_allclose(np.asarray(y).reshape(-1, d), want,
                               rtol=2e-4, atol=2e-4)
    assert float(aux) > 0


def test_moe_capacity_drops_are_bounded():
    """The capacity dispatch (what a mesh that shards the experts axis
    runs) with tiny capacity still runs, its outputs are finite, and the
    rows past capacity are dropped: the output differs from the
    dropless layer's."""
    from repro.models.moe import _capacity, moe_capacity, route
    key = jax.random.PRNGKey(1)
    p = init_moe(key, 8, 16, 4, n_shared=0)
    x = jax.random.normal(key, (1, 256, 8), jnp.float32)
    w, top, _ = route(p, x, top_k=2)
    cap = moe_capacity(256, 4, 2, 0.25)          # capacity 32, about 128 rows an expert
    y = _capacity(x, top, w, p["w_gate"].value, p["w_up"].value,
                  p["w_down"].value, "silu_glu", cap, SHD, "flat")
    assert np.isfinite(np.asarray(y)).all()
    dropless, _ = moe_layer(p, x, n_experts=4, top_k=2, capacity_factor=0.25,
                            act="silu_glu", shd=SHD)
    assert not np.allclose(np.asarray(y), np.asarray(dropless), atol=1e-3)


@pytest.mark.parametrize("dispatch", ["flat", "grouped"])
def test_capacity_dispatch_with_room_matches_the_dropless_layer(dispatch):
    """Where a mesh shards the experts axis the capacity dispatch runs;
    with capacity to spare it drops nothing, and a share of the experts
    (the last 4 of 8) gives what the dropless layer gives for it."""
    from repro.models.moe import _capacity, moe_capacity, route
    key = jax.random.PRNGKey(8)
    p = init_moe(key, 16, 32, 8, held=(4, 8))
    x = jax.random.normal(key, (2, 8, 16), jnp.float32)
    w, top, _ = route(p, x, top_k=2)
    cap = moe_capacity(8 if dispatch == "grouped" else 16, 8, 2, 8.0)
    y = _capacity(x, top - 4, w, p["w_gate"].value, p["w_up"].value,
                  p["w_down"].value, "silu_glu", cap, SHD, dispatch)
    want, _ = moe_layer(p, x, n_experts=8, top_k=2, capacity_factor=8.0,
                        act="silu_glu", shd=SHD, held=(4, 8))
    assert float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


def _ssd_naive(p, x, cfg):
    """Token-by-token recurrence reference for the chunked SSD."""
    from repro.models.ssd import _split_in, _causal_conv, xc_skip
    from repro.models.layers import _rms
    b, t, _ = x.shape
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.n_heads
    z, xbc, dt = _split_in(p, x, cfg)
    xbc = _causal_conv(xbc, p["conv_w"].value, p["conv_b"].value,
                       cfg.ssm_conv)
    xin = xbc[..., :di].reshape(b, t, h, cfg.head_dim)
    b_in = np.asarray(xbc[..., di:di + n], np.float64)
    c_in = np.asarray(xbc[..., di + n:], np.float64)
    a = -np.exp(np.asarray(p["a_log"].value, np.float64))
    dtp = np.asarray(jax.nn.softplus(
        dt.astype(jnp.float32) + p["dt_bias"].value), np.float64)
    xf = np.asarray(xin, np.float64)
    s = np.zeros((b, h, n, cfg.head_dim))
    ys = np.zeros_like(xf)
    for ti in range(t):
        decay = np.exp(dtp[:, ti] * a)                       # (B,H)
        upd = np.einsum("bn,bh,bhp->bhnp", b_in[:, ti], dtp[:, ti],
                        xf[:, ti])
        s = s * decay[..., None, None] + upd
        ys[:, ti] = np.einsum("bn,bhnp->bhp", c_in[:, ti], s)
    ys = ys + np.asarray(xc_skip(p, xin), np.float64)
    return ys, s


@pytest.mark.parametrize("t,chunk", [(16, 4), (24, 8), (7, 16)])
def test_ssd_chunked_matches_recurrence(t, chunk):
    key = jax.random.PRNGKey(2)
    cfg = SsdConfig(d_model=16, ssm_state=8, expand=2, head_dim=8,
                    chunk=chunk)
    p = init_ssd(key, cfg)
    x = jax.random.normal(key, (2, t, 16), jnp.float32) * 0.5

    from repro.models.ssd import _split_in, _causal_conv, _ssd_chunked
    z, xbc, dt = _split_in(p, x, cfg)
    xbc = _causal_conv(xbc, p["conv_w"].value, p["conv_b"].value,
                       cfg.ssm_conv)
    di, n = cfg.d_inner, cfg.ssm_state
    xh = xbc[..., :di].reshape(2, t, cfg.n_heads, cfg.head_dim)
    a = -jnp.exp(p["a_log"].value)
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].value)
    y, (_, s_scan) = _ssd_chunked(xh, dtp, a, xbc[..., di:di + n],
                                  xbc[..., di + n:], cfg)

    want, s_final = _ssd_naive(p, x, cfg)
    skip = np.asarray(
        xh * p["d_skip"].value[None, None, :, None].astype(jnp.float32),
        np.float64)
    np.testing.assert_allclose(np.asarray(y, np.float64) + skip, want,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s_scan[:, -1], np.float64),
                               s_final, rtol=2e-3, atol=2e-3)


def test_ssd_gradient_is_finite_at_a_full_size_chunk():
    """A chunk of 128 (hymba-1.5b's) spans decays whose inverse, above
    the diagonal of the intra-chunk matrix, overflows f32: training
    must still see finite gradients."""
    key = jax.random.PRNGKey(4)
    cfg = SsdConfig(d_model=16, ssm_state=8, expand=2, head_dim=8,
                    chunk=128)
    p = init_ssd(key, cfg)
    x = jax.random.normal(key, (1, 256, 16), jnp.float32)
    grads = jax.grad(lambda p: ssd_block(p, x, cfg, SHD).sum())(p)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()


def test_ssd_prefill_decode_parity():
    """Decode from a prefilled state must equal the full forward."""
    key = jax.random.PRNGKey(3)
    cfg = SsdConfig(d_model=16, ssm_state=8, expand=2, head_dim=8,
                    chunk=8)
    p = init_ssd(key, cfg)
    x = jax.random.normal(key, (2, 17, 16), jnp.float32) * 0.5

    full = ssd_block(p, x, cfg, SHD)
    out_prefix, state = ssd_block(p, x[:, :16], cfg, SHD,
                                  return_state=True)
    y_last, _ = ssd_decode(p, x[:, 16:17], state, cfg, SHD)
    np.testing.assert_allclose(np.asarray(y_last, np.float32),
                               np.asarray(full[:, 16:17], np.float32),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("family,cfg", [
    ("dense", ModelConfig(name="t", family="dense", n_layers=2,
                          d_model=32, n_heads=4, n_kv=2, d_ff=64,
                          vocab=128)),
    ("dense-kvrep", ModelConfig(name="t", family="dense", n_layers=2,
                                d_model=32, n_heads=4, n_kv=2, d_ff=64,
                                vocab=128, kv_repeat=2)),
    ("moe", ModelConfig(name="t", family="moe", n_layers=2, d_model=32,
                        n_heads=4, n_kv=2, d_ff=64, d_ff_expert=32,
                        n_experts=4, top_k=2, n_shared=1, vocab=128,
                        capacity_factor=4.0, pad_experts_to=8)),
    ("moe-grouped", ModelConfig(name="t", family="moe", n_layers=2,
                                d_model=32, n_heads=4, n_kv=2, d_ff=64,
                                d_ff_expert=32, n_experts=4, top_k=2,
                                vocab=128, capacity_factor=4.0,
                                moe_dispatch="grouped")),
    ("ssm", ModelConfig(name="t", family="ssm", n_layers=2, d_model=32,
                        n_heads=1, n_kv=1, d_ff=0, vocab=128,
                        ssm_state=8, ssm_head_dim=8, ssm_chunk=8,
                        head_dim=8)),
    ("hybrid", ModelConfig(name="t", family="hybrid", n_layers=3,
                           d_model=32, n_heads=4, n_kv=2, d_ff=64,
                           vocab=128, ssm_state=8, ssm_head_dim=8,
                           ssm_chunk=8, swa_window=8,
                           decode_cache_cap=64)),
])
def test_prefill_decode_matches_forward(family, cfg):
    """logits(decode @ pos s | prefill[:s]) == logits(forward)[s]."""
    model = build_model(cfg)
    key = jax.random.PRNGKey(4)
    params = model.init(key)
    s = 24
    tokens = jax.random.randint(key, (2, s + 1), 0, cfg.vocab)

    from repro.models.transformer import lm_logits
    full, _ = lm_logits(params, tokens, cfg, SHD)
    _, cache = model.prefill(params, {"tokens": tokens[:, :s]}, SHD,
                             max_len=s + 1)
    logits, _ = model.decode_step(params, cache, tokens[:, s:s + 1], SHD)
    # ssm/hybrid compare the chunked-scan forward against the O(1)
    # recurrence decode — different accumulation order in bf16 compute,
    # so the tolerance is wider than the dense (same-math) case.
    tol = (dict(rtol=2e-2, atol=2e-2) if family == "dense"
           else dict(rtol=5e-2, atol=8e-2))
    np.testing.assert_allclose(
        np.asarray(logits[:, 0], np.float32),
        np.asarray(full[:, s], np.float32), **tol)
