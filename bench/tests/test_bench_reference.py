"""The plain references against the program at smoke sizes on the CPU.

The program runs with float32 activations here, so both sides compute
the same equations in the same precision and differ by the order of
float32 rounding alone (the SSM as a chunked dual form against the
reference's sequential recurrence, attention through the decode cache
against the whole-sequence pass).  Tolerance: 1e-4 absolute on logits of
about unit scale, far below the 1e-2 and more that a wrong mask, a
dropped gain or a stale state gives at these sizes.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

TOL = 1e-4


def program_logits(cell, weights, tokens, p, g):
    """Prefill of tokens[:, :p], then decode of each later token through
    the cache: logits at positions p-1 .. p+g-2."""
    from repro.distributed import make_serve_fns
    from repro.models import build_model
    from repro.models.config import ModelConfig
    cfg = ModelConfig(**dict(cell.config["model"], dtype="float32"))
    model = build_model(cfg)
    params = cell.runner.program_params(weights, model.abstract_params())
    prefill, decode = make_serve_fns(model)
    logits, cache = jax.jit(functools.partial(prefill, max_len=p + g))(
        params, {"tokens": tokens[:, :p]})
    out = [logits[:, -1]]
    step = jax.jit(decode)
    for k in range(p, p + g - 1):
        logits, cache = step(params, cache, tokens[:, k:k + 1])
        out.append(logits[:, -1])
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("workload", ["hymba-tiny.rounds",
                                      "starcoder2-tiny.rounds"])
def test_prefill_then_decode_matches_the_reference(workload, data_root):
    cell = harness.resolve(workload, root=data_root)
    m = cell.config["model"]
    p, g = 24, 8                     # past hymba-tiny's window of 16
    weights = cell.runner.make_weights(cell, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, m["vocab"], (2, p + g - 1)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = program_logits(cell, weights, tokens, p, g)
        want = cell.reference.forward(weights, tokens, m,
                                      slice(p - 1, p + g - 1))
    assert got.shape == want.shape == (2, g, m["vocab"])
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_reference_sees_a_changed_weight(data_root):
    """A gain the program would drop moves the reference's logits."""
    cell = harness.resolve("hymba-tiny.rounds", root=data_root)
    m = cell.config["model"]
    w = cell.runner.make_weights(cell, 3)
    tokens = jnp.zeros((1, 20), jnp.int32).at[0, ::3].set(7)
    rows = slice(10, 20)
    a = cell.reference.forward(w, tokens, m, rows)
    w2 = jax.tree.map(lambda x: x, w)
    w2["blocks"]["ssd"]["d_skip"] = jnp.ones_like(w["blocks"]["ssd"]["d_skip"])
    b = cell.reference.forward(w2, tokens, m, rows)
    assert float(jnp.abs(a - b).max()) > 1e-2
