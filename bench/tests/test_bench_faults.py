"""A whole run at smoke size on the CPU, with the device guard skipped:
correct as it stands, and not correct with the timed path broken
underneath in each way a serving cell can break.  (The cells run on one
chip, so there is no exchange between chips to leave out.)"""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.conftest import CPU


def run(cell, seed=5):
    return harness.run_cell(cell, seed=seed, seconds=0.3, trace=False,
                            t0=0.0, device=CPU)


def test_sound_run_is_correct(data_root, clean_tuning):
    out = run(harness.resolve("hymba-tiny.rounds", root=data_root))
    assert out["correct"] is True
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}
    c = out["checks"]["served_logit_gap"]
    assert c["value"] <= c["limit"]


def _unchanged_state(prefill, decode):
    # the step returns the cache it was given
    step = jax.jit(lambda p, c, t: (decode(p, jax.tree.map(jnp.copy, c),
                                           t)[0], c))
    return prefill, step


def _half_batch(prefill, decode):
    # the second half of the rows gets the first half's tokens
    def half(out):
        tok, cache = out
        n = tok.shape[0] // 2
        return jnp.concatenate([tok[:n], tok[:n]]), cache
    return (jax.jit(lambda p, t: half(prefill(p, t))),
            jax.jit(lambda p, c, t: half(decode(p, c, t))))


def _altered_token(prefill, decode):
    # one token changed where the step produces it
    def alter(out):
        tok, cache = out
        return tok.at[0, 0].set((tok[0, 0] + 1) % 512), cache
    return prefill, jax.jit(lambda p, c, t: alter(decode(p, c, t)))


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_token])
def test_broken_timed_path_is_not_correct(fault, data_root, clean_tuning,
                                          monkeypatch):
    cell = harness.resolve("hymba-tiny.rounds", root=data_root)
    serve = cell.runner
    sound = serve.serving_fns
    monkeypatch.setattr(serve, "serving_fns",
                        lambda model, max_len: fault(*sound(model, max_len)))
    out = run(cell)
    assert out["correct"] is False
    c = out["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]
