"""Serving runner: closed-loop rounds through the program's tuned serving path.

Set-up, timed from process start to the first timed request (`setup_s`):
weights made on the device from the seed by the configuration's
reference module (one jitted call), graph pretune at the cell's batch
and prompt (`GraphTuner.tune_config`) and `tuning_cache.freeze()`,
prefill (cache sized for prompt + gen) and decode (cache donated)
traced under `use_tuned_layers()` and compiled apart, and a warm-up
prefill and three decode steps (the last one timed: it sets how many
steps go ahead).

Window: rounds from the traffic generator, all requests of a round
arriving together; the next round starts when the last token of this
one is on the host.  Inside a round, decode steps are dispatched ahead
of the host's readback (up to `AHEAD_S` seconds of steps; the next step
needs only the device-side token), and each output token is read back
to the host as soon as it is ready, so a host stall inside a round
delays the readback, not the device.  The window closes at a round
boundary: a round is started while the check's sample needs more
rounds or while that brings the close nearer to `--seconds` after the
start, every started round is finished, and the window runs to the
last token read back.  The garbage collector is frozen and off inside.
A rate is then whole rounds over their whole time, whatever the phase
of the last round against the clock.

`correct`: a seeded sample of finished requests, the last one always in
it, goes through the plain float32 reference once each (prompt plus
served tokens); the number compared is the widest gap by which a
served token's logit lies below the reference's best at its position.
"""
from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time
from typing import Dict

TRACE_DECODE_STEPS = 64   # decode steps a traced run records, from round 0
AHEAD_S = 4.0             # seconds of decode steps dispatched ahead of the readback


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also past 32 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_weights(cell, seed: int):
    """The configuration's weights, made by its reference in one jitted call."""
    import functools
    import jax
    init = functools.partial(cell.reference.init, m=cell.config["model"])
    return jax.jit(init)(seed_key(seed))


def program_params(weights, abstract):
    """The program's parameter tree holding the benchmark's arrays (no copy)."""
    from repro.models.params import Param

    def walk(node, w, path):
        if isinstance(node, Param):
            if tuple(w.shape) != tuple(node.shape) or w.dtype != node.dtype:
                raise ValueError(f"weight {'/'.join(path)}: reference has "
                                 f"{w.shape} {w.dtype}, program wants "
                                 f"{node.shape} {node.dtype}")
            return Param(w, node.dims)
        if set(node) != set(w):
            raise ValueError(f"weights at {'/'.join(path) or '/'}: reference "
                             f"has {sorted(w)}, program {sorted(node)}")
        return {k: walk(v, w[k], path + (k,)) for k, v in node.items()}

    return walk(abstract, weights, ())


def serving_fns(model, max_len: int):
    """(prefill, decode) as the window drives them: the program's serving
    functions with greedy sampling of the last position fused in."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.distributed import make_serve_fns
    prefill, decode = make_serve_fns(model)
    prefill = functools.partial(prefill, max_len=max_len)

    def greedy(logits):
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]

    def bench_prefill(params, tokens):
        logits, cache = prefill(params, {"tokens": tokens})
        return greedy(logits), cache

    def bench_decode(params, cache, tok):
        logits, cache = decode(params, cache, tok)
        return greedy(logits), cache

    return jax.jit(bench_prefill), jax.jit(bench_decode, donate_argnums=(1,))


_COMPILES = []   # one entry per backend compilation in this process


def _count_compiles():
    """Start counting backend compilations (a window must hold none)."""
    import jax
    if not _COMPILES:
        _COMPILES.append(None)

        def on(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES.append(None)
        jax.monitoring.register_event_duration_secs_listener(on)
    return len(_COMPILES)


def _log(msg):
    print(f"[bench] {msg}", flush=True)


def window(state, seconds: float, trace_dir=None) -> Dict:
    """Closed-loop rounds closed at the round boundary nearest `seconds`
    after the start; host times of every token."""
    import collections
    import jax
    import jax.numpy as jnp
    import numpy as np
    ann = jax.profiler.TraceAnnotation
    gen_mod, traffic, seed = state["generator"], state["traffic"], state["seed"]
    b, p, g = gen_mod.shape(traffic)
    vocab = state["vocab"]
    params, prefill_c, decode_c = (state["params"], state["prefill"],
                                   state["decode"])
    ahead = max(1, math.ceil(AHEAD_S / state["step_s"]))
    min_rounds = state["min_rounds"]
    requests, rounds = [], []
    now = time.perf_counter
    t0 = now()
    t1 = t0 + seconds
    r = traced = 0
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(trace_dir)
    while True:
        prompts = gen_mod.prompts(traffic, seed, r, vocab)
        start = now()
        # the rounds that the check's sample needs always; another while
        # its end, a round later, lies nearer the close than this boundary
        if r >= min_rounds and start + (start - rounds[-1]["start"]) / 2 >= t1:
            break
        times, toks, pending = [], [], collections.deque()

        def read_oldest():
            with ann("bench.readback"):
                toks.append(np.asarray(pending.popleft()))
            times.append(now())

        with ann("bench.batch"):
            with ann("bench.prefill"):
                tok, cache = prefill_c(params, jnp.asarray(prompts))
            pending.append(tok)
            k = 1
            while k < g or pending:
                if k < g and (not pending or (len(pending) <= ahead
                                              and not pending[0].is_ready())):
                    if tracing and traced == TRACE_DECODE_STEPS:
                        while pending:
                            read_oldest()
                        jax.profiler.stop_trace()
                        tracing = False
                    traced += 1
                    with ann("bench.decode"):
                        tok, cache = decode_c(params, cache, tok)
                    pending.append(tok)
                    k += 1
                else:
                    read_oldest()
        del cache
        served = np.concatenate(toks, axis=1)            # (b, g)
        rounds.append({"index": r, "tokens": served, "start": start})
        for row in range(b):
            requests.append({"start": start, "times": times, "round": r,
                             "row": row})
        r += 1
    if tracing:
        jax.profiler.stop_trace()
    return {"window": [t0, requests[-1]["times"][-1]], "requests": requests,
            "rounds": rounds}


def sample_size(cell, gen: int) -> int:
    """Finished requests that the check samples."""
    lim = cell.limits["served_logit_gap"]
    return max(int(lim["min_requests"]),
               math.ceil(int(lim["sample_tokens"]) / gen))


def sample_blocks(cell, seed: int, rounds, shape):
    """The seeded sample of finished requests (the last one always in it),
    in blocks of `reference_rows`: (prompt + served tokens but the last,
    served tokens) as arrays (n, prompt + gen - 1) and (n, gen)."""
    import numpy as np
    lim = cell.limits["served_logit_gap"]
    b, p, g = shape
    vocab = cell.config["model"]["vocab"]
    finished = [(rd["index"], row) for rd in rounds for row in range(b)]
    want = sample_size(cell, g)
    rng = np.random.default_rng([int(seed), 1])
    pick = rng.permutation(len(finished) - 1)[:want - 1]
    sample = [finished[i] for i in sorted(pick)] + [finished[-1]]
    served = {rd["index"]: rd["tokens"] for rd in rounds}
    rows = int(lim["reference_rows"])
    for i in range(0, len(sample), rows):
        block = sample[i:i + rows]
        prompts = [cell.generator.prompts(cell.traffic, seed, r, vocab)[row]
                   for r, row in block]
        out = np.stack([served[r][row] for r, row in block])
        yield np.concatenate([np.stack(prompts), out[:, :-1]], axis=1), out


def reference_fn(cell, shape, num=None):
    """The reference's logits at the served positions, jitted."""
    import functools
    import jax
    _, p, g = shape
    kw = {} if num is None else {"num": num}
    return jax.jit(functools.partial(cell.reference.forward,
                                     m=cell.config["model"],
                                     rows=slice(p - 1, p + g - 1), **kw))


def gap(ref_logits, tokens) -> float:
    """Widest gap by which the logit of `tokens` lies below the best."""
    import jax.numpy as jnp
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[..., None],
                              axis=-1)[..., 0]
    return float(jnp.max(jnp.max(ref_logits, axis=-1) - got))


def check(cell, weights, seed: int, rounds, shape, control=None) -> Dict:
    """Widest gap of a served token's logit below the reference's best.

    With `control` (a `plain.Num`), the tokens judged are not the served
    ones but those that the reference computed at that precision puts
    first at the same positions of the same prompts and served tokens:
    the control, which the limit has to fail."""
    import jax.numpy as jnp
    lim = cell.limits["served_logit_gap"]
    fwd = reference_fn(cell, shape)
    ctl = None if control is None else reference_fn(cell, shape, num=control)
    worst, n = 0.0, 0
    for toks, served in sample_blocks(cell, seed, rounds, shape):
        toks = jnp.asarray(toks)
        if ctl is not None:
            served = jnp.argmax(ctl(weights, toks), axis=-1)
        worst = max(worst, gap(fwd(weights, toks), served))
        n += len(served)
    ok = math.isfinite(worst) and worst <= float(lim["limit"])
    return {"name": "served_logit_gap", "value": worst,
            "limit": float(lim["limit"]), "ok": ok,
            "requests": n, "tokens": n * shape[2]}


def setup(cell, seed: int, t0: float) -> Dict:
    """Everything before the first timed request; times its parts."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import tuning_cache
    import repro.kernels  # noqa: F401  (registers the kernels freeze() covers)
    from repro.core.autotuner import GraphTuner
    from repro.kernels import api as kernel_api
    from repro.launch.serve import frozen_audit_problems
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.models.layers import use_tuned_layers

    m = cell.config["model"]
    cfg = ModelConfig(**m)
    b, p, g = cell.generator.shape(cell.traffic)
    model = build_model(cfg)
    weights = make_weights(cell, seed)
    params = program_params(weights, model.abstract_params())

    now = time.perf_counter
    t = now()
    db = tuning_cache.get_default_db()
    GraphTuner.tune_config(cfg, batch=b, prompt_len=p, db=db)
    n_frozen = tuning_cache.freeze()
    pretune_s = now() - t
    kernel_api.reset_dispatch_stats()
    n_records = len(db)

    prefill, decode = serving_fns(model, p + g)
    tokens_abs = jax.ShapeDtypeStruct((b, p), jnp.int32)
    with use_tuned_layers():
        t = now()
        prefill_c = prefill.lower(params, tokens_abs).compile()
        tok_abs, cache_abs = jax.eval_shape(prefill, params, tokens_abs)
        decode_c = decode.lower(params, cache_abs, tok_abs).compile()
        compile_s = now() - t
    st = kernel_api.dispatch_stats()
    audit = frozen_audit_problems({"dispatch": st,
                                   "runtime_tunes": len(db) - n_records})
    _log(f"dispatch: {st['frozen']}/{st['total']} frozen, {st['live']} live, "
         f"{st['fallback']} fallback, {len(db) - n_records} runtime tunes, "
         f"{n_frozen} frozen entries; audit {audit or 'clean'}")

    # warm-up: one prefill and three decode steps through the compiled
    # programs; the last step's time sets how many steps go ahead
    tok, cache = prefill_c(params, jnp.asarray(
        cell.generator.prompts(cell.traffic, seed, 0, m["vocab"])))
    for _ in range(2):
        tok, cache = decode_c(params, cache, tok)
    np.asarray(tok)
    t = now()
    tok, cache = decode_c(params, cache, tok)
    np.asarray(tok)
    step_s = now() - t
    del cache
    return {"model": model, "weights": weights, "params": params,
            "prefill": prefill_c, "decode": decode_c,
            "generator": cell.generator, "traffic": cell.traffic,
            "seed": seed, "vocab": m["vocab"], "shape": (b, p, g),
            "step_s": step_s, "min_rounds": math.ceil(sample_size(cell, g) / b),
            "pretune_s": pretune_s, "compile_s": compile_s}


def trace_record(trace_dir, peaks) -> Dict:
    """The traced part of the window reduced to what the readers need."""
    from bench import harness, tracefile
    names = harness.kernel_names()
    work = {kid: harness.code("work", kid).work for kid in set(names.values())}
    return tracefile.reduce(tracefile.extract(trace_dir), names, work, peaks)


def run(cell, *, seed: int, seconds: float, trace: bool, t0: float,
        device: Dict) -> Dict:
    import jax
    from bench import harness, stats
    peaks = harness.peaks_for(device["kind"]) if device["platform"] == "tpu" \
        else None
    state = setup(cell, seed, t0)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    n_compiles = _count_compiles()
    # what set-up made stays put; no collector pass runs inside the window
    gc.collect()
    gc.freeze()
    gc.disable()
    setup_s = time.perf_counter() - t0
    try:
        win = window(state, seconds, trace_dir)
    finally:
        gc.enable()
        gc.unfreeze()
    n_compiles = _count_compiles() - n_compiles
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:cell.chips])
    b, p, g = state["shape"]
    reqs = win["requests"]
    rec = {"setup_s": setup_s, "pretune_s": state["pretune_s"],
           "compile_s": state["compile_s"], "window": win["window"],
           "requests": reqs, "attempted": len(reqs),
           "failed": int(sum(((rd["tokens"] < 0)
                              | (rd["tokens"] >= state["vocab"])).any(axis=1)
                             .sum() for rd in win["rounds"])),
           "memory_peak_bytes": int(peak), "peaks": peaks, "trace": None}
    ttft = stats.ttfts(rec)
    turns = [nxt["start"] - reqs[b * i]["times"][-1]   # host, between rounds
             for i, nxt in enumerate(win["rounds"][1:])]
    _log(f"window: {len(reqs)} requests in {len(win['rounds'])} rounds, "
         f"{stats.tokens_in_window(rec)} tokens and {len(stats.gaps(rec))} "
         f"gaps inside the close, {len(ttft)} first tokens (p90 "
         f"{stats.percentile(ttft, 90)} s, longest {max(ttft, default=None)} "
         f"s, first round after {reqs[0]['start'] - win['window'][0]} s); "
         f"longest gap "
         f"{max(stats.gaps(rec), default=None)} s, longest turn between "
         f"rounds {max(turns, default=None)} s; {n_compiles} compilations "
         f"inside; setup {setup_s} s (pretune {state['pretune_s']} s, "
         f"compile {state['compile_s']} s)")
    m = cell.config["model"]
    mw = cell.model_work
    rec["work"] = {"prefill": mw.prefill(m, b, p),
                   "decode_steps": [mw.decode_step(m, b, p + k - 1)
                                    for k in range(1, g)]}
    if trace:
        rec["trace"] = trace_record(trace_dir, peaks)
        shutil.rmtree(trace_dir, ignore_errors=True)
    # free the program's state before the reference runs
    weights, rounds = state["weights"], win["rounds"]
    del state, win
    c = check(cell, weights, seed, rounds, (b, p, g))
    _log(f"checked {c['requests']} requests, {c['tokens']} served tokens "
         f"against the reference")
    rec["checks"] = [c]
    return rec
