"""Serving launcher: batched prefill + token-by-token decode.

    PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b \
        --smoke --batch 4 --prompt-len 64 --gen 32

`serve` is the production path (graph pretune, freeze the dispatch
tables, build, compile prefill and decode, warm up, time prefill and a
decode loop with donated cache, audit dispatch) against any arch in the
registry; ``main`` is its command line (it also layers ``--tuning-db``
over the packaged records), and ``chip_smoke.py`` drives the same
function on the chip.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _warm_tuning_db(db, path: str, strict: bool = False):
    """Warm ``db`` from a JSONL, reporting skipped corrupt lines.

    Returns ``(loaded, corrupt)``.  ``strict`` turns any corruption —
    an unreadable file or skipped lines — into a non-zero exit instead
    of a degraded start (deployments that treat the tuning database as
    an artifact with provenance want the loud failure)."""
    corrupt0 = db.stats.corrupt
    try:
        n = db.warm_jsonl(path)
    except OSError as e:
        msg = f"could not warm tuning cache from {path}: {e}"
        if strict:
            raise SystemExit(f"[serve] --strict-db: {msg}")
        print(f"[serve] WARNING: {msg}")
        return 0, 0
    corrupt = db.stats.corrupt - corrupt0
    print(f"[serve] warmed tuning cache: +{n} records from {path}"
          + (f" ({corrupt} corrupt lines skipped)" if corrupt else ""))
    if corrupt and strict:
        raise SystemExit(f"[serve] --strict-db: {corrupt} corrupt "
                         f"line(s) skipped in {path}")
    return n, corrupt


def _connect_tuning_server(url: str):
    """Point cold dispatches at a tuning service; never fatal — an
    unreachable service means serving starts degraded on the local
    tiers (pretuned records, then fallback params), with a banner."""
    from repro import tuning_cache
    try:
        client = tuning_cache.configure_service(url)
    except ValueError as e:
        print(f"[serve] WARNING: bad --tuning-server {url!r} ({e}); "
              f"serving DEGRADED on local tiers")
        return None
    health = client.health()
    if health is None:
        print(f"[serve] WARNING: tuning service {client.url} unreachable "
              f"— serving DEGRADED on local tiers (pretuned records, "
              f"then fallback params)")
    else:
        print(f"[serve] tuning service {client.url}: "
              f"{health.get('records', '?')} records, "
              f"generation {health.get('generation', '?')}")
    return client


def serve(cfg, *, batch: int, prompt_len: int, gen: int, seed: int = 0,
          temperature: float = 0.0, tuned_ops: bool = False,
          pretune: bool = False) -> Dict[str, Any]:
    """Serve one seeded batch of ``cfg`` end to end, printing each phase.

    Pretune (optional) and freeze the tuning database, build the model
    with seeded random weights, compile prefill (its cache sized for
    ``prompt_len + gen``) and decode apart from any run, warm both up,
    then time one prefill and ``gen`` decode steps, each window ending
    in ``block_until_ready``.  ``tuned_ops`` routes rms_norm, the gated
    MLP and full attention through the tuned kernel registry.

    Returns the run's record: ``model``, ``params``, ``batch``,
    ``prefill_logits`` (the timed prefill's), ``prefill_compiled``,
    ``compile_s`` ({"prefill", "decode"}), ``prefill_ms``,
    ``decode_ms_per_token``, ``tokens`` ((batch, gen + 1) ints),
    ``dispatch`` (`repro.kernels.api.dispatch_stats`) and
    ``runtime_tunes`` (records the database grew while serving).
    """
    from repro import tuning_cache
    import repro.kernels  # noqa: F401  (registers dispatch problems —
    #                        freeze() below compiles only registered kernels)
    from repro.distributed import make_serve_fns
    from repro.kernels import api as kernel_api
    from repro.models import build_model
    from repro.models.layers import use_tuned_layers

    # The default db auto-loads the packaged pre-tuned records, so the
    # serving path never pays a cold full-space rank.
    db = tuning_cache.get_default_db()
    print(f"[serve] tuning cache ready: {len(db)} records resident")
    if pretune:
        # Graph-level pretune (DESIGN.md §15): abstract-trace this
        # config's prefill + decode, rank every kernel instance they
        # dispatch.  Runs BEFORE freeze so the frozen tables cover the
        # whole serving path.
        from repro.core.autotuner import GraphTuner
        rep = GraphTuner.tune_config(cfg, batch=batch,
                                     prompt_len=prompt_len, db=db)
        print(f"[serve] graph pretune [{cfg.name}]: "
              f"{rep['dispatches']} dispatches, "
              f"{len(rep['instances'])} unique kernel instances ranked")
    # Freeze the warm records into the zero-overhead dispatch tables:
    # the serving hot loop then pays one lock-free probe per kernel
    # dispatch instead of the full normalize/key/LRU path.  Any later
    # cache mutation thaws automatically (and dispatch still works,
    # just through the live tiers).
    n_frozen = tuning_cache.freeze()
    print(f"[serve] dispatch tables frozen: {n_frozen} entries")
    if tuned_ops:
        print("[serve] tuned ops ON: layers dispatch through the "
              "kernel registry")
    routing = use_tuned_layers() if tuned_ops else contextlib.nullcontext()
    n_records_before = len(db)
    kernel_api.reset_dispatch_stats()

    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(model.init)(key)
    prefill, decode_step = make_serve_fns(model)
    prefill = jax.jit(functools.partial(prefill, max_len=prompt_len + gen))
    decode_step = jax.jit(decode_step, donate_argnums=(1,))
    batch_in = {"tokens": jax.random.randint(
        key, (batch, prompt_len), 0, cfg.vocab)}
    if cfg.frontend == "frames":
        batch_in["frames"] = jax.random.normal(
            key, (batch, cfg.enc_seq, cfg.d_model), jnp.bfloat16)

    # Compile both programs apart from any run (set-up time).  Tuned
    # layers resolve their kernels while these trace.
    with routing:
        t0 = time.perf_counter()
        prefill_c = prefill.lower(params, batch_in).compile()
        t_prefill_c = time.perf_counter() - t0
        _, cache_abs = jax.eval_shape(prefill, params, batch_in)
        tok_abs = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        t0 = time.perf_counter()
        decode_c = decode_step.lower(params, cache_abs, tok_abs).compile()
        t_decode_c = time.perf_counter() - t0
    print(f"[serve] compile: prefill {t_prefill_c:.2f} s, "
          f"decode {t_decode_c:.2f} s")

    def next_token(logits, key):
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits[:, -1] / temperature)[:, None]
            return tok.astype(jnp.int32), key
        return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32), key

    # Warm-up: one prefill and one decode step, sampled like the timed
    # loop, so the windows below hold no compilation.
    logits, cache = prefill_c(params, batch_in)
    tok, key = next_token(logits, key)
    logits, cache = decode_c(params, cache, tok)
    tok, key = next_token(logits, key)
    jax.block_until_ready((tok, cache))
    del cache

    t0 = time.perf_counter()
    prefill_logits, cache = prefill_c(params, batch_in)
    jax.block_until_ready(prefill_logits)
    t_prefill = time.perf_counter() - t0
    print(f"[serve] prefill {batch}x{prompt_len}: {t_prefill*1e3:.1f} ms")

    tok, key = next_token(prefill_logits, key)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for _ in range(gen):
        logits, cache = decode_c(params, cache, tok)
        tok, key = next_token(logits, key)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    dt = (time.perf_counter() - t0) / max(gen, 1)
    toks = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"[serve] decode: {dt*1e3:.1f} ms/token "
          f"({batch} sequences x {gen} tokens)")
    print(f"[serve] sample tokens[0]: {toks[0][:16].tolist()}")

    st = kernel_api.dispatch_stats()
    n_new = len(db) - n_records_before
    print(f"[serve] dispatch audit: {st['frozen']}/{st['total']} frozen, "
          f"{st['live']} live, {st['fallback']} fallback; "
          f"{n_new} runtime tunes")
    return {"model": model, "params": params, "batch": batch_in,
            "prefill_logits": prefill_logits, "prefill_compiled": prefill_c,
            "compile_s": {"prefill": t_prefill_c, "decode": t_decode_c},
            "prefill_ms": t_prefill * 1e3, "decode_ms_per_token": dt * 1e3,
            "tokens": toks, "dispatch": st, "runtime_tunes": n_new}


def frozen_audit_problems(run: Dict[str, Any]) -> List[str]:
    """Why a `serve` run was not 100% frozen dispatch with zero runtime
    tunes (empty when it was)."""
    st, n_new = run["dispatch"], run["runtime_tunes"]
    problems = []
    if st["total"] == 0:
        problems.append("no dispatches routed through the kernel "
                        "registry (missing --tuned-ops?)")
    if st["live"] or st["fallback"]:
        problems.append(f"non-frozen dispatches: live={st['live']} "
                        f"fallback={st['fallback']}")
    if st["frozen"] != st["total"]:
        problems.append(f"frozen {st['frozen']} != total {st['total']}")
    if n_new:
        problems.append(f"{n_new} runtime tune(s) grew the database")
    return problems


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tuning-db", default=None,
                    help="JSONL tuning database to warm kernel dispatch "
                         "with before serving (on top of the packaged "
                         "pre-tuned records)")
    ap.add_argument("--strict-db", action="store_true",
                    help="exit non-zero if --tuning-db has corrupt "
                         "lines (default: skip them, print the count)")
    ap.add_argument("--tuning-server", default=None, metavar="URL",
                    help="tuning service to consult for cold dispatches "
                         "(http://host:port); unreachable -> serve "
                         "degraded on the local tiers")
    ap.add_argument("--tuned-ops", action="store_true",
                    help="route rms_norm / gated-mlp / full attention "
                         "through the variant-aware tuned kernel "
                         "registry (repro.kernels.ops) instead of the "
                         "jnp layer paths")
    ap.add_argument("--pretune", action="store_true",
                    help="graph-level pretune before freezing: "
                         "enumerate every kernel instance this config's "
                         "prefill+decode dispatches and rank each into "
                         "the tuning database (GraphTuner.tune_config)")
    ap.add_argument("--assert-frozen", action="store_true",
                    help="exit non-zero unless every registry dispatch "
                         "hit the frozen tables and the database saw "
                         "zero runtime tunes (CI gate; pair with "
                         "--tuned-ops --pretune)")
    args = ap.parse_args()

    from repro import tuning_cache
    from repro.configs import get_config, get_smoke
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # --tuning-db layers a deployment-specific database over the
    # packaged pre-tuned records.
    db = tuning_cache.get_default_db()
    if args.tuning_db:
        _warm_tuning_db(db, args.tuning_db, strict=args.strict_db)
    if args.tuning_server:
        _connect_tuning_server(args.tuning_server)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    run = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                gen=args.gen, seed=args.seed, temperature=args.temperature,
                tuned_ops=args.tuned_ops, pretune=args.pretune)
    if args.assert_frozen:
        problems = frozen_audit_problems(run)
        if problems:
            raise SystemExit("[serve] --assert-frozen FAILED: "
                             + "; ".join(problems))
        print("[serve] --assert-frozen OK: 100% frozen dispatch, "
              "zero runtime tunes")


if __name__ == "__main__":
    main()
