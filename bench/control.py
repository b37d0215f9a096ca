#!/usr/bin/env python3
"""Readings that a cell's limit is set from, on the chip, in one process.

    python3 bench/control.py --workload <name> --seeds 12 --seconds 3

For each seed: new weights, a short window through the cell's compiled
programs (set up once), then over the same seeded sample of finished
requests two readings of `served_logit_gap`, both through the runner's
own `check` and the cell's limit:

- program: the widest gap by which a served token's logit lies below the
  float32 reference's best (what a run's `correct` compares);
- control: the same gap for the token that the reference computed with
  every matmul's operands rounded to float8 e4m3 puts first (the
  precision below the bfloat16 the configuration computes in).

The limit goes between the program's largest reading and the control's
smallest (limits/<workload>.json), so that the control's check comes out
not ok.  One JSON line per seed.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def readings(cell, seeds, seconds: float, t0: float):
    """Yield {seed, program, control} for each seed: the result of the
    runner's own `check` on the served tokens and on the control's."""
    from bench.reference.plain import Num
    serve = cell.runner
    state = serve.setup(cell, seeds[0], t0)
    shape = state["shape"]
    for seed in seeds:
        weights = serve.make_weights(cell, seed)
        state.update(seed=seed, weights=weights,
                     params=serve.program_params(
                         weights, state["model"].abstract_params()))
        rounds = serve.window(state, seconds)["rounds"]
        yield {"seed": seed,
               "program": serve.check(cell, weights, seed, rounds, shape),
               "control": serve.check(cell, weights, seed, rounds, shape,
                                      control=Num(fp8=True))}


def main(argv=None):
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    harness.enable_compile_cache()
    harness.device_guard(cell.chips)
    seeds = list(range(args.first_seed, args.first_seed + args.seeds))
    rows = []
    for r in readings(cell, seeds, args.seconds, T0):
        rows.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "program_max": max(r["program"]["value"] for r in rows),
        "control_min": min(r["control"]["value"] for r in rows),
        "program_all_ok": all(r["program"]["ok"] for r in rows),
        "control_any_ok": any(r["control"]["ok"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
