import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count on first init).  Do not move them.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, on the single-pod 16x16
mesh AND the 2x16x16 multi-pod mesh:

    with mesh:
        lowered  = jax.jit(step_fn).lower(*cell_inputs(...))
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves it fits
        compiled.cost_analysis()     # FLOPs/bytes for the roofline

and records one JSON artifact per cell under ``experiments/dryrun/``.
Failures (sharding mismatch, OOM at compile, unsupported collective)
are bugs; long_500k on full-attention archs is the one sanctioned skip
(DESIGN.md).

Usage:
    python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import numpy as np


def _build_step_fn(model, shape, mesh, microbatches: int = 0,
                   step_cfg_overrides: Optional[Dict] = None):
    import jax
    from repro.distributed import TrainStepConfig, make_train_step, \
        make_serve_fns
    from repro.distributed.train import recommended_microbatches
    from repro.optim import AdamWConfig

    overrides = dict(step_cfg_overrides or {})
    if shape.kind == "train":
        mb = microbatches or recommended_microbatches(model.cfg, shape,
                                                      mesh)
        step_cfg = TrainStepConfig(microbatches=mb, **overrides)
        return make_train_step(model, AdamWConfig(), mesh=mesh,
                               step_cfg=step_cfg), mb
    step_cfg = TrainStepConfig(**overrides)
    prefill, decode = make_serve_fns(model, mesh=mesh, step_cfg=step_cfg)
    if shape.kind == "prefill":
        return prefill, 1
    return decode, 1


def _parse_variant(variant: str, cfg):
    """Variant string -> (cfg, rules overrides, microbatch override).

    Components joined by '+': ``sp`` (sequence-parallel residuals),
    ``kvseq`` (split-KV decode cache), ``mb<k>`` (microbatch override),
    ``padE<n>`` (pad MoE experts to n).
    """
    import dataclasses as _dc
    from repro.distributed.sharding import (ACT_RULES, ACT_RULES_SP,
                                            CACHE_RULES,
                                            CACHE_RULES_SEQSHARD)
    act_rules, cache_rules, mb = ACT_RULES, CACHE_RULES, 0
    for part in [p for p in (variant or "").split("+") if p]:
        if part == "baseline":
            continue
        elif part == "sp":
            act_rules = ACT_RULES_SP
        elif part == "kvseq":
            cache_rules = CACHE_RULES_SEQSHARD
        elif part.startswith("mb"):
            mb = int(part[2:])
        elif part.startswith("padE"):
            cfg = _dc.replace(cfg, pad_experts_to=int(part[4:]))
        elif part == "moegrp":
            cfg = _dc.replace(cfg, moe_dispatch="grouped")
        elif part.startswith("kvrep"):
            cfg = _dc.replace(cfg, kv_repeat=int(part[5:]))
        elif part == "rdots":
            cfg = _dc.replace(cfg, remat="dots")
        else:
            raise ValueError(f"unknown variant component {part!r}")
    return cfg, act_rules, cache_rules, mb


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool,
                out_dir: str = "experiments/dryrun",
                donate: bool = True,
                keep_hlo: bool = False,
                variant: str = "baseline") -> Dict:
    import jax
    from repro.configs import get_config
    from repro.core.hlo import collective_stats, module_mix, parse_hlo
    from repro.launch.mesh import (ici_links, make_production_mesh,
                                   mesh_num_chips)
    from repro.launch.specs import cell_inputs, tree_bytes_per_device
    from repro.models import build_model
    from repro.models.config import LM_SHAPES

    cfg = get_config(arch)
    cfg, act_rules, cache_rules, mb_override = _parse_variant(variant, cfg)
    model = build_model(cfg)
    shape = LM_SHAPES[shape_name]
    mesh_tag = "pod512" if multi_pod else "pod256"
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                 "kind": shape.kind, "variant": variant}

    ok, why = model.supports_shape(shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_num_chips(mesh)
    args = cell_inputs(model, shape, mesh, act_rules=act_rules,
                       cache_rules=cache_rules)
    step_fn, microbatches = _build_step_fn(
        model, shape, mesh, microbatches=mb_override,
        step_cfg_overrides={"act_rules": act_rules,
                            "cache_rules": cache_rules})
    rec["microbatches"] = microbatches
    donate_args = ((0, 1) if shape.kind == "train"
                   else (1,) if shape.kind == "decode" else ())
    with mesh:
        lowered = jax.jit(step_fn,
                          donate_argnums=donate_args if donate else ()
                          ).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        cost = dict(compiled.cost_analysis() or {})
        try:
            mem = compiled.memory_analysis()
            mem_d = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes",
                                          None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None),
            }
        except Exception as e:
            mem_d = {"error": str(e)}
        text = compiled.as_text()
        mod = parse_hlo(text)
        coll = collective_stats(mod)      # loop-aware (trip-count x)
        mix = module_mix(mod)             # loop-aware per-device mix

    # analytic per-device residency (params/opt/cache/batch)
    arg_bytes_dev = tree_bytes_per_device(args, mesh)
    rec.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        # loop-aware statics (preferred; XLA cost_analysis counts while
        # bodies once — recorded below for reference only)
        flops=mix.mxu_flops,
        vpu_flops=mix.vpu_flops,
        transcendentals=mix.trans_flops,
        bytes_accessed=mix.hbm_bytes,
        unknown_trip_loops=mix.unknown_trip_loops,
        xla_cost_analysis={
            "flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes accessed": float(cost.get("bytes accessed", 0.0)
                                    or 0.0),
            "transcendentals": float(cost.get("transcendentals", 0.0)
                                     or 0.0),
        },
        collective_bytes=coll.total_bytes,
        collectives_by_kind={k: float(v)
                             for k, v in coll.by_kind_bytes.items()},
        collective_counts={k: float(v)
                           for k, v in coll.by_kind_count.items()},
        arg_bytes_per_device=int(arg_bytes_dev),
        memory_analysis=mem_d,
        model_flops=model.model_flops(shape),
        n_params=cfg.num_params(),
        n_active_params=cfg.num_active_params(),
        ici_links=ici_links(mesh),
        hlo_instructions=text.count("\n"),
    )
    if keep_hlo:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"{arch}_{shape_name}_{mesh_tag}.hlo.txt"),
                "w") as f:
            f.write(text)
    return rec


def save_record(rec: Dict, out_dir: str = "experiments/dryrun"):
    os.makedirs(out_dir, exist_ok=True)
    suffix = ("" if rec.get("variant", "baseline") == "baseline"
              else "_" + rec["variant"].replace("+", "_"))
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{suffix}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out-dir", type=str, default="experiments/dryrun")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", type=str, default="baseline",
                    help="sp|kvseq|mb<k>|padE<n> joined by '+'")
    args = ap.parse_args()

    from repro.configs import ARCHS
    from repro.models.config import LM_SHAPES

    archs = [args.arch] if args.arch else ARCHS
    shapes = [args.shape] if args.shape else list(LM_SHAPES)
    pods = []
    if not args.multi_pod_only:
        pods.append(False)
    if args.multi_pod or args.all or args.multi_pod_only:
        if not args.single_pod_only:
            pods.append(True)

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = "pod512" if mp else "pod256"
                path = os.path.join(args.out_dir,
                                    f"{arch}_{shape}_{tag}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] {arch} x {shape} x {tag}: cached")
                    continue
                try:
                    rec = dryrun_cell(arch, shape, mp,
                                      out_dir=args.out_dir,
                                      keep_hlo=args.keep_hlo,
                                      variant=args.variant)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh": tag,
                           "status": "error", "error": str(e),
                           "traceback": traceback.format_exc()}
                    n_fail += 1
                save_record(rec, args.out_dir)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f"flops/dev={rec['flops']:.3e} "
                             f"coll={rec['collective_bytes']:.3e}B "
                             f"compile={rec['compile_s']}s")
                elif status == "error":
                    extra = rec["error"][:160]
                else:
                    extra = rec.get("reason", "")
                print(f"[dryrun] {arch} x {shape} x {tag}: "
                      f"{status} {extra}", flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} dry-run cells failed")
    print("[dryrun] all requested cells passed")


if __name__ == "__main__":
    main()
