"""Resolve a cell by name, guard the device, run it, print its result line.

Data (BENCHMARK.json and the configs/traffic/limits files) is read from
a root directory, the checkout by default; code (runners, generators,
references, work counts, metric readers) is found by name under this
package.  A new cell, configuration, traffic mix or metric is then a new
file and a new entry, with no edit to a file that is there.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent

_MODULES: Dict[Path, Any] = {}


def load_module(path: Path):
    """Import a file by path (names may hold '-' and '.'); one module per path."""
    path = Path(path).resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise FileNotFoundError(f"no such benchmark file: {path}")
        spec = importlib.util.spec_from_file_location(
            "bench_" + "_".join(path.relative_to(PKG).with_suffix("").parts)
            .replace("-", "_").replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def code(kind: str, name: str):
    """The module `<kind>/<name>.py` of this package."""
    return load_module(PKG / kind / f"{name}.py")


def read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"[bench] no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclasses.dataclass
class Cell:
    """One workload with everything found by its names."""
    name: str
    chips: int
    config: Dict            # configs/<config>.json
    traffic: Dict           # traffic/<traffic>.json
    limits: Dict            # limits/<workload>.json
    end_to_end: List[Dict]  # BENCHMARK.json metrics that this cell reports
    per_layer: List[Dict]

    @property
    def reference(self):
        return code("reference", self.config["reference"])

    @property
    def generator(self):
        return code("traffic", self.traffic["generator"])

    @property
    def runner(self):
        return code("runners", self.traffic["runner"])

    @property
    def model_work(self):
        return code("work", self.config["work"])


def resolve(workload: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    spec = read_json(root / "BENCHMARK.json")
    w = _find(spec["workloads"], workload, "workload")
    data = root / "bench"
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=read_json(data / "configs" / f"{w['config']}.json"),
        traffic=read_json(data / "traffic" / f"{w['traffic']}.json"),
        limits=read_json(data / "limits" / f"{workload}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def peaks_for(kind: str) -> Dict:
    table = read_json(PKG / "peaks.json")["devices"]
    if kind not in table:
        raise ValueError(f"device_kind {kind!r} is not in bench/peaks.json; "
                         f"known: {sorted(table)}")
    return table[kind]


def kernel_names() -> Dict[str, str]:
    """Pallas function name -> kernel id, from every work/<kernel_id>.py."""
    out = {}
    for path in sorted((PKG / "work").glob("*.py")):
        mod = load_module(path)
        for fn in getattr(mod, "PALLAS_NAMES", ()):
            out[fn] = path.stem
    return out


def device_guard(chips: int) -> Dict:
    """The chips JAX sees, as the result line names them; raises
    SystemExit (non-zero, nothing printed on stdout) without a TPU or
    with fewer chips than the cell asks for, or on an unknown kind."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"[bench] JAX found no TPU (platform "
                         f"{dev['platform']!r}); no result")
    if dev["count"] < chips:
        raise SystemExit(f"[bench] the cell needs {chips} chips, JAX "
                         f"sees {dev['count']}; no result")
    try:
        peaks_for(dev["kind"])
    except ValueError as e:
        raise SystemExit(f"[bench] {e}; no result")
    return dev


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at one fixed directory inside
    the checkout (the program's own default, also given to it through
    JAX_COMPILATION_CACHE_DIR), holding every program however small."""
    import os
    path = str(Path(root) / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t0: float, device: Dict) -> Dict:
    """Run the cell's runner and turn its record into the result line."""
    rec = cell.runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                          t0=t0, device=device)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = code("metrics", m["name"]).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=rec["memory_peak_bytes"])
    if trace:
        dev.update(busy_s=rec["trace"]["busy_s"],
                   window_s=rec["trace"]["window_s"])
    checks = rec["checks"]
    out = {"correct": bool(checks) and all(c["ok"] for c in checks),
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": dev}
    if trace:
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def print_result(out: Dict) -> None:
    for name, c in out["checks"].items():
        print(f"[bench] check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"[bench] correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv: Optional[List[str]] = None, t0: float = 0.0) -> int:
    ap = argparse.ArgumentParser(description="run one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve(args.workload)
    enable_compile_cache()
    device = device_guard(cell.chips)
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t0=t0, device=device)
    print_result(out)
    return 0
