"""From a profiler trace to the numbers the per-layer readers take.

`extract` reads the `.xplane.pb` the JAX profiler wrote (on the chip)
into plain lists; `reduce` works on those lists alone, so it can be
checked on a small recorded trace (tests/data).  Times are the device
plane's nanoseconds.

- modules: executions on the device's "XLA Modules" line; the runner's
  jitted `bench_prefill` / `bench_decode` name them.
- ops: the "XLA Ops" line.  A loop op spans the ops of its body, so busy
  time is a union of intervals and an op's own time excludes the ops
  nested inside it.  A Pallas kernel is the custom call named after its
  `<name>_pallas` function; its operand and result shapes are kept so
  that `work/<kernel_id>.py` can count the call's required work.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

KINDS = ("prefill", "decode")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|f8e4m3fn|f8e5m2|"
                    r"bf16|f16|f32|f64)\[([0-9,]*)\]")
_INSTR = re.compile(r"^%?([A-Za-z0-9_.\-]+) = ")
_PALLAS = re.compile(r"^([A-Za-z0-9_]+_pallas)\.\d+$")


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def parse_op(text: str) -> Dict:
    """Instruction name, and for a Pallas kernel its function and shapes."""
    m = _INSTR.match(text)
    instr = m.group(1) if m else text[:80]
    op = {"instr": instr}
    k = _PALLAS.match(instr)
    if k and "custom-call(" in text:
        head, _, args = text.partition("custom-call(")
        op["kernel_fn"] = k.group(1)
        op["out"] = shapes(head.split(" = ", 1)[-1])
        op["in"] = shapes(args.split("custom_call_target", 1)[0])
    return op


def extract(trace_dir: str, device: str = "/device:TPU:0") -> Dict:
    """Modules and ops of one device in the newest trace under a dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ev = {"modules": [], "ops": []}
    for plane in pd.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    ev["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    ev["ops"] += [dict(parse_op(e.name), start=e.start_ns,
                                       dur=e.duration_ns)
                                  for e in line.events]
    return ev


# ------------------------------------------------------------- reduction

def union(intervals) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def covered(merged, a: float, b: float) -> float:
    """Length of [a, b] that the merged intervals cover."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)


def module_kind(name: str) -> Optional[str]:
    for kind in KINDS:
        if f"bench_{kind}" in name:
            return kind
    return None


def self_times(ops: List[Dict]) -> List[float]:
    """Each op's duration less the ops nested inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["start"],
                                                   -ops[i]["dur"]))
    own = [float(op["dur"]) for op in ops]
    stack: List[int] = []
    for i in order:
        s = ops[i]["start"]
        while stack and ops[stack[-1]]["start"] + ops[stack[-1]]["dur"] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i]["dur"]
        stack.append(i)
    return own


def reduce(ev: Dict, kernel_ids: Dict[str, str],
           work: Dict[str, Callable], peaks: Optional[Dict]) -> Dict:
    """Busy and idle time, module times, kernel time against its required
    time, the top device ops and the idle gaps by what the device waited
    for.  Only the device's clock is used: the host's spans sit about 2 ms
    off it in the traces read so far, a large share of a decode step."""
    mods = sorted(([module_kind(n), s, s + d] for n, s, d in ev["modules"]),
                  key=lambda x: x[1])
    ops = ev["ops"]
    busy = union([op["start"], op["start"] + op["dur"]] for op in ops)
    w0, w1 = mods[0][1], mods[-1][2]

    # a program's period runs from the end of the program before it (the
    # host's readback, sampling and dispatch) to its own end; the first
    # program of the trace has no period
    periods = {k: [] for k in KINDS}
    for prev, (kind, s, e) in zip(mods, mods[1:]):
        if kind in periods:
            periods[kind].append((prev[2], e))
    idle = {k: (1.0 - sum(covered(busy, a, b) for a, b in v)
                / sum(b - a for a, b in v)) if v else None
            for k, v in periods.items()}

    own = self_times(ops)
    kernels: Dict[str, Dict[str, List[float]]] = {k: {} for k in KINDS}
    top: Dict[str, float] = {}
    starts = [m[1] for m in mods]
    for op, t in zip(ops, own):
        j = bisect.bisect_right(starts, op["start"]) - 1
        kind = mods[j][0] if j >= 0 and op["start"] <= mods[j][2] else None
        kid = kernel_ids.get(op.get("kernel_fn", ""))
        key = f"{kind or 'other'}:{kid or op['instr']}"
        top[key] = top.get(key, 0.0) + t
        if kid and kind and peaks:
            flops, nb = work[kid](op["out"], op["in"])
            req = max(flops / peaks["bf16_flops_per_s"],
                      nb / peaks["hbm_bytes_per_s"])
            acc = kernels[kind].setdefault(kid, [0.0, 0.0, 0])
            acc[0] += op["dur"] * 1e-9
            acc[1] += req
            acc[2] += 1

    # idle gaps: before a program (waiting on the host) or inside one
    gaps: Dict[str, float] = {}
    prev = w0
    for a, b in busy + [[w1, w1]]:
        a, b = max(a, w0), min(b, w1)
        if a > prev:
            j = bisect.bisect_right(starts, prev) - 1
            inside = j >= 0 and a <= mods[j][2]
            nxt = mods[min(j + 1, len(mods) - 1)]
            label = (f"inside {mods[j][0] or 'other'}" if inside
                     else f"before {nxt[0] or 'other'}")
            gaps[label] = gaps.get(label, 0.0) + (a - prev) * 1e-9
        prev = max(prev, b)
    return {
        "busy_s": covered(busy, w0, w1) * 1e-9, "window_s": (w1 - w0) * 1e-9,
        "idle_share": idle,
        "module_s": {k: [(e - s) * 1e-9 for kind, s, e in mods if kind == k]
                     for k in KINDS},
        "kernels": kernels,
        "device_ops": sorted(([k, v * 1e-9] for k, v in top.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda x: -x[1])[:10],
    }
