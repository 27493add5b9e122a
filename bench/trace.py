"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

* busy time: the union of the intervals in which an operation ran on a
  device (line ``XLA Ops``), clipped to the traced window; ops that
  overlap (async copies, a loop and its body) count once;
* device time per jitted program (line ``XLA Modules``) and per op;
* idle gaps: the stretches of the window in which no op ran, each
  labelled by the innermost harness annotation
  (``jax.profiler.TraceAnnotation``) open on the host at its midpoint.

The window is the host annotation named ``window`` that the harness
opens around what it traces.  Device timestamps are on the host's clock
as the profiler aligns them; they can lag it by about a millisecond, so a
gap shorter than that may carry its neighbour's label.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

WINDOW = "window"
Interval = Tuple[float, float]

_HASH = re.compile(r"\(\d+\)$")


@dataclass
class Trace:
    # per device: [(name, start_ns, end_ns)]
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    # host annotations [(name, start_ns, end_ns)]
    host: List[Tuple[str, float, float]] = field(default_factory=list)


def program_name(module: str) -> str:
    """``jit__decode_impl(1234)`` -> ``jit__decode_impl``."""
    return _HASH.sub("", module.strip())


def op_name(op: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, host_names: Sequence[str]) -> Trace:
    """Read the device planes and the host annotations named in
    ``host_names`` (plus ``window``) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    wanted = set(host_names) | {WINDOW}
    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dst = tr.modules.setdefault(plane.name, [])
                elif line.name == "XLA Ops":
                    dst = tr.ops.setdefault(plane.name, [])
                else:
                    continue
                for e in line.events:
                    s = e.start_ns
                    dst.append((e.name, s, s + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        s = e.start_ns
                        tr.host.append((e.name, s, s + e.duration_ns))
    return tr


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(tr: Trace) -> Interval:
    spans = [(s, e) for n, s, e in tr.host if n == WINDOW]
    if not spans:
        raise ValueError("the trace holds no 'window' annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def label_at(tr: Trace, t: float) -> str:
    """The innermost (shortest) harness annotation open at ``t``."""
    best, width = "other", float("inf")
    for name, s, e in tr.host:
        if name != WINDOW and s <= t < e and e - s < width:
            best, width = name, e - s
    return best


def summarize(tr: Trace, top: int = 10) -> dict:
    """Busy and idle seconds, device time per program and op, and the
    longest idle gaps, over the traced window (averaged over devices)."""
    lo, hi = window_of(tr)
    devices = sorted(tr.ops) or sorted(tr.modules)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_ns, gaps = 0.0, []
    per_program: Dict[str, float] = defaultdict(float)
    per_op: Dict[str, float] = defaultdict(float)
    for dev in devices:
        ops = [(s, e) for _, s, e in tr.ops.get(dev, [])]
        busy = union(clip(ops, lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for k in range(0, len(edges), 2):
            if edges[k + 1] > edges[k]:
                gaps.append((edges[k], edges[k + 1]))
        for name, s, e in tr.modules.get(dev, []):
            for cs, ce in clip([(s, e)], lo, hi):
                per_program[program_name(name)] += (ce - cs)
        for name, s, e in tr.ops.get(dev, []):
            for cs, ce in clip([(s, e)], lo, hi):
                per_op[op_name(name)] += (ce - cs)
    n = len(devices)
    by_label: Dict[str, float] = defaultdict(float)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    for s, e in gaps:
        by_label[label_at(tr, 0.5 * (s + e))] += e - s
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns / n * 1e-9,
        "devices": n,
        "program_s": {k: v / n * 1e-9 for k, v in per_program.items()},
        "op_s": {k: v / n * 1e-9 for k, v in per_op.items()},
        "idle_by_label_s": {k: v / n * 1e-9 for k, v in by_label.items()},
        "longest_gaps": [(label_at(tr, 0.5 * (s + e)), (e - s) * 1e-9)
                         for s, e in longest],
    }


def breakdown(summary: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most
    time, and the longest idle gaps by what the host was doing."""
    ops = sorted(summary["op_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary["longest_gaps"][:top]]}


def options():
    """Profiler options: device ops and the harness's own annotations;
    no Python tracer (it would record every call of the harness)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts
