"""The reduction of a profiler trace to device metrics, checked on a
small trace recorded on a TPU v5e (``bench/testdata/record_trace.py``)
and on made-up intervals."""
import sys
from collections import defaultdict
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import trace  # noqa: E402

FIXTURE = ROOT / "bench" / "testdata" / "small.xplane.pb"
LABELS = ("work", "idle-wait")


def test_busy_is_the_union_of_intervals_not_their_sum():
    tr = trace.Trace()
    dev = "/device:TPU:0"
    tr.ops[dev] = [("%a = f32[] add(x)", 100, 200),
                   ("%b = f32[] copy(x)", 150, 300),     # overlaps a
                   ("%c = f32[] dot(x)", 120, 180),      # inside a
                   ("%a = f32[] add(x)", 500, 600)]
    tr.modules[dev] = [("jit_step(123)", 90, 310), ("jit_step(123)",
                                                    480, 610)]
    tr.host = [("window", 0, 1000), ("decode", 0, 400),
               ("idle-wait", 400, 1000)]
    s = trace.summarize(tr)
    assert s["busy_s"] == pytest.approx(300e-9)        # 100-300, 500-600
    assert sum(s["op_s"].values()) == pytest.approx(410e-9)
    assert s["op_s"]["a"] == pytest.approx(200e-9)
    assert s["program_s"] == {"jit_step": pytest.approx(350e-9)}
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["idle_by_label_s"]["decode"] == pytest.approx(100e-9)
    assert s["idle_by_label_s"]["idle-wait"] == pytest.approx(600e-9)
    assert s["longest_gaps"][0] == ("idle-wait", pytest.approx(400e-9))


def test_recorded_trace():
    from jax.profiler import ProfileData

    tr = trace.load(str(FIXTURE), LABELS)
    # the recording has no 'window' annotation: take the span of its
    # host annotations
    lo = min(s for _, s, _ in tr.host)
    hi = max(e for _, _, e in tr.host)
    tr.host.append((trace.WINDOW, lo, hi))
    s = trace.summarize(tr)
    # an independent reading of the same file
    ops, modules = [], defaultdict(float)
    for plane in ProfileData.from_file(str(FIXTURE)).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            for e in line.events:
                s0, s1 = max(e.start_ns, lo), min(e.start_ns + e.duration_ns,
                                                   hi)
                if s1 <= s0:
                    continue
                if line.name == "XLA Ops":
                    ops.append((s0, s1))
                elif line.name == "XLA Modules":
                    modules[e.name.split("(")[0]] += s1 - s0
    ops.sort()
    busy, reach = 0.0, float("-inf")
    for s0, s1 in ops:
        if s1 > reach:
            busy += s1 - max(s0, reach)
            reach = s1
    assert s["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert s["busy_s"] <= sum(b - a for a, b in ops) * 1e-9
    # every op runs inside its program
    assert s["busy_s"] <= sum(s["program_s"].values())
    assert s["program_s"] == pytest.approx(
        {k: v * 1e-9 for k, v in modules.items()}, rel=1e-9)
    assert s["program_s"] and all(v > 0 for v in s["program_s"].values())
    # the sleeps of 10 ms inside 'idle-wait' are the longest gaps
    longest = s["longest_gaps"][:3]
    assert [g[0] for g in longest] == ["idle-wait"] * 3
    assert all(0.008 < g[1] < 0.05 for g in longest)
    assert s["idle_by_label_s"]["idle-wait"] > 0.03
    assert 0 < s["busy_s"] < s["window_s"] < 1.0
    b = trace.breakdown(s)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
