"""Continuum telemetry: registry/tracer/audit units, co-sim
instrumentation, and the non-perturbation contract (control
fingerprints bit-identical with telemetry on or off)."""
import json

import numpy as np
import pytest

from repro.core import random_instance, solve_decomposed
from repro.sim.scenarios import SCENARIOS, run_scenario
from repro.telemetry import (DecisionAudit, MetricsRegistry, SpanTracer,
                             Telemetry, maybe)


# -- registry ---------------------------------------------------------------

def test_registry_basics():
    m = MetricsRegistry()
    m.counter("a.b").inc()
    m.counter("a.b").inc(2.5)
    assert m.value("a.b") == 3.5
    m.gauge("g").set(7)
    assert m.value("g") == 7.0
    assert m.value("missing", default=-1.0) == -1.0
    h = m.histogram("lat", edges=(1.0, 10.0, 100.0))
    h.observe(0.5)
    h.observe_array(np.array([5.0, 50.0, 500.0]))
    assert h.count == 4
    assert h.counts.tolist() == [1, 1, 1, 1]
    assert h.min == 0.5 and h.max == 500.0
    snap = m.snapshot()
    assert snap["counters"]["a.b"] == 3.5
    assert snap["histograms"]["lat"]["count"] == 4
    with pytest.raises(TypeError):
        m.gauge("a.b")                    # name already a counter


def test_histogram_quantile_and_edges():
    m = MetricsRegistry()
    h = m.histogram("q", edges=(10.0, 20.0, 30.0))
    h.observe_array(np.linspace(0.0, 30.0, 300))
    q50 = h.quantile(50)
    assert 10.0 <= q50 <= 20.0
    assert h.quantile(0) <= h.quantile(50) <= h.quantile(100)
    with pytest.raises(ValueError):
        m.histogram("bad", edges=(5.0, 5.0))      # non-ascending


def test_prometheus_export():
    m = MetricsRegistry()
    m.counter("requests.total").inc(3)
    m.gauge("reconfig.budget_spent").set(12.5)
    m.histogram("lat", edges=(1.0, 2.0)).observe_array(
        np.array([0.5, 1.5, 9.0]))
    text = m.to_prometheus()
    assert "repro_requests_total 3" in text
    assert "repro_reconfig_budget_spent 12.5" in text
    assert 'repro_lat_bucket{le="+Inf"} 3' in text    # cumulative
    assert "repro_lat_count 3" in text


# -- tracer -----------------------------------------------------------------

def test_tracer_spans_and_exports(tmp_path):
    tr = SpanTracer()
    tr.open(("round", 0), "round 0", 10.0, cat="round", tid=1, sid=0)
    tr.open(("round", 1), "round 1", 12.0, cat="round", tid=2)
    tr.close(("round", 0), 30.0)
    tr.close(("round", 1), 35.0)
    tr.close(("round", 99), 40.0)                 # unknown key: ignored
    tr.complete("swap", 50.0, 10.0, cat="reconfig", trigger="drift")
    tr.instant("failure", 60.0, cat="fault")
    with tr.wall("solve_decomposed.polish", cat="solver") as sp:
        pass
    assert sp.dur >= 0.0
    assert len(tr.spans) == 4 and len(tr.instants) == 1
    d = tr.durations("solve_decomposed.")
    assert set(d) == {"polish"} and d["polish"] == sp.dur
    assert [s.name for s in tr.by_cat("round")] == ["round 0", "round 1"]

    path = tmp_path / "trace.json"
    tr.write_chrome(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert [e for e in evs if e["ph"] == "M"]     # process metadata
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} >= {"round 0", "swap"}
    sw = next(e for e in xs if e["name"] == "swap")
    assert sw["ts"] == 50.0 * 1e6 and sw["dur"] == 10.0 * 1e6
    assert sw["args"]["trigger"] == "drift"
    jsonl = tmp_path / "trace.jsonl"
    tr.write_jsonl(str(jsonl))
    lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert len(lines) == 5
    assert {l["kind"] for l in lines} == {"span", "instant"}


def test_audit_log():
    a = DecisionAudit()
    a.record(5.0, "deployment_swap", "drift alarm", "applied",
             evidence={"mse": 0.2}, cost=10.0, charged=True)
    a.record(9.0, "deployment_swap", "windowed_p95_breach", "deferred",
             cost=10.0)
    with pytest.raises(ValueError):
        a.record(1.0, "x", "y", "not-an-outcome")
    assert len(a) == 2
    assert a.counts()["applied"] == 1 and a.counts()["deferred"] == 1
    assert [r.trigger for r in a.by_action("deployment_swap")] == \
        ["drift alarm", "windowed_p95_breach"]


def test_maybe_resolution():
    assert maybe(None) is None
    tel = Telemetry()
    assert maybe(tel) is tel
    assert maybe(Telemetry(enabled=False)) is None


# -- co-sim instrumentation -------------------------------------------------

def test_cosim_spans_and_metrics():
    tel = Telemetry()
    res = run_scenario(SCENARIOS["churn"](), "budgeted", seed=0,
                       duration_s=60.0, telemetry=tel)
    cats = {sp.cat for sp in tel.tracer.spans}
    assert {"round", "epoch", "aggregation"} <= cats
    m = tel.metrics
    assert m.value("training.rounds_completed") == res.rounds_completed
    assert m.value("requests.total") == res.n_requests
    h = m.get("request.latency_ms")
    assert h.count == res.n_requests
    # bucket-approximated p95 bounds the exact percentile
    exact = res.log.percentile_latency(95)
    lo = max((e for e in h.edges if e <= exact), default=0.0)
    hi = min((e for e in h.edges if e >= exact), default=h.max)
    assert lo - 1e-9 <= h.quantile(95) <= hi + 1e-9


def test_audit_covers_every_swap_and_budget_metrics():
    tel = Telemetry()
    res = run_scenario(SCENARIOS["churn"](), "budgeted", seed=0,
                       duration_s=120.0, telemetry=tel)
    swaps = tel.audit.by_action("deployment_swap")
    done = [r for r in swaps if r.outcome in ("applied", "forced")]
    assert len(done) == res.reclusters > 0
    for rec in done:
        assert rec.trigger            # every swap names its trigger
        assert rec.cost > 0.0
    m = tel.metrics
    assert m.value("reconfig.applied") + m.value("reconfig.forced") == \
        res.reclusters
    assert m.value("reconfig.deferred") == res.budget_vetoes
    assert m.value("reconfig.budget_spent") == pytest.approx(
        res.budget_spent)
    assert m.value("reconfig.cost_spent") == pytest.approx(
        res.budget_spent)


@pytest.mark.parametrize("scenario,policy,engine", [
    ("straggler", "reactive", "batched"),
    ("mobility", "budgeted", "batched"),
    ("multi_tenant", "static", "batched"),
    ("churn", "budgeted", "batched"),
    ("churn", "reactive", "heap"),
])
def test_telemetry_does_not_perturb(scenario, policy, engine):
    kw = dict(policy=policy, seed=0, duration_s=60.0, engine=engine)
    base = run_scenario(SCENARIOS[scenario](), **kw)
    tel = Telemetry()
    inst = run_scenario(SCENARIOS[scenario](), telemetry=tel, **kw)
    assert inst.fingerprint() == base.fingerprint()
    assert inst.control_fingerprint() == base.control_fingerprint()
    assert np.array_equal(inst.log.latency_ms, base.log.latency_ms)
    assert np.array_equal(inst.log.t, base.log.t)
    assert np.array_equal(inst.log.tier, base.log.tier)
    assert inst.actions == base.actions
    if policy != "static":
        assert len(tel.tracer.spans) > 0   # it did record something


def test_disabled_telemetry_is_free():
    from repro.sim.cosim import CoSim, CoSimConfig
    from repro.sim.scenarios import hot_zone_topology
    topo, loc, lam, r = hot_zone_topology(seed=0)
    off = Telemetry(enabled=False)
    cosim = CoSim(topo, CoSimConfig(duration_s=10.0, telemetry=off))
    assert cosim.tel is None               # resolved once, never checked
    assert cosim.proc._tel is None
    cosim2 = CoSim(topo, CoSimConfig(duration_s=10.0))
    assert cosim2.tel is None
    assert len(off.tracer.spans) == 0 and len(off.audit) == 0


# -- solver phase spans -----------------------------------------------------

def test_solver_phase_view_matches_tracer():
    inst = random_instance(300, 12, seed=0)
    tel = Telemetry()
    sol = solve_decomposed(inst, telemetry=tel)
    d = tel.tracer.durations("solve_decomposed.")
    assert set(d) == {"partition", "subsolve", "stitch", "polish"}
    for k, v in d.items():
        assert sol.meta["phase_s"][f"{k}_s"] == pytest.approx(v)
    sub = next(sp for sp in tel.tracer.spans
               if sp.name == "solve_decomposed.subsolve")
    assert sub.args["regions"] == sol.meta["regions"]
    assert all(sp.domain == "wall" for sp in tel.tracer.by_cat("solver"))


# -- benchmark registry round-trip ------------------------------------------

def test_bench_emit_registry_roundtrip(tmp_path, capsys):
    from benchmarks import common
    common.emit("telemetry_test_row", 123.4,
                "requests_per_s=1000;engine=batched")
    capsys.readouterr()
    rows = common.rows_from_registry()
    row = rows["telemetry_test_row"]
    assert row["us_per_call"] == pytest.approx(123.4)
    assert row["requests_per_s"] == 1000.0
    assert row["engine"] == "batched"
    path = tmp_path / "bench.json"
    common.write_json(str(path))
    data = json.loads(path.read_text())
    assert data["telemetry_test_row"] == row
    assert "repro_bench:telemetry_test_row:us_per_call" not in \
        common.TELEMETRY.to_prometheus()       # colons sanitized
    assert "repro_bench_telemetry_test_row_us_per_call 123.4" in \
        common.TELEMETRY.to_prometheus()


def test_telemetry_snapshot_and_facade(tmp_path):
    tel = Telemetry()
    tel.metrics.counter("c").inc()
    tel.tracer.complete("s", 0.0, 1.0)
    tel.audit.record(0.0, "a", "trig", "noted")
    snap = tel.snapshot()
    assert snap["enabled"] and snap["spans"] == 1
    assert snap["audit"]["noted"] == 1
    p = tmp_path / "snap.json"
    tel.write_snapshot(str(p))
    assert json.loads(p.read_text())["metrics"]["counters"]["c"] == 1.0
    assert "repro_c 1" in tel.to_prometheus()


# -- wall-span nesting and the profiler hook ---------------------------------

def test_wall_spans_record_their_parent(tmp_path):
    tr = SpanTracer()
    with tr.wall("round", cat="hfl"):
        with tr.wall("data", cat="hfl"):
            with tr.wall("data.windows", cat="hfl"):
                pass
        with tr.wall("train", cat="hfl"):
            pass
    with tr.wall("after"):
        pass
    tr.complete("swap", 1.0, 2.0)
    parent = {sp.name: sp.parent for sp in tr.spans}
    assert parent == {"data.windows": "data", "data": "round",
                      "train": "round", "round": None, "after": None,
                      "swap": None}
    # a child closes first and lies inside its parent
    by = {sp.name: sp for sp in tr.spans}
    assert [sp.name for sp in tr.spans][:3] == ["data.windows", "data",
                                                "train"]
    for child in ("data.windows", "data", "train"):
        c, p = by[child], by[parent[child]]
        assert p.t0 <= c.t0 and c.t0 + c.dur <= p.t0 + p.dur
    xs = {e["name"]: e for e in tr.to_chrome() if e["ph"] == "X"}
    assert xs["data.windows"]["args"] == {"parent": "data"}
    assert "parent" not in xs["round"]["args"]
    path = tmp_path / "spans.jsonl"
    tr.write_jsonl(str(path))
    lines = {d["name"]: d for d in map(json.loads,
                                       path.read_text().splitlines())}
    assert lines["data"]["parent"] == "round"
    assert lines["round"]["parent"] is None


def test_wall_span_stack_unwinds_on_error():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.wall("outer"):
            with tr.wall("inner"):
                raise RuntimeError("boom")
    with tr.wall("next"):
        pass
    assert [(sp.name, sp.parent) for sp in tr.spans] == [
        ("inner", "outer"), ("outer", None), ("next", None)]


def test_annotate_hook_wraps_every_wall_span():
    from contextlib import contextmanager

    log = []

    @contextmanager
    def annotate(name, **args):
        log.append(("enter", name, args))
        yield
        log.append(("exit", name))

    tel = Telemetry(annotate=annotate)
    with tel.tracer.wall("hfl.round", cat="hfl", round=0, tier="cluster"):
        log.append(("body",))
        with tel.tracer.wall("hfl.data", cat="hfl"):
            pass
    tel.tracer.complete("sim-span", 0.0, 1.0)    # sim spans: no hook
    assert log == [("enter", "hfl.round", {"round": 0, "tier": "cluster"}),
                   ("body",), ("enter", "hfl.data", {}),
                   ("exit", "hfl.data"), ("exit", "hfl.round")]
    assert [sp.name for sp in tel.tracer.spans] == ["hfl.data", "hfl.round",
                                                    "sim-span"]
