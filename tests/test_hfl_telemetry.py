"""Telemetry inside ``ContinualHFL.run_rounds``: the span tree of each
round, its counters, the non-perturbation contract on the HFL path, and
the spans' copies in a profiler trace."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.topology import ClusterTopology
from repro.data.traffic import generate, select_fl_sensors
from repro.fl.hierarchy import HFL_SPANS, ContinualHFL, HFLRunConfig
from repro.telemetry import Telemetry

ROOT = Path(__file__).resolve().parents[1]
ROUNDS = 2
CHILDREN = {"hfl.data": "hfl.round", "hfl.data.windows": "hfl.data",
            "hfl.data.upload": "hfl.data", "hfl.train": "hfl.round",
            "hfl.aggregate": "hfl.round", "hfl.eval": "hfl.round",
            "hfl.sync": "hfl.round"}


# a GRU round windows sensor readings; an LM's makes tokens instead
GRU_SPANS = tuple(n for n in HFL_SPANS if n != "hfl.data.tokens")


@pytest.fixture(scope="module")
def setting():
    ds = generate(num_days=3, n_sensors=12, seed=0)
    sensors = select_fl_sensors(ds, per_cluster=1, seed=0)     # 4 clients
    n = len(sensors)
    topo = ClusterTopology(assign=np.array([0, 0, 1, 1]), n_devices=n,
                           n_edges=2, lam=np.ones(n), r=np.ones(2), l=2)
    run = HFLRunConfig(rounds=ROUNDS, local_epochs=1, max_batches=2,
                       train_days=1, val_days=1, max_val_windows=32)
    return get_config("gru-traffic"), ds, sensors, topo, run


def _hfl(setting, mode="hier", telemetry=None):
    cfg, ds, sensors, topo, run = setting
    return ContinualHFL(cfg, ds, sensors, topo, run, mode=mode,
                        telemetry=telemetry)


def _tiers(mode, topo):
    if mode == "flat":
        return ["flat"] * ROUNDS
    return ["global" if (t + 1) % topo.l == 0 else "cluster"
            for t in range(ROUNDS)]


@pytest.mark.parametrize("mode", ["hier", "flat"])
def test_each_round_records_the_span_tree(setting, mode):
    tel = Telemetry()
    _hfl(setting, mode, tel).run_rounds()
    spans = tel.tracer.spans
    assert {sp.name for sp in spans} == set(GRU_SPANS)
    assert all(sp.cat == "hfl" and sp.domain == "wall" and sp.dur >= 0
               for sp in spans)
    rounds = [sp for sp in spans if sp.name == "hfl.round"]
    assert [sp.args for sp in rounds] == [
        {"round": t, "tier": tier}
        for t, tier in enumerate(_tiers(mode, setting[3]))]
    assert all(sp.parent is None for sp in rounds)
    # one of each phase a round, each inside its parent of the same round
    for r in rounds:
        inside = [sp for sp in spans if sp.name != "hfl.round"
                  and r.t0 <= sp.t0 and sp.t0 + sp.dur <= r.t0 + r.dur]
        assert sorted(sp.name for sp in inside) == sorted(CHILDREN)
        assert all(sp.parent == CHILDREN[sp.name] for sp in inside)
        data = next(sp for sp in inside if sp.name == "hfl.data")
        for sp in inside:
            if sp.parent == "hfl.data":
                assert data.t0 <= sp.t0
                assert sp.t0 + sp.dur <= data.t0 + data.dur
        # the phases run in order, one after the other
        order = [sp.name for sp in sorted(inside, key=lambda s: s.t0)
                 if sp.parent == "hfl.round"]
        assert order == ["hfl.data", "hfl.train", "hfl.aggregate",
                         "hfl.eval", "hfl.sync"]


def test_counters_count_rounds_and_uploaded_bytes(setting):
    _, _, sensors, _, run = setting
    tel = Telemetry()
    _hfl(setting, "hier", tel).run_rounds()
    m = tel.metrics
    assert m.value("hfl.rounds.cluster") == 1.0
    assert m.value("hfl.rounds.global") == 1.0
    assert m.value("hfl.rounds.flat", default=0.0) == 0.0
    # float32 windows of `history` readings and their targets, for the
    # training windows and the validation windows of every client
    n_train = run.train_days * 288 - run.history
    per_round = len(sensors) * 4 * (n_train + run.max_val_windows) \
        * (run.history + 1)
    assert m.value("hfl.upload_bytes") == ROUNDS * per_round


def test_counters_count_model_uploads_and_trained_readings(setting):
    """A GRU round sends every client's whole model to its aggregator
    and trains on every reading of its windows, each local epoch."""
    _, _, sensors, _, run = setting
    tel = Telemetry()
    hfl = _hfl(setting, "hier", tel)
    hfl.run_rounds()
    m = tel.metrics
    model = sum(np.asarray(x).nbytes for x in jax.tree.leaves(hfl.params))
    assert m.value("hfl.model_upload_bytes") == ROUNDS * model
    batches = min((run.train_days * 288 - run.history) // run.batch_size,
                  run.max_batches)
    assert m.value("hfl.train_tokens") == ROUNDS * len(sensors) \
        * run.local_epochs * batches * run.batch_size * run.history


def test_aggregation_compiles_only_in_the_first_call(setting):
    tel = Telemetry()
    hfl = _hfl(setting, "hier", tel)
    hfl.run_rounds()                     # a cluster round, a global round
    first = tel.metrics.value("hfl.aggregate.compiles")
    assert 0 <= first <= 2
    hfl.run_rounds()
    assert tel.metrics.value("hfl.aggregate.compiles") == first


def test_telemetry_leaves_results_and_params_bit_identical(setting):
    off = _hfl(setting)
    res_off = off.run_rounds()
    on = _hfl(setting, telemetry=Telemetry())
    res_on = on.run_rounds()
    assert res_on.mode == res_off.mode
    np.testing.assert_array_equal(res_on.mse, res_off.mse)
    np.testing.assert_array_equal(res_on.train_loss, res_off.train_loss)
    for a, b in zip(jax.tree.leaves(on.params), jax.tree.leaves(off.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # disabled telemetry records nothing
    tel = Telemetry(enabled=False)
    _hfl(setting, telemetry=tel).run_rounds(1)
    assert not tel.tracer.spans and not tel.metrics.snapshot()["counters"]


def test_profiler_trace_holds_every_forwarded_span(setting, tmp_path):
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import trace

    tel = Telemetry(annotate=jax.profiler.TraceAnnotation)
    hfl = _hfl(setting, telemetry=tel)
    hfl.run_rounds(1)                    # compile outside the trace
    n0 = len(tel.tracer.spans)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("run_rounds"):
            hfl.run_rounds()
    tr = trace.load(trace.find_xplane(str(tmp_path)),
                    ("run_rounds",) + HFL_SPANS)
    got = sorted(name for name, _, _ in tr.host if name.startswith("hfl."))
    want = sorted(sp.name for sp in tel.tracer.spans[n0:])
    assert got == want and len(got) == ROUNDS * len(GRU_SPANS)
    # on the profiler's clock each child lies inside an annotation of its
    # parent, and every span inside the caller's own
    outer = [(s, e) for n, s, e in tr.host if n == "run_rounds"]
    for name, s, e in tr.host:
        if name in CHILDREN:
            assert any(s >= ps and e <= pe for pn, ps, pe in tr.host
                       if pn == CHILDREN[name])
        if name.startswith("hfl."):
            assert any(s >= os_ and e <= oe for os_, oe in outer)
    # a gap inside the windowing is labelled by the innermost span
    name, s, e = next(x for x in tr.host if x[0] == "hfl.data.windows")
    assert trace.label_at(tr, 0.5 * (s + e)) == "hfl.data.windows"
