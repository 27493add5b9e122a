"""The readers of the FL data layer (``round_data_ms``,
``upload_mb_per_round``, ``data_idle_share.hfl``) on made-up spans,
counters and trace intervals."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import loader, trace  # noqa: E402
from repro.telemetry import Span  # noqa: E402

# the cell's sizes: 20 clients, 6,036 training and 512 validation windows
# of 12 float32 readings, and their targets
CELL_BYTES = 5_794_560 + 482_880 + 491_520 + 40_960


def _read(name, ctx):
    return loader.metric_reader(name).read(ctx)


def _spans(data_s):
    out = []
    for t, d in enumerate(data_s):
        out += [Span("hfl.data.windows", 0.0, 0.9 * d, "hfl", domain="wall",
                     parent="hfl.data"),
                Span("hfl.data", 0.0, d, "hfl", domain="wall",
                     parent="hfl.round"),
                Span("hfl.train", d, 0.01, "hfl", domain="wall",
                     parent="hfl.round"),
                Span("hfl.round", 0.0, d + 0.3, "hfl", domain="wall",
                     args={"round": t, "tier": "cluster"})]
    return out


def test_round_data_ms_is_the_mean_data_span():
    ctx = {"program_spans": _spans([0.040, 0.050, 0.060, 0.070])}
    assert _read("round_data_ms", ctx) == pytest.approx(55.0)
    assert _read("round_data_ms", {}) is None
    assert _read("round_data_ms", {"program_spans": _spans([])}) is None


def test_upload_mb_per_round_divides_by_every_tier():
    ctx = {"program_counters": {"hfl.upload_bytes": 6.0 * CELL_BYTES,
                                "hfl.rounds.cluster": 3.0,
                                "hfl.rounds.global": 3.0}}
    assert _read("upload_mb_per_round", ctx) == pytest.approx(6.80992)
    flat = {"program_counters": {"hfl.upload_bytes": 2e6,
                                 "hfl.rounds.flat": 4.0}}
    assert _read("upload_mb_per_round", flat) == pytest.approx(0.5)
    assert _read("upload_mb_per_round", {}) is None
    assert _read("upload_mb_per_round", {"program_counters": {
        "hfl.upload_bytes": 1.0}}) is None


def _round_trace():
    """One made-up round (ns): the host windows and uploads, then trains,
    aggregates, evaluates and syncs; the device runs the uploads, the
    training and the rest."""
    tr = trace.Trace()
    dev = "/device:TPU:0"
    tr.host = [("window", 0, 1000), ("run_rounds", 0, 1000),
               ("hfl.round", 0, 1000), ("hfl.data", 0, 400),
               ("hfl.data.windows", 0, 300), ("hfl.data.upload", 300, 400),
               ("hfl.train", 400, 420), ("hfl.aggregate", 420, 450),
               ("hfl.eval", 450, 470), ("hfl.sync", 470, 1000)]
    tr.ops[dev] = [("%c = f32[] copy(x)", 340, 400),      # upload
                   ("%w = f32[] while(x)", 410, 800),     # training
                   ("%f = f32[] fusion(x)", 850, 960)]    # agg + eval
    tr.modules[dev] = [("jit_train_clients_locally(1)", 410, 800)]
    return tr


def test_data_idle_share_reads_the_data_gaps():
    s = trace.summarize(_round_trace())
    ctx = {"driver": "hfl", "trace": s}
    # idle: 0-340 (windows 0-300 by its midpoint 170), 400-410 (train),
    # 800-850 and 960-1000 (sync)
    assert s["idle_by_label_s"] == pytest.approx(
        {"hfl.data.windows": 340e-9, "hfl.train": 10e-9,
         "hfl.sync": 90e-9})
    assert _read("data_idle_share.hfl", ctx) == pytest.approx(34.0)
    whole = _read("device_idle_share.hfl", ctx)
    assert whole == pytest.approx(44.0)
    # the idle split by label adds up to the whole idle share
    split = {k: 100.0 * v / s["window_s"]
             for k, v in s["idle_by_label_s"].items()}
    assert sum(split.values()) == pytest.approx(whole)
    assert _read("data_idle_share.hfl", ctx) <= whole


def test_data_idle_share_needs_the_programs_spans():
    tr = _round_trace()
    tr.host = [h for h in tr.host if not h[0].startswith("hfl.")]
    ctx = {"driver": "hfl", "trace": trace.summarize(tr)}
    assert _read("data_idle_share.hfl", ctx) is None
    assert _read("data_idle_share.hfl", {"driver": "serve",
                                         "trace": ctx["trace"]}) is None
    assert _read("data_idle_share.hfl", {"driver": "hfl"}) is None
