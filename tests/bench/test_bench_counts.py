"""Counts of operations and bytes kept with the benchmark, and its table
of peaks."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import flops, loader, peaks  # noqa: E402

STABLELM = loader.config("stablelm-1.6b")["model"]
GRU = loader.config("gru-traffic")


def test_stablelm_param_count_matches_program():
    import jax

    from repro.configs import get_config
    from repro.models import make_model

    api = make_model(get_config("stablelm-1.6b"))
    shapes = jax.eval_shape(lambda: api.init_params(jax.random.key(0))[0])
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert flops.lm_param_count(STABLELM) == n
    assert abs(n / 1e9 - 1.644) < 5e-4


def test_reference_weights_have_program_layout():
    import jax

    from repro.configs import get_config
    from repro.models import make_model

    ref = loader.reference("stablelm-1.6b")
    got = jax.eval_shape(lambda: ref.init_params(jax.random.key(0),
                                                 STABLELM))
    want = jax.eval_shape(lambda: make_model(get_config(
        "stablelm-1.6b")).init_params(jax.random.key(0))[0])
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), want)
    assert ref.param_count(STABLELM) == flops.lm_param_count(STABLELM)


def test_prefill_flops_count_live_tokens_and_last_logits_only():
    L, d, F, V = 24, 2048, 5632, 100352
    per_layer = 4 * d * d + 3 * d * F
    S = 1000
    want = 2 * L * per_layer * S + 4 * L * d * S * (S + 1) / 2 + 2 * d * V
    assert flops.prefill_flops(STABLELM, S) == pytest.approx(want, rel=1e-12)
    # the bucket the engine pads to, and logits at every position, are
    # not work the algorithm needs
    padded = flops.prefill_flops(STABLELM, 1024)
    assert flops.prefill_flops(STABLELM, S) < padded
    every_position = want + 2 * d * V * (S - 1)
    assert flops.prefill_flops(STABLELM, S) < every_position


def test_decode_bytes_are_weights_plus_live_kv():
    L, d, V = 24, 2048, 100352
    n = flops.lm_param_count(STABLELM)
    kv = L * 2 * 32 * 64 * 2
    assert flops.kv_bytes_per_token(STABLELM) == kv == 196608
    rows, live = 7, 5000
    # every parameter but the embedding table, one embedding row a row
    weights = (n - V * d + rows * d) * 2
    assert flops.decode_bytes(STABLELM, rows, live) == weights + live * kv
    assert flops.decode_bytes(STABLELM, rows, live + 1) - \
        flops.decode_bytes(STABLELM, rows, live) == kv


def test_gru_round_flops():
    m = GRU["model"]
    h = 128
    per_step = (2 * 1 * 3 * h + 2 * h * 3 * h) + (2 * h * 3 * h) * 2
    window = 3 * (12 * per_step + 2 * h)
    assert flops.gru_window_flops(m, 12) == window
    n = 21 * 288 - 12
    got = flops.hfl_round_flops(m, 20, 5, n // 16, 16, 12)
    assert got == 20 * 5 * (n // 16) * 16 * window
    assert 6.0e12 < got < 7.0e12


def test_peaks_by_device_kind():
    v5e = peaks.for_kind("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "Google Cloud" in table["source"]
