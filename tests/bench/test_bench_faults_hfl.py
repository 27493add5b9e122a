"""A whole run of the HFL cell, at a size a CPU test can hold, with only
the look for a chip skipped: ``correct`` holds on the sound program and
comes out false with its training or aggregation broken underneath."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import tiny  # noqa: E402

SEED = 2 ** 33 + 12


def test_sound_run_is_correct():
    result, checks, _ = tiny.run_cell("hfl", SEED, 0.5)
    assert result["correct"], checks.as_dict()
    assert set(result["metrics"]) == {"round_ms", "setup_s"}


def _unchanged(monkeypatch):
    import repro.fl.hierarchy as h

    train = h.train_clients_locally

    def same(params, data, rng, **kw):
        _, losses = train(params, data, rng, **kw)
        return params, losses

    monkeypatch.setattr(h, "train_clients_locally", same)


def _half_batch(monkeypatch):
    import jax

    import repro.models.gru as gru

    loss = gru.mse_loss

    def half(params, cfg, windows, targets):
        n = windows.shape[0] // 2
        return loss(params, cfg, windows[:n], targets[:n])

    jax.clear_caches()
    monkeypatch.setattr(gru, "mse_loss", half)


def _no_exchange(monkeypatch):
    import repro.fl.hierarchy as h

    monkeypatch.setattr(h, "cluster_fedavg", lambda p, *a, **k: p)
    monkeypatch.setattr(h, "global_fedavg", lambda p, *a, **k: p)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _no_exchange])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    import jax

    fault(monkeypatch)
    try:
        result, checks, _ = tiny.run_cell("hfl", SEED, 0.5)
    finally:
        jax.clear_caches()
    assert not result["correct"], checks.as_dict()
