"""A whole run of the serving cell, at a size a CPU test can hold, with
only the look for a chip skipped: ``correct`` holds on the sound
program, and comes out false with the timed path broken underneath."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import tiny  # noqa: E402

SEED = 2 ** 33 + 11


def test_sound_run_is_correct():
    result, checks, _ = tiny.run_cell("serve", SEED, 1.0)
    assert result["correct"], checks.as_dict()
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                      "output_tokens_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"


def _altered_token(monkeypatch):
    from repro.serving.engine import PagedServeEngine

    decode = PagedServeEngine.decode

    def wrong(self):
        toks = decode(self)
        return (np.asarray(toks) + 1) % self.cfg.model.vocab_size

    monkeypatch.setattr(PagedServeEngine, "decode", wrong)


def _state_unchanged(monkeypatch):
    from repro.serving.engine import PagedServeEngine

    impl = PagedServeEngine._decode_impl

    def stale(self, params, toks, pos, cache, block_tables):
        out, _ = impl(self, params, toks, pos, cache, block_tables)
        return out, cache

    monkeypatch.setattr(PagedServeEngine, "_decode_impl", stale)


def _half_batch(monkeypatch):
    """Rows in the second half of the batch get the first half's
    tokens."""
    from repro.serving.engine import PagedServeEngine

    decode = PagedServeEngine.decode

    def half(self):
        toks = np.array(decode(self))
        n = len(toks) // 2
        toks[n:] = toks[:len(toks) - n]
        return toks

    monkeypatch.setattr(PagedServeEngine, "decode", half)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result, checks, _ = tiny.run_cell("serve", SEED, 1.0)
    assert not result["correct"], checks.as_dict()
