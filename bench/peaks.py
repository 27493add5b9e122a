"""Peaks of a chip, keyed by JAX's ``device_kind`` (``peaks.json``).  A
kind that is not in the table is an error, never a default."""
from __future__ import annotations

import json

from bench.common import BENCH


def for_kind(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]
