"""Pieces every driver of the benchmark shares: the compile clock, the
device check, quartiles and percentiles, and the result line."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileClock:
    """Records the spans in which JAX traces, lowers and compiles, and the
    persistent-cache hits, while open.  Spans nest, so time is counted as
    their union.  ``backend_compiles`` counts compilations (cache hits
    included: a hit still reports a backend span of its load)."""

    def __init__(self):
        self.spans = []
        self.backend = []
        self.cache_hits = 0

    def __enter__(self) -> "CompileClock":
        import jax.monitoring

        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_event_listener(self._event)

    def _span(self, event: str, start: float, end: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.spans.append((start, end))
        if event == _BACKEND_COMPILE:
            self.backend.append(start)

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def compile_s(self, since: float, until: float) -> float:
        """Seconds (``time.time()`` clock) between ``since`` and ``until``
        inside at least one compile span."""
        total, reach = 0.0, since
        for start, end in sorted(self.spans):
            start, end = max(start, reach), min(end, until)
            if end > start:
                total += end - start
                reach = end
        return total

    def backend_between(self, since: float, until: float) -> int:
        """Backend compilations (or cache loads) begun in the span."""
        return sum(1 for s in self.backend if since <= s <= until)


def device_info(chips: int):
    """The devices JAX sees; raises :class:`NoChip` unless they are at
    least ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {dev.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPUs, JAX sees {len(devices)}")
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no statistics)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation) of ``values``."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Checks:
    """Numbers compared for ``correct``, each beside its limit.  Each is
    judged by ``value <= limit``; a missing reading fails."""

    def __init__(self):
        self.items = []

    def add(self, name: str, value, limit: float) -> None:
        v = None if value is None or not np.isfinite(value) else float(value)
        self.items.append((name, v, float(limit)))

    @property
    def ok(self) -> bool:
        return bool(self.items) and all(v is not None and v <= lim
                                        for _, v, lim in self.items)

    def as_dict(self):
        return {name: {"value": v, "limit": lim}
                for name, v, lim in self.items}

    def print(self) -> None:
        for name, v, lim in self.items:
            log(f"check {name}: {v!r} (limit {lim!r})")


def emit(result: dict, checks: Checks) -> None:
    """Print the checks as the last lines of stderr, then the result line
    (with the checks under their own key, last) as the last line of
    stdout."""
    checks.print()
    out = dict(result)
    out["checks"] = checks.as_dict()
    print(json.dumps(out), flush=True)


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}

