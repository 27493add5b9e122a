"""Scheduler: p90 over the window's requests of due time -> start of
admission (host clock)."""
from bench.common import percentile


def read(ctx):
    waits = ctx.get("queue_wait_ms")
    return percentile(waits, 90) if waits else None
