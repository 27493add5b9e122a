"""Reads a cell's per-layer metrics: each is a reader of its own,
``metrics/<name>.py``, with ``read(ctx)`` returning a number or None when
it finds nothing to read (then the metric is left out of the line)."""
from __future__ import annotations

from bench import common, loader


def applies(metric: dict, cell: dict, manifest: dict) -> bool:
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == metric["moves"])
    return "workloads" not in moved or cell["name"] in moved["workloads"]


def per_layer(workload: str, ctx: dict) -> dict:
    manifest = loader.manifest()
    cell = loader.workload(workload)
    out = {}
    for m in manifest["per_layer"]:
        if not applies(m, cell, manifest):
            continue
        value = loader.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = common.metric(value, m["unit"])
    return out
