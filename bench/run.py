"""Run one cell of the benchmark and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json``: its
configuration and plain reference under ``bench/configs/``, its traffic
under ``bench/traffic/``, its driver under ``bench/drivers/`` and, with
``--trace 1``, each per-layer metric's reader under ``bench/metrics/``.
With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the host clock around the
program's calls and from a profiler trace of the window.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()
T_START_WALL = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

# the checkout is the one place a run writes: the trace goes here, at a
# fixed path
TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(args, devices, *, control: bool = False,
            t_start: float = T_START,
            t_start_wall: float = T_START_WALL) -> tuple:
    """Run the cell on ``devices``; returns the result line's dict, the
    checks of ``correct`` and the run's context (readings included)."""
    import shutil

    from bench import common, loader, metrics_table, trace as tr

    cell = loader.workload(args.workload)
    cfg = loader.config(cell["config"])
    traffic = loader.traffic(cell["traffic"])
    checks = common.Checks()
    stamps = {}

    def stamp(name):
        return lambda: stamps.setdefault(name, (time.perf_counter(),
                                                time.time()))

    ctx = {"devices": devices, "checks": checks, "trace_dir": str(TRACE_DIR),
           "window_open": stamp("open"), "window_closed": stamp("closed"),
           "driver": cfg["driver"]}
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    with common.CompileClock() as clock:
        out = loader.driver(cfg["driver"]).run(
            cell, cfg, traffic, args.seed, args.seconds, bool(args.trace),
            ctx, control=control)
    (t_open, w_open), (_, w_closed) = stamps["open"], stamps["closed"]
    setup_s = t_open - t_start
    common.log(f"setup {setup_s:.3f}s, of it tracing, lowering and "
               f"compiling {clock.compile_s(t_start_wall, w_open):.3f}s "
               f"(persistent-cache hits {clock.cache_hits}); compiles "
               f"inside the window: {clock.backend_between(w_open, w_closed)}")
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": ctx["memory_peak_bytes"]}
    result = {"correct": checks.ok, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if args.trace:
        t = time.perf_counter()
        summary = tr.summarize(tr.load(tr.find_xplane(str(TRACE_DIR)),
                                       ctx["host_labels"]))
        common.log(f"trace read in {time.perf_counter() - t:.1f}s")
        ctx["trace"] = summary
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = tr.breakdown(summary)
        result["metrics"] = metrics_table.per_layer(args.workload, ctx)
    else:
        result["metrics"] = dict(out["e2e"])
        result["metrics"]["setup_s"] = common.metric(setup_s, "s")
    return result, checks, ctx


def main(argv=None) -> int:
    args = parse(argv)
    import jax

    from bench import common, loader
    from repro.launch.compile_cache import enable_compile_cache

    cell = loader.workload(args.workload)
    try:
        devices = common.device_info(int(cell["chips"]))
    except common.NoChip as e:
        common.log(f"bench: {e}")
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    common.log(f"bench: {args.workload} seed {args.seed} on "
               f"{devices[0].device_kind} x{len(devices)}; cache "
               f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or 'checkout'}")
    result, checks, _ = execute(args, devices)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
