"""Rate sweep of a serving cell, to find its knee: the highest rate the
system sustains.  One process, one engine, one window per rate.

    python bench/sweep.py --workload serve-conv --rates 0.4,0.6,0.8 \
        --seconds 30 --seed 7

Prints one JSON line per rate: the offered and served output tokens per
second, the tails, and how long the queue took to drain after the
window.  The knee is then written into the traffic file as a number;
the benchmark's runs never search for it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import common, generator, loader
    from bench.drivers import serve
    from repro.launch.compile_cache import enable_compile_cache

    cell = loader.workload(args.workload)
    try:
        devices = common.device_info(int(cell["chips"]))
    except common.NoChip as e:
        common.log(f"sweep: {e}")
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = loader.config(cell["config"])
    vocab = cfg["model"]["vocab_size"]
    rates = [float(r) for r in args.rates.split(",")]
    _, _, engine = serve.prepare(cfg, args.seed)
    plans = {}
    for rate in rates:
        traffic = dict(loader.traffic(cell["traffic"]), rate_per_s=rate)
        plans[rate] = generator.requests(traffic, args.seed, args.seconds,
                                         vocab)
    serve._warm(engine, sorted({len(r.prompt) for p in plans.values()
                                for r in p}), vocab)
    ctx = {"window_open": lambda: None, "window_closed": lambda: None}
    for rate in rates:
        reqs = plans[rate]
        win = serve.serve_window(engine, reqs, args.seconds, ctx)
        got = serve.summarize(win, args.seconds, vocab)
        e2e = {k: v["value"] for k, v in got["e2e"].items()}
        dec = win["decodes"]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "offered_tokens_per_s": sum(r.max_new_tokens for r in reqs)
            / args.seconds,
            **e2e, "failed": got["failed"],
            "drain_s": win["t_end"] - args.seconds,
            "decode_step_ms": float(np.mean([b - a for a, b in dec]) * 1e3)
            if dec else None,
            "mean_rows": float(np.mean([len(s[1]) for s in win["steps"]]))
            if win["steps"] else None,
            "memory_peak_bytes": common.memory_peak_bytes(devices)}),
            flush=True)
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
