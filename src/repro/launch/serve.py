"""Serving driver: continuous-batching scheduler over a Poisson inference
workload — the TPU-side realization of the paper's inference path.

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m \
      --requests 32 --slots 8

``--no-reduced`` serves the published widths (on the chip).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import make_model
from repro.routing import LatencyModel
from repro.serving import (ContinuousBatchingScheduler, ServeEngine,
                           poisson_requests, requests_from_events)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--slots", type=int, default=8,
                    help="continuous-batching slots (concurrency cap)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="2-layer CPU-sized variant (--no-reduced: the "
                         "published config)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = make_model(cfg)
    params, _ = api.init_params(jax.random.key(0))
    engine = ServeEngine(cfg, params, batch_size=args.slots, max_len=256)

    lam = np.full(args.slots, args.rate / args.slots)
    events = poisson_requests(lam, duration_s=args.requests / args.rate,
                              seed=0)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, max(cfg.model.vocab_size, 2),
                           (len(events), args.prompt_len))
    reqs = requests_from_events(events, prompts,
                                max_new_tokens=args.decode_steps)
    print(f"{len(events)} requests over {args.requests / args.rate:.1f}s "
          f"({args.slots} slots)")

    # warm the compile caches so TTFT reflects serving, not tracing
    meas = engine.measure(prompt_len=args.prompt_len,
                          decode_steps=args.decode_steps)
    print(f"engine: prefill {meas.prefill_ms:.1f}ms, "
          f"decode {meas.decode_ms_per_token:.2f}ms/token "
          f"@ {meas.batch_size} slots")

    sched = ContinuousBatchingScheduler(engine)
    stats = sched.run(reqs)
    print(f"served {len(sched.completed)} requests: {stats.summary()}")

    lat = LatencyModel.from_measurements(
        {"edge": meas}, decode_tokens=args.decode_steps)
    print(f"calibrated edge service time: "
          f"{lat.infer_ms('edge'):.2f}ms/request "
          f"(x{lat.infer_ms('edge', occupancy=2 * args.slots) / max(lat.infer_ms('edge'), 1e-9):.1f} "
          f"at 2x oversubscription)")


if __name__ == "__main__":
    main()
