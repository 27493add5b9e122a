"""HFL training driver.

Runs the full stack end-to-end: config -> model -> data pipeline ->
(hierarchical) train step -> aggregation schedule -> checkpoint.  The
default ``--reduced`` trains the 2-layer CPU-sized variant;
``--no-reduced`` trains the published config (on the chip).

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b \
      --steps 20 --mode hfl --clusters 2 --global-every 2
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_pytree
from repro.configs import get_config
from repro.data.tokens import TokenStream, TokenStreamConfig
from repro.fl.collectives import cluster_divergence, stack_for_clusters
from repro.launch.compile_cache import enable_compile_cache
from repro.models import make_model
from repro.training.optimizer import AdamW
from repro.training.train_step import (hfl_global_round, make_hfl_train_step,
                                       make_train_step)


def make_batch(stream, cfg, batch_size, seq_len, clusters=0):
    m = cfg.model
    n = max(clusters, 1)
    batches = [stream.next_batch() for _ in range(n)]
    out = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
    if clusters == 0:
        out = {k: v[0] for k, v in out.items()}
    extra = {}
    rng = np.random.default_rng(0)
    if m.family == "vlm":
        P = m.frontend.num_positions
        shape = ((clusters,) if clusters else ()) + (batch_size, P, m.d_model)
        extra["patches"] = (rng.normal(size=shape) * 0.02).astype(np.float32)
    if m.family == "audio":
        F = m.frontend.num_positions
        shape = ((clusters,) if clusters else ()) + (batch_size, F, m.d_model)
        extra["frames"] = (rng.normal(size=shape) * 0.02).astype(np.float32)
    out.update({k: jnp.asarray(v, jnp.bfloat16) for k, v in extra.items()})
    return {k: jnp.asarray(v) for k, v in out.items()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--mode", choices=("flat", "hfl"), default="hfl")
    ap.add_argument("--clusters", type=int, default=2)
    ap.add_argument("--global-every", type=int, default=2,
                    help="the paper's l: local rounds per global round")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="2-layer CPU-sized variant (--no-reduced: the "
                         "published config)")
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args()

    enable_compile_cache()

    full = get_config(args.arch)
    cfg = full.reduced() if args.reduced else full
    api = make_model(cfg)
    m = cfg.model
    print(f"arch={args.arch} (reduced={args.reduced}) params...")
    params, _ = api.init_params(jax.random.key(0))
    opt = AdamW(lr=1e-3, state_dtype=cfg.run.opt_state_dtype)
    stream = TokenStream(TokenStreamConfig(
        vocab_size=max(m.vocab_size, 2), seq_len=args.seq,
        batch_size=args.batch))

    if args.mode == "flat":
        step = jax.jit(make_train_step(api, cfg, opt))
        opt_state = opt.init(params)
        for t in range(args.steps):
            batch = make_batch(stream, cfg, args.batch, args.seq)
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, batch)
            loss = float(loss)
            print(f"step {t:3d} loss={loss:.4f} "
                  f"({time.perf_counter() - t0:.2f}s)")
    else:
        C = args.clusters
        stacked = stack_for_clusters(params, C)
        opt_state = jax.vmap(opt.init)(stacked)
        local = jax.jit(make_hfl_train_step(api, cfg, opt))
        for t in range(args.steps):
            batch = make_batch(stream, cfg, args.batch, args.seq, clusters=C)
            t0 = time.perf_counter()
            stacked, opt_state, losses = local(stacked, opt_state, batch)
            line = (f"round {t:3d} losses="
                    f"{[round(float(x), 4) for x in losses]} "
                    f"({time.perf_counter() - t0:.2f}s)")
            if (t + 1) % args.global_every == 0:
                div = float(cluster_divergence(stacked))
                stacked = hfl_global_round(stacked)
                line += f"  [GLOBAL SYNC, divergence was {div:.2e}]"
            print(line)
        params = jax.tree.map(lambda x: x[0], stacked)

    if args.checkpoint:
        save_pytree(args.checkpoint, params)
        print(f"checkpoint -> {args.checkpoint}")


if __name__ == "__main__":
    main()
