"""Serving driver: one ``PagedServeEngine`` replica behind a
``ReplicaPool`` tier, driven by the program's continuous-batching
scheduler in an open loop on the wall clock.

Every request is timed from when it was due, so a stall counts against
every request queued behind it.  The requests due inside the window are
the sample; those still in flight when it closes are drained and count
in the tails.  After the window, a sample of the finished requests,
drawn from the seed and holding the longest, is run through the plain
reference beside the configuration, and the served tokens' logits are
compared with the reference's best.
"""
from __future__ import annotations

import gc
import time
from typing import List

import numpy as np

from bench import common, generator, loader

# host annotations of the open loop, which label the trace's idle gaps
HOST_LABELS = ("admit", "decode", "scheduler", "idle-wait")
# requests still in flight this long after the window closes have failed
DRAIN_S = 60.0


def program_config(cfg: dict):
    """The program's configuration of ``cfg["arch"]``; raises where its
    sizes differ from those the configuration file states."""
    from repro.configs import get_config

    arch = get_config(cfg["arch"])
    if cfg["engine"].get("tier_reduced"):
        arch = arch.reduced()
    m, a = arch.model, arch.model.attention
    have = {"num_hidden_layers": m.num_layers, "hidden_size": m.d_model,
            "intermediate_size": m.d_ff, "vocab_size": m.vocab_size,
            "num_attention_heads": a.num_heads,
            "num_key_value_heads": a.num_kv_heads, "head_dim": a.head_dim,
            "partial_rotary_factor": a.rope_fraction,
            "rope_theta": a.rope_theta, "layer_norm_eps": m.norm_eps}
    want = cfg["model"]
    bad = {k: (v, want[k]) for k, v in have.items() if v != want[k]}
    if bad or m.norm != "layernorm" or m.act != "silu" or m.tie_embeddings:
        raise ValueError(f"{cfg['name']}: the program's sizes differ from "
                         f"the configuration file: {bad}")
    return arch


class TimedEngine:
    """The engine, with the host clock read around each ``admit`` and
    ``decode`` (each ends in a host read of its tokens)."""

    def __init__(self, engine):
        self._e = engine
        self.admits: List[tuple] = []       # (t0, t1, prompt tokens)
        self.decodes: List[tuple] = []      # (t0, t1)

    def __getattr__(self, name):
        return getattr(self._e, name)

    def admit(self, prompt, slot, reserve_tokens=None):
        t0 = time.perf_counter()
        out = self._e.admit(prompt, slot=slot, reserve_tokens=reserve_tokens)
        self.admits.append((t0, time.perf_counter(), len(prompt)))
        return out

    def decode(self):
        t0 = time.perf_counter()
        out = self._e.decode()
        self.decodes.append((t0, time.perf_counter()))
        return out


def open_loop_scheduler():
    """The program's scheduler with its loop replaced by one on the wall
    clock (its own ``run()`` keeps a virtual clock that skips idle time
    and its own host work)."""
    import jax
    from repro.serving import ContinuousBatchingScheduler

    class WallClockScheduler(ContinuousBatchingScheduler):
        def run_open_loop(self, requests, t0: float, deadline: float):
            """Serve ``requests`` (due times in seconds after ``t0``)
            until all are done or ``deadline`` passes.  Returns, per
            decode step, the host time it returned and the requests it
            advanced, and the live tokens it read."""
            clock = lambda: time.perf_counter() - t0      # noqa: E731
            for r in requests:
                self.submit(r)
            steps = []
            pool = self.engine.pool
            self.peak_pages = 0
            while self.queue or self.active:
                with jax.profiler.TraceAnnotation("scheduler"):
                    now = clock()
                    if now > deadline:
                        break
                    if not self.active and self.queue[0].arrival_s > now:
                        with jax.profiler.TraceAnnotation("idle-wait"):
                            time.sleep(self.queue[0].arrival_s - now)
                        continue
                    with jax.profiler.TraceAnnotation("admit"):
                        self._admit_ready(now)
                    self.peak_pages = max(self.peak_pages,
                                          pool.num_pages - pool.free_pages)
                    if not self.active:
                        continue
                    live = list(self.active.values())
                    kv = sum(len(r.prompt) + len(r.tokens) for r in live)
                    with jax.profiler.TraceAnnotation("decode"):
                        self._decode_once(clock())
                    steps.append((clock(), live, kv))
            return steps

    return WallClockScheduler


def _build(cfg: dict, arch, params):
    from repro.serving import ReplicaPool, TierSpec

    e = cfg["engine"]
    spec = TierSpec(e["tier"], arch=cfg["arch"], batch_size=e["max_seqs"],
                    max_len=e["max_len"], reduced=bool(e.get("tier_reduced")),
                    paged=True, page_size=e["page_size"],
                    num_pages=e["num_pages"])
    pool = ReplicaPool((spec,), shared_params=params)
    return pool.engine(e["tier"])


def _warm(engine, lengths, vocab: int) -> None:
    """Admit one prompt of every length the traffic sends (each prefill
    bucket, and the host-side ops that depend on the length), decode
    once, and free the rows."""
    rng = np.random.default_rng(0)
    for n in sorted(set(int(x) for x in lengths)):
        slot = engine.acquire_slot()
        engine.admit(rng.integers(0, vocab, n), slot=slot, reserve_tokens=1)
        engine.evict(slot)
    slot = engine.acquire_slot()
    engine.admit(rng.integers(0, vocab, int(min(lengths))), slot=slot,
                 reserve_tokens=1)
    engine.decode()
    engine.evict(slot)


def sample_requests(done, k: int, seed: int):
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    order = sorted(done, key=lambda r: (-(len(r.prompt) + len(r.tokens)),
                                        r.id))
    rest = order[1:]
    rng = generator.rng_for(seed, 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


def reference_gaps(ref, params, model: dict, reqs, max_len: int,
                   modes=("f32",)):
    """For each finished request in ``reqs``, run the plain reference
    over its prompt and served tokens.  Returns, per mode after the
    first, and for the served tokens themselves under ``"served"``, the
    widest gap by which the chosen token's reference logit lies below
    the reference's best."""
    import jax
    import jax.numpy as jnp

    K = len(reqs)
    # served lengths padded to a power of two: few shapes to compile
    M = 8
    while M < max(len(r.tokens) for r in reqs):
        M *= 2
    toks = np.zeros((K, max_len), np.int32)
    idx = np.zeros((K, M), np.int32)
    served = np.zeros((K, M), np.int32)
    valid = np.zeros((K, M), bool)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, r.tokens[:-1]]).astype(np.int32)
        toks[i, :len(seq)] = seq
        n = len(r.tokens)
        idx[i, :n] = len(r.prompt) - 1 + np.arange(n)
        served[i, :n] = r.tokens
        valid[i, :n] = True
    fns = {mode: jax.jit(lambda p, t, i, mode=mode:
                         ref.logits_at(p, t, i, model, mode))
           for mode in modes}
    ref_logits = fns[modes[0]](params, toks, idx)
    best = jnp.max(ref_logits, axis=-1)

    def gap(chosen):
        got = jnp.take_along_axis(ref_logits, jnp.asarray(chosen)[..., None],
                                  axis=-1)[..., 0]
        return float(jnp.max(jnp.where(valid, best - got, -jnp.inf)))

    out = {"served": gap(served)}
    for mode in modes[1:]:
        out[mode] = gap(jnp.argmax(fns[mode](params, toks, idx), axis=-1))
    return out


def prepare(cfg: dict, seed: int):
    """Weights from the seed, and the engine built as users build it.
    Returns (reference module, weights, timed engine)."""
    import jax

    arch = program_config(cfg)
    ref = loader.reference(cfg["name"])
    init = jax.jit(lambda k: ref.init_params(k, cfg["model"]))
    params = jax.block_until_ready(init(generator.jax_key(seed)))
    loader.check_layout(params, arch)
    return ref, params, TimedEngine(_build(cfg, arch, params))


def serve_window(engine, reqs, seconds: float, ctx: dict,
                 trace_dir=None) -> dict:
    """Serve ``reqs`` in an open loop from now on; returns what the
    window saw."""
    import jax

    from bench import trace as tr
    from repro.serving import Request

    engine.admits.clear()
    engine.decodes.clear()
    sched = open_loop_scheduler()(engine)
    served = [Request(id=r.id, arrival_s=r.due_s, prompt=r.prompt,
                      max_new_tokens=r.max_new_tokens) for r in reqs]
    if trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=tr.options())
    ctx["window_open"]()
    t0 = time.perf_counter()
    error, steps = None, []
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        try:
            steps = sched.run_open_loop(served, t0, seconds + DRAIN_S)
        except Exception as e:          # a failed step fails what is left
            error = repr(e)
    t_end = time.perf_counter() - t0
    ctx["window_closed"]()
    if trace_dir:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        common.log(f"trace stopped in {time.perf_counter() - t:.1f}s")
    return {"served": served, "done": {r.id for r in sched.completed},
            "steps": steps, "error": error, "t_end": t_end, "t0": t0,
            "peak_pages": getattr(sched, "peak_pages", 0),
            "admits": list(engine.admits), "decodes": list(engine.decodes)}


def summarize(win: dict, seconds: float, vocab: int) -> dict:
    """End-to-end numbers and the host-clock readings of a window."""
    served, steps = win["served"], win["steps"]
    whole = [r for r in served if r.id in win["done"]
             and len(r.tokens) == r.max_new_tokens
             and all(0 <= t < vocab for t in r.tokens)]
    # token times: the first at admission, then each decode step
    times = {r.id: [r.t_first_token] for r in served
             if r.t_first_token is not None}
    for t, live, _ in steps:
        for r in live:
            times[r.id].append(t)
    ttft = [(times[r.id][0] - r.arrival_s) * 1e3 for r in whole]
    itl = np.concatenate([np.diff(times[r.id]) * 1e3 for r in whole]
                         + [np.zeros(0)])
    in_window = sum(int(np.sum(np.asarray(v) < seconds))
                    for v in times.values())
    # the scheduler admits in order of due time
    by_due = sorted(served, key=lambda r: (r.arrival_s, r.id))
    queue_wait = [(a[0] - win["t0"] - r.arrival_s) * 1e3
                  for a, r in zip(win["admits"], by_due)]
    out = {"whole": whole, "failed": len(served) - len(whole),
           "queue_wait_ms": queue_wait, "e2e": {}}
    if whole:
        out["e2e"] = {
            "ttft_p90_ms": common.metric(common.percentile(ttft, 90), "ms"),
            "itl_p95_ms": common.metric(common.percentile(itl, 95), "ms"),
            "output_tokens_per_s": common.metric(in_window / seconds,
                                                 "tokens/s")}
    return out


def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, ctx: dict, control: bool = False) -> dict:
    """One run of a serving cell.  Fills ``ctx`` for the metric readers
    and returns the numbers of the result line."""
    model, eng_cfg = cfg["model"], cfg["engine"]
    vocab = model["vocab_size"]
    ref, params, engine = prepare(cfg, seed)
    reqs = generator.requests(traffic, seed, seconds, vocab)
    mem = {"weights and engine": common.memory_peak_bytes(ctx["devices"])}
    _warm(engine, [len(r.prompt) for r in reqs], vocab)
    mem["warm-up"] = common.memory_peak_bytes(ctx["devices"])
    win = serve_window(engine, reqs, seconds, ctx,
                       ctx["trace_dir"] if trace else None)
    got = summarize(win, seconds, vocab)
    steps = win["steps"]
    ctx.update({
        "admits": win["admits"], "decodes": win["decodes"],
        "decode_kv_tokens": [kv for _, _, kv in steps],
        "decode_rows": [len(live) for _, live, _ in steps],
        "queue_wait_ms": got["queue_wait_ms"], "host_labels": HOST_LABELS,
        "model": model,
    })
    common.log(f"serve: {len(reqs)} requests due in {seconds}s, "
               f"{len(got['whole'])} whole, window+drain {win['t_end']:.2f}s,"
               f" {len(steps)} decode steps, {len(win['admits'])} admits"
               + (f", error {win['error']}" if win["error"] else ""))

    # the window runs the warm-up's programs at the same shapes, on a
    # pool allocated whole at build; the peak after weights and engine
    # shows that set-up sets no higher one
    ctx["memory_peak_bytes"] = common.memory_peak_bytes(ctx["devices"])
    mem["window"] = ctx["memory_peak_bytes"]
    common.log("memory peak (bytes) after " + ", ".join(
        f"{k} {v}" for k, v in mem.items()) + f"; pool fill: at most "
        f"{win['peak_pages']} of {engine.num_pages} pages in use")
    # free the program's state before the reference runs
    del engine, win
    gc.collect()

    checks = ctx["checks"]
    checks.add("unfinished_requests", got["failed"], 0)
    limit = cfg["correct"]["max_logit_gap"]
    if got["whole"]:
        sample = sample_requests(got["whole"],
                                 cfg["correct"]["sample_requests"], seed)
        modes = ("f32", "fp8") if control else ("f32",)
        t = time.perf_counter()
        gaps = reference_gaps(ref, params, model, sample,
                              eng_cfg["max_len"], modes)
        common.log(f"reference: {len(sample)} requests, "
                   f"{sum(len(r.tokens) for r in sample)} served tokens, "
                   f"gaps {gaps} ({time.perf_counter() - t:.1f}s)")
        ctx["readings"] = gaps
        checks.add("max_logit_gap", gaps["served"], limit)
    else:
        checks.add("max_logit_gap", None, limit)
    return {"attempted": len(reqs), "failed": got["failed"],
            "e2e": got["e2e"]}
