#!/usr/bin/env python3
"""Smoke run of the system's two main paths on the TPU.

    python chip_smoke.py             # one chip: serving + HFL training
    python chip_smoke.py --chips 4   # four chips: HFL cluster-per-chip path

With no option, three phases run in this one process:

* ``serve-dense`` / ``serve-paged``: ``stablelm-1.6b`` at its published
  widths (24 layers, d_model 2048, vocab 100352; random weights from
  ``--seed``) behind two ``ReplicaPool`` tiers, a ``ServeEngine`` and a
  ``PagedServeEngine``.  Each serves the same 16 seeded requests (prompts
  of 64-512 tokens, 32 new tokens each) through
  ``ContinuousBatchingScheduler``.  Before that (``serve-ref``), the
  last-position logits of ``api.prefill`` and of one ``api.decode_step``
  after it are compared with a plain ``api.forward`` of the same tokens,
  in float32 on the model's first two layers at the same widths.
* ``hfl``: the paper's ``gru-traffic`` (2-layer GRU, hidden 128) on the
  path of ``examples/quickstart.py`` steps 1-3: seeded traffic data,
  HFLOP clustering (``LearningController.deploy``), then 3 rounds of
  continual hierarchical FL.

With ``--chips 4`` only ``hfl-shardmap`` runs: one FL cluster per chip on
a ``("cluster",)`` mesh, shard_map local steps then a psum global round,
compared with the one-device vmap step + ``global_sync`` reference.

Every phase prints its wall time with compile time split out.  A failed
check raises and the process exits non-zero.  The last line of stdout,
printed only when every phase passed on a TPU, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SERVE_ARCH = "stablelm-1.6b"
HFL_ARCH = "gru-traffic"
# The logit reference runs in float32 (highest matmul precision) on a
# depth cut: a stack of randomly initialized layers is chaotic, so at
# full depth rounding alone moves the logits by O(1) (bf16 prefill vs
# forward of stablelm-1.6b: rel-L2 0.38 on a TPU v5e).  Two layers keep
# rounding near 1e-5, while a wrong position, mask or cache entry is an
# O(1) error.
REF_LAYERS = 2
LOGIT_REL_L2_TOL = 1e-3
# shard_map and vmap run the same f32 step on the same chip kind; only
# the batching of the matmuls (and so their accumulation order) differs
SHARDMAP_ATOL, SHARDMAP_RTOL = 1e-4, 1e-3

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class CompileClock:
    """Records the spans in which JAX traces, lowers and compiles (and
    persistent-cache hits) while open, so each phase can split compile
    time out.  Spans nest (a jit traced inside another), so a phase
    counts their union, not their sum."""

    def __init__(self):
        self.spans = []
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

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def compile_s(self, since: float = 0.0) -> float:
        """Seconds inside at least one compile span since ``since``
        (``time.time()``)."""
        total, reach = 0.0, since
        for start, end in sorted(self.spans):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total

    def phase(self, name: str, fn, *args, **kwargs):
        h0 = self.cache_hits
        t0 = time.time()
        out = fn(*args, **kwargs)
        wall = time.time() - t0
        comp = self.compile_s(since=t0)
        log(name, f"wall {wall:.2f}s = compile {comp:.2f}s + other "
                  f"{wall - comp:.2f}s (persistent-cache hits "
                  f"{self.cache_hits - h0})")
        return out


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_requests(vocab: int, n: int, prompt_lens, new_tokens: int,
                  seed: int):
    """``n`` seeded requests: prompt lengths uniform in ``prompt_lens``
    (inclusive), Poisson arrivals at 8 per second."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, n)
    arrivals = np.cumsum(rng.exponential(1.0 / 8.0, n))
    return [Request(id=i, arrival_s=float(arrivals[i]),
                    prompt=rng.integers(0, vocab, int(lens[i])),
                    max_new_tokens=new_tokens)
            for i in range(n)]


def reference_check(api, params, prompt: np.ndarray, max_len: int) -> None:
    """Last-position logits of the serving path (padded ``api.prefill``
    into a fresh cache, then one ``api.decode_step``) against one plain
    ``api.forward`` over the prompt plus the token prefill chose."""
    import jax
    import jax.numpy as jnp

    from repro.serving import bucket_len

    S = len(prompt)
    padded = np.zeros((1, bucket_len(S)), np.int32)
    padded[0, :S] = prompt
    prefill = jax.jit(lambda p, t, n, c: api.prefill(p, t, c, length=n))
    logits, cache = prefill(params, padded, jnp.int32(S),
                            api.init_cache(1, max_len))
    last = logits[0, S - 1]
    first = int(jnp.argmax(last))
    dec_logits, _ = jax.jit(api.decode_step)(
        params, jnp.full((1, 1), first, jnp.int32), jnp.int32(S), cache)
    tokens = np.concatenate([prompt, [first]]).astype(np.int32)[None]
    ref = jax.jit(lambda p, t: api.forward(p, {"tokens": t})[0])(
        params, tokens)
    ref = np.asarray(ref[0].astype(jnp.float32))
    for name, got, want in (("prefill", last, ref[S - 1]),
                            ("decode", dec_logits[0, -1], ref[S])):
        got = np.asarray(got.astype(jnp.float32))
        check(bool(np.isfinite(got).all()), f"{name} logits not finite")
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        log("serve-ref", f"{name} logits vs forward (prompt {S}): rel-L2 "
                   f"{err:.2e} (tol {LOGIT_REL_L2_TOL:.0e}), max|d| "
                   f"{float(np.abs(got - want).max()):.3e}, argmax "
                   f"{'agrees' if got.argmax() == want.argmax() else 'differs'}")
        check(err <= LOGIT_REL_L2_TOL,
              f"{name} logits differ from forward: rel-L2 {err:.3e}")


def serve_reference(cfg, prompt: np.ndarray, max_len: int,
                    seed: int) -> None:
    """:func:`reference_check` on the first ``REF_LAYERS`` layers of
    ``cfg`` at its widths, in float32 (the same seed gives those layers,
    the embedding and the head the served model's weights before their
    rounding to bf16)."""
    import jax

    from repro.models import make_model

    model = dataclasses.replace(
        cfg.model, num_layers=min(REF_LAYERS, cfg.model.num_layers),
        dtype="float32", param_dtype="float32")
    api = make_model(dataclasses.replace(cfg, model=model))
    log("serve-ref", f"reference: {model.num_layers} of "
                     f"{cfg.model.num_layers} layers, d_model "
                     f"{model.d_model}, float32, highest matmul precision")
    with jax.default_matmul_precision("highest"):
        reference_check(api, api.init_params(jax.random.key(seed))[0],
                        prompt, max_len)


def serve_requests(engine, requests, vocab: int, phase: str):
    """Serve ``requests`` through the continuous-batching scheduler and
    check every one came back whole.  Returns each request's tokens."""
    from repro.serving import ContinuousBatchingScheduler

    sched = ContinuousBatchingScheduler(engine)
    stats = sched.run(requests)
    done = sorted(sched.completed, key=lambda r: r.id)
    check(len(done) == len(requests),
          f"{len(done)} of {len(requests)} requests completed")
    for r in done:
        check(len(r.tokens) == r.max_new_tokens,
              f"request {r.id}: {len(r.tokens)} of {r.max_new_tokens} tokens")
        check(all(0 <= t < vocab for t in r.tokens),
              f"request {r.id}: token id out of range")
    log(phase, f"{len(done)} requests served, peak occupancy "
               f"{stats.peak_occupancy}, slot reuses {stats.slot_reuses}; "
               f"host clock incl. compile: {stats.summary()}")
    return [list(r.tokens) for r in done]


def serving_phases(clock: CompileClock, arch: str = SERVE_ARCH, *,
                   reduced: bool = False, n_requests: int = 16,
                   prompt_lens=(64, 512), new_tokens: int = 32,
                   slots: int = 8, max_len: int = 1024,
                   seed: int = 0) -> None:
    """The serving phases for ``arch`` (published widths unless
    ``reduced``)."""
    import jax

    from repro.configs import get_config
    from repro.models import make_model
    from repro.serving import ReplicaPool, TierSpec

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    vocab = cfg.model.vocab_size
    requests = make_requests(vocab, n_requests, prompt_lens, new_tokens, seed)
    longest = max(requests, key=lambda r: len(r.prompt)).prompt
    clock.phase("serve-ref", serve_reference, cfg, longest, max_len, seed)

    api = make_model(cfg)
    params = clock.phase("serve-init", lambda: jax.block_until_ready(
        api.init_params(jax.random.key(seed))[0]))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    log("serve-init", f"{cfg.name}: {cfg.model.num_layers} layers, "
                      f"d_model {cfg.model.d_model}, vocab {vocab}, "
                      f"{n_params / 1e9:.3f} B params")
    # a dense and a paged tier of the same weights
    spec = dict(arch=arch, batch_size=slots, max_len=max_len,
                reduced=reduced)
    pool = ReplicaPool((TierSpec("edge", **spec),
                        TierSpec("cloud", paged=True, **spec)),
                       seed=seed, shared_params=params)
    tokens = {}
    for tier, phase in (("edge", "serve-dense"), ("cloud", "serve-paged")):
        # fresh requests: the scheduler fills in the ones it serves
        reqs = make_requests(vocab, n_requests, prompt_lens, new_tokens, seed)
        tokens[tier] = clock.phase(phase, serve_requests, pool.engine(tier),
                                   reqs, vocab, phase)
    same = sum(a == b for a, b in zip(tokens["edge"], tokens["cloud"]))
    log("serve-paged", f"dense and paged greedy tokens agree on {same} of "
                       f"{n_requests} requests (not checked: at full depth "
                       "bf16 rounding can flip a greedy choice)")


# ---------------------------------------------------------------------------
# HFL training
# ---------------------------------------------------------------------------

def hfl_phase(cfg, *, rounds: int = 3, seed: int = 0) -> None:
    """Quickstart steps 1-3: traffic data, HFLOP clustering, continual
    hierarchical FL.  Losses must be finite and the last round's
    validation MSE below round 0's."""
    from repro.data.traffic import generate, select_fl_sensors
    from repro.fl.hierarchy import ContinualHFL, HFLRunConfig
    from repro.orchestration import (DeviceNode, EdgeNode, Inventory,
                                     LearningController)

    ds = generate(num_days=30, seed=seed)
    sensors = select_fl_sensors(ds, per_cluster=2, seed=seed)
    rng = np.random.default_rng(seed)
    lam = rng.uniform(2.0, 6.0, len(sensors))
    devices = [DeviceNode(i, lam=float(lam[i]),
                          lan_edge=int(ds.cluster_of[sensors[i]]))
               for i in range(len(sensors))]
    edges = [EdgeNode(j, capacity_rps=float(lam.sum() / 4 * 1.4))
             for j in range(4)]
    deployment = LearningController(Inventory(devices, edges), l=2).deploy()
    topo = deployment.topology
    log("hfl", f"HFLOP: {len(sensors)} clients, "
               f"{len(np.unique(topo.assign))} clusters, l={topo.l}")
    run = HFLRunConfig(rounds=rounds, max_batches=15, max_val_windows=128,
                       seed=seed)
    result = ContinualHFL(cfg, ds, sensors, topo, run,
                          mode="hier").run_rounds()
    mse = result.mse.mean(axis=1)
    loss = result.train_loss.mean(axis=1)
    log("hfl", "val MSE by round " + " ".join(f"{v:.5f}" for v in mse)
        + " | train loss " + " ".join(f"{v:.5f}" for v in loss))
    check(bool(np.isfinite(result.train_loss).all()
               and np.isfinite(result.mse).all()), "non-finite loss")
    check(mse[-1] < mse[0],
          f"val MSE did not fall: round 0 {mse[0]:.5f}, last {mse[-1]:.5f}")


def hfl_shardmap_phase(cfg, devices, *, seed: int = 0) -> None:
    """One FL cluster per device: 3 shard_map local SGD steps on
    16-window batches, then a psum global round, against the one-device
    vmap step + ``global_sync`` on the same seeded inputs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.fl.collectives import (global_sync, global_sync_shardmap,
                                      make_hfl_local_step_shardmap,
                                      stack_for_clusters)
    from repro.models import make_model
    from repro.training.optimizer import SGD
    from repro.training.train_step import make_train_step

    n = len(devices)
    mesh = jax.make_mesh((n,), ("cluster",), axis_types=(AxisType.Auto,),
                         devices=devices)
    api = make_model(cfg)
    opt = SGD(lr=5e-2)
    base = make_train_step(api, cfg, opt)
    params0, _ = api.init_params(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    batches = [{"windows": rng.normal(size=(n, 16, 12, 1)),
                "targets": rng.normal(size=(n, 16, 1))} for _ in range(3)]
    batches = [jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), b)
               for b in batches]

    # reference: every cluster on one device, vmapped
    ref_p = stack_for_clusters(params0, n)
    ref_o = stack_for_clusters(opt.init(params0), n)
    vstep = jax.jit(jax.vmap(base))
    ref_losses = []
    for b in batches:
        ref_p, ref_o, loss = vstep(ref_p, ref_o, b)
        ref_losses.append(np.asarray(loss))
    ref_local = ref_p
    ref_p = jax.jit(global_sync)(ref_p)

    # cluster per device
    sh = NamedSharding(mesh, P("cluster"))
    put = lambda t: jax.device_put(t, sh)       # noqa: E731
    p = jax.tree.map(put, stack_for_clusters(params0, n))
    o = jax.tree.map(put, stack_for_clusters(opt.init(params0), n))
    step = jax.jit(make_hfl_local_step_shardmap(base, mesh))
    sync = jax.jit(lambda q: global_sync_shardmap(q, mesh))
    losses = []
    for b in batches:
        p, o, loss = step(p, o, jax.tree.map(put, b))
        losses.append(np.asarray(loss))
    local = p

    # the mesh orders its devices by the chips' links, not by id
    order = list(mesh.devices.flat)
    check(set(order) == set(devices), "the mesh is not over the devices")
    for leaf in jax.tree.leaves(local):
        placed = {s.index[0].start: s.device for s in leaf.addressable_shards
                  if s.data.shape[0] == 1}
        check([placed.get(k) for k in range(n)] == order,
              f"cluster replicas are not one per device: {placed}")
    log("hfl-shardmap", f"{n} cluster replicas, one per device: "
                        + ", ".join(f"cluster {k} on {d}"
                                    for k, d in enumerate(order)))
    local_hlo = step.lower(p, o, jax.tree.map(put, batches[0])) \
        .compile().as_text()
    sync_hlo = sync.lower(local).compile().as_text()
    check("all-reduce" not in local_hlo and "all-gather" not in local_hlo,
          "local step has a cross-cluster collective")
    check("all-reduce" in sync_hlo, "global round has no all-reduce")
    p = sync(local)

    err_loss = float(np.abs(np.stack(losses) - np.stack(ref_losses)).max())
    log("hfl-shardmap", "losses by step (cluster mean) "
        + " ".join(f"{v:.5f}" for v in np.stack(losses).mean(axis=1))
        + f"; max |loss - ref| {err_loss:.2e}")
    check(bool(np.isfinite(np.stack(losses)).all()), "non-finite loss")
    np.testing.assert_allclose(np.stack(losses), np.stack(ref_losses),
                               atol=SHARDMAP_ATOL, rtol=SHARDMAP_RTOL)
    spread = max(float(np.abs(np.asarray(x)[1:] - np.asarray(x)[:1]).max())
                 for x in jax.tree.leaves(local))
    # a global round that skipped or mis-weighted a cluster would miss
    # the reference by about this much: it must be far above tolerance
    check(spread > 100 * SHARDMAP_ATOL,
          f"clusters diverged by only {spread:.2e} before the global round")
    for name, got, want in (("local", local, ref_local),
                            ("synced", p, ref_p)):
        err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                  for a, b in zip(jax.tree.leaves(got),
                                  jax.tree.leaves(want)))
        log("hfl-shardmap", f"{name} params max |shard_map - vmap| "
                            f"{err:.2e}")
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=SHARDMAP_ATOL,
                                       rtol=SHARDMAP_RTOL)
    for x in jax.tree.leaves(p):
        x = np.asarray(x)
        check(bool((x == x[:1]).all()),
              "replicas differ after the global round")
    log("hfl-shardmap", f"clusters diverged by {spread:.2e} before the "
                        "global round and agree after it")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the HFL cluster-per-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the checkout first (a lone copy of this script stops here), then
    # the chip
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    cache_dir = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)
    t0 = time.time()
    with CompileClock() as clock:
        if args.chips == 4:
            clock.phase("hfl-shardmap", hfl_shardmap_phase,
                        get_config(HFL_ARCH), devices[:4], seed=args.seed)
        else:
            serving_phases(clock, seed=args.seed)
            clock.phase("hfl", hfl_phase, get_config(HFL_ARCH),
                        seed=args.seed)
    print(f"all phases passed in {time.time() - t0:.2f}s "
          f"(compile {clock.compile_s(since=t0):.2f}s)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
