"""Tiered replica pool — the paper's "replication for free" (§III): HFL
leaves a model replica at every tier (device, edge aggregator, cloud), so
serving can dispatch to whichever tier routing selects.

One :class:`ServeEngine` per tier, with per-tier batch sizes (=concurrency
caps) mirroring the hardware asymmetry: a device serves one sequence at a
time, an edge host a handful, the cloud a large batch.  The paper's own
GRU (family ``rnn``) has no token decode loop — each request is one
forward over a history window — so it is served through a jitted
per-request path instead of the slot engine.

``measure()`` produces the per-tier timings that
``LatencyModel.from_measurements`` turns into a calibrated latency model
for the routing simulator (the bridge closing the serving <-> simulation
loop).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import make_model
from repro.serving.engine import (EngineMeasurement, PagedServeEngine,
                                  ServeEngine)

TIERS = ("device", "edge", "cloud")

#: replica health states
HEALTHY, DEGRADED, DOWN = "healthy", "degraded", "down"
HEALTH_STATES = (HEALTHY, DEGRADED, DOWN)

#: failover order: where a tier's traffic goes when its replica is down
#: (up the hierarchy — the cloud is the tier of last resort)
FAILOVER_ORDER: Dict[str, Tuple[str, ...]] = {
    "device": ("edge", "cloud"),
    "edge": ("cloud",),
    "cloud": (),
}


@dataclass(frozen=True)
class TierSpec:
    tier: str                        # device | edge | cloud
    arch: str = "gru-traffic"        # config-registry name
    batch_size: int = 1              # engine rows = concurrency cap
    max_len: int = 256
    reduced: bool = True             # CPU-sized variant; False: published
    replicas: int = 1                # replicas behind this tier
    # paged cache (transformer families only): batch_size rows share a
    # PagePool instead of each reserving a dense max_len cache
    paged: bool = False
    page_size: int = 16
    num_pages: Optional[int] = None  # default: batch_size * ceil(max_len/ps)


# the paper serves ONE model from every tier; the tiers differ in
# concurrency, not in weights
DEFAULT_TIERS: Tuple[TierSpec, ...] = (
    TierSpec("device", batch_size=1),
    TierSpec("edge", batch_size=4),
    TierSpec("cloud", batch_size=16),
)


def lm_tiers(arch: str = "xlstm-125m", max_len: int = 256,
             ) -> Tuple[TierSpec, ...]:
    """Tier layout for a token-decoding LM (benchmarks / examples)."""
    return (TierSpec("device", arch=arch, batch_size=1, max_len=max_len),
            TierSpec("edge", arch=arch, batch_size=4, max_len=max_len),
            TierSpec("cloud", arch=arch, batch_size=8, max_len=max_len))


def paged_lm_tiers(arch: str = "stablelm-1.6b", max_len: int = 256,
                   page_size: int = 16) -> Tuple[TierSpec, ...]:
    """Paged tier layout: each tier keeps the SAME page budget a dense
    tier of ``lm_tiers`` would hold (num_pages defaults to batch_size *
    ceil(max_len / page_size) dense-equivalent pages) but admits by
    actual token footprint, so row counts can be set far above the dense
    slot counts."""
    pages_dense = -(-max_len // page_size)
    return (TierSpec("device", arch=arch, batch_size=4, max_len=max_len,
                     paged=True, page_size=page_size,
                     num_pages=1 * pages_dense),
            TierSpec("edge", arch=arch, batch_size=16, max_len=max_len,
                     paged=True, page_size=page_size,
                     num_pages=4 * pages_dense),
            TierSpec("cloud", arch=arch, batch_size=32, max_len=max_len,
                     paged=True, page_size=page_size,
                     num_pages=8 * pages_dense))


class _RnnReplica:
    """Per-request serving path for the paper's GRU: one jitted forward
    per request batch (the request's unit of work, gru.decode_step)."""

    def __init__(self, cfg, params):
        self.cfg = cfg
        self.params = params
        self.api = make_model(cfg)
        self._fwd = jax.jit(
            lambda p, w: self.api.forward(p, {"windows": w})[0])

    def serve(self, windows: jax.Array) -> jax.Array:
        return self._fwd(self.params, jnp.asarray(windows, jnp.float32))

    def measure(self, batch_size: int, history: int = 12,
                repeats: int = 8, seed: int = 0) -> EngineMeasurement:
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.normal(size=(batch_size, history, 1)),
                        jnp.float32)
        self.serve(w).block_until_ready()          # compile
        t0 = time.perf_counter()
        for _ in range(repeats):
            self.serve(w).block_until_ready()
        ms = (time.perf_counter() - t0) * 1e3 / repeats
        return EngineMeasurement(prefill_ms=ms, decode_ms_per_token=0.0,
                                 batch_size=batch_size, prompt_len=history,
                                 decode_steps=0)


class ReplicaPool:
    """One serving replica per tier, built lazily (constructing engines
    compiles XLA programs — deployments should stay cheap until traffic
    actually arrives at a tier)."""

    def __init__(self, specs: Sequence[TierSpec] = DEFAULT_TIERS,
                 seed: int = 0,
                 shared_params: Optional[Any] = None):
        self.specs: Dict[str, TierSpec] = {}
        for s in specs:
            if s.tier not in TIERS:
                raise ValueError(f"unknown tier {s.tier!r}")
            self.specs[s.tier] = s
        self.seed = seed
        self._shared_params = shared_params
        self._replicas: Dict[str, Any] = {}
        self._health: Dict[str, str] = {t: HEALTHY for t in self.specs}
        self.failovers = 0               # dispatches re-routed off a down tier

    @property
    def tiers(self) -> Tuple[str, ...]:
        return tuple(self.specs)

    def concurrency(self, tier: str) -> int:
        s = self.specs[tier]
        return s.batch_size * s.replicas

    def _build(self, tier: str):
        spec = self.specs[tier]
        cfg = get_config(spec.arch)
        if spec.reduced:
            cfg = cfg.reduced()
        params = self._shared_params
        if params is None:
            api = make_model(cfg)
            # all tiers replicate the SAME trained weights (same seed)
            params, _ = api.init_params(jax.random.key(self.seed))
        if cfg.model.family == "rnn":
            return _RnnReplica(cfg, params)
        if spec.paged:
            return PagedServeEngine(cfg, params, max_seqs=spec.batch_size,
                                    page_size=spec.page_size,
                                    num_pages=spec.num_pages,
                                    max_len=spec.max_len)
        return ServeEngine(cfg, params, batch_size=spec.batch_size,
                           max_len=spec.max_len)

    def replica(self, tier: str):
        if tier not in self._replicas:
            self._replicas[tier] = self._build(tier)
        return self._replicas[tier]

    def engine(self, tier: str) -> ServeEngine:
        rep = self.replica(tier)
        if not isinstance(rep, (ServeEngine, PagedServeEngine)):
            raise TypeError(f"tier {tier!r} serves a per-request model")
        return rep

    # -- health / failover --------------------------------------------------

    def health(self, tier: str) -> str:
        return self._health[tier]

    def set_health(self, tier: str, state: str) -> None:
        if tier not in self.specs:
            raise ValueError(f"unknown tier {tier!r}")
        if state not in HEALTH_STATES:
            raise ValueError(f"unknown health state {state!r}; "
                             f"pick from {HEALTH_STATES}")
        self._health[tier] = state

    def mark_down(self, tier: str) -> List[int]:
        """Crash a tier: drain its engine (in-flight sequences lose
        their cache; paged pools are verified leak-free by
        ``drain``) and stop routing to it until :meth:`mark_up`.
        Returns the drained slot ids so callers can requeue."""
        self.set_health(tier, DOWN)
        rep = self._replicas.get(tier)
        if rep is not None and hasattr(rep, "drain"):
            return rep.drain()
        return []

    def mark_up(self, tier: str) -> None:
        self.set_health(tier, HEALTHY)

    def resolve_tier(self, tier: str) -> str:
        """Failover routing: the requested tier if it can serve (healthy
        or degraded), else the first not-down tier up its
        :data:`FAILOVER_ORDER` chain.  Raises when the whole chain is
        down — there is no silent drop."""
        if self._health.get(tier, DOWN) != DOWN:
            return tier
        for alt in FAILOVER_ORDER.get(tier, ()):
            if alt in self.specs and self._health[alt] != DOWN:
                self.failovers += 1
                return alt
        raise RuntimeError(
            f"tier {tier!r} is down and so is its whole failover chain "
            f"{FAILOVER_ORDER.get(tier, ())}")

    # -- dispatch -----------------------------------------------------------

    def dispatch(self, tier: str, batch, steps: int = 8):
        """Serve one batch on ``tier`` (or its failover target when the
        tier is down — see :meth:`resolve_tier`): token generation for
        LM tiers ((B,S) int prompts -> (B,steps) tokens), a single
        forward for rnn tiers ((B,T,1) windows -> (B,1) predictions)."""
        rep = self.replica(self.resolve_tier(tier))
        if isinstance(rep, _RnnReplica):
            return rep.serve(batch)
        return rep.generate(jnp.asarray(batch, jnp.int32), steps=steps)

    # -- calibration --------------------------------------------------------

    def measure(self, prompt_len: int = 64, decode_steps: int = 16,
                occupancy_levels: Optional[Sequence[int]] = None,
                ) -> Dict[str, EngineMeasurement]:
        """Per-tier wall-clock timings — feed the result to
        ``LatencyModel.from_measurements``.  ``occupancy_levels`` sweeps
        decode time at those admitted-sequence counts per tier (levels a
        tier cannot reach are dropped), giving the latency model real
        high-occupancy points."""
        out = {}
        for tier in self.specs:
            rep = self.replica(tier)
            if isinstance(rep, _RnnReplica):
                out[tier] = rep.measure(self.specs[tier].batch_size)
            else:
                out[tier] = rep.measure(prompt_len=prompt_len,
                                        decode_steps=decode_steps,
                                        occupancy_levels=occupancy_levels)
        return out
