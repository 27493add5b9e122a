"""Tiered serving subsystem: prefill parity, continuous-batching
scheduler slot reuse/eviction, ReplicaPool per-tier dispatch, and the
calibrated latency bridge into the routing simulator."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model
from repro.routing import CalibratedLatencyModel, LatencyModel, SimConfig, \
    simulate
from repro.serving import (ContinuousBatchingScheduler, EngineMeasurement,
                           ReplicaPool, Request, ServeEngine, TierSpec,
                           batched_arrivals, bucket_len, lm_tiers,
                           poisson_requests)
from repro.serving.workload import RequestEvent


def _fp32(cfg):
    model = dataclasses.replace(cfg.model, dtype="float32",
                                param_dtype="float32")
    return dataclasses.replace(cfg, model=model)


def _api_params(arch, fp32=True, **model_overrides):
    cfg = get_config(arch).reduced()
    if fp32:
        cfg = _fp32(cfg)
    if model_overrides:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, **model_overrides))
    api = make_model(cfg)
    params, _ = api.init_params(jax.random.key(0))
    return cfg, api, params


# ---------------------------------------------------------------------------
# prefill parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["stablelm-1.6b", "deepseek-v2-lite-16b"])
def test_prefill_matches_sequential_decode(arch):
    """One-shot prefill must reproduce (a) the full forward logits and
    (b) the cache state S sequential decode steps would have built."""
    cfg, api, params = _api_params(arch)
    rng = np.random.default_rng(0)
    B, S, extra = 2, 12, 4
    tokens = jnp.asarray(
        rng.integers(0, cfg.model.vocab_size, (B, S + extra)), jnp.int32)
    full_logits, _ = api.forward(params, {"tokens": tokens[:, :S]})
    pf_logits, pf_cache = api.prefill(params, tokens[:, :S],
                                      api.init_cache(B, S + extra))
    np.testing.assert_allclose(np.asarray(pf_logits),
                               np.asarray(full_logits),
                               atol=2e-3, rtol=2e-3)
    # continuation from the prefilled cache == fully sequential decode
    cache_seq = api.init_cache(B, S + extra)
    for t in range(S + extra):
        seq_logits, cache_seq = api.decode_step(
            params, tokens[:, t:t + 1], jnp.int32(t), cache_seq)
    cache = pf_cache
    for t in range(S, S + extra):
        cont_logits, cache = api.decode_step(
            params, tokens[:, t:t + 1], jnp.int32(t), cache)
    np.testing.assert_allclose(np.asarray(cont_logits),
                               np.asarray(seq_logits),
                               atol=2e-3, rtol=2e-3)


def test_prefill_padded_prompt_and_ring_overflow():
    """Right-padded prompts must not pollute the cache, including when
    the prompt overflows a sliding-window ring cache."""
    cfg, api, params = _api_params("h2o-danube-1.8b")
    a = dataclasses.replace(cfg.model.attention, window=4)
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, attention=a))
    api = make_model(cfg)
    params, _ = api.init_params(jax.random.key(0))
    rng = np.random.default_rng(1)
    B, S, extra = 2, 10, 3
    tokens = jnp.asarray(
        rng.integers(0, cfg.model.vocab_size, (B, S + extra)), jnp.int32)
    cache_seq = api.init_cache(B, S + extra)
    for t in range(S + extra):
        seq_logits, cache_seq = api.decode_step(
            params, tokens[:, t:t + 1], jnp.int32(t), cache_seq)
    padded = jnp.concatenate([tokens[:, :S], jnp.zeros((B, 6), jnp.int32)],
                             axis=1)
    _, cache = api.prefill(params, padded, api.init_cache(B, S + extra),
                           length=S)
    for t in range(S, S + extra):
        cont_logits, cache = api.decode_step(
            params, tokens[:, t:t + 1], jnp.int32(t), cache)
    np.testing.assert_allclose(np.asarray(cont_logits),
                               np.asarray(seq_logits),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "xlstm-125m"])
def test_engine_generate_matches_seed_path(arch):
    """Engine-level greedy parity: prefill + continuous-batching decode
    produces the exact tokens of the seed token-by-token path (covers
    both the one-shot prefill and the fused-scan fallback)."""
    cfg = get_config(arch).reduced()
    api = make_model(cfg)
    params, _ = api.init_params(jax.random.key(0))
    eng = ServeEngine(cfg, params, batch_size=3, max_len=64)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.model.vocab_size, (2, 13)), jnp.int32)
    out_new = np.asarray(eng.generate(prompt, steps=5))
    out_seq = np.asarray(eng.generate_sequential(prompt, steps=5))
    assert out_new.shape == (2, 5)
    np.testing.assert_array_equal(out_new, out_seq)


def test_bucket_len():
    assert bucket_len(1) == 8
    assert bucket_len(8) == 8
    assert bucket_len(9) == 16
    assert bucket_len(128) == 128
    assert bucket_len(129) == 256


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_engine():
    cfg = get_config("stablelm-1.6b").reduced()
    api = make_model(cfg)
    params, _ = api.init_params(jax.random.key(0))
    return cfg, ServeEngine(cfg, params, batch_size=2, max_len=64)


def test_scheduler_slot_reuse_and_eviction(small_engine):
    """More requests than slots: every request completes, slots are
    recycled, and TTFT/TPOT accounting is populated."""
    cfg, eng = small_engine
    rng = np.random.default_rng(0)
    reqs = [Request(id=k, arrival_s=0.01 * k,
                    prompt=rng.integers(0, cfg.model.vocab_size, 6),
                    max_new_tokens=3)
            for k in range(6)]
    sched = ContinuousBatchingScheduler(eng)
    stats = sched.run(reqs)
    assert len(sched.completed) == 6
    assert all(len(r.tokens) == 3 for r in sched.completed)
    # 6 requests through 2 slots -> at least 4 admissions reuse a slot
    assert stats.slot_reuses >= 4
    assert stats.peak_occupancy <= eng.batch_size
    assert not sched.active and len(eng.free_slots) == eng.batch_size
    assert stats.ttft_ms.shape == (6,)
    assert (stats.ttft_ms > 0).all() and (stats.tpot_ms > 0).all()
    assert stats.tokens_generated == 18


def test_scheduler_interleaves_mid_generation_admission(small_engine):
    """A request admitted while another is mid-generation shares the
    decode program and still matches solo greedy generation."""
    cfg, eng = small_engine
    rng = np.random.default_rng(1)
    p1 = rng.integers(0, cfg.model.vocab_size, 9)
    p2 = rng.integers(0, cfg.model.vocab_size, 5)
    solo1 = np.asarray(eng.generate(jnp.asarray(p1)[None], steps=6))[0]
    solo2 = np.asarray(eng.generate(jnp.asarray(p2)[None], steps=4))[0]
    reqs = [Request(id=0, arrival_s=0.0, prompt=p1, max_new_tokens=6),
            Request(id=1, arrival_s=1e9, prompt=p2, max_new_tokens=4)]
    # force req 1 to arrive mid-generation: admit 0, decode twice, then 1
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(reqs[0])
    now = sched._admit_ready(0.0)
    now = sched._decode_once(now)
    now = sched._decode_once(now)
    reqs[1].arrival_s = now
    sched.submit(reqs[1])
    while sched.queue or sched.active:
        now = sched._admit_ready(now)
        if sched.active:
            now = sched._decode_once(now)
    done = {r.id: r for r in sched.completed}
    np.testing.assert_array_equal(done[0].tokens, solo1)
    np.testing.assert_array_equal(done[1].tokens, solo2)


def test_measure_preserves_inflight_sequences(small_engine):
    """Calibration mid-serving must not disturb active slots: tokens
    after a measure() call match an uninterrupted generation."""
    cfg, eng = small_engine
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.model.vocab_size, 8)
    expected = np.asarray(eng.generate(jnp.asarray(prompt)[None],
                                       steps=6))[0]
    slot = eng.acquire_slot()
    toks = [eng.admit(prompt, slot=slot)]
    toks.append(int(eng.decode()[slot]))
    eng.measure(prompt_len=8, decode_steps=2)        # mid-flight
    for _ in range(4):
        toks.append(int(eng.decode()[slot]))
    eng.evict(slot)
    np.testing.assert_array_equal(np.asarray(toks), expected)


def test_generate_refuses_busy_engine(small_engine):
    """generate() owns the whole engine; with sequences active it must
    refuse instead of silently advancing them."""
    cfg, eng = small_engine
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.model.vocab_size, 6)
    slot = eng.acquire_slot()
    eng.admit(prompt, slot=slot)
    with pytest.raises(RuntimeError, match="active sequences"):
        eng.generate(jnp.asarray(prompt)[None], steps=2)
    eng.evict(slot)


# ---------------------------------------------------------------------------
# replica pool
# ---------------------------------------------------------------------------

def test_replica_pool_per_tier_dispatch():
    """Each tier owns its engine with its own concurrency cap; dispatch
    routes work to the right replica (LM tiers decode tokens, the paper's
    GRU tier serves one forward per request)."""
    pool = ReplicaPool(lm_tiers("stablelm-1.6b", max_len=64))
    assert pool.tiers == ("device", "edge", "cloud")
    assert pool.concurrency("device") == 1
    assert pool.concurrency("edge") == 4
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 1024, (2, 6))
    out_edge = pool.dispatch("edge", prompts, steps=3)
    assert out_edge.shape == (2, 3)
    assert pool.engine("edge") is not pool.engine("cloud")
    assert pool.engine("edge").batch_size == 4
    # rnn tier: per-request forward
    gru_pool = ReplicaPool([TierSpec("device", arch="gru-traffic",
                                     batch_size=2)])
    pred = gru_pool.dispatch("device", rng.normal(size=(2, 12, 1)))
    assert pred.shape == (2, 1)
    with pytest.raises(TypeError):
        gru_pool.engine("device")
    with pytest.raises(ValueError):
        ReplicaPool([TierSpec("fog")])


def test_deployment_carries_replica_pool():
    from repro.orchestration import (Inventory, LearningController,
                                     random_inventory)
    from repro.serving import DEFAULT_TIERS
    inv = random_inventory(n=8, m=2, seed=0, capacity_slack=3.0)
    ctl = LearningController(inventory=inv, l=2,
                             serving_tiers=DEFAULT_TIERS)
    dep = ctl.deploy()
    assert dep.replica_pool is not None
    assert [s for s in dep.inference_services
            if s.startswith("replica/")] == [
        "replica/device", "replica/edge", "replica/cloud"]
    # without serving tiers the deployment stays pool-free (default)
    dep2 = LearningController(inventory=inv, l=2).deploy()
    assert dep2.replica_pool is None


# ---------------------------------------------------------------------------
# calibration bridge
# ---------------------------------------------------------------------------

def _meas(prefill, tpot, slots):
    return EngineMeasurement(prefill_ms=prefill, decode_ms_per_token=tpot,
                             batch_size=slots, prompt_len=16,
                             decode_steps=8)


def test_from_measurements_service_times_and_occupancy():
    lat = LatencyModel.from_measurements(
        {"edge": _meas(4.0, 0.5, 4), "cloud": _meas(2.0, 0.25, 16)},
        decode_tokens=8)
    assert isinstance(lat, CalibratedLatencyModel)
    assert lat.infer_ms("edge") == pytest.approx(4.0 + 8 * 0.5)
    assert lat.infer_ms("cloud") == pytest.approx(2.0 + 8 * 0.25)
    # within the slot budget service time is flat; beyond it requests
    # time-share the decode program
    assert lat.infer_ms("edge", occupancy=3) == pytest.approx(8.0)
    assert lat.infer_ms("edge", occupancy=7) == pytest.approx(16.0)
    # unmeasured tier falls back to the constant closed-form model
    assert lat.infer_ms("device") == LatencyModel().infer_ms("device")
    # network RTT behaviour is inherited untouched
    rng = np.random.default_rng(0)
    assert 8.0 <= float(lat.rtt("edge", rng)) <= 10.0


def test_simulator_calibrated_mode():
    """The simulator runs with engine-measured service times; the
    constant model stays the default and produces different latencies."""
    from repro.core.topology import ClusterTopology
    topo = ClusterTopology(assign=np.arange(12) % 3, n_devices=12,
                           n_edges=3, lam=np.full(12, 2.0),
                           r=np.full(3, 10.0), l=2)
    lat = LatencyModel.from_measurements(
        {"device": _meas(6.0, 0.0, 1), "edge": _meas(3.0, 0.0, 4),
         "cloud": _meas(1.0, 0.0, 16)})
    calib = simulate(topo, SimConfig(duration_s=30, seed=1, latency=lat))
    const = simulate(topo, SimConfig(duration_s=30, seed=1))
    assert len(calib.latency_ms) == len(const.latency_ms)
    assert calib.mean_latency() != pytest.approx(const.mean_latency())
    assert np.isfinite(calib.latency_ms).all()


def test_replica_pool_measure_feeds_latency_model():
    pool = ReplicaPool()                     # paper GRU at every tier
    lat = LatencyModel.from_measurements(pool.measure())
    for tier in pool.tiers:
        assert lat.infer_ms(tier) > 0.0
        assert lat.infer_ms(tier, occupancy=100) > lat.infer_ms(tier)


# ---------------------------------------------------------------------------
# workload flush semantics
# ---------------------------------------------------------------------------

def test_batched_arrivals_flushes_at_deadline():
    """A batch whose oldest member exceeds max_wait_s leaves at the
    deadline; the late arrival opens a NEW batch instead of riding along
    with (and further delaying) the stale one."""
    ev = [RequestEvent(0.00, 0), RequestEvent(0.01, 1),
          RequestEvent(0.20, 2)]
    batches = list(batched_arrivals(ev, batch_size=8, max_wait_s=0.05))
    assert len(batches) == 2
    t0, d0 = batches[0]
    assert t0 == pytest.approx(0.05)         # deadline, not 0.20
    assert list(d0) == [0, 1]
    t1, d1 = batches[1]
    assert list(d1) == [2] and t1 == pytest.approx(0.25)


def test_batched_arrivals_full_batch_and_conservation():
    lam = np.array([5.0, 10.0])
    ev = poisson_requests(lam, duration_s=10, seed=0)
    batches = list(batched_arrivals(ev, batch_size=4, max_wait_s=0.05))
    assert sum(len(b[1]) for b in batches) == len(ev)
    for t, devs in batches:
        assert len(devs) <= 4
    # emission times never precede the last member's arrival
    k = 0
    for t, devs in batches:
        assert t >= ev[k + len(devs) - 1].t - 1e-12
        k += len(devs)


def test_replica_pool_health_and_failover():
    """Down tiers re-route up the hierarchy (device->edge->cloud),
    degraded tiers still serve, a fully-down chain raises, and
    mark_down drains a built engine's in-flight rows leak-free."""
    from repro.serving import FAILOVER_ORDER, PagedServeEngine
    from repro.serving.replica import DEFAULT_TIERS

    pool = ReplicaPool(DEFAULT_TIERS)
    assert [pool.health(t) for t in pool.tiers] == ["healthy"] * 3
    assert pool.resolve_tier("edge") == "edge"
    pool.set_health("edge", "degraded")        # degraded still serves
    assert pool.resolve_tier("edge") == "edge"
    pool.set_health("edge", "down")
    assert pool.resolve_tier("edge") == "cloud"
    assert pool.resolve_tier("device") == "device"
    pool.set_health("device", "down")
    assert pool.resolve_tier("device") == "cloud"
    pool.set_health("cloud", "down")
    with pytest.raises(RuntimeError, match="failover chain"):
        pool.resolve_tier("device")
    pool.mark_up("edge")
    assert pool.resolve_tier("device") == "edge"
    assert pool.failovers == 3
    with pytest.raises(ValueError):
        pool.set_health("edge", "on-fire")
    assert FAILOVER_ORDER["cloud"] == ()

    # crash with traffic in flight: engine drained, pages conserved
    lm = ReplicaPool(
        [TierSpec("edge", arch="stablelm-1.6b", batch_size=2, max_len=64,
                  paged=True, page_size=8)],
        shared_params=None)
    lm.specs["edge"] = dataclasses.replace(lm.specs["edge"], reduced=True)
    eng = lm.engine("edge")
    assert isinstance(eng, PagedServeEngine)
    slot = eng.acquire_slot()
    eng.admit(np.arange(10) % 50, slot=slot, reserve_tokens=4)
    assert eng.active_slots == 1
    drained = lm.mark_down("edge")
    assert drained == [slot] and eng.active_slots == 0
    assert eng.pool.free_pages == eng.num_pages
    assert lm.health("edge") == "down"
