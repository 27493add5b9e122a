"""Paged serving path: PagePool invariants, paged-vs-dense greedy token
parity across attention families, scheduler end-to-end over a shared page
pool, and the measured occupancy sweep feeding the calibrated latency
model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model
from repro.routing import LatencyModel
from repro.serving import (ContinuousBatchingScheduler, EngineMeasurement,
                           PagedServeEngine, PagePool, PagesExhausted,
                           Request, ServeEngine)


def _fp32(cfg):
    model = dataclasses.replace(cfg.model, dtype="float32",
                                param_dtype="float32")
    return dataclasses.replace(cfg, model=model)


def _cfg_params(arch):
    cfg = _fp32(get_config(arch).reduced())
    api = make_model(cfg)
    params, _ = api.init_params(jax.random.key(0))
    return cfg, params


# ---------------------------------------------------------------------------
# PagePool
# ---------------------------------------------------------------------------

def test_page_pool_allocate_extend_release():
    pool = PagePool(num_pages=8, page_size=4)
    t0 = pool.allocate(0, 6)                 # 2 pages
    assert len(t0) == 2 and pool.free_pages == 6
    new = pool.extend(0, 9)                  # -> 3 pages (1 new)
    assert len(new) == 1
    assert pool.block_table(0) == t0 + new
    assert pool.length(0) == 9
    t1 = pool.allocate(1, 16)                # 4 pages
    assert pool.free_pages == 1
    assert set(t0 + new).isdisjoint(t1)
    assert not pool.can_allocate(8)          # needs 2, only 1 free
    with pytest.raises(PagesExhausted):
        pool.allocate(2, 8)
    assert pool.release(0) == 3
    assert pool.free_pages == 4
    pool.check_invariants()


def test_page_pool_misuse_raises():
    pool = PagePool(num_pages=4, page_size=4)
    pool.allocate(0, 8)
    with pytest.raises(ValueError):
        pool.allocate(0, 4)                  # seq already allocated
    with pytest.raises(ValueError):
        pool.extend(0, 4)                    # shrink
    with pytest.raises(KeyError):
        pool.release(7)                      # never allocated
    pool.release(0)
    with pytest.raises(KeyError):
        pool.release(0)                      # double release


def test_page_pool_snapshot_restore():
    pool = PagePool(num_pages=8, page_size=4)
    pool.allocate(0, 10)
    state = pool.snapshot()
    pool.allocate(1, 8)
    pool.extend(0, 14)
    pool.restore(state)
    assert pool.sequences == [0]
    assert pool.length(0) == 10
    assert pool.free_pages == 5
    pool.check_invariants()


def test_page_pool_property_churn():
    """Random admit/extend/release churn holds every pool invariant."""
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.given(st.lists(st.tuples(st.integers(0, 2),
                                         st.integers(0, 7),
                                         st.integers(1, 40)),
                               max_size=60))
    @hypothesis.settings(deadline=None, max_examples=50)
    def run(ops):
        pool = PagePool(num_pages=10, page_size=4)
        live = {}
        for op, seq, n in ops:
            if op == 0 and seq not in live:
                if pool.can_allocate(n):
                    pool.allocate(seq, n)
                    live[seq] = n
                else:
                    with pytest.raises(PagesExhausted):
                        pool.allocate(seq, n)
            elif op == 1 and seq in live and n >= live[seq]:
                try:
                    pool.extend(seq, n)
                    live[seq] = n
                except PagesExhausted:
                    pass
            elif op == 2 and seq in live:
                pool.release(seq)
                del live[seq]
            pool.check_invariants()
        assert pool.sequences == sorted(live)

    run()


# ---------------------------------------------------------------------------
# paged-vs-dense greedy parity (the tentpole's correctness bar)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["stablelm-1.6b", "h2o-danube-1.8b",
                                  "gemma3-1b", "deepseek-v2-lite-16b",
                                  "qwen2-moe-a2.7b"])
def test_paged_generate_matches_dense(arch):
    """Greedy decode through the paged cache must be token-identical to
    the dense slot engine on every supported attention family (GQA,
    sliding-window, mixed-window gemma3, MLA, MLA+MoE)."""
    cfg, params = _cfg_params(arch)
    dense = ServeEngine(cfg, params, batch_size=2, max_len=64)
    paged = PagedServeEngine(cfg, params, max_seqs=2, page_size=8,
                             max_len=64)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.model.vocab_size, (2, 13)), jnp.int32)
    out_d = np.asarray(dense.generate(prompt, steps=6))
    out_p = np.asarray(paged.generate(prompt, steps=6))
    np.testing.assert_array_equal(out_p, out_d)


def test_paged_engine_requires_transformer():
    cfg, params = _cfg_params("xlstm-125m")
    with pytest.raises(ValueError, match="paged"):
        PagedServeEngine(cfg, params, max_seqs=2, page_size=8, max_len=64)


def test_double_evict_raises_both_engines():
    cfg, params = _cfg_params("stablelm-1.6b")
    for eng in (ServeEngine(cfg, params, batch_size=2, max_len=32),
                PagedServeEngine(cfg, params, max_seqs=2, page_size=8,
                                 max_len=32)):
        slot = eng.acquire_slot()
        eng.admit(np.arange(5), slot=slot)
        eng.evict(slot)
        with pytest.raises(ValueError, match="already free"):
            eng.evict(slot)


# ---------------------------------------------------------------------------
# scheduler end-to-end over a shared page pool
# ---------------------------------------------------------------------------

def test_scheduler_paged_oversubscribes_dense_rows():
    """The paged engine admits more concurrent sequences than the dense
    engine could hold in the same cache HBM, and the scheduler completes
    every request with the exact dense-engine tokens."""
    cfg, params = _cfg_params("stablelm-1.6b")
    max_len, ps = 32, 8
    # 8 pages = 64 cache tokens = TWO dense rows of max_len
    paged = PagedServeEngine(cfg, params, max_seqs=4, page_size=ps,
                             num_pages=8, max_len=max_len)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.model.vocab_size, (6, 5))
    reqs = [Request(id=k, arrival_s=0.0, prompt=prompts[k],
                    max_new_tokens=3) for k in range(6)]
    sched = ContinuousBatchingScheduler(paged)
    stats = sched.run([dataclasses.replace(r) for r in reqs])
    assert len(sched.completed) == 6
    # each request reserves 1 page: all 4 rows fill despite the pool
    # holding only 2 dense-row equivalents
    assert stats.peak_occupancy == 4
    dense = ServeEngine(cfg, params, batch_size=4, max_len=max_len)
    sched_d = ContinuousBatchingScheduler(dense)
    sched_d.run([dataclasses.replace(r) for r in reqs])
    tok_p = {r.id: r.tokens for r in sched.completed}
    tok_d = {r.id: r.tokens for r in sched_d.completed}
    assert tok_p == tok_d


def test_scheduler_rejects_impossible_request():
    cfg, params = _cfg_params("stablelm-1.6b")
    paged = PagedServeEngine(cfg, params, max_seqs=2, page_size=8,
                             num_pages=2, max_len=32)
    sched = ContinuousBatchingScheduler(paged)
    req = Request(id=0, arrival_s=0.0, prompt=np.arange(20),
                  max_new_tokens=12)           # 32 tokens > 16-token pool
    with pytest.raises(ValueError, match="never be admitted"):
        sched.run([req])


# ---------------------------------------------------------------------------
# measured occupancy sweep -> calibrated latency model
# ---------------------------------------------------------------------------

def test_measure_occupancy_sweep_paged_engine():
    cfg, params = _cfg_params("stablelm-1.6b")
    eng = PagedServeEngine(cfg, params, max_seqs=4, page_size=8,
                           max_len=64)
    m = eng.measure(prompt_len=8, decode_steps=2,
                    occupancy_levels=[1, 2, 4])
    levels = [lvl for lvl, _ in m.occupancy_ms]
    assert levels == [1, 2, 4]
    assert all(ms > 0.0 for _, ms in m.occupancy_ms)
    # the sweep must not leak state: the engine still serves correctly
    assert len(eng.free_slots) == 4
    assert eng.pool.free_pages == eng.pool.num_pages


def test_from_measurements_sweep_interpolation():
    """The calibrated model serves the measured curve: flat below the
    lowest swept level, interpolated between levels, time-shared beyond
    the highest."""
    m = EngineMeasurement(prefill_ms=10.0, decode_ms_per_token=1.0,
                          batch_size=4, prompt_len=8, decode_steps=4,
                          occupancy_ms=((1, 2.0), (4, 4.0)))
    lat = LatencyModel.from_measurements({"edge": m}, decode_tokens=10)
    assert lat.occupancy_dependent("edge")
    assert lat.flat_service_slots("edge") == 1.0
    # service at level c: prefill + 10 tokens * per-step ms
    assert lat.infer_ms("edge", occupancy=0.0) == pytest.approx(30.0)
    assert lat.infer_ms("edge", occupancy=3.0) == pytest.approx(50.0)
    # between levels: linear in concurrency c = occ + 1
    assert lat.infer_ms("edge", occupancy=1.0) == pytest.approx(
        30.0 + 20.0 / 3.0)
    # beyond the sweep: time-share the last measured rate
    assert lat.infer_ms("edge", occupancy=7.0) == pytest.approx(100.0)
    # scalar and array paths are bit-identical (occupancy_replay needs
    # base_service_ms == infer_ms at every occupancy below the boundary)
    occ = np.asarray([0.0, 1.0, 3.0, 7.0])
    arr = lat.infer_ms_array("edge", occ)
    for o, a in zip(occ, arr):
        assert lat.infer_ms("edge", occupancy=o) == a
    assert lat.base_service_ms("edge") == lat.infer_ms("edge", 0.0)
    # tiers without a sweep keep the closed-form stretch
    assert not lat.occupancy_dependent("cloud")


def test_from_measurements_without_sweep_unchanged():
    m = EngineMeasurement(prefill_ms=10.0, decode_ms_per_token=1.0,
                          batch_size=4, prompt_len=8, decode_steps=4)
    lat = LatencyModel.from_measurements({"edge": m}, decode_tokens=10)
    assert lat.tier_sweep == {}
    assert lat.flat_service_slots("edge") == 4.0
    assert lat.infer_ms("edge", occupancy=7.0) == pytest.approx(
        20.0 * 8.0 / 4.0)


# ---------------------------------------------------------------------------
# fault tolerance: admission-failure page release, crash drain + requeue
# ---------------------------------------------------------------------------

def test_admit_failure_releases_pages():
    """When allocate succeeds but prefill raises, the pages go back to
    the pool — repeated failed admissions must not bleed the pool dry."""
    cfg, params = _cfg_params("stablelm-1.6b")
    eng = PagedServeEngine(cfg, params, max_seqs=4, page_size=8,
                           max_len=64)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 50, 12)
    good_prefill = eng._prefill

    def boom(*a, **k):
        raise RuntimeError("injected prefill failure")

    free0 = eng.pool.free_pages
    eng._prefill = boom
    for _ in range(10):                       # churn: fail, fail, ...
        slot = eng.acquire_slot()
        with pytest.raises(RuntimeError, match="injected"):
            eng.admit(prompt, slot=slot)
        eng.evict(slot)                       # row itself is still held
        assert eng.pool.free_pages == free0   # ... but no page leaked
        eng.pool.check_invariants()
    # pool is whole: a real admission still works at full capacity
    eng._prefill = good_prefill
    slot = eng.acquire_slot()
    eng.admit(prompt, slot=slot, reserve_tokens=4)
    eng.decode()
    eng.evict(slot)
    eng.pool.check_invariants()
    assert eng.pool.free_pages == eng.num_pages


def test_crash_drain_requeues_and_completes():
    """Mid-decode crash: drain releases every page, the scheduler
    requeues the in-flight requests from their prompts, and the finished
    token streams match an uninterrupted run (greedy decode is
    deterministic)."""
    cfg, params = _cfg_params("stablelm-1.6b")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 50, 10) for _ in range(3)]

    def make():
        eng = PagedServeEngine(cfg, params, max_seqs=4, page_size=8,
                               max_len=64)
        sched = ContinuousBatchingScheduler(eng)
        for k, p in enumerate(prompts):
            sched.submit(Request(id=k, arrival_s=0.0, prompt=p,
                                 max_new_tokens=6))
        return eng, sched

    def finish(sched, now):
        while sched.queue or sched.active:
            now = sched._admit_ready(now)
            if sched.active:
                now = sched._decode_once(now)
        return {r.id: list(r.tokens) for r in sched.completed}

    eng, sched = make()
    now = sched._admit_ready(0.0)
    now = sched._decode_once(now)             # two tokens in, then crash
    assert sched.active
    n = sched.requeue_active(now)
    assert n == 3 and not sched.active and sched.requeues == 3
    assert eng.pool.free_pages == eng.num_pages
    eng.pool.check_invariants()
    crashed = finish(sched, now)

    _, fresh = make()
    clean = finish(fresh, 0.0)
    assert crashed == clean
    assert eng.pool.free_pages == eng.num_pages
