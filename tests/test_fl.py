"""FL aggregation, compression, collectives, continual loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl import (ClientBatch, EFState, cluster_fedavg,
                      compressed_global_sync, dequantize_int8, fedavg,
                      global_fedavg, global_sync, init_ef_state,
                      quantize_int8, stack_clients, stack_for_clusters,
                      sync_bytes)
from repro.fl.aggregation import clusters, compiled_programs


def _stacked(C=6, shape=(4, 3), seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(C,) + shape), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(C, shape[1])), jnp.float32)}


def test_fedavg_weighted_mean():
    st = _stacked()
    w = jnp.asarray([1, 2, 3, 4, 5, 6.0])
    out = fedavg(st, w)
    manual = np.average(np.asarray(st["w"]), axis=0, weights=np.asarray(w))
    np.testing.assert_allclose(np.asarray(out["w"]), manual, rtol=1e-6)


def test_cluster_fedavg_segments():
    st = _stacked(C=6)
    cid = np.array([0, 0, 1, 1, 2, 2])
    out = cluster_fedavg(st, cid)
    for k in range(3):
        members = np.nonzero(cid == k)[0]
        manual = np.mean(np.asarray(st["w"])[members], axis=0)
        for i in members:
            np.testing.assert_allclose(np.asarray(out["w"])[i], manual,
                                       rtol=1e-5)


def test_global_fedavg_broadcasts_single_model():
    st = _stacked(C=6)
    cid = np.array([0, 0, 1, 1, 2, 2])
    out = global_fedavg(st, cid)
    w = np.asarray(out["w"])
    for i in range(1, 6):
        np.testing.assert_allclose(w[i], w[0], rtol=1e-5)
    # equal weights: global model = overall mean
    np.testing.assert_allclose(w[0], np.mean(np.asarray(st["w"]), axis=0),
                               rtol=1e-5)


def _gru_clients(seed=0):
    """The GRU's parameter tree for 20 clients in 4 unequal clusters,
    with unequal weights."""
    from repro.configs import get_config
    from repro.models import gru
    p0, _ = gru.init_params(jax.random.key(0), get_config(
        "gru-traffic").model)
    rng = np.random.default_rng(seed)
    st = jax.tree.map(lambda x: jnp.asarray(
        rng.normal(size=(20,) + x.shape), jnp.float32), p0)
    cid = np.repeat([3, 0, 7, 5], [2, 4, 6, 8])      # sparse ids, unequal
    return st, cid, rng.uniform(0.5, 3.0, 20)


@pytest.mark.parametrize("tier", ["cluster", "global", "flat"])
def test_compiled_aggregation_matches_float64_mean(tier):
    st, cid, w = _gru_clients()
    members = [np.nonzero(cid == k)[0] for k in np.unique(cid)]
    if tier == "cluster":
        out = cluster_fedavg(st, cid, w)
    elif tier == "global":
        out = global_fedavg(st, clusters(cid, w))
    else:
        out = fedavg(st, jnp.asarray(w), broadcast=True)
    for x, y in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        x, y = np.asarray(x, np.float64), np.asarray(y)
        assert y.shape == x.shape and y.dtype == np.float32
        means = [np.average(x[g], axis=0, weights=w[g]) for g in members]
        if tier == "cluster":
            want = np.empty_like(x)
            for g, m in zip(members, means):
                want[g] = m
        elif tier == "global":          # the cluster models, by weight
            want = np.average(means, axis=0,
                              weights=[w[g].sum() for g in members])
        else:
            want = np.average(x, axis=0, weights=w)
        want = np.broadcast_to(want, x.shape)
        assert np.linalg.norm(y - want) <= 1e-6 * np.linalg.norm(want)
        # the clients that share a model hold the same bits
        for g in members if tier == "cluster" else [np.arange(20)]:
            assert (y[g] == y[g[0]]).all()


def test_aggregation_compiles_once_per_cluster_count():
    st, cid, w = _gru_clients()
    c4 = clusters(cid, w)
    global_fedavg(st, c4)
    cluster_fedavg(st, c4)
    n = compiled_programs()
    other, _, w2 = _gru_clients(seed=1)
    global_fedavg(other, c4)                # same shapes, new values
    cluster_fedavg(other, cid, w2)          # host ids, prepared per call
    assert compiled_programs() == n
    cluster_fedavg(st, np.repeat([0, 1, 2, 3, 4], 4))  # five clusters
    assert compiled_programs() == n + 1


def test_global_sync_equals_mean():
    params = {"w": jnp.arange(12.0).reshape(3, 4)}
    stacked = stack_for_clusters(params, 4)
    stacked = jax.tree.map(
        lambda x: x + jnp.arange(4.0).reshape(4, 1, 1), stacked)
    out = global_sync(stacked)
    np.testing.assert_allclose(np.asarray(out["w"][0]),
                               np.asarray(params["w"]) + 1.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["w"][0]),
                               np.asarray(out["w"][3]), rtol=1e-6)


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) / 2 + 1e-7


def test_compressed_sync_error_feedback_converges():
    """Identical replicas + EF: after sync all replicas equal, and the
    anchor tracks the true mean within one quantization step."""
    rng = np.random.default_rng(1)
    shared = rng.normal(size=(8, 8))           # replicas start identical
    base = {"w": jnp.asarray(np.broadcast_to(shared, (4, 8, 8)),
                             jnp.float32)}
    ef = init_ef_state(base)
    drift = jnp.asarray(rng.normal(size=(4, 8, 8)) * 0.1, jnp.float32)
    moved = {"w": base["w"] + drift}
    synced, ef2 = compressed_global_sync(moved, ef)
    w = np.asarray(synced["w"])
    np.testing.assert_allclose(w[0], w[3], rtol=1e-6)
    true_mean = np.mean(np.asarray(moved["w"]), axis=0)
    assert np.abs(w[0] - true_mean).max() < 0.01   # int8 of 0.1-scale drift
    # residual bounded by quantization step
    assert float(jnp.abs(ef2.residual["w"]).max()) < 0.01


def test_sync_bytes_compression_ratio():
    st = {"w": jnp.zeros((4, 1024), jnp.float32)}
    assert sync_bytes(st, compressed=False) == 4096
    assert sync_bytes(st, compressed=True) == 1024


def test_train_clients_locally_improves_loss():
    from repro.configs import get_config
    from repro.fl.client import eval_clients, train_clients_locally
    from repro.models import gru
    cfg = get_config("gru-traffic")
    rng = np.random.default_rng(0)
    # learnable toy signal: next value = 0.9 * last
    T, N, C = 12, 200, 3
    X = rng.normal(size=(C, N, T, 1)).astype(np.float32)
    y = (X[:, :, -1, :] * 0.9).astype(np.float32)
    data = ClientBatch(X=jnp.asarray(X), y=jnp.asarray(y))
    p0, _ = gru.init_params(jax.random.key(0), cfg.model)
    stacked = stack_clients([p0] * C)
    before = np.asarray(eval_clients(stacked, data, cfg=cfg))
    out, _ = train_clients_locally(stacked, data, jax.random.key(1),
                                   cfg=cfg, epochs=3, batch_size=20,
                                   lr=5e-3)
    after = np.asarray(eval_clients(out, data, cfg=cfg))
    assert (after < before).all()
