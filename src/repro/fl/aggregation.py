"""FedAvg and hierarchical aggregation over stacked client parameters.

Clients are stacked on a leading axis; cluster-local aggregation is a
segment-mean over that axis (the host-level mirror of the TPU psum over
the "data" mesh axis), and global aggregation averages cluster models
(mirror of the psum over the "pod" axis).

Each call of :func:`fedavg`, :func:`cluster_fedavg` or
:func:`global_fedavg` dispatches one compiled program for the whole
parameter tree.  The number of clusters is static, so there is one
program per tier, cluster count and set of leaf shapes;
:func:`compiled_programs` counts them."""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


class Clusters(NamedTuple):
    """The clients' clusters as the aggregation programs take them: each
    client's compact cluster id (0..n-1) and weight, on the device, and
    the number of clusters ``n``.  Made once by :func:`clusters`."""
    ids: jax.Array
    weights: jax.Array
    n: int


def clusters(cluster_ids: np.ndarray,
             weights: Optional[np.ndarray] = None) -> Clusters:
    """Compact ``cluster_ids`` on the host and upload them with the
    clients' ``weights`` (all 1 if ``None``)."""
    uniq, seg = np.unique(np.asarray(cluster_ids), return_inverse=True)
    w = np.ones(seg.shape[0]) if weights is None else np.asarray(weights)
    return Clusters(jnp.asarray(seg, jnp.int32),
                    jnp.asarray(w, jnp.float32), len(uniq))


def _prepared(cluster_ids: Union[np.ndarray, Clusters],
              weights: Optional[np.ndarray]) -> Clusters:
    if isinstance(cluster_ids, Clusters):
        if weights is not None:
            raise ValueError("the weights are part of the Clusters")
        return cluster_ids
    return clusters(cluster_ids, weights)


def _expand(v: jax.Array, x: jax.Array) -> jax.Array:
    """``v`` (one value per row of ``x``) shaped to broadcast over ``x``."""
    return v.reshape(v.shape + (1,) * (x.ndim - 1)).astype(x.dtype)


def _cluster_means(stacked, seg, w, n_seg):
    denom = jax.ops.segment_sum(w, seg, n_seg)

    def agg(x):
        sums = jax.ops.segment_sum(x * _expand(w, x), seg, n_seg)
        return (sums / _expand(denom, sums))[seg]

    return jax.tree.map(agg, stacked)


@functools.partial(jax.jit, static_argnames="n_seg")
def _cluster_fedavg(stacked, seg, w, n_seg):
    return _cluster_means(stacked, seg, w, n_seg)


@functools.partial(jax.jit, static_argnames="n_seg")
def _global_fedavg(stacked, seg, w, n_seg):
    # cluster model = weighted mean of members; global = weighted mean of
    # cluster models by total member weight
    local = _cluster_means(stacked, seg, w, n_seg)
    cw = jax.ops.segment_sum(w, seg, n_seg)              # cluster weights
    gw = cw / jnp.sum(cw)

    def agg(x):
        # one representative row per cluster
        first = jnp.zeros((n_seg,) + x.shape[1:], x.dtype)
        first = first.at[seg].set(x)                  # last member wins; all equal
        return jnp.broadcast_to(jnp.sum(first * _expand(gw, first), axis=0),
                                x.shape)

    return jax.tree.map(agg, local)


@functools.partial(jax.jit, static_argnames="broadcast")
def fedavg(stacked: PyTree, weights: Optional[jax.Array] = None,
           broadcast: bool = False) -> PyTree:
    """Weighted average over the leading (client) axis; with
    ``broadcast`` every client slot gets it (a flat aggregation round)."""
    if weights is None:
        avg = lambda x: jnp.mean(x, axis=0)              # noqa: E731
    else:
        w = weights / jnp.sum(weights)
        avg = lambda x: jnp.sum(x * _expand(w, x), axis=0)   # noqa: E731
    if broadcast:
        return jax.tree.map(lambda x: jnp.broadcast_to(avg(x), x.shape),
                            stacked)
    return jax.tree.map(avg, stacked)


def cluster_fedavg(stacked: PyTree,
                   cluster_ids: Union[np.ndarray, Clusters],
                   weights: Optional[np.ndarray] = None) -> PyTree:
    """Per-cluster FedAvg (local aggregation round).

    ``cluster_ids`` is each client's cluster, or a :class:`Clusters`
    that already holds them with the weights.  Returns stacked params
    where client i's slot holds its *cluster model* — exactly what each
    aggregator redistributes to its members."""
    c = _prepared(cluster_ids, weights)
    return _cluster_fedavg(stacked, c.ids, c.weights, c.n)


def global_fedavg(stacked: PyTree,
                  cluster_ids: Union[np.ndarray, Clusters],
                  weights: Optional[np.ndarray] = None) -> PyTree:
    """Global aggregation round: average the *cluster* models (one vote
    per cluster, weighted by cluster data size), then broadcast back to
    every client slot.  ``cluster_ids`` as in :func:`cluster_fedavg`."""
    c = _prepared(cluster_ids, weights)
    return _global_fedavg(stacked, c.ids, c.weights, c.n)


def compiled_programs() -> int:
    """The aggregation programs compiled so far and still cached."""
    return sum(f._cache_size()
               for f in (fedavg, _cluster_fedavg, _global_fedavg))
