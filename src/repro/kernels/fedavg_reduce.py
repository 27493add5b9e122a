"""Weighted model-replica reduction Pallas kernel — the FedAvg hot loop.

Aggregating C client/cluster replicas of a flattened parameter vector is
a (C x N) weighted column reduction.  On TPU the N dimension is tiled
into VMEM blocks; each grid step reduces all C replicas for its tile
(C is small — 20 clients / 4 clusters — so the full column block fits)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fedavg_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                # (C, bn)
    w = w_ref[...].astype(jnp.float32)                # (C,)
    wn = w / jnp.sum(w)
    o_ref[...] = jnp.dot(wn[None, :], x,
                         preferred_element_type=jnp.float32)[0].astype(
                             o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def fedavg_reduce(stacked: jax.Array, weights: jax.Array, *,
                  bn: int = 16384, interpret: bool = False) -> jax.Array:
    """stacked (C, N) replica matrix; weights (C,) -> (N,) average."""
    C, N = stacked.shape
    bn = min(bn, N)
    pad = (-N) % bn
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    Np = N + pad
    out = pl.pallas_call(
        _fedavg_kernel,
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec((C, bn), lambda i: (0, i)),
            pl.BlockSpec((C,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Np,), stacked.dtype),
        interpret=interpret,
    )(stacked, weights)
    return out[:N]
