"""Fused softmax + top-k MoE router Pallas kernel.

One VMEM pass per token block: softmax over experts then k iterative
argmax+mask rounds (k <= 8 for the assigned MoE archs), avoiding the
separate softmax materialization + sort of the XLA path."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _router_kernel(logits_ref, w_ref, i_ref, *, k: int):
    x = logits_ref[...].astype(jnp.float32)           # (bt, E)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    bt, E = probs.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)
    cur = probs
    for j in range(k):
        best = jnp.max(cur, axis=-1)
        arg = jnp.argmax(cur, axis=-1).astype(jnp.int32)
        w_ref[:, j] = best.astype(w_ref.dtype)
        i_ref[:, j] = arg
        cur = jnp.where(cols == arg[:, None], -1.0, cur)


@functools.partial(jax.jit, static_argnames=("k", "bt", "interpret"))
def topk_router(logits: jax.Array, k: int, *, bt: int = 1024,
                interpret: bool = False):
    """logits (T,E) -> (weights (T,k) f32, idx (T,k) i32)."""
    T, E = logits.shape
    bt = min(bt, T)
    assert T % bt == 0
    kernel = functools.partial(_router_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), jnp.float32),
            jax.ShapeDtypeStruct((T, k), jnp.int32),
        ],
        interpret=interpret,
    )(logits)
