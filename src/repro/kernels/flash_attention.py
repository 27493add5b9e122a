"""Flash attention Pallas TPU kernel (causal / sliding-window), the hot
path of every attention-bearing assigned architecture.

TPU adaptation (vs the CUDA flash-attention algorithm): the grid
iterates (batch*kv_head, q_block, k_block) with the online-softmax
accumulator held in VMEM scratch across the innermost k_block dimension;
block shapes are MXU-aligned (multiples of 128 on the contracting dims).
Sliding-window blocks outside [q-W, q] are skipped via masking (the
index_map cannot skip them without ragged grids; the §Perf iteration
measures the win of halving the k-grid for causal blocks)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, window: int, causal: bool, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                     # (bq, D)
    k = k_ref[0].astype(jnp.float32)                     # (bk, D)
    v = v_ref[0].astype(jnp.float32)                     # (bk, Dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    d = q_pos - k_pos
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= d >= 0
    if window > 0:
        mask &= d < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jax.Array:
    """q (BH, T, D); k/v (BH, T, D).  GQA callers fold the group into BH.
    Returns (BH, T, Dv)."""
    BH, T, D = q.shape
    Dv = v.shape[-1]
    bq = min(bq, T)
    bk = min(bk, T)
    assert T % bq == 0 and T % bk == 0, (T, bq, bk)
    scale = 1.0 / math.sqrt(D)
    grid = (BH, T // bq, T // bk)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, window=window,
                               causal=causal, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom
        ],
        interpret=interpret,
    )(q, k, v)
