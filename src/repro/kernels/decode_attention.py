"""Flash-decode Pallas kernel: ONE query token against a blocked KV cache
with online softmax over key blocks — the hot loop of ``decode_32k`` /
``long_500k`` serving.

Grid: (batch, C/bk).  A key block holds every kv head (its minor
(Hkv, D) dims tile on the TPU), and each head's G=H/Hkv grouped query
heads are kept together, so each cache block is read exactly once (GQA
makes decode memory-bound; minimizing cache reads is the whole game)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, hkv: int, scale: float):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ok = valid_ref[0] != 0                            # (1, bk)
    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32)           # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)     # (bk, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)     # (bk, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG_INF)                 # (G, bk)

        m_prev = m_ref[h]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_cur

    @pl.when(ic == nc - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, *, bk: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q (B,H,D); k/v (B,C,Hkv,D); valid (B,C) bool -> (B,H,Dv)."""
    B, H, D = q.shape
    C, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // Hkv
    bk = min(bk, C)
    assert C % bk == 0
    qg = q.reshape(B, Hkv, G, D)
    # (B, 1, C) int32: the mask block's minor dims are (1, bk)
    valid = valid.astype(jnp.int32).reshape(B, 1, C)
    grid = (B, C // bk)
    kernel = functools.partial(_decode_kernel, hkv=Hkv,
                               scale=1.0 / math.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, c: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, D), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, Dv), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, Dv), lambda b, c: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, Dv), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v, valid)
    return out.reshape(B, H, Dv)
