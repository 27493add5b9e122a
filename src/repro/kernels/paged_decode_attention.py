"""Paged flash-decode Pallas kernels: ONE query token per sequence
against a *paged* KV (or MLA latent) cache, gathered through per-sequence
block tables instead of a contiguous ``(B, C, Hkv, D)`` cache.

Reuses the online-softmax structure of ``kernels/decode_attention.py``
(grid (batch, page) over key blocks, running max / sum / accumulator
scratch), but the key block for grid step ``p`` is page
``block_tables[b, p]`` of a global ``(P, page_size, ...)`` page array —
the block table rides in as a scalar-prefetch operand so the BlockSpec
index map can compute the DMA source before the kernel body runs.
Sequences mask by *logical* token index: token ``t`` of sequence ``b``
lives at page ``t // page_size`` slot ``t % page_size`` and is valid iff
``t < lengths[b]`` (and inside the sliding window, when one is set).

Two variants:

  * :func:`paged_decode_attention` — GQA: a page block holds every kv
    head (the ``(Hkv, D)`` minor dims tile on the TPU) and each head's
    G = H/Hkv grouped query heads stay together, so each page is read
    once.
  * :func:`paged_mla_decode_attention` — DeepSeek MLA with matrix
    absorption: queries arrive already projected into latent space
    (``q_c = q_nope @ w_uk``), scores are taken against the compressed
    ``c_kv``/``k_rope`` page arrays directly, and the context returned
    is latent-space (caller applies ``w_uv``); all H heads share every
    page read since MLA caches are head-free.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA over paged KV
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, ps: int, hkv: int, scale: float,
                  soft_cap: float, window: Optional[int]):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    tok = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    ok = tok < length                                 # (1, ps)
    if window is not None:
        ok &= (length - 1 - tok) < window
    # the page block holds every kv head (the (Hkv, D) minor dims tile);
    # each head reads its (ps, D) column with a strided load
    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32)           # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)     # (ps, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)     # (ps, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if soft_cap:
            s = jnp.tanh(s / soft_cap) * soft_cap
        s = jnp.where(ok, s, NEG_INF)                 # (G, ps)

        m_prev = m_ref[h]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        pw = jnp.exp(s - m_cur)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(pw, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            pw, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_cur

    @pl.when(p == np_ - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("soft_cap", "window",
                                             "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *, soft_cap: float = 0.0,
                           window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """q (B,H,D); k/v_pages (P, page_size, Hkv, D); block_tables
    (B, pages_per_seq) i32 page ids (pad rows past a sequence's pages
    with any in-bounds id — they mask out); lengths (B,) i32 valid
    tokens -> (B,H,Dv)."""
    B, H, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    Dv = v_pages.shape[-1]
    G = H // Hkv
    pages_per_seq = block_tables.shape[1]
    qg = q.reshape(B, Hkv, G, D)
    grid = (B, pages_per_seq)
    kernel = functools.partial(
        _paged_kernel, ps=ps, hkv=Hkv, scale=1.0 / math.sqrt(D),
        soft_cap=soft_cap, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, Hkv, G, D),
                             lambda b, p, bt, ln: (b, 0, 0, 0)),
                pl.BlockSpec((1, ps, Hkv, D),
                             lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
                pl.BlockSpec((1, ps, Hkv, Dv),
                             lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Hkv, G, Dv),
                                   lambda b, p, bt, ln: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hkv, G, Dv), jnp.float32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, H, Dv)


# ---------------------------------------------------------------------------
# MLA (absorbed) over paged latents
# ---------------------------------------------------------------------------

def _paged_mla_kernel(bt_ref, len_ref, qc_ref, qr_ref, ckv_ref, kr_ref,
                      o_ref, acc_ref, m_ref, l_ref, *, ps: int,
                      scale: float):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    qc = qc_ref[0].astype(jnp.float32)                # (H, R)
    qr = qr_ref[0].astype(jnp.float32)                # (H, Dr)
    ckv = ckv_ref[0].astype(jnp.float32)              # (ps, R)
    kr = kr_ref[0].astype(jnp.float32)                # (ps, Dr)
    tok = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    ok = tok < length
    s = (jax.lax.dot_general(qc, ckv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)) * scale
    s = jnp.where(ok, s, NEG_INF)                     # (H, ps)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    pw = jnp.exp(s - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pw, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pw, ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(p == np_ - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_mla_decode_attention(q_c: jax.Array, q_rope: jax.Array,
                               ckv_pages: jax.Array, krope_pages: jax.Array,
                               block_tables: jax.Array, lengths: jax.Array,
                               *, scale: float,
                               interpret: bool = False) -> jax.Array:
    """Absorbed-MLA paged decode.  q_c (B,H,R) latent-space queries;
    q_rope (B,H,Dr); ckv/krope_pages (P, page_size, R|Dr); block_tables
    (B, pages_per_seq); lengths (B,).  ``scale`` is the *full* qk scale
    ``1/sqrt(nope_dim + rope_dim)``.  Returns latent-space context
    (B,H,R) — apply ``w_uv`` outside."""
    B, H, R = q_c.shape
    ps = ckv_pages.shape[1]
    Dr = krope_pages.shape[-1]
    pages_per_seq = block_tables.shape[1]
    grid = (B, pages_per_seq)
    kernel = functools.partial(_paged_mla_kernel, ps=ps, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, R), lambda b, p, bt, ln: (b, 0, 0)),
                pl.BlockSpec((1, H, Dr), lambda b, p, bt, ln: (b, 0, 0)),
                pl.BlockSpec((1, ps, R),
                             lambda b, p, bt, ln: (bt[b, p], 0, 0)),
                pl.BlockSpec((1, ps, Dr),
                             lambda b, p, bt, ln: (bt[b, p], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, R),
                                   lambda b, p, bt, ln: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, R), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, R), q_c.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_c, q_rope, ckv_pages, krope_pages)
