"""Mamba2 SSD chunk Pallas kernel (zamba2's compute hot spot).

Grid: (batch, head-block, chunk).  The chunk axis is sequential
("arbitrary"): the (N x P) SSD state of every head in the block is
carried across it in VMEM scratch, while each step's chunk of x / dt /
B / C is DMA'd in by the pipeline.  Per head the intra-chunk work is two
MXU matmuls (C.B^T decay-masked, then score @ u) and the inter-chunk
state update is a rank-Q outer-product accumulation (B^T @ u).

Layout: heads are tiled by ``bh``; a block's minor dims are (bh, P), so
on the TPU ``bh`` is H (or a multiple of 128) and each head's (Q, P)
column is read with a strided load.  B/C are per-group (ngroups=1 for
the assigned configs) and shared by every head in the tile."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_final_ref,
                s_ref, *, Q: int, bh: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    Bm = b_ref[0, 0].astype(jnp.float32)              # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)              # (Q, N)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = ii >= jj
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Qi,Qj)
    la = dt_ref[0, 0].astype(jnp.float32) \
        * a_ref[...].astype(jnp.float32)              # (Q, bh) log decay
    # inclusive prefix sum as a lower-triangular mask product (Mosaic
    # has no cumsum lowering; this is one small MXU matmul)
    cum = jnp.dot(lower.astype(jnp.float32), la,
                  preferred_element_type=jnp.float32)  # (Q, bh)
    for h in range(bh):
        dt = dt_ref[0, 0, :, h:h + 1].astype(jnp.float32)   # (Q, 1)
        c_col = cum[:, h:h + 1]                             # (Q, 1)
        # the same prefix sums as a row: pick the diagonal of the
        # lane-broadcast column (a VPU reduction, no transpose)
        c_row = jnp.sum(jnp.where(ii == jj, c_col, 0.0), axis=0,
                        keepdims=True)                      # (1, Q)
        u = x_ref[0, 0, :, h, :].astype(jnp.float32) * dt   # (Q, P)

        # intra-chunk: decay-masked scores (Qi, Qj)
        decay = jnp.where(lower, jnp.exp(c_col - c_row), 0.0)
        y = jnp.dot(cb * decay, u, preferred_element_type=jnp.float32)

        # inter-chunk: contribution of the carried state
        s_prev = s_ref[h]                                   # (N, P)
        y += jnp.dot(Cm, s_prev,
                     preferred_element_type=jnp.float32) * jnp.exp(c_col)
        y_ref[0, 0, :, h, :] = y.astype(y_ref.dtype)

        # state update: S = a_chunk * S_prev + sum_j wlast_j B_j (x) u_j
        c_last = c_col[Q - 1:Q, :]                          # (1, 1)
        s_loc = jax.lax.dot_general(
            Bm, u * jnp.exp(c_last - c_col), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (N, P)
        # (1,1) -> (1,P) -> (N,P): Mosaic broadcasts one axis at a time
        a_chunk = jnp.exp(jnp.broadcast_to(c_last, (1, s_prev.shape[1])))
        s_ref[h] = a_chunk * s_prev + s_loc

    @pl.when(ci == pl.num_programs(2) - 1)
    def _done():
        s_final_ref[0] = s_ref[...].astype(s_final_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "bh", "interpret"))
def mamba_chunk_scan(x: jax.Array, dt: jax.Array, A: jax.Array,
                     Bm: jax.Array, Cm: jax.Array, *, chunk: int = 64,
                     bh: int = 0, interpret: bool = False):
    """x (B,L,H,P); dt (B,L,H) post-softplus; A (H,) negative;
    Bm/Cm (B,L,N) (ngroups=1).  Returns (y (B,L,H,P), state (B,H,N,P))."""
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    assert L % chunk == 0
    nchunks = L // chunk
    bh = bh or H
    assert H % bh == 0
    xr = x.reshape(B, nchunks, chunk, H, P)
    dtr = dt.reshape(B, nchunks, chunk, H)
    Br = Bm.reshape(B, nchunks, chunk, N)
    Cr = Cm.reshape(B, nchunks, chunk, N)
    kernel = functools.partial(_ssd_kernel, Q=chunk, bh=bh)
    y, s = pl.pallas_call(
        kernel,
        grid=(B, H // bh, nchunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, bh, P),
                         lambda b, h, c: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, chunk, bh), lambda b, h, c: (b, c, 0, h)),
            pl.BlockSpec((1, bh), lambda b, h, c: (0, h)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, bh, P),
                         lambda b, h, c: (b, c, 0, h, 0)),
            pl.BlockSpec((1, bh, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nchunks, chunk, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bh, N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xr, dtr, A.reshape(1, H), Br, Cr)
    return y.reshape(B, L, H, P), s
