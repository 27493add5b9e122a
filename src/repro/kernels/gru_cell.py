"""Fused GRU sequence Pallas kernel — the paper's own model (traffic GRU)
is the inference payload of the whole orchestration scheme, so its cell
is the per-request hot loop on device/edge replicas.

The input projection x@W_x+b is a single big matmul done OUTSIDE the
kernel (MXU-friendly); the kernel runs the sequential recurrence with the
hidden state resident in VMEM, fusing the three gate nonlinearities and
the h@W_h matmul per step.  Grid: (B/bb,) batch blocks."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gru_kernel(xw_ref, h0_ref, wh_ref, o_ref, h_ref, *, T: int, h: int):
    h_ref[...] = h0_ref[...].astype(jnp.float32)
    wh = wh_ref[...].astype(jnp.float32)

    def step(t, _):
        xt = xw_ref[:, t, :].astype(jnp.float32)      # (bb, 3h)
        hw = jnp.dot(h_ref[...], wh,
                     preferred_element_type=jnp.float32)
        xr, xz, xn = xt[:, :h], xt[:, h:2 * h], xt[:, 2 * h:]
        hr, hz, hn = hw[:, :h], hw[:, h:2 * h], hw[:, 2 * h:]
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        h2 = (1.0 - z) * n + z * h_ref[...]
        h_ref[...] = h2
        o_ref[:, t, :] = h2.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, T, step, 0)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def gru_seq(xw: jax.Array, h0: jax.Array, w_h: jax.Array, *, bb: int = 8,
            interpret: bool = False) -> jax.Array:
    """xw (B,T,3h) precomputed input projection; h0 (B,h); w_h (h,3h).
    Returns hidden states (B,T,h)."""
    B, T, h3 = xw.shape
    h = h3 // 3
    bb = min(bb, B)
    assert B % bb == 0
    kernel = functools.partial(_gru_kernel, T=T, h=h)
    return pl.pallas_call(
        kernel,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, T, 3 * h), lambda i: (i, 0, 0)),
            pl.BlockSpec((bb, h), lambda i: (i, 0)),
            pl.BlockSpec((h, 3 * h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, T, h), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, h), xw.dtype),
        scratch_shapes=[pltpu.VMEM((bb, h), jnp.float32)],
        interpret=interpret,
    )(xw, h0, w_h)
