"""Per-kernel microbenchmarks (interpret mode on CPU — correctness-path
timing only; TPU wall times come from the roofline model, since interpret
mode executes the kernel body in Python)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from benchmarks.common import emit, time_us

R = np.random.default_rng(0)


def _a(shape, dtype=jnp.float32, s=1.0):
    return jnp.asarray(R.normal(size=shape) * s, dtype)


def run():
    q = _a((4, 256, 64))
    k = _a((4, 256, 64))
    v = _a((4, 256, 64))
    f = lambda: jax.block_until_ready(
        ops.flash_attention(q, k, v, bq=128, bk=128, interpret=True))
    r = lambda: jax.block_until_ready(ref.flash_attention_ref(q, k, v))
    emit("kernel_flash_attn_256", time_us(f), f"ref_us={time_us(r):.0f}")

    qd = _a((2, 8, 64))
    kd = _a((2, 512, 2, 64))
    vd = _a((2, 512, 2, 64))
    valid = jnp.ones((2, 512), bool)
    f = lambda: jax.block_until_ready(
        ops.decode_attention(qd, kd, vd, valid, interpret=True))
    r = lambda: jax.block_until_ready(ref.decode_attention_ref(qd, kd, vd,
                                                               valid))
    emit("kernel_decode_attn_512", time_us(f), f"ref_us={time_us(r):.0f}")

    xw = _a((16, 12, 384))
    h0 = _a((16, 128))
    wh = _a((128, 384), s=0.1)
    f = lambda: jax.block_until_ready(ops.gru_seq(xw, h0, wh, interpret=True))
    r = lambda: jax.block_until_ready(ref.gru_seq_ref(xw, h0, wh))
    emit("kernel_gru_seq_16x12", time_us(f), f"ref_us={time_us(r):.0f}")

    st = _a((20, 150_000))
    w = jnp.ones(20)
    f = lambda: jax.block_until_ready(ops.fedavg_reduce(st, w, interpret=True))
    r = lambda: jax.block_until_ready(ref.fedavg_reduce_ref(st, w))
    emit("kernel_fedavg_150k", time_us(f), f"ref_us={time_us(r):.0f}")

    lg = _a((1024, 64))
    f = lambda: jax.block_until_ready(ops.topk_router(lg, 6, interpret=True))
    r = lambda: jax.block_until_ready(ref.topk_router_ref(lg, 6))
    emit("kernel_topk_router_1k", time_us(f), f"ref_us={time_us(r):.0f}")

    x = _a((2, 128, 4, 16))
    dt = jnp.asarray(R.uniform(0.01, 0.2, (2, 128, 4)), jnp.float32)
    A = jnp.asarray(-R.uniform(0.5, 2.0, 4), jnp.float32)
    Bm, Cm = _a((2, 128, 8)), _a((2, 128, 8))
    f = lambda: jax.block_until_ready(
        ops.mamba_chunk_scan(x, dt, A, Bm, Cm, chunk=32, interpret=True))
    emit("kernel_mamba_scan_128", time_us(f), "")


if __name__ == "__main__":
    run()
