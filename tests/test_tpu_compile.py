"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Nothing runs: each kernel is lowered with ``interpret=False`` and
compiled by the TPU compiler for a chip that is described, not attached,
at the widths of the models that use it (stablelm-1.6b attention,
deepseek-v2-lite MLA and routing, zamba2-1.2b SSD, the paper's GRU).
That catches what interpret mode cannot: blocks that do not tile, ops
Mosaic cannot lower, kernels that overflow VMEM.

The topology is described inside a module-scoped fixture (never at
import), because only one process at a time may load the TPU library,
and the persistent compilation cache is off around these compiles (an
entry compiled for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setitem(os.environ, "TPU_LOG_DIR",
               os.environ.get("TPU_LOG_DIR", "disabled"))
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    mp.undo()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# stablelm-1.6b attention: 32 heads (kv 32), head_dim 64
H, HKV, D = 32, 32, 64
# deepseek-v2-lite MLA: 16 heads, kv_lora_rank 512, rope dim 64
MLA_H, MLA_R, MLA_DR = 16, 512, 64
B, PS, PAGES_PER_SEQ = 8, 16, 64              # 8 rows x 1024 tokens
NUM_PAGES = B * PAGES_PER_SEQ + 1             # + the scratch page


def test_flash_attention_compiles(one_chip):
    _compile(one_chip, lambda q, k, v: ops.flash_attention(q, k, v),
             *[((H, 512, D), BF16)] * 3)


def test_decode_attention_compiles(one_chip):
    _compile(one_chip,
             lambda q, k, v, m: ops.decode_attention(q, k, v, m, bk=128),
             ((B, H, D), BF16), ((B, 1024, HKV, D), BF16),
             ((B, 1024, HKV, D), BF16), ((B, 1024), jnp.bool_))


def test_paged_decode_attention_compiles(one_chip):
    _compile(one_chip, ops.paged_decode_attention,
             ((B, H, D), BF16), ((NUM_PAGES, PS, HKV, D), BF16),
             ((NUM_PAGES, PS, HKV, D), BF16), ((B, PAGES_PER_SEQ), I32),
             ((B,), I32))


def test_paged_mla_decode_attention_compiles(one_chip):
    scale = (128 + MLA_DR) ** -0.5
    _compile(one_chip,
             lambda qc, qr, c, kr, bt, ln: ops.paged_mla_decode_attention(
                 qc, qr, c, kr, bt, ln, scale=scale),
             ((B, MLA_H, MLA_R), BF16), ((B, MLA_H, MLA_DR), BF16),
             ((NUM_PAGES, PS, MLA_R), BF16), ((NUM_PAGES, PS, MLA_DR), BF16),
             ((B, PAGES_PER_SEQ), I32), ((B,), I32))


def test_topk_router_compiles(one_chip):
    # deepseek-v2-lite routing: 64 experts, top-6
    _compile(one_chip, lambda lg: ops.topk_router(lg, 6),
             ((1024, 64), F32))


def test_gru_seq_compiles(one_chip):
    # gru-traffic: hidden 128, 12-step history windows
    _compile(one_chip, ops.gru_seq,
             ((16, 12, 3 * 128), F32), ((16, 128), F32), ((128, 3 * 128), F32))


def test_fedavg_reduce_compiles(one_chip):
    # 4 cluster replicas of a flattened gru-traffic-sized vector
    _compile(one_chip, ops.fedavg_reduce, ((4, 150_016), F32), ((4,), F32))


def test_mamba_chunk_scan_compiles(one_chip):
    # zamba2-1.2b SSD: d_inner 4096 = 64 heads x 64, state 64, chunk 128
    Hs, P, N, L = 64, 64, 64, 256
    _compile(one_chip,
             lambda x, dt, a, b, c: ops.mamba_chunk_scan(x, dt, a, b, c,
                                                         chunk=128),
             ((1, L, Hs, P), F32), ((1, L, Hs), F32), ((Hs,), F32),
             ((1, L, N), F32), ((1, L, N), F32))
