"""Pallas kernel validation: shape/dtype sweeps, assert_allclose against
the pure-jnp oracles in kernels/ref.py.  Kernels default to compiling
for the TPU, so every call here passes ``interpret=True`` (CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref

R = np.random.default_rng(0)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(R.normal(size=shape) * scale, dtype)


TOL = {jnp.float32: dict(atol=3e-5, rtol=3e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


@pytest.mark.parametrize("T,D,bq,bk", [(128, 64, 64, 64), (256, 32, 64, 128),
                                       (256, 128, 128, 64)])
@pytest.mark.parametrize("window", [0, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(T, D, bq, bk, window, dtype):
    q, k, v = (_arr((2, T, D), dtype) for _ in range(3))
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            bq=bq, bk=bk, interpret=True)
    r = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    assert o.dtype == q.dtype
    assert_allclose(np.asarray(o, np.float32), np.asarray(r, np.float32),
                    **TOL[dtype])


@pytest.mark.parametrize("H,Hkv,C,bk", [(8, 2, 256, 64), (4, 4, 128, 128),
                                        (16, 2, 512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(H, Hkv, C, bk, dtype):
    B, D = 2, 64
    q = _arr((B, H, D), dtype)
    k = _arr((B, C, Hkv, D), dtype)
    v = _arr((B, C, Hkv, D), dtype)
    valid = jnp.asarray(R.uniform(size=(B, C)) < 0.8)
    valid = valid.at[:, 0].set(True)     # at least one valid slot
    o = ops.decode_attention(q, k, v, valid, bk=bk, interpret=True)
    r = ref.decode_attention_ref(q, k, v, valid)
    assert_allclose(np.asarray(o, np.float32), np.asarray(r, np.float32),
                    **TOL[dtype])


@pytest.mark.parametrize("B,T,h,bb", [(8, 12, 32, 4), (4, 24, 64, 4),
                                      (2, 8, 128, 2)])
def test_gru_seq_sweep(B, T, h, bb):
    xw = _arr((B, T, 3 * h))
    h0 = _arr((B, h))
    wh = _arr((h, 3 * h), scale=0.1)
    o = ops.gru_seq(xw, h0, wh, bb=bb, interpret=True)
    r = ref.gru_seq_ref(xw, h0, wh)
    assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("C,N,bn", [(20, 1000, 256), (4, 513, 128),
                                    (32, 4096, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_reduce_sweep(C, N, bn, dtype):
    x = _arr((C, N), dtype)
    w = jnp.asarray(R.uniform(0.5, 2.0, C), jnp.float32)
    o = ops.fedavg_reduce(x, w, bn=bn, interpret=True)
    r = ref.fedavg_reduce_ref(x, w)
    assert_allclose(np.asarray(o, np.float32), np.asarray(r, np.float32),
                    **TOL[dtype])


@pytest.mark.parametrize("T,E,k,bt", [(64, 16, 4, 32), (128, 60, 4, 64),
                                      (32, 64, 6, 32)])
def test_topk_router_sweep(T, E, k, bt):
    logits = _arr((T, E))
    w1, i1 = ops.topk_router(logits, k, bt=bt, interpret=True)
    w2, i2 = ref.topk_router_ref(logits, k)
    assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-6)
    assert (np.asarray(i1) == np.asarray(i2)).all()


@pytest.mark.parametrize("L,H,P,N,chunk", [(128, 4, 16, 8, 32),
                                           (64, 2, 32, 16, 64),
                                           (96, 8, 8, 8, 32)])
def test_mamba_chunk_scan_sweep(L, H, P, N, chunk):
    B = 2
    x = _arr((B, L, H, P))
    dt = jnp.asarray(R.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    A = jnp.asarray(-R.uniform(0.5, 2.0, H), jnp.float32)
    Bm = _arr((B, L, N))
    Cm = _arr((B, L, N))
    y, s = ops.mamba_chunk_scan(x, dt, A, Bm, Cm, chunk=chunk,
                                interpret=True)
    yr, sr = ref.mamba_chunk_ref(x, dt, A, Bm[:, :, None, :],
                                 Cm[:, :, None, :], chunk)
    assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4, rtol=5e-4)
    assert_allclose(np.asarray(s), np.asarray(sr), atol=5e-4, rtol=5e-4)


def test_mamba_head_blocking_equivalence():
    """bh < H must give identical results (VMEM tiling invariance)."""
    B, L, H, P, N = 1, 64, 4, 8, 8
    x = _arr((B, L, H, P))
    dt = jnp.asarray(R.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    A = jnp.asarray(-R.uniform(0.5, 2.0, H), jnp.float32)
    Bm, Cm = _arr((B, L, N)), _arr((B, L, N))
    y1, s1 = ops.mamba_chunk_scan(x, dt, A, Bm, Cm, chunk=32, bh=4,
                                  interpret=True)
    y2, s2 = ops.mamba_chunk_scan(x, dt, A, Bm, Cm, chunk=32, bh=2,
                                  interpret=True)
    assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5, rtol=1e-5)
    assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention (block-table gather through scalar prefetch)
# ---------------------------------------------------------------------------

def _block_tables(B, Pseq, num_pages):
    """Distinct page ids per (seq, page) slot — a permutation, so the
    kernel's gather is exercised on genuinely scattered pages."""
    ids = R.permutation(num_pages)[:B * Pseq].reshape(B, Pseq)
    return jnp.asarray(ids, jnp.int32)


@pytest.mark.parametrize("H,Hkv,ps,Pseq", [(8, 2, 16, 4), (4, 4, 8, 6)])
@pytest.mark.parametrize("soft_cap,window", [(0.0, None), (30.0, None),
                                             (0.0, 20)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_sweep(H, Hkv, ps, Pseq, soft_cap, window,
                                      dtype):
    B, D = 2, 64
    num_pages = B * Pseq + 3
    q = _arr((B, H, D), dtype)
    k_pages = _arr((num_pages, ps, Hkv, D), dtype)
    v_pages = _arr((num_pages, ps, Hkv, D), dtype)
    bt = _block_tables(B, Pseq, num_pages)
    lengths = jnp.asarray(R.integers(1, Pseq * ps + 1, (B,)), jnp.int32)
    o = ops.paged_decode_attention(q, k_pages, v_pages, bt, lengths,
                                   soft_cap=soft_cap, window=window,
                                   interpret=True)
    r = ref.paged_decode_attention_ref(q, k_pages, v_pages, bt, lengths,
                                       soft_cap=soft_cap, window=window)
    assert o.dtype == q.dtype
    assert_allclose(np.asarray(o, np.float32), np.asarray(r, np.float32),
                    **TOL[dtype])


@pytest.mark.parametrize("H,R_dim,Dr,ps,Pseq", [(8, 64, 16, 16, 4),
                                                (4, 128, 32, 8, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_mla_decode_attention_sweep(H, R_dim, Dr, ps, Pseq, dtype):
    B = 2
    num_pages = B * Pseq + 2
    q_c = _arr((B, H, R_dim), dtype)
    q_rope = _arr((B, H, Dr), dtype)
    ckv_pages = _arr((num_pages, ps, R_dim), dtype)
    krope_pages = _arr((num_pages, ps, Dr), dtype)
    bt = _block_tables(B, Pseq, num_pages)
    lengths = jnp.asarray(R.integers(1, Pseq * ps + 1, (B,)), jnp.int32)
    scale = 1.0 / np.sqrt(R_dim + Dr)
    o = ops.paged_mla_decode_attention(q_c, q_rope, ckv_pages, krope_pages,
                                       bt, lengths, scale=scale,
                                       interpret=True)
    r = ref.paged_mla_decode_attention_ref(q_c, q_rope, ckv_pages,
                                           krope_pages, bt, lengths,
                                           scale=scale)
    assert o.dtype == q_c.dtype
    assert_allclose(np.asarray(o, np.float32), np.asarray(r, np.float32),
                    **TOL[dtype])


def test_paged_decode_attention_matches_dense_gather():
    """Paged layout is an addressing change only: gathering the pages
    back into a contiguous cache and calling the dense decode oracle
    must agree with the paged kernel."""
    B, H, Hkv, D, ps, Pseq = 2, 8, 2, 64, 8, 4
    num_pages = B * Pseq + 1
    q = _arr((B, H, D))
    k_pages = _arr((num_pages, ps, Hkv, D))
    v_pages = _arr((num_pages, ps, Hkv, D))
    bt = _block_tables(B, Pseq, num_pages)
    lengths = jnp.asarray([Pseq * ps, 11], jnp.int32)
    o = ops.paged_decode_attention(q, k_pages, v_pages, bt, lengths,
                                   interpret=True)
    k = k_pages[bt].reshape(B, Pseq * ps, Hkv, D)
    v = v_pages[bt].reshape(B, Pseq * ps, Hkv, D)
    valid = jnp.arange(Pseq * ps)[None, :] < lengths[:, None]
    r = ref.decode_attention_ref(q, k, v, valid)
    assert_allclose(np.asarray(o), np.asarray(r), atol=3e-5, rtol=3e-5)
