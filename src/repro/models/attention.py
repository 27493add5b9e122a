"""Attention: GQA (full / sliding-window / local:global), DeepSeek MLA,
encoder-decoder cross attention; training/prefill and single-token decode.

Training/prefill attention is *query-chunked* ("lazy flash"): for long
sequences we scan over query chunks so peak memory is O(chunk * S) instead
of O(S^2).  The Pallas flash kernels in ``repro.kernels`` are the TPU hot
path; this module is the XLA path used for CPU execution and dry-run
lowering (selected by config).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.models.common import ParamBuilder, shard
from repro.models.rope import apply_rope, yarn_mscale

_NEG_INF = -2.0e38  # fp32-safe mask value
Q_CHUNK_THRESHOLD = 8192
Q_CHUNK = 2048


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_gqa(pb: ParamBuilder, path: str, d_model: int,
             a: AttentionConfig) -> None:
    hd = a.head_dim
    pb.param(f"{path}/wq", (d_model, a.num_heads, hd),
             ("embed", "heads", "head_dim"))
    pb.param(f"{path}/wk", (d_model, a.num_kv_heads, hd),
             ("embed", "kv_heads", "head_dim"))
    pb.param(f"{path}/wv", (d_model, a.num_kv_heads, hd),
             ("embed", "kv_heads", "head_dim"))
    pb.param(f"{path}/wo", (a.num_heads, hd, d_model),
             ("heads", "head_dim", "embed"))
    if a.qk_norm:
        pb.param(f"{path}/q_norm", (hd,), ("head_dim",), init="ones")
        pb.param(f"{path}/k_norm", (hd,), ("head_dim",), init="ones")


def init_mla(pb: ParamBuilder, path: str, d_model: int,
             a: AttentionConfig) -> None:
    m = a.mla
    H = a.num_heads
    pb.param(f"{path}/wq", (d_model, H, m.qk_nope_head_dim + m.qk_rope_head_dim),
             ("embed", "heads", "head_dim"))
    pb.param(f"{path}/w_dkv", (d_model, m.kv_lora_rank), ("embed", "kv_lora"))
    pb.param(f"{path}/w_krope", (d_model, m.qk_rope_head_dim),
             ("embed", "head_dim"))
    pb.param(f"{path}/kv_norm", (m.kv_lora_rank,), ("kv_lora",), init="ones")
    pb.param(f"{path}/w_uk", (m.kv_lora_rank, H, m.qk_nope_head_dim),
             ("kv_lora", "heads", "head_dim"))
    pb.param(f"{path}/w_uv", (m.kv_lora_rank, H, m.v_head_dim),
             ("kv_lora", "heads", "head_dim"))
    pb.param(f"{path}/wo", (H, m.v_head_dim, d_model),
             ("heads", "head_dim", "embed"))


# ---------------------------------------------------------------------------
# Core scaled-dot-product with grouped heads + masking
# ---------------------------------------------------------------------------

def _rms_head_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
          q_pos: jax.Array, k_pos: jax.Array,
          causal: bool, window, soft_cap: float,
          k_valid: Optional[jax.Array] = None,
          scale: Optional[float] = None) -> jax.Array:
    """q (B,Tq,Hq,D), k/v (B,Tk,Hkv,D'), positions (Tq,)/(Tk,).

    ``window`` may be None, a python int, or a traced scalar (per-layer
    local:global selection inside a homogeneous layer scan).  ``scale``
    multiplies the scores (``1/sqrt(D)`` if None).
    Returns (B,Tq,Hq,Dv)."""
    B, Tq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Tq, Hkv, G, D)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    if soft_cap:
        scores = jnp.tanh(scores / soft_cap) * soft_cap
    d = q_pos[:, None].astype(jnp.int32) - k_pos[None, :].astype(jnp.int32)
    mask = jnp.ones(d.shape, bool) if not causal else (d >= 0)
    if window is not None:
        mask &= d < window
    if k_valid is not None:
        mask &= k_valid[:, None, None, None, :]
        scores = jnp.where(mask, scores, _NEG_INF)
    else:
        scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Tq, Hq, v.shape[-1])


def _chunked_sdpa(q, k, v, q_pos, k_pos, causal, window, soft_cap, scale):
    """Scan over query chunks: peak memory O(Q_CHUNK * Tk)."""
    B, Tq, Hq, D = q.shape
    n = Tq // Q_CHUNK
    qs = q.reshape(B, n, Q_CHUNK, Hq, D).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(n, Q_CHUNK)

    def step(_, qc):
        qi, pi = qc
        o = _sdpa(qi, k, v, pi, k_pos, causal, window, soft_cap,
                  scale=scale)
        return None, o

    _, outs = jax.lax.scan(step, None, (qs, ps))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Tq, Hq, v.shape[-1])


def sdpa(q, k, v, q_pos, k_pos, *, causal=True, window=None, soft_cap=0.0,
         k_valid=None, scale=None):
    big = q.shape[1] >= Q_CHUNK_THRESHOLD and q.shape[1] % Q_CHUNK == 0
    if big and k_valid is None:
        return _chunked_sdpa(q, k, v, q_pos, k_pos, causal, window, soft_cap,
                             scale)
    return _sdpa(q, k, v, q_pos, k_pos, causal, window, soft_cap, k_valid,
                 scale=scale)


# ---------------------------------------------------------------------------
# GQA forward (train / prefill) and decode
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Ring-buffer KV cache.  ``k``/``v``: (B, C, Hkv, D); ``pos``: (B, C)
    absolute position of each slot (-1 = empty); ``index``: () next write
    slot (mod C for windowed caches)."""
    k: jax.Array
    v: jax.Array
    pos: jax.Array
    index: jax.Array

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


def init_kv_cache(batch: int, capacity: int, num_kv_heads: int,
                  head_dim: int, dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, num_kv_heads, head_dim), dtype),
        v=jnp.zeros((batch, capacity, num_kv_heads, head_dim), dtype),
        pos=jnp.full((batch, capacity), -1, jnp.int32),
        index=jnp.zeros((), jnp.int32),
    )


def gqa_forward(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
                positions: jax.Array, inv_freq: Optional[jax.Array],
                window=None, causal: bool = True,
                kv_source: Optional[jax.Array] = None) -> jax.Array:
    """x (B,S,d).  ``kv_source`` switches to cross-attention (keys/values
    from encoder output; no rope, no causal mask)."""
    src = x if kv_source is None else kv_source
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if a.qk_norm:
        q = _rms_head_norm(q, p["q_norm"])
        k = _rms_head_norm(k, p["k_norm"])
    if kv_source is None:
        k_pos = positions
        if inv_freq is not None:
            q = apply_rope(q, positions, inv_freq)
            k = apply_rope(k, positions, inv_freq)
    else:
        causal = False
        k_pos = jnp.arange(src.shape[1], dtype=jnp.int32)
    q = shard(q, "batch", "seq", "heads_act", None)
    k = shard(k, "batch", "seq", "kv_heads_act", None)
    v = shard(v, "batch", "seq", "kv_heads_act", None)
    out = sdpa(q, k, v, positions, k_pos, causal=causal, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def gqa_decode(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
               pos: jax.Array, cache: KVCache,
               inv_freq: Optional[jax.Array], window=None,
               cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
               ) -> Tuple[jax.Array, KVCache]:
    """Single-token decode.  x (B,1,d); pos () absolute position.
    With ``cross_kv`` the cache is ignored (encoder KV precomputed)."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if a.qk_norm:
        q = _rms_head_norm(q, p["q_norm"])
    if cross_kv is not None:
        ck, cv = cross_kv
        k_pos = jnp.arange(ck.shape[1], dtype=jnp.int32)
        out = sdpa(q, ck, cv, pos[None], k_pos, causal=False)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qk_norm:
        k = _rms_head_norm(k, p["k_norm"])
    if inv_freq is not None:
        q = apply_rope(q, pos[None][None].repeat(B, 0), inv_freq)
        k = apply_rope(k, pos[None][None].repeat(B, 0), inv_freq)
    slot = cache.index % cache.capacity
    new_cache = KVCache(
        k=jax.lax.dynamic_update_slice_in_dim(
            cache.k, k.astype(cache.k.dtype), slot, axis=1),
        v=jax.lax.dynamic_update_slice_in_dim(
            cache.v, v.astype(cache.v.dtype), slot, axis=1),
        pos=jax.lax.dynamic_update_slice_in_dim(
            cache.pos, jnp.full((B, 1), pos, jnp.int32), slot, axis=1),
        index=cache.index + 1,
    )
    valid = new_cache.pos >= 0
    if window is not None:
        valid &= (pos - new_cache.pos) < window
    # one query vs cache slots; mask by stored absolute positions
    # (cache may be stored quantized, e.g. f8 — upcast for the dot)
    out = _sdpa(q, new_cache.k.astype(q.dtype), new_cache.v.astype(q.dtype),
                pos[None], jnp.zeros((cache.capacity,), jnp.int32),
                causal=False, window=None, soft_cap=a.logit_soft_cap,
                k_valid=valid)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def _ring_write(buf: jax.Array, new: jax.Array, slots: jax.Array) -> jax.Array:
    """Scatter ``new`` (B,S,...) into ring slots along axis 1; entries whose
    slot index equals the capacity are dropped (pad / out-of-window)."""
    return buf.at[:, slots].set(new.astype(buf.dtype), mode="drop")


def prefill_slots(capacity: int, positions: jax.Array,
                  length: jax.Array) -> jax.Array:
    """Ring slot for each prompt position: the last ``min(length,
    capacity)`` valid positions land at ``pos % capacity``; everything else
    (right padding, positions older than the ring) maps to ``capacity``,
    which ``mode='drop'`` scatters discard."""
    keep = (positions < length) & (positions >= length - capacity)
    return jnp.where(keep, positions % capacity, capacity)


def gqa_prefill(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
                positions: jax.Array, length: jax.Array, cache: KVCache,
                inv_freq: Optional[jax.Array], window=None,
                ) -> Tuple[jax.Array, KVCache]:
    """Full-sequence prefill: identical math to :func:`gqa_forward` plus a
    one-shot ring write of the roped K/V for positions ``[0, length)``.
    ``x`` may be right-padded beyond ``length``; causality keeps pad keys
    out of every valid query's receptive field."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qk_norm:
        q = _rms_head_norm(q, p["q_norm"])
        k = _rms_head_norm(k, p["k_norm"])
    if inv_freq is not None:
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
    q = shard(q, "batch", "seq", "heads_act", None)
    k = shard(k, "batch", "seq", "kv_heads_act", None)
    v = shard(v, "batch", "seq", "kv_heads_act", None)
    out = sdpa(q, k, v, positions, positions, causal=True, window=window)
    slots = prefill_slots(cache.capacity, positions, length)
    pos_rows = jnp.broadcast_to(positions[None], (B, S))
    new_cache = KVCache(
        k=_ring_write(cache.k, k, slots),
        v=_ring_write(cache.v, v, slots),
        pos=cache.pos.at[:, slots].set(pos_rows, mode="drop"),
        index=jnp.asarray(length, jnp.int32),
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# Paged KV cache (block-table) variants
# ---------------------------------------------------------------------------

class PagedKVCache(NamedTuple):
    """Paged KV cache shared by all sequences of an engine: ``k_pages`` /
    ``v_pages`` (P+1, page_size, Hkv, D).  Page ids come from the
    :class:`~repro.serving.page_pool.PagePool`; token ``t`` of a sequence
    lives at page ``block_table[t // page_size]`` slot ``t % page_size``.
    The extra page (id P) is a scratch page: free batch rows point their
    whole block table at it so the batched decode write lands somewhere
    harmless."""
    k_pages: jax.Array
    v_pages: jax.Array

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[1]


class PagedMLACache(NamedTuple):
    """Paged compressed-latent cache: ``ckv_pages`` (P+1, page_size, R),
    ``krope_pages`` (P+1, page_size, Dr).  Same scratch-page convention
    as :class:`PagedKVCache`."""
    ckv_pages: jax.Array
    krope_pages: jax.Array

    @property
    def page_size(self) -> int:
        return self.ckv_pages.shape[1]


def init_paged_kv_cache(num_pages: int, page_size: int, num_kv_heads: int,
                        head_dim: int, dtype=jnp.bfloat16) -> PagedKVCache:
    return PagedKVCache(
        k_pages=jnp.zeros((num_pages + 1, page_size, num_kv_heads,
                           head_dim), dtype),
        v_pages=jnp.zeros((num_pages + 1, page_size, num_kv_heads,
                           head_dim), dtype),
    )


def init_paged_mla_cache(num_pages: int, page_size: int, a: AttentionConfig,
                         dtype=jnp.bfloat16) -> PagedMLACache:
    m = a.mla
    return PagedMLACache(
        ckv_pages=jnp.zeros((num_pages + 1, page_size, m.kv_lora_rank),
                            dtype),
        krope_pages=jnp.zeros((num_pages + 1, page_size,
                               m.qk_rope_head_dim), dtype),
    )


def _page_write(pages: jax.Array, new: jax.Array, page_ids: jax.Array,
                slot_ids: jax.Array) -> jax.Array:
    """Scatter ``new`` (B, S, ...) into ``pages`` at (page_ids, slot_ids)
    (both (B, S)); ids equal to ``pages.shape[0]`` are dropped (padding)."""
    return pages.at[page_ids, slot_ids].set(new.astype(pages.dtype),
                                            mode="drop")


def prefill_page_ids(block_tables: jax.Array, positions: jax.Array,
                     length: jax.Array, page_size: int,
                     num_pages: int) -> Tuple[jax.Array, jax.Array]:
    """Page/slot id per prompt position for a one-shot paged prefill
    write.  ``block_tables`` (B, Pseq); ``positions`` (S,).  Positions at
    or past ``length`` (right padding) map to the out-of-bounds page id
    ``num_pages + 1`` so ``mode='drop'`` scatters discard them."""
    B = block_tables.shape[0]
    Pseq = block_tables.shape[1]
    pidx = jnp.clip(positions // page_size, 0, Pseq - 1)
    pages = jnp.take_along_axis(block_tables,
                                jnp.broadcast_to(pidx[None], (B, pidx.shape[0])),
                                axis=1)
    keep = (positions < length) & (positions // page_size < Pseq)
    pages = jnp.where(keep[None], pages, num_pages + 1)
    slots = jnp.broadcast_to((positions % page_size)[None], pages.shape)
    return pages, slots


def paged_gqa_prefill(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
                      positions: jax.Array, length: jax.Array,
                      cache: PagedKVCache, block_tables: jax.Array,
                      inv_freq: Optional[jax.Array], window=None,
                      ) -> Tuple[jax.Array, PagedKVCache]:
    """Identical attention math to :func:`gqa_prefill`; only the cache
    write differs — K/V scatter through the block table into pages."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qk_norm:
        q = _rms_head_norm(q, p["q_norm"])
        k = _rms_head_norm(k, p["k_norm"])
    if inv_freq is not None:
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
    out = sdpa(q, k, v, positions, positions, causal=True, window=window)
    ps = cache.page_size
    num_pages = cache.k_pages.shape[0] - 1
    pages, slots = prefill_page_ids(block_tables, positions, length, ps,
                                    num_pages)
    new_cache = PagedKVCache(
        k_pages=_page_write(cache.k_pages, k, pages, slots),
        v_pages=_page_write(cache.v_pages, v, pages, slots),
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def paged_gqa_decode(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
                     pos: jax.Array, cache: PagedKVCache,
                     block_tables: jax.Array,
                     inv_freq: Optional[jax.Array], window=None,
                     ) -> Tuple[jax.Array, PagedKVCache]:
    """Batched single-token paged decode.  Unlike :func:`gqa_decode`
    (vmapped per slot over private caches) every row here shares the one
    page array, so ``pos`` is per-row (B,) and the batch advances in one
    program.  Math mirrors :func:`gqa_decode` exactly: same projections,
    rope, ``_sdpa`` mask path — greedy parity with the dense engine."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qk_norm:
        q = _rms_head_norm(q, p["q_norm"])
        k = _rms_head_norm(k, p["k_norm"])
    if inv_freq is not None:
        q = apply_rope(q, pos[:, None], inv_freq)
        k = apply_rope(k, pos[:, None], inv_freq)
    ps = cache.page_size
    Pseq = block_tables.shape[1]
    pidx = jnp.take_along_axis(block_tables, (pos // ps)[:, None], axis=1)
    slot = (pos % ps)[:, None]
    new_cache = PagedKVCache(
        k_pages=_page_write(cache.k_pages, k, pidx, slot),
        v_pages=_page_write(cache.v_pages, v, pidx, slot),
    )
    C = Pseq * ps
    kg = new_cache.k_pages[block_tables].reshape(B, C, *k.shape[2:])
    vg = new_cache.v_pages[block_tables].reshape(B, C, *v.shape[2:])
    tok = jnp.arange(C, dtype=jnp.int32)[None, :]
    valid = tok <= pos[:, None]
    if window is not None:
        valid &= (pos[:, None] - tok) < window
    out = _sdpa(q, kg.astype(q.dtype), vg.astype(q.dtype),
                jnp.zeros((1,), jnp.int32), jnp.zeros((C,), jnp.int32),
                causal=False, window=None, soft_cap=a.logit_soft_cap,
                k_valid=valid)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def paged_mla_prefill(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
                      positions: jax.Array, length: jax.Array,
                      cache: PagedMLACache, block_tables: jax.Array,
                      inv_freq: Optional[jax.Array],
                      ) -> Tuple[jax.Array, PagedMLACache]:
    """:func:`mla_prefill` math with the latent write paged."""
    out, c_kv, k_rope = _mla_attend(p, a, x, positions, inv_freq)
    ps = cache.page_size
    num_pages = cache.ckv_pages.shape[0] - 1
    pages, slots = prefill_page_ids(block_tables, positions, length, ps,
                                    num_pages)
    new_cache = PagedMLACache(
        ckv_pages=_page_write(cache.ckv_pages, c_kv, pages, slots),
        krope_pages=_page_write(cache.krope_pages, k_rope, pages, slots),
    )
    return _mla_out(p, a, out), new_cache


def paged_mla_decode(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
                     pos: jax.Array, cache: PagedMLACache,
                     block_tables: jax.Array,
                     inv_freq: Optional[jax.Array],
                     ) -> Tuple[jax.Array, PagedMLACache]:
    """Absorbed MLA decode over the paged latent cache; per-row ``pos``
    (B,), math mirrors :func:`mla_decode`."""
    _no_adapters(p)
    B = x.shape[0]
    q_nope, q_rope = _mla_q(p, a, x, pos[:, None], inv_freq)
    c_new, kr_new = _mla_latents(p, a, x, pos[:, None], inv_freq)
    ps = cache.page_size
    Pseq = block_tables.shape[1]
    pidx = jnp.take_along_axis(block_tables, (pos // ps)[:, None], axis=1)
    slot = (pos % ps)[:, None]
    cache = PagedMLACache(
        ckv_pages=_page_write(cache.ckv_pages, c_new, pidx, slot),
        krope_pages=_page_write(cache.krope_pages, kr_new, pidx, slot),
    )
    C = Pseq * ps
    c_kv = cache.ckv_pages[block_tables].reshape(B, C, -1).astype(x.dtype)
    k_rope = cache.krope_pages[block_tables].reshape(B, C, -1).astype(x.dtype)
    q_c = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])
    s_nope = jnp.einsum("bshr,bcr->bhsc", q_c, c_kv)
    s_rope = jnp.einsum("bshr,bcr->bhsc", q_rope, k_rope)
    scores = (s_nope + s_rope).astype(jnp.float32) * mla_softmax_scale(a)
    tok = jnp.arange(C, dtype=jnp.int32)[None, :]
    valid = (tok <= pos[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhsc,bcr->bshr", probs, c_kv)
    out = jnp.einsum("bshr,rhk->bshk", ctx, p["w_uv"])
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2) forward + absorbed decode
# ---------------------------------------------------------------------------

class MLACache(NamedTuple):
    """Compressed KV cache: ``c_kv`` (B,C,R) latents, ``k_rope`` (B,C,Dr)."""
    c_kv: jax.Array
    k_rope: jax.Array
    pos: jax.Array
    index: jax.Array

    @property
    def capacity(self) -> int:
        return self.c_kv.shape[1]


def init_mla_cache(batch: int, capacity: int, a: AttentionConfig,
                   dtype=jnp.bfloat16) -> MLACache:
    m = a.mla
    return MLACache(
        c_kv=jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
        k_rope=jnp.zeros((batch, capacity, m.qk_rope_head_dim), dtype),
        pos=jnp.full((batch, capacity), -1, jnp.int32),
        index=jnp.zeros((), jnp.int32),
    )


def mla_softmax_scale(a: AttentionConfig) -> float:
    """``1/sqrt(qk_nope + qk_rope)``, times YaRN's ``mscale_all_dim``
    temperature squared where the config scales its rope."""
    m = a.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    rs = a.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def lora_delta(x: jax.Array, ad: Dict[str, jax.Array], scale: float,
               n_in: int = 1) -> jax.Array:
    """``x @ A @ B * scale`` over the trailing ``n_in`` dims of ``x``,
    for adapter ``ad = {"a": (*in, r), "b": (r, *out)}``.  Adapters with a
    leading client axis (``a`` (C, *in, r)) give client ``c``'s delta to
    the ``c``-th of ``C`` equal blocks of ``x``'s rows."""
    a, b = ad["a"], ad["b"]

    def one(x, a, b):
        h = jnp.tensordot(x, a.astype(x.dtype), n_in)
        return jnp.tensordot(h, b.astype(x.dtype), 1) * jnp.asarray(
            scale, x.dtype)

    if a.ndim == n_in + 1:
        return one(x, a, b)
    C = a.shape[0]
    out = jax.vmap(one)(x.reshape((C, -1) + x.shape[1:]), a, b)
    return out.reshape(x.shape[:1] + out.shape[2:])


def _no_adapters(p) -> None:
    if "lora" in p:
        raise NotImplementedError("absorbed MLA decode takes merged "
                                  "weights, not adapters")


def _mla_q(p, a, x, positions, inv_freq):
    m = a.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "lora" in p:
        q = q + lora_delta(x, p["lora"]["wq"], a.lora.scale)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    if inv_freq is not None:
        q_rope = apply_rope(q_rope, positions, inv_freq)
    return q_nope, q_rope


def _mla_latents(p, a, x, positions, inv_freq):
    m = a.mla
    c_kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_krope"])
    if "lora" in p:            # kv down-projection (HF kv_a_proj_with_mqa)
        delta = lora_delta(x, p["lora"]["kv_a"], a.lora.scale)
        c_kv = c_kv + delta[..., :m.kv_lora_rank]
        k_rope = k_rope + delta[..., m.kv_lora_rank:]
    c_kv = _rms_head_norm(c_kv, p["kv_norm"])
    if inv_freq is not None:
        k_rope = apply_rope(k_rope[:, :, None, :], positions,
                            inv_freq)[:, :, 0, :]
    return c_kv, k_rope


def _mla_kv(p, a, c_kv):
    m = a.mla
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"])
    if "lora" in p:            # kv up-projection (HF kv_b_proj)
        delta = lora_delta(c_kv, p["lora"]["kv_b"], a.lora.scale)
        k_nope = k_nope + delta[..., :m.qk_nope_head_dim]
        v = v + delta[..., m.qk_nope_head_dim:]
    return k_nope, v


def _mla_out(p, a, out):
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if "lora" in p:
        y = y + lora_delta(out, p["lora"]["wo"], a.lora.scale, n_in=2)
    return y


def _mla_attend(p, a, x, positions, inv_freq):
    """Full-sequence causal MLA: the per-head output before ``wo``, and
    the latents the cache keeps."""
    m = a.mla
    B, S, _ = x.shape
    q_nope, q_rope = _mla_q(p, a, x, positions, inv_freq)
    c_kv, k_rope = _mla_latents(p, a, x, positions, inv_freq)
    k_nope, v = _mla_kv(p, a, c_kv)
    # concat nope+rope per head (rope part broadcast across heads)
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                (B, S, a.num_heads, m.qk_rope_head_dim))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    out = sdpa(q_full, k_full, v, positions, positions, causal=True,
               scale=mla_softmax_scale(a))
    return out, c_kv, k_rope


def mla_forward(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
                positions: jax.Array, inv_freq: Optional[jax.Array],
                ) -> jax.Array:
    out, _, _ = _mla_attend(p, a, x, positions, inv_freq)
    return _mla_out(p, a, out)


def mla_prefill(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
                positions: jax.Array, length: jax.Array, cache: MLACache,
                inv_freq: Optional[jax.Array],
                ) -> Tuple[jax.Array, MLACache]:
    """Full-sequence MLA prefill: :func:`mla_forward` math plus a one-shot
    write of the compressed latents for positions ``[0, length)``."""
    B, S, _ = x.shape
    out, c_kv, k_rope = _mla_attend(p, a, x, positions, inv_freq)
    slots = prefill_slots(cache.capacity, positions, length)
    pos_rows = jnp.broadcast_to(positions[None], (B, S))
    new_cache = MLACache(
        c_kv=_ring_write(cache.c_kv, c_kv, slots),
        k_rope=_ring_write(cache.k_rope, k_rope, slots),
        pos=cache.pos.at[:, slots].set(pos_rows, mode="drop"),
        index=jnp.asarray(length, jnp.int32),
    )
    return _mla_out(p, a, out), new_cache


def mla_decode(p: Dict[str, Any], a: AttentionConfig, x: jax.Array,
               pos: jax.Array, cache: MLACache,
               inv_freq: Optional[jax.Array]) -> Tuple[jax.Array, MLACache]:
    """Absorbed MLA decode: queries projected into latent space so scores
    are computed against the *compressed* cache directly (beyond-paper
    efficiency; DeepSeek-V2 §"absorption")."""
    _no_adapters(p)
    B = x.shape[0]
    rows = pos[None][None].repeat(B, 0)
    q_nope, q_rope = _mla_q(p, a, x, rows, inv_freq)
    c_new, kr_new = _mla_latents(p, a, x, rows, inv_freq)
    slot = cache.index % cache.capacity
    cache = MLACache(
        c_kv=jax.lax.dynamic_update_slice_in_dim(
            cache.c_kv, c_new.astype(cache.c_kv.dtype), slot, 1),
        k_rope=jax.lax.dynamic_update_slice_in_dim(
            cache.k_rope, kr_new.astype(cache.k_rope.dtype), slot, 1),
        pos=jax.lax.dynamic_update_slice_in_dim(
            cache.pos, jnp.full((B, 1), pos, jnp.int32), slot, 1),
        index=cache.index + 1,
    )
    # absorb: q_c[b,h,r] = sum_k q_nope[b,h,k] * w_uk[r,h,k]
    q_c = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])
    c_kv = cache.c_kv.astype(x.dtype)      # upcast quantized latents
    s_nope = jnp.einsum("bshr,bcr->bhsc", q_c, c_kv)
    s_rope = jnp.einsum("bshr,bcr->bhsc", q_rope,
                        cache.k_rope.astype(x.dtype))
    scores = (s_nope + s_rope).astype(jnp.float32) * mla_softmax_scale(a)
    valid = (cache.pos >= 0)[:, None, None, :]
    scores = jnp.where(valid, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhsc,bcr->bshr", probs, c_kv)
    out = jnp.einsum("bshr,rhk->bshk", ctx, p["w_uv"])
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache
