"""Decoder-only transformer assembly covering the dense, moe and vlm
families (stablelm, h2o-danube, gemma3, llama3-405b, internvl2 LM,
deepseek-v2-lite, qwen2-moe).

Training/prefill runs a ``lax.scan`` over stacked layer parameters.
Heterogeneous layer *behaviour* (gemma3's 5 local : 1 global pattern,
per-layer rope bases) is expressed as traced per-layer scalars fed through
the scan, so the stack stays homogeneous.  DeepSeek's leading dense layer
is unstacked.  Decode uses a layer scan with stacked caches when the
cache geometry is uniform, else (gemma3) a python loop with per-layer
cache capacities (local layers keep only a 512-slot ring).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.common import (ParamBuilder, shard, stack_axes,
                                 stack_params, to_dtype)
from repro.models.layers import (apply_mlp, apply_norm, embed_tokens,
                                 init_embedding, init_mlp, init_norm,
                                 logits_from_hidden)
from repro.models.moe import apply_moe, init_moe
from repro.models.rope import rope_frequencies, yarn_frequencies

FULL_WINDOW = 1 << 30


# ---------------------------------------------------------------------------
# per-layer static metadata
# ---------------------------------------------------------------------------

def layer_is_global(cfg: ModelConfig, i: int) -> bool:
    a = cfg.attention
    if a.kind != "local_global":
        return True
    return (i + 1) % (a.local_global_ratio + 1) == 0


def layer_window(cfg: ModelConfig, i: int) -> int:
    a = cfg.attention
    if a.kind == "swa":
        return a.window
    if a.kind == "local_global" and not layer_is_global(cfg, i):
        return a.window
    if a.kind == "full" and a.window:          # zamba2 shared block long mode
        return a.window
    return FULL_WINDOW

def layer_theta(cfg: ModelConfig, i: int) -> float:
    a = cfg.attention
    if a.kind == "local_global" and not layer_is_global(cfg, i):
        return a.rope_theta_local or a.rope_theta
    return a.rope_theta


def stacked_rope(cfg: ModelConfig, layers=None) -> jax.Array:
    a = cfg.attention
    idx = range(cfg.num_layers) if layers is None else layers
    hd = (a.mla.qk_rope_head_dim if a.kind == "mla" and a.mla else a.head_dim)
    rows = []
    for i in idx:
        th = layer_theta(cfg, i)
        if th == 0.0:
            rows.append(np.zeros((0,), np.float32))
        elif a.rope_scaling is not None:
            rows.append(yarn_frequencies(hd, th, a.rope_scaling))
        else:
            rows.append(np.asarray(
                rope_frequencies(hd, th, a.rope_fraction)))
    return jnp.asarray(np.stack(rows))


def stacked_windows(cfg: ModelConfig, layers=None) -> jax.Array:
    idx = range(cfg.num_layers) if layers is None else layers
    return jnp.asarray([layer_window(cfg, i) for i in idx], jnp.int32)


def sinusoidal_positions(S: int, d: int, offset=0) -> jax.Array:
    p = jnp.arange(S)[:, None] + offset
    k = jnp.arange(d // 2)[None, :]
    ang = p / (10000.0 ** (2 * k / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(rng, cfg: ModelConfig, *, moe_layer: bool,
                d_ff: Optional[int] = None):
    pb = ParamBuilder(rng, dtype=to_dtype(cfg.param_dtype))
    a = cfg.attention
    init_norm(pb, "ln1", cfg.d_model, cfg.norm)
    if a.kind == "mla":
        attn.init_mla(pb, "attn", cfg.d_model, a)
    else:
        attn.init_gqa(pb, "attn", cfg.d_model, a)
    init_norm(pb, "ln2", cfg.d_model, cfg.norm)
    if moe_layer:
        init_moe(pb, "moe", cfg.d_model, cfg.moe, cfg.act)
    else:
        init_mlp(pb, "mlp", cfg.d_model, d_ff or cfg.d_ff, cfg.act)
    return pb.build()


def init_params(rng, cfg: ModelConfig):
    pb = ParamBuilder(rng, dtype=to_dtype(cfg.param_dtype))
    init_embedding(pb, cfg)
    n_dense_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    for i in range(n_dense_lead):
        p, ax = _init_layer(jax.random.fold_in(rng, 1000 + i), cfg,
                            moe_layer=False,
                            d_ff=cfg.moe.dense_d_ff or cfg.d_ff)
        pb.subtree(f"lead/{i}", p, ax)
    stackable = range(n_dense_lead, cfg.num_layers)
    per_layer = [_init_layer(jax.random.fold_in(rng, 2000 + i), cfg,
                             moe_layer=cfg.moe is not None)
                 for i in stackable]
    stacked = stack_params([p for p, _ in per_layer])
    pb.subtree("layers", stacked, stack_axes(per_layer[0][1]))
    init_norm(pb, "final_norm", cfg.d_model, cfg.norm)
    return pb.build()


# ---------------------------------------------------------------------------
# one decoder layer
# ---------------------------------------------------------------------------

def _layer_fwd(cfg: ModelConfig, p, x, positions, inv_freq, window,
               moe_layer: bool):
    a = cfg.attention
    h = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
    if a.kind == "mla":
        y = attn.mla_forward(p["attn"], a, h, positions, inv_freq)
    else:
        y = attn.gqa_forward(p["attn"], a, h, positions, inv_freq,
                             window=window)
    x = x + y
    h = apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
    if moe_layer:
        y, aux = apply_moe(p["moe"], cfg.moe, h, cfg.act)
    else:
        y, aux = apply_mlp(p["mlp"], h, cfg.act), jnp.zeros((), jnp.float32)
    return x + y, aux


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens: jax.Array,
            extra_embeds: Optional[jax.Array] = None,
            remat: str = "layer") -> Tuple[jax.Array, jax.Array]:
    """tokens (B,S) [+ optional (B,P,d) prefix embeddings for vlm/audio].
    Returns (logits (B,S_total,V), aux_loss)."""
    x = embed_tokens(params, cfg, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    if cfg.attention.rope_theta == 0.0:      # learned-position-free fallback
        x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]

    moe_layer = cfg.moe is not None
    n_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    aux_total = jnp.zeros((), jnp.float32)
    lead_fwd = _layer_fwd if remat == "none" else jax.checkpoint(
        _layer_fwd, static_argnums=(0, 6))
    for i in range(n_lead):
        p = params["lead"][str(i)]
        x, aux = lead_fwd(cfg, p, x, positions, stacked_rope(cfg, [i])[0],
                          jnp.int32(layer_window(cfg, i)), False)
        aux_total += aux

    inv_freqs = stacked_rope(cfg, range(n_lead, cfg.num_layers))
    windows = stacked_windows(cfg, range(n_lead, cfg.num_layers))

    def body(carry, xs):
        xc, aux_c = carry
        p, ifr, win = xs
        xo, aux = _layer_fwd(cfg, p, xc, positions, ifr, win, moe_layer)
        return (xo, aux_c + aux), None

    body_fn = jax.checkpoint(body) if remat != "none" else body
    (x, aux_total), _ = jax.lax.scan(
        body_fn, (x, aux_total), (params["layers"], inv_freqs, windows))
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return logits_from_hidden(params, cfg, x), aux_total


# ---------------------------------------------------------------------------
# caches + decode
# ---------------------------------------------------------------------------

def _uniform_cache_geometry(cfg: ModelConfig) -> bool:
    wins = {layer_window(cfg, i) for i in range(cfg.num_layers)}
    return len(wins) == 1


def cache_capacity(cfg: ModelConfig, i: int, max_len: int) -> int:
    w = layer_window(cfg, i)
    return min(max_len, w) if w != FULL_WINDOW else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    if dtype is None:
        from repro.models.common import to_dtype
        dtype = to_dtype(cfg.dtype)
    a = cfg.attention
    n_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    lead = {}
    for i in range(n_lead):
        cap = cache_capacity(cfg, i, max_len)
        lead[str(i)] = (attn.init_mla_cache(batch, cap, a, dtype)
                        if a.kind == "mla"
                        else attn.init_kv_cache(batch, cap, a.num_kv_heads,
                                                a.head_dim, dtype))
    rest = range(n_lead, cfg.num_layers)
    if _uniform_cache_geometry(cfg):
        cap = cache_capacity(cfg, n_lead, max_len)
        n = cfg.num_layers - n_lead
        if a.kind == "mla":
            per = [attn.init_mla_cache(batch, cap, a, dtype) for _ in range(n)]
        else:
            per = [attn.init_kv_cache(batch, cap, a.num_kv_heads,
                                      a.head_dim, dtype) for _ in range(n)]
        stackedc = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        return {"lead": lead, "layers": stackedc}
    per = {}
    for i in rest:
        cap = cache_capacity(cfg, i, max_len)
        per[str(i)] = attn.init_kv_cache(batch, cap, a.num_kv_heads,
                                         a.head_dim, dtype)
    return {"lead": lead, "layers": per}


def _layer_prefill(cfg, p, x, positions, length, cache, inv_freq, window,
                   moe_layer):
    a = cfg.attention
    h = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
    if a.kind == "mla":
        y, cache = attn.mla_prefill(p["attn"], a, h, positions, length,
                                    cache, inv_freq)
    else:
        y, cache = attn.gqa_prefill(p["attn"], a, h, positions, length,
                                    cache, inv_freq, window=window)
    x = x + y
    h = apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
    if moe_layer:
        y, _ = apply_moe(p["moe"], cfg.moe, h, cfg.act)
    else:
        y = apply_mlp(p["mlp"], h, cfg.act)
    return x + y, cache


def prefill(params, cfg: ModelConfig, tokens: jax.Array, cache,
            length=None, extra_embeds=None):
    """One-shot prefill: the same full-sequence pass as :func:`forward`,
    but every layer also writes its KV/latent cache for positions
    ``[0, length)`` in a single scatter — S sequential decode steps
    collapse into one program.  ``tokens`` (B,S) may be right-padded
    beyond ``length``; returns (logits (B,S,V), cache ready for decode at
    position ``length``)."""
    if length is None:
        length = tokens.shape[1]
    length = jnp.asarray(length, jnp.int32)
    x = embed_tokens(params, cfg, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    if cfg.attention.rope_theta == 0.0:
        x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]
    moe_layer = cfg.moe is not None
    n_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    new_lead = {}
    for i in range(n_lead):
        x, c = _layer_prefill(cfg, params["lead"][str(i)], x, positions,
                              length, cache["lead"][str(i)],
                              stacked_rope(cfg, [i])[0],
                              jnp.int32(layer_window(cfg, i)), False)
        new_lead[str(i)] = c
    rest = list(range(n_lead, cfg.num_layers))
    stacked = not isinstance(cache["layers"], dict)
    if stacked:
        inv_freqs = stacked_rope(cfg, rest)
        windows = stacked_windows(cfg, rest)

        def body(x_c, xs):
            p, c, ifr, win = xs
            xo, c2 = _layer_prefill(cfg, p, x_c, positions, length, c, ifr,
                                    win, moe_layer)
            return xo, c2

        x, new_stack = jax.lax.scan(
            body, x, (params["layers"], cache["layers"], inv_freqs, windows))
        new_cache = {"lead": new_lead, "layers": new_stack}
    else:
        new_per = {}
        for i in rest:
            p = jax.tree.map(lambda a_: a_[i - n_lead], params["layers"])
            x, c = _layer_prefill(cfg, p, x, positions, length,
                                  cache["layers"][str(i)],
                                  stacked_rope(cfg, [i])[0],
                                  jnp.int32(layer_window(cfg, i)), moe_layer)
            new_per[str(i)] = c
        new_cache = {"lead": new_lead, "layers": new_per}
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return logits_from_hidden(params, cfg, x), new_cache


# ---------------------------------------------------------------------------
# paged cache + paged prefill / decode
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=None):
    """Paged cache for every layer.  Unlike :func:`init_cache` the
    geometry is uniform by construction — windowed layers are handled by
    masking at score time, not by smaller rings — so the layer stack
    always scans, gemma3 included.  Page arrays are shared across
    sequences; per-layer arrays are stacked along a leading layer axis
    and indexed by the same pool-issued page ids."""
    if dtype is None:
        from repro.models.common import to_dtype
        dtype = to_dtype(cfg.dtype)
    a = cfg.attention

    def one():
        if a.kind == "mla":
            return attn.init_paged_mla_cache(num_pages, page_size, a, dtype)
        return attn.init_paged_kv_cache(num_pages, page_size,
                                        a.num_kv_heads, a.head_dim, dtype)

    n_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    lead = {str(i): one() for i in range(n_lead)}
    per = [one() for _ in range(cfg.num_layers - n_lead)]
    stackedc = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    return {"lead": lead, "layers": stackedc}


def _layer_paged_prefill(cfg, p, x, positions, length, cache, block_tables,
                         inv_freq, window, moe_layer):
    a = cfg.attention
    h = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
    if a.kind == "mla":
        y, cache = attn.paged_mla_prefill(p["attn"], a, h, positions,
                                          length, cache, block_tables,
                                          inv_freq)
    else:
        y, cache = attn.paged_gqa_prefill(p["attn"], a, h, positions,
                                          length, cache, block_tables,
                                          inv_freq, window=window)
    x = x + y
    h = apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
    if moe_layer:
        y, _ = apply_moe(p["moe"], cfg.moe, h, cfg.act)
    else:
        y = apply_mlp(p["mlp"], h, cfg.act)
    return x + y, cache


def paged_prefill(params, cfg: ModelConfig, tokens: jax.Array, cache,
                  block_tables: jax.Array, length=None):
    """One-shot prefill through the block table: same full-sequence math
    as :func:`prefill`, cache writes scattered into pool pages.  ``tokens``
    (B,S) right-padded past ``length``; ``block_tables`` (B, pages_per_seq)
    pool page ids.  Returns (logits (B,S,V), new paged cache)."""
    if length is None:
        length = tokens.shape[1]
    length = jnp.asarray(length, jnp.int32)
    x = embed_tokens(params, cfg, tokens)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    if cfg.attention.rope_theta == 0.0:
        x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]
    moe_layer = cfg.moe is not None
    n_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    new_lead = {}
    for i in range(n_lead):
        x, c = _layer_paged_prefill(cfg, params["lead"][str(i)], x,
                                    positions, length, cache["lead"][str(i)],
                                    block_tables, stacked_rope(cfg, [i])[0],
                                    jnp.int32(layer_window(cfg, i)), False)
        new_lead[str(i)] = c
    rest = list(range(n_lead, cfg.num_layers))
    inv_freqs = stacked_rope(cfg, rest)
    windows = stacked_windows(cfg, rest)

    def body(x_c, xs):
        p, c, ifr, win = xs
        xo, c2 = _layer_paged_prefill(cfg, p, x_c, positions, length, c,
                                      block_tables, ifr, win, moe_layer)
        return xo, c2

    x, new_stack = jax.lax.scan(
        body, x, (params["layers"], cache["layers"], inv_freqs, windows))
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return logits_from_hidden(params, cfg, x), \
        {"lead": new_lead, "layers": new_stack}


def _layer_paged_decode(cfg, p, x, pos, cache, block_tables, inv_freq,
                        window, moe_layer):
    a = cfg.attention
    h = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
    if a.kind == "mla":
        y, cache = attn.paged_mla_decode(p["attn"], a, h, pos, cache,
                                         block_tables, inv_freq)
    else:
        y, cache = attn.paged_gqa_decode(p["attn"], a, h, pos, cache,
                                         block_tables, inv_freq,
                                         window=window)
    x = x + y
    h = apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
    if moe_layer:
        y, _ = apply_moe(p["moe"], cfg.moe, h, cfg.act)
    else:
        y = apply_mlp(p["mlp"], h, cfg.act)
    return x + y, cache


def paged_decode_step(params, cfg: ModelConfig, tokens: jax.Array,
                      pos: jax.Array, cache, block_tables: jax.Array):
    """Batched paged decode: one program advances every live sequence.
    ``tokens`` (B,1); ``pos`` (B,) per-row absolute positions (free rows
    point their block table at the scratch page and are ignored by the
    caller).  Returns (logits (B,1,V), new paged cache)."""
    x = embed_tokens(params, cfg, tokens)
    if cfg.attention.rope_theta == 0.0:
        sp = jax.vmap(lambda po: sinusoidal_positions(1, cfg.d_model,
                                                      offset=po))(pos)
        x = x + sp.astype(x.dtype)
    moe_layer = cfg.moe is not None
    n_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    new_lead = {}
    for i in range(n_lead):
        x, c = _layer_paged_decode(cfg, params["lead"][str(i)], x, pos,
                                   cache["lead"][str(i)], block_tables,
                                   stacked_rope(cfg, [i])[0],
                                   jnp.int32(layer_window(cfg, i)), False)
        new_lead[str(i)] = c
    rest = list(range(n_lead, cfg.num_layers))
    inv_freqs = stacked_rope(cfg, rest)
    windows = stacked_windows(cfg, rest)

    def body(x_c, xs):
        p, c, ifr, win = xs
        xo, c2 = _layer_paged_decode(cfg, p, x_c, pos, c, block_tables,
                                     ifr, win, moe_layer)
        return xo, c2

    x, new_stack = jax.lax.scan(
        body, x, (params["layers"], cache["layers"], inv_freqs, windows))
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return logits_from_hidden(params, cfg, x), \
        {"lead": new_lead, "layers": new_stack}


def _layer_decode(cfg, p, x, pos, cache, inv_freq, window, moe_layer):
    a = cfg.attention
    h = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
    if a.kind == "mla":
        y, cache = attn.mla_decode(p["attn"], a, h, pos, cache, inv_freq)
    else:
        y, cache = attn.gqa_decode(p["attn"], a, h, pos, cache, inv_freq,
                                   window=window)
    x = x + y
    h = apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
    if moe_layer:
        y, _ = apply_moe(p["moe"], cfg.moe, h, cfg.act)
    else:
        y = apply_mlp(p["mlp"], h, cfg.act)
    return x + y, cache


def decode_step(params, cfg: ModelConfig, tokens: jax.Array, pos: jax.Array,
                cache, extra_embeds=None):
    """tokens (B,1); pos () int32 absolute position.  Returns
    (logits (B,1,V), new cache)."""
    x = embed_tokens(params, cfg, tokens)
    if cfg.attention.rope_theta == 0.0:
        x = x + sinusoidal_positions(1, cfg.d_model, offset=pos
                                     ).astype(x.dtype)[None]
    moe_layer = cfg.moe is not None
    n_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    new_lead = {}
    for i in range(n_lead):
        x, c = _layer_decode(cfg, params["lead"][str(i)], x, pos,
                             cache["lead"][str(i)],
                             stacked_rope(cfg, [i])[0],
                             jnp.int32(layer_window(cfg, i)), False)
        new_lead[str(i)] = c
    rest = list(range(n_lead, cfg.num_layers))
    stacked = not isinstance(cache["layers"], dict)
    if stacked:
        inv_freqs = stacked_rope(cfg, rest)
        windows = stacked_windows(cfg, rest)

        def body(x_c, xs):
            p, c, ifr, win = xs
            xo, c2 = _layer_decode(cfg, p, x_c, pos, c, ifr, win, moe_layer)
            return xo, c2

        x, new_stack = jax.lax.scan(
            body, x, (params["layers"], cache["layers"], inv_freqs, windows))
        new_cache = {"lead": new_lead, "layers": new_stack}
    else:
        new_per = {}
        for i in rest:
            p = jax.tree.map(lambda a_: a_[i - n_lead], params["layers"])
            x, c = _layer_decode(cfg, p, x, pos, cache["layers"][str(i)],
                                 stacked_rope(cfg, [i])[0],
                                 jnp.int32(layer_window(cfg, i)), moe_layer)
            new_per[str(i)] = c
        new_cache = {"lead": new_lead, "layers": new_per}
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return logits_from_hidden(params, cfg, x), new_cache
