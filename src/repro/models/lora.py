"""LoRA adapters on the four projections of latent attention (MLA):
``wq``, the kv down-projection (``w_dkv`` + ``w_krope``, HF
``kv_a_proj_with_mqa``), the kv up-projection (``w_uk`` + ``w_uv``, HF
``kv_b_proj``) and ``wo``.

Adapters live apart from the frozen base: a tree ``{"lead": {"0":
{target: {"a", "b"}}}, "layers": {target: {"a", "b"}}}`` whose
``layers`` leaves are stacked over the scanned layers like the base's.
:func:`with_adapters` puts them under each layer's ``attn/lora``, where
the attention applies them unmerged (``repro.models.attention.lora_delta``).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

TARGETS = ("wq", "kv_a", "kv_b", "wo")


def adapter_dims(cfg: ModelConfig) -> Dict[str, Tuple[tuple, tuple]]:
    """(input dims, output dims) of each adapted projection."""
    a, d = cfg.attention, cfg.d_model
    m, H = a.mla, a.num_heads
    return {
        "wq": ((d,), (H, m.qk_nope_head_dim + m.qk_rope_head_dim)),
        "kv_a": ((d,), (m.kv_lora_rank + m.qk_rope_head_dim,)),
        "kv_b": ((m.kv_lora_rank,), (H, m.qk_nope_head_dim + m.v_head_dim)),
        "wo": ((H, m.v_head_dim), (d,)),
    }


def _n_lead(cfg: ModelConfig) -> int:
    return cfg.moe.first_dense_layers if cfg.moe else 0


def init_adapters(rng: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    """One client's adapters, float32: ``A`` uniform in
    ``+-1/sqrt(fan_in)`` (PEFT's default), ``B`` zero, so the adapted
    model starts as the base."""
    if cfg.attention.kind != "mla" or cfg.attention.lora is None:
        raise ValueError("adapters need latent attention and a LoRAConfig")
    r = cfg.attention.lora.rank
    dims = adapter_dims(cfg)

    def layer(key, lead=()):
        out = {}
        for j, name in enumerate(TARGETS):
            ins, outs = dims[name]
            bound = 1.0 / np.sqrt(np.prod(ins))
            out[name] = {
                "a": jax.random.uniform(jax.random.fold_in(key, j),
                                        lead + ins + (r,), jnp.float32,
                                        -bound, bound),
                "b": jnp.zeros(lead + (r,) + outs, jnp.float32)}
        return out

    n_lead = _n_lead(cfg)
    return {"lead": {str(i): layer(jax.random.fold_in(rng, i))
                     for i in range(n_lead)},
            "layers": layer(jax.random.fold_in(rng, n_lead),
                            (cfg.num_layers - n_lead,))}


def with_adapters(base: Dict[str, Any], adapters: Dict[str, Any],
                  stacked: bool = False) -> Dict[str, Any]:
    """``base`` with each layer's adapters under ``attn/lora``.  With
    ``stacked``, every adapter leaf has a leading client axis (C, ...);
    the rows of the batch then go to the clients in C equal blocks."""
    layers = adapters["layers"]
    if stacked:   # (C, L, ...) -> (L, C, ...): the layer scan slices L
        layers = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), layers)

    def attach(p, ad):
        return dict(p, attn=dict(p["attn"], lora=ad))

    out = dict(base, layers=attach(base["layers"], layers))
    if adapters["lead"]:
        out["lead"] = {k: attach(base["lead"][k], v)
                       for k, v in adapters["lead"].items()}
    return out
