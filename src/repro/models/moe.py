"""Mixture-of-Experts layer: shared + routed experts, top-k routing.

Dispatch is *dropless and sort-based* (megablocks-style): the token->expert
assignments are sorted by expert id, every expert multiplies its own
contiguous run of rows in one grouped matmul (``jax.lax.ragged_dot``, the
TPU's native ragged dot), and the outputs are combined back per token,
weighted by their router probabilities.  No assignment is dropped, however
unevenly the router loads the experts; the program stays static-shape
(``tokens * top_k`` rows in all).

The load-balance auxiliary loss (Switch) is returned beside the output;
the caller decides whether to train on it.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.common import ParamBuilder, shard
from repro.models.layers import apply_mlp, init_mlp


def init_moe(pb: ParamBuilder, path: str, d_model: int, moe: MoEConfig,
             act: str) -> None:
    E = moe.num_experts
    pb.param(f"{path}/router", (d_model, E), ("embed", None),
             dtype=jnp.float32)
    if act == "silu":
        pb.param(f"{path}/wi_gate", (E, d_model, moe.d_expert),
                 ("expert", "embed", "mlp"))
        pb.param(f"{path}/wi_up", (E, d_model, moe.d_expert),
                 ("expert", "embed", "mlp"))
    else:
        pb.param(f"{path}/wi", (E, d_model, moe.d_expert),
                 ("expert", "embed", "mlp"))
    pb.param(f"{path}/wo", (E, moe.d_expert, d_model),
             ("expert", "mlp", "embed"))
    shared = moe.d_shared if moe.d_shared else moe.num_shared * moe.d_expert
    if shared:
        init_mlp(pb, f"{path}/shared", d_model, shared, act)


def _routed_plain(xf, topw, topi, w):
    """The routed experts' output (t, d): each token's ``topi`` experts
    weighted by ``topw``, dropless."""
    t, d = xf.shape
    K = topi.shape[1]
    E = w["wo"].shape[0]
    # sort the t*K assignments by expert; each expert's rows are one
    # contiguous group of the grouped matmuls
    flat_e = topi.reshape(t * K)
    order = jnp.argsort(flat_e)
    sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    xs = xf[order // K]                                      # (t*K, d)
    with jax.named_scope("moe.experts"):
        if "wi_gate" in w:
            g = jax.lax.ragged_dot(xs, w["wi_gate"], sizes)
            u = jax.lax.ragged_dot(xs, w["wi_up"], sizes)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
        else:
            h = jax.nn.gelu(jax.lax.ragged_dot(xs, w["wi"], sizes)
                            .astype(jnp.float32)).astype(xs.dtype)
        ys = jax.lax.ragged_dot(h, w["wo"], sizes)           # (t*K, d)
    # combine: back to (token, slot) order, weighted sum over the slots
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * K, dtype=order.dtype))
    y = ys.at[inverse].get(unique_indices=True).reshape(t, K, d)
    return jnp.einsum("tkd,tk->td", y, topw.astype(y.dtype),
                      preferred_element_type=jnp.float32).astype(xf.dtype)


@jax.custom_batching.custom_vmap
def _routed_rows(xf, topw, topi, w):
    return _routed_plain(xf, topw, topi, w)


@_routed_rows.def_vmap
def _fold_rows(axis_size, in_batched, xf, topw, topi, w):
    """Routing is per token, so a vmapped call (the serving engine's
    per-slot decode) is one call over the rows of every slot:
    ``ragged_dot`` has no batching rule for it."""
    if any(jax.tree.leaves(in_batched[3])):
        raise NotImplementedError("vmap over the expert weights")
    rows = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip((xf, topw, topi), in_batched[:3])]
    n, t = rows[0].shape[:2]
    out = _routed_rows(*(a.reshape((n * t,) + a.shape[2:]) for a in rows), w)
    return out.reshape(n, t, -1), True


@jax.custom_jvp
def _routed(xf, topw, topi, w):
    return _routed_rows(xf, topw, topi, w)


@_routed.defjvp
def _routed_jvp(primals, tangents):
    # differentiate the plain program: custom_vmap has no autodiff
    return jax.jvp(_routed_plain, primals, tangents)


def apply_moe(p: Dict[str, Any], moe: MoEConfig, x: jax.Array, act: str,
              ) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,d) -> (out (B,S,d), aux_loss scalar)."""
    B, S, d = x.shape
    t = B * S
    E, K = moe.num_experts, moe.top_k
    xf = x.reshape(t, d)

    # --- routing (fp32): softmax scores, top-k kept unnormalised ---
    logits = (xf.astype(jnp.float32) @ p["router"])          # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)                     # (t, K)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    f = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (t * K)
    P = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * P) * moe.aux_loss_coef

    experts = {k: p[k] for k in ("wi_gate", "wi_up", "wi", "wo") if k in p}
    out = _routed(xf, topw, topi, experts)
    out = shard(out.reshape(B, S, d), "batch", "seq", "embed_act")

    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, act)
    return out, aux
