"""Plain reference of stablelm-2-1.6b as configured in ``stablelm-1.6b.json``
(the repo's variant: no q/k/v bias, LayerNorm eps from the file).

Written from the published architecture and nothing of the program: a
pre-LayerNorm decoder; attention with rotary embeddings on the first
``partial_rotary_factor`` of each head (the two halves of that part
rotated against each other), causal softmax at ``1/sqrt(head_dim)``; a
SwiGLU MLP; a final LayerNorm and an untied head.

``init_params`` makes the weights the benchmark serves, in the layout the
program takes (checked against the program's own shapes at set-up).
``logits_at`` runs the forward in one of two precisions:

* ``"f32"``: every weight and activation in float32, matmuls at
  ``highest`` precision (the reference);
* ``"fp8"``: the control, one precision step below the configuration's
  bf16 — every matrix quantized to float8 e4m3 with one absmax scale per
  tensor, activations and the rest in bf16.

Sequences go one at a time (``lax.map``), so the reference's memory is
that of one sequence on top of the weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MATRICES = ("wq", "wk", "wv", "wo", "wi_gate", "wi_up")


def sizes(model: dict) -> dict:
    return dict(L=model["num_hidden_layers"], d=model["hidden_size"],
                F=model["intermediate_size"], V=model["vocab_size"],
                H=model["num_attention_heads"],
                Hkv=model["num_key_value_heads"], hd=model["head_dim"])


def param_count(model: dict) -> int:
    s = sizes(model)
    d, L = s["d"], s["L"]
    attn = d * s["H"] * s["hd"] * 2 + d * s["Hkv"] * s["hd"] * 2
    per_layer = attn + 3 * d * s["F"] + 4 * d
    return 2 * s["V"] * d + L * per_layer + 2 * d


def init_params(key, model: dict, dtype=jnp.bfloat16):
    """Random weights in the program's layout: every matrix N(0, 1/fan_in)
    (fan-in: the contracted dimensions), the embedding N(0, 1), norms at
    scale 1 and bias 0.  Jit this whole function: one call on the
    device.  The layers are drawn one at a time (``lax.map``), so only
    one layer's float32 draws are alive at once."""
    s = sizes(model)
    L, d, F, V, H, Hkv, hd = (s[k] for k in ("L", "d", "F", "V", "H",
                                             "Hkv", "hd"))
    layer_shapes = {
        ("attn", "wq"): ((d, H, hd), d),
        ("attn", "wk"): ((d, Hkv, hd), d),
        ("attn", "wv"): ((d, Hkv, hd), d),
        ("attn", "wo"): ((H, hd, d), H * hd),
        ("mlp", "wi_gate"): ((d, F), d),
        ("mlp", "wi_up"): ((d, F), d),
        ("mlp", "wo"): ((F, d), F),
    }

    def draw(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def layer(i):
        lk = jax.random.fold_in(key, 1000 + i)
        out: dict = {}
        for j, ((group, name), (shape, fan_in)) in enumerate(
                sorted(layer_shapes.items())):
            out.setdefault(group, {})[name] = draw(
                jax.random.fold_in(lk, j), shape, 1.0 / math.sqrt(fan_in))
        for norm in ("ln1", "ln2"):
            out[norm] = {"scale": jnp.ones((d,), dtype),
                         "bias": jnp.zeros((d,), dtype)}
        return out

    return {
        "embed": {"table": draw(jax.random.fold_in(key, 0), (V, d), 1.0)},
        "lm_head": {"w": draw(jax.random.fold_in(key, 1), (d, V),
                              1.0 / math.sqrt(d))},
        "layers": jax.lax.map(layer, jnp.arange(L)),
        "final_norm": {"scale": jnp.ones((d,), dtype),
                       "bias": jnp.zeros((d,), dtype)},
    }


def _fp8(w):
    """Round a weight to float8 e4m3 under one absmax scale."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32)) / 448.0
    q = (w32 / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def _cast(tree, mode: str):
    """Weights of one layer (or the head) in the precision of ``mode``."""
    if mode == "f32":
        return jax.tree.map(lambda x: x.astype(jnp.float32), tree)
    if mode != "fp8":
        raise ValueError(f"unknown precision {mode!r}")
    return {k: (_cast(v, mode) if isinstance(v, dict)
                else _fp8(v) if k in MATRICES + ("w",)
                else v.astype(jnp.bfloat16))
            for k, v in tree.items()}


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = jnp.square(x32 - mu).mean(-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps) * scale + bias
    return y.astype(x.dtype)


def _rotary(x, pos, rot: int, theta: float):
    """Rotate the first ``rot`` dims of each head: halves (a, b) of that
    part become (a cos - b sin, b cos + a sin) at angle pos * theta**(-2k/rot)."""
    k = jnp.arange(rot // 2, dtype=jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * theta ** (-2.0 * k / rot)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a = x[..., :rot // 2].astype(jnp.float32)
    b = x[..., rot // 2:rot].astype(jnp.float32)
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], -1)


def _sequence_hidden(p, tokens, model: dict, mode: str):
    """Final hidden states (T, d) of one sequence (T,)."""
    s = sizes(model)
    H, Hkv, hd = s["H"], s["Hkv"], s["hd"]
    eps = model["layer_norm_eps"]
    rot = int(hd * model["partial_rotary_factor"])
    rot -= rot % 2
    theta = model["rope_theta"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    x = p["embed"]["table"][tokens].astype(
        jnp.float32 if mode == "f32" else jnp.bfloat16)

    def layer(x, lp):
        lp = _cast(lp, mode)
        h = _layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"], eps)
        q = jnp.einsum("td,dhk->thk", h, lp["attn"]["wq"])
        k = jnp.einsum("td,dhk->thk", h, lp["attn"]["wk"])
        v = jnp.einsum("td,dhk->thk", h, lp["attn"]["wv"])
        q, k = _rotary(q, pos, rot, theta), _rotary(k, pos, rot, theta)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        sc = jnp.einsum("qhk,shk->hqs", q, k).astype(jnp.float32) \
            / math.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        o = jnp.einsum("hqs,shk->qhk", pr, v)
        x = x + jnp.einsum("thk,hkd->td", o, lp["attn"]["wo"])
        h = _layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"], eps)
        g = jnp.einsum("td,df->tf", h, lp["mlp"]["wi_gate"])
        u = jnp.einsum("td,df->tf", h, lp["mlp"]["wi_up"])
        f = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u)
        return x + jnp.einsum("tf,fd->td", f, lp["mlp"]["wo"]), None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    fn = _cast(p["final_norm"], mode)
    return _layer_norm(x, fn["scale"], fn["bias"], eps)


def logits_at(params, tokens, idx, model: dict, mode: str = "f32"):
    """Logits (K, M, V) float32 at positions ``idx`` (K, M) of the
    sequences ``tokens`` (K, T)."""
    prec = "highest" if mode == "f32" else "default"
    with jax.default_matmul_precision(prec):
        head = _cast(params["lm_head"], mode)["w"]

        def one(args):
            toks, ix = args
            h = _sequence_hidden(params, toks, model, mode)[ix]
            return jnp.einsum("md,dv->mv", h, head).astype(jnp.float32)

        return jax.lax.map(one, (tokens, idx))
