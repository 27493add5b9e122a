"""Plain reference of DeepSeek-V2-Lite fine-tuned by LoRA in continual
hierarchical FL, as configured in ``deepseek-v2-lite-hfl.json``.

Written from the DeepSeek-V2 paper (arXiv:2405.04434), the published
config, LoRA (arXiv:2106.09685) and FedAvg, and nothing of the program:

* a pre-RMSNorm decoder; layer 0 has a dense SwiGLU FFN
  (``intermediate_size``), the others a MoE FFN; a final RMSNorm and an
  untied head over the vocabulary slice;
* attention (MLA, no q compression): ``q = h Wq`` per head
  (``qk_nope + qk_rope`` wide); ``c = RMSNorm(h W_dkv)`` (``kv_lora_rank``)
  and one ``k_rope = h W_krope`` shared by the heads; ``k_nope = c W_uk``,
  ``v = c W_uv``; rotary embeddings on ``q_rope`` and ``k_rope`` at YaRN's
  frequencies; causal softmax at ``(qk_nope + qk_rope)**-0.5`` times
  YaRN's ``mscale(factor, mscale_all_dim)**2``.  The rotation turns the
  two halves of the rope dims against each other; the published
  checkpoint interleaves the pairs, a permutation of weight columns that
  random weights do not see;
* YaRN: frequency ``theta**(-2i/d)`` kept for the dims that rotate more
  than ``beta_fast`` times over ``original_max_position_embeddings``,
  divided by ``factor`` for those under ``beta_slow``, a linear ramp
  between;
* LoRA: each of ``wq``, the kv down-projection (``W_dkv`` and
  ``W_krope`` side by side), the kv up-projection (``W_uk`` and ``W_uv``)
  and ``wo`` adds ``x A B * alpha / rank``;
* MoE: shared experts (one SwiGLU ``n_shared_experts *
  moe_intermediate_size`` wide) plus routed ones: softmax scores over
  ``n_routed_experts``, the top ``num_experts_per_tok`` kept without
  renormalising, ``y_t = sum_k g_tk FFN_{e_tk}(u_t)`` for every token
  (none dropped).  :func:`routed_per_token` writes that sum as it
  stands, every token through every expert; the logits and the
  gradients that the benchmark checks are computed with it.  The rounds,
  some forty times the work, take the same sum expert by expert
  (:func:`routed`: the assignments sorted by expert, one grouped product
  each, only the chosen pairs computed), which the checks on the
  per-token form tie to the published model;
* a round: every client takes ``local_steps`` AdamW steps (betas 0.9 and
  0.999, eps 1e-8, no weight decay, state new each round) on the mean
  next-token cross-entropy of its batches; then each cluster averages
  its members' adapters, and every ``l``-th round the clusters' means,
  weighted by member count, go to every client.

``init_params`` makes the frozen base and the clients' adapters in the
layout the program takes.  Precision ``"f32"`` holds every weight and
activation in float32 with matmuls at ``highest`` precision (the
reference), one layer's bf16 weights upcast at a time; ``"fp8"`` is the
control one step below the configuration's bf16: every matrix of the
base rounded to float8 e4m3 under one absmax scale per tensor,
activations in bf16.  Clients go one at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "w_dkv", "w_krope", "w_uk", "w_uv", "wo", "wi_gate",
            "wi_up", "w")


def sizes(model: dict) -> dict:
    return dict(
        L=model["num_hidden_layers"], d=model["hidden_size"],
        F=model["intermediate_size"], Fe=model["moe_intermediate_size"],
        E=model["n_routed_experts"], K=model["num_experts_per_tok"],
        Fs=model["n_shared_experts"] * model["moe_intermediate_size"],
        V=model["vocab_size"], H=model["num_attention_heads"],
        R=model["kv_lora_rank"], dn=model["qk_nope_head_dim"],
        dr=model["qk_rope_head_dim"], dv=model["v_head_dim"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def init_params(key, model: dict, fl: dict, clients: int,
                dtype=jnp.bfloat16):
    """(base, adapters): the frozen base, every matrix N(0, 1/fan_in)
    (fan-in: the contracted dims), the embedding N(0, 1), norm scales 1,
    the router float32; each client's adapters float32, ``A`` uniform in
    ``+-1/sqrt(fan_in)``, ``B`` zero, stacked (the same for all).  Jit
    this whole function; the MoE layers are drawn one at a time."""
    s = sizes(model)
    d, H, R, dn, dr, dv = (s[k] for k in ("d", "H", "R", "dn", "dr", "dv"))
    attn = {"wq": ((d, H, dn + dr), d), "w_dkv": ((d, R), d),
            "w_krope": ((d, dr), d), "w_uk": ((R, H, dn), R),
            "w_uv": ((R, H, dv), R), "wo": ((H, dv, d), H * dv)}

    def draw(k, shape, std, dt=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def mats(k, shapes):
        return {n: draw(jax.random.fold_in(k, j), shape,
                        1.0 / math.sqrt(fan))
                for j, (n, (shape, fan)) in enumerate(sorted(shapes.items()))}

    def swiglu(k, width):
        return mats(k, {"wi_gate": ((d, width), d), "wi_up": ((d, width), d),
                        "wo": ((width, d), width)})

    def layer(k, ffn):
        a = mats(jax.random.fold_in(k, 0), attn)
        a["kv_norm"] = jnp.ones((R,), dtype)
        return {"ln1": {"scale": jnp.ones((d,), dtype)}, "attn": a,
                "ln2": {"scale": jnp.ones((d,), dtype)}, **ffn}

    def moe_layer(i):
        k = jax.random.fold_in(key, 2000 + i)
        E, Fe = s["E"], s["Fe"]
        experts = mats(jax.random.fold_in(k, 1), {
            "wi_gate": ((E, d, Fe), d), "wi_up": ((E, d, Fe), d),
            "wo": ((E, Fe, d), Fe)})
        experts["router"] = draw(jax.random.fold_in(k, 2), (d, E),
                                 1.0 / math.sqrt(d), jnp.float32)
        experts["shared"] = swiglu(jax.random.fold_in(k, 3), s["Fs"])
        return layer(k, {"moe": experts})

    k0 = jax.random.fold_in(key, 1000)
    base = {
        "embed": {"table": draw(jax.random.fold_in(key, 0), (s["V"], d),
                                1.0)},
        "lm_head": {"w": draw(jax.random.fold_in(key, 1), (d, s["V"]),
                              1.0 / math.sqrt(d))},
        "lead": {"0": layer(k0, {"mlp": swiglu(jax.random.fold_in(k0, 9),
                                               s["F"])})},
        "layers": jax.lax.map(moe_layer, jnp.arange(s["L"] - 1)),
        "final_norm": {"scale": jnp.ones((d,), dtype)},
    }
    return base, init_adapters(jax.random.fold_in(key, 3), model, fl,
                               clients)


def adapter_dims(model: dict) -> dict:
    s = sizes(model)
    return {"wq": ((s["d"],), (s["H"], s["dn"] + s["dr"])),
            "kv_a": ((s["d"],), (s["R"] + s["dr"],)),
            "kv_b": ((s["R"],), (s["H"], s["dn"] + s["dv"])),
            "wo": ((s["H"], s["dv"]), (s["d"],))}


def init_adapters(key, model: dict, fl: dict, clients: int):
    r = fl["lora_rank"]
    n_moe = model["num_hidden_layers"] - 1

    def one(k, lead):
        out = {}
        for j, (name, (ins, outs)) in enumerate(sorted(
                adapter_dims(model).items())):
            bound = 1.0 / math.sqrt(math.prod(ins))
            a = jax.random.uniform(jax.random.fold_in(k, j), lead + ins + (r,),
                                   jnp.float32, -bound, bound)
            out[name] = {"a": a, "b": jnp.zeros(lead + (r,) + outs,
                                               jnp.float32)}
        return out

    ad = {"lead": {"0": one(jax.random.fold_in(key, 0), ())},
          "layers": one(jax.random.fold_in(key, 1), (n_moe,))}
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (clients,) + x.shape),
                        ad)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fp8(w):
    """Round a weight to float8 e4m3 under one absmax scale."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32)) / 448.0
    q = (w32 / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def _cast(tree, mode: str):
    """Base weights of one layer (or the head) in ``mode``'s precision."""
    if mode == "f32":
        return jax.tree.map(lambda x: x.astype(jnp.float32), tree)
    if mode != "fp8":
        raise ValueError(f"unknown precision {mode!r}")
    return {k: (_cast(v, mode) if isinstance(v, dict)
                else _fp8(v) if k in MATRICES
                else v.astype(jnp.bfloat16) if k != "router" else v)
            for k, v in tree.items()}


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def yarn(model: dict):
    """(inverse frequencies (dr/2,), softmax scale) of the config's rope."""
    s, rs = sizes(model), model["rope_scaling"]
    dr, theta, f = s["dr"], float(model["rope_theta"]), float(rs["factor"])
    i = np.arange(dr // 2)
    base = theta ** (-2.0 * i / dr)

    def dim_of(rot):
        return dr * math.log(rs["original_max_position_embeddings"]
                             / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dim_of(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(rs["beta_slow"])), dr - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    inv = base / f * ramp + base * (1.0 - ramp)
    m_all = 0.1 * rs["mscale_all_dim"] * math.log(f) + 1.0
    return inv.astype(np.float32), m_all ** 2 / math.sqrt(s["dn"] + dr)


def _rotate(x, pos, inv):
    """x (B, T, ..., dr): halves (a, b) -> (a cos - b sin, b cos + a sin)
    at angle ``pos * inv``."""
    ang = pos[:, None].astype(jnp.float32) * inv
    shape = (x.shape[1],) + (1,) * (x.ndim - 3) + (inv.shape[0],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    h = x.shape[-1] // 2
    a, b = x[..., :h].astype(jnp.float32), x[..., h:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _lora(x, ad, n_in, scale):
    h = jnp.tensordot(x, ad["a"].astype(x.dtype), n_in)
    return jnp.tensordot(h, ad["b"].astype(x.dtype), 1) * scale


def _attention(h, w, ad, model, scale, pos, inv, sm_scale):
    s = sizes(model)
    dn, R = s["dn"], s["R"]
    q = jnp.einsum("btd,dhk->bthk", h, w["wq"]) + _lora(h, ad["wq"], 1,
                                                         scale)
    kv = jnp.concatenate([h @ w["w_dkv"], h @ w["w_krope"]], -1) \
        + _lora(h, ad["kv_a"], 1, scale)
    c = _rms(kv[..., :R], w["kv_norm"], model["rms_norm_eps"])
    k_rope = _rotate(kv[..., R:], pos, inv)                  # (B, T, dr)
    up = jnp.concatenate([jnp.einsum("btr,rhk->bthk", c, w["w_uk"]),
                          jnp.einsum("btr,rhk->bthk", c, w["w_uv"])], -1) \
        + _lora(c, ad["kv_b"], 1, scale)
    k_nope, v = up[..., :dn], up[..., dn:]
    q_rope = _rotate(q[..., dn:], pos, inv)
    sc = (jnp.einsum("bqhk,bshk->bhqs", q[..., :dn], k_nope)
          + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope)).astype(jnp.float32)
    T = h.shape[1]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    sc = jnp.where(causal, sc * sm_scale, -jnp.inf)
    p = jax.nn.softmax(sc, -1).astype(h.dtype)
    o = jnp.einsum("bhqs,bshk->bqhk", p, v)
    return jnp.einsum("bthk,hkd->btd", o, w["wo"]) + _lora(o, ad["wo"], 2,
                                                           scale)


def _swiglu(x, w):
    g = x @ w["wi_gate"]
    return (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
            * (x @ w["wi_up"])) @ w["wo"]


def _gates(u, router, K):
    """Softmax scores over the experts, the top ``K`` kept as they are."""
    probs = jax.nn.softmax(u.astype(jnp.float32) @ router, -1)
    return jax.lax.top_k(probs, K)


def routed_per_token(u, w, K):
    """``y_t = sum_e g_te FFN_e(u_t)`` for every token, ``g_te`` the
    softmax score where ``e`` is among the token's top ``K`` and 0
    elsewhere: the sum as it stands, every token through every expert,
    one expert at a time, with no sort and no grouped product.  The
    products run in ``u``'s dtype, the sum in float32."""
    g, e = _gates(u, w["router"], K)
    T = u.shape[0]
    gate = jnp.zeros((T, w["wi_gate"].shape[0]), jnp.float32)
    gate = gate.at[jnp.arange(T)[:, None], e].set(g)

    @jax.checkpoint
    def add(y, expert):
        wg, wu, wo, ge = expert
        f = _swiglu(u, {"wi_gate": wg, "wi_up": wu, "wo": wo})
        return y + ge[:, None] * f.astype(jnp.float32), None

    y, _ = jax.lax.scan(add, jnp.zeros(u.shape, jnp.float32),
                        (w["wi_gate"], w["wi_up"], w["wo"], gate.T))
    return y.astype(u.dtype)


def routed(u, w, K, precision=None):
    """The same sum taken expert by expert: every (token, expert) pair,
    sorted by expert, through its expert's SwiGLU as one grouped product
    (only the pairs the router chose are computed)."""
    g, e = _gates(u, w["router"], K)
    T, E = u.shape[0], w["wi_gate"].shape[0]
    tok = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(e.reshape(-1), stable=True)
    count = jnp.sum(e.reshape(-1)[:, None] == jnp.arange(E)[None, :], 0)
    rows = u[tok[order]]
    mm = lambda x, m: jax.lax.ragged_dot(                     # noqa: E731
        x, m, count.astype(jnp.int32), precision=precision)
    hid = jax.nn.silu(mm(rows, w["wi_gate"]).astype(jnp.float32)).astype(
        u.dtype) * mm(rows, w["wi_up"])
    y = mm(hid, w["wo"]) * g.reshape(-1)[order][:, None].astype(u.dtype)
    return jnp.zeros_like(u).at[tok[order]].add(y)


def hidden(base, ad, tokens, model: dict, fl: dict, mode: str = "f32",
           per_token: bool = False):
    """Final normed hidden states (B, T, d) of sequences ``tokens`` (B, T)
    under one client's adapters ``ad``; the routed experts by
    :func:`routed_per_token` if ``per_token``, else by :func:`routed`."""
    eps, K = model["rms_norm_eps"], model["num_experts_per_tok"]
    scale = fl["lora_alpha"] / fl["lora_rank"]
    inv, sm_scale = yarn(model)
    inv = jnp.asarray(inv)
    prec = jax.lax.Precision.HIGHEST if mode == "f32" else None
    pos = jnp.arange(tokens.shape[1])
    act = jnp.float32 if mode == "f32" else jnp.bfloat16
    x = base["embed"]["table"][tokens].astype(act)

    def block(x, w, a, ffn):
        w = _cast(w, mode)
        h = _rms(x, w["ln1"]["scale"], eps)
        x = x + _attention(h, w["attn"], a, model, scale, pos, inv,
                           sm_scale)
        return x + ffn(_rms(x, w["ln2"]["scale"], eps), w)

    def moe(u, w):
        m = w["moe"]
        flat = u.reshape(-1, u.shape[-1])
        r = (routed_per_token(flat, m, K) if per_token
             else routed(flat, m, K, prec))
        return _swiglu(u, m["shared"]) + r.reshape(u.shape)

    x = jax.checkpoint(lambda x, w, a: block(
        x, w, a, lambda u, w: _swiglu(u, w["mlp"])))(
            x, base["lead"]["0"], ad["lead"]["0"])
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, wa: (block(x, wa[0], wa[1], moe), None)),
        x, (base["layers"], ad["layers"]))
    return _rms(x, _cast(base["final_norm"], mode)["scale"], eps)


def logits(base, ad, tokens, model: dict, fl: dict, mode: str = "f32",
           per_token: bool = False):
    """Logits (B, T, V) float32 of sequences ``tokens`` (B, T)."""
    prec = "highest" if mode == "f32" else "default"
    with jax.default_matmul_precision(prec):
        h = hidden(base, ad, tokens, model, fl, mode, per_token)
        head = _cast(base["lm_head"], mode)["w"]
        return (h @ head).astype(jnp.float32)


def client_loss(ad, base, batch, model: dict, fl: dict, mode: str = "f32",
                per_token: bool = False):
    """Mean next-token cross-entropy of one client's batch (B, T + 1)."""
    lg = logits(base, ad, batch[:, :-1], model, fl, mode, per_token)
    gold = jnp.take_along_axis(lg, batch[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


# ---------------------------------------------------------------------------
# the rounds
# ---------------------------------------------------------------------------

def adamw_steps(ad, base, batches, model: dict, fl: dict, mode: str = "f32"):
    """AdamW from a fresh state over ``batches`` (steps, B, T + 1):
    (adapters, each step's loss)."""
    lr, b1, b2, eps = fl["lr"], 0.9, 0.999, 1e-8
    m = jax.tree.map(jnp.zeros_like, ad)
    v = jax.tree.map(jnp.zeros_like, ad)
    losses = []
    for t in range(batches.shape[0]):
        loss, g = jax.value_and_grad(client_loss)(ad, base, batches[t],
                                                  model, fl, mode)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        ad = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** (t + 1)))
            / (jnp.sqrt(v / (1 - b2 ** (t + 1))) + eps), ad, m, v)
        losses.append(loss)
    return ad, jnp.stack(losses)


def client_grads(base, stacked, batches, model: dict, fl: dict,
                 mode: str = "f32"):
    """Each client's gradient of its loss on its batch ``batches[c]`` (B,
    T + 1) with respect to its adapters ``stacked`` (per client), by
    ``jax.grad`` through :func:`routed_per_token`; stacked per client."""
    grad = jax.jit(jax.grad(lambda a, b, t: client_loss(
        a, b, t, model, fl, mode, per_token=True)))
    out = [jax.tree.map(np.asarray, grad(jax.tree.map(lambda x: x[c],
                                                      stacked),
                                         base, batches[c]))
           for c in range(batches.shape[0])]
    return jax.tree.map(lambda *xs: np.stack(xs), *out)


def fedavg(stacked, cluster_ids: np.ndarray, global_round: bool):
    """Cluster FedAvg (each client gets its cluster's mean); on a global
    round the clusters' means averaged by member count go to all."""
    ids = np.asarray(cluster_ids)
    k = int(ids.max()) + 1
    member = np.eye(k)[ids].T                                  # (k, C)
    counts = member.sum(axis=1)

    def agg(x):
        x = np.asarray(x, np.float64)
        flat = x.reshape(x.shape[0], -1)
        means = member @ flat / counts[:, None]
        out = (np.broadcast_to(counts @ means / counts.sum(), flat.shape)
               if global_round else means[ids])
        return out.reshape(x.shape).astype(np.float32)

    return jax.tree.map(agg, stacked)


def client_tokens(seed: int, client: int, round_idx: int, sequences: int,
                  seq_len: int, vocab: int, zipf_a: float) -> np.ndarray:
    """Window ``round_idx`` of a client's stream: ids of Zipf ranks (the
    inverse CDF of ``default_rng([seed, client, round_idx])``'s uniforms),
    mapped by the client's own permutation (``default_rng([seed,
    client])``)."""
    w = 1.0 / np.power(np.arange(1, vocab + 1), zipf_a)
    cdf = np.cumsum(w / w.sum())
    perm = np.random.default_rng([seed, client]).permutation(vocab)
    u = np.random.default_rng([seed, client, round_idx]).random(
        (sequences, seq_len + 1))
    ranks = np.minimum(np.searchsorted(cdf, u, side="right"), vocab - 1)
    return perm[ranks].astype(np.int32)


def round_batches(seed: int, clients, round_idx: int, model: dict,
                  fl: dict) -> np.ndarray:
    """Every client's training batches of a round: (C, steps, B, T + 1)."""
    steps, B = model["local_steps"], model["batch_size"]
    return np.stack([client_tokens(
        seed, int(c), round_idx, steps * B, fl["seq_len"],
        model["vocab_size"], fl["zipf_a"]).reshape(steps, B, -1)
        for c in clients])


def run_rounds(base, adapters0, seed: int, clients, cluster_ids, rounds: int,
               model: dict, fl: dict, mode: str = "f32", start: int = 0):
    """``rounds`` rounds from ``adapters0`` (stacked per client), round
    ``t`` on window ``start + t`` of every stream, global when ``t + 1``
    is a multiple of ``l``.  Returns (adapters after each round, each
    round's per-step losses (rounds, C, steps))."""
    step = jax.jit(lambda b, ad, batches: adamw_steps(ad, b, batches,
                                                      model, fl, mode))
    ad = jax.tree.map(np.asarray, adapters0)
    snaps, losses = [], []
    prec = "highest" if mode == "f32" else "default"
    for t in range(rounds):
        data = round_batches(seed, clients, start + t, model, fl)
        new, loss = [], []
        for c in range(len(clients)):
            with jax.default_matmul_precision(prec):
                a, lo = step(base, jax.tree.map(lambda x: x[c], ad),
                             data[c])
            new.append(jax.tree.map(np.asarray, a))
            loss.append(np.asarray(lo, np.float32))
        ad = jax.tree.map(lambda *xs: np.stack(xs), *new)
        ad = fedavg(ad, cluster_ids,
                    (t + 1) % fl["local_rounds_per_global"] == 0)
        snaps.append(ad)
        losses.append(np.stack(loss))
    return snaps, np.stack(losses)
