"""Operations a round of LoRA fine-tuning of a DeepSeek-V2 model needs,
from its configuration's sizes, as ``flops.py`` counts for the other
cells: only needed work (the experts each token is routed to, causal
attention over the pairs it reads, no recomputation), and no gradient of
the frozen base's weights."""
from __future__ import annotations


def lm_token_flops(model: dict, fl: dict, seq_len: int):
    """(forward, training) operations per token of a ``seq_len``-token
    sequence: the forward's matmuls (attention projections, their
    adapters, the dense layer's or the routed and shared experts' SwiGLU,
    the router, the head) and causal attention; training adds the
    gradients of the activations (as much again, attention twice) and of
    the adapters' weights."""
    d, H, R = (model[k] for k in ("hidden_size", "num_attention_heads",
                                  "kv_lora_rank"))
    dn, dr, dv = (model[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                     "v_head_dim"))
    L, r = model["num_hidden_layers"], fl["lora_rank"]
    lead = model["first_k_dense_replace"]
    attn = d * H * (dn + dr) + d * (R + dr) + R * H * (dn + dv) + H * dv * d
    lora = r * ((d + H * (dn + dr)) + (d + R + dr) + (R + H * (dn + dv))
                + (H * dv + d))
    fe = model["moe_intermediate_size"]
    moe = (3 * d * fe * (model["num_experts_per_tok"]
                         + model["n_shared_experts"])
           + d * model["n_routed_experts"])
    mm = (L * (attn + lora) + lead * 3 * d * model["intermediate_size"]
          + (L - lead) * moe + d * model["vocab_size"])
    ctx = (seq_len + 1) / 2                  # mean causal context
    scores = L * 2.0 * H * (dn + dr + dv) * ctx
    forward = 2.0 * mm + scores
    return forward, 2.0 * 2.0 * mm + 2.0 * L * lora + 3.0 * scores


def round_flops(model: dict, fl: dict, clients: int, steps: int,
                batch: int, seq_len: int) -> float:
    """A round: every client's local steps, then the held-out batch each
    client is evaluated on."""
    fwd, train = lm_token_flops(model, fl, seq_len)
    tokens = clients * batch * seq_len
    return tokens * (steps * train + fwd)
