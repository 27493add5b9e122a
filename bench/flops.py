"""Operations and bytes the algorithm needs, from a configuration's
sizes.  Only needed work counts: no padding, no logits the caller does
not read, no weights or cache entries a step need not touch.  A share of
a peak computed from these cannot pass 100% unless a time is short."""
from __future__ import annotations


def lm_sizes(model: dict) -> dict:
    return dict(L=model["num_hidden_layers"], d=model["hidden_size"],
                F=model["intermediate_size"], V=model["vocab_size"],
                H=model["num_attention_heads"],
                Hkv=model["num_key_value_heads"], hd=model["head_dim"])


def lm_layer_matmul_params(model: dict) -> int:
    """Weights of one decoder layer's matrices (attention + SwiGLU)."""
    s = lm_sizes(model)
    attn = s["d"] * s["hd"] * (2 * s["H"] + 2 * s["Hkv"])
    return attn + 3 * s["d"] * s["F"]


def lm_param_count(model: dict) -> int:
    """Every parameter: embedding, untied head, layers with their two
    LayerNorms (scale and bias), final LayerNorm."""
    s = lm_sizes(model)
    per_layer = lm_layer_matmul_params(model) + 4 * s["d"]
    return 2 * s["V"] * s["d"] + s["L"] * per_layer + 2 * s["d"]


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> int:
    s = lm_sizes(model)
    return s["L"] * 2 * s["Hkv"] * s["hd"] * itemsize


def prefill_flops(model: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` live tokens: every layer's matrices
    on each token, causal attention (scores and values over the
    ``S(S+1)/2`` pairs), and the head at the last position only."""
    s = lm_sizes(model)
    S = int(prompt_len)
    matmul = 2.0 * s["L"] * lm_layer_matmul_params(model) * S
    attn = 4.0 * s["L"] * s["H"] * s["hd"] * S * (S + 1) / 2
    return matmul + attn + 2.0 * s["d"] * s["V"]


def decode_bytes(model: dict, rows: int, live_tokens: int,
                 itemsize: int = 2) -> float:
    """One decode step of ``rows`` sequences holding ``live_tokens``
    cache entries in all (the new token's included): every layer's
    weights, the head, the norms, one embedding row per sequence, and
    the live keys and values."""
    s = lm_sizes(model)
    weights = (s["L"] * (lm_layer_matmul_params(model) + 4 * s["d"])
               + s["d"] * s["V"] + 2 * s["d"] + rows * s["d"])
    return float(weights * itemsize
                 + live_tokens * kv_bytes_per_token(model, itemsize))


def gru_window_flops(model: dict, history: int) -> float:
    """Forward and backward of the GRU over one window of ``history``
    steps: 3 x the forward's matmul operations (input and recurrent
    gates of every layer, then the head)."""
    h, layers = model["hidden_size"], model["num_layers"]
    per_step = 0
    for i in range(layers):
        din = model["input_size"] if i == 0 else h
        per_step += 2 * din * 3 * h + 2 * h * 3 * h
    forward = history * per_step + 2 * h
    return 3.0 * forward


def hfl_round_flops(model: dict, clients: int, epochs: int,
                    batches: int, batch_size: int, history: int) -> float:
    """Training operations of one HFL round: every window of every batch
    of every epoch of every client."""
    windows = clients * epochs * batches * batch_size
    return windows * gru_window_flops(model, history)
