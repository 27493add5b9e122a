"""The LM HFL cell at a size a CPU test can hold: DeepSeek-V2's shape
(one dense layer, then one MoE layer of 8 experts, top-2, one shared
expert; latent attention with YaRN) at width 64, a 256-id vocabulary,
8 clients two to a cluster, sequences of 32 tokens.  ``run_cell``
drives a whole run of the harness on whatever devices JAX has, skipping
only its look for a chip.  Used by the tests."""
from __future__ import annotations

import contextlib
import copy
from types import SimpleNamespace
from unittest import mock

from bench import loader

MODEL = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
         "moe_intermediate_size": 32, "n_routed_experts": 8,
         "num_experts_per_tok": 2, "n_shared_experts": 1,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "vocab_size": 256, "local_steps": 2,
         "batch_size": 2}
TRAFFIC = {"kind": "hfl_rounds", "rounds_per_call": 2, "seq_len": 32,
           "zipf_a": 1.2}


# the tiny cell's own limits: at width 64 and lr 5e-3 bf16 rounding
# moves the numbers more than at the published widths (CPU readings of
# the sound run: loss 1.8e-4, update 8e-3 and 1.2e-2, logits 0.025,
# gradients 0.022; fp8 0.136 and capacity 0.078 on the gradients)
LIMITS = {"loss_gap": 6e-4, "update1_gap": 0.05, "change2_gap": 0.05,
          "client_spread_gap": 1e-3, "max_logit_gap": 0.08,
          "grad_gap": 0.05}


def config() -> dict:
    cfg = copy.deepcopy(loader.config("deepseek-v2-lite-hfl"))
    cfg.update(MODEL)
    cfg["fl"].update(clients_per_cluster=2, lora_rank=4, lora_alpha=8,
                     lr=5e-3, clients_per_pass=4)
    cfg["correct"] = dict(LIMITS)
    return cfg


@contextlib.contextmanager
def cell():
    """Patch the loader so that workload ``tiny-lm`` resolves to the tiny
    configuration and traffic."""
    cfg = config()
    work = {"name": "tiny-lm", "config": cfg["name"], "traffic": "tiny-lm",
            "chips": 1}
    with mock.patch.object(loader, "workload", lambda n, root=None: work), \
            mock.patch.object(loader, "config", lambda n: cfg), \
            mock.patch.object(loader, "traffic", lambda n: TRAFFIC):
        yield work


def run_cell(seed: int, seconds: float, control: bool = False,
             trace: bool = False):
    """(result, checks, context) of one run of the tiny LM cell."""
    import jax

    from bench import run

    args = SimpleNamespace(workload="tiny-lm", seed=seed, seconds=seconds,
                           trace=int(trace))
    with cell():
        return run.execute(args, jax.devices()[:1], control=control)
