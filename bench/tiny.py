"""Cells at a size a CPU test can hold: the serving configuration cut to
the program's own ``.reduced()`` sizes, the HFL deployment cut to two
clients a cluster, one epoch and one day of training.  ``run_cell`` drives a whole
run of the harness on whatever devices JAX has, skipping only its look
for a chip.  Used by the tests under ``tests/bench``."""
from __future__ import annotations

import contextlib
import copy
from types import SimpleNamespace
from unittest import mock

from bench import loader

SERVE_MODEL = {
    "num_hidden_layers": 2, "hidden_size": 256, "intermediate_size": 512,
    "vocab_size": 1024, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "partial_rotary_factor": 0.25, "rope_theta": 10000.0,
    "layer_norm_eps": 1e-06}
SERVE_TRAFFIC = {
    "kind": "open_loop", "arrivals": "poisson", "rate_per_s": 6.0,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                      "min": 8, "max": 200},
    "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                      "min": 4, "max": 64}}


def serve_config() -> dict:
    cfg = copy.deepcopy(loader.config("stablelm-1.6b"))
    cfg["model"].update(SERVE_MODEL)
    cfg["engine"].update(max_len=256, num_pages=64, max_seqs=4,
                         tier_reduced=True)
    return cfg


def hfl_config() -> dict:
    cfg = copy.deepcopy(loader.config("gru-traffic"))
    cfg["run"].update(clients_per_cluster=2, local_epochs=1, train_days=1,
                      val_days=1, max_val_windows=32)
    return cfg


HFL_TRAFFIC = {"kind": "hfl_rounds", "rounds_per_call": 2, "data_days": 3}


@contextlib.contextmanager
def cell(kind: str):
    """Patch the loader so that workload ``tiny-<kind>`` resolves to the
    tiny configuration and traffic."""
    cfg, traffic = ((serve_config(), SERVE_TRAFFIC) if kind == "serve"
                    else (hfl_config(), HFL_TRAFFIC))
    name = f"tiny-{kind}"
    work = {"name": name, "config": cfg["name"], "traffic": name,
            "chips": 1}
    with mock.patch.object(loader, "workload", lambda n, root=None: work), \
            mock.patch.object(loader, "config", lambda n: cfg), \
            mock.patch.object(loader, "traffic", lambda n: traffic):
        yield work


def run_cell(kind: str, seed: int, seconds: float, control: bool = False):
    """(result, checks, context) of one run of the tiny cell ``kind``."""
    import jax

    from bench import run

    args = SimpleNamespace(workload=f"tiny-{kind}", seed=seed,
                           seconds=seconds, trace=0)
    with cell(kind):
        return run.execute(args, jax.devices()[:1], control=control)
