"""The LM HFL cell (``hfl-lora-dsv2lite``) at a size a CPU test can hold:
a whole run is ``correct`` on the sound program and comes out false with
each planted fault; the configuration is the registry's model cut as the
file says; its readers and its count of operations."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import lm_flops, loader, tiny_lm  # noqa: E402

SEED = 2 ** 33 + 17
DRIVER = loader.driver("hfl_lm")


def test_sound_run_is_correct():
    result, checks, ctx = tiny_lm.run_cell(SEED, 0.5)
    assert result["correct"], checks.as_dict()
    assert set(result["metrics"]) == {"round_ms", "setup_s"}
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert ctx["readings"]["client_spread_gap"] == 0.0


def _capacity(monkeypatch):
    from repro.models import moe

    monkeypatch.setattr(moe, "_routed", DRIVER.capacity_routed())


def _no_yarn(monkeypatch):
    import math

    from repro.models import attention, rope, transformer

    monkeypatch.setattr(transformer, "yarn_frequencies",
                        lambda hd, theta, rs: rope.rope_frequencies(hd,
                                                                    theta))
    monkeypatch.setattr(attention, "mla_softmax_scale", lambda a: 1.0
                        / math.sqrt(a.mla.qk_nope_head_dim
                                    + a.mla.qk_rope_head_dim))


def _no_exchange(monkeypatch):
    import repro.fl.hierarchy as h

    monkeypatch.setattr(h, "cluster_fedavg", lambda p, *a, **k: p)
    monkeypatch.setattr(h, "global_fedavg", lambda p, *a, **k: p)


def _sign(monkeypatch):
    import repro.fl.client as cl

    monkeypatch.setattr(cl, "lm_client_grads",
                        DRIVER.turned_grads(cl.lm_client_grads))


@pytest.mark.parametrize("fault", [_capacity, _no_yarn, _no_exchange,
                                   _sign])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    import jax

    jax.clear_caches()
    fault(monkeypatch)
    try:
        result, checks, _ = tiny_lm.run_cell(SEED, 0.5)
    finally:
        jax.clear_caches()
    assert not result["correct"], checks.as_dict()


def test_fp8_base_fails_a_limit():
    """The reference on an fp8 base, one precision step below the
    configuration's bf16, in the program's place: at least one number is
    over its limit."""
    import jax

    cfg = tiny_lm.config()
    fl = DRIVER.reference_fl(cfg, tiny_lm.TRAFFIC)
    ref = loader.reference(cfg["name"])
    C, S = 4, tiny_lm.TRAFFIC["seq_len"]
    ids = np.array([0, 0, 1, 1])
    base, ad = jax.jit(lambda k: ref.init_params(k, cfg, fl, C))(
        jax.random.key(11))
    p0 = jax.tree.map(np.asarray, ad)
    toks = ref.round_batches(SEED, np.arange(C), 0, cfg, fl)[0, 0, :, :S]

    def follow(mode):
        snaps, losses = ref.run_rounds(base, p0, SEED, np.arange(C), ids, 2,
                                       cfg, fl, mode)
        lg = jax.jit(lambda b, a, t: ref.logits(b, a, t, cfg, fl, mode,
                                                per_token=True))(
            base, jax.tree.map(lambda x: x[0], snaps[-1]), toks)
        g = ref.client_grads(base, snaps[0], batches, cfg, fl, mode)
        return (snaps[0], snaps[-1], losses.mean(axis=1).reshape(-1)), lg, g

    batches = ref.round_batches(SEED, np.arange(C), 1, cfg, fl)[:, 0]
    want, want_logits, want_grads = follow("f32")
    got, got_logits, got_grads = follow("fp8")
    readings = DRIVER.compare(got, want, p0, got_logits, want_logits,
                              got_grads, want_grads)
    over = [k for k, v in readings.items() if v > cfg["correct"][k]]
    assert over, readings


def test_program_config_is_the_registry_model_cut():
    """At the file's sizes the program runs the registry's DeepSeek-V2-
    Lite with only the cut (depth, vocabulary slice) and the adapters."""
    import dataclasses

    from repro.configs import get_config

    cfg = loader.config("deepseek-v2-lite-hfl")
    arch = DRIVER.program_config(cfg)
    reg = get_config(cfg["arch"])
    want = dataclasses.replace(reg.model, num_layers=5, vocab_size=12_800,
                               attention=dataclasses.replace(
                                   reg.model.attention,
                                   lora=arch.model.attention.lora))
    assert arch.model == want and arch.run == reg.run
    assert arch.model.attention.lora.scale == 2.0
    bad = dict(cfg, norm_topk_prob=True)
    with pytest.raises(ValueError):
        DRIVER.program_config(bad)


def test_reference_weights_have_program_layout_at_full_size():
    import jax

    from repro.models import make_model
    from repro.models.lora import init_adapters

    cfg = loader.config("deepseek-v2-lite-hfl")
    fl = DRIVER.reference_fl(cfg, loader.traffic("continual-lora-rounds"))
    arch = DRIVER.program_config(cfg)
    ref = loader.reference(cfg["name"])
    base, ad = jax.eval_shape(lambda: ref.init_params(jax.random.key(0), cfg,
                                                      fl, 20))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)  # noqa
    want = jax.eval_shape(lambda: make_model(arch).init_params(
        jax.random.key(0))[0])
    assert shape(base) == shape(want)
    one = jax.eval_shape(lambda: init_adapters(jax.random.key(0),
                                               arch.model))
    assert shape(one) == jax.tree.map(lambda x: (x.shape[1:], x.dtype), ad)
    n_base = sum(x.size for x in jax.tree.leaves(base))
    n_ad = sum(x.size for x in jax.tree.leaves(ad)) // 20
    assert abs(n_base / 1e9 - 2.47) < 0.01
    assert n_ad == 1_315_840                     # 5.26 MB of float32


def test_round_flops_count_the_routed_experts_only():
    cfg = loader.config("deepseek-v2-lite-hfl")
    fl = cfg["fl"]
    fwd, train = lm_flops.lm_token_flops(cfg, fl, 512)
    d, fe = 2048, 1408
    routed_and_shared = 3 * d * fe * (6 + 2)
    # forward: about 440 M active parameters, twice
    assert 0.87e9 < fwd < 0.92e9
    assert fwd > 2 * 4 * routed_and_shared
    assert 1.98 * fwd < train < 2.1 * fwd
    total = lm_flops.round_flops(cfg, fl, 20, 2, 4, 512)
    assert total == pytest.approx(20 * 4 * 512 * (2 * train + fwd))
    assert 1.8e14 < total < 1.9e14


def _read(name, ctx):
    return loader.metric_reader(name).read(ctx)


def test_lm_train_ms_is_the_training_program_per_round():
    ctx = {"trace": {"program_s": {"jit_train_lm_clients": 6.0,
                                   "jit_eval_lm_clients": 1.0,
                                   "jit__cluster_fedavg": 0.1}},
           "traced_rounds": 2}
    assert _read("lm_train_ms", ctx) == pytest.approx(3000.0)
    assert _read("lm_train_ms", {}) is None
    ctx["trace"]["program_s"].pop("jit_train_lm_clients")
    assert _read("lm_train_ms", ctx) is None


def test_hfl_lm_mfu_is_needed_work_over_round_time_at_peak():
    class Dev:
        device_kind = "TPU v5 lite"

    ctx = {"lm_round_flops": 197e12, "round_s": 4.0, "devices": [Dev()]}
    assert _read("hfl_lm_mfu", ctx) == pytest.approx(25.0)
    assert _read("hfl_lm_mfu", {"round_s": 4.0}) is None


def test_model_upload_mb_per_round_divides_by_every_tier():
    per_round = 20 * 1_315_840 * 4
    ctx = {"program_counters": {"hfl.model_upload_bytes": 6.0 * per_round,
                                "hfl.rounds.cluster": 3.0,
                                "hfl.rounds.global": 3.0}}
    assert _read("model_upload_mb_per_round", ctx) == pytest.approx(
        105.2672)
    assert _read("model_upload_mb_per_round", {}) is None
    assert _read("model_upload_mb_per_round", {"program_counters": {
        "hfl.rounds.cluster": 1.0}}) is None
