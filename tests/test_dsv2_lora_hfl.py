"""DeepSeek-V2 (latent attention with YaRN, dropless MoE) fine-tuned by
LoRA in continual hierarchical FL, against the benchmark's plain
reference (``bench/configs/deepseek-v2-lite-hfl.py``) at a tiny shape on
seeded random weights: 1 dense + 1 MoE layer, width 64, 8 experts top-2,
1 shared expert, small MLA ranks, YaRN on."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import loader, tiny_lm  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.core.topology import ClusterTopology  # noqa: E402
from repro.data.tokens import ClientTokenStreams  # noqa: E402
from repro.fl.client import lm_client_losses  # noqa: E402
from repro.fl.hierarchy import ContinualHFL, HFLRunConfig  # noqa: E402
from repro.models import make_model, moe  # noqa: E402
from repro.models.attention import mla_softmax_scale  # noqa: E402
from repro.models.common import ParamBuilder  # noqa: E402
from repro.models.lora import with_adapters  # noqa: E402
from repro.models.rope import rope_frequencies, yarn_frequencies  # noqa
from repro.models.transformer import stacked_rope  # noqa: E402
from repro.telemetry import Telemetry  # noqa: E402

REF = loader.reference("deepseek-v2-lite-hfl")
DRIVER = loader.driver("hfl_lm")
CFG = tiny_lm.config()
FL = DRIVER.reference_fl(CFG, tiny_lm.TRAFFIC)
S = tiny_lm.TRAFFIC["seq_len"]


def _f32(arch: ArchConfig) -> ArchConfig:
    return dataclasses.replace(arch, model=dataclasses.replace(
        arch.model, dtype="float32", param_dtype="float32"))


ARCH = _f32(DRIVER.program_config(CFG))


def _weights(seed: int, clients: int, b_scale: float = 0.05):
    """float32 base and stacked adapters, ``B`` random so that every
    adapter moves the result."""
    base, ad = REF.init_params(jax.random.key(seed), CFG, FL, clients,
                               dtype=jnp.float32)
    leaves, tree = jax.tree.flatten(ad)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    ad = jax.tree.unflatten(tree, [
        x + b_scale * jax.random.normal(k, x.shape) for x, k in
        zip(leaves, keys)])
    return base, ad


def _tokens(seed: int, shape):
    return jax.random.randint(jax.random.key(seed), shape, 0,
                              CFG["vocab_size"])


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_logits_match_the_reference():
    base, ad = _weights(0, 1)
    one = jax.tree.map(lambda x: x[0], ad)
    toks = _tokens(2, (2, S))
    fwd = make_model(ARCH).forward
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda b, a, t: fwd(with_adapters(b, a),
                                          {"tokens": t})[0])(base, one, toks)
    want = jax.jit(lambda b, a, t: REF.logits(b, a, t, CFG, FL))(base, one,
                                                                  toks)
    assert _rel(got, want) < 1e-5


def test_lora_gradients_match_the_reference():
    """Each client's gradient, from the batched program that runs every
    client's sequences through the one base, is ``jax.grad`` of the
    reference's loss on that client alone."""
    C = 3
    base, ad = _weights(3, C)
    toks = _tokens(4, (C, 2, S + 1))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda a, b, t: jnp.sum(lm_client_losses(
            a, b, t, ARCH))))(ad, base, toks)
        ref_grad = jax.jit(jax.grad(lambda a, b, t: REF.client_loss(
            a, b, t, CFG, FL)))
        for c in range(C):
            want = ref_grad(jax.tree.map(lambda x: x[c], ad), base, toks[c])
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                assert _rel(g[c], w) < 1e-4


ASSIGN = np.array([0, 1, 0, 2, 1, 0])
STREAM_SEED = 2 ** 33 + 5


def _continual(base, telemetry=None) -> ContinualHFL:
    C = len(ASSIGN)
    topo = ClusterTopology(assign=ASSIGN, n_devices=C, n_edges=3,
                           lam=np.ones(C), r=np.ones(3), l=2)
    streams = ClientTokenStreams(CFG["vocab_size"], S, seed=STREAM_SEED,
                                 zipf_a=FL["zipf_a"])
    run = HFLRunConfig(rounds=2, batch_size=CFG["batch_size"], lr=FL["lr"],
                       seed=1, local_steps=CFG["local_steps"],
                       clients_per_pass=3)
    return ContinualHFL(ARCH, streams, np.arange(C), topo, run,
                        telemetry=telemetry, base=base)


@pytest.fixture(scope="module")
def rounds():
    """A cluster round alone, then a cluster and a global round, of
    ``ContinualHFL`` with telemetry, from the same adapters."""
    base, ad = _weights(5, len(ASSIGN), b_scale=0.0)
    tel = Telemetry()
    hfl = _continual(base, tel)
    hfl.params = ad
    with jax.default_matmul_precision("highest"):
        hfl.run_rounds(1)
        after1 = jax.tree.map(np.asarray, hfl.params)
        hfl.params, hfl.window = ad, 0
        res = hfl.run_rounds(2)
    return base, ad, after1, hfl, res, tel


def test_cluster_and_global_round_match_the_reference(rounds):
    """One cluster round and one global round of ``ContinualHFL`` (local
    AdamW steps, cluster FedAvg, then global FedAvg of the adapters)
    against the reference's."""
    base, ad, after1, hfl, res, _ = rounds
    C = len(ASSIGN)
    snaps, losses = REF.run_rounds(base, jax.tree.map(np.asarray, ad),
                                   STREAM_SEED, np.arange(C), ASSIGN, 2, CFG,
                                   FL)
    np.testing.assert_allclose(res.train_loss, losses, rtol=1e-5)
    for got, want in ((after1, snaps[0]), (hfl.params, snaps[1])):
        for g, w, p in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                           jax.tree.leaves(ad)):
            assert _rel(np.asarray(g) - p, w - np.asarray(p)) < 1e-3
    spread = max(float(np.ptp(np.asarray(x), axis=0).max())
                 for x in jax.tree.leaves(hfl.params))
    assert spread == 0.0


def test_each_call_goes_on_to_the_next_windows(rounds):
    """A call after the first takes the streams' next window, and its
    first round is again a cluster round: the reference's round on window
    2 from the adapters the first call left."""
    base, ad, _, _, _, _ = rounds
    C = len(ASSIGN)
    hfl = _continual(base)
    hfl.params = ad
    with jax.default_matmul_precision("highest"):
        hfl.run_rounds(2)
        assert hfl.window == 2
        start = jax.tree.map(np.asarray, hfl.params)
        res = hfl.run_rounds(1)
    assert hfl.window == 3
    snaps, losses = REF.run_rounds(base, start, STREAM_SEED, np.arange(C),
                                   ASSIGN, 1, CFG, FL, start=2)
    np.testing.assert_allclose(res.train_loss, losses, rtol=1e-5)
    for g, w, p in zip(jax.tree.leaves(hfl.params),
                       jax.tree.leaves(snaps[0]), jax.tree.leaves(start)):
        assert _rel(np.asarray(g) - p, w - p) < 1e-3


def test_program_gradients_match_the_per_token_reference():
    """The gradients a local step takes (``lm_client_grads``, clients in
    passes) against ``jax.grad`` of the reference whose routed experts
    are the per-token sum over every expert, with no grouped product."""
    C = 4
    base, ad = _weights(7, C)
    toks = _tokens(8, (C, 2, S + 1))
    with jax.default_matmul_precision("highest"):
        got = DRIVER.program_grads(ARCH, base, ad, toks, 2, np.arange(C))
        want = REF.client_grads(base, ad, toks, CFG, FL)
    assert DRIVER.grad_gap(got, want) < 1e-4
    turned = jax.tree.map(lambda x: -x, got)
    assert DRIVER.grad_gap(turned, want) == pytest.approx(2.0, rel=1e-4)


def _skewed_moe(seed: int, t: int = 64):
    """A MoE layer whose router sends every token's first choice to
    expert 0 and its second to expert 1 or 2."""
    E, d, f = CFG["n_routed_experts"], CFG["hidden_size"], 16
    k = jax.random.split(jax.random.key(seed), 5)
    w = {"wi_gate": jax.random.normal(k[0], (E, d, f)) / math.sqrt(d),
         "wi_up": jax.random.normal(k[1], (E, d, f)) / math.sqrt(d),
         "wo": jax.random.normal(k[2], (E, f, d)) / math.sqrt(f)}
    router = jnp.zeros((d, E)).at[0, 0].set(8.0).at[1, 1].set(2.0)
    router = router.at[2, 2].set(2.0)
    x = jax.random.normal(k[3], (t, d)).at[:, 0].set(1.0)
    return x, w, router


def test_dropless_moe_keeps_every_assignment_under_skew():
    """Expert 0 takes every token, well past what capacity 1.25 would
    let it keep; the program's output is still the per-token sum over each
    token's experts, which the capacity dispatch is not."""
    x, w, router = _skewed_moe(6)
    K = CFG["num_experts_per_tok"]
    p = dict(w, router=router)
    m = dataclasses.replace(ARCH.model.moe, num_shared=0, d_shared=0)
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(lambda p, x: moe.apply_moe(p, m, x, "silu"))(
            p, x[None])
        want = jax.jit(lambda x, p: REF.routed_per_token(x, p, K))(x, p)
        g, e = jax.lax.top_k(jax.nn.softmax(x @ router), K)
        dropped = jax.jit(DRIVER.capacity_routed())(x, g, e, w)
    assert int(jnp.sum(e == 0)) == x.shape[0]
    assert _rel(got[0], want) < 1e-5
    assert _rel(dropped, want) > 0.3
    assert _rel(REF.routed(x, p, K, jax.lax.Precision.HIGHEST), want) < 1e-5


def test_yarn_off_is_the_plain_rope():
    """A config without rope scaling computes the rope frequencies and
    softmax scale it always had; YaRN's match the reference's."""
    plain = get_config("deepseek-v2-lite-16b").model
    plain = dataclasses.replace(plain, attention=dataclasses.replace(
        plain.attention, rope_scaling=None))
    a = plain.attention
    np.testing.assert_array_equal(
        np.asarray(stacked_rope(plain, [0])[0]),
        rope_frequencies(a.mla.qk_rope_head_dim, a.rope_theta))
    assert mla_softmax_scale(a) == 1.0 / math.sqrt(192)
    yarn = get_config("deepseek-v2-lite-16b").model.attention
    inv, scale = REF.yarn(loader.config("deepseek-v2-lite-hfl"))
    np.testing.assert_allclose(
        yarn_frequencies(64, 10_000.0, yarn.rope_scaling), inv, rtol=1e-6)
    assert mla_softmax_scale(yarn) == pytest.approx(scale, rel=1e-12)
    assert scale * math.sqrt(192) == pytest.approx(1.2608 ** 2, rel=1e-3)


def test_fan_in_is_read_from_the_contracted_dims():
    pb = ParamBuilder(jax.random.key(0), dtype=jnp.float32)
    shapes = {"wq": ((512, 4, 48), ("embed", "heads", "head_dim"), 512),
              "w_uk": ((256, 4, 32), ("kv_lora", "heads", "head_dim"), 256),
              "wo": ((4, 64, 512), ("heads", "head_dim", "embed"), 256),
              "wi": ((8, 512, 64), ("expert", "embed", "mlp"), 512),
              "we": ((8, 64, 512), ("expert", "mlp", "embed"), 64),
              "w2": ((64, 512), ("mlp", "embed"), 64)}
    for name, (shape, axes, fan) in shapes.items():
        w = pb.param(name, shape, axes)
        assert float(jnp.std(w)) == pytest.approx(1 / math.sqrt(fan),
                                                  rel=0.05), name


def test_client_streams_are_the_reference_windows():
    streams = ClientTokenStreams(CFG["vocab_size"], S, seed=2 ** 34 + 3,
                                 zipf_a=FL["zipf_a"])
    train, held = streams.round_tokens(np.arange(4), 3, 2, 2)
    want = REF.round_batches(2 ** 34 + 3, np.arange(4), 3, CFG, FL)
    np.testing.assert_array_equal(train, want)
    assert held.shape == (4, 2, S + 1)
    assert not np.array_equal(held, train[:, 0])
    again, _ = streams.round_tokens(np.arange(4), 4, 2, 2)
    assert not np.array_equal(again, train)


def test_lm_round_records_token_spans_and_model_uploads(rounds):
    *_, hfl, _, tel = rounds
    C = len(ASSIGN)
    names = {sp.name for sp in tel.tracer.spans}
    assert {"hfl.data.tokens", "hfl.data.upload"} <= names
    assert "hfl.data.windows" not in names
    counters = tel.metrics.snapshot()["counters"]
    per_client = sum(x[0].nbytes for x in jax.tree.leaves(hfl.params))
    steps, B = CFG["local_steps"], CFG["batch_size"]
    assert counters["hfl.rounds.cluster"] == 2
    assert counters["hfl.model_upload_bytes"] == 3 * C * per_client
    assert counters["hfl.train_tokens"] == 3 * C * steps * B * S
