"""HFL driver for a registry language model fine-tuned by LoRA: the
paper's continual hierarchical rounds through the program's
``ContinualHFL.run_rounds``, back to back, on one chip.

Set-up makes the frozen base and every client's adapters from the seed,
clusters the clients with the program's HFLOP controller (as the GRU
cell's driver does) and builds one ``ContinualHFL`` over them.  That
object takes a cluster round alone, whose adapters are kept and then put
back with the streams' first window, and its first call
(``rounds_per_call`` rounds: a cluster round, then a global one); the
plain reference follows both rounds on every client.  The program's
gradients at the first cluster round's adapters and its logits after
the call are checked against the reference's per-token sum over the
experts.  The window then runs calls until ``--seconds`` has passed and
counts every round of every call it completes; each call goes on to
the streams' next windows.

With ``--trace 1`` the object carries a ``Telemetry`` whose spans are
host annotations on the profiler's clock; the spans and the counters'
increases over the window's untraced calls go to the readers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import numpy as np

from bench import common, generator, lm_flops, loader

HOST_LABELS = ("run_rounds",)

# what the program computes without a setting, as the config names it
FIXED = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
         "n_group": 1, "topk_group": 1, "norm_topk_prob": False,
         "q_lora_rank": None, "routed_scaling_factor": 1,
         "scoring_func": "softmax", "tie_word_embeddings": False,
         "topk_method": "greedy"}


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for the file's model: the registry's
    architecture with the file's sizes, YaRN and the adapters."""
    from repro.configs import get_config
    from repro.configs.base import (ArchConfig, LoRAConfig, MLAConfig,
                                    RopeScaling)

    bad = {k: (cfg[k], v) for k, v in FIXED.items() if cfg[k] != v}
    rs = cfg["rope_scaling"]
    if bad or rs["type"] != "yarn":
        raise ValueError(f"{cfg['name']}: the program does not compute "
                         f"{bad or rs}")
    arch = get_config(cfg["arch"])
    m, fl = arch.model, cfg["fl"]
    a = dataclasses.replace(
        m.attention, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rope_scaling=RopeScaling(
            type="yarn", factor=float(rs["factor"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"])),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=0,
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        lora=LoRAConfig(rank=fl["lora_rank"], alpha=float(fl["lora_alpha"])))
    fe = cfg["moe_intermediate_size"]
    moe = dataclasses.replace(
        m.moe, num_experts=cfg["n_routed_experts"],
        num_shared=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"], d_expert=fe,
        d_shared=cfg["n_shared_experts"] * fe,
        first_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"])
    model = dataclasses.replace(
        m, num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=fe, vocab_size=cfg["vocab_size"], norm_eps=cfg["rms_norm_eps"],
        attention=a, moe=moe)
    return ArchConfig(model=model, run=arch.run)


def reference_fl(cfg: dict, traffic: dict) -> dict:
    """What the reference reads of the deployment and the traffic."""
    return dict(cfg["fl"], seq_len=traffic["seq_len"],
                zipf_a=traffic["zipf_a"])


def capacity_routed(factor: float = 1.25):
    """A planted fault: the routed experts of the program as they were
    before dropless routing, each expert taking at most
    ``ceil(t * top_k / E * factor)`` (rounded up to 8) of its assignments,
    in token order, and dropping the rest."""
    import jax.numpy as jnp

    from repro.models import moe

    plain = moe._routed_plain

    def routed(xf, topw, topi, w):
        t, K = topi.shape
        E = w["wo"].shape[0]
        cap = min(max(8, -(-math.ceil(t * K / E * factor) // 8) * 8), t * K)
        flat = topi.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        counts = jnp.bincount(flat, length=E)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.zeros_like(flat).at[order].set(
            jnp.arange(t * K) - starts[flat[order]])
        keep = (rank < cap).reshape(t, K)
        return plain(xf, jnp.where(keep, topw, 0.0), topi, w)

    return routed


def turned_grads(grads):
    """A planted fault: the clients' gradients with their sign turned,
    so that every local step climbs the loss."""
    import jax

    def turned(*args, **kwargs):
        g, losses = grads(*args, **kwargs)
        return jax.tree.map(lambda x: -x, g), losses

    return turned


def without_yarn(arch):
    """A planted fault: the program's config with plain rope."""
    m = arch.model
    return dataclasses.replace(arch, model=dataclasses.replace(
        m, attention=dataclasses.replace(m.attention, rope_scaling=None)))


def _host(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def first_calls(hfl, p0, k: int):
    """From the stacked adapters ``p0`` and the streams' first window: a
    cluster round alone (its adapters), then ``p0`` and the first window
    back and the first call of ``k`` rounds (the adapters after it, and
    each round's per-step loss averaged over the clients)."""
    import jax

    hfl.params, hfl.window = jax.tree.map(jax.numpy.asarray, p0), 0
    hfl.run_rounds(1)
    after1 = _host(hfl.params)
    hfl.params, hfl.window = jax.tree.map(jax.numpy.asarray, p0), 0
    res = hfl.run_rounds(k)
    return after1, _host(hfl.params), res.train_loss.mean(axis=1).reshape(-1)


def program_grads(arch, base, adapters, tokens, group: int, rows):
    """The gradients a local step of the timed program takes, for every
    client at ``adapters`` (stacked) on its batch ``tokens`` (C, B,
    S + 1); the rows ``rows`` of each."""
    import jax

    from repro.fl.client import lm_client_grads

    g, _ = jax.jit(lm_client_grads, static_argnames=("cfg", "group"))(
        adapters, base, tokens, cfg=arch, group=group)
    return jax.tree.map(lambda x: np.asarray(x, np.float32)[rows], g)


def program_logits(arch, base, adapters, tokens):
    """The program's logits (B, T, V) for ``tokens`` (B, T) under one
    client's adapters, through its forward at the timed sizes."""
    import jax

    from repro.models import make_model
    from repro.models.lora import with_adapters

    fwd = make_model(arch).forward
    return jax.jit(lambda b, a, t: fwd(with_adapters(b, a),
                                       {"tokens": t})[0])(base, adapters,
                                                          tokens)


def logit_gap(prog, ref) -> float:
    """Worst position's regret: how far below the reference's best logit
    lies the reference's logit of the token the program ranks first."""
    import jax.numpy as jnp

    pick = jnp.take_along_axis(ref, jnp.argmax(prog, -1)[..., None], -1)
    return float(jnp.max(jnp.max(ref, -1) - pick[..., 0]))


def grad_gap(got, want) -> float:
    """Worst leaf's distance of the program's gradients from the
    reference's, over the norm of the reference's."""
    import jax

    return max(float(np.linalg.norm(g - w) / np.linalg.norm(w))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def compare(prog, ref, p0, prog_logits, ref_logits, prog_grads,
            ref_grads) -> dict:
    """The numbers compared: each step's loss, the change after the
    cluster round and after the call, how far apart the clients lie
    after the global round, the logits' regret and the gradients'
    distance."""
    import jax

    hfl = loader.driver("hfl")
    leaves = jax.tree.leaves
    (a1, a2, losses), (r1, r2, ref_losses) = prog, ref
    d1 = [x - y for x, y in zip(leaves(a1), leaves(p0))]
    e1 = [x - y for x, y in zip(leaves(r1), leaves(p0))]
    d2 = [x - y for x, y in zip(leaves(a2), leaves(p0))]
    e2 = [x - y for x, y in zip(leaves(r2), leaves(p0))]
    first = [float(np.linalg.norm(x)) for x in e1]
    med = float(np.median(first))
    keep = {i for i, f in enumerate(first) if f >= 1e-3 * med}
    return {"loss_gap": hfl.relative_gaps(np.asarray(losses),
                                          np.asarray(ref_losses)),
            "update1_gap": hfl.norm_gap(d1, e1, first),
            "change2_gap": hfl.norm_gap(d2, e2, first),
            "client_spread_gap": abs(hfl.spread(a2, p0, keep)
                                     - hfl.spread(r2, p0, keep)),
            "max_logit_gap": logit_gap(prog_logits, ref_logits),
            "grad_gap": grad_gap(prog_grads, ref_grads)}


def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, ctx: dict, control: bool = False) -> dict:
    import jax

    from bench import trace as tr
    from repro.data.tokens import ClientTokenStreams
    from repro.fl.hierarchy import HFL_SPANS, ContinualHFL, HFLRunConfig
    from repro.models.lora import init_adapters
    from repro.telemetry import Telemetry

    arch = program_config(cfg)
    ref = loader.reference(cfg["name"])
    fl, ref_fl = cfg["fl"], reference_fl(cfg, traffic)
    k = int(traffic["rounds_per_call"])
    steps, B, S = cfg["local_steps"], cfg["batch_size"], traffic["seq_len"]
    C = fl["clusters"] * fl["clients_per_cluster"]
    clients = np.arange(C)
    rng = generator.rng_for(seed, 2)
    lan = {"cluster_of": np.repeat(np.arange(fl["clusters"]),
                                   fl["clients_per_cluster"])}
    topo = loader.driver("hfl").deploy(lan, clients,
                                       fl["local_rounds_per_global"], rng)
    run_seed = seed % (2 ** 31 - 1)
    streams = ClientTokenStreams(cfg["vocab_size"], S, seed=run_seed,
                                 zipf_a=traffic["zipf_a"])
    hcfg = HFLRunConfig(rounds=k, batch_size=B, lr=fl["lr"], seed=run_seed,
                        local_steps=steps,
                        clients_per_pass=fl["clients_per_pass"])

    base, adapters = jax.jit(lambda key: ref.init_params(
        key, cfg, ref_fl, C))(generator.jax_key(seed))
    loader.check_layout(base, arch)
    want = jax.eval_shape(lambda: init_adapters(jax.random.key(0),
                                                arch.model))
    if jax.tree.map(lambda x: (x.shape, x.dtype), want) != jax.tree.map(
            lambda x: (x.shape[1:], x.dtype), adapters):
        raise ValueError("the reference's adapters do not have the "
                         "program's layout")
    tel = Telemetry(annotate=jax.profiler.TraceAnnotation) if trace else None
    hfl = ContinualHFL(arch, streams, clients, topo, hcfg, mode=fl["mode"],
                       telemetry=tel, base=base)
    p0 = _host(adapters)
    del adapters
    prog = first_calls(hfl, p0, k)
    batch0 = streams.round_tokens(clients[:1], 0, steps, B)[0][0, 0]
    # the gradients of the global round's first step at the cluster
    # round's adapters, of the first client of each cluster
    gbatch = streams.round_tokens(clients, 1, steps, B)[0][:, 0]
    cluster_ids = np.unique(topo.assign[:C], return_inverse=True)[1]
    rows = np.unique(cluster_ids, return_index=True)[1]
    group = fl["clients_per_pass"]

    def probes(arch_, got):
        ad0 = jax.tree.map(lambda x: x[0], got[1])
        return (program_logits(arch_, base, ad0, batch0[:, :-1]),
                program_grads(arch_, base, got[0], gbatch, group, rows))

    prog_logits, prog_grads = probes(arch, prog)

    ctx["window_open"]()
    t0 = time.perf_counter()
    rounds, timed_s, timed_rounds, call = 0, 0.0, 0, 0
    spans, counters = [], {}
    while time.perf_counter() - t0 < seconds:
        traced = trace and call == 1
        if traced:
            jax.profiler.start_trace(ctx["trace_dir"],
                                     profiler_options=tr.options())
        if tel is not None and not traced:
            n_spans = len(tel.tracer.spans)
            before = dict(tel.metrics.snapshot()["counters"])
        c0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tr.WINDOW if traced else "call"):
            with jax.profiler.TraceAnnotation("run_rounds"):
                hfl.run_rounds(k)
        c1 = time.perf_counter()
        if traced:
            jax.profiler.stop_trace()
            ctx["traced_rounds"] = k
            common.log(f"traced call {c1 - c0:.2f}s, trace stopped in "
                       f"{time.perf_counter() - c1:.1f}s")
        else:
            timed_s += c1 - c0
            timed_rounds += k
            if tel is not None:
                spans += tel.tracer.spans[n_spans:]
                for name, v in tel.metrics.snapshot()["counters"].items():
                    counters[name] = (counters.get(name, 0.0) + v
                                      - before.get(name, 0.0))
        rounds += k
        call += 1
    elapsed = time.perf_counter() - t0
    ctx["window_closed"]()
    round_s = elapsed / rounds if not trace else timed_s / max(timed_rounds, 1)
    ctx.update({
        "host_labels": HOST_LABELS + HFL_SPANS, "round_s": round_s,
        "lm_round_flops": lm_flops.round_flops(cfg, fl, C, steps, B, S),
        "program_spans": spans, "program_counters": counters,
    })
    common.log(f"hfl_lm: {C} clients in {len(np.unique(topo.assign))} "
               f"clusters, {rounds} rounds in {elapsed:.3f}s of window")
    ctx["memory_peak_bytes"] = common.memory_peak_bytes(ctx["devices"])

    def reference(mode="f32"):
        snaps, losses = ref.run_rounds(base, p0, run_seed, clients,
                                       cluster_ids, k, cfg, ref_fl, mode)
        lg = jax.jit(lambda b, a, t: ref.logits(
            b, a, t, cfg, ref_fl, mode, per_token=True))(
                base, jax.tree.map(lambda x: x[0], snaps[-1]),
                batch0[:, :-1])
        g = ref.client_grads(base, jax.tree.map(lambda x: x[rows], prog[0]),
                             gbatch[rows], cfg, ref_fl, mode)
        return (snaps[0], snaps[-1], losses.mean(axis=1).reshape(-1)), lg, g

    want, ref_logits, ref_grads = reference()
    got = compare(prog, want, p0, prog_logits, ref_logits, prog_grads,
                  ref_grads)
    common.log(f"reference: {k} rounds; per-step losses program "
               f"{np.round(prog[2], 6).tolist()} reference "
               f"{np.round(want[2], 6).tolist()}; {got}")
    if control:
        ctx["control"] = control_readings(
            hfl, arch, p0, k, probes, (want, ref_logits, ref_grads),
            reference)
        common.log(f"control: {ctx['control']}")
    checks = ctx["checks"]
    for name in ("loss_gap", "update1_gap", "change2_gap",
                 "client_spread_gap", "max_logit_gap", "grad_gap"):
        checks.add(name, got[name], cfg["correct"][name])
    ctx["readings"] = got
    e2e = {"round_ms": common.metric(round_s * 1e3, "ms")}
    return {"attempted": rounds, "failed": 0, "e2e": e2e}


def control_readings(hfl, arch, p0, k, probes, wanted, reference) -> dict:
    """The numbers with each fault planted: the program with capacity
    1.25 drops, with plain rope, with no aggregation, with its
    gradients' sign turned, and the reference on an fp8 base in the
    program's place."""
    import jax

    import repro.fl.client as cl
    import repro.fl.hierarchy as h
    from repro.models import moe

    want, ref_logits, ref_grads = wanted

    def program(arch_):
        hfl.cfg = arch_
        try:
            got = first_calls(hfl, p0, k)
            lg, g = probes(arch_, got)
        finally:
            hfl.cfg = arch
        return compare(got, want, p0, lg, ref_logits, g, ref_grads)

    @contextlib.contextmanager
    def planted(module, name, value):
        saved = getattr(module, name)
        setattr(module, name, value)
        jax.clear_caches()
        try:
            yield
        finally:
            setattr(module, name, saved)
            jax.clear_caches()

    out = {}
    with planted(moe, "_routed", capacity_routed()):
        out["capacity"] = program(arch)
    out["no_yarn"] = program(without_yarn(arch))
    saved = h.cluster_fedavg, h.global_fedavg
    h.cluster_fedavg = h.global_fedavg = lambda p, *a, **kw: p
    try:
        out["no_exchange"] = program(arch)
    finally:
        h.cluster_fedavg, h.global_fedavg = saved
    with planted(cl, "lm_client_grads", turned_grads(cl.lm_client_grads)):
        out["sign"] = program(arch)
    fp8, fp8_logits, fp8_grads = reference("fp8")
    out["fp8"] = compare(fp8, want, p0, fp8_logits, ref_logits, fp8_grads,
                         ref_grads)
    return out
