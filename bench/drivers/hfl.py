"""HFL driver: the paper's continual hierarchical rounds through the
program's ``ContinualHFL.run_rounds``, back to back, on one chip.

Set-up makes the sensor data from the seed, clusters the clients with
the program's HFLOP controller, builds one ``ContinualHFL`` and gives it
weights made from the seed.  That same object then takes its first
three calls (``rounds_per_call`` rounds each: a cluster round, then a
global one), which the reference follows, and then the window's calls
until ``--seconds`` has passed.  ``run_rounds`` has no per-round hook, so
the window runs it in calls of ``rounds_per_call`` rounds, and counts
every round of every call it completes.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import common, flops, generator, loader, sensor_data

HOST_LABELS = ("run_rounds",)


def program_config(cfg: dict):
    from repro.configs import get_config

    arch = get_config(cfg["arch"])
    m, want = arch.model, cfg["model"]
    have = {"num_layers": m.rnn_layers, "hidden_size": m.rnn_hidden,
            "dtype": m.param_dtype}
    bad = {k: (v, want[k]) for k, v in have.items() if v != want[k]}
    if bad:
        raise ValueError(f"{cfg['name']}: the program's sizes differ from "
                         f"the configuration file: {bad}")
    return arch


def deploy(data: dict, sensors, l: int, rng):
    """Cluster the FL clients with the program's HFLOP controller."""
    from repro.orchestration import (DeviceNode, EdgeNode, Inventory,
                                     LearningController)

    lam = rng.uniform(2.0, 6.0, len(sensors))
    devs = [DeviceNode(i, lam=float(lam[i]),
                       lan_edge=int(data["cluster_of"][sensors[i]]))
            for i in range(len(sensors))]
    edges = [EdgeNode(j, capacity_rps=float(lam.sum() / 4 * 1.4))
             for j in range(sensor_data.N_CLUSTERS)]
    return LearningController(Inventory(devs, edges), l=l).deploy().topology


def relative_gaps(prog: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def norm_gap(prog_delta, ref_delta, ref_first=None) -> float:
    """Worst leaf's gap between the norms of the program's and the
    reference's change, over the larger of that leaf's reference norm
    and the median leaf's.  Leaves whose first reference change is under
    a thousandth of the median leaf's moved by round-off alone and are
    left out."""
    pn = [float(np.linalg.norm(x)) for x in prog_delta]
    rn = [float(np.linalg.norm(x)) for x in ref_delta]
    first = rn if ref_first is None else ref_first
    med = float(np.median(first))
    keep = [i for i, f in enumerate(first) if f >= 1e-3 * med]
    med_r = float(np.median(rn))
    return max(abs(pn[i] - rn[i]) / max(rn[i], med_r) for i in keep)


def spread(snap, p0, keep) -> float:
    """How far the clients' models lie apart after a call, as the worst
    kept leaf's largest distance of a client from the clients' mean over
    the mean's change.  A call ends in a global round, which gives every
    client the same model."""
    import jax

    out = 0.0
    for i, (x, x0) in enumerate(zip(jax.tree.leaves(snap),
                                    jax.tree.leaves(p0))):
        if i not in keep:
            continue
        x = np.asarray(x, np.float64)
        m = x.mean(axis=0)
        dev = max(float(np.linalg.norm(xc - m)) for xc in x)
        out = max(out, dev / max(float(np.linalg.norm(m - x0[0])), 1e-30))
    return out


def compare(snaps, losses, p0, ref_snaps, ref_losses) -> dict:
    """The numbers compared: losses of each round, the first call's
    change, the change after the last call, and how far apart the
    clients' models lie after it."""
    import jax

    leaves = lambda t: jax.tree.leaves(t)                 # noqa: E731
    d1 = [a - b for a, b in zip(leaves(snaps[0]), leaves(p0))]
    r1 = [a - b for a, b in zip(leaves(ref_snaps[0]), leaves(p0))]
    dn = [a - b for a, b in zip(leaves(snaps[-1]), leaves(p0))]
    rn = [a - b for a, b in zip(leaves(ref_snaps[-1]), leaves(p0))]
    first = [float(np.linalg.norm(x)) for x in r1]
    med = float(np.median(first))
    keep = {i for i, f in enumerate(first) if f >= 1e-3 * med}
    return {"loss_gap": relative_gaps(np.asarray(losses),
                                      np.asarray(ref_losses)),
            "update1_gap": norm_gap(d1, r1, first),
            "change3_gap": norm_gap(dn, rn, first),
            "client_spread_gap": abs(spread(snaps[-1], p0, keep)
                                     - spread(ref_snaps[-1], p0, keep))}


def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, ctx: dict, control: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import trace as tr
    from repro.data.traffic import TrafficDataset
    from repro.fl.hierarchy import ContinualHFL, HFLRunConfig

    arch = program_config(cfg)
    ref = loader.reference(cfg["name"])
    run_cfg, model = cfg["run"], cfg["model"]
    k = int(traffic["rounds_per_call"])
    rng = generator.rng_for(seed, 2)
    data = sensor_data.generate(int(traffic["data_days"]), rng)
    sensors = sensor_data.select_sensors(data, run_cfg["clients_per_cluster"],
                                         rng)
    topo = deploy(data, sensors, run_cfg["local_rounds_per_global"], rng)
    ds = TrafficDataset(speeds=data["speeds"], cluster_of=data["cluster_of"],
                        positions=data["positions"], mean=data["mean"],
                        std=data["std"])
    run_seed = seed % (2 ** 31 - 1)
    hcfg = HFLRunConfig(
        rounds=k, local_epochs=run_cfg["local_epochs"],
        batch_size=run_cfg["batch_size"], lr=run_cfg["lr"],
        history=run_cfg["history"], train_days=run_cfg["train_days"],
        val_days=run_cfg["val_days"], shift_steps=run_cfg["shift_steps"],
        max_batches=run_cfg["max_batches"],
        max_val_windows=run_cfg["max_val_windows"], seed=run_seed)
    hfl = ContinualHFL(arch, ds, sensors, topo, hcfg, mode=run_cfg["mode"])
    C = len(sensors)

    params = jax.jit(lambda key: ref.init_params(key, model))(
        generator.jax_key(seed))
    loader.check_layout(params, arch)
    hfl.params = jax.tree.map(
        lambda x: jnp.array(jnp.broadcast_to(x, (C,) + x.shape)), params)
    p0 = jax.tree.map(np.asarray, hfl.params)

    # the first calls, through the window's own object and call
    steps = int(cfg["correct"]["steps"])
    snaps, losses = [], []
    for _ in range(steps):
        res = hfl.run_rounds(k)
        losses.extend(res.train_loss.mean(axis=1).tolist())
        snaps.append(jax.tree.map(np.asarray, hfl.params))

    ctx["window_open"]()
    t0 = time.perf_counter()
    rounds, timed_s, timed_rounds, call = 0, 0.0, 0, 0
    while time.perf_counter() - t0 < seconds:
        traced = trace and call == 1
        if traced:
            jax.profiler.start_trace(ctx["trace_dir"],
                                     profiler_options=tr.options())
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
        rounds += k
        call += 1
    elapsed = time.perf_counter() - t0
    ctx["window_closed"]()
    round_s = elapsed / rounds if not trace else timed_s / max(timed_rounds, 1)
    n_windows = run_cfg["train_days"] * sensor_data.STEPS_PER_DAY \
        - run_cfg["history"]
    ctx.update({
        "host_labels": HOST_LABELS, "round_s": round_s,
        "round_flops": flops.hfl_round_flops(
            model, C, run_cfg["local_epochs"],
            n_windows // run_cfg["batch_size"], run_cfg["batch_size"],
            run_cfg["history"]),
    })
    common.log(f"hfl: {C} clients in {len(np.unique(topo.assign))} clusters,"
               f" {rounds} rounds in {elapsed:.3f}s of window")

    ctx["memory_peak_bytes"] = common.memory_peak_bytes(ctx["devices"])
    del hfl
    # the clients' clusters, as the HFLOP controller assigned them
    cluster_ids = np.unique(topo.assign[:C], return_inverse=True)[1]
    gc.collect()

    z = (data["speeds"] - data["mean"]) / data["std"]
    ref_snaps, ref_losses = ref.run_steps(p0, z, sensors, cluster_ids,
                                          run_cfg, run_seed, steps, k)
    got = compare(snaps, losses, p0, ref_snaps, ref_losses)
    common.log(f"reference: {steps} calls of {k} rounds; losses program "
               f"{np.round(losses, 6).tolist()} reference "
               f"{np.round(ref_losses, 6).tolist()}; {got}")
    if control:
        ctx["control"] = {}
        for mode, fault in (("bf16", ""), ("f32", "unchanged"),
                            ("f32", "half_batch"), ("f32", "no_exchange")):
            s, lo = ref.run_steps(p0, z, sensors, cluster_ids, run_cfg,
                                  run_seed, steps, k, mode=mode, fault=fault)
            ctx["control"][fault or mode] = compare(s, lo.tolist(), p0,
                                                    ref_snaps, ref_losses)
        common.log(f"control: {ctx['control']}")
    checks = ctx["checks"]
    for name in ("loss_gap", "update1_gap", "change3_gap",
                 "client_spread_gap"):
        checks.add(name, got[name], cfg["correct"][name])
    ctx["readings"] = got
    e2e = {"round_ms": common.metric(round_s * 1e3, "ms")}
    return {"attempted": rounds, "failed": 0, "e2e": e2e}
