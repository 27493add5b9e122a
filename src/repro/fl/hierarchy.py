"""Continual hierarchical FL runner — reproduces the paper's §V-B2
experiments (Fig. 6): 20 clients, 4 clusters, 5 local epochs per round,
2 local aggregations per global aggregation, sliding continual-learning
window; per-client validation MSE recorded right after the client
receives the (cluster/global) model.  The same rounds fine-tune a
registry language model (latent attention) by LoRA adapters on one
frozen base, each client on its own token stream; only the adapters are
aggregated."""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.topology import ClusterTopology
from repro.data.tokens import ClientTokenStreams
from repro.data.traffic import (TrafficDataset, continual_split,
                                windows_for_sensor)
from repro.fl.aggregation import (cluster_fedavg, clusters,
                                  compiled_programs, fedavg, global_fedavg)
from repro.fl.client import (ClientBatch, eval_clients, eval_lm_clients,
                             stack_clients, train_clients_locally,
                             train_lm_clients)
from repro.models import gru, make_model
from repro.models.lora import init_adapters
from repro.telemetry import Telemetry, maybe as _maybe_tel

#: the wall spans of one continual round (``cat="hfl"``), outermost
#: first: ``hfl.round`` holds the others; ``hfl.data`` holds
#: ``hfl.data.windows`` (GRU) or ``hfl.data.tokens`` (LM), and
#: ``hfl.data.upload``
HFL_SPANS = ("hfl.round", "hfl.data", "hfl.data.windows",
             "hfl.data.tokens", "hfl.data.upload", "hfl.train",
             "hfl.aggregate", "hfl.eval", "hfl.sync")


def _even_indices(n: int, k: int) -> np.ndarray:
    """k indices spread evenly over [0, n) (all of them if n <= k)."""
    if n <= k:
        return np.arange(n)
    return np.linspace(0, n - 1, k).astype(int)


@functools.partial(jax.jit, static_argnames=("train_len", "val_len",
                                             "history", "max_val"))
def device_windows(series, starts, *, train_len: int, val_len: int,
                   history: int, max_val: int):
    """One round's windows, made on the device from the clients' staged
    normalized series ``(T, C)``: ``X (C, N, history, 1)``, ``y (C, N,
    1)`` over the ``train_len`` readings from ``starts[0]``, and ``Xv``,
    ``yv`` over the ``max_val`` evenly spaced windows of the
    ``val_len`` readings from ``starts[1]`` -- the values
    ``windows_for_sensor`` gives, sensor by sensor.  The starts are
    traced, so one program serves every round."""

    def windows(start, length, pick):
        z = jax.lax.dynamic_slice_in_dim(series, start, length)
        n = length - history
        # (n, C, history + 1): window i holds readings i .. i + history
        w = jnp.stack([z[h:h + n] for h in range(history + 1)], axis=-1)
        w = jnp.swapaxes(w[pick], 0, 1)
        return w[..., :history, None], w[..., history:]

    X, y = windows(starts[0], train_len, slice(None))
    # subsample the val week EVENLY: max_val_windows contiguous windows
    # cover only ~max_val_windows*5min, so a truncated prefix slides
    # through the daily cycle as rounds shift and the metric tracks
    # time-of-day, not learning
    Xv, yv = windows(starts[1], val_len,
                     _even_indices(val_len - history, max_val))
    return X, y, Xv, yv


# ---------------------------------------------------------------------------
# round timeline — lives in the jax-free repro.fl.schedule (the co-sim
# imports it without pulling this module's jax stack); re-exported here
# so existing imports keep working
# ---------------------------------------------------------------------------

from repro.fl.schedule import RoundWindow, round_schedule  # noqa: E402


@dataclass
class HFLRunConfig:
    rounds: int = 100
    local_epochs: int = 5
    batch_size: int = 16
    lr: float = 1e-4
    history: int = 12
    train_days: int = 21
    val_days: int = 7
    shift_steps: int = 36
    max_batches: int = 40            # subsample batches/epoch for speed
    max_val_windows: int = 512
    seed: int = 0
    local_steps: int = 2             # LM clients: AdamW steps per round,
                                     # one batch_size batch each
    clients_per_pass: int = 0        # LM clients taken through the base
                                     # at once (0: all)


@dataclass
class HFLResult:
    # (rounds, clients) validation loss per round: MSE for the GRU,
    # held-out next-token cross-entropy for an LM
    mse: np.ndarray
    # (rounds, clients); an LM's is (rounds, clients, local_steps)
    train_loss: np.ndarray
    mode: str

    def converged_round(self, tol: float = 1.05) -> int:
        """First round whose mean MSE is within tol x of the min."""
        means = self.mse.mean(axis=1)
        target = means.min() * tol
        idx = np.nonzero(means <= target)[0]
        return int(idx[0]) if idx.size else len(means) - 1


class ContinualHFL:
    """mode: 'flat' (centralized FedAvg every round),
             'hier' (cluster aggregation each round, global every l).

    ``telemetry``: if given (and enabled), each round of
    :meth:`run_rounds` records the wall spans of :data:`HFL_SPANS` —
    ``hfl.round`` (args ``round`` and ``tier``: ``cluster``, ``global``
    or ``flat``) around ``hfl.data`` (the clients' series staged on the
    device in the first GRU round, ``hfl.data.upload``, then the split
    and the dispatch of :func:`device_windows`, ``hfl.data.windows``),
    ``hfl.train`` (dispatch of local training; the device work is
    asynchronous), ``hfl.aggregate``, ``hfl.eval`` and ``hfl.sync``
    (the host blocks on the round's results) — and counts
    ``hfl.rounds.<tier>``, ``hfl.upload_bytes`` (the data the host puts
    on the device: the staged series once, then each round's two window
    starts), ``hfl.window_bytes`` (the GRU windows a round makes on the
    device), ``hfl.model_upload_bytes`` (the client models
    sent to their aggregators: whole GRUs, or an LM's adapters),
    ``hfl.train_tokens`` (readings of the GRU's windows, or an LM's
    tokens, trained on) and ``hfl.aggregate.compiles`` (aggregation
    programs compiled in the round: none once each tier has run).  An
    LM's round makes its tokens on the host in ``hfl.data.tokens`` and
    uploads them in ``hfl.data.upload``, in place of the GRU's staging
    and ``hfl.data.windows``.  Telemetry only observes: parameters and
    results are the same with or without it.

    The model is the GRU when ``cfg`` is of the ``rnn`` family: ``ds``
    is then a :class:`TrafficDataset` and ``sensors`` the clients'
    sensors.  Otherwise ``cfg`` is a registry LM with latent attention
    and a LoRA config: ``ds`` is a :class:`ClientTokenStreams` and
    ``sensors`` each client's stream; ``params`` holds the clients'
    stacked adapters and ``base`` the one frozen base (made from
    ``run.seed`` if not given).  An LM's rounds are continual across
    calls: each round takes window :attr:`window` of every stream and
    moves it on by one, while a round's tier comes from its place in
    the call.  The GRU's round ``t`` of a call takes the data split
    ``t``, so every call starts again at the first."""

    def __init__(self, cfg: ArchConfig, ds, sensors: np.ndarray,
                 topo: ClusterTopology, run: HFLRunConfig,
                 mode: str = "hier", telemetry: Optional[Telemetry] = None,
                 base=None):
        assert mode in ("flat", "hier")
        self.cfg, self.ds, self.run, self.mode = cfg, ds, run, mode
        self._tel = _maybe_tel(telemetry)
        self.sensors = np.asarray(sensors)
        self.topo = topo
        # the clients' clusters and (equal) weights, on the device once
        self.clusters = clusters(topo.assign[:len(self.sensors)])
        rng = jax.random.key(run.seed)
        self.lm = cfg.model.family != "rnn"
        if self.lm:
            if not isinstance(ds, ClientTokenStreams):
                raise TypeError("an LM's clients read ClientTokenStreams")
            self.base = (make_model(cfg).init_params(rng)[0]
                         if base is None else base)
            params0 = init_adapters(jax.random.fold_in(rng, 1), cfg.model)
            self.window = 0          # the stream window of the next round
        else:
            params0, _ = gru.init_params(rng, cfg.model)
            self.series = None       # the clients' normalized series,
                                     # staged in the first round
        self.params = stack_clients([params0] * len(self.sensors))

    def round_schedule(self, rounds: Optional[int] = None,
                       epoch_s: float = 6.0, upload_s: float = 2.0,
                       **kwargs) -> List[RoundWindow]:
        """Timeline of this run's rounds for the co-simulation."""
        return round_schedule(rounds or self.run.rounds, l=self.topo.l,
                              local_epochs=self.run.local_epochs,
                              epoch_s=epoch_s, upload_s=upload_s, **kwargs)

    def _span(self, name: str, **args):
        """The wall span ``name`` of a round; a null context without
        telemetry."""
        if self._tel is None:
            return contextlib.nullcontext()
        return self._tel.tracer.wall(name, cat="hfl", **args)

    def _count(self, name: str, n: int):
        if self._tel is not None:
            self._tel.metrics.counter(name).inc(n)

    def _round_data(self, round_idx: int):
        r = self.run
        if self.lm:
            with self._span("hfl.data.tokens"):
                host = self.ds.round_tokens(self.sensors, round_idx,
                                            r.local_steps, r.batch_size)
            with self._span("hfl.data.upload"):
                arrays = tuple(jnp.asarray(a) for a in host)
            self._count("hfl.upload_bytes", sum(a.nbytes for a in host))
            return arrays
        with self._span("hfl.data.upload"):
            if self.series is None:
                self.series = jnp.asarray(self.ds.normalized()[
                    :, self.sensors].astype(np.float32))
                self._count("hfl.upload_bytes", self.series.nbytes)
        with self._span("hfl.data.windows"):
            tr, va = continual_split(self.ds, round_idx, r.train_days,
                                     r.val_days, r.shift_steps)
            starts = np.array([tr.start, va.start], np.int32)
            X, y, Xv, yv = device_windows(
                self.series, starts, train_len=tr.stop - tr.start,
                val_len=va.stop - va.start, history=r.history,
                max_val=r.max_val_windows)
        self._count("hfl.upload_bytes", starts.nbytes)
        self._count("hfl.window_bytes",
                    sum(a.nbytes for a in (X, y, Xv, yv)))
        return ClientBatch(X=X, y=y), ClientBatch(X=Xv, y=yv)

    def _train_tokens(self, train) -> int:
        """Readings (GRU windows) or tokens (LM) one round trains on."""
        r = self.run
        if self.lm:
            C, steps, B, S1 = train.shape
            return C * steps * B * (S1 - 1)
        C, N, H = train.X.shape[:3]
        batches = N // r.batch_size
        if r.max_batches:
            batches = min(batches, r.max_batches)
        return C * r.local_epochs * batches * r.batch_size * H

    def run_rounds(self, rounds: Optional[int] = None,
                   progress: bool = False) -> HFLResult:
        r = self.run
        rounds = rounds or r.rounds
        mse_hist, loss_hist = [], []
        rng = jax.random.key(r.seed + 1)
        for t in range(rounds):
            if self.mode == "flat":
                tier = "flat"
            elif (t + 1) % self.topo.l == 0:
                tier = "global"
            else:
                tier = "cluster"
            with self._span("hfl.round", round=t, tier=tier):
                with self._span("hfl.data"):
                    train, val = self._round_data(
                        self.window if self.lm else t)
                with self._span("hfl.train"):
                    rng, sub = jax.random.split(rng)
                    if self.lm:
                        self.params, losses = train_lm_clients(
                            self.params, self.base, train, cfg=self.cfg,
                            lr=r.lr, group=r.clients_per_pass)
                    else:
                        self.params, losses = train_clients_locally(
                            self.params, train, sub, cfg=self.cfg,
                            epochs=r.local_epochs, batch_size=r.batch_size,
                            lr=r.lr, max_batches=r.max_batches)
                self._count("hfl.train_tokens", self._train_tokens(train))
                self._count("hfl.model_upload_bytes",
                            sum(x.nbytes for x in jax.tree.leaves(self.params)))
                with self._span("hfl.aggregate"):
                    if self._tel is not None:
                        compiled = compiled_programs()
                    if tier == "flat":
                        self.params = fedavg(self.params,
                                             self.clusters.weights,
                                             broadcast=True)
                    elif tier == "global":
                        self.params = global_fedavg(self.params,
                                                    self.clusters)
                    else:
                        self.params = cluster_fedavg(self.params,
                                                     self.clusters)
                    if self._tel is not None:
                        self._tel.metrics.counter(
                            "hfl.aggregate.compiles").inc(
                                compiled_programs() - compiled)
                with self._span("hfl.eval"):
                    if self.lm:
                        val_mse = eval_lm_clients(
                            self.params, self.base, val, cfg=self.cfg,
                            group=r.clients_per_pass)
                    else:
                        val_mse = eval_clients(self.params, val,
                                               cfg=self.cfg)
                with self._span("hfl.sync"):
                    mse_hist.append(np.asarray(val_mse))
                    loss_hist.append(np.asarray(losses))
                if self.lm:
                    self.window += 1
            self._count(f"hfl.rounds.{tier}", 1)
            if progress and (t % 10 == 0 or t == rounds - 1):
                print(f"  round {t:3d}: mean val MSE "
                      f"{float(np.mean(val_mse)):.5f}")
        return HFLResult(mse=np.stack(mse_hist),
                         train_loss=np.stack(loss_hist), mode=self.mode)


def continuous_vs_static(cfg: ArchConfig, ds: TrafficDataset, sensor: int,
                         run: HFLRunConfig, rounds: int = 20
                         ) -> Dict[str, float]:
    """Paper §V-B1: a single continuously-retrained model vs a one-shot
    model, evaluated on the final validation week."""
    rng = jax.random.key(run.seed)
    params0, _ = gru.init_params(rng, cfg.model)
    stacked = stack_clients([params0])

    def data(round_idx):
        tr, va = continual_split(ds, round_idx, run.train_days,
                                 run.val_days, run.shift_steps)
        X, y = windows_for_sensor(ds, sensor, tr.start, tr.stop, run.history)
        Xv, yv = windows_for_sensor(ds, sensor, va.start, va.stop,
                                    run.history)
        idx = _even_indices(len(Xv), run.max_val_windows)
        return (ClientBatch(jnp.asarray(X[None]), jnp.asarray(y[None])),
                ClientBatch(jnp.asarray(Xv[idx][None]),
                            jnp.asarray(yv[idx][None])))

    # static: train once on round-0 window
    tr0, _ = data(0)
    static = stacked
    for _ in range(4):               # a few extra passes, like 20 epochs
        static, _ = train_clients_locally(
            static, tr0, rng, cfg=cfg, epochs=run.local_epochs,
            batch_size=run.batch_size, lr=run.lr,
            max_batches=run.max_batches)
    # continual: retrain on each shifted window
    cont = stacked
    for t in range(rounds):
        trt, _ = data(t)
        cont, _ = train_clients_locally(
            cont, trt, rng, cfg=cfg, epochs=run.local_epochs,
            batch_size=run.batch_size, lr=run.lr,
            max_batches=run.max_batches)
    _, va_last = data(rounds - 1)
    mse_static = float(eval_clients(static, va_last, cfg=cfg)[0])
    mse_cont = float(eval_clients(cont, va_last, cfg=cfg)[0])
    return {"static_mse": mse_static, "continual_mse": mse_cont}
