"""Plain reference of the paper's continual hierarchical FL round
(arXiv:2407.16836, Sec. V-B) for ``gru-traffic.json``.

Written from the paper's description and nothing of the program:

* the model: a 2-layer GRU (hidden 128) over a window of readings, then
  a linear head on the last hidden state; gates
  ``r = s(x W_xr + h W_hr + b_r)``, ``z = s(x W_xz + h W_hz + b_z)``,
  ``n = tanh(x W_xn + b_n + r * (h W_hn))``, ``h' = (1 - z) n + z h``;
* a round: every client runs ``epochs`` epochs of minibatch SGD on its
  own sensor's windows (batches drawn by a permutation of the window
  indices from the round's key, as the deployment's data order), then
  its cluster averages the members' models; every ``l``-th round the
  clusters' models are averaged too, weighted by member count, and the
  result goes to every client.

``init_params`` makes the weights the benchmark trains, in the layout
the program takes.  Precision ``"f32"`` is float32 with matmuls at
``highest`` precision (the reference); ``"bf16"`` holds weights, data and
every operation in bfloat16 (the control).  Clients are vmapped: each
one's arithmetic is its own.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

STEPS_PER_DAY = 288


def init_params(key, model: dict):
    h, layers = model["hidden_size"], model["num_layers"]
    params = {"gru": {}, "head": {}}
    k = 0
    for i in range(layers):
        din = model["input_size"] if i == 0 else h
        params["gru"][str(i)] = {
            "w_x": jax.random.normal(jax.random.fold_in(key, k), (din, 3 * h))
            / math.sqrt(din),
            "w_h": jax.random.normal(jax.random.fold_in(key, k + 1),
                                     (h, 3 * h)) / math.sqrt(h),
            "b": jnp.zeros((3 * h,), jnp.float32)}
        k += 2
    params["head"]["w"] = jax.random.normal(jax.random.fold_in(key, k),
                                            (h, model["output_size"])) \
        / math.sqrt(h)
    params["head"]["b"] = jnp.zeros((model["output_size"],), jnp.float32)
    return params


def predict(p, windows):
    """windows (B, T, 1) -> (B, 1)."""
    x = windows
    for i in range(len(p["gru"])):
        g = p["gru"][str(i)]
        h_dim = g["w_h"].shape[0]
        xs = jnp.einsum("btd,de->tbe", x, g["w_x"]) + g["b"]

        def cell(h, xt, g=g, h_dim=h_dim):
            hh = h @ g["w_h"]
            r = jax.nn.sigmoid(xt[:, :h_dim] + hh[:, :h_dim])
            z = jax.nn.sigmoid(xt[:, h_dim:2 * h_dim]
                               + hh[:, h_dim:2 * h_dim])
            n = jnp.tanh(xt[:, 2 * h_dim:] + r * hh[:, 2 * h_dim:])
            h = (1 - z) * n + z * h
            return h, h

        h0 = jnp.zeros((x.shape[0], h_dim), x.dtype)
        _, hs = jax.lax.scan(cell, h0, xs)
        x = jnp.swapaxes(hs, 0, 1)
    return x[:, -1] @ p["head"]["w"] + p["head"]["b"]


def mse(p, windows, targets):
    return jnp.mean(jnp.square(predict(p, windows) - targets))


def train_clients(params, X, y, key, *, epochs: int, batch: int, lr: float,
                  half_batch: bool = False):
    """``epochs`` epochs of SGD on each client.  Returns the new params
    and each client's mean loss over its last epoch.  ``half_batch``
    plants a fault: each step's loss over the first half of its batch."""
    C, N = X.shape[:2]
    nb = N // batch

    def client(p, Xc, yc, ck):
        def epoch(carry, ek):
            p, _ = carry
            perm = jax.random.permutation(ek, N)[:nb * batch]
            Xb = Xc[perm].reshape(nb, batch, *Xc.shape[1:])
            yb = yc[perm].reshape(nb, batch, *yc.shape[1:])

            def step(p, b):
                xb, tb = b
                if half_batch:
                    xb, tb = xb[:batch // 2], tb[:batch // 2]
                loss, g = jax.value_and_grad(mse)(p, xb, tb)
                return jax.tree.map(lambda w, gw: w - lr * gw, p, g), loss

            p, losses = jax.lax.scan(step, p, (Xb, yb))
            return (p, jnp.mean(losses)), None

        (p, last), _ = jax.lax.scan(epoch, (p, jnp.zeros((), Xc.dtype)),
                                    jax.random.split(ck, epochs))
        return p, last

    return jax.vmap(client)(params, X, y, jax.random.split(key, C))


def aggregate(params, cluster_ids: np.ndarray, global_round: bool):
    """Cluster FedAvg (each client gets its cluster's mean); on a global
    round the clusters' means averaged by member count go to all."""
    ids = np.asarray(cluster_ids)
    k = int(ids.max()) + 1
    member = jnp.asarray(np.eye(k, dtype=np.float32)[ids].T)   # (k, C)
    counts = member.sum(axis=1)

    def agg(x):
        flat = x.reshape(x.shape[0], -1)
        means = (member.astype(x.dtype) @ flat) / counts[:, None].astype(
            x.dtype)
        if global_round:
            g = (counts.astype(x.dtype) @ means) / counts.sum().astype(
                x.dtype)
            out = jnp.broadcast_to(g, flat.shape)
        else:
            out = means[jnp.asarray(ids)]
        return out.reshape(x.shape)

    return jax.tree.map(agg, params)


def round_windows(z: np.ndarray, sensors, round_idx: int, run: dict):
    """Training windows of every client for a round: X (C, N, T, 1) and
    y (C, N, 1) from the normalized readings ``z`` (time, sensor)."""
    start = round_idx * run["shift_steps"]
    stop = start + run["train_days"] * STEPS_PER_DAY
    T = run["history"]
    N = stop - start - T
    idx = start + np.arange(N)[:, None] + np.arange(T)[None, :]
    X = np.stack([z[idx, s][..., None] for s in sensors])
    y = np.stack([z[idx[:, -1] + 1, s][:, None] for s in sensors])
    return X.astype(np.float32), y.astype(np.float32)


def run_steps(params0, z, sensors, cluster_ids, run: dict, run_seed: int,
              steps: int, rounds_per_step: int, mode: str = "f32",
              fault: str = ""):
    """The first ``steps`` calls of ``rounds_per_step`` rounds each, from
    ``params0`` (stacked per client).  Each call starts its rounds at the
    first training window and its batch order at ``run_seed + 1``, as
    the deployment's runner does.  Returns (params after each call, the
    clients' mean training loss of each round).

    ``fault`` plants one in the reference: ``"unchanged"`` (a round
    returns its state), ``"half_batch"``, ``"no_exchange"`` (no
    aggregation)."""
    dtype = jnp.float32 if mode == "f32" else jnp.bfloat16
    prec = "highest" if mode == "f32" else "default"
    cast = lambda t: jax.tree.map(lambda x: jnp.asarray(x, dtype), t)  # noqa
    train = jax.jit(lambda p, X, y, k: train_clients(
        p, X, y, k, epochs=run["local_epochs"], batch=run["batch_size"],
        lr=run["lr"], half_batch=fault == "half_batch"))
    data = [tuple(cast(a) for a in round_windows(z, sensors, t, run))
            for t in range(rounds_per_step)]
    p = cast(params0)
    snaps, losses = [], []
    with jax.default_matmul_precision(prec):
        for _ in range(steps):
            rng = jax.random.key(run_seed + 1)
            for t in range(rounds_per_step):
                rng, sub = jax.random.split(rng)
                new, loss = train(p, *data[t], sub)
                if fault != "unchanged":
                    p = new
                if fault != "no_exchange":
                    glob = (t + 1) % run["local_rounds_per_global"] == 0
                    p = aggregate(p, cluster_ids, glob)
                losses.append(float(jnp.mean(loss.astype(jnp.float32))))
            snaps.append(jax.tree.map(
                lambda x: np.asarray(x, np.float32), p))
    return snaps, np.asarray(losses)
