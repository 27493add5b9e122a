"""FL client runtime.  In the paper's use case every client trains the
shared GRU on its own sensor's windows; a client can also fine-tune a
registry language model by LoRA adapters on one frozen base.

All clients are trained *batched*: their trainable trees are stacked on a
leading axis, so one XLA program trains all 20 clients at once — the
CPU-host analogue of the per-pod client sharding used on the TPU mesh
(see fl/collectives.py).  GRU clients are ``vmap``-ed; LM clients share
the one unbatched base, and their sequences go through it as one batch
whose rows each client's adapters take in turn (routing is per token, so
a MoE layer sees every client's tokens at once)."""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import gru, transformer
from repro.models.layers import cross_entropy_loss
from repro.models.lora import with_adapters
from repro.training.optimizer import AdamW

PyTree = Any


class ClientBatch(NamedTuple):
    """Stacked per-client training data: X (C, N, H, 1), y (C, N, 1)."""
    X: jax.Array
    y: jax.Array


def stack_clients(params_list) -> PyTree:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def unstack_client(stacked: PyTree, i: int) -> PyTree:
    return jax.tree.map(lambda x: x[i], stacked)


@functools.partial(jax.jit, static_argnames=("cfg", "epochs", "batch_size",
                                             "lr", "max_batches"))
def train_clients_locally(stacked_params: PyTree, data: ClientBatch,
                          rng: jax.Array, *, cfg: ArchConfig,
                          epochs: int, batch_size: int, lr: float,
                          max_batches: int = 0) -> Tuple[PyTree, jax.Array]:
    """Run ``epochs`` of minibatch SGD on every client (vmapped).

    Returns (new stacked params, mean train loss per client (C,))."""
    m = cfg.model
    C, N = data.X.shape[0], data.X.shape[1]
    n_batches = N // batch_size
    if max_batches:
        n_batches = min(n_batches, max_batches)

    def one_client(params, X, y, key):
        def epoch(carry, ekey):
            p, _ = carry
            perm = jax.random.permutation(ekey, N)[:n_batches * batch_size]
            Xb = X[perm].reshape(n_batches, batch_size, *X.shape[1:])
            yb = y[perm].reshape(n_batches, batch_size, *y.shape[1:])

            def step(p2, xy):
                xb, yb_ = xy
                loss, g = jax.value_and_grad(gru.mse_loss)(p2, m, xb, yb_)
                p3 = jax.tree.map(lambda w, gw: w - lr * gw, p2, g)
                return p3, loss

            p, losses = jax.lax.scan(step, p, (Xb, yb))
            return (p, jnp.mean(losses)), None

        keys = jax.random.split(key, epochs)
        (params, last_loss), _ = jax.lax.scan(epoch, (params, 0.0), keys)
        return params, last_loss

    keys = jax.random.split(rng, C)
    return jax.vmap(one_client)(stacked_params, data.X, data.y, keys)


@functools.partial(jax.jit, static_argnames=("cfg",))
def eval_clients(stacked_params: PyTree, data: ClientBatch, *,
                 cfg: ArchConfig) -> jax.Array:
    """Validation MSE per client (C,)."""
    m = cfg.model

    def one(params, X, y):
        return gru.mse_loss(params, m, X, y)

    return jax.vmap(one)(stacked_params, data.X, data.y)


def lm_client_losses(adapters: PyTree, base: PyTree, tokens: jax.Array,
                     cfg: ArchConfig) -> jax.Array:
    """Mean next-token cross-entropy of each client (C,) on its sequences
    ``tokens`` (C, B, S + 1), through the base with its adapters (stacked
    per client)."""
    m = cfg.model
    C, B, S1 = tokens.shape
    params = with_adapters(base, adapters, stacked=True)
    logits, _ = transformer.forward(params, m, tokens[..., :-1].reshape(
        C * B, S1 - 1), remat=cfg.run.remat)
    logits = logits.reshape((C, B) + logits.shape[1:])
    return jax.vmap(cross_entropy_loss, in_axes=(0, 0, None))(
        logits, tokens[..., 1:], m.vocab_size)


def lm_client_grads(adapters: PyTree, base: PyTree, tokens: jax.Array, *,
                    cfg: ArchConfig, group: int = 0
                    ) -> Tuple[PyTree, jax.Array]:
    """Each client's gradient of its loss on its batch ``tokens`` (C, B,
    S + 1) with respect to its own adapters (stacked per client), and the
    losses (C,); ``group`` clients at a time (all at once if 0).  The
    gradients a local step of :func:`train_lm_clients` takes."""
    def grads(ad, batch):
        # clients' losses depend on their own adapters alone, so the
        # gradient of the sum is each client's own gradient
        def total(a):
            losses = lm_client_losses(a, base, batch, cfg)
            return jnp.sum(losses), losses

        (_, losses), g = jax.value_and_grad(total, has_aux=True)(ad)
        return g, losses

    return _in_passes(grads, group, adapters, tokens)


def _in_passes(fn, group: int, *stacked):
    """``fn`` over the clients in passes of ``group`` (all at once if 0):
    ``stacked`` trees have a leading client axis, and so has the result."""
    C = jax.tree.leaves(stacked)[0].shape[0]
    g = group or C
    if C % g:
        raise ValueError(f"{C} clients do not split into passes of {g}")
    split = lambda x: x.reshape((C // g, g) + x.shape[1:])   # noqa: E731
    out = jax.lax.map(lambda args: fn(*args),
                      jax.tree.map(split, stacked))
    return jax.tree.map(lambda x: x.reshape((C,) + x.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=("cfg", "lr", "group"))
def train_lm_clients(adapters: PyTree, base: PyTree, tokens: jax.Array, *,
                     cfg: ArchConfig, lr: float,
                     group: int = 0) -> Tuple[PyTree, jax.Array]:
    """Local steps of every LM client: one AdamW step on each of its
    batches ``tokens`` (C, steps, B, S + 1), on next-token cross-entropy
    alone (LoRA leaves the router frozen, so no balance loss).  Each
    step's gradients are taken ``group`` clients at a time (all at once
    if 0), which bounds the activations held.  Returns (new stacked
    adapters, each step's loss per client (C, steps)).  The optimizer is
    AdamW at a constant ``lr`` with PyTorch's betas and eps and no weight
    decay, its state new every round."""
    opt = AdamW(lr=lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
                warmup_steps=1)

    def step(carry, batch):
        ad, state = carry
        g, losses = lm_client_grads(ad, base, batch, cfg=cfg, group=group)
        ad, state = opt.update(g, state, ad)
        return (ad, state), losses

    (adapters, _), losses = jax.lax.scan(
        step, (adapters, opt.init(adapters)), jnp.swapaxes(tokens, 0, 1))
    return adapters, losses.T


@functools.partial(jax.jit, static_argnames=("cfg", "group"))
def eval_lm_clients(adapters: PyTree, base: PyTree, tokens: jax.Array, *,
                    cfg: ArchConfig, group: int = 0) -> jax.Array:
    """Held-out next-token cross-entropy per client (C,), ``group``
    clients at a time."""
    return _in_passes(lambda a, t: lm_client_losses(a, base, t, cfg),
                      group, adapters, tokens)
