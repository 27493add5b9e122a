"""Shared utilities for the model zoo: parameter construction with logical
sharding axes, sharding-constraint context, dtype helpers.

Params are plain nested dicts of jnp arrays (no flax).  Every parameter is
created through :class:`ParamBuilder`, which simultaneously records the
parameter's *logical axes* (e.g. ``("embed", "mlp")``).  The launch layer
maps logical axes to mesh axes (see ``repro/launch/shardings.py``).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# ---------------------------------------------------------------------------
# Logical-axis sharding context
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextlib.contextmanager
def logical_sharding(mesh, rules: Dict[str, Tuple[str, ...]]):
    """Within this context, :func:`shard` applies with_sharding_constraint
    using ``rules`` (logical axis -> mesh axes).  Outside it, shard() is a
    no-op so all model code runs unchanged on a single CPU device."""
    prev = getattr(_CTX, "state", None)
    _CTX.state = (mesh, rules)
    try:
        yield
    finally:
        _CTX.state = prev


def _mesh_axes_for(logical: Sequence[Optional[str]], shape=None):
    mesh, rules = _CTX.state
    out, used = [], set()
    for i, ax in enumerate(logical):
        if ax is None:
            out.append(None)
            continue
        cand = rules.get(ax, ())
        cand = tuple(a for a in cand if a in mesh.shape and a not in used)
        if not cand:
            out.append(None)
            continue
        size = int(np.prod([mesh.shape[a] for a in cand]))
        if shape is not None and shape[i] % size != 0:
            # divisibility fallback: try progressively smaller prefixes
            ok = ()
            for k in range(len(cand), 0, -1):
                sz = int(np.prod([mesh.shape[a] for a in cand[:k]]))
                if shape[i] % sz == 0:
                    ok = cand[:k]
                    break
            cand = ok
        if not cand:
            out.append(None)
        else:
            used.update(cand)
            out.append(cand if len(cand) > 1 else cand[0])
    return out


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint to an activation (no-op outside
    a :func:`logical_sharding` context)."""
    state = getattr(_CTX, "state", None)
    if state is None:
        return x
    mesh, _ = state
    axes = _mesh_axes_for(logical, shape=x.shape)
    spec = jax.sharding.PartitionSpec(*axes)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def named_sharding_for(mesh, rules, logical: Sequence[Optional[str]],
                       shape: Sequence[int]):
    """NamedSharding for a tensor of ``shape`` with ``logical`` axes."""
    token = getattr(_CTX, "state", None)
    _CTX.state = (mesh, rules)
    try:
        axes = _mesh_axes_for(logical, shape=tuple(shape))
    finally:
        _CTX.state = token
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*axes))


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

# leading axes that index a stack of independent matrices
_BATCH_AXES = ("expert", "layers")


def fan_in(shape: Sequence[int], axes: Sequence[Optional[str]]) -> int:
    """The number of inputs a weight contracts per output, read from its
    logical axes: 2-D ``(in, out)`` weights contract ``shape[0]``; past
    leading stack axes (``expert``), a weight into the model's width
    (last axis ``embed``, e.g. ``wo`` (heads, head_dim, embed))
    contracts every other axis, a per-head map (leading ``heads``, e.g.
    xLSTM's recurrence (heads, head_dim, head_dim)) its middle ones, and
    any other (``wq`` (embed, heads, head_dim)) its first."""
    if len(shape) <= 2:
        return int(shape[0]) if shape else 1
    k = 0
    while axes[k] in _BATCH_AXES:
        k += 1
    rest, names = tuple(shape[k:]), tuple(axes[k:])
    if names[-1] == "embed":
        return int(np.prod(rest[:-1]))
    if names[0] == "heads":
        return int(np.prod(rest[1:-1]))
    return int(rest[0])


class ParamBuilder:
    """Builds a nested param dict and a parallel tree of logical axes.

    >>> pb = ParamBuilder(rng, dtype=jnp.bfloat16)
    >>> w = pb.param("attn/wq", (d, H, hd), ("embed", "heads", "head_dim"))
    """

    def __init__(self, rng: jax.Array, dtype=jnp.bfloat16):
        self.rng = rng
        self.dtype = dtype
        self.params: Dict[str, Any] = {}
        self.axes: Dict[str, Any] = {}
        self._counter = 0

    def _next_rng(self) -> jax.Array:
        self._counter += 1
        return jax.random.fold_in(self.rng, self._counter)

    def _insert(self, tree: Dict[str, Any], path: str, value: Any) -> None:
        parts = path.split("/")
        for p in parts[:-1]:
            tree = tree.setdefault(p, {})
        if parts[-1] in tree:
            raise ValueError(f"duplicate param {path}")
        tree[parts[-1]] = value

    def param(self, path: str, shape: Tuple[int, ...],
              axes: Tuple[Optional[str], ...],
              init: str = "fan_in", scale: float = 1.0,
              dtype=None) -> jax.Array:
        assert len(shape) == len(axes), (path, shape, axes)
        dtype = dtype or self.dtype
        if init == "zeros":
            val = jnp.zeros(shape, dtype)
        elif init == "ones":
            val = jnp.ones(shape, dtype)
        elif init == "normal":
            val = (jax.random.normal(self._next_rng(), shape, jnp.float32)
                   * scale).astype(dtype)
        else:  # fan_in
            std = scale / np.sqrt(max(fan_in(shape, axes), 1))
            val = (jax.random.normal(self._next_rng(), shape, jnp.float32)
                   * std).astype(dtype)
        self._insert(self.params, path, val)
        self._insert(self.axes, path, tuple(axes))
        return val

    def subtree(self, prefix: str, params: Dict[str, Any],
                axes: Dict[str, Any]) -> None:
        """Graft an externally built (params, axes) pair under ``prefix``."""
        self._insert(self.params, prefix, params)
        self._insert(self.axes, prefix, axes)

    def build(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        return self.params, self.axes


def stack_params(trees: Sequence[PyTree]) -> PyTree:
    """Stack a list of identically-structured param trees along axis 0
    (for scan-over-layers)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def stack_axes(axes_tree: PyTree, layer_axis: str = "layers") -> PyTree:
    """Prepend the layer logical axis to every axes tuple."""
    return jax.tree.map(
        lambda a: (layer_axis,) + tuple(a),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


# ---------------------------------------------------------------------------
# dtype / numerics helpers
# ---------------------------------------------------------------------------

def to_dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def count_params(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def param_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))
