"""Production mesh definitions (TPU v5e target).

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run sets the 512-device XLA flag before
any jax initialization)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — roofline denominators
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s per link (intra-pod)
DCI_BW = 25e9                     # bytes/s effective cross-pod share
HBM_BYTES = 16 * 1024 ** 3        # 16 GB per chip


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model code shards
    through ``with_sharding_constraint`` and indexes sharded arrays, which
    ``Explicit`` axes (the default of ``make_mesh``) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_hfl_mesh(*, n_clusters: int = 4, multi_pod: bool = False):
    """HFL training mesh: a leading "cluster" axis carries divergent model
    replicas (DESIGN.md §3).  Multi-pod: cluster == pod (2 clusters).
    Single-pod: the 16-wide data axis is split into (cluster, data)."""
    if multi_pod:
        return _mesh((2, 16, 16), ("cluster", "data", "model"))
    if 16 % n_clusters != 0:
        raise ValueError("n_clusters must divide 16")
    return _mesh((n_clusters, 16 // n_clusters, 16),
                 ("cluster", "data", "model"))


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small host-device mesh for unit tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count>=prod(shape))."""
    return _mesh(shape, axes)
