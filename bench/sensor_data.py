"""METR-LA-like sensor data for the HFL cells, made from the seed.

A copy of the program's ``repro.data.traffic.generate`` and
``select_fl_sensors`` (same statistics: 5-minute readings, daily rush
hours, weekend relief, four geographic clusters with correlated AR(1)
congestion, incident drops), kept here so that the reference and the
program train on data the program did not make.  Returns plain arrays;
the HFL driver wraps them in the program's ``TrafficDataset``.
"""
from __future__ import annotations

import numpy as np

STEPS_PER_DAY = 288
N_SENSORS = 207
N_CLUSTERS = 4


def generate(num_days: int, rng: np.random.Generator) -> dict:
    T = num_days * STEPS_PER_DAY
    t = np.arange(T)
    tod = (t % STEPS_PER_DAY) / STEPS_PER_DAY
    weekend = (((t // STEPS_PER_DAY) % 7) >= 5).astype(float)
    centers = rng.uniform(0, 10, (N_CLUSTERS, 2))
    cluster_of = rng.integers(0, N_CLUSTERS, N_SENSORS)
    positions = centers[cluster_of] + rng.normal(0, 0.8, (N_SENSORS, 2))
    base = rng.uniform(55, 68, N_SENSORS)

    def bump(center, width):
        return np.exp(-0.5 * ((tod - center) / width) ** 2)

    sev_am = rng.uniform(8, 22, N_CLUSTERS)[cluster_of] \
        * rng.uniform(0.8, 1.2, N_SENSORS)
    sev_pm = rng.uniform(10, 26, N_CLUSTERS)[cluster_of] \
        * rng.uniform(0.8, 1.2, N_SENSORS)
    cong = (bump(0.31, 0.045)[:, None] * sev_am[None, :]
            + bump(0.73, 0.055)[:, None] * sev_pm[None, :])
    cong *= (1.0 - 0.65 * weekend)[:, None]
    drift = 2.0 * np.sin(2 * np.pi * t / (STEPS_PER_DAY * 30))[:, None]
    ar = np.zeros((T, N_CLUSTERS))
    eps = rng.normal(0, 1.0, (T, N_CLUSTERS))
    for k in range(1, T):
        ar[k] = 0.97 * ar[k - 1] + eps[k]
    ar = ar / ar.std(axis=0, keepdims=True) * 2.2
    speeds = (base[None, :] - cong + drift + ar[:, cluster_of]
              + rng.normal(0, 1.6, (T, N_SENSORS)))
    for _ in range(num_days * 3):
        s = rng.integers(0, N_SENSORS)
        start = rng.integers(0, T - 24)
        dur = rng.integers(6, 24)
        speeds[start:start + dur, s] *= rng.uniform(0.3, 0.6)
    speeds = np.clip(speeds, 3.0, 75.0).astype(np.float32)
    mean = speeds.mean(axis=0)
    std = speeds.std(axis=0) + 1e-6
    return {"speeds": speeds, "cluster_of": cluster_of,
            "positions": positions, "mean": mean, "std": std}


def select_sensors(data: dict, per_cluster: int,
                   rng: np.random.Generator) -> np.ndarray:
    """``per_cluster`` random sensors from each geographic cluster."""
    chosen = []
    for k in range(N_CLUSTERS):
        members = np.nonzero(data["cluster_of"] == k)[0]
        chosen.extend(rng.choice(members, min(per_cluster, len(members)),
                                 replace=False))
    return np.asarray(chosen)
