"""Covertype-shaped rows: a copy of the generator in ``src/repro/data/synth.py``.

Kept here so that the benchmark's inputs do not move when the program's
module changes.  54 features: 10 continuous terrain columns, 4 one-hot
wilderness areas and 40 one-hot soil types (UCI Covertype's layout).
"""

from __future__ import annotations

import numpy as np


def _redundant_block(rng, n, latent, out_dim, noise=0.1):
    """Mix ``latent`` (n, k) into ``out_dim`` correlated observed features."""
    k = latent.shape[1]
    mix = rng.normal(size=(k, out_dim)) * (rng.random((k, out_dim)) < 0.4)
    return latent @ mix + noise * rng.normal(size=(n, out_dim))


def make_covtype(n: int, seed: int):
    """(x (n, 54) float32, y (n,) float32 in 0..6) from ``seed``."""
    rng = np.random.default_rng(seed)
    lat = rng.normal(size=(n, 6))
    cont = _redundant_block(rng, n, lat, 10, noise=0.3)
    cont[:, 0] = cont[:, 0] * 600 + 2800          # elevation-like
    cont[:, 1] = np.abs(cont[:, 1]) * 90          # slope-like
    wild = np.eye(4)[rng.integers(0, 4, n)]
    soil_id = np.clip((lat[:, 0] * 6 + rng.normal(size=n) + 20).astype(int) % 40, 0, 39)
    soil = np.eye(40)[soil_id]
    x = np.concatenate([cont, wild, soil], axis=1).astype(np.float32)
    score = (
        (cont[:, 0] - 2800) / 600
        + 0.5 * (cont[:, 1] > 45)
        + 0.8 * lat[:, 1]
        + 0.3 * soil_id / 40
        + 0.4 * rng.normal(size=n)
    )
    qs = np.quantile(score, [0.2, 0.45, 0.6, 0.75, 0.85, 0.95])
    y = np.digitize(score, qs).astype(np.float32)  # 7 classes
    return x, y


def rows(n: int, seed: int) -> np.ndarray:
    """(n, 54) float32 rows drawn from ``seed`` (the traffic's rows)."""
    return make_covtype(n, seed)[0]
