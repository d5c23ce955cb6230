"""Feature rows: Gaussian class clusters at a tenant's Table-I width.

The same construction as the program's synthetic Table-I datasets: class
centres scaled by ``separation / sqrt(n_features)``, unit noise, features
standardized by the training split's statistics.  The centres, the
statistics and the calibration rows belong to the deployment (a fixed
seed); the rows a run sends are drawn from the run's seed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

SEPARATION = 3.0
N_TRAIN = 2048       # rows the standardization statistics are taken over
N_CALIB = 256        # training rows the int8 scales are calibrated on


@dataclasses.dataclass(frozen=True)
class Deployment:
    centers: np.ndarray   # (n_classes, n_features)
    mu: np.ndarray
    sd: np.ndarray
    calib: np.ndarray     # (N_CALIB, n_features), standardized


def _sample(centers: np.ndarray, n: int, rng: np.random.Generator):
    y = rng.integers(0, len(centers), size=n)
    return centers[y] + rng.normal(size=(n, centers.shape[1]))


def deployment(n_features: int, n_classes: int, seed: int) -> Deployment:
    rng = np.random.default_rng(seed)
    centers = (rng.normal(size=(n_classes, n_features)) * SEPARATION
               / np.sqrt(n_features))
    train = _sample(centers, N_TRAIN, rng)
    mu, sd = train.mean(0), train.std(0) + 1e-6
    calib = ((train[:N_CALIB] - mu) / sd).astype(np.float32)
    return Deployment(centers, mu, sd, calib)


def rows(dep: Deployment, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` standardized rows, as a sensor of this deployment sends them."""
    return ((_sample(dep.centers, n, rng) - dep.mu) / dep.sd).astype(np.float32)
