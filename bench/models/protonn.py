"""ProtoNN (Gupta et al., ICML'17) for the benchmark: weights from a seed,
the program's DFG built from them, and a plain reference of the model's math.

    p   = W x                          sparse projection
    d_j = || p - B[:, j] ||^2          squared distance to prototype j
    y_c = sum_j exp(-gamma^2 d_j) Zs[c, j]
"""

from __future__ import annotations

import numpy as np


def init(t: dict, seed: int) -> dict[str, np.ndarray]:
    """Random weights with a trained model's shapes and sparsity: ``W``
    keeps ``w_density`` of its entries and is scaled by its fan-in."""
    rng = np.random.default_rng(seed)
    d, p, m, L = (t["n_features"], t["proj_dim"], t["n_prototypes"],
                  t["n_classes"])
    mask = rng.random((p, d)) < t["w_density"]
    W = rng.normal(size=(p, d)) * mask / np.sqrt(max(1.0, t["w_density"] * d))
    return {
        "W": W.astype(np.float32),
        "B": rng.normal(size=(p, m)).astype(np.float32),
        "Zs": (rng.normal(size=(L, m)) * 0.1).astype(np.float32),
        "gamma": np.asarray(np.float32(t["gamma"])),
    }


def build_dfg(params: dict, t: dict):
    """The program's own DFG of these weights (its ProtoNN front end)."""
    from repro.models import protonn

    cfg = protonn.ProtoNNConfig(
        n_features=t["n_features"], n_classes=t["n_classes"],
        proj_dim=t["proj_dim"], n_prototypes=t["n_prototypes"],
        gamma=t["gamma"], w_density=t["w_density"])
    return protonn.build_dfg(params, cfg, name=t["name"].replace("/", "_"))


def forward(params: dict, t: dict, X: np.ndarray, r=None) -> np.ndarray:
    """Class scores ``(n, n_classes)`` in float64.  ``r``, when given,
    rounds every weight and every intermediate tensor, by name: the
    lower-precision control."""
    r = r or (lambda name, a: a)
    w = {k: r(k, np.asarray(v, np.float64)) for k, v in params.items()}
    x = r("x", np.asarray(X, np.float64))
    p = r("p", x @ w["W"].T)
    d2 = r("d2", ((p[:, :, None] - w["B"][None]) ** 2).sum(axis=1))
    sim = r("sim", np.exp(r("arg", -(w["gamma"] ** 2) * d2)))
    return r("y", sim @ w["Zs"].T)
