"""Bonsai (Kumar et al., ICML'17) for the benchmark: weights from a seed, the
program's DFG built from them, and a plain reference of the model's math.

The reference is written from the paper's description and shares no code
with the program: the tree is walked explicitly, leaf by leaf.

    z  = Z x                               sparse projection
    s  = tanh(sigma_theta * Theta z)       one branch score per internal node
    I_k = prod over k's ancestors p of (1 + d * s_p) / 2, d = +1 when the
         path turns right at p (heap child 2p + 2), else -1
    H  = (W z) * tanh(sigma * V z)         row k * L + c: leaf k, class c
    y_c = sum_k I_k * H[k * L + c]
"""

from __future__ import annotations

import numpy as np


def init(t: dict, seed: int) -> dict[str, np.ndarray]:
    """Random weights with a trained model's shapes and sparsity: ``Z``
    keeps ``z_density`` of its entries, every matrix is scaled by its fan-in."""
    rng = np.random.default_rng(seed)
    d, p, L = t["n_features"], t["proj_dim"], t["n_classes"]
    n_int, n_leaf = 2 ** t["depth"] - 1, 2 ** t["depth"]
    mask = rng.random((p, d)) < t["z_density"]
    Z = rng.normal(size=(p, d)) * mask / np.sqrt(max(1.0, t["z_density"] * d))
    scale = 1.0 / np.sqrt(p)
    return {
        "Z": Z.astype(np.float32),
        "W": (rng.normal(size=(n_leaf * L, p)) * scale).astype(np.float32),
        "V": (rng.normal(size=(n_leaf * L, p)) * scale).astype(np.float32),
        "Theta": (rng.normal(size=(n_int, p)) * scale).astype(np.float32),
    }


def build_dfg(params: dict, t: dict):
    """The program's own DFG of these weights (its Bonsai front end)."""
    from repro.models import bonsai

    cfg = bonsai.BonsaiConfig(
        n_features=t["n_features"], n_classes=t["n_classes"],
        proj_dim=t["proj_dim"], depth=t["depth"], sigma=t["sigma"],
        sigma_theta=t["sigma_theta"], z_density=t["z_density"])
    return bonsai.build_dfg(params, cfg, name=t["name"].replace("/", "_"))


def _leaf_paths(depth: int) -> list[list[tuple[int, float]]]:
    """For each leaf, its ancestors (heap index) and the turn taken there."""
    n_int = 2 ** depth - 1
    paths = []
    for leaf in range(2 ** depth):
        node, path = leaf + n_int, []
        while node > 0:
            parent = (node - 1) // 2
            path.append((parent, 1.0 if node == 2 * parent + 2 else -1.0))
            node = parent
        paths.append(path)
    return paths


def forward(params: dict, t: dict, X: np.ndarray, r=None) -> np.ndarray:
    """Class scores ``(n, n_classes)`` in float64.  ``r``, when given,
    rounds every weight and every intermediate tensor, by name: the
    lower-precision control."""
    r = r or (lambda name, a: a)
    w = {k: r(k, np.asarray(v, np.float64)) for k, v in params.items()}
    L, n_leaf = t["n_classes"], 2 ** t["depth"]
    x = r("x", np.asarray(X, np.float64))
    z = r("z", x @ w["Z"].T)
    s = r("s", np.tanh(r("th", t["sigma_theta"] * (z @ w["Theta"].T))))
    ind = np.ones((len(x), n_leaf))
    for k, path in enumerate(_leaf_paths(t["depth"])):
        for parent, turn in path:
            ind[:, k] *= 0.5 * (1.0 + turn * s[:, parent])
    ind = r("ind", ind)
    wz = r("wz", z @ w["W"].T)
    vt = r("vt", np.tanh(r("vz", t["sigma"] * (z @ w["V"].T))))
    h = r("h", wz * vt).reshape(len(x), n_leaf, L)
    return r("y", np.einsum("nk,nkc->nc", ind, h))
