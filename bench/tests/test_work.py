"""The DFG work count, checked by hand on two Table-I programs."""

from __future__ import annotations

import json

import numpy as np

from conftest import BENCH
from harness import fleet, work


def _tenant(config: str, name: str):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    i = [t["name"] for t in cfg["tenants"]].index(name)
    t = cfg["tenants"][i]
    fam = fleet.family(cfg)
    params = fam.init(t, [cfg["weight_seed"], i])
    return params, fam.build_dfg(params, t)


def test_bonsai_usps_b_by_hand():
    # d=256 features, 2 classes, projection 16, depth 3: 7 internal nodes,
    # 8 leaves, 16 leaf-class rows.
    params, dfg = _tenant("bonsai-t1-int8", "bonsai/usps-b")
    nnz = np.count_nonzero(params["Z"])
    w = work.count(dfg, weight_itemsize=1)
    # Zx 2nnz; Theta 2*7*16; scale, tanh 7+7; three levels of (D: 2*8,
    # +1: 8, *1/2: 8); two leaf products 8+8; W, V 2*16*16 each; V scale,
    # tanh, H 16 each; E 2*16; gate 16; R 2*16; argmax over 2.
    ops = (2 * nnz + 224 + 14 + 3 * 32 + 16 + 1024 + 48 + 32 + 16 + 32 + 2)
    assert w.ops_per_request == ops
    # Z's nonzeros, Theta 112, per level D 8 + ones 8, W and V 256 each,
    # E 16, R 16 — one byte each at int8.
    assert w.weight_bytes == nnz + 112 + 3 * 16 + 512 + 32
    # 256 float32 features in; 2 scores and 1 class index out.
    assert w.io_bytes_per_request == 4 * 256 + 4 * 3
    assert w.flush(3) == (3 * ops, w.weight_bytes + 3 * (4 * 259))


def test_protonn_usps_b_by_hand():
    # d=256 features, 2 classes, projection 12, 40 prototypes.
    params, dfg = _tenant("protonn-t1-f32", "protonn/usps-b")
    nnz = np.count_nonzero(params["W"])
    w = work.count(dfg, weight_itemsize=4)
    # Wx 2nnz; distances 3*12*40; scale, exp 40+40; scores 2*2*40; argmax 2.
    assert w.ops_per_request == 2 * nnz + 1440 + 80 + 160 + 2
    # W's nonzeros, prototypes 480, class scores 80 — float32.
    assert w.weight_bytes == 4 * (nnz + 480 + 80)
    assert w.io_bytes_per_request == 4 * 256 + 4 * 3
