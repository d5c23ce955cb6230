"""Operations and bytes one request needs, counted from the source DFG.

The count reads the model's graph, not the plan that runs it, so the same
work is counted whatever implements it.  A sparse matrix counts its
nonzeros only.  Per request: two operations per multiply-add, one per
element of an elementwise op, a nonlinearity or a comparison; the input row
(float32) and the outputs.  Per flush: every weight, once, at the width the
configuration serves it in.  Padding rows are waste and are not counted.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Work:
    ops_per_request: float
    io_bytes_per_request: float
    weight_bytes: float          # read once per flush

    def flush(self, n_real: int) -> tuple[float, float]:
        """(operations, bytes) of one flush that carries ``n_real`` requests."""
        return (n_real * self.ops_per_request,
                self.weight_bytes + n_real * self.io_bytes_per_request)


_ELEMENTWISE = {"scalar_mul", "tanh", "exp", "hadamard", "add", "relu",
                "sigmoid"}


def count(dfg, weight_itemsize: int) -> Work:
    """The work of one request through ``dfg`` (a source DFG)."""
    size: dict[str, int] = {n: int(np.prod(gi.shape))
                            for n, gi in dfg.graph_inputs.items()}
    ops = 0.0
    weights = 0.0
    for nid in _topological(dfg):
        node = dfg.nodes[nid]
        n_in = size[node.inputs[0]]
        if node.op in ("spmv", "gemv"):
            m = np.asarray(node.params["matrix"])
            macs = np.count_nonzero(m) if node.op == "spmv" else m.size
            ops += 2 * macs
            weights += macs
            size[nid] = m.shape[0]
        elif node.op == "sq_l2":
            pts = np.asarray(node.params["points"])   # (dim, prototypes)
            ops += 3 * pts.size                       # subtract, square, sum
            weights += pts.size
            size[nid] = pts.shape[1]
        elif node.op in _ELEMENTWISE:
            ops += n_in
            if "vec" in node.params:
                weights += np.asarray(node.params["vec"]).size
            size[nid] = n_in
        elif node.op == "argmax":
            ops += n_in
            size[nid] = 1
        else:
            raise ValueError(f"no work count for op {node.op!r} ({nid})")
    in_bytes = 4 * sum(size[n] for n in dfg.graph_inputs)
    out_bytes = 4 * sum(size[n] for n in dfg.outputs)
    return Work(ops, in_bytes + out_bytes, weights * weight_itemsize)


def _topological(dfg) -> list[str]:
    done: set[str] = set(dfg.graph_inputs)
    order: list[str] = []
    pending = list(dfg.nodes)
    while pending:
        rest = []
        for nid in pending:
            if all(i in done for i in dfg.nodes[nid].inputs):
                done.add(nid)
                order.append(nid)
            else:
                rest.append(nid)
        if len(rest) == len(pending):
            raise ValueError("DFG has a cycle")
        pending = rest
    return order
