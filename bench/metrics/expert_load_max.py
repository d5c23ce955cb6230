"""The busiest routed expert's share of its layer's choices over the window,
times the number of experts (1 is an even load), the largest over the MoE
layers: the engine's per-expert counts of the tokens it prefilled and
decoded."""

import numpy as np


def read(run):
    c = getattr(run, "expert_counts", None)
    if c is None or np.sum(c) == 0:
        return None
    share = c.max(axis=1) / np.maximum(c.sum(axis=1), 1)
    return float(np.max(share) * c.shape[1])
