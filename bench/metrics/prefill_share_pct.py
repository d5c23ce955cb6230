"""Share of the window the engine spent in prefill and insert (the
program's ``mafia.lm.prefill`` and ``mafia.lm.insert``), in which no slot
decodes."""

import numpy as np


def read(run):
    p = getattr(run, "prefills", None)
    if p is None:
        return None
    return 100.0 * float(np.sum(p["prefill_s"] + p["insert_s"])) / run.seconds
