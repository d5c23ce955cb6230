"""The configuration's tenants: weights from the deployment's seed, each
compiled by the program's compiler through the artifact store, registered
on the engine, and every batch size of every tenant served once."""

from __future__ import annotations

import dataclasses
import importlib
import re

import numpy as np

from harness import work as work_mod
from harness.window import CLOCK
from loadgen import features

WEIGHT_ITEMSIZE = {"float32": 4, "int8": 1, "int16": 2}


@dataclasses.dataclass
class Tenant:
    name: str
    spec: dict
    params: dict
    dep: features.Deployment
    state: object          # the engine's ModelState
    work: work_mod.Work
    score_key: str
    pred_key: str
    forward_module: str    # name of the jitted forward's XLA program


def family(cfg: dict):
    return importlib.import_module(f"models.{cfg['family']}")


def build(cfg: dict, engine, store) -> tuple[list[Tenant], float]:
    """Compile and register every tenant; returns them and the seconds
    spent in the program's compiler."""
    from repro.core.compiler import MafiaCompiler

    fam = family(cfg)
    precision = cfg["precision"]
    compiler = MafiaCompiler(use_pallas=True, precision=precision,
                             exec_mode=cfg["exec_mode"], artifact_store=store)
    tenants, compile_s = [], 0.0
    for i, t in enumerate(cfg["tenants"]):
        params = fam.init(t, [cfg["weight_seed"], i])
        dep = features.deployment(t["n_features"], t["n_classes"],
                                  [cfg["weight_seed"], i, 1])
        dfg = fam.build_dfg(params, t)
        t0 = CLOCK()
        prog = compiler.compile(
            dfg, calib=None if precision == "float32" else dep.calib)
        compile_s += CLOCK() - t0
        state = engine.register_model(t["name"], prog, slo_ms=t["slo_ms"],
                                      max_batch=cfg["max_batch"])
        score_key, pred_key = _output_keys(dfg)
        tenants.append(Tenant(
            t["name"], t, params, dep, state,
            work_mod.count(dfg, WEIGHT_ITEMSIZE[precision]),
            score_key, pred_key, ""))
    return tenants, compile_s


def _output_keys(dfg) -> tuple[str, str]:
    """(class scores, class index): the argmax output and its input."""
    (pred,) = [o for o in dfg.outputs if dfg.nodes[o].op == "argmax"]
    return dfg.nodes[pred].inputs[0], pred


def warm(engine, tenants: list[Tenant], max_batch: int) -> None:
    """Serve every batch size 1..max_batch of every tenant once through
    ``flush``, so that no bucket program compiles inside the window."""
    import jax

    for t in tenants:
        x = t.dep.calib[0]
        for n in range(1, max_batch + 1):
            for _ in range(n):
                engine.submit(t.name, x)
            engine.flush(t.name, n)
        t.state.finished.clear()
        t.state.batched.stats.clear()
        arg = {t.state.input_name: jax.ShapeDtypeStruct(
            (max_batch,) + tuple(t.state.in_shape), np.float32)}
        text = t.state.batched.fn.lower(arg).as_text()
        t.forward_module = re.search(r"module @([\w.]+)", text).group(1)
