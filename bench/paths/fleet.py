"""The fleet path: a configuration's tenants compiled by the program's
compiler and served by one ``AsyncServeEngine``, driven through its public
``submit_async`` / ``result`` by a traffic kind of ``loadgen``, each answer
compared with the family's plain reference."""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib

import numpy as np

from harness import check, fleet, session, spec, trace, window
from harness.peaks import peaks
from harness.window import CLOCK
from loadgen import features

LEAD_S = 0.05        # least time from the end of set-up to the window
GRACE_S = 60.0       # how long past the window's close an answer may come


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    cell: dict
    config: dict
    traffic: dict
    seconds: float
    setup_s: float
    host_compile_s: float
    bucket_warmup_s: float
    log: window.Log            # times in seconds from the window's start
    flushes: dict              # arrays: tenant, n, bucket, t0, t1
    tenants: list
    peaks: dict
    trace: trace.Summary | None

    def in_window(self, t: np.ndarray) -> np.ndarray:
        return (t >= 0.0) & (t <= self.seconds)


@dataclasses.dataclass
class Setup:
    """A configuration made ready to serve: the engine with every tenant
    resident and warm."""
    config: dict
    engine: window.BenchEngine
    tenants: list
    peaks: dict
    host_compile_s: float
    bucket_warmup_s: float


def set_up(cfg: dict, device: dict) -> Setup:
    from repro.core.artifacts import ArtifactStore

    pk = peaks(device["kind"])
    engine = window.BenchEngine(
        max_resident=len(cfg["tenants"]),
        artifact_store=ArtifactStore(spec.CACHE / "store"))
    tenants, host_compile_s = fleet.build(cfg, engine, engine.artifact_store)
    tw = CLOCK()
    fleet.warm(engine, tenants, cfg["max_batch"])
    warm_s = CLOCK() - tw
    # What set-up built lives as long as the process.  Without this, the
    # first full collections inside the window walk all of it (JAX, the
    # programs, the plans) and stall the event loop for hundreds of ms.
    gc.collect()
    gc.freeze()
    return Setup(cfg, engine, tenants, pk, host_compile_s, warm_s)


def rows(su: Setup, seed: int) -> list[np.ndarray]:
    """Each tenant's pool of rows for this seed."""
    return [features.rows(t.dep, su.config["rows_per_tenant"],
                          np.random.default_rng([seed, 1, i]))
            for i, t in enumerate(su.tenants)]


def measure(su: Setup, cell: dict, traffic: dict, pool: list, seed: int,
            seconds: float, traced: bool, t_start: float) -> Run:
    """One window of ``traffic`` on a ready configuration."""
    kind = importlib.import_module(f"loadgen.{traffic['kind']}")
    with session.Session(traced) as sess:
        su.engine.flushes.clear()
        setup_s = CLOCK() - t_start
        # a traffic kind that needs a run-in before the window
        # (``preroll_s``) starts its clients that long before it
        t0 = CLOCK() + max(LEAD_S, float(traffic.get("preroll_s", 0.0)))
        ctx = window.Ctx(su.engine, [t.name for t in su.tenants], pool, t0,
                         seconds, np.random.default_rng([seed, 2]), GRACE_S,
                         traced)
        asyncio.run(window.serve(ctx, kind.drive, traffic, sess))
    for t in su.tenants:
        t.state.finished.clear()
    log = ctx.log
    log.finish(t0)
    names = {t.name: i for i, t in enumerate(su.tenants)}
    fl = su.engine.flushes
    flushes = {
        "tenant": np.array([names[f[0]] for f in fl], np.int64),
        "n": np.array([f[1] for f in fl], np.int64),
        "bucket": np.array([f[2] for f in fl], np.int64),
        "t0": np.array([f[3] for f in fl]) - t0,
        "t1": np.array([f[4] for f in fl]) - t0,
    }
    return Run(cell, su.config, traffic, seconds, setup_s, su.host_compile_s,
               su.bucket_warmup_s, log, flushes, su.tenants, su.peaks,
               sess.summary)


def numbers(r: Run, pool: list, control: bool = False
            ) -> tuple[dict, int, int]:
    """Compare every answer with the reference: the compared numbers, what
    was sent in the window and what never came back.  With ``control`` the
    answers compared are the control's (the reference in the next lower
    precision) for the same requests."""
    log = r.log
    fam = fleet.family(r.config)
    sent = r.in_window(log.due)
    answered = np.isfinite(log.t_done)
    failed = int(np.sum(sent & ~answered))
    per_tenant = []
    for i, t in enumerate(r.tenants):
        idx = np.nonzero((log.tenant == i) & answered)[0]
        if len(idx) == 0:
            continue
        rows = log.row[idx]
        ref_pool = fam.forward(t.params, t.spec, pool[i])
        if control:
            ctl = fam.forward(t.params, t.spec, pool[i], check.rounder(
                r.config["control"], check.calib_maxima(
                    fam.forward, t.params, t.spec, t.dep.calib)))
            scores, pred = ctl[rows], ctl[rows].argmax(axis=1)
        else:
            scores, pred = check.answers([log.outputs[j] for j in idx],
                                         t.score_key, t.pred_key)
        per_tenant.append(check.tenant_numbers(ref_pool[rows], scores, pred))
    return check.numbers(per_tenant, failed), int(np.sum(sent)), failed
