"""MafiaCompiler — the end-to-end flow of Fig. 1, rewrite-first.

input DFG → **front-end rewrite** (prune → constant-fold → CSE) →
PF-1 profiler → Best-PF estimator → scheduler generator → back-end plan
pipeline → "Verilog" (JAX callable) + simulated latency/resource report.

The front-end runs *first*: the profiler, optimizer, scheduler and
quantizer all consume the canonical rewritten graph, so PF assignments,
schedules and LUT/DSP reports refer only to nodes that actually execute —
and every estimator query shrinks with the graph.  A DFG carrying dead
nodes, duplicate subexpressions, foldable scalar_muls or add-of-const
chains compiles to exactly the same assignment and schedule as its
hand-canonicalized equivalent — and, via the rewrite-aware PF warm-start
cache (keyed on the canonical graph's structural hash), a *recompile* of
anything that canonicalizes to a seen graph reuses the prior Best-PF
result instead of searching again.

The compiler also exposes the ablation knobs needed to reconstruct the
paper's comparison mechanisms (§V-B): execution order (dataflow vs the
sequential C-HLS model), pipelining on/off, externally-imposed PF
assignments (for the `Vivado Auto Opt` / `Vivado + MAFIA` baselines), and
the optimizer strategy/benefit metric (§VI-C).
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable

from repro.core import node_types, tracing
from repro.core.constraints import PFGroups
from repro.core.cost_model import EstimatorBank, default_bank
from repro.core.dfg import DFG
from repro.core.executor import build_callable
from repro.core.fpga_model import ARTY_A7, FpgaBudget
from repro.core.lowering import (
    DEFAULT_CHAIN_SPLIT_BYTES,
    ExecutionPlan,
    RewriteResult,
    _resolve,
    lower,
    rewrite,
)
from repro.core.optimizer import (
    CostContext,
    PFResult,
    blackbox_best_pf,
    greedy_best_pf,
)
from repro.core.profiler import profile_pf1
from repro.core.scheduler import Schedule, pipeline_clusters, simulate
from repro.core.tpu_model import TpuBudget

__all__ = ["MafiaCompiler", "CompiledProgram", "BatchedProgram"]


@dataclasses.dataclass
class CompiledProgram:
    dfg: DFG                     # canonical rewritten graph (what executes)
    fn: Callable[..., dict[str, Any]]
    assignment: dict[str, int]   # PFs over the rewritten graph's nodes only
    pf_result: PFResult | None
    schedule: Schedule
    lut_true: float
    dsp_true: float
    backend: str
    budget: Any
    fused_clusters: list[list[str]] = dataclasses.field(default_factory=list)
    use_pallas: bool = False
    precision: str = "float32"
    qplan: Any | None = None     # QuantPlan on the fixed-point lanes
    plan: ExecutionPlan | None = None  # static plan every lane interprets
    # "interpret" | "megakernel" (single-launch) | "megakernel_grid"
    # (single-launch with the serving bucket on the Pallas grid)
    exec_mode: str = "interpret"
    source_dfg: DFG | None = None      # the pre-rewrite graph, for reference
    rewrite_result: RewriteResult | None = None
    # how the PF assignment was obtained: "cold" (fresh search), "near"
    # (search seeded by a cached result for the same wiring), "exact"
    # (cache hit on the canonical graph's structural hash — no search ran),
    # "external" (caller-imposed assignment), or "artifact" (restored from
    # the persistent compile-artifact store — no search, no calibration)
    pf_source: str = "cold"
    # the chain-split budget the plan was lowered with — persisted so an
    # artifact load re-runs the identical chain decomposition
    chain_split_bytes: float | None = DEFAULT_CHAIN_SPLIT_BYTES
    # "analytic" (paper cycle model) or "measured" (profile-guided:
    # calibrated µs — the schedule's units are then µs, not cycles).
    # Cost source is compile-time metadata only: it steers PF search,
    # chain splitting and the schedule, never the emitted numerics.
    cost_source: str = "analytic"

    @property
    def latency_cycles(self) -> float:
        return self.schedule.total_cycles

    @property
    def latency_us(self) -> float:
        if self.cost_source == "measured":
            return self.schedule.total_cycles   # measured schedules are µs
        return self.budget.cycles_to_us(self.schedule.total_cycles)

    def __call__(self, **inputs: Any) -> dict[str, Any]:
        return self.fn(**inputs)

    def save(self, path: Any) -> str:
        """Persist this program as a versioned on-disk artifact (data only;
        jit/Pallas callables are rebound on :meth:`load`).  Returns the
        payload's content digest.  See :mod:`repro.core.artifacts`."""
        from repro.core import artifacts

        return artifacts.save_program(self, path)

    @staticmethod
    def load(path: Any) -> "CompiledProgram":
        """Restore a program saved by :meth:`save`: validates the content
        digest, re-runs the cheap back-end plan pipeline to rebind
        callables, and checks the relinearized megakernel stream against
        the serialized fingerprint.  The result is bitwise-equivalent to
        the program that was saved; ``pf_source`` is ``"artifact"``."""
        from repro.core import artifacts

        return artifacts.load_program(path)

    def batch(self, max_batch: int = 64, *, mode: str = "vmap",
              exec_mode: str | None = None) -> "BatchedProgram":
        """Batched execution of this program (the serving path).

        Returns a callable taking each graph input with a leading batch
        axis.  Batch sizes are rounded up to power-of-two *buckets* (capped
        at ``max_batch``) so XLA recompiles only once per bucket; larger
        batches are split into ``max_batch`` chunks.

        ``mode="vmap"`` vmaps the scheduled DFG node-by-node — fused
        linear-pipeline clusters hand the whole bucket to the Pallas kernel,
        whose grid tiles the batch axis.  Fastest; last-ulp numerics may
        differ from per-sample execution (XLA lowers a vmapped matvec as a
        matmul with a different accumulation order).  ``mode="map"`` runs
        the per-sample program under ``lax.map`` in one dispatch — bitwise
        identical to calling the program once per sample.  For an int8
        program both modes are bitwise-identical: integer accumulation has
        no reassociation error.

        ``exec_mode`` selects the step-execution strategy inside each lane
        (``"interpret"``, ``"megakernel"`` or ``"megakernel_grid"``, see
        :func:`repro.core.executor.build_callable`); it defaults to the
        mode this program was compiled with, so a megakernel-compiled
        program serves single-launch buckets without further plumbing.
        Under ``"megakernel_grid"`` the ``mode="vmap"`` lane stops vmapping
        the kernel launch: each segment runs once per bucket with the batch
        axis on the Pallas grid (one launch, matrices DMA'd once).
        """
        return BatchedProgram.build(
            self, max_batch=max_batch, mode=mode,
            exec_mode=self.exec_mode if exec_mode is None else exec_mode)


@dataclasses.dataclass
class BatchedProgram:
    """Bucketed, jit-cached batched callable over a :class:`CompiledProgram`.

    A call crosses the host↔device boundary once each way: each chunk is
    zero-padded to its bucket in numpy and handed to the jitted forward,
    whose dispatch copies it to the device; the outputs of every chunk are
    fetched together, with one wait, and the padding rows are sliced off
    on the host, so the call returns numpy.

    ``stats`` counts forwards per bucket size — each distinct bucket shape
    jit-compiles exactly once (jax caches on shape), so its keys are also
    the set of compiled entry points.  ``host_pad_rows`` counts the
    padding rows added on the host.  ``phase_s`` sums the host seconds of
    each phase of a call: ``put`` (pad to the bucket in numpy), ``forward``
    (dispatch of the jitted forward, with the host→device copy; it returns
    before the device finishes), ``fetch`` (the device wait and one
    device→host copy of every output) and ``slice`` (padding rows sliced
    off, chunks joined).  Each phase is also a span of the profiler's
    trace (:mod:`repro.core.tracing`).
    """

    program: CompiledProgram
    max_batch: int
    mode: str
    fn: Callable[[dict[str, Any]], dict[str, Any]]
    exec_mode: str = "interpret"
    stats: dict[int, int] = dataclasses.field(default_factory=dict)
    host_pad_rows: int = 0
    phase_s: dict[str, float] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(
            ("put", "forward", "fetch", "slice"), 0.0))

    @classmethod
    def build(cls, program: CompiledProgram, *, max_batch: int = 64,
              mode: str = "vmap",
              exec_mode: str | None = None) -> "BatchedProgram":
        import jax

        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if exec_mode is None:
            exec_mode = program.exec_mode
        # every lane interprets the program's static plan — vmap and map
        # differ only in how the batch axis is driven, never in analysis.
        kw: dict[str, Any] = dict(
            fused_clusters=program.fused_clusters,
            use_pallas=program.use_pallas, precision=program.precision,
            qplan=program.qplan, plan=program.plan, mode=exec_mode)
        if mode == "vmap":
            inner = build_callable(program.dfg, jit=False, batch=True, **kw)

            def forward(inputs):
                return inner(**inputs)
        elif mode == "map":
            single = build_callable(program.dfg, jit=False, **kw)

            def forward(inputs):
                return jax.lax.map(lambda s: single(**s), inputs)
        else:
            raise ValueError(f"unknown batch mode {mode!r}")
        # the name of the XLA program, and so of its launches in a trace
        forward.__name__ = f"mafia_forward_{mode}"
        return cls(program=program, max_batch=max_batch, mode=mode,
                   fn=jax.jit(forward), exec_mode=exec_mode)

    def bucket(self, n: int) -> int:
        """Smallest power-of-two ≥ n, capped at ``max_batch``."""
        if n < 1:
            raise ValueError("empty batch")
        b = 1
        while b < n:
            b *= 2
        return min(b, self.max_batch)

    def __call__(self, **inputs: Any) -> dict[str, Any]:
        import jax
        import numpy as np

        allowed = set(self.program.dfg.graph_inputs)
        unknown = set(inputs) - allowed
        if unknown:  # mirror the per-sample path: extras are a caller bug
            raise TypeError(f"unknown graph inputs: {sorted(unknown)}")
        missing = allowed - set(inputs)
        if missing:
            raise TypeError(f"missing graph inputs: {sorted(missing)}")
        sizes = {np.shape(v)[0] for v in inputs.values()}
        if len(sizes) != 1:
            raise ValueError(f"inconsistent batch sizes: {sorted(sizes)}")
        (B,) = sizes
        phase = self.phase_s
        arrays: dict[str, Any] | None = None
        outs: list[dict[str, Any]] = []
        rows: list[int] = []
        t = time.perf_counter()
        for start in range(0, B, self.max_batch):
            stop = min(start + self.max_batch, B)
            nb = stop - start
            bkt = self.bucket(nb)
            pad = bkt - nb
            with tracing.span(tracing.PUT, bucket=bkt):
                if arrays is None:
                    arrays = {k: np.asarray(v) for k, v in inputs.items()}
                chunk = {
                    k: np.pad(v[start:stop],
                              ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                    if pad else v[start:stop]
                    for k, v in arrays.items()
                }
            t1 = time.perf_counter()
            with tracing.span(tracing.FORWARD, bucket=bkt):
                outs.append(self.fn(chunk))
            t2 = time.perf_counter()
            self.stats[bkt] = self.stats.get(bkt, 0) + 1
            self.host_pad_rows += pad
            rows.append(nb)
            phase["put"] += t1 - t
            phase["forward"] += t2 - t1
            t = t2
        with tracing.span(tracing.FETCH):
            # every output's copy starts before the one wait
            outs = jax.device_get(outs)
        t1 = time.perf_counter()
        phase["fetch"] += t1 - t
        t = t1
        with tracing.span(tracing.SLICE):
            parts = [{k: v[:nb] for k, v in out.items()}
                     for out, nb in zip(outs, rows)]
            joined = parts[0] if len(parts) == 1 else {
                k: np.concatenate([p[k] for p in parts], axis=0)
                for k in parts[0]
            }
        phase["slice"] += time.perf_counter() - t
        return joined


# stale-calibration warnings fire once per table per process — a fleet of
# compilers sharing one expired table should not spam N identical lines
_STALE_CALIB_WARNED: set[str] = set()


def _warn_stale_calibration(key: str, age_days: float,
                            max_age_days: float) -> None:
    if key in _STALE_CALIB_WARNED:
        return
    _STALE_CALIB_WARNED.add(key)
    age = ("of unknown age (no created_at stamp)" if age_days == float("inf")
           else f"{age_days:.1f} days old")
    warnings.warn(
        f"calibration table {key[:12]} is {age} (max_age_days="
        f"{max_age_days:g}); measurements may no longer reflect the device "
        "— falling back to the analytic cost model. Re-run "
        "repro.core.autotune.profile_device() to refresh.",
        UserWarning, stacklevel=3)


class MafiaCompiler:
    def __init__(
        self,
        *,
        backend: str = "fpga",
        budget: FpgaBudget | TpuBudget | None = None,
        strategy: str = "greedy",
        metric: str = "latency_per_lut",
        order: str = "dataflow",
        pipelining: bool = True,
        use_pallas: bool = False,
        bank: EstimatorBank | None = None,
        precision: str = "float32",
        calib_samples: int = 64,
        per_channel: bool = False,
        chain_split_bytes: float | str | None = DEFAULT_CHAIN_SPLIT_BYTES,
        warm_start: bool = True,
        exec_mode: str = "interpret",
        artifact_store: Any | None = None,
        cost_source: str = "analytic",
        autotune: bool = False,
        calibration: Any | None = None,
        max_age_days: float | None = 30.0,
    ) -> None:
        """``precision="int8"`` / ``"int16"`` emits the fixed-point program
        the paper's SeeDot-lineage workloads actually run, at either
        activation width SeeDot targets (float32 is the beyond-paper
        default): :meth:`compile` calibrates per-tensor power-of-two scales
        (from its ``calib`` batch, or ``calib_samples`` synthetic
        standard-normal samples) and the emitted callable computes in narrow
        integers with int32 accumulation — interface stays float in / float
        out.  ``per_channel=True`` additionally gives gemv/spmv weight
        matrices one scale per output row (still plain shifts).

        ``chain_split_bytes`` bounds the live footprint of each fused stage
        chain: a maximal chain over the budget is split at the cheapest
        edge (see :func:`repro.core.lowering.split_chain`); the scheduler's
        pipelined-cluster model prices the same cuts, so estimated and
        simulated latency stay consistent with the plan the executor
        interprets.  ``None`` keeps chains maximal.

        ``warm_start`` enables the rewrite-aware PF warm-start cache: each
        :meth:`compile` keys its :class:`PFResult` on the *canonical
        rewritten* graph's structural hash, so recompiling a doped/edited
        variant that canonicalizes to a seen graph reuses the prior search
        result — an exact hit (same ids/ops/edges/dims) short-circuits the
        Best-PF search entirely and returns the identical ``PFResult``; a
        near hit (same wiring, different dims) seeds the greedy/black-box
        search at the prior solution.  The cache is per compiler instance;
        every optimizer-relevant knob is fixed per instance, so the graph
        hash alone is a complete key.

        ``exec_mode="megakernel"`` makes every emitted callable (per-sample
        and batched lanes alike) execute the plan through the linearize
        pass's single-launch instruction stream instead of one dispatch per
        step — see :func:`repro.core.executor.build_callable`.  Analysis is
        unchanged: both modes interpret the same :class:`ExecutionPlan`.

        ``artifact_store`` (a :class:`repro.core.artifacts.ArtifactStore`)
        enables the *persistent* compile cache: :meth:`compile` consults
        the store — keyed on the canonical graph's structural hash, its
        parameter values, every plan-relevant knob and the calibration
        digest — **before** the Best-PF search, so a fresh process
        cold-starts from artifacts any sibling worker published.  Misses
        compile normally and publish the artifact.  The in-memory PF
        warm-start cache layers on top (hits also prime it).

        ``cost_source="measured"`` enables profile-guided compilation
        (ROADMAP item 4): the Best-PF search, chain splitting and the
        schedule simulation all consume a
        :class:`~repro.core.autotune.CalibratedCostModel` fitted from
        microbenchmarks of the live backend instead of the analytic paper
        cycle model.  ``calibration`` supplies the measurements — a
        ``CalibrationTable``, a pre-fitted ``CalibratedCostModel``, or
        ``None`` to resolve one automatically (published table in
        ``artifact_store`` for this device class, else a quick in-process
        profile, published back to the store).  A table recorded for a
        *different* device class is rejected and the compiler falls back
        to the analytic model (``cost_source`` degrades to
        ``"analytic"``).  Cost source never changes emitted numerics —
        outputs are bitwise-identical across sources; only the PF
        assignment, chain cuts and the schedule's units (µs) differ.

        ``autotune=True`` additionally applies the calibration table's
        swept kernel knobs: the linear-pipeline ``(bb, bn)`` tile winner
        is installed process-wide, and ``chain_split_bytes="auto"``
        resolves to the swept split budget (falling back to the built-in
        default when the table has no knob record).

        ``max_age_days`` bounds how old a calibration table may be before
        its measurements stop being trusted: a table stamped (``meta
        ["created_at"]``) more than ``max_age_days`` days ago — or one with
        no stamp at all — is rejected with a once-per-process warning and
        the compiler degrades to the analytic model, exactly as for a
        device-class mismatch.  ``None`` disables the check."""
        if backend not in ("fpga", "tpu"):
            raise ValueError(f"unknown backend {backend!r}")
        if precision not in ("float32", "int8", "int16"):
            raise ValueError(f"unknown precision {precision!r}")
        if exec_mode not in ("interpret", "megakernel", "megakernel_grid"):
            raise ValueError(f"unknown exec_mode {exec_mode!r}")
        if cost_source not in ("analytic", "measured"):
            raise ValueError(f"unknown cost_source {cost_source!r}")
        self.backend = backend
        self.budget = budget or (ARTY_A7 if backend == "fpga" else TpuBudget())
        self.strategy = strategy
        self.metric = metric
        self.order = order
        self.pipelining = pipelining
        self.use_pallas = use_pallas
        self.bank = bank or default_bank()
        self.precision = precision
        self.calib_samples = calib_samples
        self.per_channel = per_channel
        self.chain_split_bytes = chain_split_bytes
        self.warm_start = warm_start
        self.exec_mode = exec_mode
        self.artifact_store = artifact_store
        self.autotune = autotune
        self.cost_source = cost_source
        self.max_age_days = max_age_days
        self.calibrated: Any | None = None
        if cost_source == "measured" or autotune:
            self._resolve_calibration(calibration)
        if self.chain_split_bytes == "auto":
            knobs = self.calibrated.knobs if self.calibrated else {}
            self.chain_split_bytes = knobs.get(
                "chain_split_bytes", DEFAULT_CHAIN_SPLIT_BYTES)
        # rewrite-aware PF warm-start caches, keyed on the canonical
        # rewritten graph's structural hash (exact: ids+ops+edges+dims;
        # near: dims-blind).  Per instance — all optimizer knobs are fixed.
        self._pf_cache: dict[str, PFResult] = {}
        self._near_cache: dict[str, PFResult] = {}

    # ----------------------------------------------- profile-guided plumbing
    def _resolve_calibration(self, calibration: Any | None) -> None:
        """Resolve ``calibration`` into ``self.calibrated`` and (in measured
        mode) swap the calibrated bank in.  See ``__init__``'s docstring for
        the resolution and device-class-mismatch rules."""
        from repro.core import autotune as autotune_mod

        dev = autotune_mod.device_class()
        model: Any | None = None
        if calibration is None:
            model = autotune_mod.default_calibration(
                store=self.artifact_store, autotune=self.autotune)
        elif isinstance(calibration, autotune_mod.CalibratedCostModel):
            model = calibration
        elif isinstance(calibration, autotune_mod.CalibrationTable):
            if calibration.device_class == dev:
                if (self.autotune
                        and "chain_split_bytes" not in calibration.knobs):
                    autotune_mod.autotune_knobs(calibration)
                model = autotune_mod.CalibratedCostModel.fit(calibration)
        else:
            raise TypeError(
                "calibration must be a CalibrationTable, a "
                f"CalibratedCostModel or None, got {type(calibration)!r}")
        if model is not None and model.device_class != dev:
            model = None
        if model is not None and self.max_age_days is not None:
            age = ((time.time() - model.created_at) / 86400.0
                   if model.created_at > 0.0 else float("inf"))
            if age > self.max_age_days:
                _warn_stale_calibration(model.table_digest or dev, age,
                                        self.max_age_days)
                model = None
        if model is None:
            # mismatched/unusable calibration: measured mode would price
            # this device with another device's numbers — refuse and fall
            # back to the analytic model instead.
            self.cost_source = "analytic"
            return
        self.calibrated = model
        if self.cost_source == "measured":
            self.bank = model
        if self.autotune and "bb" in model.knobs:
            from repro.kernels import linear_pipeline

            linear_pipeline.set_tuned_tiles(model.knobs["bb"],
                                            model.knobs["bn"])

    def _profile(self, rdfg: DFG) -> None:
        """PF-1 profiling for this instance's cost source: the analytic
        template sweep, then — in measured mode — rewrite each node's
        ``latency1`` from cycles to calibrated µs, so both Best-PF
        strategies (greedy reads ``bank.latency``; blackbox reads the
        ``latency1`` array against the bank's PF-curve coefficients)
        transparently optimize measured time."""
        profile_pf1(rdfg, backend=self.backend)
        if self.cost_source == "measured" and self.calibrated is not None:
            for node in rdfg.nodes.values():
                node.latency1 = self.calibrated.lat1_us(node.op, node.latency1)

    # ----------------------------------------------------------------- stages
    def _artifact_key(self, rdfg: DFG, calib: Any | None) -> str:
        """Store key for compiling ``rdfg`` under this instance's knobs —
        every knob the emitted plan or its numerics depend on participates."""
        from repro.core import artifacts

        knobs = dict(
            backend=self.backend, budget=repr(self.budget),
            strategy=self.strategy, metric=self.metric, order=self.order,
            pipelining=self.pipelining, use_pallas=self.use_pallas,
            precision=self.precision, per_channel=self.per_channel,
            chain_split_bytes=self.chain_split_bytes,
            exec_mode=self.exec_mode, cost_source=self.cost_source)
        if self.cost_source == "measured" and self.calibrated is not None:
            # measured-cost compiles may pick different PFs/chain cuts per
            # calibration — the table digest keeps their artifacts distinct
            knobs["calibration"] = self.calibrated.table_digest
        cal = ("none" if self.precision == "float32" else
               artifacts.calib_digest(calib, n_samples=self.calib_samples))
        return artifacts.program_key(rdfg, knobs, cal)

    def optimize(
        self, dfg: DFG, warm_assignment: dict[str, int] | None = None
    ) -> tuple[PFResult, PFGroups]:
        """Run the Best-PF search.  ``warm_assignment`` (node id → PF, from
        a near-hit in the warm-start cache) seeds the search at the prior
        solution — group start PFs are derived per node id, so the seeding
        is robust to group renumbering."""
        self._profile(dfg)
        groups = PFGroups.build(dfg)
        ctx = CostContext(dfg, groups, self.budget, backend=self.backend, bank=self.bank)
        warm: list[int] | None = None
        if warm_assignment is not None:
            warm = [max((int(warm_assignment.get(nid, 1)) for nid in mem),
                        default=1)
                    for mem in groups.members]
        if self.strategy == "greedy":
            res = greedy_best_pf(ctx, metric=self.metric,  # type: ignore[arg-type]
                                 warm_start=warm)
        elif self.strategy == "blackbox":
            res = blackbox_best_pf(ctx, warm_start=warm)
        elif self.strategy == "none":
            pfs = [1] * len(groups.members)
            res = PFResult(pfs, groups.assignment(pfs), ctx.critical(pfs)[1],
                           ctx.lut_total(pfs), ctx.dsp_total(pfs), 0.0, 0)
        else:
            raise ValueError(f"unknown strategy {self.strategy!r}")
        groups.apply(res.group_pfs)
        return res, groups

    def compile(
        self,
        dfg: DFG,
        assignment: dict[str, int] | None = None,
        *,
        calib: Any | None = None,
    ) -> CompiledProgram:
        """Full flow; pass ``assignment`` to impose external PFs (baselines).

        ``pipelining`` may be True (paper §IV-G: always fuse linear-time
        clusters), False, or ``"auto"`` (beyond-paper: fuse only when the
        simulated schedule improves — a cluster's all-inputs-ready start
        condition can *delay* branchy DFGs, see benchmarks/ablations.py).

        ``calib`` (fixed-point lanes only) is the calibration batch — the
        benchmark's training split for the classical models (a
        ``(N, n_features)`` array,
        or a dict of graph-input name → batch for multi-input DFGs).  Omitted,
        calibration falls back to synthetic standardized samples, matching
        the zero-mean/unit-variance preprocessing the datasets ship with.
        """
        # the front-end rewrite pipeline runs FIRST: profiler, optimizer,
        # scheduler and quantizer all consume the canonical rewritten graph,
        # so their outputs refer only to nodes that actually execute.
        rw = rewrite(dfg, precision=self.precision)
        rdfg = rw.dfg
        # persistent artifact store, consulted BEFORE the Best-PF search:
        # a hit restores the full program (assignment, schedule, quant plan,
        # megakernel stream) and rebinds callables — no search, no
        # calibration.  External assignments bypass the store (baseline
        # paths impose their own PFs).
        art_key: str | None = None
        if self.artifact_store is not None and assignment is None:
            art_key = self._artifact_key(rdfg, calib)
            loaded = self.artifact_store.load(art_key)
            if loaded is not None:
                if self.warm_start and loaded.pf_result is not None:
                    # prime the in-memory warm-start cache so sibling
                    # compiles of doped/edited variants near-hit off it
                    k = loaded.dfg.structural_hash()
                    self._pf_cache.setdefault(k, loaded.pf_result)
                    self._near_cache.setdefault(
                        loaded.dfg.structural_hash(include_dims=False),
                        loaded.pf_result)
                return loaded
        pf_result: PFResult | None = None
        pf_source = "external"
        if assignment is None:
            exact_key = near_key = None
            cached: PFResult | None = None
            if self.warm_start:
                exact_key = rdfg.structural_hash()
                near_key = rdfg.structural_hash(include_dims=False)
                cached = self._pf_cache.get(exact_key)
            if cached is not None:
                # exact hit: identical canonical structure (ids, ops,
                # edges, dims) → the Best-PF problem is identical; reuse
                # the prior PFResult without running the search.  The
                # profiler and groups still run (the scheduler needs the
                # tagged graph), but they are cheap closed-form sweeps.
                pf_source = "exact"
                pf_result = cached
                self._profile(rdfg)
                groups = PFGroups.build(rdfg)
                # defensive copy: prog.assignment is a public, mutable
                # field (the ablation baselines tweak it) — it must never
                # alias the cached PFResult's dict
                assignment = dict(pf_result.assignment)
                # tag the graph in place like groups.apply does on the
                # search paths — Node.pf is documentation/debug metadata
                # (the scheduler consumes the assignment dict), kept
                # consistent across all three compile paths
                for nid in rdfg.nodes:
                    rdfg.nodes[nid].pf = assignment[nid]
            else:
                near = (self._near_cache.get(near_key)
                        if self.warm_start else None)
                pf_source = "near" if near is not None else "cold"
                pf_result, groups = self.optimize(
                    rdfg,
                    warm_assignment=near.assignment if near else None)
                assignment = dict(pf_result.assignment)
                if self.warm_start:
                    self._pf_cache[exact_key] = pf_result
                    self._near_cache[near_key] = pf_result
        else:
            unknown = set(assignment) - set(dfg.nodes)
            if unknown:
                raise ValueError(
                    f"assignment names unknown nodes: {sorted(unknown)}")
            # external assignments (Vivado-baseline paths) may be partial:
            # unmentioned nodes run at PF=1, the template default.  Ids that
            # the rewrite merged resolve to their canonical node; ids it
            # removed (dead code, folded constants) impose nothing.
            eff: dict[str, int] = {}
            for nid, pf in assignment.items():
                rid = _resolve(rw.alias, nid)
                if rid in rdfg.nodes:
                    eff[rid] = max(eff.get(rid, 1), int(pf))
            assignment = {nid: eff.get(nid, 1) for nid in rdfg.nodes}
            self._profile(rdfg)
            groups = PFGroups.build(rdfg)
            for nid, pf in assignment.items():
                rdfg.nodes[nid].pf = pf
        # with the fused Pallas path active, price pipelined clusters through
        # the same chain decomposition (and cost-guided splits) the plan will
        # execute — simulated latency then matches the chain-split plan.
        sim_kw: dict[str, Any] = dict(order=self.order, groups=groups)
        if self.use_pallas:
            sim_kw.update(decompose_chains=True,
                          chain_split_bytes=self.chain_split_bytes)
        if self.cost_source == "measured" and self.calibrated is not None:
            # price schedule units in measured µs: direct nodes by the
            # per-op fit, fused sub-chains as one launch (PF-independent)
            sim_kw.update(node_cost=self.calibrated.node_us,
                          chain_cost=self.calibrated.chain_us)
        if self.pipelining == "auto":
            sched_p = simulate(rdfg, assignment, pipelining=True, **sim_kw)
            sched_n = simulate(rdfg, assignment, pipelining=False, **sim_kw)
            use_pipe = sched_p.total_cycles <= sched_n.total_cycles
            sched = sched_p if use_pipe else sched_n
        else:
            use_pipe = bool(self.pipelining)
            sched = simulate(rdfg, assignment, pipelining=use_pipe, **sim_kw)
        fused = pipeline_clusters(rdfg, groups, assignment) if use_pipe else []
        qplan = None
        if self.precision != "float32":
            from repro.core import quantize as quantize_mod

            qplan = quantize_mod.calibrate(
                rdfg, calib, n_samples=self.calib_samples,
                bits=quantize_mod.PRECISION_BITS[self.precision],
                per_channel=self.per_channel)
        # the back-end plan pipeline runs ONCE here; every execution lane
        # (per-sample, vmap, map) interprets the resulting static plan.
        plan = lower(rdfg, fused_clusters=fused, use_pallas=self.use_pallas,
                     precision=self.precision, qplan=qplan, rewritten=rw,
                     chain_split_bytes=self.chain_split_bytes)
        fn = build_callable(rdfg, plan=plan, mode=self.exec_mode)
        lut_true = sum(
            node_types.get(n.op).lut(n.dims, assignment[n.id])
            for n in rdfg.nodes.values()
        )
        dsp_true = sum(
            node_types.get(n.op).dsp(assignment[n.id])
            for n in rdfg.nodes.values()
        )
        prog = CompiledProgram(
            dfg=rdfg,
            fn=fn,
            assignment=assignment,
            pf_result=pf_result,
            schedule=sched,
            lut_true=lut_true,
            dsp_true=dsp_true,
            backend=self.backend,
            budget=self.budget,
            fused_clusters=fused,
            use_pallas=self.use_pallas,
            precision=self.precision,
            qplan=qplan,
            plan=plan,
            exec_mode=self.exec_mode,
            source_dfg=dfg,
            rewrite_result=rw,
            pf_source=pf_source,
            chain_split_bytes=self.chain_split_bytes,
            cost_source=self.cost_source,
        )
        if art_key is not None:
            # publish for the fleet: the next fresh process cold-starts here
            self.artifact_store.save(art_key, prog)
        return prog
