"""Plan interpreter — the "Verilog generator" stage of the paper, retargeted.

On the FPGA, MAFIA emits Verilog from the template library.  Here the same
role is split in two: :mod:`repro.core.lowering` runs the compile-time pass
pipeline once and emits a static :class:`~repro.core.lowering.ExecutionPlan`,
and :func:`build_callable` is a thin interpreter over that plan — it walks
the pre-ordered steps, applies each pre-bound template function, and hands
pre-lowered stage chains to the fused Pallas pipeline kernel
(:mod:`repro.kernels.linear_pipeline`, float or fixed-point variant): one
HBM→VMEM→HBM round-trip for a whole §IV-G cluster instead of one per node.

All analysis (atom ordering, cluster chain decomposition, quantization
binding) happens at compile time in the lowering pipeline; nothing here
re-derives graph structure, which is what keeps the per-sample, vmap and map
lanes in agreement — they interpret the same plan.

:func:`execute` stays the *unplanned* numeric oracle: a direct per-node walk
with the float templates, no lowering, no fusion, no jit.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import node_types
from repro.core.dfg import DFG
from repro.core.lowering import (
    ChainStep,
    ExecutionPlan,
    NodeStep,
    _resolve,
    lower,
)

__all__ = ["build_callable", "execute"]


def build_callable(
    dfg: DFG,
    *,
    fused_clusters: list[list[str]] | None = None,
    use_pallas: bool = False,
    jit: bool = True,
    batch: bool = False,
    precision: str = "float32",
    qplan: Any | None = None,
    plan: ExecutionPlan | None = None,
    mode: str = "interpret",
) -> Callable[..., dict[str, Any]]:
    """Compile the DFG into a function ``f(**graph_inputs) -> {output: array}``.

    Without a pre-built ``plan`` the lowering pipeline runs here (direct
    callers, tests); :meth:`repro.core.compiler.MafiaCompiler.compile` lowers
    once and passes the plan through, so the per-sample, vmap and map lanes
    all interpret the same static plan.

    ``fused_clusters`` (from the scheduler) lists linear-time clusters to
    execute as a fused unit.  With ``use_pallas`` the plan carries pre-lowered
    stage chains executed through the Pallas linear-pipeline kernel
    (interpret mode on CPU); otherwise cluster members run per-node (which
    XLA fuses into one loop anyway — same semantics, same oracle).

    With ``batch`` every graph input (and output) carries a leading batch
    axis: per-node templates are vmapped over it, and fused chains hand the
    whole batch to the pipeline kernel directly — its grid already tiles the
    batch axis, so one kernel launch serves the entire bucket (the serving
    path of :mod:`repro.serve.classical_engine`).

    ``precision="int8"`` / ``"int16"`` runs the DFG in SeeDot-style fixed
    point (the paper's workload class): float inputs are quantized at the
    ``qplan`` scales on entry, ops with an ``OpSpec.jax_fn_q`` template run
    narrow→int32-accumulate→narrow, the rest run dequantize→float→requantize,
    fused chains execute through the fixed-point pipeline kernel (bitwise
    identical to per-node eval), and float outputs are dequantized back on
    exit (integer outputs such as argmax pass through).  Requires a
    :class:`repro.core.quantize.QuantPlan` from
    :func:`repro.core.quantize.calibrate`.  The interface stays float in /
    float out, so callers (and the serving engine) are precision-blind.

    ``mode`` selects the execution strategy over the plan:

    * ``"interpret"`` (default) — walk the step list: one template call or
      pipeline-kernel launch per step.  This is the oracle every other lane
      is verified against.
    * ``"megakernel"`` — run the linearize pass's
      :class:`~repro.kernels.megakernel.MegakernelProgram`: whole runs of
      encodable steps execute as a single ``pallas_call`` over a static
      instruction stream (one launch for a fully-encodable plan); steps
      without an ISA encoding (matmul, outer, 2-D reductions, ...) stay
      interpreted as plan-ordered islands.  Bitwise identical to
      ``"interpret"`` at float32 and lane-bitwise at int8/int16.
    * ``"megakernel_grid"`` — same instruction stream, but a batched lane
      puts the bucket on the Pallas grid (``grid=(bucket,)``) instead of
      vmapping the launch: matrices cross HBM→VMEM once per bucket and the
      whole bucket costs one launch per segment.  Bitwise identical to the
      vmapped ``"megakernel"`` lane; identical to it per-sample.
    """
    if plan is None:
        plan = lower(dfg, fused_clusters=fused_clusters, use_pallas=use_pallas,
                     precision=precision, qplan=qplan)
    return _interpret(plan, jit=jit, batch=batch, mode=mode)


def _interpret(
    plan: ExecutionPlan, *, jit: bool = True, batch: bool = False,
    mode: str = "interpret",
) -> Callable[..., dict[str, Any]]:
    """Thin interpreter over a static plan (per-sample or batched lane)."""
    if mode not in ("interpret", "megakernel", "megakernel_grid"):
        raise ValueError(f"unknown execution mode {mode!r}")
    mk = mode in ("megakernel", "megakernel_grid")
    grid = mode == "megakernel_grid" and batch
    quantized = plan.precision != "float32"
    if quantized:
        from repro.core import quantize as quantize_mod
    if any(isinstance(s, ChainStep) for s in plan.steps):
        from repro.kernels.linear_pipeline import (
            fused_linear_chain,
            fused_linear_chain_q,
        )
    if mk:
        if plan.megakernel is None:
            raise ValueError(
                "plan has no megakernel program — it predates the linearize "
                "pass; re-lower the DFG (lower()/MafiaCompiler.compile())")
        from repro.kernels.megakernel import run_segment, run_segment_grid
    allowed = set(plan.dfg.graph_inputs)
    bits = plan.bits or 8
    # output name -> env ref, resolved through the rewrite alias once here;
    # plan.verify() already guaranteed every ref is produced (a dangling
    # alias raises a ValueError at compile time, not a KeyError here).
    out_refs = {out: _resolve(plan.alias, out) for out in plan.outputs}

    def exec_step(step: NodeStep | ChainStep, env: dict[str, Any],
                  bdim: int | None) -> None:
        """Execute one plan step into ``env`` (shared by the interpret walk
        and the megakernel lane's interpreted islands)."""
        if isinstance(step, NodeStep):
            args = [env[r] for r in step.inputs]
            if batch and not step.inputs:
                # zero-input node (const): one value, broadcast over the
                # bucket so downstream vmapped templates see a batch axis.
                val = step.fn()
                env[step.nid] = (val if bdim is None
                                 else jnp.broadcast_to(val, (bdim,) + val.shape))
            else:
                env[step.nid] = (jax.vmap(step.fn)(*args) if batch
                                 else step.fn(*args))
        else:  # pre-lowered fused chain: one pipeline kernel launch.
            x = jnp.asarray(env[step.stream])
            extras = [jnp.asarray(env[r]) for r in step.extras]
            if step.quantized:
                val = fused_linear_chain_q(
                    x, step.stages,
                    [jnp.asarray(v) for v in step.vecs], extras, bits=bits)
            else:
                val = fused_linear_chain(x, step.stages, extras)
            # intermediates were proven unconsumed at lowering time; only
            # the terminal is materialized (that is the point of fusion).
            for nid in step.dead:
                env[nid] = None
            env[step.terminal] = val

    def exec_segment(seg: Any, index: int, env: dict[str, Any],
                     bdim: int | None) -> None:
        """Run segment ``index`` of the program (single launch) and publish
        its stored refs.  The batched lane vmaps the whole launch over the
        bucket."""
        args = [env[r] for r in seg.in_refs]
        if batch and args:
            if grid:
                # batch-grid lane: the bucket rides the Pallas grid — one
                # launch per segment per bucket, matrices DMA'd once.
                outs = run_segment_grid(seg, args, index=index)
            else:
                outs = jax.vmap(lambda *a: tuple(
                    run_segment(seg, a, index=index)))(*args)
            for i, r in enumerate(seg.out_refs):
                env[r] = outs[i].reshape((bdim,) + seg.out_shapes[i])
        else:
            outs = run_segment(seg, args, index=index)
            for i, r in enumerate(seg.out_refs):
                val = outs[i].reshape(seg.out_shapes[i])
                if batch and bdim is not None:
                    # zero-input segment under a batched lane: one value,
                    # broadcast like a zero-input node step.
                    val = jnp.broadcast_to(val, (bdim,) + val.shape)
                env[r] = val

    def run(**inputs: Any) -> dict[str, Any]:
        unknown = set(inputs) - allowed
        if unknown:
            raise TypeError(f"unknown graph inputs: {sorted(unknown)}")
        missing = allowed - set(inputs)
        if missing:
            raise TypeError(f"missing graph inputs: {sorted(missing)}")
        if quantized:
            env: dict[str, Any] = {
                k: quantize_mod.quantize_jnp(jnp.asarray(v, jnp.float32),
                                             plan.input_exps[k], bits)
                for k, v in inputs.items()
            }
        else:
            env = {k: jnp.asarray(v) for k, v in inputs.items()}
        bdim = next((v.shape[0] for v in env.values()), None) if batch else None

        if mk:
            index = 0
            for kind, payload in plan.megakernel.items:
                if kind == "seg":
                    exec_segment(payload, index, env, bdim)
                    index += 1
                else:   # interpreted island: a step with no ISA encoding
                    exec_step(plan.steps[payload], env, bdim)
        else:
            for step in plan.steps:
                exec_step(step, env, bdim)

        if quantized:
            return {
                out: env[ref] if plan.output_exps[out] is None
                else quantize_mod.dequantize(env[ref], plan.output_exps[out])
                for out, ref in out_refs.items()
            }
        return {out: env[ref] for out, ref in out_refs.items()}

    return jax.jit(run) if jit else run


def execute(dfg: DFG, **inputs: Any) -> dict[str, Any]:
    """One-shot reference execution — the *unplanned* numeric oracle: a
    direct per-node walk with the float templates (no lowering, no fusion,
    no jit) that plan-based execution is asserted against."""
    dfg.validate()
    missing = set(dfg.graph_inputs) - set(inputs)
    if missing:
        raise TypeError(f"missing graph inputs: {sorted(missing)}")
    env: dict[str, Any] = {k: jnp.asarray(v) for k, v in inputs.items()}
    for nid in dfg.topo_order():
        node = dfg.nodes[nid]
        spec = node_types.get(node.op)
        env[nid] = spec.jax_fn([env[s] for s in node.inputs], node.params,
                               node.dims)
    return {out: env[out] for out in dfg.outputs}
