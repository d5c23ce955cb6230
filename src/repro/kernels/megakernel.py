"""Whole-program megakernel — one Pallas launch per sample for a whole plan.

MAFIA's claim is that the *whole program* — not per-op calls — compiles into
one tightly-scheduled accelerator program (paper §IV-G).  The per-chain
pipeline kernel (:mod:`repro.kernels.linear_pipeline`) removed per-node HBM
round-trips inside a cluster; this module removes the remaining inter-step
dispatch: the lowering pipeline's linearize pass compiles the executable
portion of an :class:`~repro.core.lowering.ExecutionPlan` down to a flat,
statically-scheduled instruction stream over a tiny VLIW-ish ISA, and
:func:`run_segment` executes the whole stream in **one** ``pallas_call``.

ISA (all operands static — shapes, shifts and constants are resolved at
compile time by ``_pass_linearize``):

    ==============  ==========================================================
    ``LOAD_VEC``    ``reg[dst] ← consts[ci]`` or ``reg[dst] ← inputs[ii]``
    ``LOAD_MAT``    start the async HBM→VMEM copy of ``matrices[mi]`` into
                    its dedicated VMEM buffer (DMA + semaphore)
    ``MATVEC``      wait the DMA, then ``reg[dst] ← W @ reg[src0]`` (+ static
                    bias) — dense gemv on the VMEM-resident tile
    ``SPMV``        same compute on a sparse (dense-with-zeros) operand —
                    kept as a distinct opcode mirroring the paper's separate
                    SpMV template (nnz metadata rides the operand)
    ``ELEMENTWISE`` one fused-pipeline stage (float or ``q_*`` vocabulary of
                    :mod:`repro.kernels.ref`) on ``reg[src0]`` (and
                    ``reg[src1]`` for ``*_arr`` forms)
    ``REQUANTIZE``  int lanes: requantizing shift of the int32 accumulator
                    after a MATVEC/SPMV (per-tensor shift, or per-row shifts
                    for per-channel scales)
    ``ARGMAX``      ``reg[dst] ← argmax(reg[src0])`` — the index as a width-1
                    value.  On the int lanes this runs directly on the int32
                    carrier: the dequantize scale is a positive power of two
                    (strictly monotone), so carrier argmax is bitwise the
                    dequantized argmax, ties included
    ``REDUCE``      ``reg[dst] ← sum/max/min(reg[src0])`` (width 1).  Int
                    lanes mirror the per-node dequantize → float reduce →
                    requantize fallback exactly (operand carries the
                    calibrated exponents)
    ``SQL2``        squared-L2 distances of ``reg[src0]`` to each column of a
                    matrix-pool operand (ProtoNN's RBF distance kernel) —
                    matvec-like: the points matrix rides the double-buffered
                    DMA schedule; int lanes dequantize → float → requantize
    ``DOT``         ``reg[dst] ← reg[src0] · reg[src1]`` (width 1); int lanes
                    dequantize both operands → float dot → requantize
    ``STORE``       ``outputs[oi] ← reg[src0]`` (cast to that output's dtype:
                    the narrow activation dtype on the int lanes, int32 for
                    integer-valued outputs such as ARGMAX indices)
    ==============  ==========================================================

The register file is a set of VMEM scratch rows, one ``(1, n)`` buffer per
slot with the value's *exact* feature length — exact shapes are what keeps
the float32 lane bitwise identical to per-node eval (padding a contraction
changes XLA's reduction grouping).  Slots are allocated by the linearize
pass with liveness-based reuse, so the file is far smaller than the value
count.  Matrix operands stay in HBM (``ANY`` memory space) and are DMA'd
into dedicated VMEM buffers; the instruction stream issues each ``LOAD_MAT``
one matvec *ahead* of its use, so at most two copies are in flight and the
k-th copy overlaps the (k−1)-th matvec — double-buffered tiles at
instruction granularity.

Int lanes ride the int32 carrier: inputs widen on ``LOAD_VEC``, every value
in the file is int32 (saturated to the activation width except between a
MATVEC and its REQUANTIZE), and ``STORE`` narrows — bit-identical to
per-node integer eval, like the fused chains.

The pure-jnp twin (:func:`repro.kernels.ref.run_segment_ref`) executes the
same stream without Pallas and is the parity oracle for interpret mode.

**Batch-grid lane** (:func:`run_segment_grid`): the serving path used to
``jax.vmap`` the whole launch over the bucket — one *logical* kernel program
per sample, each with its own HBM→VMEM matrix DMAs.  The grid lane instead
puts the batch axis into the Pallas grid: ``grid=(bucket,)``, per-sample
vector rows indexed by ``pl.program_id(0)`` through the BlockSpec index
maps, and every matrix DMA'd **once** on grid step 0 (TPU grid steps are
sequential, so the VMEM tile persists across samples) — one launch per
bucket per segment, which is exactly one launch for an island-free program.
"""

from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.ref import apply_stage, apply_stage_q

__all__ = ["Instr", "MegakernelSegment", "MegakernelProgram", "run_segment",
           "run_segment_grid"]

ISA_OPS = ("LOAD_VEC", "LOAD_MAT", "MATVEC", "SPMV", "ELEMENTWISE",
           "REQUANTIZE", "ARGMAX", "REDUCE", "SQL2", "DOT", "STORE")


@dataclasses.dataclass(frozen=True)
class Instr:
    """One megakernel instruction.  ``dst``/``src`` index register slots;
    ``operand`` is the op-specific static payload (see module docstring).
    Array payloads (constants, biases, vec operands, per-row shift tables)
    live in the segment's const *pool* and are referenced by index ``ci`` —
    Pallas kernels cannot close over arrays, so the pool rides as extra
    VMEM inputs of the launch:

    * ``LOAD_VEC`` — ``("const", ci)`` or ``("in", ii)``
    * ``LOAD_MAT`` — ``mi`` (matrix index)
    * ``MATVEC``/``SPMV`` — ``(mi, bias_ci)`` with ``bias_ci`` a pool index
      or None (int lanes: the int32 bias at the accumulator scale)
    * ``ELEMENTWISE`` — ``(stage, vec_cis)``: a stage tuple in the
      :mod:`repro.kernels.ref` vocabulary (``*_arr`` index remapped to 0 →
      ``src[1]``); q-stage ``vi`` operand indices address ``vec_cis``
      positionally, a float ``*_vec`` stage's operand is ``vec_cis[0]``
    * ``REQUANTIZE`` — ``("tensor", shift)`` or ``("rows", shifts_ci)``
    * ``ARGMAX`` — None (the int32 carrier / float32 slot holds the index)
    * ``REDUCE`` — ``(kind, e_in, e_out)`` with ``kind`` in ``sum/max/min``;
      exponents are None on the float lane (no dequantize/requantize)
    * ``SQL2`` — ``(mi, e_in, e_out)``: matrix index of the (d, m) points
      operand plus the int-lane exponents (None on the float lane)
    * ``DOT`` — ``(e_a, e_b, e_out)`` (all None on the float lane)
    * ``STORE`` — ``oi`` (output index)
    """

    op: str
    dst: int = -1
    src: tuple[int, ...] = ()
    operand: Any = None
    nid: str = ""                    # DFG node realized (debug / tracing)


@dataclasses.dataclass(frozen=True)
class MegakernelSegment:
    """A maximal run of ISA-encodable plan steps, compiled to one launch."""

    instrs: tuple[Instr, ...]
    slot_widths: tuple[int, ...]          # exact feature length per register
    consts: tuple[Any, ...]               # array payload pool (extra inputs)
    matrices: tuple[Any, ...]             # MATVEC/SPMV weight operands
    in_refs: tuple[str, ...]              # env refs consumed, LOAD_VEC order
    out_refs: tuple[str, ...]             # env refs produced, STORE order
    out_widths: tuple[int, ...]
    out_shapes: tuple[tuple[int, ...], ...]
    quantized: bool = False
    bits: int = 8
    members: tuple[str, ...] = ()         # DFG nodes realized by this segment
    # per-output dtype names ("float32"/"int8"/.../"int32"): integer-valued
    # outputs (ARGMAX indices) stay int32 while quantized activations narrow.
    # Empty = legacy uniform dtype (activation dtype / float32).
    out_dtypes: tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class MegakernelProgram:
    """The linearized plan: megakernel segments interleaved (plan order) with
    the indices of steps that have no ISA encoding — the interpreted islands
    of the hybrid fallback.  A fully encodable plan has one segment."""

    items: tuple[tuple[str, Any], ...]    # ("seg", segment) | ("step", idx)

    @property
    def segments(self) -> list[MegakernelSegment]:
        return [p for k, p in self.items if k == "seg"]

    @property
    def n_islands(self) -> int:
        return sum(1 for k, _ in self.items if k == "step")

    @property
    def n_instrs(self) -> int:
        return sum(len(s.instrs) for s in self.segments)

    def fingerprint(self) -> str:
        """Content digest of the linearized program: every instruction
        (opcode, slots, static operands) plus the const/matrix pools byte
        for byte.  Two programs with equal fingerprints execute the
        identical single-launch stream — this is what the artifact store
        validates on load: the re-linearized plan must reproduce exactly
        the stream that was serialized, else the artifact was produced by
        a different toolchain and must not silently serve."""
        import hashlib

        h = hashlib.sha256()
        for kind, payload in self.items:
            if kind == "step":
                h.update(repr(("step", payload)).encode())
                continue
            seg = payload
            h.update(repr(("seg", seg.slot_widths, seg.in_refs, seg.out_refs,
                           seg.out_widths, seg.out_shapes, seg.quantized,
                           seg.bits, seg.members, seg.out_dtypes)).encode())
            for ins in seg.instrs:
                h.update(repr((ins.op, ins.dst, ins.src, ins.operand,
                               ins.nid)).encode())
            for pool in (seg.consts, seg.matrices):
                for arr in pool:
                    a = np.asarray(arr)
                    h.update(repr((a.dtype.str, a.shape)).encode())
                    h.update(a.tobytes())
        return h.hexdigest()

    def summary(self) -> str:
        segs = self.segments
        return (f"MegakernelProgram({len(segs)} segments, "
                f"{self.n_instrs} instrs, "
                f"{sum(len(s.slot_widths) for s in segs)} slots, "
                f"{self.n_islands} interpreted islands)")


_VEC_STAGES = ("add_vec", "sub_vec", "hadamard_vec")

_REDUCE_F = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}


def _seg_out_dtypes(seg: MegakernelSegment) -> list:
    """Effective per-output dtypes: the segment's ``out_dtypes`` when set,
    else the legacy uniform dtype (narrow activation dtype / float32)."""
    if getattr(seg, "out_dtypes", ()):
        return [jnp.dtype(d) for d in seg.out_dtypes]
    if seg.quantized:
        from repro.core.quantize import int_dtype

        return [jnp.dtype(int_dtype(seg.bits))] * len(seg.out_refs)
    return [jnp.dtype(jnp.float32)] * len(seg.out_refs)


def _segment_kernel(*refs, seg: MegakernelSegment, skip_dma: bool = False,
                    grid: bool = False):
    """On-core interpreter: the instruction stream unrolls into straight-line
    code at trace time (every operand is static), exactly like MAFIA's
    generated pipeline — there is no runtime dispatch left to do.

    ``skip_dma`` (interpret mode only): the HBM→VMEM double-buffering is a
    hardware-motivated data movement, not arithmetic — on the CPU emulation
    the "DMA" lowers to real array copies that only add latency.  The
    emulation reads matrix operands in place instead; every arithmetic op
    is identical, so parity with the DMA path is bitwise.

    ``grid`` (batch-grid lane): the launch carries ``grid=(bucket,)`` and
    this body runs once per sample.  Matrix DMAs are predicated on
    ``pl.program_id(0) == 0`` — grid steps execute sequentially on TPU, so
    the VMEM tiles loaded on step 0 serve every later sample: one HBM→VMEM
    copy per matrix per *bucket* instead of per sample."""
    from repro.core.quantize import (dequantize, quantize_core,
                                     requantize_core, requantize_rows)

    n_in, n_const, n_mat = len(seg.in_refs), len(seg.consts), len(seg.matrices)
    n_out, n_slot = len(seg.out_refs), len(seg.slot_widths)
    ins = refs[:n_in]
    crefs = refs[n_in:n_in + n_const]
    mats = refs[n_in + n_const:n_in + n_const + n_mat]
    base = n_in + n_const + n_mat
    outs = refs[base:base + n_out]
    slots = refs[base + n_out:base + n_out + n_slot]
    mbufs = refs[base + n_out + n_slot:base + n_out + n_slot + n_mat]
    sems = refs[base + n_out + n_slot + n_mat:]
    carrier = jnp.int32 if seg.quantized else jnp.float32
    copies: dict[int, Any] = {}          # in-flight DMAs (trace-time only)

    def dma(fn):
        """Issue one DMA start/wait — predicated to grid step 0 on the
        batch-grid lane (the tile persists across sequential grid steps)."""
        if grid:
            pl.when(pl.program_id(0) == 0)(fn)
        else:
            fn()

    def dq(x, e):
        """Dequantize-or-passthrough, exactly the per-node dq fallback."""
        return x if e is None else dequantize(x, e)

    def q(x, e):
        """Quantize-or-passthrough on the int32 carrier (value-identical to
        the per-node ``quantize_jnp`` — STORE narrows on exit)."""
        return x if e is None else quantize_core(x, e, seg.bits)

    for instr in seg.instrs:
        op = instr.op
        if op == "LOAD_VEC":
            kind, idx = instr.operand
            src = ins[idx] if kind == "in" else crefs[idx]
            slots[instr.dst][...] = src[...].astype(carrier)
        elif op == "LOAD_MAT":
            if skip_dma:
                continue
            mi = instr.operand
            cp = pltpu.make_async_copy(mats[mi], mbufs[mi], sems[mi])
            dma(cp.start)
            copies[mi] = cp
        elif op in ("MATVEC", "SPMV"):
            mi, bias_ci = instr.operand
            if not skip_dma:
                dma(copies.pop(mi).wait)
            tile = mats[mi] if skip_dma else mbufs[mi]
            if seg.quantized:
                # the TPU's MXU takes no int32 (nor int16) operands, so the
                # int lanes multiply on the VPU and sum each row: int32
                # addition wraps the same in any order, hence bitwise.
                acc = jnp.sum(tile[...] * slots[instr.src[0]][...], axis=1)
            else:
                # exact shapes end to end: (m, n) @ (n,) is the same XLA dot
                # the per-node template issues, hence bitwise at float32 on
                # the CPU.
                acc = tile[...] @ slots[instr.src[0]][0, :]
            if bias_ci is not None:
                acc = jnp.add(acc, crefs[bias_ci][0, :])
            slots[instr.dst][...] = acc.reshape(1, -1)
        elif op == "REQUANTIZE":
            kind, sh = instr.operand
            x = slots[instr.src[0]][...]
            if kind == "rows":           # per-channel: one shift per row
                y = requantize_rows(x, crefs[sh][0, :], seg.bits)
            else:
                y = requantize_core(x, sh, seg.bits)
            slots[instr.dst][...] = y.astype(carrier)
        elif op == "ARGMAX":
            # directly on the carrier: the dequantize scale is a positive
            # power of two (strictly monotone), so the index — ties included
            # — matches argmax over the dequantized floats bitwise.  Spelled
            # as the first index holding the max: the TPU compiler lowers
            # jnp.argmax for float32 only and breaks ties unlike XLA, and
            # quantized scores often tie.
            x = slots[instr.src[0]][...]
            hit = x == jnp.max(x, axis=1, keepdims=True)
            idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            first = jnp.min(jnp.where(hit, idx, x.shape[1]), axis=1,
                            keepdims=True)
            slots[instr.dst][...] = first.astype(carrier)
        elif op == "REDUCE":
            kind, e_in, e_out = instr.operand
            x = dq(slots[instr.src[0]][0, :], e_in)
            r = _REDUCE_F[kind](x, axis=-1)
            slots[instr.dst][...] = q(r, e_out).reshape(1, 1).astype(carrier)
        elif op == "SQL2":
            mi, e_in, e_out = instr.operand
            if not skip_dma:
                dma(copies.pop(mi).wait)
            pts = (mats[mi] if skip_dma else mbufs[mi])[...]
            x = dq(slots[instr.src[0]][0, :], e_in)
            diff = pts - x[:, None]
            acc = jnp.sum(diff * diff, axis=0)
            slots[instr.dst][...] = q(acc, e_out).reshape(1, -1).astype(carrier)
        elif op == "DOT":
            e_a, e_b, e_out = instr.operand
            a = dq(slots[instr.src[0]][0, :], e_a)
            b = dq(slots[instr.src[1]][0, :], e_b)
            r = jnp.dot(a, b)
            slots[instr.dst][...] = q(r, e_out).reshape(1, 1).astype(carrier)
        elif op == "ELEMENTWISE":
            stage, vec_cis = instr.operand
            x = slots[instr.src[0]][...]
            extras = [slots[s][...] for s in instr.src[1:]]
            if seg.quantized:
                vv = [crefs[ci][...] for ci in vec_cis]
                y = apply_stage_q(x, stage, vv, extras, seg.bits)
            else:
                if stage[0] in _VEC_STAGES:
                    stage = (stage[0], crefs[vec_cis[0]][...])
                y = apply_stage(x, stage, extras)
            slots[instr.dst][...] = y
        elif op == "STORE":
            oref = outs[instr.operand]
            oref[...] = slots[instr.src[0]][...].astype(oref.dtype)
        else:
            raise ValueError(f"unknown megakernel op {op!r}")


_launch_cache: dict[tuple[int, bool, int | None], Any] = {}


def _launch_pools(seg: MegakernelSegment):
    """Host-side const/matrix pools.  They stay numpy: the launch builders
    may first run inside an outer trace (vmap/jit of the whole program), and
    any jnp op here would bake that trace's tracers into the cached
    closure."""
    np_carrier = np.int32 if seg.quantized else np.float32
    crows = [np.asarray(c, np_carrier).reshape(1, -1) for c in seg.consts]
    mats = [np.asarray(m) for m in seg.matrices]
    return crows, mats


def _launch_name(index: int, grid: bool) -> str:
    """The name of a segment's kernel and of its jitted launch, by lane
    and segment: what the device trace calls its operation."""
    return f"mafia_{'grid_' if grid else ''}seg{index}"


def _build_launch(seg: MegakernelSegment, interpret: bool, index: int):
    """Build (once per segment) the jitted single-launch callable.

    The instruction stream, const pool and matrix operands are static — the
    accelerator program is compiled exactly once and then invoked per
    sample, so the launch is traced once and cached; without this every
    eager call would re-trace the whole ``pallas_call``.  In interpret mode
    the DMA emulation buffers are dropped entirely (see ``skip_dma``)."""
    carrier = jnp.int32 if seg.quantized else jnp.float32
    out_dts = _seg_out_dtypes(seg)
    crows, mats = _launch_pools(seg)
    kernel = functools.partial(_segment_kernel, seg=seg, skip_dma=interpret)
    scratch = [pltpu.VMEM((1, w), carrier) for w in seg.slot_widths]
    if not interpret:
        scratch += [pltpu.VMEM(m.shape, m.dtype) for m in mats]
        scratch += [pltpu.SemaphoreType.DMA for _ in mats]
    call = pl.pallas_call(
        kernel,
        in_specs=(
            [pl.BlockSpec(memory_space=pltpu.VMEM)
             for _ in range(len(seg.in_refs) + len(crows))]
            + [pl.BlockSpec(memory_space=pl.ANY) for _ in mats]
        ),
        out_shape=[jax.ShapeDtypeStruct((1, w), dt)
                   for w, dt in zip(seg.out_widths, out_dts)],
        scratch_shapes=scratch,
        interpret=interpret,
        name=_launch_name(index, False),
    )

    def launch(*xs):
        outs = call(*xs, *crows, *mats)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        return [o[0] for o in outs]

    launch.__name__ = _launch_name(index, False)
    return jax.jit(launch)


def _build_launch_grid(seg: MegakernelSegment, interpret: bool, nb: int,
                       index: int):
    """Build the batch-grid launch: ``grid=(nb,)``, one kernel invocation
    per sample, matrices DMA'd into VMEM once on grid step 0 and shared by
    every later step (grid steps are sequential on the same core).  This is
    the one-launch-per-bucket lane: the whole bucket costs a single
    ``pallas_call`` per segment instead of ``nb`` vmapped launches."""
    carrier = jnp.int32 if seg.quantized else jnp.float32
    out_dts = _seg_out_dtypes(seg)
    crows, mats = _launch_pools(seg)
    kernel = functools.partial(_segment_kernel, seg=seg, skip_dma=interpret,
                               grid=True)
    scratch = [pltpu.VMEM((1, w), carrier) for w in seg.slot_widths]
    if not interpret:
        scratch += [pltpu.VMEM(m.shape, m.dtype) for m in mats]
        scratch += [pltpu.SemaphoreType.DMA for _ in mats]
    # every in_ref is materialized by exactly one LOAD_VEC ("in", ii), so the
    # slot it fills gives the input's (flattened) width.
    in_w = {ins.operand[1]: seg.slot_widths[ins.dst]
            for ins in seg.instrs
            if ins.op == "LOAD_VEC" and ins.operand[0] == "in"}
    call = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=(
            # per-sample input rows: grid step i sees row i.  Rows ride a
            # squeezed leading axis of an (nb, 1, w) array, so the block's
            # last two dims equal the array's, as the TPU compiler requires.
            [pl.BlockSpec((pl.Squeezed(), 1, in_w[ii]), lambda i: (i, 0, 0),
                          memory_space=pltpu.VMEM)
             for ii in range(len(seg.in_refs))]
            # const rows are shared: every step maps to row 0.
            + [pl.BlockSpec((1, c.shape[1]), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
               for c in crows]
            # matrices stay whole in ANY; the kernel DMAs them on step 0.
            + [pl.BlockSpec(memory_space=pl.ANY)
               for _ in mats]
        ),
        out_shape=[jax.ShapeDtypeStruct((nb, 1, w), dt)
                   for w, dt in zip(seg.out_widths, out_dts)],
        out_specs=[pl.BlockSpec((pl.Squeezed(), 1, w), lambda i: (i, 0, 0),
                                memory_space=pltpu.VMEM)
                   for w in seg.out_widths],
        scratch_shapes=scratch,
        interpret=interpret,
        name=_launch_name(index, True),
    )

    def launch(*xs):
        outs = call(*(x.reshape(nb, 1, -1) for x in xs), *crows, *mats)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        return [o.reshape(nb, -1) for o in outs]

    launch.__name__ = _launch_name(index, True)
    return jax.jit(launch)


def _cached_launch(seg: MegakernelSegment, interpret: bool,
                   nb: int | None = None, index: int = 0):
    key = (id(seg), interpret, nb)
    fn = _launch_cache.get(key)
    if fn is None:
        fn = (_build_launch(seg, interpret, index) if nb is None
              else _build_launch_grid(seg, interpret, nb, index))
        _launch_cache[key] = fn
        sid = id(seg)
        weakref.finalize(
            seg,
            lambda: [_launch_cache.pop(k, None)
                     for k in list(_launch_cache) if k[0] == sid],
        )
    return fn


def run_segment(
    seg: MegakernelSegment,
    inputs: Sequence[jax.Array],
    *,
    interpret: bool | None = None,
    index: int = 0,
) -> list[jax.Array]:
    """Execute one segment in a single ``pallas_call``.

    ``inputs`` are the env values of ``seg.in_refs`` in order (any shape —
    flattened to the feature axis here); returns one flat value per
    ``seg.out_refs`` (the caller reshapes via ``seg.out_shapes``).  Int-lane
    inputs may be narrow or int32; outputs are the narrow activation dtype.
    The launch is traced once per segment and cached (the stream is static),
    so repeated eager calls pay only one XLA dispatch.  ``index``, the
    segment's place in its program, names the launch (``mafia_seg<index>``).
    """
    interpret = resolve_interpret(interpret)
    xs = [jnp.asarray(x).reshape(1, -1) for x in inputs]
    return _cached_launch(seg, interpret, index=index)(*xs)


def run_segment_grid(
    seg: MegakernelSegment,
    inputs: Sequence[jax.Array],
    *,
    interpret: bool | None = None,
    index: int = 0,
) -> list[jax.Array]:
    """Execute one segment for a whole bucket in a single ``pallas_call``.

    ``inputs`` are batched env values of ``seg.in_refs`` (leading batch
    axis, any trailing shape — flattened to ``(nb, width)`` here).  The
    batch axis rides the Pallas grid: ``grid=(nb,)`` with per-sample rows
    selected by ``program_id``, and matrix DMAs issued only on grid step 0
    so every matrix crosses HBM→VMEM once per bucket.  Returns one
    ``(nb, width)`` value per ``seg.out_refs``; bitwise identical to
    ``jax.vmap(run_segment)`` on every lane.  The launch is named
    ``mafia_grid_seg<index>``.
    """
    interpret = resolve_interpret(interpret)
    nb = int(jnp.asarray(inputs[0]).shape[0])
    xs = [jnp.asarray(x).reshape(nb, -1) for x in inputs]
    return _cached_launch(seg, interpret, nb, index)(*xs)
