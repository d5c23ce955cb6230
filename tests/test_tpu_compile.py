"""Main-path Pallas kernels compile for a described TPU v5e.

Interpret mode never checks what the TPU compiler refuses: block shapes
that are not (8, 128)-aligned or equal to the array's, operand dtypes the
MXU does not take, ops Mosaic cannot lower.  These tests compile each
kernel of the serving path for one chip of a described ``v5e:2x2``
topology, with ``interpret=False`` passed explicitly (the backend here is
the CPU, which would otherwise pick interpret mode), and check that the
compiled program holds a ``tpu_custom_call`` — the kernel itself, not an
emulation of it.  Nothing runs: results are the chip smoke test's job.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker given this file
loads the TPU compiler.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import mlperf_tiny
from repro.configs.classical import build, training_split
from repro.core.compiler import MafiaCompiler
from repro.core.lowering import ChainStep
from repro.core.quantize import int_dtype
from repro.kernels.linear_pipeline import (fused_linear_chain,
                                           fused_linear_chain_q)
from repro.kernels.megakernel import run_segment, run_segment_grid

PROGRAMS = ["bonsai/usps-b", "protonn/usps-b", "kws_mlp"]
PRECISIONS = ["float32", "int8"]
BUCKET = 64          # the serving engines' default max_batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@functools.lru_cache(maxsize=None)
def _program(name: str, precision: str):
    """Compile one served program (on the host; no TPU involved)."""
    if name in mlperf_tiny.WORKLOADS:
        dfg = mlperf_tiny.build(name)
        calib = mlperf_tiny.sample_inputs(name, 256)
    else:
        dfg, _, _ = build(name, seed=0)
        calib = training_split(name)[0][:256]
    return MafiaCompiler(
        use_pallas=True, precision=precision, exec_mode="megakernel",
    ).compile(dfg, calib=None if precision == "float32" else calib)


def _in_widths(seg) -> list[int]:
    w = {i.operand[1]: seg.slot_widths[i.dst] for i in seg.instrs
         if i.op == "LOAD_VEC" and i.operand[0] == "in"}
    return [w[i] for i in range(len(seg.in_refs))]


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("lane", ["per-sample", "vmap", "grid"])
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("name", PROGRAMS)
def test_megakernel_launch_compiles(name, precision, lane, one_chip):
    """Each megakernel segment compiles to one kernel on every lane: the
    per-sample launch, its vmap over a bucket (one launch, not one per
    sample) and the batch-grid launch."""
    prog = _program(name, precision)
    segs = prog.plan.megakernel.segments
    assert segs
    dt = jnp.dtype(int_dtype(prog.plan.bits)) if prog.plan.bits else jnp.float32
    for seg in segs:
        widths = _in_widths(seg)
        if lane == "per-sample":
            args = [jax.ShapeDtypeStruct((w,), dt, sharding=one_chip)
                    for w in widths]
            fn = lambda *a, seg=seg: run_segment(seg, a, interpret=False)
        else:
            args = [jax.ShapeDtypeStruct((BUCKET, w), dt, sharding=one_chip)
                    for w in widths]
            if lane == "vmap":
                fn = jax.vmap(lambda *a, seg=seg: tuple(
                    run_segment(seg, a, interpret=False)))
            else:
                fn = lambda *a, seg=seg: run_segment_grid(
                    seg, a, interpret=False)
        assert _compiled_text(fn, *args).count("tpu_custom_call") == 1


def _chain_width(plan, step: ChainStep) -> int:
    ref = step.stream
    gi = plan.dfg.graph_inputs
    shape = gi[ref].shape if ref in gi else plan.dfg.out_shape(ref)
    return int(np.prod(shape))


@pytest.mark.parametrize("precision", PRECISIONS)
def test_fused_linear_chain_compiles(precision, one_chip):
    """The Table-I fused stage chains (bonsai/usps-b's hadamard/tanh
    chains) compile as one kernel over a serving bucket, float and int."""
    dfg, _, _ = build("bonsai/usps-b", seed=0)
    calib = training_split("bonsai/usps-b")[0][:256]
    prog = MafiaCompiler(use_pallas=True, precision=precision).compile(
        dfg, calib=None if precision == "float32" else calib)
    chains = [s for s in prog.plan.steps if isinstance(s, ChainStep)]
    assert chains
    for step in chains:
        n = _chain_width(prog.plan, step)
        if step.quantized:
            dt = jnp.dtype(int_dtype(prog.plan.bits))
            vecs = [jnp.asarray(v) for v in step.vecs]
            fn = lambda x, *ex, step=step, vecs=vecs: fused_linear_chain_q(
                x, step.stages, vecs, list(ex), bits=prog.plan.bits,
                interpret=False)
        else:
            dt = jnp.float32
            fn = lambda x, *ex, step=step: fused_linear_chain(
                x, step.stages, list(ex), interpret=False)
        x = jax.ShapeDtypeStruct((BUCKET, n), dt, sharding=one_chip)
        extras = [x] * len(step.extras)
        assert _compiled_text(fn, x, *extras).count("tpu_custom_call") == 1


@pytest.mark.parametrize("name", ["bonsai/usps-b", "protonn/usps-b"])
def test_served_forward_names_its_programs(name, one_chip, monkeypatch):
    """The served forward compiles to an XLA program named after it, and
    its kernel's operation after the lane and segment: the names a device
    trace shows.  The lane picks the compiled kernel as it would on the
    chip."""
    from repro.core.compiler import BatchedProgram

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prog = _program(name, "float32")
    bp = BatchedProgram.build(prog, max_batch=BUCKET,
                              exec_mode="megakernel_grid")
    (inp,) = prog.dfg.graph_inputs
    arg = {inp: jax.ShapeDtypeStruct(
        (BUCKET,) + tuple(prog.dfg.graph_inputs[inp].shape), jnp.float32,
        sharding=one_chip)}
    text = bp.fn.lower(arg).compile().as_text()
    assert text.startswith("HloModule jit_mafia_forward_vmap")
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert call.lstrip().startswith("%mafia_grid_seg0")


def test_dropless_expert_layer_compiles_at_published_widths(one_chip,
                                                           monkeypatch):
    """DeepSeek-V2-Lite's expert layer as the decode step runs it: 128
    tokens, 64 experts of 2,048 → 1,408, top-6, two shared experts, bf16.
    The grouped matmuls stay grouped (the ``grouped_matmul`` kernel), not a
    dense matmul over every expert."""
    from repro.models.layers import Initializer
    from repro.models.moe import init_moe, moe_dropless

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = jax.eval_shape(lambda: init_moe(
        Initializer(jax.random.key(0)), 2048, 1408, 64, n_shared=2,
        dtype=jnp.bfloat16))
    place = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = jax.tree.map(lambda a: place(a.shape, a.dtype), params)
    x = place((128, 1, 2048), jnp.bfloat16)
    fn = jax.jit(functools.partial(moe_dropless, k=6))
    text = fn.lower(params, x).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3
    assert all("grouped_matmul" in ln for ln in calls)


@pytest.mark.parametrize("phase,temp_mb", [("decode", 64), ("prefill", 192)])
def test_stacked_experts_are_read_in_place(phase, temp_mb, one_chip,
                                           monkeypatch):
    """Two MoE layers of DeepSeek-V2-Lite at published widths (a small
    vocabulary), as the serving programs scan them: the decode step of 128
    slots and a 2,048-token prefill.  No layer's experts are copied out of
    the stack (no instruction makes a ``bf16[64,2048,1408]`` or
    ``bf16[64,1408,2048]``), so the temporary memory is the activations'
    alone: decode ~1 MB, prefill ~157 MB, mostly the float32 gate and up
    rows (2 × 69 MB); with the copies they read 388 and 596 MB."""
    import dataclasses
    import re

    from repro.configs import get_arch
    from repro.models import transformer as T

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite").model,
                              n_layers=2, n_dense_layers=0, vocab_size=1024)
    place = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = jax.tree.map(lambda a: place(a.shape, a.dtype),
                          T.abstract_params(cfg))
    if phase == "decode":
        caches = jax.tree.map(lambda a: place(a.shape, a.dtype),
                              T.init_cache(cfg, 128, 256, abstract=True))
        slots = place((128,), jnp.int32)
        fn = jax.jit(lambda p, t, c, pos: T.forward_decode(p, cfg, t, c, pos),
                     donate_argnums=(2,))
        compiled = fn.lower(params, slots, caches, slots).compile()
    else:
        fn = jax.jit(lambda p, t: T.forward_full(
            p, cfg, t, head_positions=jnp.array([2047])))
        compiled = fn.lower(params, place((1, 2048), jnp.int32)).compile()
    text = compiled.as_text()
    assert not re.search(r"= bf16\[64,(2048,1408|1408,2048)\]", text)
    assert text.count("tpu_custom_call") == 3
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mb << 20
