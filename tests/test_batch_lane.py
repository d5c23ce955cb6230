"""The batch lane's host path: pad on the host, one dispatch, one fetch.

Host (numpy) inputs are padded in numpy and handed to the jitted forward;
every output comes back in one fetch and is sliced on the host.  The
reference below is the device path it replaced: ``jnp.pad`` on the device,
the same ``bp.fn``, slices on the device.  The work and the padding are the
same, so the answers must be bitwise equal.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.serve.async_engine import AsyncServeEngine
from repro.serve.classical_engine import get_program

from test_async_engine import _requests

MAX_BATCH = 8
# a float32 and an int8 Table-I program
PROGRAMS = {"protonn-f32": ("protonn/usps-b", "float32"),
            "bonsai-int8": ("bonsai/usps-b", "int8")}


@functools.cache
def _lane(program: str, mode: str, exec_mode: str = "interpret"):
    """One ``BatchedProgram`` per lane, shared so each bucket compiles once."""
    bench, precision = PROGRAMS[program]
    prog = get_program(bench, precision=precision)
    return prog.batch(MAX_BATCH, mode=mode, exec_mode=exec_mode)


def _on_device(bp, X: np.ndarray) -> dict:
    """The lane as it ran on the device: device copy, ``jnp.pad``, forward,
    device slices, ``jnp.concatenate`` over chunks."""
    x = jnp.asarray(X)
    parts = []
    for start in range(0, len(X), bp.max_batch):
        chunk = x[start:start + bp.max_batch]
        nb = chunk.shape[0]
        pad = ((0, bp.bucket(nb) - nb),) + ((0, 0),) * (chunk.ndim - 1)
        out = bp.fn({"x": jnp.pad(chunk, pad)})
        parts.append({k: v[:nb] for k, v in out.items()})
    return {k: np.asarray(jnp.concatenate([p[k] for p in parts]))
            for k in parts[0]}


def _assert_bitwise(out: dict, ref: dict) -> None:
    assert set(out) == set(ref)
    for k, v in ref.items():
        assert out[k].dtype == v.dtype and out[k].shape == v.shape, k
        assert np.array_equal(out[k], v), k


@pytest.mark.parametrize("n", [*range(1, MAX_BATCH + 1), 2 * MAX_BATCH + 3])
@pytest.mark.parametrize("mode", ["vmap", "map"])
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_host_lane_bitwise_equals_device_pad_and_slice(program, mode, n):
    bp = _lane(program, mode)
    X = _requests(n)
    _assert_bitwise(bp(x=X), _on_device(bp, X))


@pytest.mark.parametrize("exec_mode", ["megakernel", "megakernel_grid"])
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_host_lane_bitwise_in_megakernel_lanes(program, exec_mode):
    bp = _lane(program, "vmap", exec_mode)
    X = _requests(MAX_BATCH + 3)           # one full bucket, one ragged
    _assert_bitwise(bp(x=X), _on_device(bp, X))


def test_lane_returns_numpy_for_host_and_device_inputs():
    """numpy rows come back as numpy; a ``jax.Array`` is still taken, and
    goes the same host path: padded in numpy, answers in numpy."""
    bp = _lane("bonsai-int8", "vmap")
    X = _requests(MAX_BATCH + 3)
    host = bp(x=X)
    assert all(type(v) is np.ndarray for v in host.values())
    pads = bp.host_pad_rows
    dev = bp(x=jnp.asarray(X))
    assert all(type(v) is np.ndarray for v in dev.values())
    assert bp.host_pad_rows - pads == 1       # 3 rows in a bucket of 4
    _assert_bitwise(dev, host)


@pytest.mark.parametrize("n, pad", [(1, 0), (3, 1), (4, 0), (5, 3),
                                    (MAX_BATCH, 0), (2 * MAX_BATCH + 3, 1)])
def test_host_pad_rows_counts_padding(n, pad):
    """0 for full buckets, bucket − rows for the ragged one."""
    bp = _lane("protonn-f32", "vmap")
    before = bp.host_pad_rows
    bp(x=_requests(n))
    assert bp.host_pad_rows - before == pad


def test_engine_shows_host_pad_rows():
    eng = AsyncServeEngine()
    eng.register_model("m", get_program("bonsai/usps-b"), max_batch=8,
                       batch_wait_ms=1e6)
    X = _requests(11)
    for x in X:
        eng.submit("m", x)
    eng.flush("m")                          # a full bucket: no padding
    assert eng.stats()["models"]["m"]["host_pad_rows"] == 0
    eng.flush("m")                          # 3 rows in a bucket of 4
    assert eng.stats()["models"]["m"]["host_pad_rows"] == 1


def test_serving_sizes_adds_one_program_per_bucket():
    """Sizes 1..16 through the engine build the forward of each bucket
    (1, 2, 4, 8, 16) and nothing else: no pad or slice per size."""
    compile_cache.count_compiles()
    eng = AsyncServeEngine()
    # registering builds a new jitted forward: none of its buckets is built
    eng.register_model("m", get_program("bonsai/usps-b"), max_batch=16,
                       batch_wait_ms=1e6)
    X = _requests(16)
    before = compile_cache.counts()["programs"]
    for n in range(1, 17):
        for x in X[:n]:
            eng.submit("m", x)
        assert len(eng.flush("m", n)) == n
    assert compile_cache.counts()["programs"] - before == 5
    assert eng._models["m"].batched.stats == {1: 1, 2: 1, 4: 2, 8: 4,
                                               16: 8}
