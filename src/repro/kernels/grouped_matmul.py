"""Grouped matmul over one layer of a stacked expert weight — Pallas TPU.

The dropless expert layer sorts its (token, choice) rows by expert and
multiplies each expert's contiguous rows by that expert's matrix.  The
model keeps every MoE layer's experts in one stack ``(L, E, K, N)`` that
the layer scan walks; a custom call takes no fused slice, so handing it
``w[layer]`` makes XLA copy the layer's experts out of the stack first
(369 MB per matrix at DeepSeek-V2-Lite's widths).  This kernel takes the
whole stack and the layer index instead, and its weight ``index_map``
reads ``(layer, expert)`` tiles straight from the stack in HBM.

Schedule (megablox's, cut to what the serving path needs): the rows are
padded to whole ``tm``-row tiles; a *visit* is one (expert, row tile) pair
whose rows meet, listed expert by expert, so a row tile's visits are
consecutive and its output block stays in VMEM until its last one.  Each
visit is one ``(tm, K) @ (K, N)`` product, stored under the mask of the
expert's rows.  An expert's whole matrix is one block (5.8 MB at
DeepSeek-V2-Lite's widths, double-buffered in VMEM), so consecutive visits
of one expert keep the same weight block and Pallas does not fetch it
again: each expert's weights cross HBM once per call, and an expert no row
chose is never read.  The grid is sized for the most visits any routing
can make; the steps past the real count repeat the last visit's blocks
(no DMA) and compute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["grouped_matmul"]

_TM = 128                # rows per tile: a whole MXU pass
_VMEM_LIMIT = 64 << 20   # two weight blocks, two row and two output tiles


def _visits(group_sizes: jax.Array, tm: int, n_visits_max: int):
    """Per grid step: the expert and the row tile it visits, clamped to the
    last real visit past the real count; and that count."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    n_visits = tiles.sum()
    gid = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                     total_repeat_length=n_visits_max)
    tile = first[gid] + jnp.arange(n_visits_max) - (jnp.cumsum(tiles) -
                                                    tiles)[gid]
    step = jnp.minimum(jnp.arange(n_visits_max), n_visits - 1)
    return (gid[step].astype(jnp.int32), tile[step].astype(jnp.int32),
            starts.astype(jnp.int32), ends.astype(jnp.int32),
            n_visits.astype(jnp.int32).reshape(1))


def _kernel(layer_ref, gid_ref, tile_ref, starts_ref, ends_ref, nv_ref,
            lhs_ref, rhs_ref, out_ref, *, tm: int):
    v = pl.program_id(0)

    @pl.when(v < nv_ref[0])
    def _visit():
        g = gid_ref[v]
        lhs = lhs_ref[...]
        y = jax.lax.dot_general(lhs, rhs_ref[...].astype(lhs.dtype),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                          y.shape, 0)
        mine = (row >= starts_ref[g]) & (row < ends_ref[g])
        out_ref[...] = jnp.where(mine, y, out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(lhs, rhs, layer, group_sizes, *, interpret):
    M, K = lhs.shape
    N = rhs.shape[-1]
    G = rhs.shape[1]
    tm = _TM
    m_pad = -(-M // tm) * tm
    if m_pad != M:
        lhs = jnp.pad(lhs, ((0, m_pad - M), (0, 0)))
    # each expert that received rows adds at most one tile it shares
    n_visits_max = m_pad // tm + min(G, M) - 1
    meta = _visits(group_sizes.astype(jnp.int32), tm, n_visits_max)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row_map(v, layer, gid, tile, *_):
        return tile[v], 0

    def rhs_map(v, layer, gid, *_):
        return layer[0], gid[v], 0, 0

    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n_visits_max,),
            in_specs=[pl.BlockSpec((tm, K), row_map),
                      pl.BlockSpec((None, None, K, N), rhs_map)],
            out_specs=pl.BlockSpec((tm, N), row_map),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul",
    )(layer, *meta, lhs, rhs)
    return out[:M]


def grouped_matmul(
    lhs: jax.Array,           # (M, K): rows sorted by expert
    rhs: jax.Array,           # (L, E, K, N): every layer's experts
    layer: jax.Array | int,   # which of the L layers
    group_sizes: jax.Array,   # (E,) int: rows per expert, summing to M
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """``(M, N)`` float32: each row of expert ``e`` times ``rhs[layer, e]``,
    the weights cast to ``lhs``'s dtype in VMEM, float32 accumulation.
    ``jax.lax.ragged_dot(lhs, rhs[layer], group_sizes)`` without the copy
    of ``rhs[layer]``."""
    return _call(lhs, rhs, layer, group_sizes,
                 interpret=resolve_interpret(interpret))
