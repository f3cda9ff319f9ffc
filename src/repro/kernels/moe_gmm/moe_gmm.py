"""Pallas TPU kernel: grouped matmul over the experts held here.

Adapted from the forward ``gmm`` of ``jax.experimental.pallas.ops.tpu.
megablox``, whose group metadata it uses. ``lhs`` holds (token, expert)
rows sorted by expert; ``group_sizes`` counts the rows of every expert
the router chooses among, and ``rhs`` holds the weights of only
``rhs.shape[0]`` of them, from expert ``first`` on. The grid walks
(column tile, (expert, row tile) pair) over the held experts' pairs
alone: an expert with no rows is skipped, and rows of experts not held
here, or of no expert at all (padding past the last group), are never
computed. Their output rows are left unwritten; the caller masks them.

Each step multiplies a row tile by one expert's whole-depth weight
tile, so there is no reduction axis. A row tile that two experts share
is visited once for each, consecutively, and each visit stores only its
own expert's rows; every held expert's weight tiles are read once per
row tile that it touches.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

LANES = 128
#: the most bytes one weight tile may take (it is double-buffered)
RHS_TILE_BYTES = 8 * 2**20
VMEM_LIMIT_BYTES = 48 * 2**20


def row_tile(m: int) -> int:
    """Rows a grid step takes: ``m`` rounded up to 16, at most 128."""
    return min(128, -(-m // 16) * 16)


def col_tile(k: int, n: int, itemsize: int) -> int:
    """The widest lane-aligned divisor of ``n`` whose (k, tile) weight block
    fits `RHS_TILE_BYTES`; all of ``n`` where none is lane-aligned."""
    fits = [t for t in range(LANES, n + 1, LANES)
            if n % t == 0 and k * t * itemsize <= RHS_TILE_BYTES]
    return max(fits) if fits else n


def group_metadata(group_sizes, m: int, first, held: int):
    """The grid's walk over the held experts' (expert, row tile) pairs, and its length."""
    return make_group_metadata(group_sizes=group_sizes, m=m, tm=row_tile(m),
                               start_group=jnp.asarray(first, jnp.int32),
                               num_nonzero_groups=held, visit_empty_groups=False)


def _kernel(offsets, group_ids, m_tile_ids, first, lhs_ref, rhs_ref, out_ref):
    del first
    tm, tn = out_ref.shape
    i = pl.program_id(1)
    g = group_ids[i]
    rows = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + m_tile_ids[i] * tm
    own = (rows >= offsets[g]) & (rows < offsets[g + 1])
    acc = jnp.dot(lhs_ref[...], rhs_ref[...], preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(own, acc, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def held_expert_gmm_pallas(lhs, rhs, meta, n_tiles, first, out_dtype=jnp.float32,
                           interpret: bool = False):
    """lhs: (m, k) rows sorted by expert, ``m`` a multiple of `row_tile`;
    rhs: (held, k, n); ``meta``, ``n_tiles``: `group_metadata` at this ``m``.
    Returns (m, n): each held expert's rows times its weights, every
    other row unwritten."""
    m, k = lhs.shape
    held, _, n = rhs.shape
    tm, tn = row_tile(m), col_tile(k, n, rhs.dtype.itemsize)
    if m % tm:
        raise ValueError(f"{m} rows are not a multiple of the row tile {tm}")
    offsets, group_ids, m_tile_ids = meta
    first = jnp.asarray(first, jnp.int32)[None]

    def lhs_map(j, i, offsets, group_ids, m_tile_ids, first):
        return m_tile_ids[i], 0

    def rhs_map(j, i, offsets, group_ids, m_tile_ids, first):
        return group_ids[i] - first[0], 0, j

    def out_map(j, i, offsets, group_ids, m_tile_ids, first):
        return m_tile_ids[i], j

    cost = pl.CostEstimate(
        flops=2 * m * k * n, transcendentals=0,
        bytes_accessed=(lhs.size * lhs.dtype.itemsize * (n // tn)
                        + held * k * n * rhs.dtype.itemsize
                        + m * n * jnp.dtype(out_dtype).itemsize))
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, k), lhs_map),
                      pl.BlockSpec((None, k, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, n_tiles),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        cost_estimate=cost,
        name="held_expert_gmm",
    )(offsets, group_ids, m_tile_ids, first, lhs, rhs)
