"""Pallas TPU ragged paged decode-attention: one query token vs a paged KV pool.

The cache is a heads-major **page pool** ``(Hkv, n_pages, page_size, D)``
addressed through per-row page tables.  The table's width is whatever the
caller reserved: in the serve loop and the benchmark, a slot's whole
reservation, not the longest live sequence.

**The walk.**  The grid is ``(B, n_chunks)``: one step takes a *chunk* of
``pages_per_chunk`` consecutive table entries of one row, for every KV
head at once, into a VMEM buffer ``(Hkv, pages_per_chunk * ps, D)``.  The
page ids ride in as scalar-prefetch arguments.  Copies are double-buffered
across grid steps: while one chunk computes, the pages of the next live
chunk are in flight, which at a row's end are those of the next live row.
Chunks at or past a row's ``kv_len`` issue no DMA and no compute, so a row
with ``kv_len == 0`` costs ``n_chunks`` empty grid steps, not a walk of its
whole table.  How the pages are fetched depends on the head width:

- ``D % 128 == 0`` (qwen2.5-3b's 128): `_dma_kernel`.  The pools stay in
  HBM (``memory_space=pl.ANY``); per live page the kernel issues one
  ``make_async_copy`` of ``pages.at[:, page]`` — ``(Hkv, ps, D)``, strided
  over heads — into one of two buffer slots.  It starts the first live
  chunk at step ``(0, 0)`` and, at each live chunk, the next one.  That
  chain runs across rows, so both grid axes are sequential.
- otherwise (phi3-mini's 96): `_block_kernel`.  Mosaic refuses any slice of
  an HBM ref whose minor dimension is not a multiple of 128, so each page
  of a chunk is its own input block ``(Hkv, 1, ps, D)`` (the pools are
  passed ``pages_per_chunk`` times each), and Mosaic's grid pipeline
  double-buffers them.  It copies a block only when its page id changes
  from the previous grid step, and `_fetch_table` repeats the previous
  step's page for every block past a row's last page, so dead chunks copy
  nothing.  They still pay the pipeline's per-block bookkeeping, which
  is why the lane-aligned case takes the manual walk.

Within the last live chunk, buffer rows past ``kv_len`` hold what an
earlier chunk left there: they are masked out of the scores and their
value rows zeroed.

**The chunk rule** (`pages_per_chunk`): as many pages as fit
`CHUNK_VMEM_BYTES` with K and V double-buffered (each page of all heads
counted at the lane-padded width ``ceil(D / 128) * 128``), at most
`CHUNK_TOKENS` tokens, at most the table's width, and at most ``bk``
tokens when the caller passes one (rounded down to whole pages, at least
one).  On a v5e that gives 64 pages at qwen2.5-3b's heads and 16 at
phi3-mini's, the fastest of those measured at each.

Math, per KV head and its ``group`` query heads: float32 scores and
accumulators under an online softmax, scaled by ``1/sqrt(D)``; rows with
``kv_len == 0`` — the serve loop's free/padded slots — flush **exact
zeros** instead of the 0/0 NaN a dense softmax would produce.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pool import NULL_PAGE

NEG_INF = -1e30
LANES = 128
#: VMEM for the K and V chunk buffers, both slots (v5e scopes 16 MiB to a
#: kernel by default; a chunk's float32 working copies need room too)
CHUNK_VMEM_BYTES = 8 * 2**20
#: longest chunk: the last live chunk of a row computes all of it
CHUNK_TOKENS = 1024


def pages_per_chunk(hkv: int, ps: int, d: int, itemsize: int, width: int,
                    bk: int | None = None) -> int:
    """Pages one grid step fetches, from the shapes alone (see module doc)."""
    page_bytes = 2 * 2 * hkv * ps * (-(-d // LANES) * LANES) * itemsize
    n = min(CHUNK_VMEM_BYTES // page_bytes, CHUNK_TOKENS // ps, width)
    if bk is not None:
        n = min(n, int(bk) // ps)
    return max(1, n)


def _init(acc, m_ref, l_ref):
    acc[...] = jnp.zeros_like(acc)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _attend(q, k, v, start, kv_len, acc, m_ref, l_ref, *, scale):
    """Fold one chunk into the online softmax.

    q: (Hkv, group, D); k, v: (Hkv, tokens, D) holding positions from
    ``start``; acc (Hkv, group, D) and m, l (Hkv, group, 1) float32.
    """
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale  # (Hkv, group, tokens)
    pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(pos < kv_len, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=2, keepdims=True)
    # rows past kv_len hold an earlier chunk's values: zero, not 0 * stale
    v_pos = start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    v = jnp.where(v_pos < kv_len, v.astype(jnp.float32), 0.0)
    acc[...] = acc[...] * corr + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _flush(o_ref, acc, l_ref):
    # kv_len == 0 rows never folded a chunk; flush exact zeros, not 0/0
    l = l_ref[...]
    out = acc[...] / jnp.where(l > 0.0, l, 1.0)
    out = jnp.where(l > 0.0, out, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def _dma_kernel(
    table_ref, len_ref, first_live_ref, q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sems, acc, m_ref, l_ref, slot_ref,
    *, scale, ps, ppc, n_rows, n_chunks,
):
    b, c = pl.program_id(0), pl.program_id(1)
    tokens = ppc * ps
    kv_len = len_ref[b]

    def copies(row, chunk, slot, wait):
        """Start (or wait for) the DMAs of one chunk's live pages."""
        first = chunk * ppc
        n = jnp.minimum(ppc, (len_ref[row] + ps - 1) // ps - first)

        def page(i, carry):
            pid = table_ref[row, first + i]
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                cp = pltpu.make_async_copy(
                    hbm.at[:, pid], buf.at[slot, :, pl.ds(i * ps, ps)], sems.at[slot])
                if wait:
                    cp.wait()
                else:
                    cp.start()
            return carry

        jax.lax.fori_loop(0, n, page, 0)

    @pl.when((b == 0) & (c == 0))
    def _boot():
        slot_ref[0] = 0
        row = first_live_ref[0]

        @pl.when(row < n_rows)
        def _():
            copies(row, 0, 0, wait=False)

    @pl.when(c == 0)
    def _():
        _init(acc, m_ref, l_ref)

    @pl.when(c * tokens < kv_len)
    def _step():
        slot = slot_ref[0]
        more = (c + 1) * tokens < kv_len
        next_row = jnp.where(more, b, first_live_ref[b + 1])

        @pl.when(next_row < n_rows)
        def _prefetch():
            copies(next_row, jnp.where(more, c + 1, 0), 1 - slot, wait=False)

        slot_ref[0] = 1 - slot
        copies(b, c, slot, wait=True)
        _attend(q_ref[0], k_buf[slot], v_buf[slot], c * tokens, kv_len,
                acc, m_ref, l_ref, scale=scale)

    @pl.when(c == n_chunks - 1)
    def _():
        _flush(o_ref, acc, l_ref)


def _block_kernel(fetch_ref, len_ref, q_ref, *refs, scale, ps, ppc, n_chunks):
    del fetch_ref  # read by the index maps
    k_refs, v_refs = refs[:ppc], refs[ppc:2 * ppc]
    o_ref, acc, m_ref, l_ref = refs[2 * ppc:]
    c = pl.program_id(1)
    kv_len = len_ref[pl.program_id(0)]
    tokens = ppc * ps

    @pl.when(c == 0)
    def _():
        _init(acc, m_ref, l_ref)

    @pl.when(c * tokens < kv_len)
    def _step():
        # (Hkv, tokens, D): the chunk's pages in table order
        k = jnp.concatenate([r[:, 0] for r in k_refs], axis=1)
        v = jnp.concatenate([r[:, 0] for r in v_refs], axis=1)
        _attend(q_ref[0], k, v, c * tokens, kv_len, acc, m_ref, l_ref, scale=scale)

    @pl.when(c == n_chunks - 1)
    def _():
        _flush(o_ref, acc, l_ref)


def _fetch_table(page_table, kv_len, ps: int, ppc: int, n_chunks: int):
    """(B, n_chunks * ppc): the page block ``j`` of step ``(b, c)`` holds.

    A live page (``c * ppc + j`` below the row's page count) is the row's
    own; any other block keeps the page it held at the previous grid step
    (row-major order), so the pipeline copies nothing for it.
    """
    b, width = page_table.shape
    cols = n_chunks * ppc
    table = jnp.pad(page_table, ((0, 0), (0, cols - width)), constant_values=NULL_PAGE)
    live = jnp.arange(cols) < ((kv_len + ps - 1) // ps)[:, None]
    table = table.reshape(b * n_chunks, ppc)
    live = live.reshape(b * n_chunks, ppc)
    step = jnp.arange(b * n_chunks, dtype=jnp.int32)[:, None]
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=0)  # last live step per block
    held = jnp.take_along_axis(table, jnp.maximum(last, 0), axis=0)
    return jnp.where(last >= 0, held, NULL_PAGE).reshape(b, cols)


@partial(jax.jit, static_argnames=("bk", "interpret"))
def paged_decode_attention_pallas(
    q, k_pages, v_pages, page_table, kv_len, bk: int | None = None,
    interpret: bool = True,
):
    """q: (B,Hq,D); pages (Hkv,P,ps,D); page_table (B,max_pages) int32;
    kv_len (B,) int32 -> (B,Hq,D).

    ``bk`` caps a grid step's chunk, in tokens (whole pages, at least one).
    """
    b, hq, d = q.shape
    hkv, _, ps, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    width = page_table.shape[1]
    ppc = pages_per_chunk(hkv, ps, d, k_pages.dtype.itemsize, width, bk)
    n_chunks = -(-width // ppc)
    page_table = page_table.astype(jnp.int32)
    kv_len = kv_len.astype(jnp.int32)
    scale = 1.0 / (d**0.5)

    # q head h*group + g attends KV head h: view q as (B, Hkv, group, D)
    q4 = q.reshape(b, hkv, group, d)
    q_spec = pl.BlockSpec((1, hkv, group, d), lambda bb, c, *_: (bb, 0, 0, 0))
    softmax_state = [
        pltpu.VMEM((hkv, group, d), jnp.float32),
        pltpu.VMEM((hkv, group, 1), jnp.float32),
        pltpu.VMEM((hkv, group, 1), jnp.float32),
    ]
    if d % LANES == 0:
        # first_live[r]: the first row >= r with kv_len > 0, else B
        rows = jnp.where(kv_len > 0, jnp.arange(b, dtype=jnp.int32), b)
        first_live = jnp.append(jax.lax.cummin(rows, reverse=True), jnp.int32(b))
        kernel = partial(_dma_kernel, scale=scale, ps=ps, ppc=ppc, n_rows=b,
                         n_chunks=n_chunks)
        scalars = (page_table, kv_len, first_live)
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [q_spec, any_spec, any_spec]
        pools = (k_pages, v_pages)
        buf = pltpu.VMEM((2, hkv, ppc * ps, d), k_pages.dtype)
        scratch = [buf, buf, pltpu.SemaphoreType.DMA((2,)), *softmax_state,
                   pltpu.SMEM((1,), jnp.int32)]
        semantics = ("arbitrary", "arbitrary")
    else:
        kernel = partial(_block_kernel, scale=scale, ps=ps, ppc=ppc, n_chunks=n_chunks)
        scalars = (_fetch_table(page_table, kv_len, ps, ppc, n_chunks), kv_len)
        page_specs = [
            pl.BlockSpec((hkv, 1, ps, d),
                         lambda bb, c, f, _, j=j: (0, f[bb, c * ppc + j], 0, 0))
            for j in range(ppc)
        ]
        in_specs = [q_spec, *page_specs, *page_specs]
        pools = (k_pages,) * ppc + (v_pages,) * ppc
        scratch = softmax_state
        semantics = ("parallel", "arbitrary")

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, n_chunks),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="paged_decode_attention_pallas",
    )(*scalars, q4, *pools)
    return out.reshape(b, hq, d)
