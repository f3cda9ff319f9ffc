"""Pallas TPU ragged paged decode-attention: one query token vs a paged KV pool.

Same flash-decode shape as `repro.kernels.decode_attention` — grid
(B, Hkv, blocks) streaming the cache in (bk, D) VMEM tiles, all `group`
q-heads sharing a KV head processed as one (group, D) tile — except the
cache is a heads-major **page pool** ``(Hkv, n_pages, page_size, D)``
addressed through per-row page tables instead of a dense
``(B, S, Hkv, D)`` slab.  Heads-major keeps every block's last two dims
``(bk, D)`` and ``(group, D)``, the tile shapes Mosaic accepts.

The page table and per-row ragged lengths ride in as **scalar-prefetch**
arguments (`pltpu.PrefetchScalarGridSpec`), so the KV BlockSpec index map
can chase the indirection *before* the kernel body runs: block ``bi`` of
row ``b`` loads page ``table[b, bi // (ps // bk)]`` at sub-page offset
``bi % (ps // bk)`` — the DMA engine streams exactly the pages the row
owns, and the grid's block axis covers only ``table.shape[1]`` pages (the
longest *live* sequence), not a worst-case dense ``S_max``.

Ragged contract: positions ``>= kv_len[b]`` are masked, blocks past the
row's length are skipped (their table entries point at the reserved null
page and are never read into compute), and rows with ``kv_len == 0`` —
the serve loop's free/padded slots — flush **exact zeros** instead of the
0/0 NaN a dense softmax would produce.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    table_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc, m_ref, l_ref,
    *, scale, bk, n_blk,
):
    bi = pl.program_id(2)

    @pl.when(bi == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[pl.program_id(0)]

    @pl.when(bi * bk < kv_len)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # (group, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (group, bk)
        pos = bi * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]  # (group, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(bi == n_blk - 1)
    def _flush():
        # kv_len == 0 rows never ran `_step`; flush exact zeros, not 0/0
        l = l_ref[...]
        out = acc[...] / jnp.where(l > 0.0, l, 1.0)
        out = jnp.where(l > 0.0, out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("bk", "interpret"))
def paged_decode_attention_pallas(
    q, k_pages, v_pages, page_table, kv_len, bk: int | None = None,
    interpret: bool = True,
):
    """q: (B,Hq,D); pages (Hkv,P,ps,D); page_table (B,max_pages) int32;
    kv_len (B,) int32 -> (B,Hq,D).
    """
    b, hq, d = q.shape
    hkv, _, ps, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    bk = ps if bk is None else max(1, min(int(bk), ps))
    assert ps % bk == 0, "bk must divide the page size"
    sub = ps // bk  # KV blocks per page
    max_pages = page_table.shape[1]
    n_blk = max_pages * sub
    grid = (b, hkv, n_blk)

    # q head h*group + g attends KV head h: view q as (B, Hkv, group, D)
    q4 = q.reshape(b, hkv, group, d)
    q_spec = pl.BlockSpec((1, 1, group, d), lambda bb, h, bi, tab, ln: (bb, h, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d),
        lambda bb, h, bi, tab, ln: (h, tab[bb, bi // sub], bi % sub, 0),
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        partial(_kernel, scale=1.0 / (d**0.5), bk=bk, n_blk=n_blk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32), q4, k_pages, v_pages)
    return out.reshape(b, hq, d)
