"""Public held-expert grouped-matmul ops (interpret mode on the CPU).

`held_expert_gmm` is one grouped matmul; `held_expert_ffn` is the SwiGLU
of the held experts, ``wo(silu(x wg) * x wi)``, three calls of the same
kernel over one walk. Rows are padded to the kernel's row tile here, and
every row not of a held expert comes back as zeros.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import interpret_default

from .moe_gmm import group_metadata, held_expert_gmm_pallas, row_tile


def _walk(group_sizes, m: int, first, held: int):
    """Padded row count, the kernel's walk, and which padded rows are held here."""
    mp = -(-m // row_tile(m)) * row_tile(m)
    meta, n_tiles = group_metadata(group_sizes, mp, first, held)
    offsets = meta[0]
    rows = jnp.arange(mp)
    own = (rows >= offsets[first]) & (rows < offsets[first + held])
    return mp, meta, n_tiles, own


def _gmm(lhs, rhs, meta, n_tiles, first, own, out_dtype):
    out = held_expert_gmm_pallas(lhs, rhs, meta, n_tiles, first, out_dtype=out_dtype,
                                 interpret=interpret_default())
    return jnp.where(own[:, None], out, 0)


def held_expert_gmm(lhs, rhs, group_sizes, first=0, out_dtype=jnp.float32):
    """lhs: (m, k) rows sorted by expert; rhs: (held, k, n), experts ``first``
    on; group_sizes: (experts,) int32, rows of each expert the router
    chooses among (their sum may be under m). Returns (m, n)."""
    m = lhs.shape[0]
    mp, meta, n_tiles, own = _walk(group_sizes, m, first, rhs.shape[0])
    lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    return _gmm(lhs, rhs, meta, n_tiles, first, own, out_dtype)[:m]


def held_expert_ffn(x, wi, wg, wo, group_sizes, first=0):
    """x: (m, d) rows sorted by expert; wi, wg: (held, d, ff); wo: (held, ff, d).
    Returns (m, d) float32: ``wo(silu(x wg) * x wi)`` of each row's expert
    where it is held here, else zeros."""
    m = x.shape[0]
    mp, meta, n_tiles, own = _walk(group_sizes, m, first, wi.shape[0])
    x = jnp.pad(x, ((0, mp - m), (0, 0)))
    h = _gmm(x, wi.astype(x.dtype), meta, n_tiles, first, own, jnp.float32)
    g = _gmm(x, wg.astype(x.dtype), meta, n_tiles, first, own, jnp.float32)
    a = (jax.nn.silu(g) * h).astype(x.dtype)
    return _gmm(a, wo.astype(x.dtype), meta, n_tiles, first, own, jnp.float32)[:m]
