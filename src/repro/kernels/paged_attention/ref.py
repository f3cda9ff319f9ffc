"""Oracles for paged flash-decode, shared with the dense kernel's tests.

`ragged_decode_ref` is THE oracle for ragged single-token decode — both
the dense `decode_attention` and the paged kernel are tested against it.
It extends `decode_attention_ref` with the ragged contract the serving
loop needs: rows with ``kv_len == 0`` (free/padded slots) are **exact
zeros**, where a naive masked softmax would emit a uniform average (or
NaN) instead.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention.ref import decode_attention_ref


def ragged_decode_ref(q, k_cache, v_cache, kv_len):
    """q: (B,Hq,D); caches (B,S,Hkv,D); kv_len (B,) -> (B,Hq,D).

    Rows with ``kv_len == 0`` return exact zeros (nothing to attend to).
    """
    out = decode_attention_ref(q, k_cache, v_cache, jnp.maximum(kv_len, 1))
    return jnp.where((kv_len > 0)[:, None, None], out, 0.0).astype(q.dtype)


def gather_pages(pages, page_table):
    """(Hkv,P,ps,D) pages + (B,max_pages) table -> dense (B,max_pages*ps,Hkv,D)."""
    b, n = page_table.shape
    hkv, _, ps, d = pages.shape
    dense = pages[:, page_table.reshape(-1)]  # (Hkv, B*n, ps, D)
    return dense.reshape(hkv, b, n * ps, d).transpose(1, 2, 0, 3)


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, kv_len):
    """Paged oracle: gather the pages dense, then `ragged_decode_ref`."""
    k_dense = gather_pages(k_pages, page_table)
    v_dense = gather_pages(v_pages, page_table)
    return ragged_decode_ref(
        q, k_dense.astype(q.dtype), v_dense.astype(q.dtype), kv_len
    )
