"""Public paged decode-attention ops + the energy-tuner variant model.

`paged_decode_attention` dispatches the Pallas kernel (interpret on CPU)
or the gather-dense oracle; `pack_prefill_pages` scatters one admitted
request's prefilled dense K/V rows into its pool pages; and
`paged_tuner_model` is the (config → time, StepCost) hook consumed by
`repro.power.tuner` — the page-size × block × buffer-depth sweep that
`benchmarks/paged_decode.py` drives through the marker-free
`attribution_strategy` to trace the latency × J/token frontier.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import interpret_default
from repro.power.tpu_model import DvfsState, StepCost, TpuChipSpec

from .paged_attention import paged_decode_attention_pallas
from .ref import paged_decode_attention_ref

#: the tuner's knobs: page granularity, VMEM tile within a page, and the
#: DMA pipeline depth hiding the page-table-indirect issue latency
SEARCH_SPACE = {
    "page_size": (32, 64, 128, 256),
    "bk": (32, 128),
    "depth": (1, 2, 4),
}


def paged_decode_attention(
    q, k_pages, v_pages, page_table, kv_len, bk: int | None = None,
    use_pallas: bool = True,
):
    """q: (B,Hq,D); pages (Hkv,P,ps,D); page_table (B,max_pages); kv_len (B,).

    The table must cover every row's ``kv_len`` (unused entries point at
    the null page); ``kv_len == 0`` rows return exact zeros.
    """
    if not use_pallas:
        return paged_decode_attention_ref(q, k_pages, v_pages, page_table, kv_len)
    return paged_decode_attention_pallas(
        q, k_pages, v_pages, page_table, kv_len,
        bk=bk, interpret=interpret_default(),
    )


def init_page_arrays(n_pages, page_size, n_kv_heads, head_dim, dtype=jnp.bfloat16):
    """Zeroed device K and V page pools, heads-major ``(Hkv, n_pages, ps, Dh)``."""
    z = jnp.zeros((n_kv_heads, n_pages, page_size, head_dim), dtype)
    return z, z


@jax.jit
def pack_prefill_pages(k_pages, v_pages, k_dense, v_dense, page_ids):
    """Scatter one request's prefilled K/V into its pool pages.

    ``k_pages``/``v_pages``: (..., Hkv, P, ps, Dh) pools (a leading layer
    axis is fine); ``k_dense``/``v_dense``: (..., S, Hkv, Dh) the request's
    prefill rows; ``page_ids``: (n,) int32 with ``n * ps >= S`` (the tail
    of the last page is zero-filled — positions ``>= kv_len`` are masked
    by the kernel anyway).
    """
    ps = k_pages.shape[-2]
    s = k_dense.shape[-3]
    n = page_ids.shape[0]
    pad = [(0, 0)] * k_dense.ndim
    pad[-3] = (0, n * ps - s)

    def pack(pages, dense):
        lead = dense.shape[:-3]
        paged = jnp.pad(dense, pad).reshape(lead + (n, ps) + dense.shape[-2:])
        # (..., n, ps, Hkv, Dh) -> heads-major (..., Hkv, n, ps, Dh)
        paged = jnp.moveaxis(paged, -2, -4).astype(pages.dtype)
        return pages.at[..., page_ids, :, :].set(paged)

    return pack(k_pages, k_dense), pack(v_pages, v_dense)


def apply_page_permutation(pages, perm):
    """Reorder device pages after `PagedKVPool.defrag` (``perm[new] = old``)."""
    return pages[..., jnp.asarray(perm), :, :]


# --------------------------------------------------------------------------
# modelled TPU cost (the autotuner's measurement target on this container)
# --------------------------------------------------------------------------
def paged_variant_time_cost(
    cfg: dict, chip: TpuChipSpec, dvfs: DvfsState,
    b: int = 64, hq: int = 8, hkv: int = 2, d: int = 128,
    kv_mean: float = 600.0, dtype_bytes: int = 2,
):
    """(time_s, StepCost) for one paged decode step of ``b`` sequences.

    Napkin model (what the sweep actually trades off):

    * **over-fetch** — whole pages stream through HBM regardless of tail
      occupancy, so bytes grow with ``page_size`` on ragged lengths
      (``ceil(kv/ps)·ps`` vs ``kv``): big pages buy speed with joules;
    * **issue latency** — every (row, kv-head, block) grid step pays a
      page-table-indirect DMA setup on the core clock; ``depth``-deep
      buffering overlaps it, ``bk`` sets how many blocks a page splits
      into;
    * **DVFS** — the DMA descriptors and part of the memory fabric live
      in the core clock domain, so downclocking stretches the step while
      dynamic energy drops with ``f·V²``: that is the real speed/joules
      axis the latency × J/token front trades along;
    * **VMEM** — ``depth`` in-flight (bk, D) K+V tiles plus the (group, D)
      q/acc tiles must fit; violations fall off a cliff.
    """
    ps = int(cfg["page_size"])
    bk = min(int(cfg["bk"]), ps)
    depth = int(cfg["depth"])
    group = hq // hkv

    pages_per_seq = np.ceil(kv_mean / ps)
    kv_bytes = 2.0 * b * pages_per_seq * ps * hkv * d * dtype_bytes  # K + V
    io_bytes = kv_bytes + 2.0 * b * hq * d * dtype_bytes  # + q in, o out
    flops = 2.0 * 2.0 * b * hq * d * kv_mean  # qk^T + pv

    n_blocks = b * hkv * pages_per_seq * (ps // bk)
    t_issue = n_blocks * 5e-8 / (depth * dvfs.scale)

    vmem = depth * 2 * bk * d * dtype_bytes + 4 * 3 * group * d
    fits = vmem <= chip.vmem_bytes
    # ~45% of the effective streaming bandwidth rides the core clock
    # domain (descriptor issue, on-chip interconnect), the rest is pure
    # HBM — so downclocking costs time even on a memory-bound kernel
    bw = chip.hbm_bw * (0.9 if fits else 0.25) * (0.55 + 0.45 * dvfs.scale)
    t_mem = io_bytes / bw
    # decode GQA runs skinny (group, bk) matmuls — far off MXU peak
    t_compute = flops / (chip.peak_flops_bf16 * 0.15 * dvfs.scale)
    time_s = max(t_mem, t_compute) + t_issue
    return time_s, StepCost(flops=flops, hbm_bytes=io_bytes, ici_bytes=0.0)


def paged_tuner_model(
    b: int = 64, hq: int = 8, hkv: int = 2, d: int = 128, kv_mean: float = 600.0,
):
    from repro.power.tuner import KernelVariantModel

    return KernelVariantModel(
        name="paged-decode-attention",
        useful_flops=2.0 * 2.0 * b * hq * d * kv_mean,
        model=partial(
            paged_variant_time_cost, b=b, hq=hq, hkv=hkv, d=d, kv_mean=kv_mean
        ),
        search_space=SEARCH_SPACE,
    )
