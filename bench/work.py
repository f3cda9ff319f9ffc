"""Operations and bytes the work needs, from its shapes alone.

The benchmark's own counts: nothing here comes from the program's cost
model or from HLO. "Needs" means the least a correct implementation
must do: the live tokens and their cached keys and values, the
vocabulary as published (not padded), causal attention counted over
the lower triangle, no recomputation. A multiply-add is two operations.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


def peaks(device_kind: str, root: Path = BENCH) -> dict:
    """The table's peaks for this device; a device missing from it is an error."""
    with open(root / "peaks.json") as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def _dims(c: dict):
    d, hq = c["hidden_size"], c["num_attention_heads"]
    return d, hq, c["num_key_value_heads"], d // hq, c["intermediate_size"], c["num_hidden_layers"]


def matmul_params(c: dict) -> int:
    """Weights that every token multiplies: all layers' projections and the head."""
    d, hq, hkv, hd, ff, n_layers = _dims(c)
    per_layer = d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * ff
    return n_layers * per_layer + d * c["vocab_size"]


def paged_attention_call(c: dict, kv_lens, dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) one layer's paged decode-attention call needs.

    ``kv_lens``: (B,) tokens each row attends over, the new token included;
    0 for a padded row. Keys and values of the live tokens are read once;
    q is read and the output written for every row of the batch.
    """
    _, hq, hkv, hd, _, _ = _dims(c)
    kv = np.asarray(kv_lens, np.float64)
    flops = 4.0 * hq * hd * kv.sum()  # q.k and p.v
    nbytes = (2.0 * hkv * hd * kv.sum() + 2.0 * hq * hd * kv.size) * dtype_bytes
    return flops, nbytes


def decode_step_flops(c: dict, kv_lens) -> float:
    """Operations one decode step needs: 2 per weight per live token, plus attention."""
    kv = np.asarray(kv_lens)
    live = int((kv > 0).sum())
    attn = paged_attention_call(c, kv)[0] * c["num_hidden_layers"]
    return 2.0 * matmul_params(c) * live + attn


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
