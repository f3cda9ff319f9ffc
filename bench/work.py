"""The chip's peaks and the roofline: the least time a piece of work could take.

The benchmark's own table and arithmetic: nothing here comes from the
program's cost model or from HLO. The operations and bytes a piece of
work needs come from the configuration's model module (`bench.models`),
from its shapes alone.
"""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def peaks(device_kind: str, root: Path = BENCH) -> dict:
    """The table's peaks for this device; a device missing from it is an error."""
    with open(root / "peaks.json") as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
