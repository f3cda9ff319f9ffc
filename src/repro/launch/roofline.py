"""Roofline extraction from compiled dry-run artifacts.

Three per-device terms (TPU v5e-class constants from `repro.power`):

    t_compute    = HLO_FLOPs / peak_FLOP/s        (197 TF/s bf16)
    t_memory     = HLO_bytes / HBM_bw             (819 GB/s)
    t_collective = collective_bytes / ICI_bw      (4 × 50 GB/s links)

`cost_analysis()` counts a `lax.scan` body ONCE (verified empirically),
so per-cell costs are assembled **compositionally**: small per-component
lowerings (one layer fwd / fwd+bwd, embed+head+loss, optimizer update)
with their scans unrolled, multiplied by static repeat counts.  The full
step is still compiled — that artifact is the proof-of-compile, the
memory analysis and the collective *schedule*; the component sums are the
cost numbers.  Collective bytes use ring-algorithm wire formulas with
group sizes parsed from `replica_groups`.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.power.tpu_model import V5E, StepCost, TpuChipSpec

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\]\S*))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _bytes_of_shape(tok: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(tok):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_wire_bytes(hlo_text: str) -> dict:
    """Per-device wire bytes by op kind (ring formulas), plus op counts."""
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_tok, kind = m.group(1), m.group(2)
        size = _bytes_of_shape(shape_tok)
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        else:
            gb = _GROUPS_BRACE_RE.search(line)
            if gb:
                g = len(gb.group(1).split(","))
        if g <= 1:
            continue
        frac = (g - 1) / g
        if kind == "all-gather":
            wire = size * frac  # result shape is the gathered size
        elif kind == "all-reduce":
            wire = 2.0 * size * frac
        elif kind == "reduce-scatter":
            wire = size * frac
        elif kind == "all-to-all":
            wire = size * frac
        else:  # collective-permute
            wire = size
        out[kind] += wire
        counts[kind] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


def cost_of_lowered(lowered) -> StepCost:
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    coll = collective_wire_bytes(compiled.as_text())["total"]
    return StepCost(flops=flops, hbm_bytes=byts, ici_bytes=coll)


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    cost: StepCost  # per device, per step
    model_flops_global: float
    n_devices: int
    chip: TpuChipSpec = field(default_factory=lambda: V5E)
    memory: dict | None = None
    collectives: dict | None = None
    components: dict | None = None

    @property
    def t_compute(self) -> float:
        return self.cost.flops / self.chip.peak_flops_bf16

    @property
    def t_memory(self) -> float:
        return self.cost.hbm_bytes / self.chip.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.cost.ici_bytes / self.chip.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (global) — remat/redundancy waste."""
        hlo_global = self.cost.flops * self.n_devices
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute fraction of peak at the modelled step time —
        the MFU-analogue this container can compute without wall clocks."""
        if self.step_time <= 0:
            return 0.0
        useful_per_dev = self.model_flops_global / self.n_devices
        return useful_per_dev / self.step_time / self.chip.peak_flops_bf16

    def to_dict(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "n_devices": self.n_devices,
            "flops_per_dev": self.cost.flops,
            "hbm_bytes_per_dev": self.cost.hbm_bytes,
            "coll_bytes_per_dev": self.cost.ici_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time,
            "model_flops_global": self.model_flops_global,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "memory": self.memory,
            "collectives": self.collectives,
            "components": self.components,
        }


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train, 2·N·D prefill, 2·N·B decode.

    Enc-dec: each token passes through only half the stack (T_enc frames
    through the encoder, T_dec tokens through the decoder), so the
    effective token count is shape.tokens / 2.
    """
    n = cfg.param_count_estimate()
    tokens = shape.tokens / 2 if cfg.is_encdec else shape.tokens
    if shape.kind == "train":
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # one token per sequence


def memory_analysis_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    keys = [
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ]
    return {k: int(getattr(ma, k, 0)) for k in keys}
