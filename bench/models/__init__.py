"""A configuration's model: the module that its file names.

Each file under ``bench/configs/`` names its model (``"model": "dense"``),
and ``bench/models/<model>.py`` is all that the harness knows of it. The
module provides:

- ``arch_config(c)``: the program's `ArchConfig` for configuration ``c``;
- ``leaves(c)``: program leaf path -> `Leaf`, for every leaf of the
  program's parameter tree: the reference's name for it, its shape and how
  it is drawn. Weights, the reference's weights and training's change
  norms all read this one map;
- ``logits_at(c, seed, tokens, rows, quant=None)``: the float32 reference;
- ``decode_step_flops(c, kv_lens)``, ``paged_attention_call(c, kv_lens)``
  and ``attention_layers(c)``: the benchmark's own work counts;
- ``follow(c, seed, batches, opt, quant=None, sharding=None)``, where the
  model trains: the float32 training reference.

A configuration that names no model, or one whose module is missing, is
an error: there is no default.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Leaf:
    """One leaf of the program's parameter tree, as the benchmark draws it.

    ``role``: ``"norm"`` (1 + 0.1 N(0, 1)), ``"bias"`` (0.3 N(0, 1)) or
    ``"matrix"`` (N(0, 1) / sqrt(``fan_in``)). ``shape`` is one layer's
    leaf where ``layers`` > 0 (the program stacks that many), else the
    whole leaf. The reference keeps the first ``rows`` rows (all where
    None) and, where ``tied`` names one, also reads the leaf transposed
    under that name.
    """

    ref: str
    shape: tuple
    role: str
    fan_in: int = 0
    layers: int = 0
    rows: int | None = None
    tied: str | None = None

    @property
    def program_shape(self) -> tuple:
        return (self.layers, *self.shape) if self.layers else tuple(self.shape)


def load(c: dict) -> ModuleType:
    """The module of the model that configuration ``c`` names."""
    if "model" not in c:
        raise KeyError(f"configuration {c.get('name')!r} names no model (key \"model\")")
    path = HERE / f"{c['model']}.py"
    if not path.exists():
        raise FileNotFoundError(
            f"configuration {c.get('name')!r} names model {c['model']!r}, "
            f"but bench/models/{path.name} does not exist")
    return importlib.import_module(f"{__name__}.{c['model']}")
