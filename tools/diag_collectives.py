"""Diagnostic: per-collective breakdown of a cell's sharded L=1 lowering.

    PYTHONPATH=src python tools/diag_collectives.py chameleon-34b train_4k [overrides...]
"""
import os
import re
import sys
from dataclasses import replace

import jax

from repro.configs import ALIASES, SHAPES, get_config
from repro.launch import mesh as mesh_lib
from repro.launch.components import _reduced_cfgs, _step_fn_and_args
from repro.launch.roofline import _bytes_of_shape, _COLL_RE, _GROUPS_RE
from repro.launch.specs import default_run_config


def main():
    arch = ALIASES.get(sys.argv[1], sys.argv[1])
    shape = SHAPES[sys.argv[2]]
    cfg = get_config(arch)
    run = default_run_config(shape.kind)
    for kv in sys.argv[3:]:
        k, v = kv.split("=")
        run = replace(run, **{k: (v if not v.isdigit() else int(v))
                              if v not in ("True", "False") else v == "True"})
    c1, c2, mult = _reduced_cfgs(cfg)
    if os.environ.get('DIAG_L2'):
        c1 = c2
    mesh = mesh_lib.make_production_mesh()
    fn, args = _step_fn_and_args(c1, shape, replace(run, scan_layers=False), mesh=mesh)
    txt = jax.jit(fn).lower(*args).compile().as_text()
    rows = []
    for line in txt.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        size = _bytes_of_shape(m.group(1))
        gm = _GROUPS_RE.search(line)
        g = int(gm.group(2)) if gm else 1
        op = re.search(r'op_name="([^"]*)"', line)
        rows.append((size * (g - 1) / g * (2 if m.group(2) == "all-reduce" else 1),
                     m.group(2), g, (op.group(1) if op else "")[:110]))
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    print(f"total wire bytes (L=1 module): {total/1e9:.2f} GB/dev; multiplier ~{mult}")
    for wire, kind, g, name in rows[:25]:
        print(f"{wire/1e9:9.3f} GB  {kind:<18s} g={g:<3d} {name}")


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
