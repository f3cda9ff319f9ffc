"""Sharding rules + a miniature end-to-end pjit dry-run on 8 virtual devices.

The 512-device production dry-run needs its own process (XLA_FLAGS are
locked at first jax init), so this test launches `repro.launch.dryrun`-
equivalent lowering in a SUBPROCESS with 8 forced host devices and a
(2, 4) mesh — structure-identical to the production path.
"""
import json
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.launch import mesh as mesh_lib
from repro.models import build_model


def test_param_specs_divisible():
    """Every rule-produced spec divides the actual dims (all 10 archs)."""
    from repro.configs import ARCH_IDS, get_config

    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

        def check(path, leaf):
            spec = mesh_lib.param_spec(FakeMesh, path, leaf)
            for dim, ax in zip(leaf.shape, spec):
                if ax is not None:
                    size = 16 if not isinstance(ax, tuple) else 16
                    assert dim % FakeMesh.shape.get(ax if isinstance(ax, str) else "data", 1) == 0

        jax.tree_util.tree_map_with_path(check, shapes)


def test_major_params_are_sharded():
    """The big 2D projections must not silently fall through to replicated."""
    class FakeMesh:
        shape = {"data": 16, "model": 16}

    cfg = smoke_config("qwen25_3b")
    from dataclasses import replace

    cfg = replace(cfg, d_model=256, d_ff=512, vocab_size=4096)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sharded = {}

    def check(path, leaf):
        spec = mesh_lib.param_spec(FakeMesh, path, leaf)
        name = mesh_lib._path_str(path)
        if leaf.size >= 256 * 256:
            sharded[name] = any(s is not None for s in spec)

    jax.tree_util.tree_map_with_path(check, shapes)
    assert sharded and all(sharded.values()), sharded


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    from repro.configs import RunConfig, SHAPES, smoke_config
    from repro.launch import mesh as mesh_lib
    from repro.launch.specs import build_cell
    import repro.launch.specs as specs
    from dataclasses import replace

    mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
    arch, shape_name = sys.argv[1], sys.argv[2]

    # shrink the cell: patch SHAPES to a tiny variant with the same kind
    kind = SHAPES[shape_name].kind
    import repro.configs as C
    tiny = C.ShapeSpec(shape_name, seq_len=64, global_batch=8, kind=kind)
    C.SHAPES = dict(C.SHAPES); C.SHAPES[shape_name] = tiny
    specs.SHAPES = C.SHAPES

    import repro.configs
    cfg = smoke_config(arch)
    # route get_config -> smoke config for this subprocess
    import repro.launch.specs as sp
    sp.get_config = lambda a: cfg

    run = RunConfig(attn_impl="full", remat="none", lr_chunk=8, moe_group=64)
    cell = build_cell(arch, shape_name, mesh, run)
    lowered = jax.jit(cell.fn, out_shardings=cell.out_shardings).lower(*cell.args)
    compiled = lowered.compile()
    from repro.launch.roofline import collective_wire_bytes
    ca = compiled.cost_analysis()
    colls = collective_wire_bytes(compiled.as_text())
    print(json.dumps({
        "flops": float(ca.get("flops", 0.0)),
        "coll_total": colls["total"],
        "counts": colls["counts"],
    }))
    """
)


@pytest.mark.parametrize(
    "arch,shape",
    [
        ("qwen25_3b", "train_4k"),
        ("phi35_moe", "train_4k"),
        ("zamba2_7b", "decode_32k"),
        ("rwkv6_3b", "long_500k"),
        ("whisper_base", "prefill_32k"),
    ],
)
def test_mini_dryrun_subprocess(arch, shape):
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC, arch, shape],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd="/root/repo",
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    # sharded params guarantee at least one all-gather somewhere
    assert sum(rec["counts"].values()) > 0
