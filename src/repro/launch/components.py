"""Compositional cost extraction (see roofline.py docstring).

`cost_analysis()` counts scan bodies once, so exact per-cell costs come
from two-point extrapolation over depth: lower the cell's step with
L=1 and L=2 layers (scans unrolled where they carry real work), then

    cost(L) = fixed + L · layer   ⇒   layer = c2 − c1, fixed = c1 − layer.

FLOPs/bytes are measured UNSHARDED on the global shapes (per-device =
global / n_devices under even sharding) — this keeps the unrolled
lowerings off the SPMD partitioner.  Collective wire bytes are measured
from SHARDED L∈{1,2} lowerings with the layer loop unrolled (python
loop) but inner scans intact (collectives live at layer boundaries).
The optimizer update is elementwise over stacked params (no scan) and is
lowered once at full size.

Hybrid (zamba2) extrapolates over layer *groups* (6 Mamba layers + the
shared attention block); the 3-layer tail is counted as half a group's
Mamba share (documented approximation, <2 % of depth).
"""
from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig, RunConfig, ShapeSpec
from repro.models import build_model
from repro.optim import AdamWConfig, init_opt_state, apply_updates
from repro.power.tpu_model import StepCost

from . import mesh as mesh_lib
from .roofline import collective_wire_bytes


def _reduced_cfgs(cfg: ArchConfig) -> tuple[ArchConfig, ArchConfig, float]:
    """(cfg_L1, cfg_L2, multiplier) for two-point depth extrapolation."""
    if cfg.family == "hybrid":
        g = cfg.attn_every
        mult = cfg.n_layers // g + (cfg.n_layers % g) / g * 0.5
        return (
            replace(cfg, n_layers=g),
            replace(cfg, n_layers=2 * g),
            mult,
        )
    if cfg.is_encdec:
        return (
            replace(cfg, n_layers=2, enc_layers=1, dec_layers=1),
            replace(cfg, n_layers=4, enc_layers=2, dec_layers=2),
            float(cfg.enc_layers),  # enc_layers == dec_layers for whisper
        )
    return (
        replace(cfg, n_layers=1),
        replace(cfg, n_layers=2),
        float(cfg.n_layers),
    )


def _unrolled(run: RunConfig) -> RunConfig:
    return replace(run, scan_layers=False, scan_unroll=True)


def _cost_of(lowered) -> StepCost:
    ca = lowered.compile().cost_analysis()
    return StepCost(
        flops=float(ca.get("flops", 0.0)),
        hbm_bytes=float(ca.get("bytes accessed", 0.0)),
        ici_bytes=0.0,
    )


def _coll_of(lowered) -> float:
    return collective_wire_bytes(lowered.compile().as_text())["total"]


def _step_fn_and_args(cfg: ArchConfig, shape: ShapeSpec, run: RunConfig, mesh=None):
    """Build (fn, args) for the cell's step at this cfg size.

    With `mesh` the args carry shardings; otherwise unsharded global
    shapes on the default (single) device.
    """
    from .specs import batch_shapes  # local import to avoid a cycle

    if run.constrain_activations:
        from repro.models import sharding_ctx

        sharding_ctx.set_mesh(mesh)  # None for the unsharded cost lowerings
    model = build_model(cfg, run)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if run.bf16_params:
        params_shape = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, jnp.bfloat16 if jnp.issubdtype(l.dtype, jnp.floating) else l.dtype
            ),
            params_shape,
        )
    if mesh is not None:
        p_sh = mesh_lib.params_shardings(mesh, params_shape)
        params_shape = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            params_shape, p_sh,
        )
    if shape.kind == "train":
        bshape = batch_shapes(cfg, shape)
        if mesh is not None:
            b_sh = mesh_lib.batch_shardings(mesh, bshape)
            bshape = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                bshape, b_sh,
            )

        def fn(params, batch):
            return jax.value_and_grad(lambda p: model.loss_fn(p, batch)[0])(params)

        return fn, (params_shape, bshape)
    if shape.kind == "prefill":
        bshape = batch_shapes(cfg, shape)
        if mesh is not None:
            b_sh = mesh_lib.batch_shardings(mesh, bshape)
            bshape = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                bshape, b_sh,
            )

        if cfg.is_encdec:
            def fn(params, batch):
                return model.prefill(params, batch)
        else:
            def fn(params, batch):
                return model.prefill(params, batch["tokens"])

        return fn, (params_shape, bshape)
    # decode
    b, s = shape.global_batch, shape.seq_len
    if cfg.is_encdec:
        cache_shape = jax.eval_shape(lambda: model.init_cache(b, max_len=s // 2, enc_len=s // 2))
    else:
        cache_shape = jax.eval_shape(lambda: model.init_cache(b, max_len=s))
    tok = jax.ShapeDtypeStruct((b,), jnp.int32)
    if mesh is not None:
        c_sh = mesh_lib.cache_shardings(mesh, cache_shape, seq_shard=run.decode_seq_shard)
        cache_shape = jax.tree.map(
            lambda l, s_: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s_),
            cache_shape, c_sh,
        )

    def fn(params, cache, token):
        return model.decode_step(params, cache, token)

    return fn, (params_shape, cache_shape, tok)


def compute_cell_costs(cfg: ArchConfig, shape: ShapeSpec, run: RunConfig, mesh,
                       include_collectives: bool = True) -> dict:
    """Returns global flops/bytes, per-device collective bytes, components."""
    c1_cfg, c2_cfg, mult = _reduced_cfgs(cfg)
    run_u = _unrolled(run)

    # ---- flops / bytes: unsharded two-point -------------------------------
    fn1, args1 = _step_fn_and_args(c1_cfg, shape, run_u, mesh=None)
    fn2, args2 = _step_fn_and_args(c2_cfg, shape, run_u, mesh=None)
    c1 = _cost_of(jax.jit(fn1).lower(*args1))
    c2 = _cost_of(jax.jit(fn2).lower(*args2))
    layer = StepCost(c2.flops - c1.flops, c2.hbm_bytes - c1.hbm_bytes, 0.0)
    fixed = StepCost(c1.flops - layer.flops, c1.hbm_bytes - layer.hbm_bytes, 0.0)
    total = StepCost(
        max(fixed.flops, 0.0) + mult * max(layer.flops, 0.0),
        max(fixed.hbm_bytes, 0.0) + mult * max(layer.hbm_bytes, 0.0),
        0.0,
    )

    # ---- optimizer update (train only): elementwise, lowered once ---------
    opt_cost = StepCost(0.0, 0.0, 0.0)
    if shape.kind == "train":
        model = build_model(cfg, run)
        params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        opt_shape = jax.eval_shape(
            lambda p: init_opt_state(p, master_weights=run.bf16_params), params_shape
        )
        grads_shape = params_shape

        def opt_fn(p, g, s):
            return apply_updates(p, g, s, AdamWConfig())

        opt_cost = _cost_of(jax.jit(opt_fn).lower(params_shape, grads_shape, opt_shape))
        total = total + opt_cost

    # ---- collective wire bytes: sharded two-point -------------------------
    coll_per_dev = 0.0
    coll_parts = {}
    if include_collectives and mesh is not None:
        run_c = replace(run, scan_layers=False)
        fn1s, args1s = _step_fn_and_args(c1_cfg, shape, run_c, mesh=mesh)
        fn2s, args2s = _step_fn_and_args(c2_cfg, shape, run_c, mesh=mesh)
        w1 = _coll_of(jax.jit(fn1s).lower(*args1s))
        w2 = _coll_of(jax.jit(fn2s).lower(*args2s))
        layer_w = max(w2 - w1, 0.0)
        fixed_w = max(w1 - layer_w, 0.0)
        coll_per_dev = fixed_w + mult * layer_w
        coll_parts = {"fixed": fixed_w, "per_layer": layer_w, "multiplier": mult}
        if shape.kind == "train":
            # gradient reduction across pods (params replicated per pod)
            if "pod" in mesh.shape and mesh.shape["pod"] > 1:
                import numpy as np

                n_params = cfg.param_count_estimate()
                g = mesh.shape["pod"]
                pod_ar = 2.0 * (n_params * 4 / (mesh.shape["data"] * mesh.shape["model"])) * (g - 1) / g
                coll_per_dev += pod_ar
                coll_parts["pod_grad_allreduce"] = pod_ar

    n_dev = mesh.size if mesh is not None else 1
    return {
        "global": total,
        "per_device": StepCost(total.flops / n_dev, total.hbm_bytes / n_dev, coll_per_dev),
        "components": {
            "layer": {"flops": layer.flops, "hbm_bytes": layer.hbm_bytes, "count": mult},
            "fixed": {"flops": fixed.flops, "hbm_bytes": fixed.hbm_bytes},
            "optimizer": {"flops": opt_cost.flops, "hbm_bytes": opt_cost.hbm_bytes},
            "collectives": coll_parts,
        },
    }
