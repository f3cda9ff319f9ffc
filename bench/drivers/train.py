"""Training: the program's train step on its mesh, driven for a window.

Set-up builds the step as ``launch/train.py`` does: `make_train_step`
jitted with the mesh's shardings (`launch/mesh.py`), parameters and
optimizer state donated, float32 weights drawn from the seed straight
into their shardings. It drives that one object through the mix's first
``check_steps`` steps, through the window's own call and feed, and
reads from them what the check compares: each step's loss, each leaf's
first gradient as the optimizer got it (its first moment after one step,
over ``1 - b1``) and each leaf's change after the last of them. The
window then runs the same object on.

End-to-end metric: tokens of the steps completed in the window, over its
length. ``attempted`` counts the steps started in the window; one whose
loss is not finite fails. Once the window has closed and the program's
state is freed, the float32 reference (`bench.reference.train`) follows
the first steps from the same weights and batches on the same chips, and
`bench.check.judge_train` compares. Weights, change norms and the
reference all come through the configuration's model module
(`bench.models`).
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

from bench.serving import TraceWindow, trace_stop, trace_tick


def _by_path(tree) -> dict:
    from bench.weights import path_str

    return {path_str(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@jax.jit
def _norms(tree):
    return jax.tree.map(jnp.linalg.norm, tree)


def run(ctx) -> dict:
    from bench import check, models
    from bench.reference.train import spread, spread_leaf
    from bench.traffic import train_batches
    from bench.weights import change_norms, make_params
    from repro.configs import RunConfig
    from repro.launch import mesh as mesh_lib
    from repro.models import build_model
    from repro.optim import AdamWConfig, init_opt_state
    from repro.train import step as train_step

    mix, seed = ctx.mix, ctx.seed
    module = models.load(ctx.cfg)
    leaves = module.leaves(ctx.cfg)
    cfg = module.arch_config(ctx.cfg)
    model = build_model(cfg, RunConfig(attn_impl=mix["attn_impl"], remat=mix["remat"]))
    mesh = mesh_lib.make_mesh(tuple(mix["mesh"]), ("data", "model"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = mesh_lib.params_shardings(mesh, shapes)
    o_sh = mesh_lib.opt_state_shardings(mesh, jax.eval_shape(init_opt_state, shapes))
    batches = train_batches(mix, seed, cfg.vocab_size)
    b_sh = mesh_lib.batch_shardings(mesh, {"tokens": jax.ShapeDtypeStruct(batches.shape[1:],
                                                                          jnp.int32)})
    opt_cfg = AdamWConfig(**mix["adamw"])
    step = jax.jit(train_step.make_train_step(model, opt_cfg), donate_argnums=(0, 1),
                   in_shardings=(p_sh, o_sh, b_sh), out_shardings=(p_sh, o_sh, None))
    params = make_params(model, seed, leaves, jnp.float32, p_sh)
    opt = jax.jit(init_opt_state, out_shardings=o_sh)(params)
    feed = [jax.device_put({"tokens": x}, b_sh) for x in batches]

    n_check = int(mix["check_steps"])
    prog = {"losses": []}
    for i in range(n_check):
        params, opt, met = step(params, opt, feed[i])
        prog["losses"].append(float(met["loss"]))
        if i == 0:
            prog["grad_norms"] = {p: float(x) / (1 - opt_cfg.b1)
                                  for p, x in _by_path(_norms(opt["m"])).items()}
    prog["change_norms"] = change_norms(_by_path(params), seed, leaves)

    trace = None
    if ctx.trace:
        t_len = min(float(mix["trace_s"]), ctx.seconds / 2)
        trace = TraceWindow(start_s=ctx.seconds / 4, stop_s=ctx.seconds / 4 + t_len,
                            log_dir=str(ctx.trace_dir))
    tokens_per_step = int(batches.shape[1]) * (int(batches.shape[2]) - 1)
    setup_s = time.perf_counter() - ctx.t_start
    ctx.compiles.window_open()
    done, pending, i = [], None, n_check
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if trace is not None:
            trace_tick(trace, now)
        if now >= ctx.seconds:
            break
        with jax.profiler.TraceAnnotation("host:step"):
            params, opt, met = step(params, opt, feed[i % len(feed)])
        i += 1
        if trace is not None and trace.on:
            trace.steps += 1
        if pending is not None:
            with jax.profiler.TraceAnnotation("host:fetch"):
                done.append((float(pending), time.perf_counter() - t0))
        pending = met["loss"]
    if pending is not None:
        done.append((float(pending), time.perf_counter() - t0))
    if trace is not None and trace.on:
        trace_stop(trace)
    ctx.compiles.window_close()
    memory_peak = ctx.memory_peak()
    in_window = sum(1 for _, t in done if t <= ctx.seconds)
    failed = sum(1 for loss, _ in done if not math.isfinite(loss))
    del params, opt, met, pending, feed, step

    devices = list(mesh.devices.flat)
    ref = module.follow(ctx.cfg, seed, spread(devices, batches[:n_check]),
                        dict(mix["adamw"]), sharding=spread_leaf(devices))
    verdict = check.judge_train(prog, ref, ctx.limits)
    return {"attempted": len(done), "failed": failed,
            "metrics": {"train_tok_s": in_window * tokens_per_step / ctx.seconds,
                        "setup_s": setup_s},
            "memory_peak_bytes": memory_peak, "verdict": verdict, "trace": trace,
            "summary": {"steps_in_window": in_window, "tokens_per_step": tokens_per_step,
                        "losses": prog["losses"], "reference_losses": ref["losses"],
                        "last_loss": done[-1][0] if done else None}}
