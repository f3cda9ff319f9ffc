"""The training loop: jit'd step + telemetry + checkpoint/restart + faults.

Determinism contract (tested): `train()` interrupted at any step and
resumed from its checkpoint produces bitwise-identical parameters to an
uninterrupted run — the data pipeline is O(1)-indexable and the step is a
pure function.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

from repro.optim import AdamWConfig, init_opt_state

from . import checkpoint as ckpt
from .fault import FaultInjector, PreemptionHandler, SimulatedPreemption, StragglerWatchdog
from .step import TrainStepConfig, make_train_step


@dataclass
class LoopConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: str | None = None
    keep_last: int = 3
    async_checkpoint: bool = True
    resume: bool = True
    seed: int = 0
    accum_steps: int = 1


@dataclass
class TrainResult:
    params: dict
    opt_state: dict
    history: list = field(default_factory=list)
    stopped_at: int = 0
    preempted: bool = False
    straggler_events: list = field(default_factory=list)
    #: per-kernel `repro.attrib.EnergyLedger` (set when an attributor runs)
    energy_ledger: object | None = None


def train(
    model,
    data,
    opt_cfg: AdamWConfig,
    loop_cfg: LoopConfig,
    telemetry=None,
    fault_injector: FaultInjector | None = None,
    mesh=None,
    shardings=None,
    attributor=None,
) -> TrainResult:
    """Run (or resume) training.  `shardings`: optional dict with keys
    'params', 'opt', 'batch' (NamedSharding pytrees) for pjit execution.

    ``attributor``: an optional `repro.attrib.StepAttributor`.  Every step
    is bracketed with a time-synced marker on its virtual sensor and the
    modelled phase trace is played through the full 20 kHz chain; the
    resulting per-kernel energy ledger lands in ``result.energy_ledger``.
    """
    step_fn = make_train_step(model, opt_cfg, TrainStepConfig(loop_cfg.accum_steps))
    jit_kwargs = {}
    if shardings is not None:
        jit_kwargs = dict(
            in_shardings=(shardings["params"], shardings["opt"], shardings["batch"]),
            out_shardings=(shardings["params"], shardings["opt"], None),
        )
    step_jit = jax.jit(step_fn, donate_argnums=(0, 1), **jit_kwargs)

    # ---- init or resume ---------------------------------------------------
    start_step = 0
    params = opt_state = None
    if loop_cfg.resume and loop_cfg.ckpt_dir:
        latest = ckpt.latest_step(loop_cfg.ckpt_dir)
        if latest is not None:
            tree, extra = ckpt.restore(
                ckpt.step_path(loop_cfg.ckpt_dir, latest),
                shardings={"params": shardings["params"], "opt": shardings["opt"]}
                if shardings
                else None,
            )
            params, opt_state = tree["params"], tree["opt"]
            data.load_state_dict(extra["data_state"])
            start_step = extra["step"]
    if params is None:
        # built under jit straight into their shardings: no device ever
        # holds the whole unsharded tree
        params = jax.jit(
            model.init, out_shardings=shardings["params"] if shardings else None
        )(jax.random.PRNGKey(loop_cfg.seed))
        opt_state = jax.jit(
            init_opt_state, out_shardings=shardings["opt"] if shardings else None
        )(params)
        data.step = 0

    saver = (
        ckpt.AsyncCheckpointer(loop_cfg.ckpt_dir, loop_cfg.keep_last)
        if loop_cfg.ckpt_dir
        else None
    )
    watchdog = StragglerWatchdog()
    history: list[dict] = []

    def checkpoint_now(step: int, sync: bool = False) -> None:
        if saver is None:
            return
        extra = {"step": step, "data_state": data.state_dict()}
        tree = {"params": params, "opt": opt_state}
        if sync or not loop_cfg.async_checkpoint:
            saver.save_sync(step, tree, extra)
        else:
            saver.save_async(step, tree, extra)

    result = TrainResult(params=params, opt_state=opt_state, history=history)
    with PreemptionHandler() as preempt:
        step = start_step
        try:
            while step < loop_cfg.steps:
                batch = data.batch_at(step)
                t0 = time.perf_counter()
                if fault_injector is not None:
                    fault_injector.check(step)
                params, opt_state, metrics = step_jit(params, opt_state, batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                data.step = step + 1
                watchdog.observe(step, dt)
                rec = {
                    "step": step,
                    "loss": float(metrics["loss"]),
                    "grad_norm": float(metrics["grad_norm"]),
                    "lr": float(metrics["lr"]),
                    "step_time_s": dt,
                }
                if telemetry is not None:
                    tokens = int(np.prod(batch["tokens"].shape))
                    erec = telemetry.record_step(step, dt, tokens)
                    rec["joules"] = erec.joules
                    rec["j_per_token"] = erec.j_per_token
                if attributor is not None:
                    attributor.on_step()
                history.append(rec)
                if loop_cfg.log_every and step % loop_cfg.log_every == 0:
                    msg = f"step {step:6d} loss {rec['loss']:.4f} gnorm {rec['grad_norm']:.3f} {dt*1e3:.0f} ms"
                    if "joules" in rec:
                        msg += f" {rec['joules']:.1f} J/step(model)"
                    print(msg, flush=True)
                step += 1
                if preempt.requested:
                    checkpoint_now(step, sync=True)
                    result.preempted = True
                    break
                if loop_cfg.ckpt_every and step % loop_cfg.ckpt_every == 0:
                    checkpoint_now(step)
        except SimulatedPreemption:
            # a *real* preemption gives no chance to checkpoint: resume
            # must come from the last periodic checkpoint
            result.preempted = True
        if not result.preempted and step >= loop_cfg.steps:
            checkpoint_now(step, sync=True)
    if saver:
        saver.wait()
    if attributor is not None:
        result.energy_ledger = attributor.finish()
        if loop_cfg.log_every:
            from repro.attrib import render_text

            print(render_text(result.energy_ledger, top=8,
                              title="per-kernel energy (measured)"), flush=True)
    result.params = params
    result.opt_state = opt_state
    result.stopped_at = step
    result.straggler_events = watchdog.events
    return result
