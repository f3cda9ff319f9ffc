"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
        --steps 100 --ckpt-dir /tmp/ckpt

`--smoke` selects the reduced config (CPU-runnable); the full configs are
for real accelerators (and are exercised shape-wise by the dry-run).
Every run emits per-step energy telemetry through the TPU power model,
and `--psrun` wraps the whole job PowerSensor3-style (total J, avg W,
sensor-verified).
"""
from __future__ import annotations

import argparse
import json
from dataclasses import replace

import jax

from repro.compile_cache import place_compile_cache, place_tpu_logs
from repro.configs import ALIASES, RunConfig, get_config, smoke_config
from repro.data import SyntheticTokens
from repro.launch import mesh as mesh_lib
from repro.models import build_model
from repro.optim import AdamWConfig, init_opt_state
from repro.power import EnergyTelemetry, StepCost
from repro.train import FaultInjector, LoopConfig, train


def make_recording_attributor(path, telemetry, seed: int = 0, **kwargs):
    """A `StepAttributor` that also archives its sensor session.

    Taps the attributor's virtual-sensor ring after every step and writes
    a `repro.replay` trace archive (markers included) on ``finish()`` —
    so a training run's measured per-kernel energy can be re-attributed
    offline from the archive instead of re-running the job.
    """
    from repro.attrib import StepAttributor
    from repro.replay import SessionRecorder

    class _RecordingAttributor(StepAttributor):
        def __init__(self):
            super().__init__(telemetry, seed=seed, **kwargs)
            self.recorder = SessionRecorder(
                self.sensor, name="train", meta={"launcher": "train", "seed": seed}
            )

        def on_step(self) -> None:
            super().on_step()
            self.recorder.capture()

        def finish(self, min_coverage: float = 0.5):
            # archive before super() closes (and releases) the sensor
            self.sensor.poll()
            archive = self.recorder.save(path, extra_meta={"steps": self._steps})
            print(f"recorded {archive.n_frames} frames to {path} "
                  f"(replay: repro.replay.replay_sensor)")
            return super().finish(min_coverage)

    return _RecordingAttributor()


def main(argv=None, cfg=None):
    """Run the training CLI; ``cfg`` replaces the ``--arch``/``--smoke``
    choice with a given `ArchConfig` (e.g. a published config cut in depth)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--crash-at", type=int, default=-1)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 -> (data=2, model=4)")
    ap.add_argument("--history-out", default=None)
    ap.add_argument("--record", default=None, metavar="PATH",
                    help="attribute every step through the virtual sensor and "
                         "record the session to a replayable trace archive")
    args = ap.parse_args(argv)

    if cfg is None:
        cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    run = RunConfig(attn_impl="full" if args.seq <= 512 else "chunked",
                    remat="none" if args.smoke else "layer", lr_chunk=16)
    model = build_model(cfg, run)
    data = SyntheticTokens(cfg, global_batch=args.batch, seq_len=args.seq, seed=args.seed)

    shardings = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = mesh_lib.make_mesh((d, m), ("data", "model"))
        params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(args.seed))
        opt_shape = jax.eval_shape(init_opt_state, params_shape)
        batch_shape = jax.eval_shape(lambda: data.batch_at(0))
        shardings = {
            "params": mesh_lib.params_shardings(mesh, params_shape),
            "opt": mesh_lib.opt_state_shardings(mesh, opt_shape),
            "batch": mesh_lib.batch_shardings(mesh, batch_shape),
        }

    # energy telemetry: per-step cost from the analytic model estimate
    n = cfg.param_count_estimate()
    tokens_per_step = args.batch * args.seq
    cost = StepCost(
        flops=6.0 * n * tokens_per_step,
        hbm_bytes=12.0 * n + 4.0 * tokens_per_step * cfg.d_model * cfg.n_layers,
        ici_bytes=0.0,
    )
    telemetry = EnergyTelemetry(
        cost_per_step=cost, n_layers=cfg.n_layers,
        useful_flops_per_step=6.0 * n * tokens_per_step,
    )

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(5, args.steps // 20),
                          total_steps=args.steps)
    loop_cfg = LoopConfig(
        steps=args.steps, log_every=args.log_every, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, resume=not args.no_resume, seed=args.seed,
        accum_steps=args.accum,
    )
    injector = FaultInjector(args.crash_at) if args.crash_at >= 0 else None
    attributor = (
        make_recording_attributor(args.record, telemetry, seed=args.seed)
        if args.record
        else None
    )
    result = train(model, data, opt_cfg, loop_cfg, telemetry=telemetry,
                   fault_injector=injector, shardings=shardings,
                   attributor=attributor)
    summary = telemetry.summary()
    print(f"finished at step {result.stopped_at} (preempted={result.preempted})")
    if summary:
        print(
            f"energy(model): {summary['total_joules']:.1f} J total, "
            f"{summary['j_per_token']*1e3:.3f} mJ/token, "
            f"{summary['modelled_step_s']*1e3:.2f} ms/step on {telemetry.chip.name}"
        )
    if result.straggler_events:
        print(f"straggler events: {len(result.straggler_events)}")
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(result.history, f)
    return result


if __name__ == "__main__":
    place_tpu_logs()
    place_compile_cache()
    main()
