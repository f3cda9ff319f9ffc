"""The training driver at smoke widths on the CPU, its check and what the check catches.

The driver runs the program's jitted train step (bf16 compute, float32
weights and AdamW) and the float32 reference follows its first steps.
The vocabularies here are multiples of 256, so the program pads none
(see PERF.md: the program's cross-entropy also spans its padded rows).
"""
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import check
from bench.drivers import train
from bench.reference import train as reference
from bench.tests.conftest import ROOT, SMOKE_CONFIGS, _NoCompiles
from bench.traffic import train_batches

MIX = {"driver": "train", "global_batch": 4, "seq_len": 32, "mesh": [1, 1], "remat": "layer",
       "attn_impl": "full", "check_steps": 3, "batches": 6, "trace_s": 1,
       "adamw": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                 "clip_norm": 1.0, "warmup_steps": 5, "total_steps": 1000, "min_lr_frac": 0.1}}
#: between the program's readings here (loss 2e-3, gradient 1.7e-3, change
#: 1.2e-2 at most) and the fp8 control's (gradient 7.7e-3 at least)
LIMITS = {"loss_gap": 0.05, "grad_norm_gap": 0.005, "change_norm_gap": 0.1}
VOCAB = {"gqa": 512, "mha": 256}


def ctx(config: str, seed: int, mix=MIX):
    return SimpleNamespace(
        workload=f"smoke.train.{config}", cfg=dict(SMOKE_CONFIGS[config], vocab_size=VOCAB[config]),
        mix=json.loads(json.dumps(mix)), seed=seed, seconds=1.0, trace=False, trace_dir=None,
        t_start=time.perf_counter(), compiles=_NoCompiles(), memory_peak=lambda: 0,
        limits=dict(LIMITS), peak=None)


@pytest.mark.parametrize("config", ["gqa", "mha"])
def test_a_sound_step_agrees_with_the_reference(config):
    res = train.run(ctx(config, seed=2**33 + 3))
    assert res["verdict"]["correct"], res["verdict"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["train_tok_s"] > 0 and res["metrics"]["setup_s"] > 0
    assert res["verdict"]["leaves_moved"] == res["verdict"]["leaves_compared"]


def state_unchanged(params, grads, state, cfg):
    from repro.models.params import global_norm

    return params, state, {"lr": jnp.float32(0.0), "grad_norm": global_norm(grads)}


def half_batch_left_out(monkeypatch):
    from repro.models.transformer import DecoderLM

    original = DecoderLM.loss_fn

    def loss_fn(self, params, batch):
        t = batch["tokens"]
        return original(self, params, {"tokens": t[: t.shape[0] // 2]})

    monkeypatch.setattr(DecoderLM, "loss_fn", loss_fn)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch_left_out"])
@pytest.mark.parametrize("config", ["gqa", "mha"])
def test_a_broken_step_is_not_correct(monkeypatch, fault, config):
    if fault == "state_unchanged":
        monkeypatch.setattr("repro.train.step.apply_updates", state_unchanged)
    else:
        half_batch_left_out(monkeypatch)
    res = train.run(ctx(config, seed=11))
    assert not res["verdict"]["correct"], res["verdict"]


@pytest.mark.parametrize("seed", [3, 4, 2**31 + 5])
def test_the_fp8_control_is_not_correct(seed):
    c = ctx("gqa", seed).cfg
    dev = jax.devices()[:1]
    batches = reference.spread(dev, train_batches(MIX, seed, c["vocab_size"])[:3])
    kw = {"sharding": reference.spread_leaf(dev)}
    f32 = reference.follow(c, seed, batches, MIX["adamw"], **kw)
    fp8 = reference.follow(c, seed, batches, MIX["adamw"], quant="fp8", **kw)
    verdict = check.judge_train(fp8, f32, LIMITS)
    assert not verdict["correct"], verdict
    assert verdict["numbers"]["grad_norm_gap"]["value"] > LIMITS["grad_norm_gap"]


def test_on_a_2x2_mesh_of_virtual_devices():
    """The same run on four devices: FSDP x TP shardings, the reference spread over them."""
    code = (
        "import json, sys; sys.path[:0] = [sys.argv[1], sys.argv[1] + '/src']\n"
        "from bench.tests import test_train as t\n"
        "mix = dict(t.MIX, mesh=[2, 2])\n"
        "res = t.train.run(t.ctx('gqa', 2**32 + 9, mix))\n"
        "print(json.dumps({'correct': res['verdict']['correct'], 'failed': res['failed']}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code, str(ROOT)], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {"correct": True, "failed": 0}
