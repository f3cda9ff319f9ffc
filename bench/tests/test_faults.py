"""The check catches a broken timed path: each fault a serving cell can have
makes ``correct`` come out false in the result line, with the rest of a run
(`bench.run.measure`: set-up, window, drain, check) driven as usual.

The faults are planted in the program's decode step, under the harness:
a step that hands back its cache unchanged, a step that leaves half the
batch out, and a token altered where it is produced. (A one-chip cell has
no exchange between chips to leave out.) The cells run at smoke widths on
the CPU, under their own names, so the line is the one a chip run prints.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.conftest import LOOSE, ROOT, SMOKE_CONFIGS, SMOKE_MIXES
from repro.models.transformer import DecoderLM

ORIGINAL = DecoderLM.decode_step_paged
CELLS = {"qwen2.5-3b.chat": ("gqa", "open_loop"), "phi3-mini.batch-long": ("mha", "backlog")}


def state_unchanged(self, params, cache, *args):
    logits, _ = ORIGINAL(self, params, cache, *args)
    return logits, cache


def half_batch_left_out(self, params, cache, token, *args):
    logits, new = ORIGINAL(self, params, cache, token, *args)
    half = max(1, logits.shape[0] // 2)
    return logits.at[:half].set(0.0), new


def token_altered(self, params, cache, *args):
    logits, new = ORIGINAL(self, params, cache, *args)
    return jnp.roll(logits, 1, axis=-1), new


def measure(cell: str, seed: int) -> dict:
    config, mix = CELLS[cell]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return run.measure(bench, cell, dict(SMOKE_CONFIGS[config]), dict(SMOKE_MIXES[mix]),
                       dict(LOOSE), seed, 2.0, False, jax.devices()[:1], {})


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_sound_step_is_correct(cell):
    line = measure(cell, seed=2**33 + 11)
    assert line["correct"] and line["failed"] == 0, line
    gap = line["check"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out, token_altered])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_broken_step_is_not_correct(monkeypatch, fault, cell):
    monkeypatch.setattr(DecoderLM, "decode_step_paged", fault)
    line = measure(cell, seed=11)
    assert not line["correct"], line
    gap = line["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
