"""Shared pieces of the benchmark's CPU tests: smoke-width configurations and
a run context like the one ``bench/run.py`` builds, without the look for a chip."""
from __future__ import annotations

import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: published-config keys at smoke widths: GQA with QKV bias and a tied
#: head (Qwen2-like), MHA without bias, untied (Phi-3-like), and GQA whose
#: heads are wider than hidden_size / heads (4 heads of 32 at d 64)
SMOKE_CONFIGS = {
    "gqa": {"name": "smoke-gqa", "model": "dense", "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
            "vocab_size": 300, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
            "tie_word_embeddings": True, "qkv_bias": True},
    "mha": {"name": "smoke-mha", "model": "dense", "hidden_size": 96, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
            "vocab_size": 260, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
            "tie_word_embeddings": False, "qkv_bias": False},
    "head_dim": {"name": "smoke-head-dim", "model": "dense", "hidden_size": 64,
                 "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
                 "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 300,
                 "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
                 "tie_word_embeddings": False, "qkv_bias": True},
}

SMOKE_MIXES = {
    "open_loop": {"driver": "open_loop", "arrival": {"process": "poisson", "rate_per_s": 6.0},
                  "prompt_len": {"values": [16, 40], "shares": [0.5, 0.5]},
                  "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                                 "min": 6, "max": 24},
                  "slots": 3, "page_size": 16, "steps_per_sync": 4, "fleet_devices": 2,
                  "drain_s": 120, "trace_s": 1},
    "backlog": {"driver": "backlog", "backlog": {"depth_per_slot": 2, "block": 8},
                "prompt_len": {"values": [24, 48], "shares": [0.5, 0.5]},
                "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.4,
                               "min": 6, "max": 20},
                "slots": 3, "page_size": 16, "steps_per_sync": 4, "fleet_devices": 2,
                "dispatch_ahead": 3, "drain_s": 120, "trace_s": 1},
}

LOOSE = {"max_logit_gap": 0.05, "min_served_tokens": 80, "max_requests": 8}


class _NoCompiles:
    def window_open(self):
        pass

    def window_close(self):
        pass


def smoke_ctx(config="gqa", driver="open_loop", seed=3, seconds=2.0, limits=LOOSE):
    return SimpleNamespace(
        workload=f"smoke.{driver}", cfg=dict(SMOKE_CONFIGS[config]),
        mix=dict(SMOKE_MIXES[driver]), seed=seed, seconds=seconds, trace=False,
        trace_dir=None, t_start=time.perf_counter(), compiles=_NoCompiles(),
        memory_peak=lambda: 0, limits=dict(limits), peak=None)


@pytest.fixture
def ctx_factory():
    return smoke_ctx
