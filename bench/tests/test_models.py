"""Each configuration's model module, on the CPU: it maps every leaf the program
builds, its weights weigh what the published keys say, and a model that is
missing stops a run before anything is measured."""
import json
import math
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import models
from bench.models import dense
from bench.tests.conftest import ROOT, SMOKE_CONFIGS
from bench.weights import arch_config, load_config, path_str

FILES = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
CONTRACT = ("arch_config", "leaves", "logits_at", "decode_step_flops", "paged_attention_call",
            "attention_layers")


def configuration(name: str) -> dict:
    return dict(SMOKE_CONFIGS[name[6:]]) if name.startswith("smoke:") else load_config(name)


def program_leaves(c: dict) -> dict:
    """Program leaf path -> (shape, dtype) of the served tree, without drawing it."""
    from repro.launch.serve import SERVE_RUN
    from repro.models import build_model

    tree = jax.eval_shape(build_model(arch_config(c), SERVE_RUN).init, jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {path_str(p): (x.shape, x.dtype) for p, x in flat}


@pytest.mark.parametrize("name", FILES + [f"smoke:{k}" for k in sorted(SMOKE_CONFIGS)])
def test_the_leaf_map_covers_exactly_the_program_tree(name):
    c = configuration(name)
    module = models.load(c)
    assert all(callable(getattr(module, f, None)) for f in CONTRACT)
    want = program_leaves(c)
    got = module.leaves(c)
    assert sorted(got) == sorted(want), "unmapped: %s; unused: %s" % (
        sorted(set(want) - set(got)), sorted(set(got) - set(want)))
    for p, leaf in got.items():
        assert leaf.program_shape == want[p][0], p
        assert leaf.role in ("norm", "bias", "matrix"), p
        assert leaf.role != "matrix" or leaf.fan_in > 0, p


def published_params(c: dict) -> int:
    """Parameters of a dense decoder from its published keys, the vocabulary
    padded to a multiple of 256 as the program pads it."""
    d, hq, hkv, hd, ff, n = dense._dims(c)
    vp = math.ceil(c["vocab_size"] / 256) * 256
    layer = 2 * d + d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff
    if c["qkv_bias"]:
        layer += (hq + 2 * hkv) * hd
    top = vp * d + d + (0 if c["tie_word_embeddings"] else d * vp)
    return n * layer + top


@pytest.mark.parametrize("name", [f for f in FILES if load_config(f)["model"] == "dense"])
def test_served_weights_weigh_what_the_published_keys_say(name):
    c = load_config(name)
    nbytes = sum(math.prod(shape) * dtype.itemsize for shape, dtype in program_leaves(c).values())
    assert nbytes == 2 * published_params(c)  # bfloat16
    rounded = {"qwen2.5-3b": 6.17, "phi3-mini": 7.64}  # as PERF.md gives them
    if name in rounded:
        assert round(nbytes / 1e9, 2) == rounded[name]


def test_work_counts_read_head_dim():
    """Two query heads of width 3 over one KV head at hidden 4: q, k, v and o
    are 3 wide per head, not 4 / 2."""
    c = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 3,
         "intermediate_size": 6, "num_hidden_layers": 1, "vocab_size": 10}
    # q,k,v 4*(2+1+1)*3 = 48; o 2*3*4 = 24; mlp 3*4*6 = 72; head 4*10 = 40
    assert dense.matmul_params(c) == 184
    flops, nbytes = dense.paged_attention_call(c, [5, 0])
    assert flops == 4 * 2 * 3 * 5
    assert nbytes == (2 * 1 * 3 * 5 + 2 * 2 * 3 * 2) * 2


def test_a_configuration_must_name_a_model_that_exists():
    c = {k: v for k, v in SMOKE_CONFIGS["gqa"].items() if k != "model"}
    with pytest.raises(KeyError, match="names no model"):
        models.load(c)
    with pytest.raises(FileNotFoundError, match="bench/models/no_such_model.py"):
        models.load(dict(c, model="no_such_model"))
    with pytest.raises(FileNotFoundError, match="bench/models/no_such_model.py"):
        arch_config(dict(c, model="no_such_model"))


def test_run_stops_with_code_2_where_the_model_is_missing(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    cfg_file = tmp_path / "bench" / "configs" / "qwen2.5-3b.json"
    cfg_file.write_text(json.dumps(dict(json.loads(cfg_file.read_text()), model="no_such_model")))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.5-3b.chat", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "bench/models/no_such_model.py" in p.stderr
    assert not p.stdout.strip()
