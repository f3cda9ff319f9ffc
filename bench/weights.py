"""Model configurations and seeded weights, shared by the timed path and the reference.

A configuration file (``bench/configs/<name>.json``) holds the published
``config.json`` keys. `arch_config` turns it into the program's
`ArchConfig`. Weights never come from the program's own ``init``: every
leaf is drawn here from ``--seed``, keyed by its name and, for stacked
layers, by its layer index, so that the reference can draw layer ``l``
alone and get the same bfloat16 values the program serves.
"""
from __future__ import annotations

import json
import math
import zlib
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent

#: the reference's name for each leaf of the program's dense decoder tree
#: (path in the program's pytree -> reference name); the one adapter
PROGRAM_LEAVES = {
    "embed": "embed",
    "head": "head",
    "final_norm": "final_norm",
    "layers/ln1": "ln1",
    "layers/ln2": "ln2",
    "layers/attn/wq": "wq",
    "layers/attn/wk": "wk",
    "layers/attn/wv": "wv",
    "layers/attn/wo": "wo",
    "layers/attn/bq": "bq",
    "layers/attn/bk": "bk",
    "layers/attn/bv": "bv",
    "layers/mlp/wg": "w_gate",
    "layers/mlp/wi": "w_up",
    "layers/mlp/wo2": "w_down",
}


def load_config(name: str, root: Path = BENCH) -> dict:
    with open(root / "configs" / f"{name}.json") as fh:
        return json.load(fh)


def arch_config(c: dict):
    """The program's `ArchConfig` for a published ``config.json`` dict."""
    from repro.configs import ArchConfig

    d, h = c["hidden_size"], c["num_attention_heads"]
    return ArchConfig(
        name=c["name"],
        family="dense",
        n_layers=c["num_hidden_layers"],
        d_model=d,
        n_heads=h,
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        head_dim=c.get("head_dim", d // h),
        qkv_bias=c["qkv_bias"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"],
    )


def seed_key(seed: int) -> jax.Array:
    """A JAX key for any whole-number seed, including ones past 32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw_leaf(key, ref_name: str, shape, dtype=jnp.bfloat16):
    """One layer's (or one unstacked) leaf: its distribution depends on its role."""
    if ref_name in ("ln1", "ln2", "final_norm"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif ref_name in ("bq", "bk", "bv"):
        x = 0.3 * jax.random.normal(key, shape, jnp.float32)
    elif ref_name == "embed":
        x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-1])
    else:  # a (fan_in, fan_out) matrix
        x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])
    return x.astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def program_leaf(key, path: str, shape, n_layers: int, dtype=jnp.bfloat16):
    """The leaf at program ``path`` of shape ``shape``, all layers stacked
    where it is a layer's; ``key`` is `seed_key` of the run's seed."""
    name, k = PROGRAM_LEAVES[path], _leaf_key(key, path)
    if path.startswith("layers/"):
        ks = jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(n_layers))
        return jax.vmap(lambda kk: draw_leaf(kk, name, shape[1:], dtype))(ks)
    return draw_leaf(k, name, shape, dtype)


def make_params(model, seed: int, dtype=jnp.bfloat16, shardings=None):
    """Every leaf of the program's parameter tree, drawn on the device in one
    jit (straight into ``shardings``, a matching tree, where given)."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    n_layers = model.cfg.n_layers

    def build(key):
        leaves = [program_leaf(key, path_str(path), leaf.shape, n_layers, dtype)
                  for path, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def served_leaf(key, path: str, layer, shape):
    """The leaf at ``path`` (layer ``layer``, or None) as `make_params` serves it,
    widened to float32; ``key`` is `seed_key` of the run's seed."""
    k = _leaf_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return draw_leaf(k, PROGRAM_LEAVES[path], shape, jnp.bfloat16).astype(jnp.float32)


@partial(jax.jit, static_argnames=("path", "n_layers", "sharding"))
def _change_norm(a, key, path, n_layers, sharding):
    x = program_leaf(key, path, a.shape, n_layers, jnp.float32)
    if sharding is not None:
        x = jax.lax.with_sharding_constraint(x, sharding)
    return jnp.linalg.norm(a.astype(jnp.float32) - x)


def change_norms(leaves: dict, seed: int, n_layers: int) -> dict:
    """Per leaf (keyed by program path): the norm of its change from the float32
    value drawn from ``seed``; one leaf is drawn at a time, laid out as its
    changed copy is."""
    key = seed_key(seed)
    out = {}
    for p, a in leaves.items():
        sh = a.sharding if isinstance(a.sharding, jax.sharding.NamedSharding) else None
        out[p] = float(_change_norm(a, key, p, n_layers, sh))
    return out
