"""Configurations and seeded weights, shared by the timed path and the reference.

A configuration file (``bench/configs/<name>.json``) holds the published
``config.json`` keys and names its model module (`bench.models`), which
makes the program's `ArchConfig` and maps every leaf of the program's
parameter tree. Weights never come from the program's own ``init``: every
leaf is drawn here from ``--seed``, keyed by its program path and, for
stacked layers, by its layer index, so that the reference can draw layer
``l`` alone and get the same bfloat16 values the program serves.
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

from bench import models
from bench.models import Leaf

BENCH = Path(__file__).resolve().parent


def load_config(name: str, root: Path = BENCH) -> dict:
    with open(root / "configs" / f"{name}.json") as fh:
        return json.load(fh)


def arch_config(c: dict):
    """The program's `ArchConfig` for configuration ``c``, as its model module makes it."""
    return models.load(c).arch_config(c)


def seed_key(seed: int) -> jax.Array:
    """A JAX key for any whole-number seed, including ones past 32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw_leaf(key, leaf: Leaf, dtype=jnp.bfloat16):
    """One layer's (or one unstacked) leaf, drawn as its role says."""
    x = jax.random.normal(key, leaf.shape, jnp.float32)
    if leaf.role == "norm":
        x = 1.0 + 0.1 * x
    elif leaf.role == "bias":
        x = 0.3 * x
    elif leaf.role == "matrix":
        x = x / math.sqrt(leaf.fan_in)
    else:
        raise ValueError(f"leaf {leaf.ref!r} has no known role: {leaf.role!r}")
    return x.astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def program_leaf(key, path: str, leaf: Leaf, dtype=jnp.bfloat16):
    """The leaf at program ``path``, all its layers stacked where it is
    per layer; ``key`` is `seed_key` of the run's seed."""
    k = _leaf_key(key, path)
    if leaf.layers:
        ks = jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(leaf.layers))
        return jax.vmap(lambda kk: draw_leaf(kk, leaf, dtype))(ks)
    return draw_leaf(k, leaf, dtype)


def make_params(model, seed: int, leaves: dict, dtype=jnp.bfloat16, shardings=None):
    """Every leaf of the program's parameter tree, drawn on the device in one
    jit (straight into ``shardings``, a matching tree, where given).
    ``leaves`` is the leaf map of the configuration ``model`` was built from."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    for path, leaf in flat:
        p = path_str(path)
        if p not in leaves or leaves[p].program_shape != leaf.shape:
            raise ValueError(f"the model's leaf map does not match the program at {p!r}: "
                             f"{leaves.get(p)} against {leaf.shape}")

    def build(key):
        drawn = [program_leaf(key, path_str(path), leaves[path_str(path)], dtype)
                 for path, _ in flat]
        return jax.tree_util.tree_unflatten(treedef, drawn)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def served_leaf(key, path: str, leaf: Leaf, layer):
    """The leaf at ``path`` (layer ``layer``, or None) as `make_params` serves it,
    widened to float32; ``key`` is `seed_key` of the run's seed."""
    k = _leaf_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return draw_leaf(k, leaf, jnp.bfloat16).astype(jnp.float32)


@partial(jax.jit, static_argnames=("path", "leaf", "sharding"))
def _change_norm(a, key, path, leaf, sharding):
    x = program_leaf(key, path, leaf, jnp.float32)
    if sharding is not None:
        x = jax.lax.with_sharding_constraint(x, sharding)
    return jnp.linalg.norm(a.astype(jnp.float32) - x)


def change_norms(arrays: dict, seed: int, leaves: dict) -> dict:
    """Per leaf (keyed by program path): the norm of its change from the float32
    value drawn from ``seed``; ``leaves`` is the model's leaf map. One leaf is
    drawn at a time, laid out as its changed copy is."""
    key = seed_key(seed)
    out = {}
    for p, a in arrays.items():
        sh = a.sharding if isinstance(a.sharding, jax.sharding.NamedSharding) else None
        out[p] = float(_change_norm(a, key, p, leaves[p], sh))
    return out
