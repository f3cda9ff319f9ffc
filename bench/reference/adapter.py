"""The reference's one way to its weights: draw them from the seed, as served.

The reference takes nothing the program made. It draws each weight with
the benchmark's own generator (`bench.weights`), under the key the
timed path's copy was drawn with, at the shape that the configuration's
model module (`bench.models`) gives from the published configuration,
and widens the served bfloat16 values to float32. The program's leaf
paths only name the keys; the reference reads each weight by its own
name from the same map.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import models
from bench.weights import program_leaf, seed_key, served_leaf


def _split(cfg: dict, layered: bool) -> tuple:
    """(path, leaf) of the per-layer leaves, or of the others, in path order."""
    return tuple(sorted((p, leaf) for p, leaf in models.load(cfg).leaves(cfg).items()
                        if bool(leaf.layers) == layered))


@partial(jax.jit, static_argnames=("items",))
def _draw_layer(key, layer, items):
    return {leaf.ref: served_leaf(key, p, leaf, layer) for p, leaf in items}


def layer_weights(cfg: dict, seed: int, layer: int) -> dict:
    return _draw_layer(seed_key(seed), jnp.int32(layer), _split(cfg, True))


@partial(jax.jit, static_argnames=("items",))
def _draw_top(key, items):
    out = {}
    for p, leaf in items:
        x = served_leaf(key, p, leaf, None)
        out[leaf.ref] = x[: leaf.rows]
        if leaf.tied:
            out[leaf.tied] = x.T
    return out


def top_weights(cfg: dict, seed: int) -> dict:
    """The leaves that are not per layer, by reference name, each cut to its
    ``rows``; a ``tied`` leaf also transposed, under the name it is tied to."""
    return _draw_top(seed_key(seed), _split(cfg, False))


def train_weights(cfg: dict, seed: int, sharding=None) -> dict:
    """Every weight as a training run starts from it: float32 as drawn, not
    rounded, the layers stacked; keyed by the program's leaf paths.
    ``sharding(shape)`` places each leaf, where given."""
    leaves = models.load(cfg).leaves(cfg)

    def draw(key):
        return {p: program_leaf(key, p, leaf, jnp.float32) for p, leaf in leaves.items()}

    out = None if sharding is None else {p: sharding(leaf.program_shape)
                                         for p, leaf in leaves.items()}
    return jax.jit(draw, out_shardings=out)(seed_key(seed))
