"""The reference's one way to its weights: draw them from the seed, as served.

The reference takes nothing the program made. It draws each weight with
the benchmark's own generator (`bench.weights`), under the key the
timed path's copy was drawn with, at the shape the published
configuration gives, and widens the served bfloat16 values to float32.
The leaf paths below are where the program keeps each weight; they only
name the keys.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.weights import program_leaf, seed_key, served_leaf

PAD = 256  # the program pads its vocabulary rows to a multiple of this


def _shapes(cfg: dict):
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    layer = {
        "layers/ln1": (d,), "layers/ln2": (d,),
        "layers/attn/wq": (d, hq * hd), "layers/attn/wk": (d, hkv * hd),
        "layers/attn/wv": (d, hkv * hd), "layers/attn/wo": (hq * hd, d),
        "layers/mlp/wg": (d, ff), "layers/mlp/wi": (d, ff), "layers/mlp/wo2": (ff, d),
    }
    if cfg["qkv_bias"]:
        layer |= {"layers/attn/bq": (hq * hd,), "layers/attn/bk": (hkv * hd,),
                  "layers/attn/bv": (hkv * hd,)}
    return layer


def _ref_name(path: str) -> str:
    from bench.weights import PROGRAM_LEAVES

    return PROGRAM_LEAVES[path]


@partial(jax.jit, static_argnames=("shapes",))
def _draw_layer(key, layer, shapes):
    return {_ref_name(p): served_leaf(key, p, layer, s) for p, s in shapes}


def layer_weights(cfg: dict, seed: int, layer: int) -> dict:
    shapes = tuple(sorted(_shapes(cfg).items()))
    return _draw_layer(seed_key(seed), jnp.int32(layer), shapes)


def top_weights(cfg: dict, seed: int) -> dict:
    """Embedding (vocab_size rows), final norm and head (d, padded vocab)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    vp = -(-v // PAD) * PAD
    key = seed_key(seed)

    @jax.jit
    def draw(key):
        embed = served_leaf(key, "embed", None, (vp, d))
        head = embed.T if cfg["tie_word_embeddings"] else served_leaf(key, "head", None, (d, vp))
        return {"embed": embed[:v], "final_norm": served_leaf(key, "final_norm", None, (d,)),
                "head": head}

    return draw(key)


def train_weights(cfg: dict, seed: int, sharding=None) -> dict:
    """Every weight as a training run starts from it: float32 as drawn, not
    rounded, the layers stacked; keyed by the program's leaf paths.
    ``sharding(shape)`` places each leaf, where given."""
    d, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    vp = -(-v // PAD) * PAD
    shapes = {p: (n, *s) for p, s in _shapes(cfg).items()}
    shapes |= {"embed": (vp, d), "final_norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        shapes["head"] = (d, vp)

    def draw(key):
        return {p: program_leaf(key, p, s, n, jnp.float32) for p, s in shapes.items()}

    out = None if sharding is None else {p: sharding(s) for p, s in shapes.items()}
    return jax.jit(draw, out_shardings=out)(seed_key(seed))
