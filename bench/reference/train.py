"""Plain float32 training reference: next-token cross-entropy, its gradient, AdamW.

Written from the published descriptions, not from the program: the
decoder of `bench.reference.model` (its `block` scanned over the stacked
layers, each recomputed in the backward pass so that it fits), the mean
cross-entropy of the next token over the published vocabulary, the
gradient clipped to a global norm, and AdamW (decoupled weight decay on
every leaf, bias-corrected moments) under linear warm-up and cosine
decay. Matmuls run at ``highest``; ``quant="fp8"`` is the control, as in
`bench.reference.model`.

`follow` starts from the seeded float32 weights and takes the given
batches, one step each, and returns each step's loss, each leaf's clipped
gradient norm at the first step and each leaf's change after the last.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench import models
from bench.weights import change_norms

from . import adapter
from .model import _mm, _rmsnorm, block, frozen


def spread_leaf(devices: list):
    """Sharding of a leaf over ``devices``: its largest axis that they divide,
    or the whole leaf on each where none does."""
    mesh = Mesh(np.asarray(devices), ("all",))

    def place(shape):
        axes = [a for a in np.argsort(shape)[::-1] if shape[a] % len(devices) == 0]
        spec = [None] * len(shape)
        if axes:
            spec[axes[0]] = "all"
        return NamedSharding(mesh, P(*spec))

    return place


def spread(devices: list, batches) -> list:
    """Each (B, S+1) batch placed on ``devices``, split by rows where they divide B."""
    place = spread_leaf(devices)
    return [jax.device_put(b, place((b.shape[0], 1))) for b in batches]


def loss(w: dict, tokens, c, names, quant=None):
    """Mean next-token cross-entropy of ``tokens`` (B, S+1) under weights ``w``
    (by program path); ``names``: (path, reference name, per layer) of each."""
    cfg = dict(c)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    layers = {ref: w[p] for p, ref, layered in names if layered}
    w = {ref: w[p] for p, ref, layered in names if not layered}
    x = w["embed"][inputs]

    @jax.checkpoint
    def body(h, lw):
        return jax.vmap(lambda s: block(s, lw, c, quant))(h), None

    x, _ = jax.lax.scan(body, x, layers)
    x = _rmsnorm(x, w["final_norm"], cfg["rms_norm_eps"])
    head = w["head"] if "head" in w else w["embed"].T
    logits = _mm(x, head[:, : cfg["vocab_size"]], quant)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def lr_at(opt: dict, step: int) -> float:
    """Learning rate of step ``step`` (0-based): linear warm-up, then cosine
    decay to ``min_lr_frac`` of the peak at ``total_steps``."""
    warm = min(1.0, (step + 1) / max(1, opt["warmup_steps"]))
    frac = min(max((step - opt["warmup_steps"])
                   / max(1, opt["total_steps"] - opt["warmup_steps"]), 0.0), 1.0)
    lo = opt["min_lr_frac"]
    return opt["lr"] * warm * (lo + (1 - lo) * 0.5 * (1 + math.cos(math.pi * frac)))


@partial(jax.jit, static_argnames=("c", "names", "quant", "opt"), donate_argnums=(0, 1, 2))
def _step(w, m, v, tokens, lr, t, c, names, quant, opt):
    o = dict(opt)
    b1, b2 = o["b1"], o["b2"]
    value, g = jax.value_and_grad(loss)(w, tokens, c, names, quant)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    scale = jnp.minimum(1.0, o["clip_norm"] / (norm + 1e-6)) if o["clip_norm"] else 1.0
    g = {p: x * scale for p, x in g.items()}
    m = {p: b1 * m[p] + (1 - b1) * g[p] for p in g}
    v = {p: b2 * v[p] + (1 - b2) * g[p] * g[p] for p in g}

    def new(p):
        m_hat, v_hat = m[p] / (1 - b1 ** t), v[p] / (1 - b2 ** t)
        return w[p] - lr * (m_hat / (jnp.sqrt(v_hat) + o["eps"]) + o["weight_decay"] * w[p])

    return {p: new(p) for p in w}, m, v, value, {p: jnp.linalg.norm(x) for p, x in g.items()}


def follow(cfg: dict, seed: int, batches: list, opt: dict, quant=None, sharding=None) -> dict:
    """Take one AdamW step per batch from the seeded weights.

    ``opt``: lr, b1, b2, eps, weight_decay, clip_norm, warmup_steps,
    total_steps, min_lr_frac. ``sharding(shape)`` places each leaf, and
    the batches should be placed to match. Returns ``losses`` (one per
    step), ``grad_norms`` (per leaf, the first step's clipped gradient)
    and ``change_norms`` (per leaf, after the last step).
    """
    c, o = frozen(cfg), tuple(sorted(opt.items()))
    leaves = models.load(cfg).leaves(cfg)
    names = tuple(sorted((p, leaf.ref, bool(leaf.layers)) for p, leaf in leaves.items()))
    w = adapter.train_weights(cfg, seed, sharding)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            w, m, v, value, gn = _step(w, m, v, tokens, jnp.float32(lr_at(opt, i)),
                                       jnp.float32(i + 1), c, names, quant, o)
            losses.append(float(value))
            if first is None:
                first = {p: float(x) for p, x in gn.items()}
    del m, v
    return {"losses": losses, "grad_norms": first,
            "change_norms": change_norms(w, seed, leaves)}
