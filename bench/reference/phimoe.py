"""Plain float32 forward pass of Phi-3.5-MoE (`PhiMoEForCausalLM`) at the
expert share held here.

Written from the published description, not from the program: token
embedding, per layer LayerNorm (weight and bias) -> q/k/v projections with
bias -> rotary embedding (rotate-half) -> causal grouped-query attention ->
output projection with bias -> residual, LayerNorm -> the router's logits
over every expert -> sparsemixer top-2 -> each held expert's SwiGLU
``w2(silu(w1 x) * w3 x)`` weighted by its routing weight -> residual, then
a final LayerNorm and the head with its bias. Matmuls run at ``highest``
precision, one layer at a time, each layer's weights drawn just before
use (`adapter.layer_weights`). No sort, no kernel: every held expert
runs over every token and its weight is zero where it was not chosen.

Departures from the published model, both the configuration's cut:
plain RoPE at ``rope_theta`` (the published longrope factors are not in
this repository); and 8 of the 16 experts held, from ``expert_first``:
the router scores all of them, and what the experts held elsewhere would
add is left out, as the program leaves it out.

``quant="fp8"`` is the control: every matmul's operands rounded to
float8_e4m3fn (`bench.reference.model`); ``"fp8_experts"`` rounds only the
experts' matmuls.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import adapter
from .model import _attention, _mm, _rope


def _layernorm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def sparsemixer(scores, eps):
    """Top-2 routing of (S, E) logits: (weights (S, 2), experts (S, 2)).

    Expert one is the best score; its weight is its softmax share over the
    experts i whose (best - s_i) / max(|s_i|, best) is at most 2 eps. Expert
    two is the best of the others, its weight likewise, over the others,
    with its own score in place of the best; not renormalised.
    """
    n = scores.shape[-1]
    weights, experts = [], []
    left = scores
    for _ in range(2):
        e = jnp.argmax(left, axis=-1)
        best = jnp.max(left, axis=-1, keepdims=True)
        far = (best - scores) / jnp.maximum(jnp.abs(scores), best) > 2 * eps
        kept = jnp.where(far, -jnp.inf, left)
        p = jnp.exp(kept - best) / jnp.sum(jnp.exp(kept - best), axis=-1, keepdims=True)
        weights.append(jnp.sum(jnp.where(jnp.arange(n) == e[:, None], p, 0.0), axis=-1))
        experts.append(e)
        left = jnp.where(jnp.arange(n) == e[:, None], -jnp.inf, left)
    return jnp.stack(weights, -1), jnp.stack(experts, -1)


def experts(m, w, c, quant=None):
    """The held experts' weighted sum for tokens m (S, d): (S, d)."""
    cfg = dict(c)
    q = "fp8" if quant in ("fp8", "fp8_experts") else None
    weight, chosen = sparsemixer(_mm(m, w["router"], quant if quant == "fp8" else None),
                                 cfg["router_jitter_noise"])
    y = jnp.zeros_like(m)
    for j in range(cfg["num_local_experts"]):
        g = jnp.sum(jnp.where(chosen == cfg["expert_first"] + j, weight, 0.0), axis=-1)
        h = jax.nn.silu(_mm(m, w["w1"][j], q)) * _mm(m, w["w3"][j], q)
        y = y + g[:, None] * _mm(h, w["w2"][j], q)
    return y


def block(x, w, c, quant=None):
    """One decoder layer over one sequence: x (S, d) float32 -> (S, d)."""
    cfg = dict(c)
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    qa = quant if quant == "fp8" else None
    a = _layernorm(x, w["ln1"], w["ln1_b"], eps)
    q = _mm(a, w["wq"], qa) + w["bq"]
    k = _mm(a, w["wk"], qa) + w["bk"]
    v = _mm(a, w["wv"], qa) + w["bv"]
    s = x.shape[0]
    q = _rope(q.reshape(s, hq, hd), theta)
    k = _rope(k.reshape(s, hkv, hd), theta)
    o = _attention(q, k, v.reshape(s, hkv, hd)).reshape(s, hq * hd)
    x = x + _mm(o, w["wo"], qa) + w["bo"]
    return x + experts(_layernorm(x, w["ln2"], w["ln2_b"], eps), w, c, quant)


@partial(jax.jit, static_argnames=("c", "quant"))
def _layer(h, w, c, quant):
    """h: (N, S, d) float32 -> (N, S, d)."""
    return jax.lax.map(lambda x: block(x, w, c, quant), h)


@partial(jax.jit, static_argnames=("c", "quant"))
def _head(x, top, c, quant):
    """x: (M, d) rows whose next token is scored -> (M, vocab) logits."""
    cfg = dict(c)
    x = _layernorm(x, top["final_norm"], top["final_norm_b"], cfg["rms_norm_eps"])
    logits = _mm(x, top["head"], quant if quant == "fp8" else None)
    return logits[:, : cfg["vocab_size"]] + top["head_b"]


def frozen(cfg: dict):
    """The configuration keys the reference reads, hashable for `jax.jit`."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta", "vocab_size", "num_local_experts", "expert_first",
            "router_jitter_noise")
    hd = cfg.get("head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
    return tuple((k, cfg[k]) for k in keys) + (("head_dim", hd),)


def logits_at(cfg: dict, seed: int, tokens, rows, quant: str | None = None):
    """Reference logits of the next token at chosen positions.

    ``tokens``: (N, S) int32, right-padded (padding never reaches a real
    position through causal attention, and routing is per token).
    ``rows``: (M, 2) int32 of (sequence, position) pairs. Returns
    (M, vocab_size) float32.
    """
    c = frozen(cfg)
    top = adapter.top_weights(cfg, seed)
    h = top["embed"][tokens]
    for layer in range(cfg["num_hidden_layers"]):
        h = _layer(h, adapter.layer_weights(cfg, seed, layer), c, quant)
    return _head(h[rows[:, 0], rows[:, 1]], top, c, quant)
