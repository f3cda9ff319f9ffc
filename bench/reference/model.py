"""Plain float32 forward pass of a dense decoder (Qwen2 / Phi-3 style).

Written from the published description, not from the program: token
embedding, per layer RMSNorm -> q/k/v projections (+ bias where the
configuration has one) -> rotary embedding (rotate-half) -> causal
grouped-query attention -> output projection -> residual, RMSNorm ->
SwiGLU MLP (down(silu(gate(x)) * up(x))) -> residual, then a final RMSNorm
and the head (the embedding's transpose where tied). Matmuls run at
``highest`` precision, so float32 is float32 on a TPU too.

It runs one layer at a time over a batch of right-padded sequences, with
each layer's weights drawn just before use (`adapter.layer_weights`), so
it fits beside nothing: the program's state is freed before it runs.

``quant="fp8"`` is the control: every matmul's operands rounded to
float8_e4m3fn with a scale per row of the activations and per output
column of the weights (in training, the backward matmuls' too), the step
below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import adapter

E4M3_MAX = 448.0


def _fp8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _dot(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _mm_fp8(x, w):
    return _dot(_fp8(x, -1), _fp8(w, 0))


def _mm_fp8_fwd(x, w):
    return _mm_fp8(x, w), (x, w)


def _mm_fp8_bwd(res, dy):
    """Both backward matmuls in fp8 too, each operand scaled along the axis
    it keeps: dx = dy w^T, dw = x^T dy over every row."""
    x, w = res
    dx = _dot(_fp8(dy, -1), _fp8(w.T, 0))
    x2, dy2 = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
    return dx, _dot(_fp8(x2.T, -1), _fp8(dy2, 0))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(x, w, quant):
    return _mm_fp8(x, w) if quant == "fp8" else _dot(x, w)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """q: (S, Hq, D); k, v: (S, Hkv, D); causal, GQA by repeating kv heads."""
    s, hq, d = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=jax.lax.Precision.HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)


def block(x, w, c, quant=None):
    """One decoder layer over one sequence: x (S, d) float32 -> (S, d).

    ``w``: the layer's weights by reference name; ``c``: `frozen` config.
    """
    cfg = dict(c)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = _rmsnorm(x, w["ln1"], eps)
    q = _mm(a, w["wq"], quant)
    k = _mm(a, w["wk"], quant)
    v = _mm(a, w["wv"], quant)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    s = x.shape[0]
    q = _rope(q.reshape(s, hq, hd), theta)
    k = _rope(k.reshape(s, hkv, hd), theta)
    o = _attention(q, k, v.reshape(s, hkv, hd)).reshape(s, hq * hd)
    x = x + _mm(o, w["wo"], quant)
    m = _rmsnorm(x, w["ln2"], eps)
    gate, up = _mm(m, w["w_gate"], quant), _mm(m, w["w_up"], quant)
    return x + _mm(jax.nn.silu(gate) * up, w["w_down"], quant)


@partial(jax.jit, static_argnames=("c", "quant"))
def _layer(h, w, c, quant):
    """h: (N, S, d) float32 -> (N, S, d)."""
    return jax.lax.map(lambda x: block(x, w, c, quant), h)


@partial(jax.jit, static_argnames=("c", "quant"))
def _head(x, final_norm, head, c, quant):
    """x: (M, d) rows whose next token is scored -> (M, vocab) logits."""
    cfg = dict(c)
    x = _rmsnorm(x, final_norm, cfg["rms_norm_eps"])
    return _mm(x, head, quant)[:, : cfg["vocab_size"]]


def frozen(cfg: dict):
    """The configuration keys the reference reads, hashable for `jax.jit`;
    ``head_dim`` is ``hidden_size // num_attention_heads`` where not given."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "rope_theta", "vocab_size")
    hd = cfg.get("head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
    return tuple((k, cfg[k]) for k in keys) + (("head_dim", hd),)


def logits_at(cfg: dict, seed: int, tokens, rows, quant: str | None = None):
    """Reference logits of the next token at chosen positions.

    ``tokens``: (N, S) int32, right-padded (padding never reaches a real
    position through causal attention). ``rows``: (M, 2) int32 of
    (sequence, position) pairs. Returns (M, vocab_size) float32.
    """
    c = frozen(cfg)
    top = adapter.top_weights(cfg, seed)
    h = top["embed"][tokens]
    for layer in range(cfg["num_hidden_layers"]):
        h = _layer(h, adapter.layer_weights(cfg, seed, layer), c, quant)
    x = h[rows[:, 0], rows[:, 1]]
    return _head(x, top["final_norm"], top["head"], c, quant)
