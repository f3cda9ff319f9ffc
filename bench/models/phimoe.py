"""Phi-3.5-MoE (`PhiMoEForCausalLM`) at the expert share one chip holds.

A configuration file names this model with ``"model": "phimoe"``; it holds
the published ``config.json`` keys, with ``num_local_experts`` the experts
held here, ``router_experts`` the experts the router chooses among and
``expert_first`` the first held expert's id. Every layer: LayerNorm with
weight and bias, GQA attention with biases on q, k, v and o, a router with
no bias and sparsemixer top-2, SwiGLU experts; an untied head with a bias.

Every bias is drawn as a matrix of fan-in ``hidden_size`` would be
(std 1 / 64 at 4096, near the published ``initializer_range`` of 0.02), not at
`bench.weights`' bias scale of 0.3: the o bias, the same for every
token, is added to the residual stream in every layer, and at 0.3 per
element it outweighs the token's own part of the stream, so that every
token routes alike.

The work counts are the least a correct implementation must do, as
`bench.models.dense`'s are. The routing is not visible to the benchmark
(it happens inside the decode program), so the experts' counts are
expectations under uniform routing: each live token sends
``num_experts_per_tok`` pairs, a share ``held / router_experts`` of them
here, and a held expert is hit where any of the step's pairs reaches it.
"""
from __future__ import annotations

import numpy as np

from bench.models import Leaf
from bench.models.dense import PAD, _dims, attention_layers, paged_attention_call  # noqa: F401
from bench.reference.phimoe import logits_at  # noqa: F401  (the float32 reference)


def arch_config(c: dict):
    """The program's `ArchConfig` for a published ``config.json`` dict and its share."""
    from repro.configs import ArchConfig

    d, hq, hkv, hd, ff, n = _dims(c)
    return ArchConfig(
        name=c["name"],
        family="moe",
        n_layers=n,
        d_model=d,
        n_heads=hq,
        n_kv_heads=hkv,
        d_ff=ff,
        vocab_size=c["vocab_size"],
        head_dim=hd,
        qkv_bias=c["attention_bias"],
        o_bias=c["attention_bias"],
        head_bias=c["lm_head_bias"],
        rope_theta=float(c["rope_theta"]),
        norm="layer",
        norm_eps=float(c["rms_norm_eps"]),
        n_experts=c["router_experts"],
        experts_per_token=c["num_experts_per_tok"],
        router="sparsemixer",
        router_jitter=float(c["router_jitter_noise"]),
        experts_held=c["num_local_experts"],
        expert_first=c["expert_first"],
        tie_embeddings=c["tie_word_embeddings"],
    )


def leaves(c: dict) -> dict:
    """Every leaf of the program's moe decoder tree, by program path."""
    d, hq, hkv, hd, ff, n = _dims(c)
    v = c["vocab_size"]
    vp = -(-v // PAD) * PAD
    held = c["num_local_experts"]

    def layer(ref, shape, role, fan_in=0):
        return Leaf(ref, shape, role, fan_in=fan_in, layers=n)

    def bias(ref, shape, layers=n, rows=None):  # drawn small: see the module's docstring
        return Leaf(ref, shape, "matrix", fan_in=d, layers=layers, rows=rows)

    out = {
        "embed": Leaf("embed", (vp, d), "matrix", fan_in=d, rows=v),
        "head": Leaf("head", (d, vp), "matrix", fan_in=d),
        "head_b": bias("head_b", (vp,), layers=0, rows=v),
        "final_norm": Leaf("final_norm", (d,), "norm"),
        "final_norm_b": bias("final_norm_b", (d,), layers=0),
        "layers/attn/wq": layer("wq", (d, hq * hd), "matrix", d),
        "layers/attn/wk": layer("wk", (d, hkv * hd), "matrix", d),
        "layers/attn/wv": layer("wv", (d, hkv * hd), "matrix", d),
        "layers/attn/wo": layer("wo", (hq * hd, d), "matrix", hq * hd),
        "layers/attn/bq": bias("bq", (hq * hd,)),
        "layers/attn/bk": bias("bk", (hkv * hd,)),
        "layers/attn/bv": bias("bv", (hkv * hd,)),
        "layers/attn/bo": bias("bo", (d,)),
        "layers/moe/router": layer("router", (d, c["router_experts"]), "matrix", d),
        # the program's wg is the gated one (w1), wi the other (w3), wo the down (w2)
        "layers/moe/wg": layer("w1", (held, d, ff), "matrix", d),
        "layers/moe/wi": layer("w3", (held, d, ff), "matrix", d),
        "layers/moe/wo": layer("w2", (held, ff, d), "matrix", ff),
    }
    for norm in ("ln1", "ln2"):
        out[f"layers/{norm}"] = layer(norm, (d,), "norm")
        out[f"layers/{norm}_b"] = bias(f"{norm}_b", (d,))
    return out


def matmul_params(c: dict) -> int:
    """Weights that every live token multiplies: attention, router and head."""
    d, hq, hkv, hd, _, n = _dims(c)
    per_layer = d * (hq + 2 * hkv) * hd + hq * hd * d + d * c["router_experts"]
    return n * per_layer + d * c["vocab_size"]


def held_pairs(c: dict, live: int) -> float:
    """Expected (token, expert) pairs that reach experts held here."""
    return live * c["num_experts_per_tok"] * c["num_local_experts"] / c["router_experts"]


def expert_call(c: dict, kv_lens, dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) one layer's held-expert matmuls need in one decode step.

    ``kv_lens``: (B,) as `dense.paged_attention_call` takes them; a row is
    live where it is above 0. Each expected held pair multiplies w1, w3 and
    w2 once and is read in and written out once; the three matrices of each
    held expert are read once where any pair reaches it, which under uniform
    routing is ``held * (1 - (1 - k / router_experts) ** live)`` experts.
    """
    d, _, _, _, ff, _ = _dims(c)
    live = int((np.asarray(kv_lens) > 0).sum())
    k, held = c["num_experts_per_tok"], c["num_local_experts"]
    pairs = held_pairs(c, live)
    hit = held * (1.0 - (1.0 - k / c["router_experts"]) ** live)
    flops = 2.0 * 3 * d * ff * pairs
    nbytes = (3.0 * d * ff * hit + 2.0 * pairs * d) * dtype_bytes
    return flops, nbytes


def decode_step_flops(c: dict, kv_lens) -> float:
    """Operations one decode step needs: 2 per weight per live token for
    attention, router and head, the held experts' expected pairs, and
    attention over each live row's cache."""
    kv = np.asarray(kv_lens)
    live = int((kv > 0).sum())
    n = c["num_hidden_layers"]
    experts = expert_call(c, kv)[0] * n
    attn = paged_attention_call(c, kv)[0] * attention_layers(c)
    return 2.0 * matmul_params(c) * live + experts + attn
