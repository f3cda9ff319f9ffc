"""Dense decoder (Qwen2, Phi-3): GQA attention and a SwiGLU MLP in every layer.

A configuration file names this model with ``"model": "dense"``; it holds
the published ``config.json`` keys. The head width is ``head_dim`` where
the file gives it, else ``hidden_size // num_attention_heads``.

The work counts are the least a correct implementation must do: the live
tokens and their cached keys and values, the vocabulary as published (not
padded), no recomputation. A multiply-add is two operations.
"""
from __future__ import annotations

import numpy as np

from bench.models import Leaf
from bench.reference.model import logits_at  # noqa: F401  (the float32 reference)
from bench.reference.train import follow  # noqa: F401  (the float32 training reference)

PAD = 256  # the program pads its vocabulary rows to a multiple of this


def _dims(c: dict):
    """hidden, query heads, KV heads, head width, MLP width, layers."""
    d, hq = c["hidden_size"], c["num_attention_heads"]
    hd = c["head_dim"] if "head_dim" in c else d // hq
    return d, hq, c["num_key_value_heads"], hd, c["intermediate_size"], c["num_hidden_layers"]


def arch_config(c: dict):
    """The program's `ArchConfig` for a published ``config.json`` dict."""
    from repro.configs import ArchConfig

    d, hq, hkv, hd, ff, n = _dims(c)
    return ArchConfig(
        name=c["name"],
        family="dense",
        n_layers=n,
        d_model=d,
        n_heads=hq,
        n_kv_heads=hkv,
        d_ff=ff,
        vocab_size=c["vocab_size"],
        head_dim=hd,
        qkv_bias=c["qkv_bias"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"],
    )


def leaves(c: dict) -> dict:
    """Every leaf of the program's dense decoder tree, by program path."""
    d, hq, hkv, hd, ff, n = _dims(c)
    v = c["vocab_size"]
    vp = -(-v // PAD) * PAD
    tied = c["tie_word_embeddings"]

    def norm(ref):
        return Leaf(ref, (d,), "norm", layers=n)

    def matrix(ref, fan_in, fan_out):
        return Leaf(ref, (fan_in, fan_out), "matrix", fan_in=fan_in, layers=n)

    out = {
        # each row of the embedding drawn at the scale of a d-wide fan-in
        "embed": Leaf("embed", (vp, d), "matrix", fan_in=d, rows=v,
                      tied="head" if tied else None),
        "final_norm": Leaf("final_norm", (d,), "norm"),
        "layers/ln1": norm("ln1"),
        "layers/ln2": norm("ln2"),
        "layers/attn/wq": matrix("wq", d, hq * hd),
        "layers/attn/wk": matrix("wk", d, hkv * hd),
        "layers/attn/wv": matrix("wv", d, hkv * hd),
        "layers/attn/wo": matrix("wo", hq * hd, d),
        "layers/mlp/wg": matrix("w_gate", d, ff),
        "layers/mlp/wi": matrix("w_up", d, ff),
        "layers/mlp/wo2": matrix("w_down", ff, d),
    }
    if not tied:
        out["head"] = Leaf("head", (d, vp), "matrix", fan_in=d)
    if c["qkv_bias"]:
        for ref, width in (("bq", hq * hd), ("bk", hkv * hd), ("bv", hkv * hd)):
            out[f"layers/attn/{ref}"] = Leaf(ref, (width,), "bias", layers=n)
    return out


def matmul_params(c: dict) -> int:
    """Weights that every token multiplies: all layers' projections and the head."""
    d, hq, hkv, hd, ff, n_layers = _dims(c)
    per_layer = d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * ff
    return n_layers * per_layer + d * c["vocab_size"]


def attention_layers(c: dict) -> int:
    """Layers that call the paged decode-attention kernel: every one."""
    return c["num_hidden_layers"]


def paged_attention_call(c: dict, kv_lens, dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) one layer's paged decode-attention call needs.

    ``kv_lens``: (B,) tokens each row attends over, the new token included;
    0 for a padded row. Keys and values of the live tokens are read once;
    q is read and the output written for every row of the batch.
    """
    _, hq, hkv, hd, _, _ = _dims(c)
    kv = np.asarray(kv_lens, np.float64)
    flops = 4.0 * hq * hd * kv.sum()  # q.k and p.v
    nbytes = (2.0 * hkv * hd * kv.sum() + 2.0 * hq * hd * kv.size) * dtype_bytes
    return flops, nbytes


def decode_step_flops(c: dict, kv_lens) -> float:
    """Operations one decode step needs: 2 per weight per live token, plus attention."""
    kv = np.asarray(kv_lens)
    live = int((kv > 0).sum())
    attn = paged_attention_call(c, kv)[0] * attention_layers(c)
    return 2.0 * matmul_params(c) * live + attn
