"""How ``correct`` is decided: for a served model, and for training.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one
with the most served tokens, goes through the float32 reference of the
configuration's model module (``logits_at``, `bench.models`) with its
prompt and served tokens. For each served token the gap is the
reference's best logit minus the logit of the token served there; greedy
serving reads 0 where it agrees with the reference. The widest gap is
held against the cell's limit (``bench/limits/<workload>.json``), set in
PERF.md from the program's sound runs and from the fp8 control.

Training (`judge_train`): the program's first steps against the float32
reference's from the same weights and batches: each step's loss, and by
the worst leaf the norm of the first clipped gradient and of the change
after the last step.
"""
from __future__ import annotations

import json
import math
import statistics
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent


def load_limits(workload: str, root: Path = BENCH) -> dict:
    with open(root / "limits" / f"{workload}.json") as fh:
        return json.load(fh)


def sample(finished: list, seed: int, min_tokens: int, max_requests: int) -> list:
    """The longest finished request, then others in a seeded order, until the
    sample holds ``min_tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.tokens), r.rid))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    picked, served = [longest], len(longest.tokens)
    for i in order:
        if served >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        served += len(rest[i].tokens)
    return picked


def pack_rows(reqs: list, pad_to: int = 128):
    """Right-padded token matrix and the (sequence, position) of each served token."""
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)]) for r in reqs]
    s = -(-max(len(x) for x in seqs) // pad_to) * pad_to
    tokens = np.zeros((len(seqs), s), np.int32)
    rows, served = [], []
    for i, (r, x) in enumerate(zip(reqs, seqs)):
        tokens[i, : len(x)] = x
        for j, t in enumerate(r.tokens):
            rows.append((i, r.prompt_len - 1 + j))
            served.append(t)
    return tokens, np.asarray(rows, np.int32), np.asarray(served, np.int32)


def logit_gaps(cfg: dict, seed: int, reqs: list, chosen=None, quant=None) -> np.ndarray:
    """Per served token: the float32 reference's best logit minus the logit of
    ``chosen`` (the served tokens when None). With ``quant`` the chosen token
    is instead what the quantised reference ranks first at each position."""
    from bench import models

    logits_at = models.load(cfg).logits_at
    tokens, rows, served = pack_rows(reqs)
    with jax.default_matmul_precision("highest"):
        ref = logits_at(cfg, seed, jnp.asarray(tokens), jnp.asarray(rows))
        if quant is not None:
            low = logits_at(cfg, seed, jnp.asarray(tokens), jnp.asarray(rows), quant=quant)
            served = np.asarray(jnp.argmax(low, axis=-1))
    pick = jnp.asarray(served if chosen is None else chosen)
    gap = jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return np.asarray(gap)


def judge(cfg: dict, seed: int, finished: list, limits: dict) -> dict:
    """Compare a sample of served requests with the reference; the verdict and its numbers."""
    picked = sample(finished, seed, limits["min_served_tokens"], limits["max_requests"])
    if not picked:
        return {"correct": False,
                "numbers": {"max_logit_gap": {"value": None, "limit": limits["max_logit_gap"]}},
                "requests_compared": 0}
    gaps = logit_gaps(cfg, seed, picked)
    widest = float(gaps.max())
    ok = bool(np.isfinite(gaps).all() and widest <= limits["max_logit_gap"])
    return {
        "correct": ok,
        "numbers": {"max_logit_gap": {"value": widest, "limit": limits["max_logit_gap"]}},
        "served_tokens_compared": int(gaps.size),
        "tokens_off_reference_argmax": int((gaps > 0).sum()),
        "requests_compared": len(picked),
    }


#: a leaf whose first reference gradient is under this share of the median
#: leaf's moves under AdamW by round-off alone: left out of the change
MOVED = 1e-3


def judge_train(prog: dict, ref: dict, limits: dict) -> dict:
    """Compare the program's first training steps with the reference's.

    ``loss_gap``: the widest gap of a step's loss. ``grad_norm_gap`` and
    ``change_norm_gap``: by the worst leaf, the gap between the program's
    norm and the reference's (the first step's clipped gradient; the
    change after the last step), over the larger of that leaf's reference
    norm and the median leaf's. The change leaves out leaves whose first
    reference gradient is under `MOVED` of the median leaf's.
    """
    rg = ref["grad_norms"]
    median = statistics.median(rg.values())
    moved = [p for p in rg if rg[p] >= MOVED * median]

    def worst(key, leaves):
        r = ref[key]
        floor = statistics.median(r[p] for p in leaves)
        return max(abs(prog[key][p] - r[p]) / max(r[p], floor) for p in leaves)

    values = {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": worst("grad_norms", list(rg)),
        "change_norm_gap": worst("change_norms", moved),
    }
    ok = all(math.isfinite(v) and v <= limits[k] for k, v in values.items())
    return {"correct": ok,
            "numbers": {k: {"value": v, "limit": limits[k]} for k, v in values.items()},
            "leaves_compared": len(rg), "leaves_moved": len(moved)}
