"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) gives the prompt lengths as values
with shares, the output lengths as a clipped lognormal, and either an
open-loop arrival rate or a backlog depth. Every seed gets the same
sizes and gaps between arrivals, in the same order: sizes and gaps are
stratified quantiles of the mix's distributions, laid out in a fixed
low-discrepancy order (`spread_order`), so that every stretch of the
schedule holds short and long requests in the mix's proportions; only
the token ids are drawn from the seed. A window holds few requests (a
backlog of three slots admits about nine), so a seeded order would
change which contexts are live together, and with them the work. A training mix
(driver ``train``) gives the batch, the sequence length and how many
distinct batches to draw (`train_batches`).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist

import numpy as np

BENCH = Path(__file__).resolve().parent
_NORMAL = NormalDist()


@dataclass
class Req:
    rid: int
    due_s: float  # open loop: when it is sent; backlog: 0
    prompt: np.ndarray  # (prompt_len,) int32
    n_out: int  # output tokens to serve, the first from prefill
    # filled by the serving loop
    tokens: list = field(default_factory=list)
    times: list = field(default_factory=list)  # host clock, window-relative
    admitted_s: float | None = None

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def load_mix(name: str, root: Path = BENCH) -> dict:
    with open(root / "traffic" / f"{name}.json") as fh:
        return json.load(fh)


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def prompt_lengths(mix: dict, n: int) -> np.ndarray:
    """``n`` prompt lengths in the mix's shares (largest remainder), unshuffled."""
    vals = np.asarray(mix["prompt_len"]["values"], np.int64)
    shares = np.asarray(mix["prompt_len"]["shares"], np.float64)
    shares = shares / shares.sum()
    counts = np.floor(shares * n).astype(np.int64)
    rest = np.argsort(-(shares * n - counts), kind="stable")[: n - counts.sum()]
    counts[rest] += 1
    return np.repeat(vals, counts)


def output_lengths(mix: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the clipped lognormal output length."""
    o = mix["output_len"]
    z = np.array([_NORMAL.inv_cdf(p) for p in _strata(n)])
    x = o["median"] * np.exp(o["sigma"] * z)
    return np.clip(np.round(x), o["min"], o["max"]).astype(np.int64)


def max_lengths(mix: dict) -> tuple[int, int]:
    return max(mix["prompt_len"]["values"]), int(mix["output_len"]["max"])


#: one irrational step per quantity, so that their orders are unrelated
_STEP = {"prompt": math.sqrt(2.0) - 1.0, "output": (math.sqrt(5.0) - 1.0) / 2.0,
         "gap": math.sqrt(3.0) - 1.0}


def spread_order(n: int, what: str) -> np.ndarray:
    """A fixed permutation of ``range(n)`` whose every prefix spreads over the
    whole range: entry i is the rank of frac((i + 1) * step) among all n."""
    x = np.modf((np.arange(n) + 1) * _STEP[what])[0]
    return np.argsort(np.argsort(x, kind="stable"), kind="stable")


def _block(mix: dict, n: int, rng: np.random.Generator, vocab: int, rid0: int):
    lens = prompt_lengths(mix, n)[spread_order(n, "prompt")]
    outs = output_lengths(mix, n)[spread_order(n, "output")]
    return [
        Req(rid=rid0 + i, due_s=0.0,
            prompt=rng.integers(0, vocab, size=int(s), dtype=np.int32), n_out=int(o))
        for i, (s, o) in enumerate(zip(lens, outs))
    ]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """Requests due in ``[0, seconds)`` at the mix's mean rate (Poisson gaps)."""
    rng = np.random.default_rng(seed)
    n = max(1, round(mix["arrival"]["rate_per_s"] * seconds))
    reqs = _block(mix, n, rng, vocab, 0)
    gaps = -np.log(1.0 - _strata(n))[spread_order(n, "gap")]  # exponential quantiles
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    for r, t in zip(reqs, due):
        r.due_s = float(t)
    return reqs


class Backlog:
    """An endless queue of requests, drawn a block at a time."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self.rng = np.random.default_rng(seed)
        self.block = int(mix["backlog"]["block"])
        self._next: list[Req] = []
        self._rid = 0

    def take(self) -> Req:
        if not self._next:
            self._next = _block(self.mix, self.block, self.rng, self.vocab, self._rid)
            self._rid += self.block
        return self._next.pop(0)


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else math.nan


def train_batches(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """(batches, global_batch, seq_len + 1) token ids drawn from the seed: the
    rows a training cell feeds, every one of them different."""
    shape = (int(mix["batches"]), int(mix["global_batch"]), int(mix["seq_len"]) + 1)
    return np.random.default_rng([int(seed), 1]).integers(0, vocab, size=shape, dtype=np.int32)
