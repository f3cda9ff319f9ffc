"""Paged decode-attention benchmark: conformance, churny throughput, energy.

Three gated sections (the run exits nonzero unless every gate holds):

* **conformance** — the paged Pallas kernel must match the shared ragged
  oracle (`ragged_decode_ref`) on ragged batches whose lengths include 0
  and exactly-full, and ``kv_len == 0`` rows must be **exact zeros** (the
  serve loop's free/draining slots feed those rows — the NaN this PR
  fixes in the dense kernel must never come back in the paged one);
* **throughput** — a churny ragged serve workload (slots retiring and
  re-admitting at different fill stages) decoded through the paged path
  (page-indirect KV writes + page-table flash-decode over the *live*
  pages) must sustain at least the dense-cache serve path's decoded
  tokens/s (slab scatter + ragged flash-decode over the run-global
  ``S_max`` slab — the dense grid streams every allocated block whether
  or not anyone is that long);
* **energy** — `repro.power.tuner.EnergyTuner` sweeps the kernel's
  page-size × block × buffer-depth space across a DVFS ladder, scored
  marker-free by `AttributionStrategy` (changepoint-segmented per-launch
  energy), and the resulting latency × J/token Pareto front must be
  non-degenerate (>= 2 distinct points): big pages buy speed with
  over-fetched joules, so a healthy cost model cannot collapse to one
  point.

    PYTHONPATH=src python -m benchmarks.paged_decode [--smoke] [--json PATH]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import decode_attention
from repro.kernels.paged_attention import (
    PagedKVPool,
    init_page_arrays,
    pack_prefill_pages,
    paged_decode_attention,
    paged_tuner_model,
    pages_for,
    ragged_decode_ref,
)
from repro.power.tpu_model import DvfsState
from repro.power.tuner import EnergyTuner, attribution_strategy

from .common import BenchReport, add_json_arg

TOL = dict(rtol=1e-3, atol=1e-3)


# --------------------------------------------------------------------------- conformance
def _build_paged(rng, kv_lens, ps, max_pages, hkv, d):
    pool = PagedKVPool(n_pages=1 + len(kv_lens) * max_pages, page_size=ps)
    kp, vp = init_page_arrays(pool.n_pages, ps, hkv, d, jnp.float32)
    s = max_pages * ps
    kd = np.zeros((len(kv_lens), s, hkv, d), np.float32)
    vd = np.zeros_like(kd)
    slot_rids = []
    for r, ln in enumerate(kv_lens):
        if ln == 0:
            slot_rids.append(None)
            continue
        pages = pool.alloc(r, ln)
        pool.note_tokens(r, ln)
        k = rng.normal(size=(ln, hkv, d)).astype(np.float32)
        v = rng.normal(size=(ln, hkv, d)).astype(np.float32)
        kd[r, :ln], vd[r, :ln] = k, v
        kp, vp = pack_prefill_pages(
            kp, vp, jnp.asarray(k), jnp.asarray(v), jnp.asarray(pages, jnp.int32)
        )
        slot_rids.append(r)
    table = jnp.asarray(pool.table(slot_rids, max_pages))
    lens = jnp.asarray(pool.kv_lens(slot_rids))
    return kp, vp, table, lens, jnp.asarray(kd), jnp.asarray(vd)


def bench_conformance(report: BenchReport) -> list[str]:
    failures: list[str] = []
    rng = np.random.default_rng(0)
    cases = [
        # (ps, max_pages, hq, hkv, d, ragged lens incl. 0 and exactly-full)
        (16, 4, 4, 2, 64, (0, 1, 37, 64)),
        (32, 2, 8, 2, 64, (0, 33, 64)),
        (8, 3, 4, 1, 32, (24, 5, 0)),
    ]
    worst = 0.0
    zero_ok = True
    for ps, max_pages, hq, hkv, d, kv_lens in cases:
        kp, vp, table, lens, kd, vd = _build_paged(rng, kv_lens, ps, max_pages, hkv, d)
        q = jnp.asarray(rng.normal(size=(len(kv_lens), hq, d)), jnp.float32)
        out = np.asarray(paged_decode_attention(q, kp, vp, table, lens))
        ref = np.asarray(ragged_decode_ref(q, kd, vd, lens))
        err = float(np.abs(out - ref).max())
        worst = max(worst, err)
        for row, ln in enumerate(kv_lens):
            if ln == 0 and not (out[row] == 0.0).all():
                zero_ok = False
    report.emit("paged_decode_worst_abs_err", worst, "paged kernel vs ragged oracle")
    if not report.gate(
        "paged:conformance", worst <= TOL["atol"], value=worst, limit=TOL["atol"],
        detail="max |paged - ragged_decode_ref| over ragged batches",
    ):
        failures.append(f"paged kernel diverges from the ragged oracle by {worst:.2e}")
    if not report.gate(
        "paged:kv0-exact-zero", zero_ok,
        detail="kv_len == 0 rows must be exact zeros, never NaN",
    ):
        failures.append("a kv_len == 0 row was not exact zeros")
    return failures


# --------------------------------------------------------------------------- throughput
def bench_churn_throughput(report: BenchReport, smoke: bool) -> list[str]:
    """Dense-cache vs paged decode step rate on one churny ragged workload.

    Both paths run their actual serve building blocks under identical
    churn: per step, the dense path scatters the new token into a
    run-global ``(B, S_max)`` slab and flash-decodes over *all* of it
    (blocks past ``kv_len`` masked but streamed); the paged path writes
    through the page table and flash-decodes only the pages the live
    requests own.  Every ``churn_every`` steps one slot retires (one
    dead ``kv_len == 0`` step — both kernels' zero contract on the hot
    path) and is re-admitted at the prompt length.
    """
    failures: list[str] = []
    b, hq, hkv, d = 4, 4, 2, 64
    ps = 64
    prompt = 96
    s_max = 512 if smoke else 2048  # dense slab: run-global worst case
    n_steps = 24 if smoke else 80
    churn_every = 4
    max_pages = pages_for(prompt + n_steps, ps) + 1

    rng = np.random.default_rng(1)
    key = jax.random.PRNGKey(1)

    @jax.jit
    def dense_step(q, kc, vc, knew, vnew, lens, live):
        iota = jnp.arange(s_max)[None, :, None, None]
        write = (iota == lens[:, None, None, None]) & live[:, None, None, None]
        kc = jnp.where(write, knew[:, None], kc)
        vc = jnp.where(write, vnew[:, None], vc)
        new_len = jnp.where(live, lens + 1, 0)
        return decode_attention(q, kc, vc, new_len, bk=ps), kc, vc

    @jax.jit
    def paged_step(q, kp, vp, table, lens, live):
        page = jnp.where(live, table[jnp.arange(b), lens // ps], 0)
        off = lens % ps
        knew = jax.random.normal(jax.random.PRNGKey(0), (b, hkv, d), jnp.float32)
        kp = kp.at[:, page, off].set(knew.swapaxes(0, 1))
        vp = vp.at[:, page, off].set(knew.swapaxes(0, 1))
        new_len = jnp.where(live, lens + 1, 0)
        return paged_decode_attention(q, kp, vp, table, new_len), kp, vp

    def run_dense() -> float:
        kc = jnp.zeros((b, s_max, hkv, d), jnp.float32)
        vc = jnp.zeros_like(kc)
        lens = np.full(b, prompt, np.int64)
        live = np.ones(b, bool)
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        knew = jnp.asarray(rng.normal(size=(b, hkv, d)), jnp.float32)
        # warm the compile outside the timed region
        o, kc_w, _ = dense_step(q, kc, vc, knew, knew, jnp.asarray(lens), jnp.asarray(live))
        o.block_until_ready()
        t0 = time.perf_counter()
        for step in range(n_steps):
            if step % churn_every == churn_every - 1:
                slot = step // churn_every % b
                live[slot], lens[slot] = False, 0  # retire: one dead step
            elif step % churn_every == 0 and not live[step // churn_every % b]:
                slot = step // churn_every % b
                live[slot], lens[slot] = True, prompt  # re-admit at prompt
            o, kc, vc = dense_step(
                q, kc, vc, knew, knew, jnp.asarray(lens), jnp.asarray(live)
            )
            o.block_until_ready()
            lens[live] += 1
        return time.perf_counter() - t0

    def run_paged() -> float:
        pool = PagedKVPool(n_pages=1 + b * max_pages, page_size=ps)
        kp, vp = init_page_arrays(pool.n_pages, ps, hkv, d, jnp.float32)
        slot_rids = []
        for r in range(b):
            pool.note_tokens(r, prompt) if pool.alloc(r, prompt + n_steps) else None
            slot_rids.append(r)
        next_rid = b
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        table = jnp.asarray(pool.table(slot_rids, max_pages))
        lens = jnp.asarray(pool.kv_lens(slot_rids))
        live = jnp.asarray([r is not None for r in slot_rids])
        o, kp_w, _ = paged_step(q, kp, vp, table, lens, live)
        o.block_until_ready()
        t0 = time.perf_counter()
        for step in range(n_steps):
            if step % churn_every == churn_every - 1:
                slot = step // churn_every % b
                if slot_rids[slot] is not None:
                    pool.free(slot_rids[slot])
                    slot_rids[slot] = None  # retire: pages back to the pool
            elif step % churn_every == 0 and slot_rids[step // churn_every % b] is None:
                slot = step // churn_every % b
                if pool.alloc(next_rid, prompt + n_steps) is not None:
                    pool.note_tokens(next_rid, prompt)
                    slot_rids[slot] = next_rid
                    next_rid += 1
            table = jnp.asarray(pool.table(slot_rids, max_pages))
            lens = jnp.asarray(pool.kv_lens(slot_rids))
            live = jnp.asarray([r is not None for r in slot_rids])
            o, kp, vp = paged_step(q, kp, vp, table, lens, live)
            o.block_until_ready()
            for r in slot_rids:
                if r is not None:
                    pool.append(r)
        return time.perf_counter() - t0

    # best-of-N: single timed passes are too exposed to scheduler noise
    reps = 2 if smoke else 3
    dense_s = min(run_dense() for _ in range(reps))
    paged_s = min(run_paged() for _ in range(reps))
    dense_tps = b * n_steps / dense_s
    paged_tps = b * n_steps / paged_s
    ratio = paged_tps / dense_tps if dense_tps else 0.0
    report.emit(
        "paged_decode_dense_tokens_per_s", dense_tps,
        f"dense slab S_max={s_max}, churny ragged workload",
    )
    report.emit(
        "paged_decode_paged_tokens_per_s", paged_tps,
        f"page size {ps}, {max_pages}-page tables, same workload",
    )
    report.emit("paged_decode_speedup", ratio, "paged / dense decoded tokens/s")
    if not report.gate(
        "paged:throughput", ratio >= 1.0, value=ratio, limit=1.0,
        detail="paged must sustain the dense-cache serve path's tokens/s",
    ):
        failures.append(
            f"paged path decoded {ratio:.2f}x the dense rate (gate: >= 1.0x)"
        )
    return failures


# --------------------------------------------------------------------------- energy sweep
def bench_energy_sweep(report: BenchReport, smoke: bool) -> list[str]:
    failures: list[str] = []
    b = 64
    kernel = paged_tuner_model(b=b, kv_mean=600.0)  # ragged mean, off page grid
    tuner = EnergyTuner()
    strategy = attribution_strategy(seed=0, n_trials=3 if smoke else 7)
    dvfs = [DvfsState(1.0), DvfsState(0.85), DvfsState(0.7)]
    res = tuner.tune(kernel, strategy, dvfs_states=dvfs)
    front = res.pareto_front()

    # the frontier in serving units: per-step latency x J/token
    pts = [(r.time_s * 1e6, r.joules / b * 1e3, r.config, r.dvfs_scale) for r in front]
    for i, (lat_us, mj_tok, cfg, scale) in enumerate(pts):
        report.emit(
            f"paged_pareto_{i}_latency_us", lat_us,
            f"page={cfg['page_size']} bk={cfg['bk']} depth={cfg['depth']} "
            f"dvfs={scale:.2f}: {mj_tok:.4f} mJ/token",
        )
        report.record(f"paged_pareto_{i}_mj_per_token", mj_tok)
    report.emit("paged_tuner_configs", float(len(res.records)),
                f"{len(front)}-point Pareto front, "
                f"{res.total_tuning_time_s:.1f}s modelled tuning time")
    fast, eff = res.fastest(), res.most_efficient()
    report.record("paged_tuner_fastest_us", fast.time_s * 1e6)
    report.record("paged_tuner_most_efficient_mj_tok", eff.joules / b * 1e3)

    distinct = {(round(lat, 3), round(mj, 6)) for lat, mj, _, _ in pts}
    if not report.gate(
        "paged:pareto-nondegenerate", len(distinct) >= 2, value=len(distinct),
        limit=2, detail="latency x J/token front must trade off, not collapse",
    ):
        failures.append(
            f"energy sweep produced a degenerate Pareto front ({len(distinct)} point)"
        )
    # the tradeoff must be real: the fastest config must not also be the
    # most efficient one (otherwise the cost model has no energy axis)
    if not report.gate(
        "paged:speed-efficiency-tradeoff",
        fast.config != eff.config or fast.dvfs_scale != eff.dvfs_scale,
        detail="fastest and most-efficient variants must differ",
    ):
        failures.append("fastest == most-efficient: cost model has no tradeoff")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    add_json_arg(ap)
    args = ap.parse_args(argv)

    report = BenchReport("paged_decode", {"smoke": bool(args.smoke)})
    failures = bench_conformance(report)
    failures += bench_churn_throughput(report, args.smoke)
    failures += bench_energy_sweep(report, args.smoke)
    ok = report.finish(failures, args.json)
    for f in failures:
        print(f"FAIL: {f}")
    print(f"paged_decode: {'ok' if ok else 'FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
