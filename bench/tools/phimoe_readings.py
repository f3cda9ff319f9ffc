"""Correctness readings for a routed model, and its held experts' real hit rate.

    python3 bench/tools/phimoe_readings.py --config phi3.5-moe --traffic batch-decode \
        --seconds 12 --seeds 1 2 3 --routing-seed 1

Per seed, as `bench/tools/limits.py` does for a cell: fresh weights, the
mix's own serving loop at its load for a short window and its drain, then
a sample of finished requests (`bench.check.sample`, at least
``--min-tokens`` served tokens or ``--max-requests`` requests). For three
sets of picks over the same prompts and served tokens it prints the
statistics of the gap to the float32 reference's best logit (largest,
mean, 99th percentile, share of tokens off the reference's best): the
program's served tokens, the reference's own picks computed in fp8 at
every matmul (``fp8``), and in fp8 at the experts' matmuls alone
(``fp8_experts``). A statistic that tells the program from both controls
can decide ``correct`` for this model; `bench.check` uses the largest gap
alone.

On ``--routing-seed`` the decode program also hands every layer's routing
to the host (a debug callback, so that run is slower), and the tool
prints the held pairs per live token and the held experts hit per
layer-step, beside uniform routing's expectation at the same live count
(the model module's ``expert_call`` counts). Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import defaultdict
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def gap_stats(g) -> dict:
    import numpy as np

    return {"max": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.percentile(g, 99)), "off_share": float((g > 0).mean())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="phi3.5-moe")
    ap.add_argument("--traffic", default="batch-decode")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--routing-seed", type=int, default=None)
    ap.add_argument("--min-tokens", type=int, default=1500)
    ap.add_argument("--max-requests", type=int, default=6)
    args = ap.parse_args()

    from repro.compile_cache import place_compile_cache, place_tpu_logs

    place_tpu_logs()
    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import repro.models.transformer as tr
    from bench import check, models
    from bench.serving import Engine, Source, make_params_for, serve
    from bench.traffic import Backlog, load_mix
    from bench.weights import load_config
    from repro.launch.serve import SERVE_RUN
    from repro.models import build_model
    from repro.models.moe import route

    c, mix = load_config(args.config), load_mix(args.traffic)
    module = models.load(c)
    cfg = module.arch_config(c)
    plain = tr._moe_serve
    stats = defaultdict(list)

    def on_host(idx, live):
        live, idx = np.asarray(live), np.asarray(idx)
        n = int(live.sum())
        if n:
            idx = idx[live]
            held = (idx >= cfg.expert_first) & (idx < cfg.expert_first + cfg.n_held)
            stats["live"].append(n)
            stats["pairs"].append(int(held.sum()))
            stats["hit"].append(len(set(idx[held].tolist())))

    def counted(h, p, cfg_, live=None):
        if live is not None:  # decode steps only
            logits = jnp.dot(h.astype(jnp.float32), p["router"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            _, idx = route(logits, cfg_.experts_per_token, cfg_.router, cfg_.router_jitter)
            jax.debug.callback(on_host, idx, live)
        return plain(h, p, cfg_, live)

    model = build_model(cfg, SERVE_RUN)
    for seed in args.seeds:
        t = time.perf_counter()
        tr._moe_serve = counted if seed == args.routing_seed else plain
        engine = Engine(cfg, mix, make_params_for(model, seed, module.leaves(c)),
                        work=partial(module.decode_step_flops, c))
        engine.warm_up(mix["prompt_len"]["values"])
        depth = int(mix["backlog"]["depth_per_slot"]) * int(mix["slots"])
        source = Source(backlog=Backlog(mix, seed, cfg.vocab_size), depth=depth)
        res = serve(engine, source, args.seconds, float(mix["drain_s"]), seed)
        engine.params = engine.pcache = None
        del engine
        reqs = [r for r in res["requests"].values() if source.attempted(r, args.seconds)]
        finished = [r for r in reqs if len(r.tokens) == r.n_out]
        picked = check.sample(finished, seed, args.min_tokens, args.max_requests)
        out = {"seed": seed, "requests": len(picked), "steps": res["steps"]}
        for quant in (None, "fp8", "fp8_experts"):
            gaps = check.logit_gaps(c, seed, picked, quant=quant)
            out[quant or "program"] = gap_stats(gaps)
        out["tokens"] = int(gaps.size)
        if seed == args.routing_seed and stats["live"]:
            live = np.asarray(stats["live"], float)
            k, held, e = cfg.experts_per_token, cfg.n_held, cfg.n_experts
            out["routing"] = {
                "layer_steps": len(live), "live_mean": float(live.mean()),
                "held_pairs_per_live": float(np.sum(stats["pairs"]) / live.sum()),
                "uniform_pairs_per_live": k * held / e,
                "hit_mean": float(np.mean(stats["hit"])),
                "uniform_hit_mean": float(np.mean(held * (1 - (1 - k / e) ** live))),
                "hit_hist": np.bincount(stats["hit"], minlength=held + 1).tolist()}
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
