"""Readings that a serving cell's correctness limit is set from, and its fp8 control.

    python3 bench/tools/limits.py --workload qwen2.5-3b.chat --seconds 12 --seeds 1 2 3

For each seed, in one process: fresh weights, the cell's own serving
loop at its own load for a short window and its drain, then the same
sample of finished requests that a benchmark run would compare
(`bench.check.sample`). It prints, per seed, the program's widest logit
gap against the float32 reference, and the control's: at each position
of the same prompts and served tokens, the gap of the token that the
reference computed in fp8 (e4m3, per-row and per-column scales) ranks
first. The lower reading of the limit is the program's largest gap over
the seeds, the upper the control's smallest. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=1, help="also read the fp8 control")
    args = ap.parse_args()

    from repro.compile_cache import place_compile_cache, place_tpu_logs

    place_tpu_logs()
    place_compile_cache()
    import jax
    import numpy as np

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import check, models
    from bench.serving import Engine, Source, make_params_for, serve
    from bench.traffic import Backlog, load_mix, open_loop
    from bench.weights import load_config

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = {x["name"]: x for x in bench["workloads"]}[args.workload]
    c, mix = load_config(w["config"]), load_mix(w["traffic"])
    limits = check.load_limits(args.workload)
    module = models.load(c)
    cfg = module.arch_config(c)
    from repro.launch.serve import SERVE_RUN
    from repro.models import build_model

    model = build_model(cfg, SERVE_RUN)
    for seed in args.seeds:
        t = time.perf_counter()
        engine = Engine(cfg, mix, make_params_for(model, seed, module.leaves(c)),
                        work=partial(module.decode_step_flops, c))
        engine.warm_up(mix["prompt_len"]["values"])
        if mix["driver"] == "open_loop":
            reqs = open_loop(mix, seed, args.seconds, cfg.vocab_size)
            source = Source(reqs=reqs)
        else:
            depth = int(mix["backlog"]["depth_per_slot"]) * int(mix["slots"])
            source = Source(backlog=Backlog(mix, seed, cfg.vocab_size), depth=depth)
        res = serve(engine, source, args.seconds, float(mix["drain_s"]), seed)
        engine.params = engine.pcache = None
        del engine
        reqs = [r for r in res["requests"].values() if source.attempted(r, args.seconds)]
        finished = [r for r in reqs if len(r.tokens) == r.n_out]
        picked = check.sample(finished, seed, limits["min_served_tokens"],
                              limits["max_requests"])
        t_ref = time.perf_counter()
        gaps = check.logit_gaps(c, seed, picked)
        t_ref = time.perf_counter() - t_ref
        out = {"seed": seed, "requests": len(picked), "tokens": int(gaps.size),
               "program_max_gap": float(gaps.max()),
               "program_off_argmax": int((gaps > 0).sum()),
               "program_p99_gap": float(np.percentile(gaps, 99)), "reference_s": t_ref}
        if args.control:
            cg = check.logit_gaps(c, seed, picked, quant="fp8")
            out |= {"control_max_gap": float(cg.max()), "control_off_argmax": int((cg > 0).sum()),
                    "control_p99_gap": float(np.percentile(cg, 99))}
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
