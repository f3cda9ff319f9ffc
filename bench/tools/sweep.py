"""Find an open-loop cell's knee: the same set-up, driven at several fixed rates.

    python3 bench/tools/sweep.py --workload qwen2.5-3b.chat --seed 11 --seconds 45 \
        --rates 1.0 1.4 1.8 2.2

One process sets up once and then runs the cell's window at each rate in
turn (the mix's other parameters unchanged), printing one JSON line per
rate: requests due and finished, the tails and medians of time to first
token and of the gap between tokens, and how late admission ran behind
the schedule. Not part of a benchmark run; its result is written into
the cell's mix and into PERF.md. A rate is past the knee where admission
runs later behind the schedule in the last quarter of the requests than
in the first: the backlog grows through the window.
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
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    from repro.compile_cache import place_compile_cache, place_tpu_logs

    place_tpu_logs()
    place_compile_cache()
    import jax
    import numpy as np

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import models
    from bench.serving import Engine, Source, make_params_for, serve
    from bench.traffic import load_mix, open_loop, percentile
    from bench.weights import load_config

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = {x["name"]: x for x in bench["workloads"]}[args.workload]
    c, mix = load_config(w["config"]), load_mix(w["traffic"])
    module = models.load(c)
    cfg = module.arch_config(c)
    from repro.launch.serve import SERVE_RUN
    from repro.models import build_model

    t = time.perf_counter()
    engine = Engine(cfg, mix, make_params_for(build_model(cfg, SERVE_RUN), args.seed, module.leaves(c)),
                    work=partial(module.decode_step_flops, c))
    engine.warm_up(mix["prompt_len"]["values"])
    print(f"setup {time.perf_counter() - t:.1f} s", file=sys.stderr, flush=True)
    for rate in args.rates:
        m = dict(mix, arrival=dict(mix["arrival"], rate_per_s=rate))
        reqs = open_loop(m, args.seed, args.seconds, cfg.vocab_size)
        res = serve(engine, Source(reqs=reqs), args.seconds, float(mix["drain_s"]), args.seed)
        done = [r for r in reqs if len(r.tokens) == r.n_out]
        ttft = [(r.times[0] - r.due_s) * 1e3 for r in reqs if r.times]
        gaps = [g * 1e3 for r in reqs for g in np.diff(r.times)]
        late = [(r.admitted_s - r.due_s) * 1e3 for r in reqs if r.admitted_s is not None]
        q = max(1, len(late) // 4)
        print(json.dumps({
            "rate_per_s": rate, "due": len(reqs), "finished": len(done),
            "ttft_p50_ms": percentile(ttft, 50), "ttft_p95_ms": percentile(ttft, 95),
            "itl_p50_ms": percentile(gaps, 50), "itl_p95_ms": percentile(gaps, 95),
            "admit_late_p50_ms": percentile(late, 50), "admit_late_p95_ms": percentile(late, 95),
            "admit_late_first_quarter_p50_ms": percentile(late[:q], 50),
            "admit_late_last_quarter_p50_ms": percentile(late[-q:], 50),
            "mean_output_len": sum(r.n_out for r in reqs) / len(reqs),
            "decode_steps": res["steps"], "loop_s": res["t_end"],
            "output_tok_s_in_window": sum(1 for r in reqs for x in r.times
                                          if x < args.seconds) / args.seconds,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
