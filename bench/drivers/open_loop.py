"""Open-loop serving: requests are due on a Poisson schedule, whatever the server does.

End-to-end metrics: the 95th percentile of time to first token, taken
from when each request was due until the host holds its first token,
over every request due in the window; and the 95th percentile of every
gap between consecutive tokens of those requests, as the host receives
them. A request that never finishes counts as failed.
"""
from __future__ import annotations

import sys

import numpy as np

from bench.serving import Source, run_cell
from bench.traffic import open_loop, percentile


def run(ctx) -> dict:
    def make_source(vocab):
        return Source(reqs=open_loop(ctx.mix, ctx.seed, ctx.seconds, vocab))

    def end_to_end(reqs, attempted, window_s):
        ttft = [(r.times[0] - r.due_s) * 1e3 if r.times else np.inf for r in attempted]
        gaps = [g * 1e3 for r in attempted for g in np.diff(r.times)]
        lateness = [(r.admitted_s - r.due_s) * 1e3 for r in attempted if r.admitted_s is not None]
        print(f"open loop: {len(attempted)} requests due, rate "
              f"{len(attempted) / window_s:.3f}/s; ttft median {percentile(ttft, 50):.3f} ms, "
              f"p80 {percentile(ttft, 80):.3f} ms, p90 {percentile(ttft, 90):.3f} ms, "
              f"itl median {percentile(gaps, 50):.3f} ms over {len(gaps)} gaps; "
              f"generator lateness (due to admitted) median {percentile(lateness, 50):.3f} ms, "
              f"p95 {percentile(lateness, 95):.3f} ms", file=sys.stderr)
        return {"ttft_p95_ms": percentile(ttft, 95), "itl_p95_ms": percentile(gaps, 95)}

    return run_cell(ctx, make_source, end_to_end)
