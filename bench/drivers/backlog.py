"""Closed-backlog serving: a queue kept ``depth_per_slot`` x slots deep, no think time.

End-to-end metric: output tokens of real requests that reach the host
inside the window, over the window's length (padded slots count for
nothing). Where the mix dispatches steps ahead, the window ends once every
step sent before its time was up has reached the host, and all of those
tokens count over all of that time. Requests admitted in the window are
followed through a capped drain; one that has not finished by then counts
as failed.
"""
from __future__ import annotations

import sys

from bench.serving import Source, run_cell
from bench.traffic import Backlog, percentile


def run(ctx) -> dict:
    def make_source(vocab):
        depth = int(ctx.mix["backlog"]["depth_per_slot"]) * int(ctx.mix["slots"])
        return Source(backlog=Backlog(ctx.mix, ctx.seed, vocab), depth=depth)

    def end_to_end(reqs, attempted, window_s):
        in_window = sum(1 for r in reqs for t in r.times if t <= window_s)
        per_req = [len(r.tokens) / (r.times[-1] - r.times[0])
                   for r in attempted if len(r.times) > 1]
        print(f"backlog: {len(attempted)} requests admitted in the window, {in_window} tokens "
              f"delivered in its {window_s:.3f} s; per-request decode rate median "
              f"{percentile(per_req, 50):.3f} tokens/s", file=sys.stderr)
        return {"output_tok_s": in_window / window_s}

    return run_cell(ctx, make_source, end_to_end)
