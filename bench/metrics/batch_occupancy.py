"""Share of the decode batch's slots that held a live request, in %.

100 × Σ ``live`` / Σ ``slots`` over the program's ``sched:step`` spans
(`ContinuousBatch.step_billing`, one per decode step) that start inside
the traced window.
"""
from bench import program_spans


def read(m):
    steps = program_spans.stats(m.tw.log_dir, "sched:step")
    slots = sum(s["slots"] for s in steps)
    if slots == 0:
        return None
    return 100.0 * sum(s["live"] for s in steps) / slots
