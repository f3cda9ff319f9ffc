"""Time an admitted request waited in the scheduler's queue, in ms; the mean.

Read from the program's ``sched:queue`` spans (`ContinuousBatch.admit`,
one per admitted request, ``wait_ms`` = admission time less the due
time) that start inside the traced window. At chat's 2.4 req/s a 6-s
window holds about 14 of them, so this is a mean of few requests; it moves
with admission's rounds (once per 4 decode steps), not with a tail.
"""
from bench import program_spans


def read(m):
    waits = [s["wait_ms"] for s in program_spans.stats(m.tw.log_dir, "sched:queue")]
    if not waits:
        return None
    return sum(waits) / len(waits)
