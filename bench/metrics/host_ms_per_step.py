"""Host time of the scheduler, page pool and sensor fleet per decode step, in ms.

Read from the harness's host clock over the traced window: the time spent
in `ContinuousBatch` admission and billing, `PagedKVPool` table building,
appends and frees, and the fleet's marker, advance and attribution, over
the decode steps run in that window.
"""


def read(m):
    if m.tw.steps == 0:
        return None
    return m.tw.host_s / m.tw.steps * 1e3
