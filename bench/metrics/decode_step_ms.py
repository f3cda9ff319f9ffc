"""Device time of one decode step (`decode_step_paged` and its greedy pick), in ms.

Sum of the device durations of the decode program in the trace over the
number of times it ran there.
"""


def read(m):
    n = m.trace["module_n"].get(m.names["decode"], 0)
    if n == 0:
        return None
    return m.trace["module_s"][m.names["decode"]] / n * 1e3
