"""The decode step's share of the chip's bf16 peak, taken whole, in %.

Operations the step needs (2 per weight per live token, plus attention
over each row's live ``kv_len``: the model module's ``decode_step_flops``) over the
device time of the decode program times the peak. Padded slots and
recomputation count for nothing.
"""


def read(m):
    n = m.trace["module_n"].get(m.names["decode"], 0)
    if n == 0 or m.tw.steps == 0:
        return None
    flops = m.tw.step_flops / m.tw.steps * n
    return 100.0 * flops / (m.trace["module_s"][m.names["decode"]] * m.peak["bf16_flops_per_s"])
