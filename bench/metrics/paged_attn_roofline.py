"""The paged decode-attention kernel's share of its roofline, in %.

For each call, the least time the chip could take for the operations and
bytes the kernel needs at the ``kv_lens`` it was given
(the model module's ``paged_attention_call``, `bench.work.roofline_s`),
summed, times the layers that call it (``attention_layers``), and divided
by the kernel's device time in the trace. At these shapes the memory
bound applies.
"""
from bench import work


def read(m):
    kernel_s = sum(v for k, v in m.trace["op_s"].items() if m.names["kernel"].search(k))
    n = m.trace["module_n"].get(m.names["decode"], 0)
    if kernel_s == 0 or n == 0 or not m.tw.attn_calls:
        return None
    per_step = sum(work.roofline_s(*m.model.paged_attention_call(m.cfg, kv), m.peak)[0]
                   for kv in m.tw.attn_calls) / len(m.tw.attn_calls)
    return 100.0 * per_step * m.model.attention_layers(m.cfg) * n / kernel_s
