"""The held-expert grouped-matmul kernel's share of its roofline, in %.

For each traced decode step, the least time the chip could take for the
operations and bytes one layer's held-expert matmuls need at that step's
``kv_lens`` (the model module's ``expert_call``, `bench.work.roofline_s`),
times the layers, averaged over the steps, times the decode programs in
the trace, over the self time of the ``%held_expert_gmm`` ops inside the
decode program (prefill's calls are left out). The module's counts are
expectations under uniform routing (see its docstring). A model without
``expert_call``, or a trace without the kernel, reads nothing.
"""
import re

from bench import work

KERNEL = re.compile(r"%held_expert_gmm")


def read(m):
    count = getattr(m.model, "expert_call", None)
    decode = m.names["decode"]
    kernel_s = sum(v for k, v in m.trace["op_s"].items()
                   if k.startswith(f"{decode}/") and KERNEL.search(k))
    n = m.trace["module_n"].get(decode, 0)
    if count is None or kernel_s == 0 or n == 0 or not m.tw.attn_calls:
        return None
    per_step = sum(work.roofline_s(*count(m.cfg, kv), m.peak)[0]
                   for kv in m.tw.attn_calls) / len(m.tw.attn_calls)
    return 100.0 * per_step * m.cfg["num_hidden_layers"] * n / kernel_s
