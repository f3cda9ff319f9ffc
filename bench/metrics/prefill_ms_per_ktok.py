"""Device time of prefill and of packing the prompt into its pages, per 1000 prompt tokens, in ms.

The prefill program (`model.prefill` at batch 1, the request's own
length) and `pack_prefill_pages`, as they ran in the trace, over the
prompt tokens the harness prefilled in the traced window.
"""


def read(m):
    n = m.trace["module_n"].get(m.names["prefill"], 0)
    if n == 0 or m.tw.prefills == 0:
        return None
    dev = m.trace["module_s"][m.names["prefill"]] + m.trace["module_s"].get(m.names["pack"], 0.0)
    per_prefill_tokens = m.tw.prefill_tokens / m.tw.prefills
    return dev / (n * per_prefill_tokens / 1000.0) * 1e3
