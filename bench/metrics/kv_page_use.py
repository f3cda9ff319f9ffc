"""Share of the KV pages reserved for live requests that their tokens fill, in %.

100 × Σ ``used`` / Σ ``reserved`` over the program's ``pool:table``
spans (`PagedKVPool.table`, one per decode step: ``used`` is the live
rows' tokens, ``reserved`` their pages' capacity in tokens) that start
inside the traced window.
"""
from bench import program_spans


def read(m):
    tables = program_spans.stats(m.tw.log_dir, "pool:table")
    reserved = sum(s["reserved"] for s in tables)
    if reserved == 0:
        return None
    return 100.0 * sum(s["used"] for s in tables) / reserved
