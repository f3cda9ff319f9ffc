"""Record the small chip trace that ``bench/tests/test_trace_reduce.py`` reduces.

    python3 bench/tools/record_trace.py bench/tests/data/small_trace

On one TPU: the program's paged decode-attention kernel at qwen2.5-3b's
attention widths (4 rows, 16 query / 2 KV heads of 128, pages of 16),
jitted as ``small_step``, run three times under ``host:step`` spans, with
a 50 ms ``host:wait`` span between the second and the third call, so the
trace holds one long idle gap and knows what the host did in it. It
prints the kernel's device time per call as the harness will read it.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    out = Path(sys.argv[1])
    from repro.compile_cache import place_tpu_logs

    place_tpu_logs()
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    from repro.kernels.paged_attention import paged_decode_attention

    rng = np.random.default_rng(0)
    b, hq, hkv, d, ps, pages = 4, 16, 2, 128, 16, 8
    kp = jnp.asarray(rng.standard_normal((hkv, 1 + b * pages, ps, d)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((hkv, 1 + b * pages, ps, d)), jnp.bfloat16)
    table = jnp.asarray(1 + np.arange(b * pages).reshape(b, pages), jnp.int32)
    lens = jnp.asarray([0, 17, 64, 128], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.bfloat16)

    def small_step(q, kp, vp, table, lens):
        return paged_decode_attention(q, kp, vp, table, lens).sum()

    step = jax.jit(small_step)
    step(q, kp, vp, table, lens).block_until_ready()
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(str(out))
    for i in range(3):
        if i == 2:
            with jax.profiler.TraceAnnotation("host:wait"):
                time.sleep(0.05)
        with jax.profiler.TraceAnnotation("host:step"):
            step(q, kp, vp, table, lens).block_until_ready()
    jax.profiler.stop_trace()
    from bench.trace_reduce import find_xplane, reduce_trace

    red = reduce_trace(out)
    keep = out / "small.xplane.pb"
    src = find_xplane(out)
    shutil.move(str(src), keep)
    for p in sorted(out.iterdir()):
        if p != keep:
            shutil.rmtree(p) if p.is_dir() else p.unlink()
    print(json.dumps({"bytes": keep.stat().st_size, "module_s": red["module_s"],
                      "module_n": red["module_n"], "busy_s": red["busy_s"],
                      "idle_gaps": red["idle_gaps"][:3],
                      "top_ops": sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:8]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
