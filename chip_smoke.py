"""Smoke test of the main path on TPU, at the full width of qwen2.5-3b.

    python chip_smoke.py             # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4   # four chips: the mesh training path only

It needs a TPU: on any other backend it exits non-zero before the first
phase and prints no result.  Weights and data are random, drawn from
``--seed``.  Each phase prints one line; a failing phase raises, and the
process exits non-zero.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

One chip, with (c) run first so that the peak of device memory it
prints is serving's own (the figure is the process's high-water mark,
and every phase line prints it as it stands after that phase):

(a) the paged decode kernel, lowered by Mosaic, against the float32
    oracle at qwen2.5-3b attention widths (16 q / 2 kv heads, head dim
    128, page 16, bf16) over ragged lengths that include 0 and a full
    table; ``kv_len == 0`` rows must be exact zeros;
(b) the full-width model (36 layers, bf16 weights): prefill 4 prompts,
    then one ``decode_step_paged`` against one dense ``decode_step`` on
    the same prefill.  Only the first step after a full-batch admission
    is compared: the dense cache's batch-global position clock is wrong
    under churn.  The paged step's HLO must hold the Pallas kernel
    (``tpu_custom_call``);
(c) ``repro.launch.serve.main --full --kv paged`` serving 8 requests
    that arrive mid-decode on 4 slots.

Four chips (``--chips 4``): qwen2.5-3b training 3 steps through
``repro.launch.train.main --mesh 2x2`` (FSDP x tensor parallel,
``remat="layer"``, batch 8, seq 512, lr 3e-4) at full width; then the same steps
of the configuration cut to 2 layers, on one device and on the mesh,
whose losses must agree.

Times printed here come from one run and include compilation; they are
bring-up figures, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"
#: bf16 kernel vs float32 oracle: |out - ref| <= ATOL + RTOL * |ref|
KERNEL_ATOL, KERNEL_RTOL = 2e-3, 2e-2
#: paged vs dense logits after 36 bf16 layers, as a share of the dense
#: logits' L2 norm (the two paths round attention differently)
LOGITS_REL_L2 = 5e-2
#: one-device vs 2x2-mesh training loss, per step (f32 params, bf16 compute)
LOSS_ATOL = 2e-2


class _CompileClock:
    """Sums XLA backend compile time reported through `jax.monitoring`."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _line(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _peak_bytes() -> int | None:
    """The process's high-water mark of device memory so far."""
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def phase_kernel(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.paged_attention import (
        PagedKVPool, init_page_arrays, pack_prefill_pages,
        paged_decode_attention, paged_decode_attention_ref,
    )

    cfg = get_config(ARCH)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ps, max_pages = 16, 32
    kv_lens = [0, 1, 15, 16, 17, 300, ps * max_pages, 0]  # 0s and a full table
    rng = np.random.default_rng(seed)
    pool = PagedKVPool(n_pages=1 + len(kv_lens) * max_pages, page_size=ps)
    kp, vp = init_page_arrays(pool.n_pages, ps, hkv, d, jnp.bfloat16)
    rids = []
    for r, n in enumerate(kv_lens):
        if n == 0:
            rids.append(None)
            continue
        pages = pool.alloc(r, n)
        pool.note_tokens(r, n)
        k, v = (jnp.asarray(rng.standard_normal((n, hkv, d)), jnp.bfloat16) for _ in "kv")
        kp, vp = pack_prefill_pages(kp, vp, k, v, jnp.asarray(pages, jnp.int32))
        rids.append(r)
    table = jnp.asarray(pool.table(rids, max_pages))
    lens = jnp.asarray(pool.kv_lens(rids))
    q = jnp.asarray(rng.standard_normal((len(kv_lens), hq, d)), jnp.bfloat16)

    compiled = jax.jit(paged_decode_attention).lower(q, kp, vp, table, lens).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("the paged kernel was not lowered by Mosaic")
    out = np.asarray(compiled(q, kp, vp, table, lens), np.float32)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(paged_decode_attention_ref(
            q.astype(f32), kp.astype(f32), vp.astype(f32), table, lens))
    err = np.abs(out - ref)
    excess = float((err - KERNEL_ATOL - KERNEL_RTOL * np.abs(ref)).max())
    zero = np.asarray(lens) == 0
    if excess > 0 or not np.isfinite(out).all():
        raise AssertionError(f"paged kernel vs oracle: max |err| {err.max()}")
    if not (out[zero] == 0.0).all():
        raise AssertionError("kv_len == 0 rows are not exact zeros")
    _line("a:paged-kernel", hq=hq, hkv=hkv, head_dim=d, page=ps, kv_lens=kv_lens,
          max_abs_err=float(err.max()), atol=KERNEL_ATOL, rtol=KERNEL_RTOL,
          zero_rows_exact=True, tpu_custom_call=True, peak_bytes_in_use=_peak_bytes())


def phase_model(seed: int) -> None:
    """Paged vs dense first decode step after a full-batch prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.paged_attention import PagedKVPool, pack_prefill_pages, pages_for
    from repro.launch.serve import SERVE_RUN
    from repro.models import build_model

    cfg = get_config(ARCH)
    b, prompt_len, ps = 4, 128, 16
    model = build_model(cfg, SERVE_RUN)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (b, prompt_len), 2, cfg.vocab_size)
    logits, dense = jax.jit(lambda p, t: model.prefill(p, t, max_len=prompt_len + 1))(params, toks)

    n = pages_for(prompt_len + 1, ps)
    pool = PagedKVPool(n_pages=1 + b * n, page_size=ps)
    pcache = model.init_paged_cache(pool.n_pages, ps)
    kp, vp = pcache["layers"]["k"], pcache["layers"]["v"]
    for r in range(b):
        pages = pool.alloc(r, prompt_len + 1)
        pool.note_tokens(r, prompt_len)
        kp, vp = pack_prefill_pages(
            kp, vp, dense["layers"]["k"][:, r, :prompt_len],
            dense["layers"]["v"][:, r, :prompt_len], jnp.asarray(pages, jnp.int32),
        )
    pcache = {"layers": {"k": kp, "v": vp}}
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    slots = list(range(b))
    args = (params, pcache, tok, jnp.asarray(pool.table(slots, n)),
            jnp.asarray(pool.kv_lens(slots)), jnp.ones((b,), bool))
    step = jax.jit(model.decode_step_paged).lower(*args).compile()
    if "tpu_custom_call" not in step.as_text():
        raise AssertionError("decode_step_paged HLO holds no tpu_custom_call")
    lg_p = np.asarray(step(*args)[0])
    lg_d = np.asarray(jax.jit(model.decode_step)(params, dense, tok)[0])
    rel = float(np.linalg.norm(lg_p - lg_d) / np.linalg.norm(lg_d))
    if not (np.isfinite(lg_p).all() and rel <= LOGITS_REL_L2):
        raise AssertionError(f"paged vs dense logits: rel L2 {rel}")
    _line("b:full-width-model", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
          batch=b, prompt_len=prompt_len, logits_rel_l2=rel, limit=LOGITS_REL_L2,
          max_abs_diff=float(np.abs(lg_p - lg_d).max()),
          top1_agree=int((lg_p.argmax(-1) == lg_d.argmax(-1)).sum()),
          tpu_custom_call=True, peak_bytes_in_use=_peak_bytes())


def phase_serve(clock: _CompileClock) -> None:
    from repro.launch import serve

    requests, gen_len = 8, 16
    c0, t0 = clock.seconds, time.perf_counter()
    res = serve.main([
        "--arch", ARCH, "--full", "--kv", "paged",
        "--requests", str(requests), "--decode-batch", "4",
        "--prompt-len", "128", "--gen-len", str(gen_len), "--arrive-every", "2",
    ])
    wall = time.perf_counter() - t0
    if res["served"] != requests or res["billed_tokens"] != requests * gen_len:
        raise AssertionError(f"serve: {res}")
    _line("c:serve", served=res["served"], requests=requests,
          billed_tokens=res["billed_tokens"], decode_steps=res["decode_steps"],
          compile_s=clock.seconds - c0, wall_s=wall,
          peak_bytes_in_use=_peak_bytes())


def phase_mesh_train(clock: _CompileClock, seed: int) -> None:
    import numpy as np

    from repro.configs import get_config
    from repro.launch import train

    # at the launcher's default lr (3e-3, sized for smoke models) the
    # full-width loss jumps back up by the third step, and a diverging
    # run amplifies the mesh's different reduction order past any fixed
    # bound; 3e-4 is a usual pretraining lr at this size
    common = ["--arch", ARCH, "--full", "--steps", "3", "--batch", "8", "--seq", "512",
              "--lr", "3e-4", "--log-every", "1", "--seed", str(seed)]

    def losses(extra, cfg=None):
        c0, t0 = clock.seconds, time.perf_counter()
        res = train.main(common + extra, cfg=cfg)
        out = [h["loss"] for h in res.history]
        if len(out) != 3 or not np.isfinite(out).all():
            raise AssertionError(f"training losses {out}")
        return out, clock.seconds - c0, time.perf_counter() - t0

    full, c_full, w_full = losses(["--mesh", "2x2"])
    _line("4a:full-width-mesh-train", layers=get_config(ARCH).n_layers, mesh="data=2,model=2",
          losses=full, compile_s=c_full, wall_s=w_full)
    cut = replace(get_config(ARCH), n_layers=2)
    one, c_one, _ = losses([], cut)
    mesh, c_mesh, _ = losses(["--mesh", "2x2"], cut)
    diff = float(np.abs(np.subtract(one, mesh)).max())
    if diff > LOSS_ATOL:
        raise AssertionError(f"one device {one} vs mesh {mesh}")
    _line("4b:cut-depth-one-vs-mesh", layers=2, one_device=one, mesh=mesh,
          max_abs_diff=diff, limit=LOSS_ATOL, compile_s=c_one + c_mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import place_compile_cache, place_tpu_logs

    place_tpu_logs()  # before libtpu loads
    place_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1
    clock = _CompileClock()
    if args.chips == 4:
        phase_mesh_train(clock, args.seed)
    else:
        phase_serve(clock)
        phase_kernel(args.seed)
        phase_model(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
