"""Serving launcher: continuous batching priced in joules.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --smoke \
        --requests 16 --prompt-len 64 --gen-len 32 --policy energy-fair

The main loop is a **step loop** over a fixed compiled decode batch,
driven by `repro.sched.ContinuousBatch`: requests join and leave the
live batch per decode step instead of per wave.

Slot lifecycle: each of the ``--decode-batch`` slots is *free*, *active*
(occupied by a live request) or *draining* (its request finished or was
evicted; the compiled batch shape still decodes the slot as padding,
which is excluded from billing and throughput, and the slot is reusable
at the next admission).  Admission happens between steps: the policy
(``--policy``: throughput-max, cap-strict, energy-fair) orders the queue
and bounds the number of live slots — so cap-strict holds the modelled
batch power under ``--cap-w`` at step boundaries even as completions and
arrivals churn the batch — and every admitted request takes a
per-request joules commitment against ``--budget-j``.  Admitted prompts
are prefilled at the compiled batch shape and their cache rows scattered
into the live decode cache (chunked prefill admission; batch-global
leaves such as the decode position clock are kept live).

Cache backends (``--kv``):

* ``dense`` (default) — one ``(L, B, S_max, Hkv, Dh)`` slab sized for the
  whole run; admission scatters freshly prefilled rows into the admitted
  slots (`_scatter_slots`), and a batch-global position clock marches
  every slot forward together, so a slot's row holds dead history until
  it is overwritten.
* ``paged`` — the slab becomes a `repro.kernels.paged_attention` page
  pool (``--page-size`` tokens per page).  Admission **allocates pages**
  (one all-or-nothing `PagedKVPool` reservation covering prompt +
  generation) and packs the prefilled rows into them; each decode step
  attends through per-slot page tables at per-slot *ragged* lengths via
  the paged flash-decode kernel — free/draining slots decode as
  ``kv_len == 0`` padding whose attention output is exact zeros (never
  NaN) — and retire **frees the pages** back to the pool for the next
  admission to reuse.  Needs attention layers (dense/moe families only);
  prefill runs at ``prompt_len``, not the run-global ``S_max``, so cache
  memory scales with *live* tokens instead of worst-case sequence length.

Step-interval attribution: with ``--fleet N`` (default 2, ``--fleet 0``
disables), every batch of ``--steps-per-sync`` decode steps — one *step
interval* — is bracketed by one occurrence of a single time-synced
marker char on every virtual PowerSensor3 device.  The measured interval
energy, attributed from the ring buffers via `repro.attrib`, is split
across the requests occupying slots during that interval by real-token
share and reconciled into the scheduler, correcting the `EnergyPricer`
online.  Wave markers are the degenerate one-interval case of the same
machinery.

Degraded-telemetry billing rules (what lands on a request's bill when
measurement is imperfect):

    condition                               billing rule
    --------------------------------------  ------------------------------
    interval measured on all devices        measured J, split by token share
    some devices missing the span           measured J scaled up by
                                            n_devices / n_measured (shards
                                            are identical by construction)
    span evicted / markers lost (faults)    released at *predicted* J —
                                            budget commitment settled, the
                                            pricer correction not fed
    padded (free/draining) slots            never billed; counted only in
                                            the pricer's decoded-token
                                            correction denominator
    no live request in the interval         settled as fleet overhead, not
                                            billed to any request
"""
from __future__ import annotations

import argparse
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.attrib import EnergyLedger, KernelSpan, attribute_block, render_text
from repro.compile_cache import place_compile_cache, place_tpu_logs
from repro.configs import RunConfig, get_config, smoke_config
from repro.models import build_model
from repro.obs import trace as obs_trace
from repro.power import EnergyTelemetry, StepCost
from repro.sched import (
    POLICIES,
    ContinuousBatch,
    EnergyPricer,
    Request,
    format_report_rows,
    get_policy,
)

#: one char brackets every step interval; interval k spans occurrences
#: k .. k+1 of it (wave-era goldens use the same char, one wave = one
#: interval)
_STEP_MARK = "W"

#: serving numerics: jnp prefill attention, no remat, bf16 weights
SERVE_RUN = RunConfig(attn_impl="full", remat="none", lr_chunk=16,
                      param_dtype="bfloat16")


def _make_fleet(n_devices: int, total_watts: float, seed: int):
    """N virtual sensor devices, each playing one shard of the serving power."""
    from repro.core import ConstantLoad
    from repro.stream import make_virtual_fleet

    volts = 12.0
    per_dev = max(total_watts, 1e-3) / n_devices
    return make_virtual_fleet(
        [ConstantLoad(volts, per_dev / volts) for _ in range(n_devices)],
        seed=seed,
        window_s=0.5,
        ring_capacity=1 << 18,  # ~13 s of history per device at 20 kHz
    )


def _cache_batch_axes(prefill_fn, params, example_inputs):
    """Which axis of every cache leaf is the batch axis (-1 = batch-global).

    Probed abstractly (`jax.eval_shape`, nothing runs) by prefilling the
    same prompt shape at batch 1 and batch 2 and diffing leaf shapes: the
    axis that grew is the batch axis; leaves that didn't grow (the decode
    position clock, shared norms) are batch-global and must *keep their
    live value* when new requests scatter in.
    """

    def rebatch(x, bb):
        return jax.ShapeDtypeStruct((bb,) + tuple(x.shape[1:]), x.dtype)

    def probe(bb):
        inputs = jax.tree.map(lambda x: rebatch(x, bb), example_inputs)
        _, cache = jax.eval_shape(prefill_fn, params, inputs)
        return cache

    c1, c2 = probe(1), probe(2)

    def axis(l1, l2):
        for a, (s1, s2) in enumerate(zip(l1.shape, l2.shape)):
            if s1 != s2:
                return a
        return -1

    return jax.tree.map(axis, c1, c2)


def _scatter_slots(live, fresh, axes, slots):
    """Copy the freshly prefilled rows of ``slots`` into the live cache.

    Per-leaf along its probed batch axis; batch-global leaves (axis -1)
    keep the live value so the shared decode clock never rewinds.
    """
    idx = jnp.asarray(slots, dtype=jnp.int32)

    def one(lv, fr, ax):
        if ax < 0:
            return lv
        lv0 = jnp.moveaxis(lv, ax, 0)
        fr0 = jnp.moveaxis(fr, ax, 0)
        return jnp.moveaxis(lv0.at[idx].set(fr0[idx]), 0, ax)

    return jax.tree.map(one, live, fresh, axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--decode-batch", type=int, default=4,
                    help="compiled decode batch shape = number of slots")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv", default="dense", choices=("dense", "paged"),
                    help="decode cache backend: one dense slab per layer, or "
                         "a paged pool with per-slot page tables")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged backend only)")
    ap.add_argument("--fleet", type=int, default=2,
                    help="virtual PowerSensor3 devices for measured J/token (0 = off)")
    ap.add_argument("--policy", default="throughput-max", choices=sorted(POLICIES))
    ap.add_argument("--clients", type=int, default=3,
                    help="synthetic clients round-robined across requests")
    ap.add_argument("--budget-j", type=float, default=0.0,
                    help="total joules budget for admission (0 = unlimited)")
    ap.add_argument("--cap-w", type=float, default=0.0,
                    help="fleet power cap for cap-strict admission (0 = uncapped)")
    ap.add_argument("--steps-per-sync", type=int, default=4,
                    help="decode steps per marker-bracketed step interval")
    ap.add_argument("--arrive-every", type=int, default=0,
                    help="request j arrives at decode step j*N (0 = all upfront) "
                         "— mid-decode churn")
    ap.add_argument("--record", default=None, metavar="PATH",
                    help="record the fleet session to a trace archive "
                         "(replayable via repro.replay; needs --fleet > 0)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable the flight recorder and write a "
                         "Chrome-trace-event JSON (Perfetto-loadable)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable metrics and write a Prometheus text "
                         "snapshot at exit")
    args = ap.parse_args(argv)
    if args.record and args.fleet <= 0:
        ap.error("--record needs a sensor fleet (--fleet > 0)")

    if args.trace or args.metrics:
        from repro import obs

        obs.enable()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.kv == "paged" and (cfg.is_encdec or cfg.family not in ("dense", "moe")):
        ap.error(f"--kv paged needs dense/moe attention layers; "
                 f"{args.arch} is family {cfg.family!r}")
    model = build_model(cfg, SERVE_RUN)
    # one program draws and casts every leaf: no float32 tree on the device
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    b = args.decode_batch
    paged = args.kv == "paged"
    # dense: the position clock is batch-global — one cache serves every
    # request that ever occupies a slot, so its length must cover the whole
    # run.  paged: prefill only needs the prompt rows (decode growth lives
    # in pool pages at per-slot ragged lengths).
    if paged:
        max_len = args.prompt_len
    else:
        max_len = args.prompt_len + min(args.requests * args.gen_len, 4096)

    def _prefill_tokens(p, t):
        return model.prefill(p, t, max_len=max_len)

    def _prefill_encdec(p, inputs):
        return model.prefill(p, inputs, max_len=max_len)

    # both prefill paths jitted ONCE, next to the decoder — the compiled
    # batch shape is fixed, so admission never recompiles
    prefill = jax.jit(_prefill_tokens)
    prefill_encdec = jax.jit(_prefill_encdec)
    decode = jax.jit(model.decode_step)

    pool = None
    pcache = None
    if paged:
        from repro.kernels.paged_attention import (
            PagedKVPool, pack_prefill_pages, pages_for,
        )

        ps = args.page_size
        # one reservation per slot covers prompt + full generation, plus a
        # page of slack; +1 for the reserved null page
        table_width = pages_for(args.prompt_len + args.gen_len, ps) + 1
        pool = PagedKVPool(n_pages=1 + b * table_width, page_size=ps)
        pcache = model.init_paged_cache(pool.n_pages, ps)
        decode_paged = jax.jit(model.decode_step_paged)

    def _sweep_pool():
        """Free the pages of every request that left the live batch."""
        if pool is None:
            return
        for rid in pool.rids - set(sched.live_rids):
            pool.free(rid)

    def _make_inputs(prompts: np.ndarray):
        tokens = jnp.asarray(prompts)
        if cfg.is_encdec:
            frames = jnp.asarray(
                rng.standard_normal((b, args.prompt_len, cfg.d_model)), jnp.float32
            )
            return {"frames": frames, "tokens": tokens}
        return tokens

    def _prefill(inputs):
        if cfg.is_encdec:
            return prefill_encdec(params, inputs)
        return prefill(params, inputs)

    n = cfg.param_count_estimate()
    telemetry = EnergyTelemetry(
        cost_per_step=StepCost(2.0 * n * b, 2.0 * n, 0.0),
        n_layers=cfg.n_layers, useful_flops_per_step=2.0 * n * b,
    )

    # joule-priced admission: the per-kernel phase timeline prices one decode
    # step, the measured interval ledgers correct that price online
    pricer = EnergyPricer.from_phases(
        telemetry.phases, telemetry.chip, tokens_per_step=b, dvfs=telemetry.dvfs
    )
    modelled_watts = (
        telemetry.modelled_step_joules / telemetry.modelled_step_time_s
        if telemetry.modelled_step_time_s
        else 0.0
    )
    sched = ContinuousBatch(
        pricer,
        get_policy(args.policy),
        n_slots=b,
        budget_j=args.budget_j if args.budget_j > 0 else math.inf,
        cap_w=args.cap_w if args.cap_w > 0 else None,
        # modelled batch power scales weakly with live slots on this fleet
        # model: expose the telemetry estimate so cap-strict has something
        # to bound at every step-boundary admission
        power_of_batch=lambda bb: modelled_watts * (0.5 + 0.5 * bb / b) if b else 0.0,
    )
    pending = [
        Request(
            rid=rid,
            client=f"client{rid % max(args.clients, 1)}",
            prompt_len=args.prompt_len,
            gen_len=args.gen_len,
            payload=rng.integers(
                2, cfg.vocab_size, size=args.prompt_len
            ).astype(np.int32),
        )
        for rid in range(args.requests)
    ]

    fleet = None
    recorder = None
    if args.fleet > 0:
        fleet = _make_fleet(args.fleet, modelled_watts, args.seed)
        if args.record:
            from repro.replay import SessionRecorder

            recorder = SessionRecorder(
                fleet,
                meta={"launcher": "serve", "arch": args.arch,
                      "policy": args.policy, "seed": args.seed},
            )

    # measured per-interval energy, resolved incrementally (one interval
    # after its closing marker lands) so long runs never outlive the ring
    interval_ledger = EnergyLedger()
    interval_devices: dict[int, int] = {}  # interval -> devices that attributed
    interval_occ: dict[int, int] = {}  # interval -> its opening marker occurrence
    n_marks = 0  # total markers issued (flush marks shift occurrences)

    def _mark_fleet() -> None:
        nonlocal n_marks
        if fleet is not None:
            fleet.mark_all(_STEP_MARK)
            n_marks += 1

    def _resolve_interval(k: int) -> None:
        """Attribute step interval k (its marker occurrence pair) and settle.

        The fleet plays modelled watts over *wall* time (the marker span),
        so raw measured joules are inflated by the span/modelled time ratio
        (huge on CPU, ~1 on real hardware); the scheduler is settled on
        the modelled time base — each device's joules scaled by
        ``modelled interval time / span`` — so predicted and measured J
        stay in the same units and a ``--budget-j`` set from modelled
        numbers keeps meaning something.  The raw sensor joules stay in
        ``interval_ledger`` untouched.
        """
        if fleet is None or k < 0 or k in interval_devices or k not in interval_occ:
            return
        occ = interval_occ[k]  # the interval closes at the *next* marker
        modelled_s = telemetry.modelled_step_time_s * sched.intervals[k].steps
        n_dev = 0
        energy = 0.0
        for name in fleet.names:
            hit = fleet.marker_window(
                name, _STEP_MARK, occurrence=occ, occurrence_b=occ + 1
            )
            if hit is None:
                continue
            t0, t1, block = hit
            led = attribute_block(
                block, [KernelSpan(f"int{k}", t0, t1)], min_coverage=0.9
            )
            if led.entries:
                interval_ledger.absorb(led)
                dev_j = led.total_energy_j
                if modelled_s > 0 and t1 > t0:
                    dev_j *= modelled_s / (t1 - t0)
                energy += dev_j
                n_dev += 1
                orec = obs_trace.active()
                if orec is not None:
                    # attributed interval on the device timeline: the span
                    # the exporter aligns against control-plane spans
                    orec.device_span("attr:interval", t0, t1,
                                     track=f"attr:{name}",
                                     value=led.total_energy_j)
        if n_dev:
            interval_devices[k] = n_dev
            # devices are identical shards: scale up for any whose ring had
            # already evicted the span, instead of silently undercounting
            energy *= len(fleet.names) / n_dev
            sched.settle_interval(k, energy)

    def _flush_and_settle(release_rest: bool) -> None:
        """Flush the open interval's closing marker; settle what measured,
        optionally release the rest at prediction."""
        if fleet is not None and sched.intervals:
            _mark_fleet()
            fleet.advance(0.01)
            for kk in list(sched.unsettled()):
                _resolve_interval(kk)
        if release_rest:
            for kk in list(sched.unsettled()):
                sched.release_interval(kk)

    t0 = time.perf_counter()
    t_sync = t0
    step_count = 0  # decode steps executed (the churn arrival clock)
    billed_tokens = 0  # real-request tokens (padded slots excluded)
    decoded_tokens = 0  # what the hardware ran, padded slots included
    logits = None
    cache = None
    cache_axes = None
    while True:
        # churn arrivals: request j reaches the queue at decode step j*N
        while pending and (
            args.arrive_every <= 0
            or step_count >= (pending[0].rid * args.arrive_every)
        ):
            sched.submit(pending.pop(0))
        admitted = sched.admit(time.perf_counter() - t0)
        if not sched.live_rids:
            if sched.queue and sched.unsettled():
                # blocked on in-flight interval settlements, not the hard
                # budget: flush the open interval's closing marker, settle,
                # release what can never measure, and retry admission
                _flush_and_settle(release_rest=True)
                admitted = sched.admit(time.perf_counter() - t0)
            if not admitted:
                if sched.queue:
                    break  # starved by the budget: accounted below
                if pending:
                    # idle until the next churn arrival is due
                    step_count = pending[0].rid * args.arrive_every
                    continue
                break
        if admitted:
            # chunked prefill admission at the compiled batch shape: the
            # admitted slots' prompt rows are real, the rest placeholder,
            # and only the admitted rows scatter into the live cache
            adm = dict(admitted)  # slot -> request
            filler = admitted[0][1].payload
            prompts = np.stack(
                [adm[i].payload if i in adm else filler for i in range(b)]
            )
            new_logits, new_cache = _prefill(_make_inputs(prompts))
            slots = [slot for slot, _ in admitted]
            if paged:
                # paged admission: allocate each request's reservation and
                # pack its prefilled rows into the granted pages — no dense
                # scatter, and draining occupants were swept back already
                _sweep_pool()
                kp, vp = pcache["layers"]["k"], pcache["layers"]["v"]
                for slot, req in admitted:
                    pages = pool.alloc(req.rid, req.prompt_len + req.gen_len)
                    assert pages is not None, "pool holds one reservation per slot"
                    pool.note_tokens(req.rid, req.prompt_len)
                    kp, vp = pack_prefill_pages(
                        kp, vp,
                        new_cache["layers"]["k"][:, slot],
                        new_cache["layers"]["v"][:, slot],
                        jnp.asarray(pages, jnp.int32),
                    )
                pcache = {"layers": {"k": kp, "v": vp}}
                idx = jnp.asarray(slots, dtype=jnp.int32)
                logits = (new_logits if logits is None
                          else logits.at[idx].set(new_logits[idx]))
            elif cache is None:
                logits, cache = new_logits, new_cache
            else:
                if cache_axes is None:
                    cache_axes = _cache_batch_axes(
                        _prefill_encdec if cfg.is_encdec else _prefill_tokens,
                        params,
                        _make_inputs(prompts),
                    )
                idx = jnp.asarray(slots, dtype=jnp.int32)
                logits = logits.at[idx].set(new_logits[idx])
                cache = _scatter_slots(cache, new_cache, cache_axes, slots)
        # one step interval: marker bracket + up to --steps-per-sync steps
        k = sched.current_interval
        interval_occ[k] = n_marks
        _mark_fleet()
        with obs_trace.span("serve:interval", interval=k):
            for _ in range(max(args.steps_per_sync, 1)):
                if not sched.live_rids:
                    break
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32) % cfg.vocab_size
                if paged:
                    # per-slot ragged state from the pool: draining/free slots
                    # decode as kv_len == 0 padding (exact-zero attention)
                    live_set = set(sched.live_rids)
                    slot_r = [r if r in live_set else None for r in sched.slot_rids]
                    table = jnp.asarray(pool.table(slot_r, table_width))
                    lens = jnp.asarray(pool.kv_lens(slot_r))
                    live_m = jnp.asarray([r is not None for r in slot_r])
                    logits, pcache = decode_paged(
                        params, pcache, tok, table, lens, live_m
                    )
                    for r in slot_r:
                        if r is not None:
                            assert pool.append(r), "reservation covers the generation"
                else:
                    logits, cache = decode(params, cache, tok)
                rec = sched.step_billing(1)
                _sweep_pool()
                telemetry.record_step(step_count, 0.0, b)
                step_count += 1
                billed_tokens += rec.billed_tokens
                decoded_tokens += rec.decoded_tokens
            sealed = sched.seal_interval()
        if sealed is None:
            interval_occ.pop(k, None)
            continue
        if fleet is None:
            # no sensors to measure against: settle at prediction right away
            # so budget commitments never pile up unreleased
            sched.release_interval(sealed.index)
        else:
            # devices play modelled power over the interval's wall time
            now = time.perf_counter()
            fleet.advance(now - t_sync)
            t_sync = now
            # this interval's advance flushed the previous one's closing
            # marker: settle everything that is now attributable
            for kk in list(sched.unsettled()):
                _resolve_interval(kk)
            if recorder is not None:
                # tap the rings once per interval: eviction between taps
                # would punch (counted) holes in the archive
                recorder.capture()
    n_intervals = len(sched.intervals)
    # closing bracket of the last interval, then settle or release the rest
    _flush_and_settle(release_rest=True)
    # anything still queued when the loop gave up was starved by the budget:
    # account for it as rejected rather than dropping it silently
    if sched.queue or pending:
        sched.rejected.extend(sched.queue)
        sched.rejected.extend(pending)
        sched.queue.clear()
        pending.clear()
    dt = time.perf_counter() - t0
    s = telemetry.summary()
    dev = jax.devices()[0]
    print(f"served {len(sched.finished)}/{args.requests} requests "
          f"({len(sched.rejected)} rejected by SLO), {billed_tokens} tokens in "
          f"{dt:.2f}s ({billed_tokens/dt:.1f} tok/s wall on {dev.platform} "
          f"{dev.device_kind}, compiles included) "
          f"over {step_count} decode steps / {n_intervals} {args.policy} intervals")
    if decoded_tokens:
        print(f"slot utilization: {billed_tokens}/{decoded_tokens} decoded "
              f"tokens billed ({billed_tokens/decoded_tokens:.0%}; padded "
              f"slots excluded from billing and throughput)")
    if s:
        print(f"modelled: {s['j_per_token']*1e3:.3f} mJ/token, "
              f"{s['modelled_step_s']*1e3:.3f} ms/decode-step on {telemetry.chip.name}")
    if pool is not None:
        _sweep_pool()
        st = pool.stats()
        print(f"paged KV: page size {st.page_size}, "
              f"{st.high_water}/{st.n_pages - 1} pages high water, "
              f"{st.allocs} allocs / {st.frees} frees "
              f"({st.reused_pages} reused, {st.alloc_failures} refused), "
              f"{st.in_use} in use at exit")
    if fleet is not None:
        snap = fleet.snapshot()
        print(f"fleet: {snap.aggregate.n_devices} devices, "
              f"{snap.aggregate.mean_w:.1f} W windowed mean, "
              f"{snap.aggregate.energy_j:.2f} J in window")
        print(render_text(
            interval_ledger, title="per-interval measured energy (raw sensor J)"
        ))
        print("per-request energy SLO accounting, modelled time base "
              f"(pricer correction {pricer.correction:.3f} after "
              f"{pricer.n_updates} intervals):")
        print(format_report_rows(sched.report_rows()))
        released = sum(1 for r in sched.intervals if r.released)
        if released:
            print(f"  ({released} intervals settled at prediction: "
                  f"ring history evicted)")
        if sched.overhead_j:
            print(f"  (fleet overhead not billed to any request: "
                  f"{sched.overhead_j:.4f} J)")
        if recorder is not None:
            archive = recorder.save(
                args.record, extra_meta={"intervals": n_intervals}
            )
            print(f"recorded {archive.n_frames} frames / {len(archive)} devices "
                  f"to {args.record} (replay: repro.replay.ReplayFleet)")
        fleet.close()
    if args.trace:
        from repro.obs import export as obs_export

        orec = obs_trace.active()
        obs_export.write_chrome_trace(
            orec, args.trace,
            metadata={"launcher": "serve", "arch": args.arch,
                      "policy": args.policy, "seed": args.seed},
        )
        print(f"wrote flight-recorder trace ({orec.head} events) to "
              f"{args.trace} — load in Perfetto / chrome://tracing")
    if args.metrics:
        from repro.obs import export as obs_export
        from repro.obs import metrics as obs_metrics

        with open(args.metrics, "w") as fh:
            fh.write(obs_export.prometheus_text(obs_metrics.active()))
        print(f"wrote metrics snapshot to {args.metrics}")
    return {
        "served": len(sched.finished),
        "rejected": len(sched.rejected),
        "billed_tokens": billed_tokens,
        "decode_steps": step_count,
    }


if __name__ == "__main__":
    place_tpu_logs()
    place_compile_cache()
    main()
