"""The serving loop the window drives, built from the program's own parts.

In the order ``launch/serve.py``'s step loop calls them: the scheduler
(`ContinuousBatch`, throughput-max, no budget, no cap) admits between
step intervals; each admitted request is prefilled (`model.prefill`) and
packed into its reserved pages (`pack_prefill_pages`, `PagedKVPool`);
every step runs `model.decode_step_paged` (the paged Pallas kernel) over
all slots; every ``steps_per_sync`` steps the virtual PowerSensor3 fleet
brackets the interval with a marker, attributes it (`attribute_block`)
and settles it (`settle_interval`).

Where this loop departs from ``serve.py`` (listed in PERF.md too):
prefill runs at batch 1 and at the request's own length; the decode
jit donates the page pool; each step's greedy tokens are fetched to the
host, as a streaming server must, and the host takes the time of each;
a batch mix may send steps ahead of that fetch (``dispatch_ahead``).
"""
from __future__ import annotations

import math
import re
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from bench.trace_reduce import WINDOW_SPAN
from bench.traffic import Req, max_lengths

STEP_MARK = "W"


@dataclass
class TraceWindow:
    """When the profiler runs inside the window, and what the loop did meanwhile."""

    start_s: float
    stop_s: float
    log_dir: str
    on: bool = False
    done: bool = False
    t_on: float = 0.0
    t_off: float = 0.0
    steps: int = 0
    step_flops: float = 0.0  # required FLOPs of the decode steps run
    attn_calls: list = field(default_factory=list)  # per step: kv lengths the kernel saw
    prefill_tokens: int = 0
    prefills: int = 0
    host_s: float = 0.0  # host work of scheduler, pool and fleet
    span: object = None  # the annotation open over the traced window


class Engine:
    """The program's serving parts, compiled for one cell."""

    def __init__(self, cfg, mix: dict, params, work=None):
        from repro.kernels.paged_attention import PagedKVPool, pack_prefill_pages, pages_for
        from repro.launch.serve import SERVE_RUN
        from repro.models import build_model

        self.cfg, self.mix, self.params = cfg, mix, params
        self.model = build_model(cfg, SERVE_RUN)
        self.slots = int(mix["slots"])
        self.ps = int(mix["page_size"])
        self.pages_for = pages_for
        self.pack = pack_prefill_pages
        max_p, max_o = max_lengths(mix)
        # serve.py's reservation: prompt + generation, one page of slack
        self.table_width = pages_for(max_p + max_o, self.ps) + 1
        self.pool = PagedKVPool(n_pages=1 + self.slots * self.table_width, page_size=self.ps)
        self.pcache = self.model.init_paged_cache(self.pool.n_pages, self.ps)
        vocab, model = cfg.vocab_size, self.model

        def prefill(params, tokens):
            logits, cache = model.prefill(params, tokens)
            tok = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
            return tok, cache["layers"]["k"][:, 0], cache["layers"]["v"][:, 0]

        def decode(params, cache, prev, fresh_tok, fresh, table, lens, live):
            # each slot's input: a just-admitted request's first token, else
            # the slot's last output while it stays live, else padding
            tok = jnp.where(fresh, fresh_tok, jnp.where(live, prev, 0))
            logits, cache = model.decode_step_paged(params, cache, tok, table, lens, live)
            return jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32), cache

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(1,))
        self.prev = None  # the last decode step's tokens, on the device
        self.work = work

    # ------------------------------------------------------------ warm-up
    def warm_up(self, prompt_lens) -> None:
        """Compile and run every shape the cell's traffic uses, and no other."""
        for s in sorted(set(int(x) for x in prompt_lens)):
            tok, k, v = self._prefill(self.params, jnp.zeros((1, s), jnp.int32))
            ids = np.arange(1, 1 + self.pages_for(s, self.ps), dtype=np.int32)
            kp, vp = self.pack(self.pcache["layers"]["k"], self.pcache["layers"]["v"], k, v, ids)
            self.pcache = {"layers": {"k": kp, "v": vp}}
            np.asarray(tok)
        b = self.slots
        self.prev = jnp.zeros(b, jnp.int32)
        for _ in range(2):  # the second call takes the first one's output, as steps do
            self.prev, self.pcache = self._decode(
                self.params, self.pcache, self.prev, np.zeros(b, np.int32), np.zeros(b, bool),
                np.zeros((b, self.table_width), np.int32), np.zeros(b, np.int32),
                np.zeros(b, bool))
        np.asarray(self.prev)
        jax.block_until_ready(self.pcache)

    # ----------------------------------------------------------- one request
    def prefill(self, req: Req, t0: float) -> int:
        """Prefill and pack one admitted request; returns its first token."""
        pages = self.pool.alloc(req.rid, req.prompt_len + req.n_out)
        if pages is None:
            raise RuntimeError("pool holds one reservation per slot")
        self.pool.note_tokens(req.rid, req.prompt_len)
        with jax.profiler.TraceAnnotation("host:prefill"):
            tok, k, v = self._prefill(self.params, req.prompt[None])
        with jax.profiler.TraceAnnotation("host:pack"):
            ids = np.asarray(pages[: self.pages_for(req.prompt_len, self.ps)], np.int32)
            kp, vp = self.pack(self.pcache["layers"]["k"], self.pcache["layers"]["v"], k, v, ids)
            self.pcache = {"layers": {"k": kp, "v": vp}}
        with jax.profiler.TraceAnnotation("host:fetch"):
            first = int(np.asarray(tok)[0])
        req.tokens.append(first)
        req.times.append(time.perf_counter() - t0)
        return first

    def step(self, slot_rids: list, fresh_tok: np.ndarray, fresh: np.ndarray):
        """Dispatch one decode step over every slot (``None`` slots decode as
        padding); its tokens stay on the device until `fetch`."""
        table = self.pool.table(slot_rids, self.table_width)
        lens = self.pool.kv_lens(slot_rids)
        live = np.array([r is not None for r in slot_rids])
        with jax.profiler.TraceAnnotation("host:decode"):
            self.prev, self.pcache = self._decode(self.params, self.pcache, self.prev,
                                                  fresh_tok, fresh, table, lens, live)
        return self.prev, lens, live

    @staticmethod
    def fetch(out) -> np.ndarray:
        with jax.profiler.TraceAnnotation("host:fetch"):
            return np.asarray(out)


class Fleet:
    """serve.py's step-interval energy settlement over the virtual sensor fleet."""

    def __init__(self, sched, n_devices: int, watts: float, modelled_step_s: float, seed: int):
        from repro.launch.serve import _make_fleet

        self.sched = sched
        self.fleet = _make_fleet(n_devices, watts, seed) if n_devices > 0 else None
        self.modelled_step_s = modelled_step_s
        self.occ: dict[int, int] = {}
        self.devices: dict[int, int] = {}
        self.n_marks = 0
        self.t_sync = time.perf_counter()

    def open_interval(self) -> None:
        self.occ[self.sched.current_interval] = self.n_marks
        if self.fleet is not None:
            self.fleet.mark_all(STEP_MARK)
            self.n_marks += 1

    def _resolve(self, k: int) -> None:
        from repro.attrib import KernelSpan, attribute_block

        if k in self.devices or k not in self.occ:
            return
        occ = self.occ[k]
        modelled_s = self.modelled_step_s * self.sched.intervals[k].steps
        n_dev, energy = 0, 0.0
        for name in self.fleet.names:
            hit = self.fleet.marker_window(name, STEP_MARK, occurrence=occ, occurrence_b=occ + 1)
            if hit is None:
                continue
            t0, t1, block = hit
            led = attribute_block(block, [KernelSpan(f"int{k}", t0, t1)], min_coverage=0.9)
            if led.entries:
                dev_j = led.total_energy_j
                if modelled_s > 0 and t1 > t0:
                    dev_j *= modelled_s / (t1 - t0)
                energy += dev_j
                n_dev += 1
        if n_dev:
            self.devices[k] = n_dev
            self.sched.settle_interval(k, energy * len(self.fleet.names) / n_dev)

    def close_interval(self) -> None:
        sealed = self.sched.seal_interval()
        if sealed is None:
            self.occ.pop(self.sched.current_interval, None)
            return
        if self.fleet is None:
            self.sched.release_interval(sealed.index)
            return
        now = time.perf_counter()
        self.fleet.advance(now - self.t_sync)
        self.t_sync = now
        for kk in list(self.sched.unsettled()):
            self._resolve(kk)

    def close(self) -> None:
        if self.fleet is not None:
            self.fleet.mark_all(STEP_MARK)
            self.fleet.advance(0.01)
            for kk in list(self.sched.unsettled()):
                self._resolve(kk)
            self.fleet.close()
        for kk in list(self.sched.unsettled()):
            self.sched.release_interval(kk)


def make_scheduler(cfg, slots: int):
    """serve.py's throughput-max `ContinuousBatch` and its modelled fleet watts."""
    from repro.power import EnergyTelemetry, StepCost
    from repro.sched import ContinuousBatch, EnergyPricer, get_policy

    n = cfg.param_count_estimate()
    tel = EnergyTelemetry(cost_per_step=StepCost(2.0 * n * slots, 2.0 * n, 0.0),
                          n_layers=cfg.n_layers, useful_flops_per_step=2.0 * n * slots)
    pricer = EnergyPricer.from_phases(tel.phases, tel.chip, tokens_per_step=slots, dvfs=tel.dvfs)
    watts = (tel.modelled_step_joules / tel.modelled_step_time_s
             if tel.modelled_step_time_s else 0.0)
    sched = ContinuousBatch(pricer, get_policy("throughput-max"), n_slots=slots,
                            budget_j=math.inf, cap_w=None)
    return sched, watts, tel.modelled_step_time_s


class Source:
    """Where requests come from: an open-loop schedule or a kept-full backlog."""

    def __init__(self, reqs: list | None = None, backlog=None, depth: int = 0):
        self.reqs = reqs or []
        self.backlog = backlog
        self.depth = depth
        self._i = 0
        self.closed = False

    def feed(self, now: float, sched, by_rid: dict, window_s: float) -> None:
        from repro.sched import Request

        def submit(r: Req):
            by_rid[r.rid] = r
            sched.submit(Request(rid=r.rid, prompt_len=r.prompt_len, gen_len=r.n_out - 1,
                                 arrival_s=r.due_s))

        if self.backlog is not None:
            if now >= window_s:
                sched.queue.clear()  # never admitted: not attempted
                self.closed = True
                return
            while len(sched.queue) < self.depth:
                submit(self.backlog.take())
            return
        while self._i < len(self.reqs) and self.reqs[self._i].due_s <= now:
            submit(self.reqs[self._i])
            self._i += 1
        self.closed = self._i >= len(self.reqs)

    def attempted(self, r: Req, window_s: float) -> bool:
        """Open loop: due in the window. Backlog: admitted in the window."""
        if self.backlog is None:
            return r.due_s < window_s
        return r.admitted_s is not None and r.admitted_s < window_s

    def next_due(self) -> float | None:
        if self.backlog is None and self._i < len(self.reqs):
            return self.reqs[self._i].due_s
        return None


def serve(engine: Engine, source: Source, window_s: float, drain_s: float, seed: int,
          trace: TraceWindow | None = None) -> dict:
    """Run the window and its drain; returns every request the loop touched.

    With the mix's ``dispatch_ahead`` at n > 0, up to n decode steps run
    ahead of the one whose tokens the host waits for, so that the chip
    stays fed while the host is slow; slots are retired and refilled by
    token counts, which the host knows without the tokens. Then the window
    closes so: when its time is up, no step more is sent until every step
    sent has reached the host, and ``t_close`` is read after that wait.
    At 0 every step's tokens are fetched before the next is sent, and
    ``t_close`` is None.
    """
    mix = engine.mix
    sched, watts, step_s = make_scheduler(engine.cfg, engine.slots)
    fleet = Fleet(sched, int(mix["fleet_devices"]), watts, step_s, seed)
    per_sync = int(mix["steps_per_sync"])
    ahead = int(mix.get("dispatch_ahead", 0))
    by_rid: dict[int, Req] = {}
    fresh_tok = np.zeros(engine.slots, np.int32)
    fresh = np.zeros(engine.slots, bool)
    pending: deque = deque()  # dispatched steps whose tokens the host has not read
    t_close = None
    steps = 0
    t0 = time.perf_counter()

    def receive() -> None:
        out, slot_rids = pending.popleft()
        out = engine.fetch(out)
        t_host = time.perf_counter() - t0
        for slot, rid in enumerate(slot_rids):
            if rid is not None:
                by_rid[rid].tokens.append(int(out[slot]))
                by_rid[rid].times.append(t_host)

    while True:
        now = time.perf_counter() - t0
        if ahead and t_close is None and now >= window_s:
            while pending:
                receive()
            t_close = time.perf_counter() - t0
        if trace is not None:
            trace_tick(trace, now)
        hs = time.perf_counter()
        with jax.profiler.TraceAnnotation("host:sched"):
            source.feed(now, sched, by_rid, window_s)
            for rid in engine.pool.rids - set(sched.live_rids):
                engine.pool.free(rid)
            admitted = sched.admit(now)
        host = time.perf_counter() - hs
        while admitted and pending:  # a prefill waits for the steps before it anyway
            receive()
        for slot, sreq in admitted:
            r = by_rid[sreq.rid]
            r.admitted_s = now
            fresh_tok[slot] = engine.prefill(r, t0)
            fresh[slot] = True
            if trace is not None and trace.on:
                trace.prefills += 1
                trace.prefill_tokens += r.prompt_len
        if not sched.live_rids:
            while pending:
                receive()
            if trace is not None and trace.on:
                trace.host_s += host
            if source.closed and not sched.queue:
                break
            if now > window_s + drain_s:
                break
            nxt = source.next_due()
            with jax.profiler.TraceAnnotation("host:wait"):
                time.sleep(min(max((nxt if nxt is not None else now) - now, 0.0), 0.002)
                           if nxt is not None else 0.001)
            continue
        hs = time.perf_counter()
        with jax.profiler.TraceAnnotation("host:fleet"):
            fleet.open_interval()
        host += time.perf_counter() - hs
        for _ in range(per_sync):
            if not sched.live_rids:
                break
            if ahead and t_close is None and time.perf_counter() - t0 >= window_s:
                break  # the window closes at the top of the loop
            hs = time.perf_counter()
            live_set = set(sched.live_rids)
            slot_rids = [r if r in live_set else None for r in sched.slot_rids]
            host += time.perf_counter() - hs
            out, lens, live = engine.step(slot_rids, fresh_tok, fresh)
            # new arrays, not cleared in place: the step may still read these
            fresh_tok = np.zeros(engine.slots, np.int32)
            fresh = np.zeros(engine.slots, bool)
            pending.append((out, slot_rids))
            while len(pending) > ahead:
                receive()
            hs = time.perf_counter()
            with jax.profiler.TraceAnnotation("host:bill"):
                for rid in slot_rids:
                    if rid is not None:
                        engine.pool.append(rid)
                sched.step_billing(1)
                for rid in engine.pool.rids - set(sched.live_rids):
                    engine.pool.free(rid)
            host += time.perf_counter() - hs
            steps += 1
            if trace is not None and trace.on:
                trace.steps += 1
                kv = np.where(live, lens + 1, 0)
                trace.attn_calls.append(kv)
                if engine.work is not None:
                    trace.step_flops += engine.work(kv)
        hs = time.perf_counter()
        with jax.profiler.TraceAnnotation("host:fleet"):
            fleet.close_interval()
        host += time.perf_counter() - hs
        if trace is not None and trace.on:
            trace.host_s += host
        if time.perf_counter() - t0 > window_s + drain_s:
            break
    while pending:
        receive()
    if trace is not None and trace.on:
        trace_stop(trace)
    fleet.close()
    return {"requests": by_rid, "steps": steps, "t_end": time.perf_counter() - t0,
            "t_close": t_close, "pool": engine.pool.stats()}


def trace_tick(trace: TraceWindow, now: float) -> None:
    if not trace.on and not trace.done and now >= trace.start_s:
        from jax.profiler import ProfileOptions

        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace.log_dir, profiler_options=opts)
        trace.on, trace.t_on = True, time.perf_counter()
        # the window in the trace's own clock, for the reduction to clip to
        trace.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        trace.span.__enter__()
    elif trace.on and now >= trace.stop_s:
        trace_stop(trace)


def trace_stop(trace: TraceWindow) -> None:
    trace.span.__exit__(None, None, None)
    trace.t_off = time.perf_counter()
    jax.profiler.stop_trace()
    trace.on, trace.done = False, True


def run_cell(ctx, make_source, end_to_end) -> dict:
    """Set up, warm up, run the window, free the program, check the served tokens.

    ``make_source(vocab)`` gives the requests; ``end_to_end(reqs, attempted,
    window_s)`` gives the cell's end-to-end metrics from what the loop
    recorded, over the window as it closed (`serve`'s ``t_close`` where
    steps ran ahead).
    """
    from functools import partial

    from bench import check, models

    module = models.load(ctx.cfg)
    cfg = module.arch_config(ctx.cfg)
    source = make_source(cfg.vocab_size)
    from repro.launch.serve import SERVE_RUN
    from repro.models import build_model

    params = make_params_for(build_model(cfg, SERVE_RUN), ctx.seed, module.leaves(ctx.cfg))
    engine = Engine(cfg, ctx.mix, params, work=partial(module.decode_step_flops, ctx.cfg))
    engine.warm_up(ctx.mix["prompt_len"]["values"])
    del params
    trace = None
    if ctx.trace:
        t_len = min(float(ctx.mix["trace_s"]), ctx.seconds / 2)
        trace = TraceWindow(start_s=ctx.seconds / 4, stop_s=ctx.seconds / 4 + t_len,
                            log_dir=str(ctx.trace_dir))
    setup_s = time.perf_counter() - ctx.t_start
    ctx.compiles.window_open()
    res = serve(engine, source, ctx.seconds, float(ctx.mix["drain_s"]), ctx.seed, trace)
    ctx.compiles.window_close()
    memory_peak = ctx.memory_peak()
    reqs = list(res["requests"].values())
    attempted = [r for r in reqs if source.attempted(r, ctx.seconds)]
    finished = [r for r in attempted if len(r.tokens) == r.n_out]
    metrics = end_to_end(reqs, attempted, res["t_close"] or ctx.seconds)
    metrics["setup_s"] = setup_s
    summary = {"decode_steps": res["steps"], "loop_s": res["t_end"],
               "pool_high_water": res["pool"].high_water,
               "pool_pages": res["pool"].n_pages - 1}
    engine.params = engine.pcache = None
    del engine
    verdict = check.judge(ctx.cfg, ctx.seed, finished, ctx.limits)
    return {"attempted": len(attempted), "failed": len(attempted) - len(finished),
            "metrics": metrics, "memory_peak_bytes": memory_peak, "verdict": verdict,
            "trace": trace, "summary": summary}


def make_params_for(model, seed: int, leaves: dict):
    from bench.weights import make_params

    params = make_params(model, seed, leaves)
    jax.block_until_ready(params)
    return params


#: what the programs above are called in a profiler trace: the jitted
#: functions' module names, and the paged Pallas kernel among their ops
NAMES = {
    "decode": "jit_decode",
    "prefill": "jit_prefill",
    "pack": "jit_pack_prefill_pages",
    "kernel": re.compile(r"%paged_decode_attention"),
}
