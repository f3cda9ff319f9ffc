"""From the program's own spans to the per-layer metrics, on the CPU.

A profiler trace is recorded around four rounds of the serving loop's
host parts at smoke size, as `bench/serving.py` drives them:
`ContinuousBatch` admission and billing, a `PagedKVPool`, and the
harness's two-device virtual sensor fleet settled every round, all
inside a ``trace:window`` span. No model runs, so the trace holds no
device and no device op.
"""
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import program_spans
from bench.run import reader
from bench.serving import Fleet, make_scheduler
from bench.tests.conftest import SMOKE_CONFIGS
from bench.trace_reduce import WINDOW_SPAN, _union, clip
from bench.weights import arch_config
from repro.kernels.paged_attention import PagedKVPool
from repro.sched import Request

SLOTS, PAGE = 3, 16
#: every program span the serving loop opens, with its metadata
SPANS = {
    "sched:admit": {"admitted", "queued"},
    "sched:queue": {"rid", "wait_ms", "prompt_len"},
    "sched:step": {"live", "slots", "billed"},
    "sched:seal": {"interval", "decoded"},
    "sched:settle": {"interval", "measured"},
    "pool:table": {"rows", "used", "reserved"},
    "pool:alloc": {"rid", "pages"},
    "pool:free": {"rid", "pages"},
    "fleet:mark": {"devices"},
    "fleet:advance": {"devices"},
    "fleet:window": {"devices"},
    "attrib:block": {"spans"},
}
SMALL_TRACE = Path(__file__).parent / "data" / "small_trace" / "small.xplane.pb"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The trace's directory, and what the scheduler's and pool's state said."""
    log_dir = tmp_path_factory.mktemp("program_spans")
    sched, watts, step_s = make_scheduler(arch_config(SMOKE_CONFIGS["gqa"]), SLOTS)
    fleet = Fleet(sched, 2, watts, step_s, seed=2**31 + 7)
    pool = PagedKVPool(n_pages=1 + SLOTS * 4, page_size=PAGE)
    reqs = [Request(rid=i, prompt_len=(16, 40)[i % 2], gen_len=2 + i % 4, arrival_s=0.03 * i)
            for i in range(8)]
    seen = {"waits": [], "live": 0, "slots": 0, "used": 0, "reserved": 0}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            for _ in range(4):
                now = time.perf_counter() - t0
                while reqs and reqs[0].arrival_s <= now:
                    sched.submit(reqs.pop(0))
                for rid in pool.rids - set(sched.live_rids):
                    pool.free(rid)
                for _, req in sched.admit(now):
                    seen["waits"].append((now - req.arrival_s) * 1e3)
                    pool.alloc(req.rid, req.prompt_len + req.gen_len + 1)
                    pool.note_tokens(req.rid, req.prompt_len)
                fleet.open_interval()
                for _ in range(4):
                    if not sched.live_rids:
                        break
                    live = set(sched.live_rids)
                    slot_rids = [r if r in live else None for r in sched.slot_rids]
                    pool.table(slot_rids, 4)
                    seen["live"] += len(live)
                    seen["slots"] += SLOTS
                    seen["used"] += sum(pool.kv_len(r) for r in live)
                    seen["reserved"] += sum(pool.capacity_tokens(r) for r in live)
                    time.sleep(0.004)  # the step's time, for the fleet to sample
                    for r in live:
                        pool.append(r)
                    sched.step_billing(1)
                fleet.close_interval()
                time.sleep(0.03)
    finally:
        jax.profiler.stop_trace()
    fleet.close()
    return str(log_dir), seen


def _m(log_dir, window_s=1.0):
    return SimpleNamespace(tw=SimpleNamespace(log_dir=log_dir), window_s=window_s, trace={})


def test_every_span_comes_back_with_its_metadata(recorded):
    tr = program_spans.load(recorded[0])
    lo, hi = tr.window
    assert {s.name for s in tr.spans} == set(SPANS)
    for s in tr.spans:
        assert set(s.stats) == SPANS[s.name], s
        assert lo <= s.start <= hi
    assert not tr.busy  # the CPU's trace holds no device


@pytest.mark.parametrize("name, want", [
    ("batch_occupancy.chat", lambda s: 100.0 * s["live"] / s["slots"]),
    ("kv_page_use.batch", lambda s: 100.0 * s["used"] / s["reserved"]),
    ("queue_wait_ms.chat", lambda s: sum(s["waits"]) / len(s["waits"])),
])
def test_reader_equals_the_state(recorded, name, want):
    log_dir, seen = recorded
    assert reader(name)(_m(log_dir)) == pytest.approx(want(seen), rel=1e-9)


def test_fleet_idle_share_reads_zero_without_device_ops(recorded):
    assert reader("fleet_idle_share.chat")(_m(recorded[0])) == 0.0


def test_fleet_idle_share_never_exceeds_the_device_idle_share(recorded):
    """On the recorded spans, against devices busy in random intervals."""
    tr = program_spans.load(recorded[0])
    lo, hi = tr.window
    rng = np.random.default_rng(5)
    for _ in range(20):
        starts = np.sort(rng.uniform(lo - 1e6, hi, 40))
        busy = _union(np.stack([starts, starts + rng.uniform(0, 8e6, 40)], axis=1))
        dev = SimpleNamespace(window=tr.window, spans=tr.spans, busy=[busy])
        idle_s = (hi - lo - (lambda c: (c[:, 1] - c[:, 0]).sum())(clip(busy, lo, hi))) * 1e-9
        assert 0.0 <= program_spans.fleet_idle_s(dev) <= idle_s + 1e-12
    # a device busy throughout leaves the fleet nothing; one never busy, all its time
    full = SimpleNamespace(window=tr.window, spans=tr.spans, busy=[np.array([[lo, hi]])])
    assert program_spans.fleet_idle_s(full) == 0.0
    empty = SimpleNamespace(window=tr.window, spans=tr.spans, busy=[np.zeros((0, 2))])
    fleet_s = sum(b - a for n, a, b in program_spans.innermost(tr.spans)
                  if n.startswith(program_spans.FLEET)) * 1e-9
    assert program_spans.fleet_idle_s(empty) == pytest.approx(fleet_s, rel=1e-9)
    assert 0.0 < fleet_s < (hi - lo) * 1e-9


def test_innermost_splits_nested_spans():
    S = program_spans.Span
    spans = [S("fleet:advance", 0.0, 10.0, {}, 0), S("attrib:block", 2.0, 4.0, {}, 0),
             S("sched:step", 5.0, 6.0, {}, 0), S("sched:settle", 12.0, 13.0, {}, 0)]
    assert program_spans.innermost(spans) == [
        ("fleet:advance", 0.0, 2.0), ("attrib:block", 2.0, 4.0), ("fleet:advance", 4.0, 5.0),
        ("sched:step", 5.0, 6.0), ("fleet:advance", 6.0, 10.0), ("sched:settle", 12.0, 13.0)]


def test_idle_inside_matches_a_sampled_count():
    rng = np.random.default_rng(9)
    a = np.sort(rng.uniform(0, 100, 30))
    pieces = _union(np.stack([a, a + rng.uniform(0, 3, 30)], axis=1))
    b = np.sort(rng.uniform(0, 100, 30))
    busy = _union(np.stack([b, b + rng.uniform(0, 3, 30)], axis=1))
    t = np.arange(0, 110, 1e-3) + 5e-4
    inside = lambda iv: ((t[:, None] >= iv[:, 0]) & (t[:, None] < iv[:, 1])).any(axis=1)  # noqa: E731
    want = (inside(pieces) & ~inside(busy)).sum() * 1e-3
    assert program_spans.idle_inside(pieces, busy) == pytest.approx(want, abs=0.05)


@pytest.mark.parametrize("name", ["queue_wait_ms.chat", "batch_occupancy.batch",
                                  "kv_page_use.chat", "fleet_idle_share.chat"])
def test_a_program_without_spans_reads_none(name):
    """The small v5e trace predates the program's spans: each reader gives None."""
    assert reader(name)(_m(str(SMALL_TRACE), window_s=0.1)) is None
