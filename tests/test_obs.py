"""Flight recorder, metrics registry, exporters, and instrumented call sites.

Unit tier for `repro.obs.trace` / `repro.obs.metrics` / `repro.obs.export`
plus end-to-end emission checks: with a recorder + registry installed,
the receiver, fleet monitor, governor, scheduler and fault ledger must
produce the documented series — and with nothing installed every call
site must stay a no-op.
"""
import io
import json

import pytest

from repro import obs
from repro.core import ConstantLoad, PowerSensor, make_device
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.export import (
    chrome_trace_events,
    chrome_trace_json,
    prometheus_text,
    write_chrome_trace,
)
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import COUNTER, DEVICE, INSTANT, SPAN, WALL, TraceRecorder


@pytest.fixture(autouse=True)
def _no_global_obs():
    """Every test starts and ends with tracing disabled."""
    obs.disable()
    yield
    obs.disable()


# ------------------------------------------------------------ trace ring
def test_ring_wraps_and_counts_dropped():
    rec = TraceRecorder(capacity=8)
    for i in range(20):
        rec.instant(f"e{i}", t_us=i)
    assert len(rec) == 8
    assert rec.head == 20
    assert rec.dropped == 12
    # oldest-first, only the newest `capacity` events survive
    assert [e.name for e in rec.events()] == [f"e{i}" for i in range(12, 20)]


def test_ring_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        TraceRecorder(capacity=0)


def test_span_context_manager_records_wall_span():
    rec = TraceRecorder(capacity=16)
    with rec.span("work", track="loop", value=3.0):
        pass
    (ev,) = rec.events()
    assert ev.kind == SPAN and ev.kind_name == "span"
    assert ev.name == "work" and ev.track == "loop"
    assert ev.clock == WALL and ev.value == 3.0
    assert ev.dur_us >= 0 and ev.t1_us == ev.t_us + ev.dur_us


def test_span_at_clamps_negative_duration():
    rec = TraceRecorder(capacity=4)
    rec.span_at("x", 100, 50)
    assert rec.events()[0].dur_us == 0


def test_device_events_and_anchor_offset():
    rec = TraceRecorder(capacity=16)
    assert rec.device_offset_us() is None
    rec.device_span("k", 0.25, 0.30, track="attr", value=1.0)
    rec.device_instant("m", 0.275, track="attr")
    span, inst = rec.events()
    assert span.clock == DEVICE and span.t_us == 250_000 and span.dur_us == 50_000
    assert inst.kind == INSTANT and inst.t_us == 275_000
    assert rec.track_clock("attr") == DEVICE

    rec.anchor(2.0, wall_us=5_000_000)
    assert rec.device_offset_us() == 3_000_000
    rec.anchor_once(9.0, wall_us=1)  # no-op: an anchor already exists
    assert rec.anchors == [(5_000_000, 2_000_000)]


def test_counter_total_and_events_named():
    rec = TraceRecorder(capacity=16)
    rec.counter("rx.frames", 10.0, t_us=1)
    rec.counter("rx.frames", 32.0, t_us=2)
    rec.counter("rx.markers", 1.0, t_us=3)
    rec.instant("rx.frames", t_us=4)  # same name, not a counter sample
    assert rec.counter_total("rx.frames") == 42.0
    assert len(rec.events_named("rx.frames")) == 3
    assert all(e.kind == COUNTER for e in rec.events_named("rx.markers"))


def test_trace_install_uninstall_active():
    assert obs_trace.active() is None
    rec = obs_trace.install()
    assert obs_trace.active() is rec
    assert obs_trace.uninstall() is rec
    assert obs_trace.active() is None and obs_trace.uninstall() is None


# ----------------------------------------------------------- program spans
def test_program_span_without_a_sink_records_nothing():
    """No recorder installed, no profiler running: a no-op that raises nothing."""
    idle = TraceRecorder(capacity=4)  # built, never installed
    with obs_trace.span("sched:admit", queued=3) as sp:
        sp.set_metadata(admitted=2)
    assert obs_trace.active() is None and idle.head == 0


def test_program_span_writes_one_ring_event_with_a_bare_name():
    rec = obs_trace.install(TraceRecorder(capacity=16))
    with obs_trace.span("pool:alloc", rid=7, pages=3) as sp:
        sp.set_metadata(granted=3)
    (ev,) = rec.events()
    assert ev.kind == SPAN and ev.clock == WALL
    # metadata in value, never in the name; the track is the name's prefix
    assert ev.name == "pool:alloc" and ev.track == "pool" and ev.value == 7.0


def test_trace_module_imports_numpy_and_stdlib_only():
    import os
    import subprocess
    import sys

    code = ("import sys\n"
            "from repro.obs import trace\n"
            "with trace.span('sched:step', live=1, slots=2):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'trace pulled in jax'\n")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr


def test_name_table_stays_bounded_over_many_intervals():
    """Interval indices and request ids travel as values, so a long-running
    server does not grow the recorder's name table."""
    from repro.kernels.paged_attention import PagedKVPool
    from repro.sched import ContinuousBatch, EnergyPricer, Request, get_policy

    rec = obs_trace.install(TraceRecorder(capacity=256))
    sched = ContinuousBatch(
        EnergyPricer(j_per_token=1.0), get_policy("throughput-max"), n_slots=2
    )
    pool = PagedKVPool(n_pages=8, page_size=4)
    sizes = []
    for i in range(1000):
        sched.submit(Request(rid=i, gen_len=1, arrival_s=float(i)))
        for _, req in sched.admit(float(i) + 0.5):
            pool.alloc(req.rid, 3)
        pool.table(sched.slot_rids, 2)
        sched.step_billing(1)
        pool.free(i)
        sealed = sched.seal_interval()
        if i % 2:
            sched.settle_interval(sealed.index, 1.0)
        else:
            sched.release_interval(sealed.index)
        sizes.append(len(rec._names))
    assert sizes[-1] == sizes[1] == len(set(rec._names))
    assert set(rec._names) == {"sched:admit", "sched:queue", "pool:alloc", "pool:table",
                               "sched:step", "pool:free", "sched:seal", "sched:settle"}
    assert rec.dropped > 0  # the ring wrapped; the name table did not grow


# --------------------------------------------------------------- metrics
def test_counter_monotonic():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1.0)


def test_gauge_last_write_wins():
    g = Gauge()
    g.set(2.0)
    g.set(-7.5)
    assert g.value == -7.5


def test_histogram_buckets_and_quantiles():
    h = Histogram(lo=1e-3, hi=1.0, per_decade=2)
    for v in (2e-3, 5e-2, 5e-2, 0.9, 50.0):  # last one overflows
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(2e-3 + 0.1 + 0.9 + 50.0)
    bounds, cums = zip(*h.cumulative())
    assert bounds[-1] == float("inf") and cums[-1] == 5
    assert all(b <= a for a, b in zip(cums[1:], cums[:-1]))  # non-decreasing
    assert h.quantile(0.5) <= h.quantile(0.99)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_validation_and_empty_quantile():
    for bad in (dict(lo=0.0), dict(hi=1e-7), dict(per_decade=0)):
        with pytest.raises(ValueError):
            Histogram(**bad)
    assert Histogram().quantile(0.5) != Histogram().quantile(0.5)  # nan


def test_registry_labels_make_distinct_series():
    reg = MetricsRegistry()
    reg.counter("hits", device="dev0").inc(3)
    reg.counter("hits", device="dev1").inc(5)
    assert reg.get_value("hits", device="dev0") == 3.0
    assert reg.get_value("hits", device="dev1") == 5.0
    assert reg.get_value("hits") is None  # unlabelled series never created
    assert len(reg.series()) == 2


def test_registry_kind_mismatch_and_histogram_get_value():
    reg = MetricsRegistry()
    reg.counter("x", "a counter").inc()
    with pytest.raises(TypeError, match="already registered as counter"):
        reg.gauge("x")
    reg.histogram("lat_s").observe(0.1)
    assert reg.get_value("lat_s") is None  # histograms have no scalar value
    assert reg.help_text("x") == "a counter"


def test_metrics_install_uninstall_active():
    assert obs_metrics.active() is None
    reg = obs_metrics.install()
    assert obs_metrics.active() is reg
    assert obs_metrics.uninstall() is reg
    assert obs_metrics.active() is None


# -------------------------------------------------------------- exporters
def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("rx_frames_total", "frames decoded", device="dev0").inc(100)
    reg.gauge("fleet_power_w").set(123.5)
    reg.histogram("tick_s", "tick latency", lo=1e-3, hi=1.0).observe(0.01)
    text = prometheus_text(reg)
    assert "# HELP rx_frames_total frames decoded" in text
    assert "# TYPE rx_frames_total counter" in text
    assert 'rx_frames_total{device="dev0"} 100.0' in text
    assert "fleet_power_w 123.5" in text
    assert "# TYPE tick_s histogram" in text
    assert 'tick_s_bucket{le="+Inf"} 1' in text
    assert "tick_s_count 1" in text and "tick_s_sum 0.01" in text
    assert prometheus_text(MetricsRegistry()) == ""


def test_chrome_trace_device_fallback_without_anchor():
    rec = TraceRecorder(capacity=16)
    rec.device_instant("fault:dropout", 0.5, track="faults:dev0")
    evs = chrome_trace_events(rec)
    procs = {e["pid"]: e["args"]["name"]
             for e in evs if e["name"] == "process_name"}
    assert procs == {1: "repro", 2: "device-time"}
    (inst,) = [e for e in evs if e.get("ph") == "i"]
    assert inst["pid"] == 2 and inst["ts"] == 500_000  # raw device µs


def test_chrome_trace_anchored_alignment_and_counters():
    rec = TraceRecorder(capacity=16)
    rec.anchor(1.0, wall_us=rec.t0_us + 100)  # device 1.0 s == t0 + 100 µs
    rec.device_span("k", 1.0, 1.002, track="attr")
    rec.counter("rx.frames", 64.0, t_us=rec.t0_us + 40, track="rx")
    evs = chrome_trace_events(rec)
    assert all(e["pid"] == 1 for e in evs if e["name"] != "process_name")
    (span,) = [e for e in evs if e.get("ph") == "X"]
    assert span["ts"] == 100 and span["dur"] == 2000  # shifted onto wall
    (ctr,) = [e for e in evs if e.get("ph") == "C"]
    assert ctr["ts"] == 40 and ctr["args"] == {"rx.frames": 64.0}
    # distinct tracks get distinct named threads within the process
    named = {e["args"]["name"]: (e["pid"], e["tid"])
             for e in evs if e["name"] == "thread_name"}
    assert set(named) == {"attr", "rx"}
    assert len(set(named.values())) == 2


def test_chrome_trace_json_and_write(tmp_path):
    rec = TraceRecorder(capacity=2)
    for i in range(3):  # one event drops
        rec.instant(f"e{i}")
    text = chrome_trace_json(rec, metadata={"scenario": "unit"})
    doc = json.loads(text)
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"] == {
        "recorded_events": 3, "dropped_events": 1, "scenario": "unit",
    }
    p = tmp_path / "trace.json"
    write_chrome_trace(rec, str(p))
    assert json.loads(p.read_text())["traceEvents"]
    buf = io.StringIO()
    write_chrome_trace(rec, buf)
    assert json.loads(buf.getvalue())["otherData"]["recorded_events"] == 3


# ------------------------------------------------------- package plumbing
def test_enable_disable_roundtrip():
    rec, reg = obs.enable(capacity=32)
    assert obs_trace.active() is rec and rec.capacity == 32
    assert obs_metrics.active() is reg
    obs.disable()
    assert obs_trace.active() is None and obs_metrics.active() is None


def test_lazy_watch_attribute():
    mod = obs.watch
    assert hasattr(mod, "SignatureWatchdog")
    with pytest.raises(AttributeError, match="no attribute 'bogus'"):
        obs.bogus


# ------------------------------------------------- instrumented call sites
def test_host_emits_frame_counters_and_anchor():
    dev = make_device(["pcie8pin-20a"], ConstantLoad(12.0, 1.0), seed=0)
    ps = PowerSensor(dev)
    try:
        ps.run_for(0.01)
        frames0 = ps._frame_count  # handshake-era frames predate tracing
        rec, _reg = obs.enable()
        ps.mark("S")
        ps.run_for(0.02)
        assert rec.counter_total("rx.frames") == float(ps._frame_count - frames0)
        assert rec.counter_total("rx.markers") >= 1.0
        assert rec.anchors, "receiver must anchor device time on first batch"
        track = rec.events_named("rx.frames")[0].track
        assert track.startswith("rx:")
    finally:
        ps.close()


def test_host_is_silent_when_disabled():
    dev = make_device(["pcie8pin-20a"], ConstantLoad(12.0, 1.0), seed=0)
    ps = PowerSensor(dev)
    try:
        ps.run_for(0.02)  # no recorder installed: must simply not crash
    finally:
        ps.close()
    assert obs_trace.active() is None


def test_fleet_emits_power_and_health_series():
    from repro.faultlab import Disconnect, Scenario, inject
    from repro.stream import make_virtual_fleet

    fleet = make_virtual_fleet(
        [ConstantLoad(12.0, 2.0), ConstantLoad(12.0, 3.0)],
        window_s=0.02, lost_after_s=0.15,
    )
    rec, reg = obs.enable()
    inject(fleet, Scenario(faults=(Disconnect(0.1, 0.4, devices=("dev0",)),)))
    try:
        t = 0.0
        while t < 0.6 - 1e-12:
            fleet.advance(0.02)
            t += 0.02
            fleet.fleet_power()
    finally:
        fleet.close()
    assert reg.get_value("fleet_power_reads_total") == 30.0
    assert reg.get_value("fleet_power_w") > 0.0
    assert 0.0 < reg.get_value("fleet_quorum_frac") <= 1.0
    # the disconnected device's health walk lands on the transition counter
    assert reg.get_value("fleet_health_transitions_total",
                         device="dev0", to="stale") >= 1.0
    assert reg.get_value("fleet_health_transitions_total",
                         device="dev0", to="healthy") >= 1.0
    health_evs = [e for e in rec.events() if e.name.startswith("health:")]
    assert health_evs and all(e.track == "health:dev0" for e in health_evs)


def test_scheduler_emits_admission_and_settlement_series():
    from repro.sched import ContinuousBatch, EnergyPricer, Request, get_policy

    rec, reg = obs.enable()
    sched = ContinuousBatch(
        EnergyPricer(j_per_token=1.0), get_policy("throughput-max"), n_slots=2
    )
    sched.submit(Request(rid=0, client="a", gen_len=2))
    sched.submit(Request(rid=1, client="b", gen_len=2))
    sched.admit(0.0)
    for _ in range(2):
        sched.step_billing(1)
    sealed = sched.seal_interval()
    sched.settle_interval(sealed.index, 10.0)
    assert reg.get_value("sched_admitted_total") == 2.0
    assert reg.get_value("sched_intervals_sealed_total") == 1.0
    assert reg.get_value("sched_intervals_settled_total", mode="measured") == 1.0
    assert reg.get_value("sched_settled_joules_total") == 10.0
    names = {e.name for e in rec.events()}
    assert {"sched:admit", "sched:queue", "sched:step", "sched:seal", "sched:settle"} <= names
    assert all(e.track == "sched" for e in rec.events())
    # ids and indices travel as values, never in the name
    assert sorted(e.value for e in rec.events_named("sched:queue")) == [0.0, 1.0]
    assert [e.value for e in rec.events_named("sched:seal")] == [float(sealed.index)]
    assert [e.value for e in rec.events_named("sched:settle")] == [float(sealed.index)]


def test_governor_emits_tick_metrics():
    from repro.power import V5E
    from repro.sched import (
        GovernorConfig,
        OperatingGrid,
        PowerCapGovernor,
        VirtualPlant,
        decode_cost_of_batch,
    )

    grid = OperatingGrid(
        decode_cost_of_batch(80e6, 80e6, tokens_per_slot_step=8),
        n_layers=4, batches=(1, 2, 4, 8), tokens_per_slot_step=8,
    )
    rec, reg = obs.enable()
    plant = VirtualPlant(grid, n_devices=1, biases=[1.0], seed=0,
                         calibrate_samples=0)
    gov = PowerCapGovernor(
        plant, GovernorConfig(cap_w=0.8 * grid.max_watts, kp=0.15, ki=80.0)
    )
    try:
        gov.run(0.1, demand_of_t=lambda t: 8)
    finally:
        plant.close()
    ticks = reg.get_value("governor_ticks_total")
    assert ticks and ticks == float(len(gov.history))
    assert reg.get_value("governor_measured_w") >= V5E.p_static
    switch_evs = [e for e in rec.events()
                  if e.name.startswith("governor:switch")]
    if gov.n_switches:  # every switch shows up on the governor track
        assert len(switch_evs) == gov.n_switches
        assert all(e.track == "governor" for e in switch_evs)


def test_fault_ledger_obs_overlay():
    from repro.faultlab.transport import FaultLedger

    led = FaultLedger(
        device="dev3",
        dropped_spans=[(0.1, 0.2)],
        disconnect_spans=[(0.4, 0.5)],
        drift_spans=[(0.6, 0.7, 1.5)],
    )
    assert led.record_obs(None) == 0  # no recorder anywhere: clean no-op
    rec = TraceRecorder(capacity=16)
    assert led.record_obs(rec, epoch_s=1.0) == 3
    spans = {e.name: e for e in rec.events()}
    assert set(spans) == {"fault:dropout", "fault:disconnect", "fault:drift x1.5"}
    drop = spans["fault:dropout"]
    assert drop.clock == DEVICE and drop.track == "faults:dev3"
    assert drop.t_us == 1_100_000 and drop.dur_us == 100_000
    assert spans["fault:drift x1.5"].value == 1.5
