"""FleetMonitor: one queryable snapshot API over N PowerSensor devices.

Scales the host side from "one sensor, one script" to a fleet of devices
feeding live consumers (paper §III-C's lightweight-receiver design, applied
per device).  The monitor

* owns named `PowerSensor` instances (any object with the PowerSensor
  surface: ``poll``, ``read``, ``mark``, ``ring``, ``markers``, ``device``);
* drains them **round-robin** (``poll(k)`` / ``poll_all()``) or via one
  background receiver thread per device (``start_threads``);
* exposes `snapshot()`: per-device windowed stats (from each device's ring
  buffer) plus fleet aggregates computed as the sum over devices;
* answers **marker-aligned interval queries**: energy / average power per
  device between two named markers, straight from the ring buffer.

For *per-kernel* accounting on top of these primitives — changepoint
segmentation of ring views, marker-aligned energy ledgers, power
signatures — see `repro.attrib` (`segment_block` / `attribute_block`
consume the same `FrameBlock`s that `interval()` reads).

Degraded-telemetry semantics (the contract `repro.faultlab` tests):

=============  ==============================  =================================
state          entered when                    effect on fleet queries
=============  ==============================  =================================
healthy        frames younger than             contributes its windowed power
               ``stale_after_s``
stale          no frames for                   excluded from `fleet_power`; the
               ``stale_after_s``               healthy sum is rescaled by the
                                               known fleet fraction (quorum)
lost           no frames for ``lost_after_s``  excluded, and counted against
               *or* its receiver thread died   ``min_quorum_frac``
link-lost      its transport ``read()``        mapped to ``lost`` immediately
(lost)         raised out of a fleet poll      (the poller survives; the error
               (socket died mid-poll)          is held until a later poll
                                               succeeds — reacquire — and is
                                               surfaced via `stop_threads`)
attach-grace   the device was just added, or   staleness is measured from the
(healthy)      `FleetHead` reacquired its      *attach time*, not from an
               link (`note_attach`)            empty ring's epoch — a fresh
                                               device gets ``stale_after_s``
                                               of grace to deliver its first
                                               frame instead of being born
                                               ``lost`` (and emitting a bogus
                                               lost→healthy transition)
backpressure   a bounded link buffer filled    no frame loss and no health
               (`repro.net` receive queues,    change: the reader pauses, the
               server send windows)            sender blocks on the socket,
                                               and the stall is *counted*
                                               (``backpressure_waits``), so a
                                               slow consumer shows up in link
                                               stats instead of as drops
=============  ==============================  =================================

Lock-free reader rules (what `fleet_power` / `window_power_w` see while
the receiver — solo or pooled — is mid-publish):

* `FrameRing.append` runs under the receiver lock and brackets its slice
  writes with a seqlock ``version`` counter (odd while mutating).  Hot
  readers (`tail_mean_watts`) take **no lock**: they snapshot the version,
  reduce, and retry if the version moved.  A reader therefore never
  observes a torn frame — each individual slice store is atomic under the
  GIL, and any read that overlapped a publish is discarded and retried;
* health scans read preallocated per-device mirrors (``last_time_s``,
  ``head``) that the ring updates *after* the version counter closes, so
  a mirror value never refers to frames that are not yet readable;
* block readers (`marker_window`, `snapshot`, `tail_window`) still take
  the receiver lock — they return multi-array copies whose consistency a
  version counter alone cannot vouch for.

When *no* device is healthy, `fleet_power` holds the last good reading
for up to ``holdover_s`` (``holdover=True``); the reading is flagged
``stale`` whenever quorum drops below ``min_quorum_frac``, and consumers
(the power-cap governor) must treat a stale reading as a safety event,
not a number.

Observability under degradation (mirrored in the README table): every
health transition lands one ``health:<from>-><to>`` trace instant plus a
``fleet_health_transitions_total`` increment; stale / holdover readings
are counted per reading (``fleet_stale_reads_total`` /
``fleet_holdover_reads_total``) while stale entry/exit are *edge* events
on the trace timeline; the signature watchdog skips stale/lost devices
(``watchdog_skipped_total``) and freezes their cursors, so recovery
resumes from fresh data instead of re-judging the past.

This module deliberately avoids importing `repro.core` at module scope —
`repro.core.host` imports `repro.stream.ring`, and keeping this side lazy
keeps the package import-cycle free.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Iterable, Mapping

import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from .aggregate import WindowStats, window_stats
from .ring import FrameBlock, FrameRing

if TYPE_CHECKING:  # pragma: no cover
    from repro.core.host import PowerSensor, State


@dataclass(frozen=True)
class DeviceSnapshot:
    name: str
    state: "State"
    window: WindowStats


@dataclass(frozen=True)
class FleetAggregate:
    """Fleet-wide totals: the sum over per-device windowed stats."""

    n_devices: int
    n_frames: int
    mean_w: float  # sum of per-device windowed mean watts
    peak_w: float  # sum of per-device peaks (synchronous-peak upper bound)
    ewma_w: float
    energy_j: float


@dataclass(frozen=True)
class FleetSnapshot:
    time_s: float
    devices: dict[str, DeviceSnapshot]
    aggregate: FleetAggregate


@dataclass(frozen=True)
class DeviceHealth:
    """One device's telemetry liveness at a point in time."""

    name: str
    state: str  # 'healthy' | 'stale' | 'lost'
    staleness_s: float  # now − newest retained frame time
    last_frame_s: float
    receiver_alive: bool  # False when a started poller thread died
    dropped_frames: int

    @property
    def healthy(self) -> bool:
        return self.state == "healthy"


@dataclass(frozen=True)
class FleetPowerReading:
    """Quorum-aware fleet power: a number plus how much to trust it.

    ``power_w`` is the healthy-device sum rescaled by the known fleet
    fraction (``n_total / n_healthy``); ``raw_power_w`` is the unscaled
    healthy sum.  ``stale`` means the estimate must not be trusted for
    control (quorum below ``min_quorum_frac``, or no healthy device at
    all); ``holdover`` means ``power_w`` is the *last good* reading, held
    because nothing fresh exists.  ``data_age_s`` is the age of the data
    behind ``power_w`` (0 for a live reading).
    """

    power_w: float
    raw_power_w: float
    n_healthy: int
    n_total: int
    quorum_frac: float
    stale: bool
    holdover: bool
    time_s: float
    data_age_s: float = 0.0


@dataclass(frozen=True)
class IntervalStats:
    """Marker-aligned interval query result for one device."""

    t0_s: float
    t1_s: float
    n_frames: int
    energy_j: np.ndarray  # per pair
    mean_w: np.ndarray  # per pair

    @property
    def duration_s(self) -> float:
        return self.t1_s - self.t0_s

    @property
    def total_energy_j(self) -> float:
        return float(self.energy_j.sum())

    @property
    def total_mean_w(self) -> float:
        return float(self.mean_w.sum())


class FleetMonitor:
    """Own, poll, and aggregate over a fleet of PowerSensor devices."""

    def __init__(
        self,
        sensors: Mapping[str, "PowerSensor"] | None = None,
        window_s: float = 1.0,
        pct: float = 95.0,
        stale_after_s: float | None = None,
        lost_after_s: float | None = None,
        min_quorum_frac: float = 0.5,
        holdover_s: float | None = None,
    ):
        self._sensors: dict[str, PowerSensor] = {}
        self.window_s = float(window_s)
        self.pct = float(pct)
        # degraded-telemetry thresholds (see the module docstring table)
        self.stale_after_s = (
            max(2.0 * self.window_s, 0.005)
            if stale_after_s is None
            else float(stale_after_s)
        )
        self.lost_after_s = (
            10.0 * self.stale_after_s if lost_after_s is None else float(lost_after_s)
        )
        self.min_quorum_frac = float(min_quorum_frac)
        self.holdover_s = (
            5.0 * self.stale_after_s if holdover_s is None else float(holdover_s)
        )
        self._last_good: tuple[float, float] | None = None  # (time, power_w)
        # transports whose read() raised out of a fleet poll: the device
        # is reported `lost` (not crashed-silent) until a poll succeeds
        self._poll_errors: dict[str, BaseException] = {}
        self._rr = 0  # round-robin cursor
        self._last_health: dict[str, str] = {}  # for obs transition events
        self._stale_streak = False  # edge-trigger for stale-read events
        # attach times: health grace windows start here, not at frame 0
        self._attach_t: dict[str, float] = {}
        # preallocated per-device vectors for the health/power hot path:
        # rings mirror (last_time_s, head) into slots via bind_stats, so a
        # 1 kHz fleet_power tick does vector arithmetic instead of a dict
        # loop over N dataclasses (see _health_vectors)
        self._vnames: list[str] = []
        self._vsensors: list = []
        self._v_last_t = np.zeros(0)
        self._v_head = np.zeros(0, dtype=np.int64)
        self._v_attach = np.zeros(0)
        self._v_err = np.zeros(0, dtype=bool)
        self._v_alive = np.zeros(0, dtype=bool)
        self._prev_code = np.zeros(0, dtype=np.int8)  # -1 = never sighted
        self._unmirrored: list[int] = []  # duck rings without bind_stats
        self._pool = None  # optional PooledDecoder (see enable_pool)
        if sensors:
            for name, ps in sensors.items():
                self.add(name, ps)

    # ------------------------------------------------------------ membership
    def add(self, name: str, sensor: "PowerSensor") -> None:
        if name in self._sensors:
            raise ValueError(f"duplicate device name {name!r}")
        self._sensors[name] = sensor
        # label the receiver's own trace events with the fleet name
        if getattr(sensor, "obs_name", None) is None:
            try:
                sensor.obs_name = name
            except AttributeError:  # duck-typed sensor with __slots__
                pass
        self._rebuild_vectors()
        # health grace starts now: a device joining a long-running fleet
        # must not be born `lost` just because its ring is still empty
        self.note_attach(name)

    def note_attach(self, name: str) -> None:
        """(Re)start ``name``'s health grace window at the fleet's now.

        Called on `add` and by `FleetHead` after a redial reacquires a
        link: staleness is measured from this attach time (or the newest
        frame, whichever is later), so a fresh or reacquired device gets
        ``stale_after_s`` to deliver its first frame instead of reading
        ``staleness = now`` off an empty/frozen ring and instantly
        classifying `lost` (which also emitted a spurious lost→healthy
        transition on the first frame).
        """
        t = self._now_s()
        self._attach_t[name] = t
        i = self._vnames.index(name) if name in self._sensors else -1
        if i >= 0:
            self._v_attach[i] = t

    def _rebuild_vectors(self) -> None:
        """Rebuild the preallocated health mirrors after membership changes."""
        names = list(self._sensors)
        self._vnames = names
        self._vsensors = [self._sensors[nm] for nm in names]
        n = len(names)
        self._v_last_t = np.zeros(n)
        self._v_head = np.zeros(n, dtype=np.int64)
        self._v_attach = np.array(
            [self._attach_t.get(nm, 0.0) for nm in names]
        ) if n else np.zeros(0)
        self._v_err = np.array([nm in self._poll_errors for nm in names], dtype=bool)
        self._v_alive = np.ones(n, dtype=bool)
        code_of = {"healthy": 0, "stale": 1, "lost": 2}
        self._prev_code = np.array(
            [code_of.get(self._last_health.get(nm), -1) for nm in names],
            dtype=np.int8,
        )
        self._unmirrored = []
        for i, ps in enumerate(self._vsensors):
            ring = getattr(ps, "ring", None)
            if ring is not None and hasattr(ring, "bind_stats"):
                ring.bind_stats(self._v_last_t, self._v_head, i)
            else:
                self._unmirrored.append(i)

    def __len__(self) -> int:
        return len(self._sensors)

    def __getitem__(self, name: str) -> "PowerSensor":
        return self._sensors[name]

    @property
    def names(self) -> list[str]:
        return list(self._sensors)

    # ------------------------------------------------------------ polling
    def _safe_poll(self, name: str, ps: "PowerSensor") -> int:
        """Poll one device; a raising transport maps to `lost`, not a crash.

        A socket that dies mid-``read()`` raises out of ``poll()``; killing
        the whole fleet poller for one bad link would silently freeze every
        *other* device's ring.  The error is recorded (driving the device's
        health to ``lost``, surfaced later by `stop_threads`) and cleared
        again by the first successful poll — the reacquire path.
        """
        try:
            n = ps.poll()
        except BaseException as exc:
            self._mark_poll_error(name, exc)
            return 0
        self._clear_poll_error(name)
        return n

    def _mark_poll_error(self, name: str, exc: BaseException) -> None:
        fresh = name not in self._poll_errors
        self._poll_errors[name] = exc
        try:
            self._v_err[self._vnames.index(name)] = True
        except ValueError:
            pass
        reg = obs_metrics.active()
        if reg is not None:
            reg.counter(
                "fleet_poll_errors_total",
                "transport read() failures escaping a device poll",
                device=name,
            ).inc()
        if fresh:
            rec = obs_trace.active()
            if rec is not None:
                rec.device_instant(
                    f"link:poll-error:{type(exc).__name__}",
                    self._now_s(), track=f"health:{name}",
                )

    def _clear_poll_error(self, name: str) -> None:
        if self._poll_errors.pop(name, None) is not None:
            try:
                self._v_err[self._vnames.index(name)] = False
            except ValueError:
                pass

    def poll(self, k: int = 1) -> int:
        """Drain the next ``k`` devices round-robin. Returns frames seen."""
        names = self.names
        if not names:
            return 0
        total = 0
        for _ in range(min(k, len(names))):
            name = names[self._rr % len(names)]
            self._rr += 1
            total += self._safe_poll(name, self._sensors[name])
        return total

    def poll_all(self) -> int:
        if self._pool is not None:
            res = self._pool.poll()
            if self._poll_errors:  # reacquired links clear on first success
                for nm in res.polled:
                    self._clear_poll_error(nm)
            for nm, exc in res.errors.items():
                self._mark_poll_error(nm, exc)
            return res.frames
        return self.poll(len(self._sensors))

    def enable_pool(self):
        """Switch `poll_all` to the fused fleet-wide decode path.

        Builds a `repro.stream.pool.PooledDecoder` over this monitor's
        sensors (membership changes are picked up live).  Decoded output
        is bit-identical to per-device polling; only the cost changes —
        one fused numpy pass instead of N full receiver passes.
        """
        if self._pool is None:
            from .pool import PooledDecoder

            self._pool = PooledDecoder(self._sensors)
        return self._pool

    @property
    def pool(self):
        """The attached `PooledDecoder` (None: per-device polling)."""
        return self._pool

    @property
    def poll_errors(self) -> dict[str, BaseException]:
        """Live view of per-device transport errors (cleared on reacquire)."""
        return dict(self._poll_errors)

    def start_threads(self, real_time_factor: float = 0.0, tick_s: float = 0.01) -> None:
        """One lightweight receiver thread per device (§III-C, per device)."""
        for ps in self._sensors.values():
            ps.start_thread(real_time_factor=real_time_factor, tick_s=tick_s)

    def stop_threads(self, timeout_s: float = 5.0) -> dict[str, BaseException]:
        """Stop every receiver thread, joining each with a timeout.

        Returns ``{device: error}`` for every receiver that died mid-poll
        or refused to join — a dead poller previously vanished here while
        `window_power_w` kept serving its frozen ring forever.  The errors
        are also warned so unchecked callers still get a signal.
        """
        errors: dict[str, BaseException] = dict(self._poll_errors)
        for name, ps in self._sensors.items():
            try:
                err = ps.stop_thread(timeout_s=timeout_s)
            except TypeError:  # duck-typed sensor without the timeout param
                err = ps.stop_thread()
            if err is not None:
                errors[name] = err
        if errors:
            detail = "; ".join(f"{n}: {e!r}" for n, e in errors.items())
            warnings.warn(f"fleet receiver thread(s) failed — {detail}", RuntimeWarning)
        return errors

    # ------------------------------------------------------------ sim helpers
    def advance(self, dt_s: float) -> None:
        """Advance every (virtual) device's clock and drain it."""
        with obs_trace.span("fleet:advance", devices=len(self._sensors)):
            for ps in self._sensors.values():
                ps.device.advance(dt_s)
            self.poll_all()

    def run_for(self, seconds: float, chunk_s: float = 0.5) -> None:
        remaining = seconds
        while remaining > 1e-12:
            step = min(chunk_s, remaining)
            self.advance(step)
            remaining -= step

    # ------------------------------------------------------------ markers
    def mark_all(self, char: str = "M") -> None:
        with obs_trace.span("fleet:mark", devices=len(self._sensors)):
            for ps in self._sensors.values():
                ps.mark(char)

    def _marker_time(self, ps: "PowerSensor", char: str, occurrence: int = 0) -> float | None:
        hits = [t for c, t in ps.markers if c == char]
        if occurrence >= len(hits):
            return None
        return hits[occurrence]

    def marker_window(
        self,
        device: str,
        char_a: str,
        char_b: str | None = None,
        occurrence: int = 0,
        occurrence_b: int | None = None,
    ) -> tuple[float, float, FrameBlock] | None:
        """One device's ring frames between two marker occurrences.

        Returns ``(t0, t1, block)`` — the marker times plus a locked read
        of the frames between them — or None when either marker is
        missing, out of order, under-sampled, or no longer fully retained
        (an evicted head would silently undercount).  ``char_b`` defaults
        to ``char_a``, so one repeated char brackets an unbounded sequence
        of intervals — wave ``k`` is ``occurrence=k, occurrence_b=k+1`` —
        with no wrapping marker alphabet to collide.

        This is the raw-frames core under `interval()`; consumers that do
        their own integration (e.g. `repro.attrib.attribute_block`) start
        here instead of reaching into the ring and lock directly.
        """
        with obs_trace.span("fleet:window", devices=len(self._sensors)):
            if char_b is None:
                char_b = char_a
            if occurrence_b is None:
                occurrence_b = occurrence
            ps = self._sensors[device]
            # one pass over the (copied) marker list serves both lookups
            hits_a = [t for c, t in ps.markers if c == char_a]
            hits_b = hits_a if char_b == char_a else [t for c, t in ps.markers if c == char_b]
            if occurrence >= len(hits_a) or occurrence_b >= len(hits_b):
                return None
            t0, t1 = hits_a[occurrence], hits_b[occurrence_b]
            if t1 <= t0:
                return None
            block = self._locked_ring_read(ps, lambda: ps.ring.window(t0, t1))
            if len(block) < 2:
                return None
            # evicted head: first retained frame starts well after t0.  The
            # frame interval is estimated as the *median* inter-frame dt — the
            # first two frames alone are unreliable exactly when it matters
            # (a delivery gap at the window's leading edge inflates their dt,
            # making this check too lenient and silently accepting a window
            # that is missing its leading coverage)
            frame_dt = float(np.median(np.diff(block.times_s)))
            if block.times_s[0] - t0 > 2.0 * frame_dt:
                return None
            return t0, t1, block

    def marker_windows(
        self,
        device: str,
        char: str,
        start_occurrence: int = 0,
    ) -> list[tuple[int, float, float, FrameBlock]]:
        """All retained step intervals of one repeated marker char.

        Returns ``(k, t0, t1, block)`` for every interval ``k`` (occurrence
        ``k`` → ``k+1`` of ``char``) from ``start_occurrence`` on that the
        ring still fully retains, with `marker_window`'s integrity rules
        applied per interval.  Unretainable intervals are *skipped, not a
        stop*: after a fault or head eviction swallows interval ``k``,
        later intervals may still be intact — the continuous-batching
        settle loop releases the missing ones at prediction and settles
        the rest from measurement.
        """
        ps = self._sensors[device]
        hits = [t for c, t in ps.markers if c == char]
        out: list[tuple[int, float, float, FrameBlock]] = []
        for k in range(max(int(start_occurrence), 0), len(hits) - 1):
            hit = self.marker_window(device, char, occurrence=k, occurrence_b=k + 1)
            if hit is None:
                continue
            t0, t1, block = hit
            out.append((k, t0, t1, block))
        return out

    def interval(
        self,
        char_a: str,
        char_b: str,
        occurrence: int = 0,
        occurrence_b: int | None = None,
    ) -> dict[str, IntervalStats]:
        """Per-device energy/power between markers `char_a` and `char_b`.

        ``occurrence`` indexes repeated markers; ``occurrence_b`` (default:
        same as ``occurrence``) indexes the closing marker independently —
        see `marker_window()`, which this integrates over per device.

        Devices missing either marker, or whose ring no longer retains the
        *whole* span (eviction would silently undercount), are omitted.
        """
        out: dict[str, IntervalStats] = {}
        for name in self._sensors:
            hit = self.marker_window(name, char_a, char_b, occurrence, occurrence_b)
            if hit is None:
                continue
            t0, t1, block = hit
            out[name] = IntervalStats(
                t0_s=t0,
                t1_s=t1,
                n_frames=len(block),
                energy_j=np.trapezoid(block.watts, block.times_s, axis=0),
                mean_w=block.watts.mean(axis=0),
            )
        return out

    # ------------------------------------------------------------ snapshots
    @staticmethod
    def _locked_ring_read(ps: "PowerSensor", fn):
        """Read from a sensor's ring under its receiver lock (thread mode)."""
        lock = getattr(ps, "_lock", None)
        if lock is None:
            return fn()
        with lock:
            return fn()

    @classmethod
    def _ring_tail_mean(cls, ps: "PowerSensor", window_s: float) -> float:
        """Trailing-window mean power, lock-free where the ring allows it.

        `FrameRing.tail_mean_watts` is seqlock-protected (see the module
        docstring's lock-free reader rules) so the hot path never takes
        the receiver lock; duck-typed rings without the version counter
        keep the locked read.
        """
        ring = ps.ring
        if isinstance(ring, FrameRing):
            return ring.tail_mean_watts(window_s)
        return cls._locked_ring_read(ps, lambda: ring.tail_mean_watts(window_s))

    def read_all(self) -> dict[str, "State"]:
        return {name: ps.read() for name, ps in self._sensors.items()}

    # ------------------------------------------------------------ health
    def _now_s(self) -> float:
        """The fleet's 'now': the newest clock any device can vouch for."""
        best = 0.0
        for ps in self._sensors.values():
            t = getattr(ps.device, "t_s", None)
            best = max(best, ps.ring.last_time_s if t is None else float(t))
        return best

    _STATE_NAMES = ("healthy", "stale", "lost")

    def _health_vectors(self, now: float) -> tuple[np.ndarray, np.ndarray]:
        """(codes, staleness) over the preallocated per-device mirrors.

        The `fleet_power` hot path: no dict, no dataclasses, no per-device
        ring attribute reads — the rings mirror (last_time_s, head) into
        shared slots on every append (`FrameRing.bind_stats`), and health
        classification is three vector ops.  Codes: 0 healthy / 1 stale /
        2 lost.  Also emits the health-transition obs events (diffed
        against the previous codes, so steady state emits nothing).
        """
        for i in self._unmirrored:  # duck rings without the stats mirror
            ring = self._vsensors[i].ring
            self._v_last_t[i] = ring.last_time_s if len(ring) else 0.0
            self._v_head[i] = len(ring)
        has_frames = self._v_head > 0
        # grace window: staleness runs from the newest frame or the attach
        # time, whichever is later — never from an empty ring's epoch
        eff_last = np.where(
            has_frames,
            np.maximum(self._v_last_t, self._v_attach),
            self._v_attach,
        )
        staleness = np.maximum(now - eff_last, 0.0)
        alive = self._v_alive
        alive[:] = True
        for i, ps in enumerate(self._vsensors):
            if not getattr(ps, "receiver_ok", True):
                alive[i] = False
        np.logical_and(alive, ~self._v_err, out=alive)
        codes = np.where(
            ~alive | (staleness > self.lost_after_s),
            np.int8(2),
            np.where(staleness > self.stale_after_s, np.int8(1), np.int8(0)),
        )
        changed = np.flatnonzero(codes != self._prev_code)
        for i in changed:
            name = self._vnames[i]
            state = self._STATE_NAMES[codes[i]]
            prev = self._last_health.get(name)
            self._last_health[name] = state
            self._prev_code[i] = codes[i]
            if prev is not None and prev != state:
                rec = obs_trace.active()
                if rec is not None:
                    rec.device_instant(
                        f"health:{prev}->{state}", now,
                        track=f"health:{name}", value=float(staleness[i]),
                    )
                reg = obs_metrics.active()
                if reg is not None:
                    reg.counter(
                        "fleet_health_transitions_total",
                        "device health state changes",
                        device=name, to=state,
                    ).inc()
        return codes, staleness

    def device_health(self, now_s: float | None = None) -> dict[str, DeviceHealth]:
        """Per-device health states (see the module docstring table)."""
        now = self._now_s() if now_s is None else float(now_s)
        codes, staleness = self._health_vectors(now)
        out: dict[str, DeviceHealth] = {}
        for i, name in enumerate(self._vnames):
            ps = self._vsensors[i]
            out[name] = DeviceHealth(
                name=name,
                state=self._STATE_NAMES[codes[i]],
                staleness_s=float(staleness[i]),
                last_frame_s=float(self._v_last_t[i]) if self._v_head[i] > 0 else 0.0,
                receiver_alive=bool(self._v_alive[i]),
                dropped_frames=int(getattr(ps, "dropped_frames", 0)),
            )
        return out

    def fleet_power(
        self,
        window_s: float | None = None,
        poll: bool = True,
        now_s: float | None = None,
    ) -> FleetPowerReading:
        """Quorum-based fleet power with explicit staleness semantics.

        Healthy devices contribute their trailing-window ring power; the
        sum is rescaled by the known fleet fraction so a partial quorum
        still estimates *fleet* watts.  Stale/lost devices are excluded —
        their rings only hold the past — instead of silently freezing the
        total.  With no healthy device at all the last good reading is
        held for ``holdover_s`` (``holdover=True``); any reading whose
        quorum is below ``min_quorum_frac`` is flagged ``stale``.
        """
        window_s = self.window_s if window_s is None else float(window_s)
        if poll:
            self.poll_all()
        now = self._now_s() if now_s is None else float(now_s)
        codes, _ = self._health_vectors(now)
        n_total = len(self._sensors)
        healthy_idx = np.flatnonzero(codes == 0)
        n_healthy = int(healthy_idx.size)
        quorum = n_healthy / n_total if n_total else 0.0
        if n_healthy:
            # lock-free seqlock reads: the governor's tick never contends
            # with the receiver lock (duck rings fall back to locked reads)
            raw = 0.0
            for i in healthy_idx:
                raw += self._ring_tail_mean(self._vsensors[i], window_s)
            power = raw * n_total / n_healthy
            stale = quorum < self.min_quorum_frac
            if not stale:
                self._last_good = (now, power)
            self._note_reading(now, power, quorum, stale, holdover=False)
            return FleetPowerReading(
                power_w=power,
                raw_power_w=raw,
                n_healthy=n_healthy,
                n_total=n_total,
                quorum_frac=quorum,
                stale=stale,
                holdover=False,
                time_s=now,
            )
        # nothing healthy: holdover semantics, always flagged stale
        if self._last_good is not None:
            t_good, p_good = self._last_good
            age = max(now - t_good, 0.0)
            self._note_reading(now, p_good, 0.0, True, holdover=age <= self.holdover_s)
            return FleetPowerReading(
                power_w=p_good,
                raw_power_w=0.0,
                n_healthy=0,
                n_total=n_total,
                quorum_frac=0.0,
                stale=True,
                holdover=age <= self.holdover_s,
                time_s=now,
                data_age_s=age,
            )
        self._note_reading(now, 0.0, 0.0, True, holdover=False)
        return FleetPowerReading(
            power_w=0.0,
            raw_power_w=0.0,
            n_healthy=0,
            n_total=n_total,
            quorum_frac=0.0,
            stale=True,
            holdover=False,
            time_s=now,
            data_age_s=math.inf,
        )

    def _note_reading(
        self, now: float, power_w: float, quorum: float, stale: bool, holdover: bool
    ) -> None:
        """Obs hooks for one `fleet_power` reading (no-ops when disabled).

        Stale entry/exit are *edge* events on the trace timeline (a 1 kHz
        control loop would otherwise flood the ring); counters accumulate
        per reading so scrape-side rates stay meaningful.
        """
        reg = obs_metrics.active()
        if reg is not None:
            reg.counter("fleet_power_reads_total", "fleet_power readings").inc()
            if stale:
                reg.counter(
                    "fleet_stale_reads_total",
                    "fleet_power readings flagged stale (quorum below floor)",
                ).inc()
            if holdover:
                reg.counter(
                    "fleet_holdover_reads_total",
                    "stale readings served from the held last-good value",
                ).inc()
            reg.gauge("fleet_power_w", "latest fleet power estimate").set(power_w)
            reg.gauge("fleet_quorum_frac", "latest healthy-device fraction").set(quorum)
        if stale != self._stale_streak:
            self._stale_streak = stale
            rec = obs_trace.active()
            if rec is not None:
                rec.device_instant(
                    "fleet:stale-enter" if stale else "fleet:stale-exit",
                    now, track="fleet", value=quorum,
                )

    def window_power_w(self, window_s: float | None = None, poll: bool = True) -> float:
        """Fleet-summed trailing-window mean power — the governor's fast hook.

        Unlike `snapshot()` this never materialises `FrameBlock` copies:
        each device answers from its ring's maintained per-frame totals
        (`FrameRing.tail_mean_watts`), so a control loop can poll it every
        millisecond without competing with the 20 kHz receive path.

        Quorum-based since the fault-injection lab landed: stale and lost
        devices are excluded and the healthy sum is rescaled by the known
        fleet fraction — callers that need the staleness/holdover flags
        use `fleet_power` (this is its ``power_w`` field).
        """
        return self.fleet_power(window_s, poll=poll).power_w

    def device_window_power_w(
        self, window_s: float | None = None, poll: bool = True
    ) -> dict[str, float]:
        """Per-device trailing-window mean power (same fast path)."""
        window_s = self.window_s if window_s is None else float(window_s)
        out: dict[str, float] = {}
        if poll and self._pool is not None:
            self.poll_all()
        for name, ps in self._sensors.items():
            if poll and self._pool is None:
                self._safe_poll(name, ps)
            out[name] = self._ring_tail_mean(ps, window_s)
        return out

    def snapshot(self, window_s: float | None = None) -> FleetSnapshot:
        """One queryable view of the whole fleet: per-device + aggregate."""
        window_s = self.window_s if window_s is None else float(window_s)
        devices: dict[str, DeviceSnapshot] = {}
        for name, ps in self._sensors.items():
            state = ps.read()  # drains the device, then snapshots
            block = self._locked_ring_read(ps, lambda: ps.ring.tail_window(window_s))
            stats = window_stats(block, pct=self.pct)
            devices[name] = DeviceSnapshot(name=name, state=state, window=stats)
        snaps = devices.values()
        agg = FleetAggregate(
            n_devices=len(devices),
            n_frames=sum(d.window.n_frames for d in snaps),
            mean_w=sum(d.window.total_mean_w for d in snaps),
            peak_w=sum(d.window.total_peak_w for d in snaps),
            ewma_w=sum(d.window.total_ewma_w for d in snaps),
            energy_j=sum(d.window.total_energy_j for d in snaps),
        )
        t = max((d.state.time_s for d in snaps), default=0.0)
        return FleetSnapshot(time_s=t, devices=devices, aggregate=agg)

    def close(self) -> None:
        self.stop_threads()
        for ps in self._sensors.values():
            ps.close()


def make_virtual_fleet(
    loads: Iterable,
    module: str = "pcie8pin-20a",
    seed: int = 0,
    window_s: float = 1.0,
    ring_capacity: int = 1 << 16,
    **monitor_kwargs,
) -> FleetMonitor:
    """Build a FleetMonitor over virtual devices, one per load.

    Extra keyword arguments (``stale_after_s``, ``min_quorum_frac``, ...)
    are forwarded to the `FleetMonitor`.
    """
    from repro.core import PowerSensor, make_device

    fleet = FleetMonitor(window_s=window_s, **monitor_kwargs)
    for i, load in enumerate(loads):
        dev = make_device([module], load, seed=seed * 1009 + i)
        fleet.add(f"dev{i}", PowerSensor(dev, ring_capacity=ring_capacity))
    return fleet
