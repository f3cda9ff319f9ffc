"""Flight recorder: a preallocated ring buffer of spans, instants, counters.

Design goals, in order:

1. **Near-zero cost when disabled.**  Call sites hold no recorder; they
   ask :func:`active` for the module-global and skip everything when it
   is ``None``.  That is one attribute read and one identity test.
2. **Bounded, allocation-free recording.**  All event storage is
   preallocated numpy columns; recording writes six scalars under a
   lock.  When the ring wraps, the oldest events are overwritten
   (flight-recorder semantics) and ``dropped`` counts them.
3. **Two clock domains.**  Control-plane events are stamped with the
   wall monotonic clock (``time.perf_counter_ns() // 1000``, µs).
   Device-side overlays (fault windows, marker-delimited attribution
   intervals) live on the virtual device clock, in seconds.  Recorded
   ``anchor`` pairs let the exporter shift device-time tracks onto the
   wall timeline so one Perfetto view aligns both.
4. **One span call, two sinks.**  :func:`span` is what the program's
   parts (scheduler, KV pool, sensor fleet, attribution) call around
   their work.  It opens a profiler TraceMe whenever JAX is loaded, so
   the span lands in any JAX profiler trace on the device trace's clock
   with its metadata as the event's stats; with a recorder installed it
   also writes the span into the ring.  Span names come from a fixed
   set: indices and ids travel as metadata, never in the name, so the
   name table stays bounded however long a server runs.

Only numpy + stdlib may be imported here: ``repro.core.host`` and
``repro.stream.fleet`` import this module from their hot paths.  JAX is
looked up in ``sys.modules`` when a span opens, never imported.
"""

from __future__ import annotations

import sys
import threading
import time
from dataclasses import dataclass

import numpy as np

__all__ = [
    "SPAN",
    "INSTANT",
    "COUNTER",
    "WALL",
    "DEVICE",
    "TraceEvent",
    "TraceRecorder",
    "install",
    "uninstall",
    "active",
    "now_us",
    "span",
]

# event kinds
SPAN = 0  # t_us = start, dur_us = duration  (Chrome phase "X")
INSTANT = 1  # point event                     (Chrome phase "i")
COUNTER = 2  # value sample on a counter track (Chrome phase "C")

# clock domains for tracks
WALL = 0  # monotonic microseconds (perf_counter)
DEVICE = 1  # virtual device seconds, stored as microseconds

_KIND_NAMES = {SPAN: "span", INSTANT: "instant", COUNTER: "counter"}
_CLOCK_NAMES = {WALL: "wall", DEVICE: "device"}


def now_us() -> int:
    """Current wall (monotonic) time in microseconds."""
    return time.perf_counter_ns() // 1000


@dataclass(frozen=True)
class TraceEvent:
    """One decoded ring entry, oldest-first order from :meth:`events`."""

    kind: int
    name: str
    track: str
    clock: int
    t_us: int
    dur_us: int
    value: float

    @property
    def kind_name(self) -> str:
        return _KIND_NAMES[self.kind]

    @property
    def t1_us(self) -> int:
        return self.t_us + self.dur_us


class _Span:
    """Context manager recording a wall-clock span on exit, inside a
    profiler TraceMe ``me`` when one is given (:func:`span`)."""

    __slots__ = ("_rec", "_name", "_track", "_value", "_t0", "_me")

    def __init__(self, rec: "TraceRecorder", name: str, track: str, value: float,
                 me=None):
        self._rec = rec
        self._name = name
        self._track = track
        self._value = value
        self._me = me

    def __enter__(self) -> "_Span":
        self._t0 = now_us()
        if self._me is not None:
            self._me.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._me is not None:
            self._me.__exit__(*exc)
        t1 = now_us()
        self._rec.span_at(
            self._name, self._t0, t1, track=self._track, value=self._value
        )

    def set_metadata(self, **meta) -> None:
        """Add metadata known only once the work is done (profiler only)."""
        if self._me is not None:
            self._me.set_metadata(**meta)


class TraceRecorder:
    """Preallocated, thread-safe ring buffer of trace events.

    ``capacity`` is the number of retained events; older events are
    overwritten once the ring wraps.  ``head`` counts every event ever
    recorded (monotonic), so ``dropped == max(0, head - capacity)``.
    """

    def __init__(self, capacity: int = 1 << 16):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = int(capacity)
        self._kind = np.zeros(self.capacity, dtype=np.uint8)
        self._name_id = np.zeros(self.capacity, dtype=np.uint32)
        self._track_id = np.zeros(self.capacity, dtype=np.uint16)
        self._t_us = np.zeros(self.capacity, dtype=np.int64)
        self._dur_us = np.zeros(self.capacity, dtype=np.int64)
        self._value = np.zeros(self.capacity, dtype=np.float64)
        self._lock = threading.Lock()
        self.head = 0
        # string interning: names and tracks are small, bounded sets
        self._names: list[str] = []
        self._name_ids: dict[str, int] = {}
        self._tracks: list[str] = []
        self._track_ids: dict[str, int] = {}
        self._track_clock: dict[int, int] = {}
        # wall<->device correspondence points: (wall_us, device_us)
        self._anchors: list[tuple[int, int]] = []
        self.t0_us = now_us()

    # -- interning ---------------------------------------------------------

    def _intern_name(self, name: str) -> int:
        nid = self._name_ids.get(name)
        if nid is None:
            nid = len(self._names)
            if nid > 0xFFFFFFFF:
                raise RuntimeError("too many distinct trace names")
            self._names.append(name)
            self._name_ids[name] = nid
        return nid

    def _intern_track(self, track: str, clock: int) -> int:
        tid = self._track_ids.get(track)
        if tid is None:
            tid = len(self._tracks)
            if tid > 0xFFFF:
                raise RuntimeError("too many distinct trace tracks")
            self._tracks.append(track)
            self._track_ids[track] = tid
            self._track_clock[tid] = clock
        return tid

    def track_clock(self, track: str) -> int:
        """Clock domain a track was first recorded under."""
        return self._track_clock[self._track_ids[track]]

    # -- recording ---------------------------------------------------------

    def _record(
        self, kind: int, name: str, track: str, clock: int, t_us: int,
        dur_us: int, value: float,
    ) -> None:
        with self._lock:
            i = self.head % self.capacity
            self._kind[i] = kind
            self._name_id[i] = self._intern_name(name)
            self._track_id[i] = self._intern_track(track, clock)
            self._t_us[i] = t_us
            self._dur_us[i] = dur_us
            self._value[i] = value
            self.head += 1

    def span_at(
        self, name: str, t0_us: int, t1_us: int, *, track: str = "main",
        clock: int = WALL, value: float = 0.0,
    ) -> None:
        """Record a completed span [t0_us, t1_us] on ``track``."""
        self._record(SPAN, name, track, clock, int(t0_us),
                     max(0, int(t1_us) - int(t0_us)), value)

    def span(self, name: str, *, track: str = "main", value: float = 0.0) -> _Span:
        """Context manager: record a wall-clock span around the block."""
        return _Span(self, name, track, value)

    def instant(
        self, name: str, *, t_us: int | None = None, track: str = "main",
        clock: int = WALL, value: float = 0.0,
    ) -> None:
        """Record a point event."""
        if t_us is None:
            t_us = now_us()
        self._record(INSTANT, name, track, clock, int(t_us), 0, value)

    def counter(
        self, name: str, value: float, *, t_us: int | None = None,
        track: str = "counters", clock: int = WALL,
    ) -> None:
        """Record one sample of a numeric counter series."""
        if t_us is None:
            t_us = now_us()
        self._record(COUNTER, name, track, clock, int(t_us), 0, float(value))

    def device_span(
        self, name: str, t0_s: float, t1_s: float, *, track: str = "device",
        value: float = 0.0,
    ) -> None:
        """Record a span stamped in device seconds (stored as µs)."""
        self.span_at(name, round(t0_s * 1e6), round(t1_s * 1e6),
                     track=track, clock=DEVICE, value=value)

    def device_instant(
        self, name: str, t_s: float, *, track: str = "device", value: float = 0.0,
    ) -> None:
        self.instant(name, t_us=round(t_s * 1e6), track=track,
                     clock=DEVICE, value=value)

    def anchor(self, device_s: float, wall_us: int | None = None) -> None:
        """Record that device time ``device_s`` corresponds to ``wall_us``."""
        if wall_us is None:
            wall_us = now_us()
        with self._lock:
            self._anchors.append((int(wall_us), round(device_s * 1e6)))

    def anchor_once(self, device_s: float, wall_us: int | None = None) -> None:
        """Record an anchor only if none exists yet (hot-path friendly)."""
        if not self._anchors:
            self.anchor(device_s, wall_us)

    # -- reading -----------------------------------------------------------

    @property
    def dropped(self) -> int:
        """Events overwritten after the ring wrapped."""
        return max(0, self.head - self.capacity)

    def __len__(self) -> int:
        return min(self.head, self.capacity)

    @property
    def anchors(self) -> list[tuple[int, int]]:
        with self._lock:
            return list(self._anchors)

    def device_offset_us(self) -> int | None:
        """Wall µs minus device µs from the first anchor, or None."""
        with self._lock:
            if not self._anchors:
                return None
            wall, dev = self._anchors[0]
        return wall - dev

    def events(self) -> list[TraceEvent]:
        """Decode retained events, oldest first."""
        with self._lock:
            n = min(self.head, self.capacity)
            if n == 0:
                return []
            if self.head <= self.capacity:
                order = np.arange(n)
            else:
                start = self.head % self.capacity
                order = np.concatenate(
                    [np.arange(start, self.capacity), np.arange(start)]
                )
            kinds = self._kind[order].copy()
            name_ids = self._name_id[order].copy()
            track_ids = self._track_id[order].copy()
            t_us = self._t_us[order].copy()
            dur_us = self._dur_us[order].copy()
            values = self._value[order].copy()
            names = list(self._names)
            tracks = list(self._tracks)
            clocks = dict(self._track_clock)
        return [
            TraceEvent(
                kind=int(kinds[i]),
                name=names[name_ids[i]],
                track=tracks[track_ids[i]],
                clock=clocks[int(track_ids[i])],
                t_us=int(t_us[i]),
                dur_us=int(dur_us[i]),
                value=float(values[i]),
            )
            for i in range(n)
        ]

    def events_named(self, name: str) -> list[TraceEvent]:
        return [e for e in self.events() if e.name == name]

    def counter_total(self, name: str) -> float:
        """Sum of all retained samples of a counter series."""
        return float(sum(e.value for e in self.events()
                         if e.kind == COUNTER and e.name == name))


# -- module-global active recorder ----------------------------------------

_active: TraceRecorder | None = None


def install(rec: TraceRecorder | None = None) -> TraceRecorder:
    """Make ``rec`` (or a fresh recorder) the process-global recorder."""
    global _active
    if rec is None:
        rec = TraceRecorder()
    _active = rec
    return rec


def uninstall() -> TraceRecorder | None:
    """Remove and return the global recorder (tracing becomes a no-op)."""
    global _active
    rec, _active = _active, None
    return rec


def active() -> TraceRecorder | None:
    """The installed recorder, or None when tracing is disabled."""
    return _active


# -- program spans: one call, two sinks -------------------------------------


class _NoSpan:
    """What :func:`span` returns when neither the profiler nor a recorder
    can take the span."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **meta) -> None:
        return None


_NO_SPAN = _NoSpan()


def span(name: str, **meta):
    """Context manager: a program span named ``name`` with numeric ``meta``.

    The span goes to the JAX profiler (``jax.profiler.TraceAnnotation``,
    ``meta`` as its keywords) whenever JAX is loaded; with the profiler
    off that costs about a microsecond.  With a recorder installed it
    also lands in the ring, on the track named by ``name``'s prefix
    (``"sched:admit"`` -> ``"sched"``), with the first ``meta`` value as
    its ``value``.  The object bound by ``with`` takes more metadata for
    the profiler through ``set_metadata(**meta)``.  ``name`` is one of a
    fixed set (PERF.md, section 3): never put an index or an id in it.
    """
    jax = sys.modules.get("jax")
    me = jax.profiler.TraceAnnotation(name, **meta) if jax is not None else None
    rec = _active
    if rec is None:
        return _NO_SPAN if me is None else me
    return _Span(rec, name, name.partition(":")[0],
                 float(next(iter(meta.values()), 0.0)), me)
