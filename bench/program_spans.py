"""The program's own spans in a profiler trace, for the per-layer readers.

The scheduler, KV pool, sensor fleet and attribution open spans through
`repro.obs.trace.span`, which lands them on the profiler trace's host
plane (``/host:CPU``) with their metadata as the event's stats, on the
same clock as the device planes. This module opens the run's
``.xplane.pb``, keeps the spans named ``sched:*``, ``pool:*``,
``fleet:*`` and ``attrib:*`` that start inside the ``trace:window``
span, and, for `fleet_idle_s`, the device's busy intervals as
`bench.trace_reduce` takes them. A program without such spans, or a trace
without the window span, gives none, and the readers then return None.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache

import numpy as np

from bench.trace_reduce import OPS_LINE, WINDOW_SPAN, _union, clip, find_xplane

PREFIXES = ("sched:", "pool:", "fleet:", "attrib:")
#: spans whose time is the fleet's settlement on the host
FLEET = ("fleet:", "attrib:", "sched:settle")


@dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns, the trace's clock
    end: float
    stats: dict
    line: int  # which host line (thread) it ran on


@dataclass(frozen=True)
class Trace:
    window: tuple | None  # (start, end) of the ``trace:window`` span, ns
    spans: list = field(default_factory=list)  # program spans starting in the window
    busy: list = field(default_factory=list)  # per device: merged (start, end) op intervals, ns


@lru_cache(maxsize=1)
def _load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window, spans, busy = None, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    busy.append(_union(np.asarray(
                        [(e.start_ns, e.start_ns + e.duration_ns) for e in ln.events],
                        dtype=float).reshape(-1, 2)))
        elif plane.name.startswith("/host:CPU"):
            for i, ln in enumerate(plane.lines):
                for e in ln.events:
                    if e.name == WINDOW_SPAN and window is None:
                        window = (float(e.start_ns), float(e.start_ns + e.duration_ns))
                    elif e.name.startswith(PREFIXES):
                        spans.append(Span(e.name, float(e.start_ns),
                                          float(e.start_ns + e.duration_ns),
                                          {k: v for k, v in e.stats}, i))
    if window is None:
        return Trace(None)
    lo, hi = window
    return Trace(window, [s for s in spans if lo <= s.start <= hi], busy)


def load(log_dir) -> Trace:
    """The program spans of the run's trace under ``log_dir`` (the harness's
    ``TraceWindow.log_dir``)."""
    return _load(str(find_xplane(log_dir)))


def stats(log_dir, name: str) -> list[dict]:
    """The metadata of every span called ``name`` that starts in the window."""
    return [s.stats for s in load(log_dir).spans if s.name == name]


def innermost(spans: list) -> list:
    """``(name, a, b)``: the pieces of time in which ``name`` is the innermost
    open span, for spans of one thread (which nest)."""
    out, stack, t = [], [], -np.inf
    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1][1] <= s.start:
            name, end = stack.pop()
            out.append((name, t, max(t, end)))
            t = max(t, end)
        if stack:
            out.append((stack[-1][0], t, max(t, s.start)))
        t = max(t, s.start)
        stack.append((s.name, s.end))
    while stack:
        name, end = stack.pop()
        out.append((name, t, max(t, end)))
        t = max(t, end)
    return [p for p in out if p[2] > p[1]]


def _covered_before(iv: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of the sorted disjoint intervals ``iv`` that lies before each ``t``."""
    if len(iv) == 0:
        return np.zeros(len(t))
    cum = np.concatenate([[0.0], np.cumsum(iv[:, 1] - iv[:, 0])])
    i = np.searchsorted(iv[:, 0], t, side="right") - 1  # the last interval begun by t
    j = np.maximum(i, 0)
    part = np.clip(np.minimum(t, iv[j, 1]) - iv[j, 0], 0.0, None)
    return np.where(i >= 0, cum[j] + part, 0.0)


def idle_inside(pieces: np.ndarray, busy: np.ndarray) -> float:
    """Length of the sorted disjoint intervals ``pieces`` in which ``busy``
    (sorted, disjoint) has no interval."""
    if len(pieces) == 0:
        return 0.0
    covered = _covered_before(busy, pieces[:, 1]) - _covered_before(busy, pieces[:, 0])
    return float((pieces[:, 1] - pieces[:, 0]).sum() - covered.sum())


def fleet_idle_s(tr: Trace) -> float:
    """Device-idle seconds in the window whose innermost open program span is
    the fleet's (``fleet:*``, ``attrib:*``, ``sched:settle``), averaged over
    the devices; 0 where the trace holds no device.

    Busy is the union of the device's op intervals clipped to the window,
    as `bench.trace_reduce` takes it for ``device_idle_share``, so the
    result never exceeds that share's idle time.
    """
    if tr.window is None or not tr.busy:
        return 0.0
    pieces = []
    for line in {s.line for s in tr.spans}:
        pieces += [(a, b) for name, a, b in innermost([s for s in tr.spans if s.line == line])
                   if name.startswith(FLEET)]
    lo, hi = tr.window
    fleet = clip(_union(np.asarray(pieces, dtype=float).reshape(-1, 2)), lo, hi)
    return sum(idle_inside(fleet, clip(b, lo, hi)) for b in tr.busy) / len(tr.busy) * 1e-9
