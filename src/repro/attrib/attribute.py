"""Marker-aligned energy attribution: watts back to named kernels.

Takes a decoded power trace (a `stream.FrameBlock` or raw arrays), a set
of **spans** — named time intervals for each kernel occurrence — and
produces an :class:`EnergyLedger`: per-kernel joules, average/peak watts,
total duration and occurrence count, aggregated across repeated steps.

Spans come from three sources:

* :func:`marker_spans` — consecutive occurrences of one marker char from
  ``PowerSensor.markers()`` (what `launch.serve` uses per request wave;
  occurrence-indexed, so the ledger never wraps an alphabet);
* :func:`timeline_spans` — a *declared* kernel timeline (e.g.
  ``power.tpu_model.phases_for_step``) laid out from per-step anchor
  markers, optionally stretched to the measured step length;
* `repro.attrib.segment` — marker-free changepoints, via
  :func:`spans_from_segments`.

:class:`StepAttributor` packages the train-loop integration: it plays the
modelled per-step phase trace through the full virtual-sensor chain,
brackets every step with a marker, and on ``finish()`` returns the ledger
measured *through the sensor* rather than assumed from the model.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Iterable, Sequence

import numpy as np

from repro.obs import trace as obs_trace
from repro.stream.aggregate import cumulative_energy
from repro.stream.ring import FrameBlock

from .segment import Segmentation


@dataclass(frozen=True)
class KernelSpan:
    """One occurrence of a named kernel in device time."""

    name: str
    t0_s: float
    t1_s: float

    @property
    def duration_s(self) -> float:
        return self.t1_s - self.t0_s


@dataclass
class LedgerEntry:
    """Aggregate of all attributed occurrences of one kernel."""

    name: str
    count: int = 0
    energy_j: float = 0.0
    duration_s: float = 0.0
    peak_w: float = 0.0
    #: span time actually backed by samples (gaps in the trace excluded);
    #: ``energy_j`` is extrapolated across gaps, and ``coverage_frac``
    #: is the explicit uncertainty of that extrapolation
    covered_s: float = 0.0

    @property
    def avg_w(self) -> float:
        return self.energy_j / self.duration_s if self.duration_s > 0 else 0.0

    @property
    def j_per_occurrence(self) -> float:
        return self.energy_j / self.count if self.count else 0.0

    @property
    def coverage_frac(self) -> float:
        """Fraction of the attributed time that samples actually covered."""
        return (
            min(self.covered_s / self.duration_s, 1.0)
            if self.duration_s > 0
            else 1.0
        )


@dataclass
class EnergyLedger:
    """Per-kernel energy accounting over one or more attributed windows."""

    entries: dict[str, LedgerEntry] = field(default_factory=dict)
    #: integral of the whole attributed trace window(s), attributed or not
    trace_energy_j: float = 0.0
    t0_s: float = 0.0
    t1_s: float = 0.0
    #: spans dropped because the ring no longer retained enough of them
    skipped_spans: int = 0

    @property
    def total_energy_j(self) -> float:
        return float(sum(e.energy_j for e in self.entries.values()))

    @property
    def attributed_fraction(self) -> float:
        return self.total_energy_j / self.trace_energy_j if self.trace_energy_j else 0.0

    def ranked(self) -> list[LedgerEntry]:
        """Entries sorted by energy, biggest consumer first."""
        return sorted(self.entries.values(), key=lambda e: -e.energy_j)

    @property
    def coverage_frac(self) -> float:
        """Sample coverage over all attributed time (1.0 = gap-free)."""
        dur = sum(e.duration_s for e in self.entries.values())
        cov = sum(e.covered_s for e in self.entries.values())
        return min(cov / dur, 1.0) if dur > 0 else 1.0

    def add_occurrence(
        self,
        name: str,
        energy_j: float,
        duration_s: float,
        peak_w: float,
        covered_s: float | None = None,
    ) -> None:
        e = self.entries.setdefault(name, LedgerEntry(name))
        e.count += 1
        e.energy_j += energy_j
        e.duration_s += duration_s
        e.peak_w = max(e.peak_w, peak_w)
        e.covered_s += duration_s if covered_s is None else covered_s

    def absorb(self, other: "EnergyLedger") -> "EnergyLedger":
        """Merge another ledger in place (multi-device / multi-window)."""
        was_empty = not self.entries and self.trace_energy_j == 0.0
        for name, e in other.entries.items():
            mine = self.entries.setdefault(name, LedgerEntry(name))
            mine.count += e.count
            mine.energy_j += e.energy_j
            mine.duration_s += e.duration_s
            mine.peak_w = max(mine.peak_w, e.peak_w)
            mine.covered_s += e.covered_s
        self.trace_energy_j += other.trace_energy_j
        self.skipped_spans += other.skipped_spans
        if other.entries or other.trace_energy_j:
            self.t0_s = other.t0_s if was_empty else min(self.t0_s, other.t0_s)
            self.t1_s = other.t1_s if was_empty else max(self.t1_s, other.t1_s)
        return self


# --------------------------------------------------------------------- spans
def interval_spans(
    markers: Iterable[tuple[str, float]],
    char: str,
    names: Sequence[str] | None = None,
    start: int = 0,
) -> list[KernelSpan]:
    """Spans for the step intervals bracketed by one marker char.

    The serving loop emits one occurrence of ``char`` per step interval (a
    batch of decode steps); interval ``k`` runs from occurrence ``k`` to
    occurrence ``k+1``.  Occurrence-indexed by construction, so repeated
    brackets (step intervals, request waves, tuning trials) never collide
    the way a wrapping marker alphabet does.  ``start`` skips already
    settled intervals while keeping *global* interval indices in the
    default names (``f"{char}{k}"``) — the index the scheduler settles by.
    """
    ts = [t for c, t in markers if c == char]
    spans = []
    for k in range(max(int(start), 0), len(ts) - 1):
        j = k - start
        name = names[j] if names is not None and j < len(names) else f"{char}{k}"
        spans.append(KernelSpan(name, ts[k], ts[k + 1]))
    return spans


def marker_spans(
    markers: Iterable[tuple[str, float]],
    char: str,
    names: Sequence[str] | None = None,
) -> list[KernelSpan]:
    """Spans between consecutive occurrences of one marker char.

    The degenerate one-interval-per-wave case of :func:`interval_spans`
    (``start=0``): span ``k`` runs from occurrence ``k`` to occurrence
    ``k+1`` of ``char``.  Default names are ``f"{char}{k}"``.  Kept as the
    wave-era entry point; existing goldens replay bit-identically through
    either.
    """
    return interval_spans(markers, char, names=names, start=0)


def attribute_intervals(
    block: FrameBlock,
    markers: Iterable[tuple[str, float]],
    char: str,
    start: int = 0,
    pair: int | None = None,
    min_coverage: float = 0.0,
    gap_factor: float = 3.0,
) -> dict[int, LedgerEntry]:
    """Attribute every retained step interval at once: {interval: entry}.

    One `attribute` pass over all intervals of ``char`` from occurrence
    ``start`` on, keyed by *global* interval index — what the continuous
    batch settles `settle_interval(k, entry.energy_j)` against.  Intervals
    the ring evicted or the gap logic rejects are simply absent (the
    caller releases those at prediction); present entries carry the same
    gap-aware energy/coverage semantics as any other attribution.
    """
    spans = interval_spans(markers, char, start=start)
    ledger = attribute_block(
        block, spans, pair=pair, min_coverage=min_coverage, gap_factor=gap_factor
    )
    out: dict[int, LedgerEntry] = {}
    for name, entry in ledger.entries.items():
        out[int(name[len(char):])] = entry
    return out


def timeline_spans(
    phases: Sequence,
    anchors: Sequence[float],
    stretch: bool = True,
    t_end: float | None = None,
) -> list[KernelSpan]:
    """Lay a declared kernel timeline out from per-step anchor markers.

    ``phases`` is anything with ``.name`` / ``.duration_s`` (e.g.
    `power.tpu_model.Phase`) or ``(name, duration_s)`` tuples; one copy of
    the timeline is placed at every anchor.  With ``stretch=True`` the
    declared durations are rescaled so each step exactly fills the gap to
    the next anchor (or to ``t_end`` for the last one) — aligning the
    modelled timeline to the *measured* step length.
    """
    items = [
        (p.name, p.duration_s) if hasattr(p, "duration_s") else (p[0], float(p[1]))
        for p in phases
    ]
    total = sum(d for _, d in items)
    anchors = sorted(float(a) for a in anchors)
    spans: list[KernelSpan] = []
    for k, a in enumerate(anchors):
        if k + 1 < len(anchors):
            budget = anchors[k + 1] - a
        elif t_end is not None:
            budget = t_end - a
        else:
            budget = total
        scale = budget / total if stretch and total > 0 and budget > 0 else 1.0
        t = a
        for name, dur in items:
            spans.append(KernelSpan(name, t, t + dur * scale))
            t += dur * scale
    return spans


def spans_from_segments(
    seg: Segmentation, names: Sequence[str] | None = None
) -> list[KernelSpan]:
    """Wrap detected segments as spans (names default ``seg0..segN-1``)."""
    return [
        KernelSpan(
            names[i] if names is not None and i < len(names) else f"seg{i}",
            s.t0_s,
            s.t1_s,
        )
        for i, s in enumerate(seg.segments)
    ]


# ----------------------------------------------------------------- attribute
def attribute(
    times_s: np.ndarray,
    watts: np.ndarray,
    spans: Sequence[KernelSpan],
    min_coverage: float = 0.0,
    gap_factor: float = 3.0,
) -> EnergyLedger:
    """Integrate a 1-D power series over each span; aggregate by name.

    Span energies come from one cumulative trapezoid prefix plus two
    binary searches per span — O(n + m log n) for n samples, m spans.
    Span edges are quantised to sample boundaries (≤ one 50 µs frame of
    slack at 20 kHz).

    Gap-aware: inter-sample steps longer than ``gap_factor`` × the median
    frame interval are *delivery gaps* (dropouts, disconnects), not data.
    Energy is integrated over the covered segments only and extrapolated
    across the gaps by ``1 / coverage_frac``, with the coverage recorded
    per entry — a gap is surfaced as uncertainty, never silently
    under-counted as zero watts nor bridged as fake samples.

    ``min_coverage`` guards against spans too hollow to extrapolate
    (ring evicted the head, the gap swallowed the whole span): those are
    dropped and tallied in ``ledger.skipped_spans``.
    """
    t = np.asarray(times_s, dtype=np.float64)
    w = np.asarray(watts, dtype=np.float64)
    ledger = EnergyLedger()
    if t.size < 2 or not spans:
        ledger.skipped_spans = len(spans)
        return ledger
    cumE = cumulative_energy(t, w)
    dts = np.diff(t)
    dt_est = float(np.median(dts))
    gap_thresh = gap_factor * dt_est
    bad = dts > gap_thresh
    # segment-level prefixes: energy and gap time over covered steps only
    seg_e = 0.5 * (w[1:] + w[:-1]) * dts
    cum_e_cov = np.concatenate([[0.0], np.cumsum(np.where(bad, 0.0, seg_e))])
    cum_gap = np.concatenate([[0.0], np.cumsum(np.where(bad, dts, 0.0))])
    lo = np.searchsorted(t, [s.t0_s for s in spans], side="left")
    hi = np.searchsorted(t, [s.t1_s for s in spans], side="left")
    ledger.trace_energy_j = float(cumE[-1])
    ledger.t0_s, ledger.t1_s = float(t[0]), float(t[-1])
    for span, a, b in zip(spans, lo, hi):
        n = int(b - a)
        dur = span.duration_s
        if n < 2 or dur <= 0:
            ledger.skipped_spans += 1
            continue
        # uncovered time: interior gaps plus edge gaps beyond one frame
        # (edge slack of ≤ dt_est is quantisation, not a gap)
        gap_s = float(cum_gap[b - 1] - cum_gap[a])
        gap_s += max(float(t[a]) - span.t0_s - dt_est, 0.0)
        gap_s += max(span.t1_s - float(t[b - 1]) - dt_est, 0.0)
        coverage = min(max(1.0 - gap_s / dur, 0.0), 1.0)
        if coverage <= 0.0 or coverage < min_coverage:
            ledger.skipped_spans += 1
            continue
        e_cov = float(cum_e_cov[b - 1] - cum_e_cov[a])
        ledger.add_occurrence(
            span.name,
            energy_j=e_cov / coverage,
            duration_s=dur,
            peak_w=float(w[a:b].max()),
            covered_s=coverage * dur,
        )
    return ledger


def attribute_block(
    block: FrameBlock,
    spans: Sequence[KernelSpan],
    pair: int | None = None,
    min_coverage: float = 0.0,
    gap_factor: float = 3.0,
) -> EnergyLedger:
    """`attribute` over a `FrameRing` view (pair=None sums across pairs)."""
    with obs_trace.span("attrib:block", spans=len(spans)):
        w = block.total_watts if pair is None else block.watts[:, pair]
        return attribute(
            block.times_s, w, spans, min_coverage=min_coverage, gap_factor=gap_factor
        )


def refine_spans(
    spans: Sequence[KernelSpan], seg: Segmentation, tol_s: float = 2e-3
) -> list[KernelSpan]:
    """Snap span edges to the nearest *detected* changepoint within tol_s.

    Declared timelines carry model error; measured changepoints don't.
    Edges with no changepoint nearby are left where the timeline put them.
    """
    if seg.boundaries_s.size == 0:
        return list(spans)
    b = seg.boundaries_s

    def snap(x: float) -> float:
        j = int(np.argmin(np.abs(b - x)))
        return float(b[j]) if abs(b[j] - x) <= tol_s else x

    out = []
    for s in spans:
        t0, t1 = snap(s.t0_s), snap(s.t1_s)
        out.append(replace(s, t0_s=t0, t1_s=t1) if t1 > t0 else s)
    return out


# ------------------------------------------------------------- train bridge
class StepAttributor:
    """Bracket every training/serving step with markers on a virtual
    sensor playing the modelled phase trace; ``finish()`` → energy ledger.

    The declared timeline is ``telemetry.phases`` (from
    ``power.tpu_model.phases_for_step``); each ``on_step()`` marks the
    step start and advances the device by one modelled step, so the
    marker stream and the 20 kHz frame stream stay time-synced exactly as
    the paper's ``psrun -m`` does.
    """

    def __init__(
        self,
        telemetry,
        seed: int = 0,
        volts: float = 12.0,
        module: str = "pcie8pin-20a",
        ring_capacity: int | None = None,
        marker: str = "S",
    ):
        from repro.core import PowerSensor, TraceLoad, make_device
        from repro.core.host import DEFAULT_RING_CAPACITY
        from repro.power.trace import render_phases

        self.telemetry = telemetry
        self.marker = marker
        self._phases = list(telemetry.phases)
        trace = render_phases(self._phases, telemetry.chip, telemetry.dvfs)
        self._step_s = float(trace.times_s[-1])
        dev = make_device([module], TraceLoad(
            times_s=trace.times_s,
            watts=trace.watts,
            volts=volts,
            repeat=True,
        ), seed=seed)
        self._ps = PowerSensor(
            dev, ring_capacity=ring_capacity or DEFAULT_RING_CAPACITY
        )
        self._steps = 0
        self._closed = False

    @property
    def sensor(self):
        return self._ps

    def on_step(self) -> None:
        """Mark the step start and play one modelled step through the chain."""
        self._ps.mark(self.marker)
        self._ps.run_for(self._step_s)
        self._steps += 1

    def finish(self, min_coverage: float = 0.5) -> EnergyLedger:
        """Flush, attribute every retained step, and release the sensor."""
        self._ps.poll()
        anchors = [t for c, t in self._ps.markers if c == self.marker]
        block = self._ps.ring.latest()
        ledger = EnergyLedger()
        if anchors:
            spans = timeline_spans(
                self._phases, anchors, stretch=True, t_end=anchors[-1] + self._step_s
            )
            ledger = attribute_block(block, spans, min_coverage=min_coverage)
        if not self._closed:
            self._ps.close()
            self._closed = True
        return ledger
