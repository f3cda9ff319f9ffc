"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

    python bench/trace_reduce.py <trace dir or .xplane.pb> [--dump]

From the device planes (``/device:TPU:n``): the union of the intervals
in which an XLA op ran (busy time); the device time of each XLA program
(``XLA Modules`` line, by module name with its ``(id)`` suffix dropped)
and how often it ran; the self time of each op (``XLA Ops`` line, where a
``while`` op encloses the ops of its body: an op's self time is its
duration less that of the ops it encloses), keyed by
``<program>/<op> <result type> <opcode>``; and the idle gaps between busy intervals. From the host plane: the
harness's ``host:*`` spans (`jax.profiler.TraceAnnotation`), which name
each gap by the innermost span open over its middle. Where the host plane
holds a ``trace:window`` span (the harness opens one over the traced
window), busy time is clipped to it and ``window_s`` is its length, both
in the trace's clock: an op that began before the window or ended after
it counts only its part inside. Times from several devices are averaged
over them. ``--dump`` prints the planes, lines and
the most frequent event names, to look at a trace by hand.
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import Counter, defaultdict
from pathlib import Path

import numpy as np

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_SPAN = "host:"
WINDOW_SPAN = "trace:window"
_LAYOUT = re.compile(r"\{[^{}]*\}")


def find_xplane(path) -> Path:
    p = Path(path)
    if p.is_file():
        return p
    found = sorted(p.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {p}")
    return found[-1]


def module_name(name: str) -> str:
    return name.split("(")[0].strip()


def short_op(name: str) -> str:
    """``%name = type{layout} opcode(operands), attrs`` -> ``%name type opcode``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:100]
    if rhs.startswith("("):  # a tuple result: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        rtype, rest = "(tuple)", rhs[i + 1:]
    else:
        rtype, _, rest = rhs.partition(" ")
    opcode = rest.strip().split("(")[0].split(" ")[0]
    return f"{lhs} {_LAYOUT.sub('', rtype)} {opcode}".strip()


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merged (start, end) intervals, sorted."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [iv[0].copy()]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append(np.array([s, e]))
    return np.asarray(out)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Sorted disjoint (start, end) intervals cut to [lo, hi]; empty ones dropped."""
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def self_times(events: list) -> list:
    """(name, start, self duration) of nested (name, start, duration) events:
    each event's duration less the durations of the events directly inside it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack: list[int] = []
    for i in order:
        _, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return [(e[0], e[1], max(o, 0.0)) for e, o in zip(events, own)]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]


def reduce_trace(path, n_gaps: int = 10) -> dict:
    """Busy/idle, per-program and per-op device time, named gaps."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(find_xplane(path)))
    devices, spans, window = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            lines = {ln.name: _events(ln) for ln in plane.lines}
            ops = lines.get(OPS_LINE, [])
            if ops:
                devices.append((ops, lines.get(MODULES_LINE, [])))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                evs = _events(ln)
                spans += [ev for ev in evs if ev[0].startswith(HOST_SPAN)]
                window += [ev for ev in evs if ev[0] == WINDOW_SPAN]
    if not devices:
        return {"devices": 0}
    busy, op_s, mod_s, mod_n = [], Counter(), Counter(), Counter()
    gaps = []
    for ops, mods in devices:
        u = _union(np.asarray([(s, s + d) for _, s, d in ops]))
        inside = clip(u, window[0][1], window[0][1] + window[0][2]) if window else u
        busy.append(float((inside[:, 1] - inside[:, 0]).sum()) * 1e-9)
        mods = sorted(mods, key=lambda m: m[1])
        m_start = [m[1] for m in mods]
        for name, s, own in self_times(ops):
            j = bisect.bisect_right(m_start, s) - 1
            prog = module_name(mods[j][0]) if j >= 0 and s < mods[j][1] + mods[j][2] else "?"
            op_s[f"{prog}/{short_op(name)}"] += own * 1e-9
        for name, _, d in mods:
            mod_s[module_name(name)] += d * 1e-9
            mod_n[module_name(name)] += 1
        gaps += [(float(u[i, 1]), float(u[i + 1, 0])) for i in range(len(u) - 1)]
    n_dev = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    span_iv = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in span_iv]

    def name_at(t: float) -> str:
        """The innermost host span open at time ``t``."""
        i = bisect.bisect_right(starts, t)
        open_ = [s for s in span_iv[max(0, i - 64):i] if t <= s[1] + s[2]]
        return min(open_, key=lambda s: s[2])[0] if open_ else "none"

    gap_by_span = defaultdict(float)
    for a, b in gaps:
        gap_by_span[name_at((a + b) / 2)] += (b - a) * 1e-9
    named = [[name_at((a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:n_gaps]]
    return {
        "devices": n_dev,
        "busy_s": sum(busy) / n_dev,
        "window_s": window[0][2] * 1e-9 if window else None,
        "op_s": {k: v / n_dev for k, v in op_s.items()},
        "module_s": {k: v / n_dev for k, v in mod_s.items()},
        "module_n": {k: v // n_dev for k, v in mod_n.items()},
        "idle_gaps": named,
        "idle_by_span": {k: v / n_dev for k, v in gap_by_span.items()},
    }


def dump(path) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(find_xplane(path)))
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for ln in plane.lines:
            evs = list(ln.events)
            names = Counter(e.name for e in evs)
            t0 = min((e.start_ns for e in evs), default=0)
            t1 = max((e.end_ns for e in evs), default=0)
            print(f"  LINE {ln.name!r}: {len(evs)} events, {t0:.0f}..{t1:.0f} ns")
            for name, n in names.most_common(12):
                ev = next(e for e in evs if e.name == name)
                stats = {k: v for k, v in list(ev.stats)[:6]} if ev.stats else {}
                print(f"    {n:6d} x {name[:110]!r} {stats}")


if __name__ == "__main__":
    if "--dump" in sys.argv:
        dump(sys.argv[1])
    else:
        import json

        print(json.dumps(reduce_trace(sys.argv[1]), indent=1)[:20000])
