"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

A traced run records one ``jax.profiler`` trace around a stretch of its
window.  The harness brackets each host step of that stretch in a
``jax.profiler.TraceAnnotation`` span (``bench.data``, ``bench.step``,
``bench.read``, ``bench.sweep``); this module reads the trace back with
``jax.profiler.ProfileData`` and reduces it:

* the traced window runs from the first harness span's start to the last
  one's end, on the host's clock;
* a device's busy time is the union of the intervals of the events on its
  ``XLA Ops`` line, clipped to the window, averaged over the devices used;
* events are summed by name, over the events that contain no other event
  (a ``while`` loop's event spans its body's, which are listed on the
  same line), so each op's total is its own time; the top ones become
  ``device_ops``;
* each stretch of the window in which no device op runs is an idle gap,
  charged to the harness span that overlaps it most (``other`` where none
  does); idle time is summed by that span name (``idle_gaps``).
"""
from __future__ import annotations

import glob
import os
from typing import Iterable

#: the device line whose events are the operations that ran on the chip
OPS_LINE = "XLA Ops"
#: prefix of the harness's own host spans
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` written under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint union of half-open ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals cut to ``[lo, hi]``, empty ones dropped."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that the disjoint ``busy`` leaves out."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a: tuple[float, float], b: tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def charge(gap_list, spans: list[tuple[str, float, float]]) -> dict[str, float]:
    """Idle time summed by the name of the span that overlaps each gap most."""
    out: dict[str, float] = {}
    spans = sorted(spans, key=lambda x: x[1])
    j = 0
    for g in gap_list:
        while j < len(spans) and spans[j][2] <= g[0]:
            j += 1
        best, name = 0.0, "other"
        k = j
        while k < len(spans) and spans[k][1] < g[1]:
            ov = _overlap(g, (spans[k][1], spans[k][2]))
            if ov > best:
                best, name = ov, spans[k][0]
            k += 1
        out[name] = out.get(name, 0.0) + (g[1] - g[0])
    return out


def leaves(evs: list[tuple[str, float, float]]) -> list[tuple[str, float, float]]:
    """The events of one line that contain no other event."""
    evs = sorted(evs, key=lambda x: (x[1], -x[2]))
    return [ev for ev, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= ev[2]]


def planes(path: str):
    """(host spans, {device plane: [(name, start, end)]}) in nanoseconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for pl in pd.planes:
        if pl.name.startswith("/device:"):
            evs = []
            for line in pl.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            if evs:
                devices[pl.name] = evs
        elif pl.name.startswith("/host:"):
            for line in pl.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return spans, devices


def reduce(path: str, n_devices: int, top: int = 10) -> dict:
    """Busy and window seconds, op totals and idle gaps of one trace.

    ``n_devices`` device planes (the cell's chips, in plane order) are
    averaged.  Returns ``busy_s``, ``window_s``, ``ops`` (seconds by event
    name, summed over those devices and divided by their number),
    ``idle`` (seconds by host span, likewise) and ``breakdown`` (the top
    ``top`` of each, largest first).
    """
    spans, devices = planes(path)
    if not spans:
        raise ValueError(f"{path}: no harness spans ({SPAN_PREFIX}*)")
    names = sorted(devices)[:n_devices]
    if len(names) < n_devices:
        raise ValueError(f"{path}: {len(names)} device planes with ops, "
                         f"need {n_devices}")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy_ns, ops, idle = 0.0, {}, {}
    for dev in names:
        evs = devices[dev]
        busy = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e in leaves(evs):
            d = _overlap((s, e), (lo, hi))
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d
        for name, d in charge(gaps(busy, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + d
    n = float(len(names))
    ops = {k: v / n / 1e9 for k, v in ops.items()}
    idle = {k: v / n / 1e9 for k, v in idle.items()}
    first = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_ns / n / 1e9, "window_s": (hi - lo) / 1e9,
            "ops": ops, "idle": idle,
            "breakdown": {"device_ops": [list(x) for x in first(ops)],
                          "idle_gaps": [list(x) for x in first(idle)]}}


def op_seconds(ops: dict[str, float], name: str) -> float:
    """Device seconds of the ops whose HLO instruction is ``name`` or
    ``name.<n>`` (an event is named by its instruction's text,
    ``%name.3 = ...``)."""
    def inst(k):
        return k.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]
    return sum(v for k, v in ops.items() if inst(k) == name)
