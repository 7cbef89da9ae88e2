"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

``load`` reads the trace with ``jax.profiler.ProfileData`` and keeps
two things: the device operations (the ``XLA Ops`` line of every
``/device:`` plane) and the benchmark's own host spans (names starting
``bench.``, written with ``jax.profiler.TraceAnnotation``).  The rest
works on plain ``(name, start_ns, duration_ns)`` tuples:

- ``busy_ns``: the union of the intervals in which an operation ran,
  clipped to the window;
- ``reduce``: busy and idle seconds of the traced window, averaged over
  the devices, the operations that took most device time, and the idle
  gaps summed by the innermost host span that was open at each gap's
  midpoint (``"no span"`` where none was);
- ``per_span``: the device time of chosen operations inside each span
  of one name, an operation going to the span that holds its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


def start(trace_dir: str) -> None:
    """Start the profiler with its Python tracer off: the host spans
    the reduction reads are ``TraceAnnotation``s, and tracing every
    Python call would slow the served path's host work many times."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> dict:
    """-> {"devices": {plane: [(name, start_ns, dur_ns)]},
    "spans": [(name, start_ns, dur_ns)]}."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def merged(events, lo: float, hi: float) -> list:
    """Sorted disjoint ``[start, end)`` intervals covered by
    ``events``, clipped to ``[lo, hi)``."""
    out: list = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(events, lo, hi))


def gaps(events, lo: float, hi: float) -> list:
    """The idle ``(start, end)`` intervals of ``[lo, hi)``."""
    out, t = [], lo
    for a, b in merged(events, lo, hi):
        if a > t:
            out.append((t, a))
        t = b
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans, t: float) -> str:
    """Name of the shortest span open at ``t`` (``"no span"``).
    ``spans`` sorted by start; the benchmark's spans nest at most a
    few deep, so only the few latest-starting spans are looked at."""
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    best = None
    for s, end, name in spans[max(i - 7, 0):i + 1]:
        if s <= t < end and (best is None or end - s < best[1]):
            best = (name, end - s)
    return best[0] if best else "no span"


def short(name: str, width: int = 120) -> str:
    """An operation's HLO text without its layouts, cut to ``width``."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def window_of(spans, name: str = "bench.window") -> tuple:
    """``(start_ns, end_ns)`` of the one span called ``name``."""
    hits = [(s, s + d) for n, s, d in spans if n == name]
    if len(hits) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(hits)}")
    return hits[0]


def reduce(trace: dict, window: tuple, top: int = 10) -> dict:
    """Busy, idle and breakdown of ``window`` (ns) over every device
    plane of ``trace`` (as ``load`` returns it)."""
    lo, hi = window
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(ev, lo, hi) for ev in devs.values()]
    by_op: dict = {}
    idle: dict = {}
    spans = sorted((s, s + d, n) for n, s, d in trace["spans"]
                   if n != "bench.window")
    for ev in devs.values():
        for name, s, d in ev:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                by_op[name] = by_op.get(name, 0.0) + (b - a)
        for a, b in gaps(ev, lo, hi):
            who = innermost(spans, (a + b) / 2)
            idle[who] = idle.get(who, 0.0) + (b - a)
    n = len(devs)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / n * 1e-9

    def ranked(d):
        return [[short(k), v / n * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s else 0.0,
            "device_ops": ranked(by_op), "idle_gaps": ranked(idle)}


def per_span(trace: dict, span_name: str, keep) -> list:
    """For each span called ``span_name`` (in start order), the device
    nanoseconds of the operations ``keep(name)`` accepts whose midpoint
    falls inside it, summed over the devices."""
    spans = sorted((s, s + d) for n, s, d in trace["spans"]
                   if n == span_name)
    starts = [s for s, _ in spans]
    out = [0.0] * len(spans)
    for ev in trace["devices"].values():
        for name, s, d in ev:
            if not keep(name):
                continue
            mid = s + d / 2
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid < spans[i][1]:
                out[i] += d
    return out
