"""Shared by the engine-span readers: the engine's phase spans
(``ServingEngine.stats()["spans"]``: ``(name, t0_ns, t1_ns, tick, uid)``
on the host's ``perf_counter_ns`` clock) moved onto the profiler's
clock, and the device's idle time inside them.

The two clocks share no origin, so the spans are aligned through the
one thing both timelines hold: the harness's serving loop runs every
engine tick inside one ``bench.tick`` annotation, and both the span log
and the profile start with the window (``on_window`` resets the engine's
stats just before the profiler starts).  The k-th ``engine.tick`` span
pairs with the k-th ``bench.tick`` annotation; the offset is the median
of their start differences.  A pair agrees with it where its start or
its end differs by the offset to within ``MAX_RESIDUAL_NS``: a host
pause between the harness's annotation and the engine's clock read (a
garbage collection at the tick's first allocation) shifts one end only,
while a pairing off by a tick shifts both.  A program without the span
log, a log that dropped spans, tick counts that differ, or a pair that
does not agree give no alignment (None), and the readers report
nothing.
"""
from __future__ import annotations

import bisect
import statistics
from typing import List, Optional, Sequence, Tuple

from bench import trace as T

TICK = "engine.tick"
HARNESS_TICK = "bench.tick"
MAX_RESIDUAL_NS = 500_000

Interval = Tuple[float, float]


def aligned(run) -> Optional[List[tuple]]:
    """The window's spans with their times on the profiler's clock, or
    None where they cannot be placed there."""
    spans = run.stats.get("spans")
    if spans is None or run.stats.get("spans_dropped"):
        return None
    ticks = [s[1:3] for s in spans if s[0] == TICK]
    marks = sorted((s, e) for n, s, e in run.trace["host"]
                   if n == HARNESS_TICK)
    if not ticks or len(ticks) != len(marks):
        return None
    pairs = [(m0 - t0, m1 - t1) for (m0, m1), (t0, t1) in zip(marks, ticks)]
    off = statistics.median(d0 for d0, _ in pairs)
    if any(min(abs(d0 - off), abs(d1 - off)) > MAX_RESIDUAL_NS
           for d0, d1 in pairs):
        return None
    return [(n, t0 + off, t1 + off, tick, uid)
            for n, t0, t1, tick, uid in spans]


def idle(run) -> List[List[Interval]]:
    """Per device, the intervals of the window in which no op ran."""
    w0, w1 = T.window(run.trace)
    out = []
    for d in run.trace["devices"].values():
        gaps, t = [], w0
        for s, e in T.union(T._clip(d["ops"], w0, w1)) + [(w1, w1)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        out.append(gaps)
    return out


def _overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two sorted, disjoint lists."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_ms_per(run, inside: Sequence[str], per: str) -> Optional[float]:
    """Device idle time (ms, averaged over devices) that falls within the
    union of the spans named in ``inside``, over the number of ``per``
    spans that start in the window."""
    spans = aligned(run)
    if spans is None or not run.trace["devices"]:
        return None
    w0, w1 = T.window(run.trace)
    n = sum(1 for s in spans if s[0] == per and w0 <= s[1] <= w1)
    if not n:
        return None
    cover = T.union(T._clip([s[:3] for s in spans if s[0] in inside],
                            w0, w1))
    per_dev = [_overlap(gaps, cover) for gaps in idle(run)]
    return 1e-6 * sum(per_dev) / len(per_dev) / n



def idle_ms_tick_median(run, inside: Sequence[str]) -> Optional[float]:
    """Per engine tick that holds a span named in ``inside`` starting in
    the window, the device idle time (ms, averaged over devices) within
    the union of that tick's such spans; the median over those ticks."""
    spans = aligned(run)
    if spans is None or not run.trace["devices"]:
        return None
    w0, w1 = T.window(run.trace)
    by_tick = {}
    for s in spans:
        if s[0] in inside and s[3] is not None and w0 <= s[1] <= w1:
            by_tick.setdefault(s[3], []).append(s[:3])
    if not by_tick:
        return None
    gaps = idle(run)
    starts = [[g[0] for g in dev] for dev in gaps]
    per_tick = []
    for iv in by_tick.values():
        cover = T.union(T._clip(iv, w0, w1))
        tot = 0.0
        for dev, st in zip(gaps, starts):
            lo = max(0, bisect.bisect_right(st, cover[0][0]) - 1)
            hi = bisect.bisect_left(st, cover[-1][1])
            tot += _overlap(dev[lo:hi], cover)
        per_tick.append(tot / len(gaps))
    return 1e-6 * statistics.median(per_tick)
