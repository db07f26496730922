"""From the profiler's trace to numbers.

``record(logdir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
and keeps only what the reduction uses, as plain lists (the form the
committed test trace has): the harness's own host annotations (names
starting ``bench.``) and, per device, its op and module events.  All
times are nanoseconds on the profiler's clock.

The reduction then works inside the ``bench.window`` annotation: busy
time is the union of the device's op intervals, idle gaps are the rest,
each labelled by the innermost harness annotation that covers it, and
kernel or step time is the sum of the durations of the events whose name
holds a given pattern.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"


def record(logdir: str) -> Dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    host, devices = [], {}
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if is_dev and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                devices.setdefault(plane.name, {"ops": [], "modules": []})[
                    key].extend([e.name, e.start_ns, e.end_ns]
                                for e in line.events)
            elif not is_dev:
                host.extend([e.name, e.start_ns, e.end_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"host": host, "devices": devices}


def window(ev: Dict) -> Tuple[float, float]:
    w = [(s, e) for n, s, e in ev["host"] if n == WINDOW]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found {len(w)}")
    return w[0]


def _clip(iv: Sequence, w0: float, w1: float) -> List[Tuple[float, float]]:
    return [(max(s, w0), min(e, w1)) for _, s, e in iv if e > w0 and s < w1]


def union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ev: Dict) -> float:
    """Union of op intervals inside the window, averaged over devices."""
    w0, w1 = window(ev)
    per = [sum(e - s for s, e in union(_clip(d["ops"], w0, w1)))
           for d in ev["devices"].values()]
    return sum(per) / len(per) if per else 0.0


def op_name(name: str) -> str:
    """An op event's own name: the trace gives the HLO instruction's text
    (``%fused_paged_decode_grouped.3 = bf16[...] custom-call(...)``), whose
    operands name other ops; keep the name before `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")


def time_ns(ev: Dict, pattern: str, what: str = "ops") -> Tuple[float, int]:
    """(total duration, count) of the ``what`` events ("ops" or
    "modules") inside the window whose own name matches ``pattern`` (a
    regular expression), summed over devices."""
    w0, w1 = window(ev)
    rx = re.compile(pattern)
    tot, n = 0.0, 0
    for d in ev["devices"].values():
        for name, s, e in d[what]:
            if s >= w0 and e <= w1 and rx.search(op_name(name)):
                tot += e - s
                n += 1
    return tot, n


# ops that only contain others (a scanned layer stack is one ``while``)
CONTAINERS = {"while", "conditional", "call"}


def _label(name: str) -> str:
    return re.sub(r"\.\d+$", "", op_name(name))


def breakdown(ev: Dict, top: int = 10) -> Dict[str, List]:
    """The device ops that took most time (by name, numbered copies
    merged, containers left out) and the longest idle gaps, labelled by the innermost harness
    annotation that covers each gap's midpoint (seconds)."""
    w0, w1 = window(ev)
    ops: Dict[str, float] = {}
    gaps = []
    for d in ev["devices"].values():
        for name, s, e in d["ops"]:
            k = _label(name)
            if e > w0 and s < w1 and k not in CONTAINERS:
                ops[k] = ops.get(k, 0.0) + (min(e, w1) - max(s, w0))
        t = w0
        for s, e in union(_clip(d["ops"], w0, w1)) + [(w1, w1)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
    anns = [(n, s, e) for n, s, e in ev["host"] if n != WINDOW]
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        inner = [(a_e - a_s, n) for n, a_s, a_e in anns if a_s <= mid <= a_e]
        name = min(inner)[1] if inner else "bench.outside"
        labelled.append([name, (e - s) * 1e-9])
    return {"device_ops": [[k, v * 1e-9] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": labelled}
