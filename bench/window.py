"""Window arithmetic: the end-to-end numbers of one measured window from
the harness's own per-request records.

A record holds the request's due time, the time the harness submitted
it, the slot the engine admitted it to, and the time at which each
output token was seen (after the tick that produced it returned).  The
window is ``(w0, w1]``.

* Time to first token runs from the due time.  Every request due in the
  window counts; one with no token by ``w1`` counts with its wait so far,
  ``w1 - due``, so a stall cannot hide.
* A gap between tokens counts where its later token falls in the window.
* Output tokens are those seen in the window.
* A request was attempted where it was due in the window or the window
  served it a token.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Record:
    due: float                       # absolute host time it was due
    prompt_len: int
    max_new: int
    submitted: Optional[float] = None
    slot: int = -1                   # the engine slot that served it
    stamps: List[float] = field(default_factory=list)
    done: bool = False


def percentile(values: List[float], p: float) -> float:
    """Nearest-rank percentile (``p`` in (0, 100]) of ``values``."""
    v = sorted(values)
    return v[max(int(math.ceil(p / 100.0 * len(v))) - 1, 0)]


def ttfts(records: List[Record], w0: float, w1: float) -> List[float]:
    out = []
    for r in records:
        if w0 <= r.due < w1:
            first = r.stamps[0] if r.stamps and r.stamps[0] <= w1 else None
            out.append((first if first is not None else w1) - r.due)
    return out


def gaps(records: List[Record], w0: float, w1: float) -> List[float]:
    out = []
    for r in records:
        s = r.stamps
        out.extend(b - a for a, b in zip(s, s[1:]) if w0 < b <= w1)
    return out


def tokens_in(records: List[Record], w0: float, w1: float) -> int:
    return sum(1 for r in records for t in r.stamps if w0 < t <= w1)


def attempted(records: List[Record], w0: float, w1: float) -> int:
    """Requests due in the window, and those due earlier that it served a
    token (a backlog's requests are all due before the window)."""
    return sum(1 for r in records if w0 <= r.due < w1
               or any(w0 < t <= w1 for t in r.stamps))


def submit_lags(records: List[Record], w0: float, w1: float) -> List[float]:
    return [(r.submitted if r.submitted is not None and r.submitted <= w1
             else w1) - r.due for r in records if w0 <= r.due < w1]


def summary(records: List[Record], w0: float, w1: float) -> Dict[str, float]:
    """Every number the harness can take from a window (seconds unless
    the name says ms)."""
    t = ttfts(records, w0, w1)
    g = gaps(records, w0, w1)
    lag = submit_lags(records, w0, w1)
    out = {"due": len(t), "attempted": attempted(records, w0, w1),
           "gaps": len(g),
           "tokens": tokens_in(records, w0, w1), "window_s": w1 - w0}
    out["output_tok_s"] = out["tokens"] / (w1 - w0)
    if t:
        out["ttft_p50_s"] = percentile(t, 50)
        out["ttft_p90_s"] = percentile(t, 90)
        out["unanswered"] = sum(
            1 for r in records if w0 <= r.due < w1
            and not (r.stamps and r.stamps[0] <= w1))
    if g:
        out["itl_mean_ms"] = 1e3 * sum(g) / len(g)
        out["itl_p50_ms"] = 1e3 * percentile(g, 50)
        out["itl_p95_ms"] = 1e3 * percentile(g, 95)
        out["itl_p99_ms"] = 1e3 * percentile(g, 99)
    if lag:
        out["submit_lag_p99_ms"] = 1e3 * percentile(lag, 99)
    return out
