"""Set-up and the serving loop of one cell: the engine as users run it
(monolithic, paged, prefix cache on, synchronous), warmed on every shape
the cell's traffic can produce, then driven by the traffic against the
wall clock.

The loop is an open loop: each request is submitted once it is due,
whether or not earlier ones have finished, and its latency runs from its
due time.  After every ``tick`` the harness looks at each of its own
requests and stamps every new output token with the time the tick
returned, which is when a server loop could deliver it.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import traffic as TR
from bench.window import Record

clock = time.perf_counter


def make_engine(model, params, settings: Dict):
    from repro.serving import ServingEngine
    return ServingEngine(
        model, params, slots=settings["slots"], max_seq=settings["max_seq"],
        prefill_bucket=settings["prefill_bucket"], paged=True,
        page_size=settings["page"], num_blocks=settings["num_blocks"],
        prefix_cache=True, overlap=False)


def prefill_shapes(mix: Dict, settings: Dict) -> List[int]:
    """Every padded prefill length the mix can produce: with no shared
    prefixes a prefill runs over the whole prompt."""
    lo, hi = TR.prompt_range(mix)
    b, cap = settings["prefill_bucket"], settings["max_seq"] - 1
    return sorted({min(-(-n // b) * b, cap)
                   for n in range(lo, hi + 1, 1)})


def warm(eng, mix: Dict, settings: Dict, vocab: int, seed: int):
    """Run the engine once over each prefill shape the traffic can produce
    (and its decode step), then through a copy-on-write of a shared
    partial block, which a warm prefix can cause mid-window."""
    from repro.serving import Request
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 9])
    uid = -1
    for n in prefill_shapes(mix, settings):
        eng.submit(Request(uid, rng.integers(1, vocab, n, dtype=np.int32), 2))
        uid -= 1
    eng.run()
    page = settings["page"]
    a = Request(uid, rng.integers(1, vocab, page + page // 2,
                                  dtype=np.int32), page)
    eng.submit(a)
    eng.run()
    # a prompt that ends inside A's second block, which A's decode filled
    # and registered: it shares that block, and its first decode write
    # copies it
    b = Request(uid - 1, np.concatenate(
        [a.prompt, np.asarray(a.out_tokens[:page // 4], np.int32)]), 2)
    eng.submit(b)
    eng.run()
    return eng.stats()["cache"]["cow_copies"]


@dataclass
class TickLog:
    """Work of one tick, for the FLOP counts of the traced window."""
    decode_positions: List[int] = field(default_factory=list)
    prefills: List[tuple] = field(default_factory=list)   # (length, offset)


@dataclass
class Drive:
    records: List[Record]
    requests: list
    ticks: List[TickLog]
    w0: float
    w1: float
    submit_errors: int


def drive(eng, items: List[TR.Item], *, ramp_s: float, seconds: float,
          on_window: Callable[[], None] = lambda: None,
          annotate: bool = False) -> Drive:
    """Submit ``items`` as they fall due and tick the engine until the
    window, which starts ``ramp_s`` after the first item's due time,
    has run ``seconds``.  ``on_window`` runs when the window opens (it
    resets the engine's counters and may start the profiler)."""
    from repro.serving import Request
    if annotate:
        import jax
        ann = jax.profiler.TraceAnnotation
    else:
        def ann(_name):
            return contextlib.nullcontext()
    n = len(items)
    recs: List[Record] = []
    reqs = []
    live: List[int] = []            # indices of submitted, unfinished
    ticks: List[TickLog] = []
    errors = 0
    t0 = clock()
    w_open, w0, w1 = t0 + ramp_s, None, None
    i = 0
    win = None
    while True:
        now = clock()
        if w0 is None and now >= w_open:
            on_window()
            win = ann("bench.window")
            win.__enter__()
            w0 = clock()
            w1 = w0 + seconds
            n_pf = 0
            now = w0
        if w1 is not None and now >= w1:
            break
        if i < n and t0 + items[i].due <= now:
            with ann("bench.submit"):
                while i < n and t0 + items[i].due <= now:
                    it = items[i]
                    r = Request(i, it.prompt, it.max_new)
                    rec = Record(t0 + it.due, len(it.prompt), it.max_new)
                    try:
                        eng.submit(r)
                        rec.submitted = clock()
                        live.append(len(recs))
                    except ValueError:
                        errors += 1
                    recs.append(rec)
                    reqs.append(r)
                    i += 1
        if eng.queue or eng.active:
            with ann("bench.tick"):
                eng.tick()
            now = clock()
            log = TickLog() if w0 is not None else None
            if log is not None:
                pf = eng.prefill_token_counts[n_pf:]
                n_pf += len(pf)
                pf = list(pf)
            still = []
            for j in live:
                r, rec = reqs[j], recs[j]
                have, got = len(rec.stamps), len(r.out_tokens)
                if got > have:
                    rec.slot = r.slot       # set at admission
                    rec.stamps.extend([now] * (got - have))
                    if log is not None:
                        for k in range(have, got):
                            if k > 0:
                                log.decode_positions.append(
                                    rec.prompt_len + k - 1)
                            elif pf:
                                suffix = pf.pop(0)
                                log.prefills.append(
                                    (suffix, rec.prompt_len - suffix))
                if r.t_done:
                    rec.done = True
                else:
                    still.append(j)
            live = still
            if log is not None:
                ticks.append(log)
        else:
            nxt = t0 + items[i].due if i < n else float("inf")
            stop = w1 if w1 is not None else w_open
            with ann("bench.wait"):
                time.sleep(max(0.0, min(nxt, stop) - clock()))
    win.__exit__(None, None, None)
    return Drive(recs, reqs, ticks, w0, w1, errors)
