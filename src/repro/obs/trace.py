"""Engine observability on the host: the always-on phase recorder and
the opt-in, ring-buffered tracer.

:class:`PhaseRecorder` is the serving engine's phase clock, always on
and bounded.  Each host phase of a tick is entered once through
:meth:`PhaseRecorder.span`, which takes one ``perf_counter_ns`` pair,
adds the phase's own time (its length less that of the bucketed phases
nested in it) to its ``phase_time_s`` bucket (``PHASE_BUCKETS``), appends ``(name, t0_ns, t1_ns, tick, uid)`` to a
preallocated window log, and opens a ``jax.profiler.TraceAnnotation`` of
the same name, so a profile shows every engine phase beside the device
ops.  When a :class:`Tracer` is attached, the recorder hands each span on
to it (under ``TRACER_NAMES``, with the args the engine gives).  Each
request's wait from ``submit()`` to its admission enters the log as
``engine.queue``.

:class:`Tracer` stores *complete* records (spans carry both endpoints)
in a fixed-size ring, so exporting can never produce a ``B`` without its
matching ``E`` even after the ring wraps.  All timestamps come from
``time.perf_counter()`` — the clock of ``perf_counter_ns``, which the
phase recorder and the engine's request latencies use, so trace spans
line up with engine stats by construction.

Record shapes (plain tuples, newest-kept ring):

- ``("X", track, name, t0, t1, args, flow_out, flow_in)`` — a span.
- ``("I", track, name, t, args)`` — an instant event.
- ``("C", track, name, t, values)`` — a counter sample (dict of series).
- ``("F", track, phase, fid, t)`` — a bare flow endpoint (``"s"``/``"f"``).

``track`` is either a string (``"tick"``, ``"requests"``) or a tuple
(``("stage", j)``, ``("replica", r)``); the Perfetto exporter maps each
track to its own named thread so stages and replicas render as parallel
timelines.

The hot path contract: when tracing is disabled the engine holds
``self._tr is None``, the phase recorder holds no tracer, and every
other emission site is guarded, so *zero* tracer records are created.
``RECORDS_TOTAL`` below counts every record ever pushed by any tracer in
the process — tests use it as an allocation probe to pin the no-op fast
path.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple, Union

from jax.profiler import TraceAnnotation

Track = Union[str, Tuple[str, int]]

# Process-wide record counter.  Incremented on every record pushed into
# any Tracer; an engine running with trace=None must leave it untouched
# (asserted by tests/test_obs.py).
RECORDS_TOTAL = 0


@dataclass(frozen=True)
class TraceConfig:
    """Configuration for engine tracing.

    capacity: ring size in records; oldest records are dropped once the
        ring wraps (``Tracer.dropped`` counts them).
    path: optional output path — callers (CLI, benchmarks) write the
        Perfetto JSON here when the run finishes.
    """

    capacity: int = 1 << 16
    path: Optional[str] = None


class Tracer:
    """Ring-buffered trace-event recorder (monotonic clock)."""

    __slots__ = ("capacity", "_buf", "_idx", "t0")

    def __init__(self, capacity: int = 1 << 16):
        if capacity <= 0:
            raise ValueError("tracer capacity must be positive")
        self.capacity = int(capacity)
        self._buf: List[Optional[tuple]] = [None] * self.capacity
        self._idx = 0
        self.t0 = time.perf_counter()

    # -- clock ---------------------------------------------------------
    @staticmethod
    def now() -> float:
        return time.perf_counter()

    # -- recording -----------------------------------------------------
    def _push(self, rec: tuple) -> None:
        global RECORDS_TOTAL
        RECORDS_TOTAL += 1
        self._buf[self._idx % self.capacity] = rec
        self._idx += 1

    def span(
        self,
        track: Track,
        name: str,
        t0: float,
        t1: Optional[float] = None,
        args: Optional[Dict[str, Any]] = None,
        flow_out: Optional[int] = None,
        flow_in: Optional[int] = None,
    ) -> None:
        """Record a complete span [t0, t1] (t1 defaults to now)."""
        if t1 is None:
            t1 = time.perf_counter()
        self._push(("X", track, name, t0, t1, args, flow_out, flow_in))

    def instant(
        self,
        track: Track,
        name: str,
        t: Optional[float] = None,
        args: Optional[Dict[str, Any]] = None,
    ) -> None:
        if t is None:
            t = time.perf_counter()
        self._push(("I", track, name, t, args))

    def counter(
        self,
        track: Track,
        name: str,
        values: Dict[str, float],
        t: Optional[float] = None,
    ) -> None:
        if t is None:
            t = time.perf_counter()
        self._push(("C", track, name, t, values))

    def flow(self, track: Track, phase: str, fid: int, t: Optional[float] = None) -> None:
        """Record a bare flow endpoint (phase 's' start / 'f' finish)."""
        if phase not in ("s", "f"):
            raise ValueError(f"flow phase must be 's' or 'f', got {phase!r}")
        if t is None:
            t = time.perf_counter()
        self._push(("F", track, phase, fid, t))

    # -- inspection ----------------------------------------------------
    @property
    def events(self) -> int:
        """Total records ever pushed (including dropped ones)."""
        return self._idx

    @property
    def dropped(self) -> int:
        return max(0, self._idx - self.capacity)

    def records(self) -> List[tuple]:
        """Retained records, oldest first."""
        if self._idx <= self.capacity:
            return [r for r in self._buf[: self._idx]]
        head = self._idx % self.capacity
        return [r for r in self._buf[head:] + self._buf[:head]]

    def clear(self) -> None:
        self._buf = [None] * self.capacity
        self._idx = 0


# ---------------------------------------------------------------------------
# the engine's phase clock

# phase -> the ``phase_time_s`` bucket its time accrues to.  A bucketed
# span accrues its own time: its length less that of the bucketed spans
# nested in it (an admission loop less the prefills it dispatched).  The
# bucketed spans that nest in none follow one another through the tick:
# the controller, a re-plan, the admission loop, the prefill stage-step
# and the decode phase.
# None marks a span that only names a part of a bucketed one, wraps the
# tick (``engine.tick``) or spans ticks (``engine.queue``).
PHASE_BUCKETS: Dict[str, Optional[str]] = {
    "engine.tick": None,
    "engine.control": "replan",        # the adaptive controller's verdict
    "engine.replan": "replan",         # a live plan swap
    "engine.rebalance": "replan",      # same-plan slot rebalancing
    "engine.replan.drain": None,       # in-flight steps landing in a swap
    "engine.admission": "admission",   # the tick's admission loop
    "engine.admit": None,              # one request: pager admit, padding
    "engine.admit.blocked": None,      # an attempt the pool cannot supply
    #                                    yet (the profile, which names an
    #                                    annotation as it opens, shows it
    #                                    as engine.admit)
    "engine.prefill": "prefill",       # input upload, dispatch through
    #                                    the first token's readback
    "engine.prefill.step": "prefill",  # chunked prefill: one stage-step
    #                                    and the requests it finished
    "engine.prefill.finish": None,     # one finished request: first
    #                                    token, scatter
    "engine.decode": "decode",         # the tick's decode phase
    "engine.decode.prep": None,        # page writes, tables, uploads,
    #                                    the step's dispatch
    "engine.decode.wait": None,        # one replica's readback
    "engine.verify": None,             # one replica's verify readback
    "engine.decode.collect": None,     # tokens to requests, retirement
    "engine.decode.dispatch": None,    # overlap mode: one replica's
    #                                    dispatch, no readback
    "engine.drain": None,              # overlap mode: a delayed readback
    "engine.queue": None,
}
BUCKETS = ("admission", "prefill", "decode", "replan", "idle", "host_sync")

# the names an attached Tracer records phases under: the span names it
# had before the phase recorder, for the same extent of the tick; any
# other phase keeps its own name
TRACER_NAMES = {
    "engine.admission": "admission",
    "engine.prefill": "prefill",
    "engine.decode.wait": "decode",    # from the step's dispatch (since=)
    "engine.verify": "verify",         # likewise
    "engine.decode.dispatch": "decode_dispatch",
    "engine.drain": "drain",
    "engine.replan.drain": "drain",
    "engine.replan": "replan",
    "engine.rebalance": "rebalance",
    "engine.queue": "queue",
}

# name, t0_ns, t1_ns, tick, uid
Span = Tuple[str, int, int, Optional[int], Any]


class _Phase:
    """One open phase: the annotation, then the clock pair inside it."""

    __slots__ = ("rec", "name", "uid", "track", "args", "since", "ann",
                 "base", "t0")

    def __init__(self, rec: "PhaseRecorder", name: str, uid, track: Track,
                 args, since):
        self.rec, self.name, self.uid, self.track = rec, name, uid, track
        self.args, self.since = args, since

    def __enter__(self) -> "_Phase":
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.base = self.rec._accrued
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self.ann.__exit__(None, None, None)
        self.rec._close(self, t1)
        return False


class PhaseRecorder:
    """The engine's phase clock: ``phase_time_s`` buckets, and the spans
    they are summed from in a bounded log of the stats window.

    ``time`` holds the buckets in seconds.  ``admission``, ``prefill``,
    ``decode`` and ``replan`` are the sums of their spans' own time
    (``PHASE_BUCKETS``); ``idle`` is the host wall between one
    ``engine.tick`` span and the next; ``host_sync`` is an overlay the
    engine accrues itself (device readback time, inside whichever phase
    is open).

    The log keeps the newest ``capacity`` spans; ``dropped`` counts the
    rest.  ``tick`` in a span is the index (since ``reset()``) of the
    ``engine.tick`` it lies in (an ``engine.tick``'s own), None outside
    ticks and for ``engine.queue``, which spans them; ``uid`` is the
    request's, None for a phase of no one request.
    """

    def __init__(self, capacity: int = 1 << 16):
        if capacity <= 0:
            raise ValueError("phase log capacity must be positive")
        self.capacity = int(capacity)
        self._buf: List[Optional[Span]] = [None] * self.capacity
        self._accrued = 0               # ns accrued to any bucket, ever
        self.time: Dict[str, float] = dict.fromkeys(BUCKETS, 0.0)
        self.tracer: Optional[Tracer] = None
        self.reset()

    def reset(self) -> None:
        """Start a new stats window: zero the buckets, empty the log."""
        self._n = 0
        for k in self.time:
            self.time[k] = 0.0
        self.ticks = 0                  # engine.tick spans closed
        self._in_tick = False
        self.first_tick_ns: Optional[int] = None
        self.last_tick_end_ns: Optional[int] = None

    # -- recording -----------------------------------------------------
    def span(self, name: str, uid=None, track: Track = "tick", args=None,
             since: Optional[int] = None) -> _Phase:
        """``with rec.span(name):`` times one phase.  The rest is read
        only when a Tracer is attached: ``track`` places the span on its
        timeline, ``args`` (a callable, called as the span closes) gives
        the record's args (else ``{"uid": uid}``), and ``since`` (a
        ``perf_counter_ns`` stamp) starts the record there instead."""
        return _Phase(self, name, uid, track, args, since)

    def tick(self) -> _Phase:
        """``with rec.tick():`` wraps one whole engine tick."""
        self._in_tick = True
        return _Phase(self, "engine.tick", None, "tick", None, None)

    def queued(self, uid, t_submit: float, t_admit_ns: int) -> None:
        """Log the wait of request ``uid`` from ``submit()`` (its
        ``perf_counter()`` stamp) to the start of its admission."""
        t0 = round(t_submit * 1e9)
        self._put(("engine.queue", t0, t_admit_ns, None, uid))
        if self.tracer is not None:
            self.tracer.span("requests", "queue", t0 * 1e-9,
                             t_admit_ns * 1e-9, {"uid": uid})

    def _close(self, ph: _Phase, t1: int) -> None:
        name, t0 = ph.name, ph.t0
        tick = self.ticks if self._in_tick else None
        if name == "engine.tick":
            if self.last_tick_end_ns is not None:
                self.time["idle"] += (t0 - self.last_tick_end_ns) * 1e-9
            if self.first_tick_ns is None:
                self.first_tick_ns = t0
            self.last_tick_end_ns = t1
            self.ticks += 1
            self._in_tick = False
        else:
            bucket = PHASE_BUCKETS[name]
            if bucket is not None:
                own = (t1 - t0) - (self._accrued - ph.base)
                self.time[bucket] += own * 1e-9
                self._accrued += own
        self._put((name, t0, t1, tick, ph.uid))
        if self.tracer is not None:
            if ph.args is not None:
                args = ph.args()
            else:
                args = None if ph.uid is None else {"uid": ph.uid}
            self.tracer.span(ph.track, TRACER_NAMES.get(name, name),
                             (ph.since or t0) * 1e-9, t1 * 1e-9, args)

    def _put(self, rec: Span) -> None:
        i = self._n
        self._buf[i % self.capacity] = rec
        self._n = i + 1

    # -- inspection ----------------------------------------------------
    @property
    def dropped(self) -> int:
        return max(0, self._n - self.capacity)

    def spans(self, queued=()) -> List[Span]:
        """A copy of the retained spans, oldest first, then one
        ``engine.queue`` span per request still queued (``queued``: pairs
        of uid and submit stamp) that ends where the last tick ended: its
        wait so far, as of the last tick that left it queued."""
        n = self._n
        if n <= self.capacity:
            out = self._buf[:n]
        else:
            head = n % self.capacity
            out = self._buf[head:] + self._buf[:head]
        end = self.last_tick_end_ns
        for uid, t_submit in queued:
            t0 = round(t_submit * 1e9)
            out.append(("engine.queue", t0, max(t0, end or t0), None, uid))
        return out
