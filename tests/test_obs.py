"""Observability layer: phase recorder, tracer ring, metrics registry,
Perfetto export, engine integration (no-op guarantee, parity,
utilization, snapshots)."""
import json

import numpy as np
import pytest

import jax

import repro.obs.trace as trace_mod
from repro.configs import REGISTRY, reduced
from repro.models import build_model
from repro.obs import (PHASE_BUCKETS, MetricsRegistry, PhaseRecorder,
                       TraceConfig, Tracer, TrafficSnapshot, to_perfetto,
                       validate_perfetto, write_metrics, write_trace)
from repro.plan import lower_serving, uniform_plan
from repro.serving import AdaptiveConfig, Request, ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(REGISTRY["yi-6b"], layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _plan(cfg, slots=2, chunk=4):
    return lower_serving(uniform_plan(cfg.num_groups, 2, n_microbatches=2),
                         slots=slots, chunk=chunk)


def _drive(eng, n=3, new_tokens=4):
    for i in range(n):
        eng.submit(Request(i, np.arange(1, 7 + i, dtype=np.int32),
                           new_tokens))
    eng.run()
    return [list(r.out_tokens) for r in sorted(eng.done, key=lambda r: r.uid)]


# ---------------------------------------------------------------------------
# trace core


def test_tracer_ring_wraps_and_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant("tick", f"e{i}", t=float(i))
    assert tr.events == 10
    assert tr.dropped == 6
    recs = tr.records()
    assert len(recs) == 4
    # oldest-first, and only the newest 4 survive the wrap
    assert [r[2] for r in recs] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert tr.events == 0 and tr.records() == []


def test_tracer_records_complete_spans_only():
    """Spans enter the ring with both endpoints — a wrapped ring can
    never export a B without its E."""
    tr = Tracer(capacity=2)
    for i in range(5):
        tr.span("tick", "s", t0=float(i), t1=float(i) + 0.5)
    for rec in tr.records():
        assert rec[0] == "X" and rec[4] > rec[3]
    with pytest.raises(ValueError):
        tr.flow("tick", "x", 1)
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_perfetto_export_shape_and_validation():
    tr = Tracer()
    t = tr.t0
    tr.span("tick", "outer", t + 0.001, t + 0.005)
    tr.span(("stage", 0), "prefill_chunk", t + 0.002, t + 0.003,
            args={"tokens": 4})
    tr.span("requests", "admit", t + 0.002, t + 0.002, flow_out=7)
    tr.span("requests", "retire", t + 0.004, t + 0.004, flow_in=7)
    tr.instant("requests", "submit", t=t + 0.0005)
    tr.counter("tick", "engine", {"queue": 1}, t=t + 0.001)
    obj = to_perfetto(tr)
    assert not validate_perfetto(
        obj, require_names=("outer", "prefill_chunk", "submit"))
    ev = obj["traceEvents"]
    # B/E pairing per span, metadata names the tracks
    assert sum(1 for e in ev if e.get("ph") == "B") == \
        sum(1 for e in ev if e.get("ph") == "E") == 4
    meta = {e["args"]["name"] for e in ev
            if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"engine", "requests", "prefill stage 0"} <= meta
    # flows resolve: one s and one f with the same id
    flows = [(e["ph"], e["id"]) for e in ev if e.get("ph") in ("s", "f")]
    assert ("s", "7") in flows and ("f", "7") in flows
    # validator catches a dangling flow finish
    tr2 = Tracer()
    tr2.span("requests", "retire", tr2.t0, tr2.t0 + 1e-4, flow_in=9)
    assert any("flow" in p for p in validate_perfetto(to_perfetto(tr2)))


# ---------------------------------------------------------------------------
# metrics registry


def test_metrics_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("repro_requests_total", help="requests").inc(3)
    reg.gauge("repro_occ", stage="0").set(0.5)
    reg.gauge("repro_occ", stage="1").set(0.25)
    h = reg.histogram("repro_lat_seconds", (0.01, 0.1, 1.0), help="lat")
    for v in (0.005, 0.05, 0.05, 5.0):
        h.observe(v)
    txt = reg.to_prometheus()
    assert "# TYPE repro_requests_total counter" in txt
    assert "repro_requests_total 3" in txt
    assert 'repro_occ{stage="0"} 0.5' in txt
    assert 'repro_occ{stage="1"} 0.25' in txt
    # histogram buckets are CUMULATIVE in the exposition, +Inf == count
    assert 'repro_lat_seconds_bucket{le="0.01"} 1' in txt
    assert 'repro_lat_seconds_bucket{le="0.1"} 3' in txt
    assert 'repro_lat_seconds_bucket{le="1"} 3' in txt
    assert 'repro_lat_seconds_bucket{le="+Inf"} 4' in txt
    assert "repro_lat_seconds_count 4" in txt
    # registry invariants
    assert reg.counter("repro_requests_total") is \
        reg.counter("repro_requests_total")
    with pytest.raises(ValueError):
        reg.gauge("repro_requests_total")
    with pytest.raises(ValueError):
        reg.counter("repro_requests_total").inc(-1)
    snap = reg.snapshot()
    assert snap["repro_lat_seconds_count"] == 4.0
    reg.reset()
    assert reg.counter("repro_requests_total").value == 0.0
    assert reg.names() == sorted(reg.names())


# ---------------------------------------------------------------------------
# engine integration: no-op guarantee + parity + valid traces


@pytest.mark.parametrize("mode", ["mono-dense", "mono-paged",
                                  "plan-dense", "plan-paged"])
def test_tracing_is_noop_off_and_parity_preserving_on(setup, mode, tmp_path):
    cfg, model, params = setup
    kw = {}
    if mode.startswith("plan"):
        kw["plan"] = _plan(cfg)
    if mode.endswith("paged"):
        kw.update(paged=True, page_size=4)

    before = trace_mod.RECORDS_TOTAL
    gold = _drive(ServingEngine(model, params, slots=2, max_seq=48, **kw))
    assert trace_mod.RECORDS_TOTAL == before, (
        "trace=None engine pushed trace records on the hot path")

    eng = ServingEngine(model, params, slots=2, max_seq=48,
                        trace=TraceConfig(), **kw)
    assert _drive(eng) == gold, f"{mode}: traced streams diverged"
    assert trace_mod.RECORDS_TOTAL > before

    obj = to_perfetto(eng._tr)
    prefill_name = "prefill_chunk" if "plan" in mode else "prefill"
    assert not validate_perfetto(
        obj, require_names=(prefill_name, "decode", "admit", "retire",
                            "submit"))
    path = tmp_path / "t.json"
    write_trace(eng._tr, str(path))
    assert json.loads(path.read_text())["traceEvents"]


def test_trace_spans_carry_stage_replica_and_request_tags(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, slots=2, max_seq=48,
                        plan=_plan(cfg), paged=True, page_size=4, trace=True)
    _drive(eng)
    recs = eng._tr.records()
    chunk_tracks = {r[1] for r in recs if r[0] == "X" and
                    r[2] == "prefill_chunk"}
    assert chunk_tracks and all(t[0] == "stage" for t in chunk_tracks)
    dec_tracks = {r[1] for r in recs if r[0] == "X" and r[2] == "decode"}
    assert dec_tracks == {("replica", 0), ("replica", 1)}
    admits = [r for r in recs if r[0] == "X" and r[2] == "admit"]
    assert admits and all(r[6] == r[5]["uid"] for r in admits)  # flow id


def test_utilization_stats_present_and_bounded(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, slots=2, max_seq=48,
                        plan=_plan(cfg), paged=True, page_size=4)
    _drive(eng)
    u = eng.stats()["utilization"]
    assert u["pipeline_ticks"] > 0
    assert set(u["stage_bubble_frac"]) == {0, 1}
    assert all(0.0 <= v <= 1.0 for v in u["stage_bubble_frac"].values())
    assert set(u["replica_occupancy"]) == {0, 1}
    assert all(0.0 < v <= 1.0 for v in u["replica_occupancy"].values())
    assert 0.0 <= u["replica_load_spread"] <= 1.0
    assert 0.0 <= u["spec_acceptance_rate"] <= 1.0
    assert 0.0 <= u["prefix_hit_rate"] <= 1.0
    # mono window: replica 0 spans all slots, no pipeline ticks
    mono = ServingEngine(model, params, slots=2, max_seq=48)
    _drive(mono)
    mu = mono.stats()["utilization"]
    assert mu["pipeline_ticks"] == 0 and mu["stage_bubble_frac"] == {}
    assert set(mu["replica_occupancy"]) == {0}


def test_replan_decision_events_carry_scored_candidates(setup):
    cfg, model, params = setup
    plan = _plan(cfg)
    eng = ServingEngine(
        model, params, slots=2, max_seq=48, plan=plan, paged=True,
        page_size=4, trace=True,
        adapt=AdaptiveConfig(plans=[None, plan], interval_ticks=2,
                             cooldown_ticks=2, window_s=5.0, measure=False))
    _drive(eng, n=4, new_tokens=6)
    decisions = [r for r in eng._tr.records()
                 if r[0] == "I" and r[2] == "replan_decision"]
    assert decisions, "no replan_decision instants traced"
    scored = [r for r in decisions if r[4]["scores"]]
    assert scored, "no decision carried candidate scores"
    for label, score in scored[0][4]["scores"]:
        assert isinstance(label, str) and isinstance(score, float)
    assert all("decision" in r[4] for r in decisions)


def test_traffic_snapshot_is_typed_and_none_when_idle(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, slots=2, max_seq=48)
    assert eng.traffic_snapshot(1.0) is None       # no traffic yet
    _drive(eng)
    sig = eng.traffic_snapshot(60.0, slo_ttft_s=1.0, slo_tpot_s=1.0)
    assert isinstance(sig, TrafficSnapshot)
    assert sig.lam > 0 and sig.avg_prompt > 0 and sig.avg_new > 0
    assert sig.window_s == 60.0
    assert sig.queue_len == 0 and sig.active == 0
    with pytest.raises(Exception):
        sig.lam = 1.0                              # frozen dataclass


def test_export_metrics_and_write_paths(setup, tmp_path):
    cfg, model, params = setup
    eng = ServingEngine(model, params, slots=2, max_seq=48,
                        plan=_plan(cfg), paged=True, page_size=4)
    _drive(eng)
    reg = eng.export_metrics()
    names = set(reg.names())
    assert {"repro_ttft_seconds", "repro_tpot_seconds",
            "repro_requests_total", "repro_tokens_generated_total",
            "repro_throughput_tok_s", "repro_phase_seconds",
            "repro_stage_bubble_frac", "repro_replica_occupancy",
            "repro_replans_total"} <= names
    # folding is idempotent: gauges are set, not accrued
    a = eng.export_metrics().snapshot()
    b = eng.export_metrics().snapshot()
    assert a == b
    assert a["repro_requests_total"] == 3.0
    p = tmp_path / "m.prom"
    write_metrics(reg, str(p))
    txt = p.read_text()
    assert txt.endswith("\n")
    assert "repro_ttft_seconds_bucket" in txt
    # write_trace refuses on an untraced engine
    with pytest.raises(ValueError):
        eng.write_trace(str(tmp_path / "t.json"))


# ---------------------------------------------------------------------------
# the phase recorder: the engine's phase clock as spans


@pytest.mark.parametrize("mode", ["mono-paged", "plan-paged"])
def test_phase_spans_tile_each_tick_and_sum_to_the_buckets(setup, mode):
    cfg, model, params = setup
    kw = dict(paged=True, page_size=4)
    if mode.startswith("plan"):
        kw["plan"] = _plan(cfg)
    eng = ServingEngine(model, params, slots=2, max_seq=48, **kw)
    _drive(eng)                      # 3 requests over 2 slots: one queues
    st = eng.stats()
    spans = st["spans"]
    assert st["spans_dropped"] == 0
    assert {s[0] for s in spans} <= set(PHASE_BUCKETS)
    ticks = [s for s in spans if s[0] == "engine.tick"]
    assert len(ticks) == st["ticks"]
    assert [s[3] for s in ticks] == list(range(len(ticks)))
    def within(a, b):
        return a is not b and b[1] <= a[1] and a[2] <= b[2]

    for k, (_, t0, t1, _, _) in enumerate(ticks):
        # every phase of tick k lies inside it; two phases either nest or
        # follow one another, and those that nest in none are bucketed
        inner = sorted((s for s in spans if s[3] == k
                        and s[0] != "engine.tick"),
                       key=lambda s: (s[1], -s[2]))
        assert inner and all(t0 <= s[1] <= s[2] <= t1 for s in inner)
        for i, a in enumerate(inner):
            assert all(b[1] >= a[2] or b[2] <= a[2] for b in inner[i + 1:])
        top = [s for s in inner if not any(within(s, o) for o in inner)]
        assert all(PHASE_BUCKETS[s[0]] is not None for s in top)
        assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))
    # the admission loop holds each request's admit and prefill
    loops = [s for s in spans if s[0] == "engine.admission"]
    if mode.startswith("mono"):
        assert all(any(within(s, o) for o in loops) for s in spans
                   if s[0] in ("engine.admit", "engine.prefill"))
    # a bucket is the sum of its spans' own time: their length less
    # that of the bucketed spans nested in them
    bucketed = [s for s in spans if PHASE_BUCKETS[s[0]] is not None]
    sums = dict.fromkeys(st["phase_time_s"], 0.0)
    for s in bucketed:
        own = s[2] - s[1] - sum(c[2] - c[1] for c in bucketed
                                if within(c, s))
        sums[PHASE_BUCKETS[s[0]]] += own * 1e-9
    for b in ("admission", "prefill", "decode", "replan"):
        assert sums[b] == pytest.approx(st["phase_time_s"][b], abs=1e-6), b
    assert st["phase_time_s"]["decode"] > 0
    # one queue span per request, from its submit to its admission
    queued = [s for s in spans if s[0] == "engine.queue"]
    assert sorted(s[4] for s in queued) == [0, 1, 2]
    admit_start = {s[4]: s[1] for s in spans if s[0] == "engine.admit"}
    assert all(s[2] == admit_start[s[4]] for s in queued)
    assert all(s[1] <= s[2] for s in queued)


def test_a_request_still_queued_counts_its_wait_so_far(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, slots=1, max_seq=48)
    for uid in range(2):
        eng.submit(Request(uid, np.arange(1, 6, dtype=np.int32), 4))
    eng.tick()                                     # admits uid 0 only
    spans = eng.stats()["spans"]
    queued = {s[4]: s for s in spans if s[0] == "engine.queue"}
    assert set(queued) == {0, 1}
    tick_end = max(s[2] for s in spans if s[0] == "engine.tick")
    assert queued[1][2] == tick_end and queued[1][1] < tick_end
    eng.reset_stats()
    assert [s[0] for s in eng.stats()["spans"]] == ["engine.queue"]


def test_phase_log_wraps_and_counts_drops(setup):
    rec = PhaseRecorder(capacity=4)
    for i in range(10):
        with rec.span("engine.decode", uid=i):
            pass
    spans = rec.spans()
    assert rec.dropped == 6 and len(spans) == 4
    assert [s[4] for s in spans] == [6, 7, 8, 9]   # newest kept, in order
    assert rec.time["decode"] > 0
    rec.reset()
    assert rec.spans() == [] and rec.dropped == 0
    assert rec.time["decode"] == 0.0
    with pytest.raises(ValueError):
        PhaseRecorder(capacity=0)
    # through the engine: a small log wraps, and stats() says so
    cfg, model, params = setup
    eng = ServingEngine(model, params, slots=2, max_seq=48)
    eng._phases = PhaseRecorder(capacity=8)
    _drive(eng)
    st = eng.stats()
    assert st["spans_dropped"] > 0 and len(st["spans"]) == 8


def test_phase_spans_go_to_an_attached_tracer_only(setup):
    cfg, model, params = setup
    before = trace_mod.RECORDS_TOTAL
    eng = ServingEngine(model, params, slots=2, max_seq=48)
    _drive(eng)
    assert trace_mod.RECORDS_TOTAL == before
    assert eng.stats()["spans"]
    eng = ServingEngine(model, params, slots=2, max_seq=48, trace=True)
    _drive(eng)
    recs = [r for r in eng._tr.records() if r[0] == "X"]
    names = {r[2] for r in recs}
    assert {"admission", "prefill", "decode", "queue", "engine.admit",
            "engine.decode", "engine.decode.prep", "engine.decode.collect",
            "engine.tick"} <= names
    # the spans the Tracer had before keep their extent and their args
    by = {}
    for r in recs:
        by.setdefault(r[2], []).append(r)
    assert all(set(r[5]) == {"queued", "admitted", "plan"}
               for r in by["admission"])
    assert all(set(r[5]) == {"uid", "slot", "tokens", "reused", "plan"}
               and r[1] == ("stage", 0) for r in by["prefill"])
    # decode: one replica's step, from its dispatch through its readback
    preps = sorted(r[3] for r in by["engine.decode.prep"])
    assert sorted(r[3] for r in by["decode"]) == preps
    assert all(set(r[5]) == {"active", "slots", "plan"}
               and r[1] == ("replica", 0) for r in by["decode"])


def test_a_bucketed_span_accrues_its_own_time():
    rec = PhaseRecorder()
    with rec.span("engine.admission"):
        with rec.span("engine.admit"):
            pass
        with rec.span("engine.prefill"):
            pass
    (_, a0, a1, _, _), (_, p0, p1, _, _) = [
        s for s in rec.spans() if s[0] != "engine.admit"][::-1]
    assert rec.time["prefill"] == pytest.approx((p1 - p0) * 1e-9, abs=1e-12)
    assert rec.time["admission"] == pytest.approx(
        (a1 - a0 - (p1 - p0)) * 1e-9, abs=1e-12)


def test_an_admission_the_pool_cannot_supply_is_logged_apart(setup):
    cfg, model, params = setup
    # room for one request's pages at a time: the second waits for them
    eng = ServingEngine(model, params, slots=2, max_seq=48, paged=True,
                        page_size=4, num_blocks=4)
    _drive(eng, n=2)
    spans = eng.stats()["spans"]
    names = [s[0] for s in spans]
    assert names.count("engine.admit") == 2
    assert names.count("engine.admit.blocked") > 0
    assert sorted(s[4] for s in spans if s[0] == "engine.queue") == [0, 1]


def test_a_replan_keeps_its_tracer_record(setup):
    cfg, model, params = setup
    plan = _plan(cfg)
    eng = ServingEngine(model, params, slots=2, max_seq=48, plan=plan,
                        paged=True, page_size=4, trace=True)
    for i in range(2):
        eng.submit(Request(i, np.arange(1, 7, dtype=np.int32), 6))
    for _ in range(4):
        eng.tick()
    eng.replan(None)
    eng.run()
    swaps = [r for r in eng._tr.records() if r[0] == "X" and r[2] == "replan"]
    assert len(swaps) == 1 and swaps[0][5] == {
        "from": plan.label, "to": "mono", "migrations": eng.migrations,
        "migration_copies": eng.migration_copies}
    assert "engine.replan" in {s[0] for s in eng.stats()["spans"]}
