"""The readers of the engine's phase spans, on a synthetic window whose
answers are known: the span log is on its own clock, 5 ms ahead of the
profile's, and each engine tick starts 2 us after its harness tick."""
import pytest

from bench import spec
from bench.metrics import _spans
from bench.run import Run

US = 1000                    # ns
SHIFT = 5000 * US            # span clock - profile clock


def _span(name, t0_us, t1_us, tick=None, uid=None):
    return (name, t0_us * US + SHIFT, t1_us * US + SHIFT, tick, uid)


def _run(**stats):
    # profile: window 0-1000 us, harness ticks 100-500 and 600-900;
    # device ops 160-240 (prefill), 300-440 and 660-840 (decode), 950-990
    trace = {
        "host": [["bench.window", 0, 1000 * US],
                 ["bench.tick", 100 * US, 500 * US],
                 ["bench.tick", 600 * US, 900 * US]],
        "devices": {"/device:TPU:0": {"ops": [
            ["prefill", 160 * US, 240 * US], ["decode", 300 * US, 440 * US],
            ["decode", 660 * US, 840 * US], ["other", 950 * US, 990 * US]],
            "modules": []}},
    }
    spans = [
        _span("engine.admit", 104, 144, 0, 7),
        _span("engine.queue", 0, 104, None, 7),
        _span("engine.prefill", 144, 244, 0, 7),
        _span("engine.decode.prep", 254, 294, 0),
        _span("engine.decode.wait", 294, 444, 0),
        _span("engine.decode.collect", 444, 484, 0),
        _span("engine.tick", 102, 492),
        _span("engine.decode.prep", 604, 644, 1),
        _span("engine.decode.wait", 644, 844, 1),
        _span("engine.decode.collect", 844, 884, 1),
        _span("engine.tick", 602, 892),
        _span("engine.queue", 700, 892, None, 8),     # still queued
    ]
    st = {"spans": spans, "spans_dropped": 0}
    st.update(stats)
    return Run(stats=st, trace=trace)


def _read(metric, run):
    return spec.reader(metric)(run)


def test_alignment_moves_the_log_onto_the_profile():
    spans = _spans.aligned(_run())
    ticks = [(s[1], s[2]) for s in spans if s[0] == "engine.tick"]
    assert ticks == [(100 * US, 490 * US), (600 * US, 890 * US)]


def test_readers_on_a_synthetic_window():
    run = _run()
    # idle 0-160, 240-300, 440-660, 840-950, 990-1000 us; prep, wait
    # and collect (aligned: 252-482 and 602-882) overlap it by 48 + 42
    # and 58 + 42 us: two decode steps, whose median is 95 us
    assert _read("decode_host_idle_ms", run) == pytest.approx(0.095)
    # admission 102-142 lies inside the idle 0-160: 40 us, one admission
    assert _read("admit_host_idle_ms", run) == pytest.approx(0.040)


def test_a_stall_in_one_step_leaves_the_decode_reading():
    run = _run()
    # a third tick, 1000-2100 us of the profile, whose readback waits
    # out a stall of the device (the window grows to take it in)
    run.trace["host"][0][2] = 3000 * US
    run.trace["host"].append(["bench.tick", 1000 * US, 2100 * US])
    run.stats["spans"] += [
        _span("engine.decode.prep", 1004, 1014, 2),
        _span("engine.decode.wait", 1014, 2004, 2),
        _span("engine.decode.collect", 2004, 2010, 2),
        _span("engine.tick", 1002, 2011)]
    run.trace["devices"]["/device:TPU:0"]["ops"] += [
        ["decode", 1000 * US, 1020 * US], ["decode", 2090 * US, 3000 * US]]
    # per step 90, 100 and 988 us (1020-2008 idle): the median is 100,
    # where a mean would read 393
    assert _read("decode_host_idle_ms", run) == pytest.approx(0.100)


def test_no_alignment_when_tick_counts_differ():
    run = _run()
    run.trace["host"].pop()                  # one harness tick fewer
    assert _spans.aligned(run) is None
    assert _read("decode_host_idle_ms", run) is None
    assert _read("admit_host_idle_ms", run) is None


def test_a_pause_at_one_end_of_a_tick_keeps_the_alignment():
    run = _run()
    run.trace["host"][2][2] += 100_000 * US  # a 100 ms pause after the
    #                                          engine's tick ended
    assert _read("decode_host_idle_ms", run) == pytest.approx(0.095)


def test_no_alignment_off_the_offset_or_with_drops():
    run = _run()
    run.trace["host"][2][1] += 1200 * US     # both ends 0.6 ms off the
    run.trace["host"][2][2] += 1200 * US     # median
    assert _spans.aligned(run) is None
    dropped = _run(spans_dropped=3)
    for m in ("decode_host_idle_ms", "admit_host_idle_ms"):
        assert _read(m, dropped) is None


def test_a_program_without_the_span_log_reads_nothing():
    run = _run()
    del run.stats["spans"], run.stats["spans_dropped"]
    for m in ("decode_host_idle_ms", "admit_host_idle_ms"):
        assert _read(m, run) is None
