"""The reduction from trace events to numbers: on a synthetic trace whose
answers are known, and on a small trace recorded once on the chip."""
import gzip
import json
import os

import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def _synthetic():
    # window 0-100; device ops 10-30, 20-40 (overlap), 60-70, one op
    # straddling the window's end 95-120
    return {
        "host": [["bench.window", 0, 100], ["bench.tick", 6, 45],
                 ["bench.wait", 45, 58], ["bench.tick", 58, 99]],
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 10, 30], ["my_kernel", 20, 40],
                    ["fusion.2", 60, 70], ["copy.3", 95, 120]],
            "modules": [["jit_serve_step(1)", 10, 40],
                        ["jit_other", 60, 70]]}},
    }


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    assert T.busy_ns(_synthetic()) == 30 + 10 + 5


def test_time_of_named_events():
    ev = _synthetic()
    assert T.time_ns(ev, r"my_kernel") == (20, 1)
    assert T.time_ns(ev, r"serve_step", "modules") == (30, 1)
    assert T.time_ns(ev, r"^fusion") == (30, 2)


def test_breakdown_labels_idle_gaps_by_the_innermost_annotation():
    b = T.breakdown(_synthetic())
    assert b["device_ops"][0] == ["fusion", pytest.approx(30e-9)]
    gaps = {round(s * 1e9): name for name, s in b["idle_gaps"]}
    # gaps: 0-10 (in no tick: outside), 40-60 (midpoint 50: wait),
    # 70-95 (midpoint 82.5: tick)
    assert gaps == {10: "bench.outside", 20: "bench.wait", 25: "bench.tick"}


def test_union_merges_touching_intervals():
    assert T.union([(5, 6), (0, 2), (2, 3)]) == [(0, 3), (5, 6)]


def _recorded():
    path = os.path.join(DATA, "trace_chat.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_chip_trace_reduces():
    ev = _recorded()
    w0, w1 = T.window(ev)
    busy = T.busy_ns(ev)
    assert 0 < busy < w1 - w0
    ns, n = T.time_ns(ev, r"serve_step", "modules")
    assert n == 3 and 0 < ns <= busy
    assert T.time_ns(ev, r"prefill_suffix_paged", "modules")[1] == 1
    # one fused decode call per layer per decode step, one prefill
    # attention call per layer per prefill (yi-6b: 32 layers)
    assert T.time_ns(ev, r"^fused_paged_decode")[1] == 3 * 32
    assert T.time_ns(ev, r"^paged_prefill_attention")[1] == 32
    b = T.breakdown(ev)
    assert len(b["device_ops"]) == 10 and b["idle_gaps"]
