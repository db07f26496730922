"""The engine's phase spans on the profiler's timeline: a few ticks of a
tiny mono-paged engine, each inside a ``bench.tick`` annotation as the
benchmark harness runs them, profiled on the CPU in this process.  Every
engine phase shows as a host annotation, and the benchmark's alignment
(``bench/metrics/_spans.py``) places the engine's span log on the
profile within 200 us of those annotations."""
import glob
import os
import sys

import jax
import numpy as np

from repro.configs import REGISTRY, reduced
from repro.models import build_model
from repro.serving import Request, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PHASES = ("engine.tick", "engine.admission", "engine.admit",
          "engine.prefill", "engine.decode", "engine.decode.prep",
          "engine.decode.wait", "engine.decode.collect")


def _profiled_ticks(logdir):
    from jax.profiler import TraceAnnotation
    model = build_model(reduced(REGISTRY["yi-6b"], layers=2))
    params = model.init(jax.random.key(0))
    eng = ServingEngine(model, params, slots=2, max_seq=48, paged=True,
                        page_size=4, prefill_bucket=8)
    prompts = [np.arange(1, 6 + i, dtype=np.int32) for i in range(3)]
    for i, p in enumerate(prompts):           # compile every shape first
        eng.submit(Request(-1 - i, p, 4))
    eng.run()
    eng.reset_stats()
    jax.profiler.start_trace(logdir)
    with TraceAnnotation("bench.window"):
        for i, p in enumerate(prompts):
            eng.submit(Request(i, p, 4))
        while eng.queue or eng.active:
            with TraceAnnotation("bench.tick"):
                eng.tick()
    stats = eng.stats()
    jax.profiler.stop_trace()
    return stats


def _host_events(logdir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return [[e.name, e.start_ns, e.end_ns]
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("bench.", "engine."))]


def test_engine_phases_are_annotations_the_span_log_aligns_to(tmp_path):
    from bench.metrics import _spans
    from bench.run import Run
    stats = _profiled_ticks(str(tmp_path))
    host = _host_events(str(tmp_path))
    anns = {}
    for name, t0, t1 in host:
        anns.setdefault(name, []).append((t0, t1))
    assert set(PHASES) <= set(anns), sorted(anns)

    spans = _spans.aligned(Run(stats=stats, trace={"host": host}))
    assert spans is not None
    for name in PHASES:
        logged = sorted(s[1:3] for s in spans if s[0] == name)
        shown = sorted(anns[name])
        assert logged and len(logged) == len(shown), name
        for (s0, s1), (a0, a1) in zip(logged, shown):
            assert abs(s0 - a0) <= 200_000 and abs(s1 - a1) <= 200_000, name
