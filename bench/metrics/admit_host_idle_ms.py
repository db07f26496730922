"""Host admission work on the chip's critical path: device idle time
inside the window that falls within the engine's ``engine.admit`` spans
(pager admit: prefix match and allocation; prompt padding), per
admission.  An attempt the pool cannot supply yet is logged as
``engine.admit.blocked`` and counts for neither.  The spans are placed
on the profile by ``bench/metrics/_spans.py``."""
from bench.metrics import _spans


def read(run):
    return _spans.idle_ms_per(run, ("engine.admit",), "engine.admit")
