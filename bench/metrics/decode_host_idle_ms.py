"""The host's decode phase on the chip's critical path: per decode step
(engine tick), the device idle time inside the window that falls within
the tick's ``engine.decode.prep``, ``engine.decode.wait`` and
``engine.decode.collect`` spans (page writes, tables, uploads and the
step's dispatch; the readback; tokens to requests and retirement); the
median over the window's steps.

The three run from one step's preparation to its retirement, so where
within them the profile places the device's ops against the host's
moves no idle time out of the reading.  A median, since a rare stall
(85-130 ms of idle inside one step's readback, one or two in some
traced windows and none in others) would move a mean by more than the
host's decode work does.  The spans are placed on the profile by
``bench/metrics/_spans.py``."""
from bench.metrics import _spans


def read(run):
    return _spans.idle_ms_tick_median(
        run, ("engine.decode.prep", "engine.decode.wait",
              "engine.decode.collect"))
