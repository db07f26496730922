"""Whole-step model FLOP/s utilization: every prefill and decode model
FLOP of the traced window over (window x bf16 peak).  Bounds every
kernel's share from above: a kernel taken off the path leaves its own
roofline silent, not this."""
from bench import trace as T
from bench.metrics import _steps


def read(run):
    w0, w1 = T.window(run.trace)
    flops = _steps.decode_flops(run) + _steps.prefill_flops(run)
    if not flops:
        return None
    return 100.0 * flops / ((w1 - w0) * 1e-9 * run.peak["bf16_flops"])
