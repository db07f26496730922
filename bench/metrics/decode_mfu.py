"""Model FLOP/s utilization of the decode step: the model FLOPs of every
token decoded in the traced window (bench/flops.py, at each token's
position) over the device time of the decode step program in the trace
(module ``serve_step``) times the chip's bf16 peak.  Work the step does
for idle slots is not model FLOPs."""
from bench import trace as T
from bench.metrics import _steps


def read(run):
    ns, n = T.time_ns(run.trace, _steps.DECODE_MODULE, "modules")
    flops = _steps.decode_flops(run)
    if not n or not flops:
        return None
    return 100.0 * flops / (ns * 1e-9 * run.peak["bf16_flops"])
