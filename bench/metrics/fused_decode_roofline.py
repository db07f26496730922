"""The fused paged decode kernel's share of its roofline: the least time
its calls in the traced window could take on this chip (per call, the
larger of FLOPs over peak and bytes over HBM bandwidth; the architecture
module's ``fused_decode_calls``, at each active slot's length) over their
device time in the trace."""
from bench import flops as F
from bench import trace as T
from bench.metrics import _steps


def read(run):
    ns, n = T.time_ns(run.trace, _steps.FUSED_DECODE_OP)
    steps = [t.decode_positions for t in run.ticks if t.decode_positions]
    if not n or not steps:
        return None
    best = sum(F.roofline_s(flops, nbytes, run.peak) * calls
               for pos in steps
               for flops, nbytes, calls in run.arch.fused_decode_calls(pos))
    return 100.0 * best / (ns * 1e-9)
