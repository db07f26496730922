"""The paged prefill attention kernel's share of its roofline: the least
time its calls in the traced window could take (per call, the larger of
FLOPs over peak and bytes over HBM bandwidth, at the suffix's true length
and offset; the architecture module's ``prefill_attn_calls``) over their
device time in the trace."""
from bench import flops as F
from bench import trace as T
from bench.metrics import _steps


def read(run):
    ns, n = T.time_ns(run.trace, _steps.PREFILL_ATTN_OP)
    calls = [c for t in run.ticks for c in t.prefills]
    if not n or not calls:
        return None
    best = sum(F.roofline_s(flops, nbytes, run.peak) * k
               for ln, off in calls
               for flops, nbytes, k in run.arch.prefill_attn_calls(ln, off))
    return 100.0 * best / (ns * 1e-9)
