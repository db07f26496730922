"""Model FLOP/s utilization of prefill: the model FLOPs of every prompt
suffix prefilled in the traced window (its true length after its cached
offset, not the padded bucket) over the device time of the prefill
program in the trace (module ``prefill_suffix_paged``) times the bf16
peak."""
from bench import trace as T
from bench.metrics import _steps


def read(run):
    ns, n = T.time_ns(run.trace, _steps.PREFILL_MODULE, "modules")
    flops = _steps.prefill_flops(run)
    if not n or not flops:
        return None
    return 100.0 * flops / (ns * 1e-9 * run.peak["bf16_flops"])
