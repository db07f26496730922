"""Shared by the step and kernel readers: the model FLOPs of the traced
window's ticks (from the harness's tick log) and the names by which the
trace shows each step program and each kernel."""
from bench import flops as F

# jitted step programs, as the trace's module line names them (the
# engine's ``make_serve_step`` / ``make_prefill_suffix_paged_step``)
DECODE_MODULE = r"serve_step"
PREFILL_MODULE = r"prefill_suffix_paged"
# Pallas kernels, as the trace's op line names them: no ``pallas_call``
# passes ``name=``, so the op takes the kernel function's name
# (``fused_paged_decode_grouped.3``, ``paged_prefill_attention_grouped.6``)
FUSED_DECODE_OP = r"^fused_paged_decode"
PREFILL_ATTN_OP = r"^paged_prefill_attention"


def decode_flops(run):
    return sum(F.decode_token_flops(run.dims, p)
               for t in run.ticks for p in t.decode_positions)


def prefill_flops(run):
    return sum(F.prefill_flops(run.dims, n, o)
               for t in run.ticks for n, o in t.prefills)
