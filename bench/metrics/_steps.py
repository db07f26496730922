"""Shared by the step and kernel readers: the model FLOPs of the traced
window's ticks (from the harness's tick log, counted by the
configuration's architecture module, ``run.arch``) and the names by
which the trace shows each step program and each kernel."""

# jitted step programs, as the trace's module line names them (the
# engine's ``make_serve_step`` / ``make_prefill_suffix_paged_step``)
DECODE_MODULE = r"serve_step"
PREFILL_MODULE = r"prefill_suffix_paged"
# Pallas kernels, as the trace's op line names them: the op takes the
# ``name=`` its ``pallas_call`` passes (``kernels/paged_attention.py``),
# numbered (``fused_paged_decode_grouped.3``,
# ``paged_prefill_attention_grouped.6``)
FUSED_DECODE_OP = r"^fused_paged_decode"
PREFILL_ATTN_OP = r"^paged_prefill_attention"


def decode_flops(run):
    return sum(run.arch.decode_token_flops(p)
               for t in run.ticks for p in t.decode_positions)


def prefill_flops(run):
    return sum(run.arch.prefill_flops(n, o)
               for t in run.ticks for n, o in t.prefills)
