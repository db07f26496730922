"""Gated linear recurrence Pallas TPU kernel:  h_t = a_t * h_{t-1} + b_t.

Backs the Mamba selective scan (jamba) and the xLSTM recurrences.  The GPU
formulation (CUDA selective-scan with warp shuffles) does not transfer;
the TPU-native shape is: grid over (rows, feature blocks), sequential over
sequence blocks, with the carry h held in VMEM scratch — the sequence
streams HBM→VMEM once and states never rematerialize in HBM (the same
"single streaming pass with carried state" idea as SSR's line buffer).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import compat


def _scan_kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, block_s):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)   # (1, bf)

    # one row per step, read from and written to the refs at a dynamic
    # sublane offset (a value cannot be sliced dynamically in-kernel)
    def step(t, h):
        row = pl.ds(t, 1)
        h = a_ref[0, row, :].astype(jnp.float32) * h \
            + b_ref[0, row, :].astype(jnp.float32)
        o_ref[0, row, :] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("block_s", "block_f",
                                             "interpret"))
def linear_scan(a, b, h0=None, *, block_s=256, block_f=512, interpret=False):
    """a, b: (N, S, F); h0: (N, F) or None.  Returns h_all (N, S, F)."""
    n, s, f = a.shape
    if h0 is None:
        h0 = jnp.zeros((n, f), jnp.float32)
    bs = min(block_s, s)
    bf = min(block_f, f)
    assert s % bs == 0 and f % bf == 0, (s, f, bs, bf)

    kernel = functools.partial(_scan_kernel, block_s=bs)
    # NB: the sequential (sequence) dimension is the *last* grid dim so the
    # VMEM carry scratch persists correctly between consecutive steps.
    return pl.pallas_call(
        kernel,
        grid=(n, f // bf, s // bs),
        in_specs=[
            pl.BlockSpec((1, bs, bf), lambda i, fb, sb: (i, sb, fb)),
            pl.BlockSpec((1, bs, bf), lambda i, fb, sb: (i, sb, fb)),
            # h0 rides as (N, 1, F): a (1, bf) block of it is a whole
            # second-minor dimension, as the TPU tiling requires
            pl.BlockSpec((1, 1, bf), lambda i, fb, sb: (i, 0, fb)),
        ],
        out_specs=pl.BlockSpec((1, bs, bf), lambda i, fb, sb: (i, sb, fb)),
        out_shape=compat.kernel_out((n, s, f), a.dtype, a, b, h0),
        scratch_shapes=[compat.vmem_scratch((1, bf), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0.reshape(n, 1, f))
