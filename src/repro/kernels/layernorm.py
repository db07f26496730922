"""One-pass LayerNorm / RMSNorm Pallas TPU kernel.

SSR's line-buffer LayerNorm overlaps the mean pass with the variance pass so
the data is read once from the producer stream.  On TPU the analogue is a
single HBM read per row block: the row lives in VMEM while mean, variance,
and the normalized output are all computed — one read, one write, no second
pass over HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import compat


def _ln_kernel(x_ref, scale_ref, bias_ref, o_ref, *, kind, eps):
    x = x_ref[...].astype(jnp.float32)               # (br, d)
    if kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
        y = y * scale_ref[...].astype(jnp.float32) \
            + bias_ref[...].astype(jnp.float32)
    else:  # rmsnorm
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "block_rows",
                                             "interpret"))
def norm_onepass(x, scale, bias=None, *, kind="rmsnorm", eps=1e-6,
                 block_rows=256, interpret=False):
    """x: (R, D) row-normalized -> (R, D)."""
    r, d = x.shape
    br = min(block_rows, r)
    assert r % br == 0, (r, br)
    if bias is None:
        bias = jnp.zeros((d,), x.dtype)
    kernel = functools.partial(_ln_kernel, kind=kind, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=compat.kernel_out((r, d), x.dtype, x, scale, bias),
        interpret=interpret,
    )(x, scale.reshape(1, d), bias.reshape(1, d))
