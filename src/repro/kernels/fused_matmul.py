"""Fused matmul + epilogue Pallas TPU kernel.

SSR fuses reuse-distance-1 ops (bias add, Reformat dtype casts, activation,
Transpose-free layouts) into the HMM matmul epilogue so they never occupy a
separate accelerator or an extra HBM round trip.  The TPU equivalent: a
tiled MXU matmul whose VMEM-resident fp32 accumulator gets bias + activation
+ down-cast applied in the epilogue before the single HBM write-back.

Grid (M/bm, N/bn, K/bk); K is the sequential accumulation dimension.
Block shapes default to MXU-aligned 128 multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import compat


def _epilogue(acc, bias, activation):
    if bias is not None:
        acc = acc + bias
    if activation == "gelu":
        acc = jax.nn.gelu(acc, approximate=True)
    elif activation == "silu":
        acc = jax.nn.silu(acc)
    elif activation == "relu2":
        acc = jnp.square(jnp.maximum(acc, 0.0))
    elif activation != "none":
        raise ValueError(activation)
    return acc


def _mm_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, activation, k_blocks):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kb == k_blocks - 1)
    def _done():
        bias = b_ref[...].astype(jnp.float32) if b_ref is not None else None
        o_ref[...] = _epilogue(acc_ref[...], bias, activation).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_m", "block_n", "block_k",
                     "out_dtype", "interpret"))
def matmul_fused(x, w, bias=None, *, activation="none", block_m=256,
                 block_n=256, block_k=512, out_dtype=None, interpret=False):
    """x: (M, K) @ w: (K, N) [+ bias (N,)] with fused epilogue -> (M, N)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    out_dtype = out_dtype or x.dtype
    k_blocks = k // bk

    kernel = functools.partial(_mm_kernel, activation=activation,
                               k_blocks=k_blocks)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
        pl.BlockSpec((bk, bn), lambda i, j, kb: (kb, j)),
    ]
    args = [x, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)))
        args.append(bias.reshape(1, n))
    else:
        # pallas needs a concrete operand list; pass a dummy 0-bias row.
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)))
        args.append(jnp.zeros((1, n), x.dtype))

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=compat.kernel_out((m, n), out_dtype, x, w, bias),
        scratch_shapes=[compat.vmem_scratch((bm, bn), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
