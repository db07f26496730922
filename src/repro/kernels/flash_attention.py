"""Flash attention Pallas TPU kernel (online softmax).

This is the TPU adaptation of SSR's "fine-grained pipeline for nonlinear
kernels": the paper's bypass line-buffer lets the Softmax reduction overlap
the MM that produces its input; on TPU the idiomatic equivalent is the
*online softmax* — running (m, l) statistics carried across KV blocks in
VMEM scratch so scores never round-trip to HBM and the softmax "second pass"
disappears into the QK^T/PV matmul pipeline on the MXU.

Layout: q (B, H, Sq, D); k, v (B, Hkv, Skv, D); positions as (1, S) int32
rows so masking (causal / sliding-window / ring-cache validity) is computed
from *absolute positions* inside the kernel, matching the ref oracle.

Grid: (B, H, Sq/bq, Skv/bk) — the KV dimension is sequential ("arbitrary")
and accumulates into (acc, m, l) VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import compat

NEG_INF = -1e30


def _flash_kernel(qpos_ref, kpos_ref, kvalid_ref, q_ref, k_ref, v_ref,  # ins
                  o_ref,                                                # outs
                  acc_ref, m_ref, l_ref,                                # scratch
                  *, scale, causal, window, softcap, kv_blocks):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    qp = qpos_ref[0].astype(jnp.int32)               # (bq,)
    kp = kpos_ref[0].astype(jnp.int32)               # (bk,)
    rel = qp[:, None] - kp[None, :]
    ok = kvalid_ref[0][None, :] > 0
    if causal:
        ok &= rel >= 0
    if window > 0:
        ok &= rel < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                              # (bq, 1)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                           # (bq, bk)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == kv_blocks - 1)
    def _done():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0, ...] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"))
def flash_attention_bhsd(q, k, v, q_pos, k_pos, k_valid, *, causal=True,
                         window=0, softcap=0.0, block_q=128, block_k=128,
                         interpret=False):
    """q: (B,H,Sq,D), k/v: (B,Hkv,Skv,D) — returns (B,H,Sq,D).

    q_pos: (Sq,) int32; k_pos: (Skv,) int32; k_valid: (Skv,) int32 (0/1)."""
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    groups = h // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    kv_blocks = skv // bk
    scale = 1.0 / math.sqrt(d)

    qp = q_pos.reshape(1, sq).astype(jnp.int32)
    kp = k_pos.reshape(1, skv).astype(jnp.int32)
    kv = k_valid.reshape(1, skv).astype(jnp.int32)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, kv_blocks=kv_blocks)

    grid = (b, h, sq // bq, kv_blocks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq), lambda b_, h_, i, j: (0, i)),
            pl.BlockSpec((1, bk), lambda b_, h_, i, j: (0, j)),
            pl.BlockSpec((1, bk), lambda b_, h_, i, j: (0, j)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j, g=groups: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j, g=groups: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=compat.kernel_out((b, h, sq, d), q.dtype, q, k, v),
        scratch_shapes=[
            compat.vmem_scratch((bq, d), jnp.float32),
            compat.vmem_scratch((bq, 1), jnp.float32),
            compat.vmem_scratch((bq, 1), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, kv, q, k, v)
