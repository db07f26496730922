"""Paged attention Pallas TPU kernels: decode, fused decode, chunk prefill.

Attention where K/V live in a physical block pool addressed through
per-slot block tables, instead of one dense ``(slots, max_seq, ...)``
reservation.  The block table rides the grid as a **scalar-prefetch**
operand: each grid step's ``index_map`` reads ``bt[b, j]`` to DMA the
j-th *logical* page of slot ``b`` straight from wherever it physically
sits — the gather never materializes a contiguous K/V copy in HBM, which
is the whole point (the SSR spatial story applied to memory: decode
replicas stay busy because their KV footprint is live-token-sized).

Layout (head-major pages): pool ``(N, Hkv, P, D)`` — every (page, kv
head) is one contiguous ``(P, D)`` tile, so each block's last two
dimensions are ``(page, head_dim)`` as the TPU compiler requires; int8
pools carry f32 scales ``(N, Hkv, P)``; q ``(B, Hkv, G, D)`` (query heads
grouped per kv head — GQA runs as one MXU matmul per kv head); block
tables ``(B, NB)`` int32 (host pre-clips unmapped entries into range —
rows past the length are masked anyway).

Decode grids are ``(B, NB)``: one step takes one logical page of one slot
for ALL kv heads (the ``(Hkv, P)`` scale blocks and the ``(Hkv, D)``
fresh rows are then whole array dimensions).  The prefill grid is
``(B, Hkv, NQ, NB)`` over query tiles of at most ``PREFILL_TQ`` rows, so
its VMEM footprint does not grow with the chunk length.  The page
dimension is sequential ("arbitrary"): online-softmax (m, l, acc)
statistics carry across pages in VMEM scratch, exactly like
``flash_attention.py`` carries them across KV blocks.  Pages past a
slot's last valid position keep the previous block index (no DMA) and
skip their compute; they would contribute exact zeros anyway.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import compat

NEG_INF = -1e30
PREFILL_TQ = 256          # query rows per prefill tile (VMEM bound)


def _online_softmax_step(s, v, acc_ref, m_ref, l_ref):
    """Fold one page's scores ``s`` (R, P) and values ``v`` (P, D) into
    the running (acc, m, l) statistics held in the given refs."""
    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _finish(acc_ref, l_ref):
    l = l_ref[...]
    return acc_ref[...] / jnp.where(l > 0.0, l, 1.0)


def _scores(q, k, scale, softcap):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    return s


# ---------------------------------------------------------------------------
# decode (length-masked)
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref,   # ins
                  o_ref,                                  # outs
                  acc_ref, m_ref, l_ref,                  # scratch
                  *, scale, softcap, page, nb, hk):
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # rows of logical page j hold positions j*P + i; valid below length.
    # the query sits at position length-1, so the length mask subsumes
    # causality — every valid cached key is attendable.
    @pl.when(j * page < length)
    def _page():
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        ok = pos < length
        for h in range(hk):
            q = q_ref[0, h].astype(jnp.float32)          # (G, D)
            k = k_ref[0, h].astype(jnp.float32)          # (P, D)
            v = v_ref[0, h].astype(jnp.float32)
            s = jnp.where(ok, _scores(q, k, scale, softcap), NEG_INF)
            _online_softmax_step(s, v, acc_ref.at[h], m_ref.at[h],
                                 l_ref.at[h])

    @pl.when(j == nb - 1)
    def _done():
        for h in range(hk):
            o_ref[0, h] = _finish(acc_ref.at[h], l_ref.at[h]).astype(
                o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_attention_grouped(q, k_pages, v_pages, block_tables, lengths, *,
                            softcap=0.0, interpret=False):
    """q: (B, Hkv, G, D); k_pages/v_pages: (N, Hkv, P, D);
    block_tables: (B, NB) int32 (in-range); lengths: (B,) int32.
    Returns (B, Hkv, G, D)."""
    b, hk, g, d = q.shape
    page = k_pages.shape[2]
    nb = block_tables.shape[1]

    def page_in(b_, j, bt, ln):
        last = jnp.maximum(ln[b_] - 1, 0) // page
        return (bt[b_, jnp.minimum(j, last)], 0, 0, 0)

    row = pl.BlockSpec((1, hk, g, d), lambda b_, j, bt, ln: (b_, 0, 0, 0))
    grid_spec = compat.prefetch_grid_spec(
        num_scalar_prefetch=2,           # block tables + lengths
        grid=(b, nb),
        in_specs=[row, pl.BlockSpec((1, hk, page, d), page_in),
                  pl.BlockSpec((1, hk, page, d), page_in)],
        out_specs=row,
        scratch_shapes=[
            compat.vmem_scratch((hk, g, d), jnp.float32),
            compat.vmem_scratch((hk, g, 1), jnp.float32),
            compat.vmem_scratch((hk, g, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(d),
                               softcap=softcap, page=page, nb=nb, hk=hk)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=compat.kernel_out((b, hk, g, d), q.dtype, q, k_pages,
                                    v_pages, lengths),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# fused RoPE + page write + decode
# ---------------------------------------------------------------------------

def _fused_decode_kernel(bt_ref, pos_ref, q_ref, kn_ref, vn_ref,
                         kp_ref, vp_ref, *rest,
                         scale, softcap, theta, page, nb, hk, quantized):
    """RoPE + page-write + decode attention, one pass over the pool.

    Scalar-prefetch: block tables (B, NB) + write positions (B,).  The
    pool blocks arrive through the same ``bt[b, j]`` index maps as the
    unfused kernel; the OUTPUT pool blocks alias the inputs
    (``input_output_aliases``) with a j-constant index map pinned to the
    slot's write page ``bt[b, pos // page]`` — each slot writes exactly
    one page (the input content with the fresh roped row substituted in
    every kv head) and every other page rides through the alias
    untouched.  RoPE runs in-kernel, once per slot at the first page, as
    ``x * cos + (x @ R) * sin`` with the rotate-half matrix R built from
    2D iotas, so the fresh K and the query never round-trip HBM between
    rotation and attention.  On int8 pools (``quantized``) the pages
    carry per-row scales ``(Hkv, P)``: the K scale row multiplies the
    scores and the V scale row the probabilities (``q.(k*s) ==
    (q.k)*s``), and the fresh row quantizes in-kernel."""
    if quantized:
        (ks_ref, vs_ref, o_ref, ko_ref, vo_ref, kso_ref, vso_ref,
         acc_ref, m_ref, l_ref, qr_ref, kf_ref, vf_ref,
         ksc_ref, vsc_ref) = rest
    else:
        (o_ref, ko_ref, vo_ref, acc_ref, m_ref, l_ref, qr_ref, kf_ref,
         vf_ref) = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[b]
    jt = jnp.minimum(pos // page, nb - 1)
    row_t = pos % page

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        d = q_ref.shape[-1]
        half = d // 2
        # RoPE tables, full-D layout: column c rotates at frequency
        # theta^(-2*(c % half)/d) — the ref's inv_freq duplicated per half.
        col = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
        inv = jnp.exp((col % half).astype(jnp.float32)
                      * jnp.float32(-2.0 * math.log(theta) / d))
        ang = pos.astype(jnp.float32) * inv
        cosf, sinf = jnp.cos(ang), jnp.sin(ang)
        # rotate-half as a matmul: R[c+half, c] = -1, R[c-half, c] = +1
        rr = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
        rc = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
        rot = jnp.where(rr == rc + half, -1.0, 0.0) \
            + jnp.where(rr + half == rc, 1.0, 0.0)

        def rope(x):
            return x * cosf + jax.lax.dot_general(
                x, rot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sinf

        for h in range(hk):
            qr_ref[h] = rope(q_ref[0, h].astype(jnp.float32))    # (G, D)
        knr = rope(kn_ref[0].astype(jnp.float32))                # (Hkv, D)
        vn = vn_ref[0].astype(jnp.float32)
        if quantized:
            def quant(x, sc_ref):
                amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
                sc = jnp.where(amax > 0.0, amax / 127.0, 1.0)    # (Hkv, 1)
                sc_ref[...] = sc
                # integer-valued f32; the cache stores it as int8
                return jnp.round(x / sc)
            kf_ref[...] = quant(knr, ksc_ref)
            vf_ref[...] = quant(vn, vsc_ref)
        else:
            cdt = kp_ref.dtype
            kf_ref[...] = knr.astype(cdt).astype(jnp.float32)
            vf_ref[...] = vn.astype(cdt).astype(jnp.float32)

    fresh = (j == jt) & (jax.lax.broadcasted_iota(
        jnp.int32, (page, 1), 0) == row_t)               # (P, 1)
    fresh_row = (j == jt) & (jax.lax.broadcasted_iota(
        jnp.int32, (1, page), 1) == row_t)               # (1, P)
    if quantized:
        ks_all = jnp.where(fresh_row, ksc_ref[...], ks_ref[0])  # (Hkv, P)
        vs_all = jnp.where(fresh_row, vsc_ref[...], vs_ref[0])

    def page_rows(h):
        k = jnp.where(fresh, kf_ref[pl.ds(h, 1), :],
                      kp_ref[0, h].astype(jnp.float32))          # (P, D)
        v = jnp.where(fresh, vf_ref[pl.ds(h, 1), :],
                      vp_ref[0, h].astype(jnp.float32))
        return k, v

    @pl.when(j <= jt)
    def _page():
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        ok = kpos <= pos          # length mask at pos+1 (fresh row included)
        for h in range(hk):
            k, v = page_rows(h)
            s = _scores(qr_ref[h], k, scale, softcap)
            if quantized:
                s = s * ks_all[h:h + 1]
            s = jnp.where(ok, s, NEG_INF)
            if quantized:
                # the V scales weight the probabilities; the running sum
                # l must still see the unscaled p
                m_prev = m_ref[h]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1,
                                                    keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p * vs_all[h:h + 1], v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new
            else:
                _online_softmax_step(s, v, acc_ref.at[h], m_ref.at[h],
                                     l_ref.at[h])

    @pl.when(j == jt)
    def _write():
        for h in range(hk):
            k, v = page_rows(h)
            ko_ref[0, h] = k.astype(ko_ref.dtype)
            vo_ref[0, h] = v.astype(vo_ref.dtype)
        if quantized:
            kso_ref[0] = ks_all
            vso_ref[0] = vs_all

    @pl.when(j == nb - 1)
    def _done():
        for h in range(hk):
            o_ref[0, h] = _finish(acc_ref.at[h], l_ref.at[h]).astype(
                o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("theta", "softcap", "interpret"))
def fused_paged_decode_grouped(q, k_new, v_new, k_pages, v_pages,
                               block_tables, positions, *, theta,
                               softcap=0.0, k_scales=None, v_scales=None,
                               interpret=False):
    """Fused RoPE + page-write + decode attention over the paged pool.

    q: (B, Hkv, G, D) un-roped; k_new/v_new: (B, Hkv, D) un-roped fresh
    K/V; k_pages/v_pages: (N, Hkv, P, D); block_tables: (B, NB) int32
    (in-range); positions: (B,) int32 write position per slot.
    k_scales/v_scales: (N, Hkv, P) f32 on int8 pools (None on fp).
    Returns (out (B, Hkv, G, D), k_pages, v_pages, k_scales, v_scales) —
    the pool buffers are aliased in/out, so callers MUST rebind them.
    """
    b, hk, g, d = q.shape
    page = k_pages.shape[2]
    nb = block_tables.shape[1]
    quantized = k_scales is not None

    def page_out(b_, j, bt, ps):
        return (bt[b_, jnp.minimum(ps[b_] // page, nb - 1)], 0, 0, 0)

    def page_in(b_, j, bt, ps):
        jt = jnp.minimum(ps[b_] // page, nb - 1)
        return (bt[b_, jnp.minimum(j, jt)], 0, 0, 0)

    def scale_in(b_, j, bt, ps):
        return page_in(b_, j, bt, ps)[:3]

    def scale_out(b_, j, bt, ps):
        return page_out(b_, j, bt, ps)[:3]

    qspec = pl.BlockSpec((1, hk, g, d), lambda b_, j, bt, ps: (b_, 0, 0, 0))
    fresh = pl.BlockSpec((1, hk, d), lambda b_, j, bt, ps: (b_, 0, 0))
    in_specs = [qspec, fresh, fresh,
                pl.BlockSpec((1, hk, page, d), page_in),
                pl.BlockSpec((1, hk, page, d), page_in)]
    out_specs = [qspec,
                 pl.BlockSpec((1, hk, page, d), page_out),
                 pl.BlockSpec((1, hk, page, d), page_out)]
    everything = (q, k_new, v_new, k_pages, v_pages, block_tables,
                  positions, k_scales, v_scales)
    out_shape = [
        compat.kernel_out((b, hk, g, d), q.dtype, *everything),
        compat.kernel_out(k_pages.shape, k_pages.dtype, *everything),
        compat.kernel_out(v_pages.shape, v_pages.dtype, *everything),
    ]
    # alias indices count the two scalar-prefetch operands: inputs are
    # (bt, positions, q, k_new, v_new, k_pages, v_pages[, ks, vs])
    aliases = {5: 1, 6: 2}
    operands = [q, k_new, v_new, k_pages, v_pages]
    scratch = [
        compat.vmem_scratch((hk, g, d), jnp.float32),   # acc
        compat.vmem_scratch((hk, g, 1), jnp.float32),   # m
        compat.vmem_scratch((hk, g, 1), jnp.float32),   # l
        compat.vmem_scratch((hk, g, d), jnp.float32),   # roped q
        compat.vmem_scratch((hk, d), jnp.float32),      # fresh k row
        compat.vmem_scratch((hk, d), jnp.float32),      # fresh v row
    ]
    if quantized:
        in_specs += [pl.BlockSpec((1, hk, page), scale_in)] * 2
        out_specs += [pl.BlockSpec((1, hk, page), scale_out)] * 2
        out_shape += [
            compat.kernel_out(k_scales.shape, k_scales.dtype, *everything),
            compat.kernel_out(v_scales.shape, v_scales.dtype, *everything)]
        aliases.update({7: 3, 8: 4})
        operands += [k_scales, v_scales]
        scratch += [compat.vmem_scratch((hk, 1), jnp.float32)] * 2

    grid_spec = compat.prefetch_grid_spec(
        num_scalar_prefetch=2,           # block tables + positions
        grid=(b, nb), in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch)
    kernel = functools.partial(_fused_decode_kernel,
                               scale=1.0 / math.sqrt(d), softcap=softcap,
                               theta=theta, page=page, nb=nb, hk=hk,
                               quantized=quantized)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        name="fused_paged_decode_grouped",
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), positions.astype(jnp.int32),
      *operands)
    if quantized:
        return outs
    out, kp, vp = outs
    return out, kp, vp, None, None


# ---------------------------------------------------------------------------
# suffix / chunk prefill (causal)
# ---------------------------------------------------------------------------

def _paged_prefill_kernel(bt_ref, off_ref, q_ref, k_ref, v_ref,   # ins
                          o_ref,                                  # outs
                          acc_ref, m_ref, l_ref,                  # scratch
                          *, scale, softcap, page, nb, g, tq):
    qi = pl.program_id(2)
    j = pl.program_id(3)
    q0 = off_ref[0] + qi * tq          # position of the tile's first row

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # rows of logical page j hold positions j*P + i; row r of the flat
    # query block sits at position q0 + (r % tq).  Pure causal mask: a
    # suffix query attends the whole reused prefix plus itself.  Pages
    # wholly after the tile's last query are skipped.
    @pl.when(j * page <= q0 + tq - 1)
    def _page():
        # (G, tq, D) -> (G*tq, D): one query row per (group, position)
        # pair so the tile runs as a single MXU matmul per page.
        d = q_ref.shape[-1]
        q = q_ref[0, 0].astype(jnp.float32).reshape(g * tq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (P, D)
        v = v_ref[0, 0].astype(jnp.float32)
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        qpos = q0 + (jax.lax.broadcasted_iota(jnp.int32, (g * tq, 1), 0)
                     % tq)
        s = jnp.where(pos <= qpos, _scores(q, k, scale, softcap), NEG_INF)
        _online_softmax_step(s, v, acc_ref, m_ref, l_ref)

    @pl.when(j == nb - 1)
    def _done():
        d = o_ref.shape[-1]
        o_ref[0, 0] = _finish(acc_ref, l_ref).astype(o_ref.dtype).reshape(
            g, tq, d)


def prefill_tile(s: int) -> int:
    """Query rows per prefill tile for an ``s``-token chunk: the chunk
    itself rounded up to the sublane multiple of 8, capped at
    ``PREFILL_TQ`` (the chunk is padded up to a whole number of tiles)."""
    return min(PREFILL_TQ, -(-s // 8) * 8)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_prefill_attention_grouped(q, k_pages, v_pages, block_tables,
                                    offset, *, softcap=0.0,
                                    interpret=False):
    """Suffix/chunked prefill over the paged pool: q (B, Hkv, G, S, D)
    holds S fresh tokens at positions offset..offset+S-1 (K/V already
    written into the pool); block_tables (B, NB) int32 (in-range) maps
    every logical block — shared prefix and fresh suffix; offset () int32.
    Returns (B, Hkv, G, S, D).  Same page-sequential online-softmax walk
    as decode, with the causal mask replacing the length mask, over query
    tiles of ``prefill_tile(S)`` rows (pad rows are computed and
    dropped)."""
    b, hk, g, s, d = q.shape
    page = k_pages.shape[2]
    nb = block_tables.shape[1]
    tq = prefill_tile(s)
    nq = -(-s // tq)
    if nq * tq != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, nq * tq - s), (0, 0)))

    def page_in(b_, h_, qi, j, bt, off):
        last = (off[0] + qi * tq + tq - 1) // page
        return (bt[b_, jnp.minimum(j, last)], h_, 0, 0)

    qspec = pl.BlockSpec((1, 1, g, tq, d),
                         lambda b_, h_, qi, j, bt, off: (b_, h_, 0, qi, 0))
    grid_spec = compat.prefetch_grid_spec(
        num_scalar_prefetch=2,           # block tables + offset
        grid=(b, hk, nq, nb),
        in_specs=[qspec, pl.BlockSpec((1, 1, page, d), page_in),
                  pl.BlockSpec((1, 1, page, d), page_in)],
        out_specs=qspec,
        scratch_shapes=[
            compat.vmem_scratch((g * tq, d), jnp.float32),
            compat.vmem_scratch((g * tq, 1), jnp.float32),
            compat.vmem_scratch((g * tq, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_prefill_kernel,
                               scale=1.0 / math.sqrt(d), softcap=softcap,
                               page=page, nb=nb, g=g, tq=tq)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=compat.kernel_out((b, hk, g, nq * tq, d), q.dtype, q,
                                    k_pages, v_pages, offset),
        name="paged_prefill_attention_grouped",
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32),
      jnp.asarray(offset, jnp.int32).reshape(1),
      q, k_pages, v_pages)
    return out[:, :, :, :s]
