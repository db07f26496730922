"""Paged attention Pallas TPU kernels: decode, fused decode, chunk prefill.

Attention where K/V live in a physical block pool addressed through
per-slot block tables, instead of one dense ``(slots, max_seq, ...)``
reservation.  The block table is a **scalar-prefetch** operand, so the
gather reads the j-th *logical* page of slot ``b`` (``bt[b, j]``)
straight from wherever it physically sits — it never materializes a
contiguous K/V copy in HBM, which is the whole point (the SSR spatial
story applied to memory: decode replicas stay busy because their KV
footprint is live-token-sized).

Layout (head-major pages): pool ``(N, Hkv, P, D)`` — every (page, kv
head) is one contiguous ``(P, D)`` tile, so each block's last two
dimensions are ``(page, head_dim)`` as the TPU compiler requires; int8
pools carry f32 scales ``(N, Hkv, P)``; q ``(B, Hkv, G, D)`` (query heads
grouped per kv head — GQA runs as one MXU matmul per kv head); block
tables ``(B, NB)`` int32 (host pre-clips unmapped entries into range —
rows past the length are masked anyway).

The unfused decode grid is ``(B, NB)``: one step takes one logical page
of one slot for ALL kv heads through the grid's ``index_map``.  The
fused decode grid is ``(B,)``: the pools stay in HBM and each slot walks
only its live pages, ``128 / P`` pages a block (``decode_pages_per_block``),
gathered by DMA into a double buffer while the previous block computes;
a block is one scores matmul and one online-softmax update per kv head.
The prefill grid is ``(B, Hkv, NQ, NB)`` over query tiles of at most
``PREFILL_TQ`` rows, so its VMEM footprint does not grow with the chunk
length.  The page dimension is sequential ("arbitrary"): online-softmax
(m, l, acc) statistics carry across pages in VMEM scratch, exactly like
``flash_attention.py`` carries them across KV blocks.  In the grid
kernels, pages past a slot's last valid position keep the previous block
index (no DMA) and skip their compute; they would contribute exact zeros
anyway.
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

DECODE_BLOCK_TOKENS = 128   # tokens per fused-decode block (one matmul)
DECODE_KV_VMEM = 4 << 20    # bytes the double-buffered K/V blocks may take


def decode_pages_per_block(page, hk, d, itemsize):
    """Pages per block of the fused decode walk: ``DECODE_BLOCK_TOKENS``
    tokens (8 pages of 16, 4 of int8's 32), at least one page, and few
    enough that K and V, double-buffered, fit ``DECODE_KV_VMEM``."""
    fit = DECODE_KV_VMEM // (4 * hk * page * d * itemsize)
    return max(1, min(DECODE_BLOCK_TOKENS // page, fit))


def _fused_decode_kernel(bt_ref, pos_ref, q_ref, kn_ref, vn_ref,
                         kp_hbm, vp_hbm, *rest,
                         scale, softcap, theta, page, nb, ppb, hk,
                         quantized):
    """RoPE + page-write + decode attention for one slot.

    Scalar-prefetch: block tables (B, NB) + write positions (B,).  The
    pools stay in HBM; a ``fori_loop`` walks the slot's live blocks only
    (``ppb`` logical pages each, the last one cut at the write page),
    gathering each page of every kv head with one DMA per pool into a
    double buffer, so block i+1 is in flight while block i computes.
    Each block is one scores matmul and one online-softmax update per kv
    head.  Rows past the write position are masked (and V zeroed there:
    the tail of the last block is stale VMEM).

    RoPE runs once per slot, before the walk, as ``x * cos + (x @ R) *
    sin`` with the rotate-half matrix R built from 2D iotas, so the fresh
    K and the query never round-trip HBM between rotation and attention.
    The fresh row substitutes for its pool row in attention, and its page
    (``bt[b, pos // page]``, the last live page, already in VMEM) leaves
    through the OUTPUT pool block, which aliases the input pool
    (``input_output_aliases``) — every other page stays untouched.  On
    int8 pools (``quantized``) the pages carry per-row scales ``(Hkv,
    P)``, which arrive as the slot's gathered ``(NB, Hkv, P)`` block: the
    K scale row multiplies the scores and the V scale row the
    probabilities (``q.(k*s) == (q.k)*s``), and the fresh row quantizes
    in-kernel.  The raw scale pools are only aliased, never read."""
    if quantized:
        (_, _, ks_ref, vs_ref, o_ref, ko_ref, vo_ref, kso_ref, vso_ref,
         kbuf, vbuf, sem, acc_ref, m_ref, l_ref, qr_ref, kf_ref, vf_ref,
         ksc_ref, vsc_ref) = rest
    else:
        (o_ref, ko_ref, vo_ref, kbuf, vbuf, sem, acc_ref, m_ref, l_ref,
         qr_ref, kf_ref, vf_ref) = rest
    pools = ((kp_hbm, kbuf), (vp_hbm, vbuf))
    b = pl.program_id(0)
    pos = pos_ref[b]
    jt = jnp.minimum(pos // page, nb - 1)      # write page = last live page
    row_t = pos % page
    fpos = jt * page + row_t                   # the fresh row's key slot
    nblk = jt // ppb + 1
    tk = ppb * page
    d = q_ref.shape[-1]

    def fetch(i, slot, start):
        # one DMA per live page of block i and pool; a wait rebuilds the
        # descriptor its start issued
        for p in range(ppb):
            @pl.when(i * ppb + p <= jt)
            def _page():
                phys = bt_ref[b, i * ppb + p]
                for t, (src, buf) in enumerate(pools):
                    cp = compat.async_copy(src.at[phys], buf.at[slot, p],
                                           sem.at[t, slot])
                    if start:
                        cp.start()
                    else:
                        cp.wait()

    fetch(0, 0, True)                          # in flight during RoPE

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    half = d // 2
    # RoPE tables, full-D layout: column c rotates at frequency
    # 1 / theta^(2*(c % half)/d) — the ref's inv_freq, op for op,
    # duplicated per half.
    col = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    inv = 1.0 / (jnp.float32(theta)
                 ** ((2 * (col % half)).astype(jnp.float32) / d))
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
        qr_ref[h] = rope(q_ref[0, h].astype(jnp.float32))        # (G, D)
    knr = rope(kn_ref[0].astype(jnp.float32))                    # (Hkv, D)
    vn = vn_ref[0].astype(jnp.float32)
    if quantized:
        def quant(x, sc_ref):
            amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
            sc = jnp.where(amax > 0.0, amax / 127.0, 1.0)        # (Hkv, 1)
            sc_ref[...] = sc
            # integer-valued f32; the cache stores it as int8
            return jnp.round(x / sc)
        kf_ref[...] = quant(knr, ksc_ref)
        vf_ref[...] = quant(vn, vsc_ref)
    else:
        cdt = kbuf.dtype
        kf_ref[...] = knr.astype(cdt).astype(jnp.float32)
        vf_ref[...] = vn.astype(cdt).astype(jnp.float32)

    def scale_row(s_ref, i, h, fresh_row, sc_ref):
        # block i's scales of kv head h as one (1, T) row, the fresh
        # row's scale substituted
        r = jnp.concatenate(
            [s_ref[0, jnp.minimum(i * ppb + p, nb - 1), pl.ds(h, 1), :]
             for p in range(ppb)], axis=1)
        return jnp.where(fresh_row, sc_ref[pl.ds(h, 1), :], r)

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < nblk)
        def _prefetch():
            fetch(i + 1, 1 - slot, True)

        fetch(i, slot, False)
        kcol = i * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
        krow = i * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        fresh = kcol == fpos                               # (T, 1)
        ok = krow <= pos          # length mask at pos+1 (fresh row included)
        for h in range(hk):
            k = kbuf[slot, :, h].astype(jnp.float32).reshape(tk, d)
            v = vbuf[slot, :, h].astype(jnp.float32).reshape(tk, d)
            k = jnp.where(fresh, kf_ref[pl.ds(h, 1), :], k)
            v = jnp.where(fresh, vf_ref[pl.ds(h, 1), :],
                          jnp.where(kcol <= pos, v, 0.0))
            s = _scores(qr_ref[h], k, scale, softcap)
            if quantized:
                s = s * scale_row(ks_ref, i, h, krow == fpos, ksc_ref)
            s = jnp.where(ok, s, NEG_INF)
            if quantized:
                # the V scales weight the probabilities; the running sum
                # l must still see the unscaled p
                vs = jnp.where(ok, scale_row(vs_ref, i, h, krow == fpos,
                                             vsc_ref), 0.0)
                m_prev = m_ref[h]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1,
                                                    keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p * vs, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new
            else:
                _online_softmax_step(s, v, acc_ref.at[h], m_ref.at[h],
                                     l_ref.at[h])
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)

    # the write page: the last block's buffer, fresh row substituted
    last = (nblk - 1) % 2
    pj = jt - (nblk - 1) * ppb
    wr = jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0) == row_t
    for h in range(hk):
        ko_ref[0, h] = jnp.where(
            wr, kf_ref[pl.ds(h, 1), :],
            kbuf[last, pj, h].astype(jnp.float32)).astype(ko_ref.dtype)
        vo_ref[0, h] = jnp.where(
            wr, vf_ref[pl.ds(h, 1), :],
            vbuf[last, pj, h].astype(jnp.float32)).astype(vo_ref.dtype)
    if quantized:
        wc = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1) == row_t
        kso_ref[0] = jnp.where(wc, ksc_ref[...], ks_ref[0, jt])
        vso_ref[0] = jnp.where(wc, vsc_ref[...], vs_ref[0, jt])

    for h in range(hk):
        o_ref[0, h] = _finish(acc_ref.at[h], l_ref.at[h]).astype(o_ref.dtype)


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
    The grid is one step per slot; each walks ``ceil((pos + 1) / (P *
    ppb))`` blocks of ``ppb = decode_pages_per_block(...)`` pages.
    """
    b, hk, g, d = q.shape
    page = k_pages.shape[2]
    nb = block_tables.shape[1]
    block_tables = block_tables.astype(jnp.int32)
    quantized = k_scales is not None
    ppb = decode_pages_per_block(page, hk, d, k_pages.dtype.itemsize)

    def write_page(b_, bt, ps):
        return (bt[b_, jnp.minimum(ps[b_] // page, nb - 1)], 0, 0, 0)

    qspec = pl.BlockSpec((1, hk, g, d), lambda b_, bt, ps: (b_, 0, 0, 0))
    fresh = pl.BlockSpec((1, hk, d), lambda b_, bt, ps: (b_, 0, 0))
    page_out = pl.BlockSpec((1, hk, page, d), write_page)
    in_specs = [qspec, fresh, fresh, compat.hbm_spec(), compat.hbm_spec()]
    out_specs = [qspec, page_out, page_out]
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
    buffers = [compat.vmem_scratch((2, ppb, hk, page, d), k_pages.dtype),
               compat.vmem_scratch((2, ppb, hk, page, d), v_pages.dtype)]
    scratch = [
        compat.vmem_scratch((hk, g, d), jnp.float32),   # acc
        compat.vmem_scratch((hk, g, 1), jnp.float32),   # m
        compat.vmem_scratch((hk, g, 1), jnp.float32),   # l
        compat.vmem_scratch((hk, g, d), jnp.float32),   # roped q
        compat.vmem_scratch((hk, d), jnp.float32),      # fresh k row
        compat.vmem_scratch((hk, d), jnp.float32),      # fresh v row
    ]
    if quantized:
        scale_out = pl.BlockSpec((1, hk, page),
                                 lambda b_, bt, ps: write_page(b_, bt,
                                                               ps)[:3])
        # the scale pools pass through (aliased; only the write page's
        # block is written); the slot's scale rows, gathered here, ride
        # in as one block per slot: a (Hkv, P) f32 page is narrower than
        # a lane tile, so the compiler refuses a per-page DMA of it
        slot_scales = pl.BlockSpec((1, nb, hk, page),
                                   lambda b_, bt, ps: (b_, 0, 0, 0))
        in_specs += [compat.hbm_spec()] * 2 + [slot_scales] * 2
        out_specs += [scale_out] * 2
        out_shape += [
            compat.kernel_out(k_scales.shape, k_scales.dtype, *everything),
            compat.kernel_out(v_scales.shape, v_scales.dtype, *everything)]
        aliases.update({7: 3, 8: 4})
        operands += [k_scales, v_scales, k_scales[block_tables],
                     v_scales[block_tables]]
        scratch += [compat.vmem_scratch((hk, 1), jnp.float32)] * 2
    buffers.append(compat.dma_semaphores((len(buffers), 2)))

    grid_spec = compat.prefetch_grid_spec(
        num_scalar_prefetch=2,           # block tables + positions
        grid=(b,), in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=buffers + scratch)
    kernel = functools.partial(_fused_decode_kernel,
                               scale=1.0 / math.sqrt(d), softcap=softcap,
                               theta=theta, page=page, nb=nb, ppb=ppb,
                               hk=hk, quantized=quantized)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        name="fused_paged_decode_grouped",
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(block_tables, positions.astype(jnp.int32), *operands)
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
