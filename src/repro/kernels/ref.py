"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
used by the per-kernel sweep tests and by the CPU execution path)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_rope_ref(x, positions, theta):
    """Standard (non-M) RoPE, op-for-op the model layer's math.

    x: (B, S, H, D); positions: (B, S).  This mirrors
    ``layers.apply_rope``'s no-mrope branch exactly — same op order, same
    f32 casts — so the fused decode path that ropes inside the kernel can
    be pinned bit-identical to the layer-side rotation on fp paths.
    """
    b, s, h, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[..., None] * inv   # (B,S,d/2)
    cos = jnp.cos(angles)[:, :, None, :]                      # (B,S,1,d/2)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def quantize_int8_rows(x):
    """Symmetric per-row int8 quantization over the last axis.

    x: (..., D) -> (q int8 (..., D), scale f32 (...,)).  Zero rows get
    scale 1 so dequant is exact (all-zero stays all-zero)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.round(xf / scale[..., None]).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    """Inverse of ``quantize_int8_rows``: (..., D) int8 + (...,) f32."""
    return q.astype(jnp.float32) * scale[..., None]


def _gather_pages(k_pages, v_pages, bt, k_scales, v_scales, b, nb, p, hk, d):
    """Gather (and dequantize, when scales are given) head-major pool
    pages ``(N, Hkv, P, D)`` through clipped block tables into
    logical-ordered (B, NB*P, Hkv, D) f32."""
    def rows(x):          # (B, NB, Hkv, P, ...) -> (B, NB*P, Hkv, ...)
        x = jnp.swapaxes(x, 2, 3)
        return x.reshape((b, nb * p, hk) + x.shape[4:])
    k = rows(k_pages[bt]).astype(jnp.float32)
    v = rows(v_pages[bt]).astype(jnp.float32)
    if k_scales is not None:
        k = k * rows(k_scales[bt])[..., None]
        v = v * rows(v_scales[bt])[..., None]
    return k, v


def flash_attention_ref(q, k, v, q_pos, k_pos, k_valid, *, causal=True,
                        window=0, softcap=0.0):
    """q: (B,H,Sq,D), k/v: (B,Hkv,Skv,D) -> (B,H,Sq,D).  Plain softmax."""
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    groups = h // hkv
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    rel = q_pos[:, None] - k_pos[None, :]
    ok = k_valid[None, :] > 0
    if causal:
        ok &= rel >= 0
    if window > 0:
        ok &= rel < window
    s = jnp.where(ok, s, NEG_INF)
    # guard fully-masked rows like the kernel (output 0, not nan)
    any_ok = jnp.any(ok, axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    out = jnp.where(any_ok[None, None, :, None], out, 0.0)
    return out.astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                        softcap=0.0, k_scales=None, v_scales=None):
    """Decode attention over a paged KV cache, pure jnp.

    q: (B, Hkv, G, D) — one query token per slot, q heads grouped per kv
    head; k_pages/v_pages: (N, Hkv, P, D) physical block pool;
    block_tables: (B, NB) int32 logical->physical map (entries >= N are
    unmapped: clipped to a garbage page and masked); lengths: (B,) valid
    tokens per slot (the query sits at position lengths-1).
    k_scales/v_scales: (N, Hkv, P) f32 per-row dequant scales when the
    pool holds int8 blocks (None on fp pools).
    Returns (B, Hkv, G, D).

    The gathered layout is logical-ordered, so key position == gather row
    and the length mask reproduces exactly the dense path's masking: the
    valid keys are summed in the same order with the same f32 softmax, so
    paged decode is bit-identical to dense decode.
    """
    b, hk, g, d = q.shape
    n, _, p, _ = k_pages.shape
    nb = block_tables.shape[1]
    dt = q.dtype
    bt = jnp.clip(block_tables, 0, n - 1)
    k, v = _gather_pages(k_pages, v_pages, bt, k_scales, v_scales,
                         b, nb, p, hk, d)             # (B, T, Hkv, D)
    s = jnp.einsum("bhgd,bthd->bhgt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    ok = jnp.arange(nb * p)[None, :] < lengths[:, None]   # (B, T)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgt,bthd->bhgd", probs.astype(dt), v.astype(dt))
    return out.astype(dt)


def fused_paged_decode_ref(q, k_new, v_new, k_pages, v_pages, block_tables,
                           positions, *, theta, softcap=0.0,
                           k_scales=None, v_scales=None):
    """RoPE + page-write + decode attention in one step, pure jnp — the
    oracle for the fused Pallas decode kernel.

    q: (B, Hkv, G, D) UN-roped grouped queries; k_new/v_new: (B, Hkv, D)
    the slot's un-roped fresh K/V projection; k_pages/v_pages:
    (N, Hkv, P, D) pool; block_tables: (B, NB) int32 (in-range — the
    manager's sentinel rows point at the sink page); positions: (B,)
    int32 write position per slot (= tokens already cached).
    k_scales/v_scales: (N, Hkv, P) f32 on int8 pools (None on fp).

    Composes exactly the unfused model-layer sequence: rope q and k_new
    at ``positions`` (``decode_rope_ref`` — bit-identical to
    ``layers.apply_rope``), scatter the fresh row into its page
    (quantizing on int8 pools), then run ``paged_attention_ref`` at
    ``lengths = positions + 1``.  Returns
    (out (B, Hkv, G, D), k_pages, v_pages, k_scales, v_scales).
    """
    b, hk, g, d = q.shape
    n, page = k_pages.shape[0], k_pages.shape[2]
    nb = block_tables.shape[1]
    cdt = k_pages.dtype
    pos_bs = positions[:, None]                       # (B, 1)
    qr = decode_rope_ref(q.reshape(b, 1, hk * g, d), pos_bs,
                         theta).reshape(b, hk, g, d)
    kr = decode_rope_ref(k_new[:, None], pos_bs, theta)[:, 0]  # (B,Hkv,D)

    blk = jnp.clip(positions // page, 0, nb - 1)
    pages = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    rows = positions % page
    if k_scales is not None:
        kq, ks = quantize_int8_rows(kr)
        vq, vs = quantize_int8_rows(v_new)
        k_pages = k_pages.at[pages, :, rows].set(kq, mode="drop")
        v_pages = v_pages.at[pages, :, rows].set(vq, mode="drop")
        k_scales = k_scales.at[pages, :, rows].set(ks, mode="drop")
        v_scales = v_scales.at[pages, :, rows].set(vs, mode="drop")
    else:
        k_pages = k_pages.at[pages, :, rows].set(kr.astype(cdt),
                                                 mode="drop")
        v_pages = v_pages.at[pages, :, rows].set(v_new.astype(cdt),
                                                 mode="drop")
    out = paged_attention_ref(qr, k_pages, v_pages, block_tables,
                              positions + 1, softcap=softcap,
                              k_scales=k_scales, v_scales=v_scales)
    return out, k_pages, v_pages, k_scales, v_scales


def paged_prefill_attention_ref(q, k_pages, v_pages, block_tables, offset,
                                *, softcap=0.0, k_scales=None,
                                v_scales=None):
    """Suffix/chunked prefill attention over a paged KV cache, pure jnp.

    q: (B, Hkv, G, S, D) — S fresh query tokens per slot sitting at
    positions ``offset .. offset+S-1``, q heads grouped per kv head;
    k_pages/v_pages: (N, Hkv, P, D) physical block pool (the fresh
    chunk's K/V are already written in); block_tables: (B, NB) int32
    logical->physical map over *all* mapped blocks — shared prefix and
    fresh suffix alike (entries >= N are unmapped: clipped to a garbage
    page and causally masked); offset: () int32 position of the first
    fresh query.  Returns (B, Hkv, G, S, D).

    The mask is purely causal (``kpos <= qpos``): a suffix query attends
    every earlier cached position — the reused prefix — plus the fresh
    chunk up to itself.  The gathered layout is logical-ordered, so key
    position == gather row and the valid keys reduce in the same order
    with the same f32 softmax as the dense path; masked rows contribute
    exact zeros, so suffix-only prefill decodes token-identically to a
    cold full prefill.
    """
    b, hk, g, s, d = q.shape
    n, _, p, _ = k_pages.shape
    nb = block_tables.shape[1]
    dt = q.dtype
    bt = jnp.clip(block_tables, 0, n - 1)
    k, v = _gather_pages(k_pages, v_pages, bt, k_scales, v_scales,
                         b, nb, p, hk, d)             # (B, T, Hkv, D)
    sc = jnp.einsum("bhgsd,bthd->bhgst", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / math.sqrt(d)
    if softcap > 0:
        sc = softcap * jnp.tanh(sc / softcap)
    qpos = offset + jnp.arange(s)                     # (S,)
    kpos = jnp.arange(nb * p)                         # (T,)
    ok = kpos[None, :] <= qpos[:, None]               # (S, T)
    sc = jnp.where(ok[None, None, None, :, :], sc, NEG_INF)
    probs = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgst,bthd->bhgsd", probs.astype(dt), v.astype(dt))
    return out.astype(dt)


def paged_verify_attention_ref(q, k_pages, v_pages, block_tables, offset,
                               *, softcap=0.0, k_scales=None,
                               v_scales=None):
    """Speculative-verify attention over a paged KV cache, pure jnp.

    Identical to ``paged_prefill_attention_ref`` except ``offset`` is a
    PER-SLOT ``(B,)`` vector: each slot's S-token verify window (current
    token + K drafted tokens) sits at its own positions
    ``offset[b] .. offset[b]+S-1`` — slots verify at different depths in
    one batched step, exactly as the decode path's per-slot position
    vector allows.

    q: (B, Hkv, G, S, D); k_pages/v_pages: (N, Hkv, P, D) with the
    window's K/V already written in; block_tables: (B, NB);
    offset: (B,) int32.  Returns (B, Hkv, G, S, D).

    Same logical-ordered gather, causal mask, and f32 softmax as the
    other paged refs — so greedy verification scores each window
    position exactly as a sequential one-token decode would, which is
    what keeps accepted speculative tokens bit-identical to the one-shot
    greedy stream.
    """
    b, hk, g, s, d = q.shape
    n, _, p, _ = k_pages.shape
    nb = block_tables.shape[1]
    dt = q.dtype
    bt = jnp.clip(block_tables, 0, n - 1)
    k, v = _gather_pages(k_pages, v_pages, bt, k_scales, v_scales,
                         b, nb, p, hk, d)             # (B, T, Hkv, D)
    sc = jnp.einsum("bhgsd,bthd->bhgst", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / math.sqrt(d)
    if softcap > 0:
        sc = softcap * jnp.tanh(sc / softcap)
    qpos = offset[:, None] + jnp.arange(s)[None, :]   # (B, S)
    kpos = jnp.arange(nb * p)                         # (T,)
    ok = kpos[None, None, :] <= qpos[:, :, None]      # (B, S, T)
    sc = jnp.where(ok[:, None, None, :, :], sc, NEG_INF)
    probs = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgst,bthd->bhgsd", probs.astype(dt), v.astype(dt))
    return out.astype(dt)


def matmul_fused_ref(x, w, bias=None, *, activation="none", out_dtype=None):
    acc = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if activation == "gelu":
        acc = jax.nn.gelu(acc, approximate=True)
    elif activation == "silu":
        acc = jax.nn.silu(acc)
    elif activation == "relu2":
        acc = jnp.square(jnp.maximum(acc, 0.0))
    return acc.astype(out_dtype or x.dtype)


def norm_onepass_ref(x, scale, bias=None, *, kind="rmsnorm", eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * scale.astype(jnp.float32) + (0 if bias is None
                                             else bias.astype(jnp.float32))
    else:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


def linear_scan_ref(a, b, h0=None):
    """a, b: (N, S, F) -> all states (N, S, F) via a plain sequential scan."""
    n, s, f = a.shape
    if h0 is None:
        h0 = jnp.zeros((n, f), jnp.float32)

    def step(h, ab):
        a_t, b_t = ab
        h = a_t * h + b_t
        return h, h

    a_t = jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    b_t = jnp.moveaxis(b.astype(jnp.float32), 1, 0)
    _, hs = jax.lax.scan(step, h0.astype(jnp.float32), (a_t, b_t))
    return jnp.moveaxis(hs, 0, 1).astype(a.dtype)
