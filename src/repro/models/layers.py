"""Core transformer layer primitives (pure JAX, functional).

Conventions:
  * params are nested dicts of jnp arrays;
  * activations ``x`` are (batch, seq, d_model);
  * all matmuls accumulate in fp32 (``preferred_element_type``);
  * attention softmax in fp32 (this is also what the Pallas flash kernel
    does — see ``repro.kernels``).

The SSR "fine-grained pipeline" for nonlinear ops appears here as the
*dispatch point*: ``attention``/``rmsnorm`` route through the kernel
dispatch front door (``repro.backend.dispatch``) to the fused Pallas
kernels on TPU and to the jnp reference elsewhere.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, MoEConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / math.sqrt(max(in_axis_size, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: Optional[int] = None):
    dim = dim or cfg.d_model
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if cfg.norm_kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def apply_norm(p, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm (gemma-style 1+scale for gemma configs is folded into init)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float, mrope_sections=()):
    """x: (B, S, H, D); positions: (B, S) or (3, B, S) for M-RoPE."""
    b, s, h, d = x.shape
    inv = rope_freqs(d, theta)                      # (d/2,)
    if mrope_sections:
        # M-RoPE: frequency bands split into (temporal, h, w) sections, each
        # rotated by its own position stream.  positions: (3, B, S).
        assert positions.ndim == 3, "M-RoPE needs (3, B, S) positions"
        sec = jnp.concatenate([
            jnp.full((n,), i, jnp.int32)
            for i, n in enumerate(mrope_sections)])  # (d/2,)
        pos = positions.astype(jnp.float32)          # (3, B, S)
        # pick per-frequency position stream: (B, S, d/2)
        pos_per_freq = jnp.take(pos, sec, axis=0)    # (d/2, B, S)
        angles = jnp.einsum("fbs,f->bsf", pos_per_freq, inv)
    else:
        if positions.ndim == 3:
            positions = positions[0]
        angles = positions.astype(jnp.float32)[..., None] * inv  # (B,S,d/2)
    cos = jnp.cos(angles)[:, :, None, :]             # (B,S,1,d/2)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, jnp.float32) * (-math.log(10000.0) / dim))
    pe = jnp.zeros((seq, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def sinusoidal_position_at(pos, dim: int):
    """Single (possibly traced) position -> (dim,) sinusoidal embedding."""
    div = jnp.exp(jnp.arange(0, dim, 2, jnp.float32) * (-math.log(10000.0) / dim))
    ang = pos.astype(jnp.float32) * div
    pe = jnp.zeros((dim,), jnp.float32)
    pe = pe.at[0::2].set(jnp.sin(ang))
    pe = pe.at[1::2].set(jnp.cos(ang))
    return pe


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False):
    kq, kk, kv, ko = split_keys(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": dense_init(kq, (d, qd), d, dt),
        "wk": dense_init(kk, (d, kvd), d, dt),
        "wv": dense_init(kv, (d, kvd), d, dt),
        "wo": dense_init(ko, (qd, d), qd, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm(cfg, cfg.head_dim)
        p["k_norm"] = init_norm(cfg, cfg.head_dim)
    return p


CHUNKED_ATTN_THRESHOLD = 8192   # chunk prefill queries beyond this length


def _attend_block(qg, k, v, cfg, q_pos, k_pos, k_valid, causal, window, dt):
    """One (q-block) x (full kv) attention.  qg: (B,cq,Hk,G,D).

    q_pos/k_pos/k_valid may be shared across the batch — q_pos (S,),
    k_pos (T,), k_valid (T,) — or per-batch-element — (B,S)/(B,T)/(B,T) —
    for per-slot continuous batching where every slot sits at its own
    decode position.  The mask math broadcasts over either layout."""
    b, cq = qg.shape[:2]
    hd = qg.shape[-1]
    rel = q_pos[..., :, None] - k_pos[..., None, :]   # (S,T) or (B,S,T)
    ok = jnp.ones(rel.shape, bool)
    if causal:
        ok &= rel >= 0
    if window and window > 0:
        ok &= rel < window
    if k_valid is not None:
        ok &= k_valid[..., None, :]
    bias = jnp.where(ok, 0.0, -1e30)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    if bias.ndim == 3:                 # per-batch mask -> (B,1,1,S,T)
        bias = bias[:, None, None]
    scores = scores + bias
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(dt), v.astype(dt))
    return out.reshape(b, cq, -1)


def _attend(q, k, v, cfg: ModelConfig, *, q_pos, k_pos, k_valid, causal,
            window, dt):
    """Core GQA attention with position-based masking.

    q: (B,S,H,D); k,v: (B,T,Hkv,D); q_pos: (S,) absolute query positions;
    k_pos: (T,) absolute key positions; k_valid: (T,) bool or None.
    Dispatches to the fused flash kernel on TPU via the dispatch front door
    (repro.backend.dispatch); long-prefill falls back to q-chunked attention
    so the (S,T) score matrix never materializes at full size
    (flash-attention structure, visible to XLA on every backend — §Perf
    jamba iteration 2)."""
    import os

    from repro.backend import dispatch as kops
    b, s, h, hd = q.shape
    hk = k.shape[2]
    groups = h // hk
    batched_pos = q_pos.ndim > 1 or k_pos.ndim > 1

    if not batched_pos and kops.use_flash(cfg, q, k):
        return kops.dispatch_flash_attention(
            q, k, v, q_pos=q_pos, k_pos=k_pos, k_valid=k_valid,
            causal=causal, window=window,
            softcap=cfg.attn_logit_softcap).astype(dt)

    qg = q.reshape(b, s, hk, groups, hd)
    thresh = int(os.environ.get("REPRO_CHUNKED_ATTN",
                                CHUNKED_ATTN_THRESHOLD))
    if not batched_pos and thresh and s > thresh \
            and s % (cq := thresh // 4) == 0:
        nc = s // cq
        qc = jnp.moveaxis(qg.reshape(b, nc, cq, hk, groups, hd), 1, 0)
        pc = q_pos.reshape(nc, cq)

        def body(_, inp):
            q_i, p_i = inp
            o = _attend_block(q_i, k, v, cfg, p_i, k_pos, k_valid,
                              causal, window, dt)
            return None, o
        _, outs = lax.scan(body, None, (qc, pc))
        return jnp.moveaxis(outs, 0, 1).reshape(b, s, h * hd)

    return _attend_block(qg, k, v, cfg, q_pos, k_pos, k_valid, causal,
                         window, dt)


def ring_k_positions(last, W: int):
    """Absolute position of every row of a ring cache whose newest token
    sits at position ``last`` — a scalar, or (B, 1) for per-slot decode.
    Returns (k_pos, k_valid): rows not yet written get negative positions
    and are masked.  This is THE ring invariant; both the lock-step and
    the per-slot decode paths must read the cache through it."""
    i = jnp.arange(W)
    k_pos = last - ((last - i) % W)
    return k_pos, k_pos >= 0


def _paged_write(kv_cache, pages, rows, k, v):
    """Scatter fresh K/V rows into head-major pool pages ``(N, Hkv, P,
    D)``, quantizing on int8 pools.

    ``pages``/``rows`` index physical (page, row) per fresh token —
    shapes (B,), (B, S), or (S,) matching ``k``/``v``'s leading dims; the
    trailing dims are (Hkv, D).  Out-of-range pages drop the write.
    Returns the new pool-leaf dict (scale leaves updated alongside the
    int8 rows so dequant always sees matching data)."""
    def put(pool, x):
        return pool.at[pages, :, rows].set(x.astype(pool.dtype),
                                           mode="drop")
    if "k_scales" in kv_cache:
        from repro.kernels import ref as R
        kq, ks = R.quantize_int8_rows(k)
        vq, vs = R.quantize_int8_rows(v)
        return {"k_pages": put(kv_cache["k_pages"], kq),
                "v_pages": put(kv_cache["v_pages"], vq),
                "k_scales": put(kv_cache["k_scales"], ks),
                "v_scales": put(kv_cache["v_scales"], vs)}
    return {"k_pages": put(kv_cache["k_pages"], k),
            "v_pages": put(kv_cache["v_pages"], v)}


def _scale_kw(cache):
    """Dequant-scale kwargs for the paged dispatch calls (empty on fp)."""
    if "k_scales" in cache:
        return {"k_scales": cache["k_scales"], "v_scales": cache["v_scales"]}
    return {}


def cross_kv(p, enc_out, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output (cached once at
    prefill so decode steps skip the projections)."""
    b, t, _ = enc_out.shape
    hk, hd = cfg.num_kv_heads, cfg.head_dim
    k = jnp.einsum("bsd,dq->bsq", enc_out, p["wk"],
                   preferred_element_type=jnp.float32)
    v = jnp.einsum("bsd,dq->bsq", enc_out, p["wv"],
                   preferred_element_type=jnp.float32)
    return (k.astype(enc_out.dtype).reshape(b, t, hk, hd),
            v.astype(enc_out.dtype).reshape(b, t, hk, hd))


def multi_head_attention(p, x, cfg: ModelConfig, *, positions=None,
                         causal=True, window=0, kv_cache=None,
                         cache_index=None, kv_source=None, use_rope=True,
                         precomputed_kv=None, attend_cache=False,
                         block_tables=None, write_tables=None):
    """General attention supporting GQA, RoPE/M-RoPE, logit softcap, sliding
    window (ring-buffer cache), cross-attention (``kv_source``), and KV-cache
    prefill/decode.

    Modes:
      * train:   kv_cache is None — full attention over x itself.
      * prefill: kv_cache given, x length > 1 — attend over fresh k/v and
                 write the (window-)tail into the cache.
      * chunked prefill continuation: kv_cache given, x length > 1 AND
                 ``attend_cache=True`` — the fresh tokens additionally
                 attend the tokens already sitting in the cache (scalar
                 ``cache_index`` = their count), so a prompt can stream
                 through the stack as consecutive chunks.
      * decode:  kv_cache given, x length small — read/modify/write cache.

    kv_cache: {"k": (B, W, Hkv, D), "v": ...} where W is max_seq for global
    attention or the window size (ring buffer) for local attention — OR a
    *paged* cache {"k_pages": (N, Hkv, P, D), "v_pages": ...}: a physical
    block pool shared by all slots, addressed through ``block_tables``
    ((B, max_blocks) int32, unmapped entries out of range).  Paged caches
    serve two modes: per-slot decode (one token per slot at its own
    position — the write lands in the slot's current page row and the
    attend gathers pages through the table, ``dispatch_paged_attention``)
    and batch-1 suffix/chunk prefill (scalar ``cache_index`` = tokens
    already cached, x = the fresh chunk: K/V are scattered straight into
    the pool rows named by ``write_tables`` — sentinel entries drop the
    write, protecting shared prefix blocks — then every query attends the
    full mapped prefix through ``block_tables`` under a causal mask,
    ``dispatch_paged_prefill_attention``).  ``write_tables`` defaults to
    ``block_tables`` when the whole table is writable (cold prefill with
    no shared blocks).
    cache_index: tokens already in the cache — a scalar int when the whole
    batch decodes in lock-step, or a (B,) vector for per-slot continuous
    batching (each slot writes its own cache row, attends under its own
    length mask, and rotates RoPE at its own position).
    Returns (out, new_kv_cache_or_None).
    """
    b, s, _ = x.shape
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = x.dtype

    q = jnp.einsum("bsd,dq->bsq", x, p["wq"],
                   preferred_element_type=jnp.float32).astype(dt)
    q = q.reshape(b, s, h, hd)
    if precomputed_kv is not None:
        k, v = precomputed_kv
        kv_source = k          # marks cross-attention (no rope on k, no causal)
    else:
        kv_in = x if kv_source is None else kv_source
        k = jnp.einsum("bsd,dq->bsq", kv_in, p["wk"],
                       preferred_element_type=jnp.float32).astype(dt)
        v = jnp.einsum("bsd,dq->bsq", kv_in, p["wv"],
                       preferred_element_type=jnp.float32).astype(dt)
        k = k.reshape(b, kv_in.shape[1], hk, hd)
        v = v.reshape(b, kv_in.shape[1], hk, hd)

    if "q_norm" in p:
        q = apply_norm(p["q_norm"], q, cfg)
        k = apply_norm(p["k_norm"], k, cfg)

    offset = 0 if cache_index is None else cache_index
    per_slot = cache_index is not None \
        and getattr(cache_index, "ndim", 0) == 1       # (B,) slot positions
    if per_slot:
        pos_bs = offset[:, None] + jnp.arange(s)[None, :]         # (B,S)
    # fused paged decode: RoPE + page-write + attention run as ONE kernel
    # (``dispatch_fused_paged_decode``) when the step is a plain per-slot
    # paged decode rotating at its own cache position — the rotation then
    # happens in-kernel, so the early apply_rope below is skipped and q/k
    # reach the dispatch un-roped.  Anything fancier (M-RoPE, explicit
    # positions, verify windows, rope-free families) keeps the unfused
    # sequence.
    fuse_decode = (kv_cache is not None and "k_pages" in kv_cache
                   and s == 1 and per_slot and kv_source is None
                   and use_rope and cfg.rope_theta > 0
                   and not cfg.mrope_sections and positions is None
                   and not window and block_tables is not None)
    if positions is None:
        if per_slot:
            positions = pos_bs
        else:
            base = offset + jnp.arange(s)[None, :]
            positions = jnp.broadcast_to(base, (b, s))
    if use_rope and cfg.rope_theta > 0 and not fuse_decode:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        if kv_source is None:
            k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)

    causal = causal and kv_source is None
    q_pos = pos_bs if per_slot else jnp.arange(s) + offset

    if kv_cache is None:
        out = _attend(q, k, v, cfg, q_pos=q_pos, k_pos=jnp.arange(k.shape[1]),
                      k_valid=None, causal=causal, window=window, dt=dt)
        new_cache = None
    elif "k_pages" in kv_cache:
        if window:
            raise NotImplementedError(
                "sliding-window attention keeps its dense ring cache "
                "(ring wrap order is position-, not block-, aligned)")
        if block_tables is None:
            raise NotImplementedError(
                "paged KV caches are addressed through block_tables "
                "(per-slot decode or batch-1 suffix/chunk prefill)")
        page = kv_cache["k_pages"].shape[2]
        from repro.backend import dispatch as kops
        if s == 1 and per_slot and fuse_decode:
            # ---- fused paged decode: RoPE (q and fresh k), the page
            # write, and the attention gather run as one dispatch — one
            # HBM round-trip over the pool instead of three.  Same
            # length-masked logical-ordered semantics as the unfused
            # path below (the ref composes exactly that sequence), so fp
            # parity stays bit-exact.
            out, nkp, nvp, nks, nvs = kops.dispatch_fused_paged_decode(
                q, k, v, kv_cache["k_pages"], kv_cache["v_pages"],
                block_tables, offset, theta=cfg.rope_theta,
                softcap=cfg.attn_logit_softcap,
                k_scales=kv_cache.get("k_scales"),
                v_scales=kv_cache.get("v_scales"))
            new_cache = {"k_pages": nkp, "v_pages": nvp}
            if nks is not None:
                new_cache["k_scales"] = nks
                new_cache["v_scales"] = nvs
            out = out.astype(dt)
        elif s == 1 and per_slot:
            # ---- paged decode: the slot's fresh K/V lands in its
            # current page row (table lookup; out-of-range pages drop the
            # write, so idle slots riding along at fixed shape touch
            # nothing), then attention gathers K/V through the block
            # table.  The gathered layout is logical-ordered, so the
            # per-slot length mask reproduces the dense masking exactly —
            # paged decode is bit-identical to dense decode (see
            # kernels/ref.py).
            blk_idx = jnp.clip(offset // page, 0, block_tables.shape[1] - 1)
            pages = jnp.take_along_axis(block_tables, blk_idx[:, None],
                                        axis=1)[:, 0]
            rows = offset % page
            new_cache = _paged_write(kv_cache, pages, rows, k[:, 0], v[:, 0])
            out = kops.dispatch_paged_attention(
                q, new_cache["k_pages"], new_cache["v_pages"],
                block_tables, offset + 1, softcap=cfg.attn_logit_softcap,
                **_scale_kw(new_cache)).astype(dt)
        elif per_slot:
            # ---- paged speculative verify: each slot writes an S-token
            # window (current token + drafted tokens) at its own
            # positions — page lookups per window element, out-of-range
            # blocks drop the write — then every window query attends
            # the slot's full mapped prefix through the block table
            # under a per-slot causal mask.  Greedy argmax over each
            # position then scores the drafts exactly as S sequential
            # one-token decodes would (rejected rows are causally masked
            # everywhere and overwritten by the next window before they
            # could become valid).
            nb = block_tables.shape[1]
            n = kv_cache["k_pages"].shape[0]
            blk = pos_bs // page                                    # (B,S)
            pages = jnp.where(
                blk < nb,
                jnp.take_along_axis(block_tables,
                                    jnp.clip(blk, 0, nb - 1), axis=1), n)
            rows = pos_bs % page
            new_cache = _paged_write(kv_cache, pages, rows, k, v)
            out = kops.dispatch_paged_verify_attention(
                q, new_cache["k_pages"], new_cache["v_pages"],
                block_tables, offset, softcap=cfg.attn_logit_softcap,
                **_scale_kw(new_cache)).astype(dt)
        else:
            # ---- paged suffix/chunk prefill: write the fresh chunk's
            # K/V straight into the pool (write_tables names each fresh
            # block's physical page; sentinel entries — shared prefix
            # blocks and pad positions past the mapped range — drop the
            # write), then attend ALL mapped positions through the block
            # table under a pure causal mask.  A warm suffix thereby
            # attends the reused prefix without ever recomputing it.
            if b != 1:
                raise NotImplementedError(
                    "paged prefill writes through one write-table row; "
                    "batch the chunks, not the slots")
            wt = block_tables if write_tables is None else write_tables
            n = kv_cache["k_pages"].shape[0]
            nb = wt.shape[1]
            pos = offset + jnp.arange(s)
            blk = pos // page
            rows = pos % page
            # pad positions can run past the table; clipping must not
            # alias them onto the last real block, so map them to the
            # drop sentinel explicitly.
            phys = jnp.where(blk < nb, wt[0, jnp.clip(blk, 0, nb - 1)], n)
            new_cache = _paged_write(kv_cache, phys, rows, k[0], v[0])
            out = kops.dispatch_paged_prefill_attention(
                q, new_cache["k_pages"], new_cache["v_pages"],
                block_tables, offset, softcap=cfg.attn_logit_softcap,
                **_scale_kw(new_cache)).astype(dt)
    else:
        W = kv_cache["k"].shape[1]
        cdt = kv_cache["k"].dtype
        if s > 1 and not per_slot:
            if attend_cache:
                # ---- chunked-prefill continuation: the chunk's queries
                # attend the cached tokens (ring rows at their absolute
                # positions, unwritten rows masked) AND the fresh k/v.
                # For a global cache the ring is position-ordered, so the
                # valid keys appear in exactly the order the one-shot
                # prefill sums them — chunking is numerically lossless.
                k_pos_old, k_valid_old = ring_k_positions(offset - 1, W)
                k_all = jnp.concatenate([kv_cache["k"].astype(dt), k], axis=1)
                v_all = jnp.concatenate([kv_cache["v"].astype(dt), v], axis=1)
                k_pos_all = jnp.concatenate(
                    [k_pos_old, offset + jnp.arange(s)])
                k_valid_all = jnp.concatenate(
                    [k_valid_old, jnp.ones((s,), bool)])
                out = _attend(q, k_all, v_all, cfg, q_pos=q_pos,
                              k_pos=k_pos_all, k_valid=k_valid_all,
                              causal=causal, window=window, dt=dt)
            else:
                # ---- prefill: attend over the fresh full-length k/v ----
                out = _attend(q, k, v, cfg, q_pos=q_pos,
                              k_pos=jnp.arange(k.shape[1]), k_valid=None,
                              causal=causal, window=window, dt=dt)
            # write the last min(s, W) tokens into (ring) cache slots.
            tail = min(s, W)
            k_tail = k[:, s - tail:].astype(cdt)
            v_tail = v[:, s - tail:].astype(cdt)
            tail_pos = offset + jnp.arange(s - tail, s)
            slots = tail_pos % W
            new_k = kv_cache["k"].at[:, slots].set(k_tail)
            new_v = kv_cache["v"].at[:, slots].set(v_tail)
            new_cache = {"k": new_k, "v": new_v}
        elif per_slot:
            # ---- per-slot decode: each batch row writes its own cache
            # row(s) and attends under its own length mask (slots sit at
            # different positions under continuous batching).  s > 1 is
            # the speculative-verify window: S rows land at the slot's
            # own positions and the per-query causal mask scores each
            # window position exactly as S sequential decodes would ----
            rows = pos_bs % W                                       # (B,S)
            bidx = jnp.arange(b)[:, None]
            new_k = kv_cache["k"].at[bidx, rows].set(k.astype(cdt))
            new_v = kv_cache["v"].at[bidx, rows].set(v.astype(cdt))
            new_cache = {"k": new_k, "v": new_v}
            k_pos, k_valid = ring_k_positions(
                (offset + s - 1)[:, None], W)        # (B,W) per-slot mask
            out = _attend(q, new_k, new_v, cfg, q_pos=q_pos, k_pos=k_pos,
                          k_valid=k_valid, causal=causal, window=window,
                          dt=dt)
        else:
            # ---- decode: ring write then attend over the cache ----
            slots = (offset + jnp.arange(s)) % W
            new_k = kv_cache["k"].at[:, slots].set(k.astype(cdt))
            new_v = kv_cache["v"].at[:, slots].set(v.astype(cdt))
            new_cache = {"k": new_k, "v": new_v}
            k_pos, k_valid = ring_k_positions(offset + s - 1, W)
            out = _attend(q, new_k, new_v, cfg, q_pos=q_pos, k_pos=k_pos,
                          k_valid=k_valid, causal=causal, window=window,
                          dt=dt)

    out = jnp.einsum("bsq,qd->bsd", out, p["wo"],
                     preferred_element_type=jnp.float32).astype(dt)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def _act(name):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True),
            "relu2": lambda x: jnp.square(jax.nn.relu(x))}[name]


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = split_keys(key, 3)
    p = {"wi": dense_init(ks[0], (cfg.d_model, d_ff), cfg.d_model, dt),
         "wo": dense_init(ks[1], (d_ff, cfg.d_model), d_ff, dt)}
    if cfg.gated_mlp:
        p["wg"] = dense_init(ks[2], (cfg.d_model, d_ff), cfg.d_model, dt)
    return p


def apply_mlp(p, x, cfg: ModelConfig):
    dt = x.dtype
    hid = jnp.einsum("bsd,df->bsf", x, p["wi"],
                     preferred_element_type=jnp.float32)
    act = _act(cfg.mlp_activation)
    if "wg" in p:
        gate = jnp.einsum("bsd,df->bsf", x, p["wg"],
                          preferred_element_type=jnp.float32)
        hid = act(gate) * hid
    else:
        hid = act(hid)
    out = jnp.einsum("bsf,fd->bsd", hid.astype(dt), p["wo"],
                     preferred_element_type=jnp.float32)
    return out.astype(dt)


def init_moe(key, cfg: ModelConfig):
    moe: MoEConfig = cfg.moe
    dt = jnp.dtype(cfg.param_dtype)
    ks = split_keys(key, 6)
    e, d, f = moe.num_experts, cfg.d_model, moe.expert_d_ff
    p = {
        "router": dense_init(ks[0], (d, e), d, jnp.float32),
        "wi": dense_init(ks[1], (e, d, f), d, dt),
        "wg": dense_init(ks[2], (e, d, f), d, dt),
        "wo": dense_init(ks[3], (e, f, d), f, dt),
    }
    if moe.num_shared_experts:
        sf = moe.shared_expert_d_ff
        p["shared"] = {
            "wi": dense_init(ks[4], (d, sf), d, dt),
            "wg": dense_init(ks[5], (d, sf), d, dt),
            "wo": dense_init(ks[4], (sf, d), sf, dt),
        }
    return p


def apply_moe(p, x, cfg: ModelConfig):
    """GShard-style top-k capacity-limited dispatch (scatter/gather, no
    (N,E,C) dense dispatch tensor): FLOPs scale with tokens*k*capacity_factor,
    which keeps MODEL_FLOPS/HLO_FLOPS honest for the roofline."""
    moe: MoEConfig = cfg.moe
    b, s, d = x.shape
    n = b * s
    e, k = moe.num_experts, moe.experts_per_token
    # capacity per choice-round: each token contributes ONE slot per round,
    # so a round routes n slots over e experts (not n*k — that would k²-
    # inflate the expert matmul FLOPs).
    cap = max(1, int(math.ceil(n * moe.capacity_factor / e)))
    if s == 1:
        # decode: drop-free capacity.  Capacity drops couple batch rows
        # (tokens compete for expert rows via the routing cumsum), which
        # would make one serving slot's next token depend on what the
        # OTHER slots decoded — breaking the continuous-batching parity
        # guarantee.  n is tiny at decode, so the extra rows are free.
        cap = n
    dt = x.dtype
    xf = x.reshape(n, d)

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, k)                       # (n, k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)

    # position-in-expert via per-round cumulative counts (GShard Alg.1,
    # with one dispatch buffer per choice round).
    pos_list, keep_list = [], []
    for j in range(k):
        onehot = jax.nn.one_hot(top_e[:, j], e, dtype=jnp.int32)   # (n, e)
        pos = jnp.cumsum(onehot, axis=0) - 1
        pos_j = jnp.sum(pos * onehot, axis=-1)                     # (n,)
        keep_list.append(pos_j < cap)
        pos_list.append(jnp.clip(pos_j, 0, cap - 1))

    y = jnp.zeros((n, d), jnp.float32)
    # dispatch buffers (e, cap, d) per choice are scatter-filled then
    # expert-matmul'd; combine gathers back with routing weights.
    for j in range(k):
        idx_e = top_e[:, j]
        idx_c = pos_list[j]
        keep = keep_list[j]
        buf = jnp.zeros((e, cap, d), dt)
        src = jnp.where(keep[:, None], xf, 0).astype(dt)
        buf = buf.at[idx_e, idx_c].add(src, mode="drop")
        hid = jnp.einsum("ecd,edf->ecf", buf, p["wi"],
                         preferred_element_type=jnp.float32)
        gate = jnp.einsum("ecd,edf->ecf", buf, p["wg"],
                          preferred_element_type=jnp.float32)
        hid = (jax.nn.silu(gate) * hid).astype(dt)
        out = jnp.einsum("ecf,efd->ecd", hid, p["wo"],
                         preferred_element_type=jnp.float32)
        tok_out = out[idx_e, idx_c]                                # (n, d)
        y = y + jnp.where(keep[:, None], tok_out, 0) * top_w[:, j:j + 1]

    if "shared" in p:
        sh = p["shared"]
        hid = jnp.einsum("nd,df->nf", xf, sh["wi"],
                         preferred_element_type=jnp.float32)
        gate = jnp.einsum("nd,df->nf", xf, sh["wg"],
                          preferred_element_type=jnp.float32)
        hid = (jax.nn.silu(gate) * hid).astype(dt)
        y = y + jnp.einsum("nf,fd->nd", hid, sh["wo"],
                           preferred_element_type=jnp.float32)

    # auxiliary load-balance loss (Switch): stored for the training loop.
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    return y.reshape(b, s, d).astype(dt), aux


# ---------------------------------------------------------------------------
# embeddings / heads
# ---------------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.param_dtype)
    return {"table": dense_init(key, (cfg.vocab_size, cfg.d_model), cfg.d_model, dt)}


def embed(p, ids, cfg: ModelConfig):
    out = jnp.take(p["table"], ids, axis=0)
    if cfg.family == "dense" and cfg.tie_embeddings:
        out = out * jnp.sqrt(jnp.float32(cfg.d_model)).astype(out.dtype)
    return out


def chunked_softmax_xent(x, w, labels, cfg: ModelConfig, *,
                         chunk: int = 8192):
    """Cross-entropy fused with the LM head over vocab chunks: the
    (tokens × vocab) logits tensor never materializes — an online
    logsumexp runs across vocab chunks (flash-softmax structure applied to
    the loss; the dominant train activation for 256k-vocab archs).

    x: (N, D) final hidden; w: (D, V); labels: (N,) int32 (< 0 = masked).
    Returns per-token nll (N,)."""
    n, d = x.shape
    v = w.shape[1]
    if v % chunk:
        chunk = v  # fallback: single chunk
    n_chunks = v // chunk
    wc = w.T.reshape(n_chunks, chunk, d)      # (C, chunk, D)
    cap = cfg.final_logit_softcap
    xf = x.astype(jnp.float32)

    def body(carry, inp):
        m, l, tgt = carry
        w_c, c_idx = inp
        logits = jnp.einsum("nd,cd->nc", xf, w_c.astype(jnp.float32))
        if cap > 0:
            logits = cap * jnp.tanh(logits / cap)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        # pick up the target logit if it lives in this chunk
        local = labels - c_idx * chunk
        in_chunk = (local >= 0) & (local < chunk)
        got = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[:, None], axis=-1)[:, 0]
        tgt = jnp.where(in_chunk, got, tgt)
        return (m_new, l, tgt), None

    body = jax.checkpoint(body)   # recompute chunk logits in bwd
    m0 = jnp.full((n,), -1e30, jnp.float32)
    l0 = jnp.zeros((n,), jnp.float32)
    t0 = jnp.zeros((n,), jnp.float32)
    (m, l, tgt), _ = lax.scan(body, (m0, l0, t0),
                              (wc, jnp.arange(n_chunks)))
    lse = m + jnp.log(l)
    return lse - tgt                          # (N,) nll


def logits_head(p_embed, p_head, x, cfg: ModelConfig):
    if cfg.tie_embeddings or p_head is None:   # (whisper ties decoder embed)
        w = p_embed["table"].T
    else:
        w = p_head["w"]
    out = jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=jnp.float32)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        out = c * jnp.tanh(out / c)
    return out
