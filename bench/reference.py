"""Plain float32 pieces of the reference forwards, and the widest logit
gap of served tokens under an architecture's reference.

Each ``arch/<model_type>.py`` writes its forward (``hidden``) from the
model's published description, not from the program, out of these
pieces: matrix products, RMSNorm, rotary embeddings and blocked causal
attention.  Every matrix product runs in float32 at
``Precision.HIGHEST`` (on a TPU anything less rounds to bfloat16).
Nothing is batched or cached: the whole sequence runs through each layer
in turn, one layer's weights at a time, so the reference fits on the
chip once the program is freed.

``fp8=True`` is the control: the same forward with both operands
of every weight product rounded to float8 e4m3 (a scale per row of the
activations and per column of the weights, as an fp8 serving path would
keep them), accumulated in float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
Q_CHUNK = 512           # query rows per attention block
ROW_BUCKET = 512        # rows run at their length rounded up to this


def _round_fp8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, fp8=False):
    """``x @ w`` over the last axis of ``x``; with ``fp8`` the control's
    rounding of both operands."""
    if fp8:
        x, w = _round_fp8(x, -1), _round_fp8(w, 0)
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (B, S, H, hd) at positions 0..S-1, rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal softmax attention of q (B, S, H, hd) over k, v (B, S, Hkv,
    hd), each kv head shared by H / Hkv query heads, scaled by
    1/sqrt(hd), in blocks of ``Q_CHUNK`` query rows; (B, S, H * hd)."""
    b, s, heads, hd = q.shape
    kv_heads = k.shape[2]
    q = q.reshape(b, s, kv_heads, heads // kv_heads, hd) / jnp.sqrt(
        jnp.float32(hd))
    outs = []
    for a in range(0, s, Q_CHUNK):
        e = min(a + Q_CHUNK, s)
        sc = jnp.einsum("bqkgd,bskd->bkgqs", q[:, a:e], k[:, :e],
                        precision=HI)
        causal = jnp.arange(e)[None, :] <= jnp.arange(a, e)[:, None]
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bkgqs,bskd->bqkgd", p, v[:, :e],
                               precision=HI))
    return jnp.concatenate(outs, axis=1).reshape(b, s, heads * hd)


def embedded(emb, rows):
    """Each row of token ids as a (1, S, d) float32 embedding, padded at
    the end to a multiple of ``ROW_BUCKET`` (causal attention keeps the
    padding out of every real position)."""
    xs = []
    for seq in rows:
        toks = np.zeros((1, -(-len(seq) // ROW_BUCKET) * ROW_BUCKET), np.int32)
        toks[0, :len(seq)] = seq
        xs.append(jnp.take(emb, jnp.asarray(toks), axis=0))
    return xs


@partial(jax.jit, static_argnums=(5,))
def _row_gaps(h_ref, h_ctl, norm, head, target, eps):
    """Over the positions of one row that have a target (``target >= 0``):
    the widest gap between the reference's best logit and its logit of
    the target, and of the token the control (``h_ctl``) puts first."""
    ref = mm(rms(h_ref, norm, eps), head)
    best = jnp.max(ref, axis=-1)
    mask = target >= 0
    got = jnp.take_along_axis(ref, jnp.maximum(target, 0)[:, None], -1)[:, 0]
    served = jnp.max(jnp.where(mask, best - got, 0.0))
    if h_ctl is None:
        return served, jnp.float32(0.0)
    pick = jnp.argmax(mm(rms(h_ctl, norm, eps), head, True), axis=-1)
    picked = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return served, jnp.max(jnp.where(mask, best - picked, 0.0))


def gaps(arch, seed: int, rows, *, control: bool = False):
    """Widest gap, over every served token of ``rows`` ([(prompt,
    served tokens)]), by which the served token's reference logit lies
    below the reference's best at that position, under the architecture
    ``arch`` (``spec.architecture``: its ``hidden`` and the ``final``
    norm and head).  With ``control``, also the widest gap of the tokens
    the fp8 control forward puts first at the same positions."""
    assert rows
    seqs, tgts = [], []
    for prompt, out in rows:
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(out[:-1], np.int32)])
        tgt = np.full((-(-len(seq) // ROW_BUCKET) * ROW_BUCKET,), -1,
                      np.int32)
        tgt[len(prompt) - 1:len(prompt) - 1 + len(out)] = out
        seqs.append(seq)
        tgts.append(tgt)
    h_ref = arch.hidden(seed, seqs)
    h_ctl = arch.hidden(seed, seqs, fp8=True) if control else None
    norm, head, eps = arch.final(seed)
    served, ctl = 0.0, 0.0
    for b, tgt in enumerate(tgts):
        g, c = _row_gaps(h_ref[b], None if h_ctl is None else h_ctl[b],
                         norm, head, jnp.asarray(tgt), eps)
        served, ctl = max(served, float(g)), max(ctl, float(c))
    out = {"widest_gap": served, "tokens": int(sum((t >= 0).sum()
                                                   for t in tgts)),
           "rows": len(rows)}
    if control:
        out["control_gap"] = ctl
    return out
