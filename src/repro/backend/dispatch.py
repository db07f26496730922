"""Kernel-dispatch front door: one routing decision for every hot kernel.

Each ``dispatch_*`` picks the execution path for its kernel:

  * ``pallas``    — compiled Pallas-TPU (the fused production path);
  * ``interpret`` — the same Pallas kernel under the interpreter (CPU
                    correctness testing of the real kernel body);
  * ``ref``       — the pure-jnp oracle in ``repro.kernels.ref`` (XLA-fused
                    on any backend; the CPU serving/training path).

Selection: ``REPRO_KERNELS`` env var forces a path ("pallas" /
"interpret" / "ref"); the default "auto" resolves to ``pallas`` on TPU and
``ref`` elsewhere.  Models, the serving engine, and the pipeline executor
all call through here, so one flag flips the whole system between the
fused kernels and the oracle — and the kernel sweep tests compare the two.

On the ``pallas`` path a shape the kernel cannot tile raises a
``ValueError`` naming the kernel and the shape; it never quietly runs the
oracle.  The cases that run the oracle by design (int8 pools in the
unfused paged kernels, paged verify on every path, attention shapes
``use_flash`` turns away) are reported as ``ref``: every dispatch records
the path it took into the open ``record_paths`` collectors, which is how
the serving engine's stats name what each compiled kernel really ran.
"""
from __future__ import annotations

import contextlib
import contextvars
import os

import jax.numpy as jnp

from repro.backend import compat

_PATHS = ("pallas", "interpret", "ref")

# every dispatchable kernel, by override name (REPRO_KERNELS=name=path)
_KERNELS = ("matmul", "flash_attention", "paged_attention",
            "paged_prefill_attention", "paged_verify_attention",
            "fused_paged_decode", "layernorm", "linear_scan")


_RECORDERS: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_path_recorders", default=())


@contextlib.contextmanager
def record_paths(into: dict):
    """While the block runs, add ``kernel -> {path, ...}`` to ``into`` for
    every kernel dispatched.  Dispatch happens when a step is traced, so
    wrap the calls that compile (a jitted step traces on first call)."""
    token = _RECORDERS.set(_RECORDERS.get() + (into,))
    try:
        yield into
    finally:
        _RECORDERS.reset(token)


def _took(kernel: str, path: str) -> str:
    for rec in _RECORDERS.get():
        rec.setdefault(kernel, set()).add(path)
    return path


def _check_paged_tile(kernel: str, q, k_pages, int8: bool = False):
    """The pallas path needs (page, head_dim) blocks the TPU compiler
    accepts: head_dim a multiple of 128 lanes, page a multiple of 8
    sublanes (32 for int8 tiles).  Off the tile it raises; it never
    falls back to the jnp reference."""
    rows = 32 if int8 else 8
    page, d = k_pages.shape[2], k_pages.shape[3]
    if d % 128 or page % rows:
        raise ValueError(
            f"{kernel}: q {q.shape}, pages {k_pages.shape} are off the TPU "
            f"tile (head_dim % 128, page % {rows}); the pallas path does "
            f"not fall back to the jnp reference — set REPRO_KERNELS=ref "
            f"or {kernel}=ref to run it")


def _resolve(path: str) -> str:
    if path == "auto":
        return "pallas" if compat.on_tpu() else "ref"
    return path


def kernel_path(kernel: str = None) -> str:
    """The active path ("pallas" | "interpret" | "ref") for ``kernel``
    (or the global default when ``kernel`` is None).

    ``REPRO_KERNELS`` is a comma-separated list: one bare base path
    ("auto" / "pallas" / "interpret" / "ref") plus optional per-kernel
    overrides ``name=path`` (e.g. "ref,fused_paged_decode=interpret" runs
    everything on the oracle but the fused decode kernel under the
    interpreter).  Anything else raises (a typo silently falling back to
    the jnp oracle would fake a kernel benchmark)."""
    mode = os.environ.get("REPRO_KERNELS", "auto")
    base, overrides = "auto", {}
    for part in mode.split(","):
        part = part.strip()
        name, sep, val = part.partition("=")
        if sep:
            if name not in _KERNELS or val not in ("auto",) + _PATHS:
                raise ValueError(
                    f"REPRO_KERNELS override {part!r} is not valid; paths "
                    f"are {('auto',) + _PATHS} and kernel names are "
                    f"{_KERNELS}")
            overrides[name] = val
        else:
            if part not in ("auto",) + _PATHS:
                raise ValueError(
                    f"REPRO_KERNELS={part!r} is not a valid kernel path; "
                    f"choose one of {('auto',) + _PATHS}, or a per-kernel "
                    f"override 'name=path' with name in {_KERNELS}")
            base = part
    return _resolve(overrides.get(kernel, base))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def use_flash(cfg, q, k) -> bool:
    """Whether the model's attention should route to the fused kernel:
    only when shapes tile cleanly to the MXU and we're not on the oracle
    path.  (The jnp fallback is itself XLA-fused on CPU.)"""
    if kernel_path("flash_attention") == "ref":
        _took("flash_attention", "ref")
        return False
    b, s, h, d = q.shape
    t = k.shape[1]
    if s % 8 == 0 and t % 128 == 0 and d % 128 == 0:
        return True
    _took("flash_attention", "ref")
    return False


def dispatch_flash_attention(q, k, v, *, q_pos, k_pos, k_valid=None,
                             causal=True, window=0, softcap=0.0):
    """Layout adapter: (B,S,H,D) model layout -> (B,H,S,D) kernel layout;
    returns (B, S, H*D)."""
    from repro.kernels import ref as R
    qk = jnp.swapaxes(q, 1, 2)
    kk = jnp.swapaxes(k, 1, 2)
    vk = jnp.swapaxes(v, 1, 2)
    if k_valid is None:
        k_valid = jnp.ones((kk.shape[2],), jnp.int32)
    path = _took("flash_attention", kernel_path("flash_attention"))
    if path == "ref":
        out = R.flash_attention_ref(qk, kk, vk, q_pos, k_pos, k_valid,
                                    causal=causal, window=window,
                                    softcap=softcap)
    else:
        from repro.kernels.flash_attention import flash_attention_bhsd
        out = flash_attention_bhsd(qk, kk, vk, q_pos, k_pos, k_valid,
                                   causal=causal, window=window,
                                   softcap=softcap,
                                   interpret=(path == "interpret"))
    return jnp.swapaxes(out, 1, 2).reshape(q.shape[0], q.shape[1], -1)


# ---------------------------------------------------------------------------
# paged attention (decode over a block-pool KV cache)
# ---------------------------------------------------------------------------

def dispatch_paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                             softcap=0.0, k_scales=None, v_scales=None):
    """Decode attention through per-slot block tables over a physical
    page pool.  Layout adapter: q arrives in model layout (B, 1, H, D)
    and leaves as (B, 1, H*D); pages are head-major (N, Hkv, P, D);
    block_tables (B, NB) int32 may carry out-of-range entries for
    unmapped logical blocks (clipped here — rows past ``lengths`` are
    masked regardless); lengths (B,) counts each slot's valid tokens.
    k_scales/v_scales: (N, Hkv, P) f32 dequant scales on int8 pools
    (None on fp) — int8 pools run the dequantizing jnp reference on every
    path (recorded as ``ref``).

    The pallas path requires head_dim % 128 and page % 8 and raises
    otherwise."""
    from repro.kernels import ref as R
    b, s, h, d = q.shape
    assert s == 1, f"paged attention is a decode (one-token) path, got {s}"
    n, hk = k_pages.shape[:2]
    qg = q[:, 0].reshape(b, hk, h // hk, d)
    bt = jnp.clip(block_tables, 0, n - 1)
    path = kernel_path("paged_attention")
    if k_scales is not None:
        path = "ref"
    elif path == "pallas":
        _check_paged_tile("paged_attention", q, k_pages)
    if _took("paged_attention", path) == "ref":
        out = R.paged_attention_ref(qg, k_pages, v_pages, bt, lengths,
                                    softcap=softcap, k_scales=k_scales,
                                    v_scales=v_scales)
    else:
        from repro.kernels.paged_attention import paged_attention_grouped
        out = paged_attention_grouped(qg, k_pages, v_pages, bt, lengths,
                                      softcap=softcap,
                                      interpret=(path == "interpret"))
    return out.reshape(b, s, h * d)


def dispatch_fused_paged_decode(q, k_new, v_new, k_pages, v_pages,
                                block_tables, positions, *, theta,
                                softcap=0.0, k_scales=None, v_scales=None):
    """Fused RoPE + page-write + decode attention: one kernel, one HBM
    round-trip over the pool instead of three (rope-write / gather /
    attend).  Layout adapter: q arrives UN-roped in model layout
    (B, 1, H, D), k_new/v_new as the slot's un-roped fresh projections
    (B, 1, Hkv, D); positions (B,) is each slot's write position (tokens
    already cached — the length mask runs at positions+1).  Returns
    ``(out (B, 1, H*D), k_pages, v_pages, k_scales, v_scales)`` — the
    pool buffers with the fresh (quantized, on int8 pools) row written.

    The pallas path requires head_dim % 128 and page % 8 (page % 32 on
    int8 pools, the int8 (32, 128) tile) and raises otherwise."""
    from repro.kernels import ref as R
    b, s, h, d = q.shape
    assert s == 1, f"fused paged decode is a one-token path, got {s}"
    n, hk = k_pages.shape[:2]
    qg = q[:, 0].reshape(b, hk, h // hk, d)
    kn = k_new[:, 0]
    vn = v_new[:, 0]
    bt = jnp.clip(block_tables, 0, n - 1)
    path = kernel_path("fused_paged_decode")
    if path == "pallas":
        _check_paged_tile("fused_paged_decode", q, k_pages,
                          int8=k_scales is not None)
    if _took("fused_paged_decode", path) == "ref":
        out, kp, vp, ks, vs = R.fused_paged_decode_ref(
            qg, kn, vn, k_pages, v_pages, bt, positions, theta=theta,
            softcap=softcap, k_scales=k_scales, v_scales=v_scales)
    else:
        from repro.kernels.paged_attention import fused_paged_decode_grouped
        out, kp, vp, ks, vs = fused_paged_decode_grouped(
            qg, kn, vn, k_pages, v_pages, bt, positions, theta=theta,
            softcap=softcap, k_scales=k_scales, v_scales=v_scales,
            interpret=(path == "interpret"))
    return out.reshape(b, s, h * d), kp, vp, ks, vs


def dispatch_paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                     offset, *, softcap=0.0, k_scales=None,
                                     v_scales=None):
    """Suffix/chunked prefill attention through per-slot block tables:
    the fresh chunk's K/V are already written into the pool, and every
    query attends the full mapped prefix (shared + fresh) under a causal
    mask.  Layout adapter: q arrives in model layout (B, S, H, D) and
    leaves as (B, S, H*D); pages are head-major (N, Hkv, P, D);
    block_tables (B, NB) int32 may carry out-of-range entries for
    unmapped logical blocks (clipped here, causally masked); offset ()
    int32 is the position of the first fresh query.  int8 pools run the
    dequantizing jnp reference on every path (recorded as ``ref``).

    The pallas path requires head_dim % 128 and page % 8 and raises
    otherwise; any chunk length tiles (the kernel pads its query
    tiles)."""
    from repro.kernels import ref as R
    b, s, h, d = q.shape
    n, hk = k_pages.shape[:2]
    g = h // hk
    qg = jnp.swapaxes(q, 1, 2).reshape(b, hk, g, s, d)
    bt = jnp.clip(block_tables, 0, n - 1)
    path = kernel_path("paged_prefill_attention")
    if k_scales is not None:
        path = "ref"
    elif path == "pallas":
        _check_paged_tile("paged_prefill_attention", q, k_pages)
    if _took("paged_prefill_attention", path) == "ref":
        out = R.paged_prefill_attention_ref(qg, k_pages, v_pages, bt,
                                            offset, softcap=softcap,
                                            k_scales=k_scales,
                                            v_scales=v_scales)
    else:
        from repro.kernels.paged_attention import (
            paged_prefill_attention_grouped)
        out = paged_prefill_attention_grouped(
            qg, k_pages, v_pages, bt, offset, softcap=softcap,
            interpret=(path == "interpret"))
    return jnp.swapaxes(out.reshape(b, hk * g, s, d), 1, 2).reshape(
        b, s, h * d)


def dispatch_paged_verify_attention(q, k_pages, v_pages, block_tables,
                                    offset, *, softcap=0.0, k_scales=None,
                                    v_scales=None):
    """Speculative-verify attention through per-slot block tables: each
    slot's S-token verify window (current token + drafted tokens) is
    already written into the pool and attends its full mapped prefix
    under a causal mask anchored at a PER-SLOT ``offset`` (B,) — slots
    verify at different sequence depths in one batched step.  Layout
    adapter: q arrives in model layout (B, S, H, D) and leaves as
    (B, S, H*D).

    Verify windows are short (K+1 tokens) and off the steady-state
    decode hot loop, so every path runs the jnp reference for now
    (recorded as ``ref``); a Pallas lowering can slot in behind this
    front door without touching callers."""
    from repro.kernels import ref as R
    b, s, h, d = q.shape
    n, hk = k_pages.shape[:2]
    g = h // hk
    qg = jnp.swapaxes(q, 1, 2).reshape(b, hk, g, s, d)
    bt = jnp.clip(block_tables, 0, n - 1)
    _took("paged_verify_attention", "ref")
    out = R.paged_verify_attention_ref(qg, k_pages, v_pages, bt, offset,
                                       softcap=softcap, k_scales=k_scales,
                                       v_scales=v_scales)
    return jnp.swapaxes(out.reshape(b, hk * g, s, d), 1, 2).reshape(
        b, s, h * d)


# ---------------------------------------------------------------------------
# fused matmul
# ---------------------------------------------------------------------------

def dispatch_matmul(x, w, bias=None, *, activation="none", out_dtype=None):
    from repro.kernels import ref as R
    path = _took("matmul", kernel_path("matmul"))
    if path == "ref":
        return R.matmul_fused_ref(x, w, bias, activation=activation,
                                  out_dtype=out_dtype)
    from repro.kernels.fused_matmul import matmul_fused
    return matmul_fused(x, w, bias, activation=activation,
                        out_dtype=out_dtype,
                        interpret=(path == "interpret"))


# ---------------------------------------------------------------------------
# one-pass norm
# ---------------------------------------------------------------------------

def dispatch_layernorm(x, scale, bias=None, *, kind="rmsnorm", eps=1e-6):
    from repro.kernels import ref as R
    path = _took("layernorm", kernel_path("layernorm"))
    if path == "ref":
        return R.norm_onepass_ref(x, scale, bias, kind=kind, eps=eps)
    from repro.kernels.layernorm import norm_onepass
    return norm_onepass(x, scale, bias, kind=kind, eps=eps,
                        interpret=(path == "interpret"))


# ---------------------------------------------------------------------------
# linear recurrence scan
# ---------------------------------------------------------------------------

def use_scan_kernel() -> bool:
    """Whether recurrent models should flatten into the Pallas linear-scan
    kernel (vs the model-side chunked associative scan on the ref path)."""
    if kernel_path("linear_scan") == "ref":
        _took("linear_scan", "ref")
        return False
    return True


def dispatch_linear_scan(a, b, h0=None):
    """a, b: (N, S, F).  Returns all states (N, S, F)."""
    from repro.kernels import ref as R
    path = _took("linear_scan", kernel_path("linear_scan"))
    if path == "ref":
        return R.linear_scan_ref(a, b, h0)
    from repro.kernels.linear_scan import linear_scan
    return linear_scan(a, b, h0, interpret=(path == "interpret"))


__all__ = [
    "kernel_path", "record_paths", "use_flash", "use_scan_kernel",
    "dispatch_flash_attention", "dispatch_paged_attention",
    "dispatch_fused_paged_decode",
    "dispatch_paged_prefill_attention", "dispatch_paged_verify_attention",
    "dispatch_matmul", "dispatch_layernorm", "dispatch_linear_scan",
]
