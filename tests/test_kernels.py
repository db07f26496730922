"""Per-kernel sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.fused_matmul import matmul_fused
from repro.kernels.layernorm import norm_onepass
from repro.kernels.linear_scan import linear_scan


def _rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hk,sq,skv,d", [
    (1, 2, 2, 128, 128, 128),     # MHA
    (2, 4, 2, 256, 256, 128),     # GQA
    (1, 8, 1, 128, 384, 128),     # MQA, uneven kv
])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 128, 0.0), (False, 0, 0.0), (True, 0, 30.0),
])
def test_flash_attention(b, h, hk, sq, skv, d, dtype, causal, window,
                         softcap):
    r = _rng(hash((b, h, sq, skv)) % 2**31)
    q = jnp.asarray(r.standard_normal((b, h, sq, d)), dtype)
    k = jnp.asarray(r.standard_normal((b, hk, skv, d)), dtype)
    v = jnp.asarray(r.standard_normal((b, hk, skv, d)), dtype)
    qp = jnp.arange(sq) + (skv - sq)
    kp = jnp.arange(skv)
    kv = jnp.ones((skv,), jnp.int32)
    out = flash_attention_bhsd(q, k, v, qp, kp, kv, causal=causal,
                               window=window, softcap=softcap,
                               block_q=128, block_k=128, interpret=True)
    ref = R.flash_attention_ref(q, k, v, qp, kp, kv, causal=causal,
                                window=window, softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_ring_validity():
    """Decode over a ring cache: invalid (unwritten) slots must be ignored."""
    r = _rng(3)
    b, h, skv, d = 1, 2, 128, 128
    q = jnp.asarray(r.standard_normal((b, h, 8, d)), jnp.float32)
    k = jnp.asarray(r.standard_normal((b, h, skv, d)), jnp.float32)
    v = jnp.asarray(r.standard_normal((b, h, skv, d)), jnp.float32)
    k_pos = jnp.where(jnp.arange(skv) < 40, jnp.arange(skv), -1)
    k_valid = (k_pos >= 0).astype(jnp.int32)
    qp = jnp.arange(8) + 40
    out = flash_attention_bhsd(q, k, v, qp, k_pos, k_valid, causal=True,
                               interpret=True)
    ref = R.flash_attention_ref(q, k, v, qp, k_pos, k_valid, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# fused matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (256, 512, 384),
                                   (512, 128, 256)])
@pytest.mark.parametrize("act", ["none", "gelu", "silu", "relu2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_fused(m, k, n, act, dtype):
    r = _rng(hash((m, k, n)) % 2**31)
    x = jnp.asarray(r.standard_normal((m, k)) * 0.1, dtype)
    w = jnp.asarray(r.standard_normal((k, n)) * 0.1, dtype)
    b = jnp.asarray(r.standard_normal((n,)) * 0.1, dtype)
    out = matmul_fused(x, w, b, activation=act, block_m=128, block_n=128,
                       block_k=128, interpret=True)
    ref = R.matmul_fused_ref(x, w, b, activation=act)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_matmul_fused_no_bias():
    r = _rng(9)
    x = jnp.asarray(r.standard_normal((128, 128)), jnp.float32)
    w = jnp.asarray(r.standard_normal((128, 128)), jnp.float32)
    out = matmul_fused(x, w, None, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x @ w), atol=1e-3, rtol=1e-4)


# ---------------------------------------------------------------------------
# one-pass norm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
@pytest.mark.parametrize("r_,d", [(128, 256), (512, 384), (256, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_norm_onepass(kind, r_, d, dtype):
    r = _rng(hash((kind, r_, d)) % 2**31)
    x = jnp.asarray(r.standard_normal((r_, d)), dtype)
    s = jnp.asarray(r.standard_normal((d,)), dtype)
    b = jnp.asarray(r.standard_normal((d,)), dtype)
    out = norm_onepass(x, s, b, kind=kind, block_rows=128, interpret=True)
    ref = R.norm_onepass_ref(x, s, b, kind=kind)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# linear scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,s,f", [(2, 128, 256), (4, 256, 128),
                                   (1, 512, 512)])
def test_linear_scan(n, s, f):
    r = _rng(hash((n, s, f)) % 2**31)
    a = jnp.asarray(r.uniform(0.5, 0.999, (n, s, f)), jnp.float32)
    b = jnp.asarray(r.standard_normal((n, s, f)), jnp.float32)
    h0 = jnp.asarray(r.standard_normal((n, f)), jnp.float32)
    out = linear_scan(a, b, h0, block_s=128, block_f=128, interpret=True)
    ref = R.linear_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_linear_scan_matches_chunked_model_path():
    """The model-side chunked associative scan must agree with the kernel."""
    from repro.models.ssm import linear_scan_chunked
    r = _rng(11)
    a = jnp.asarray(r.uniform(0.5, 0.999, (2, 128, 8, 4)), jnp.float32)
    b = jnp.asarray(r.standard_normal((2, 128, 8, 4)), jnp.float32)
    h_all, h_last = linear_scan_chunked(a, b, chunk=32)
    flat = linear_scan(a.reshape(2, 128, 32), b.reshape(2, 128, 32),
                       block_s=64, block_f=32, interpret=True)
    np.testing.assert_allclose(np.asarray(h_all.reshape(2, 128, 32)),
                               np.asarray(flat), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last),
                               np.asarray(h_all[:, -1]), atol=1e-6)


# ---------------------------------------------------------------------------
# fused paged decode (RoPE + page write + attention in one kernel)
# ---------------------------------------------------------------------------

def _fused_setup(seed, *, b=3, h=4, hk=2, d=128, page=32, nb=3,
                 kv_dtype=jnp.float32, int8=False):
    """Random fused-decode operands with DISJOINT per-slot page tables
    (+ a trailing sink page).  Disjointness mirrors the engine's
    ownership invariant — write pages are exclusively owned — which the
    in-place aliased kernel writes rely on; colliding random tables
    would interleave one slot's write with another's gather."""
    r = _rng(seed)
    n = b * nb + 1                                   # + sink page
    q = jnp.asarray(r.standard_normal((b, hk, h // hk, d)), jnp.float32)
    kn = jnp.asarray(r.standard_normal((b, hk, d)), jnp.float32)
    vn = jnp.asarray(r.standard_normal((b, hk, d)), jnp.float32)
    bt = jnp.asarray(r.permutation(b * nb).reshape(b, nb), jnp.int32)
    pos = jnp.asarray([page - 1, page + 5, 2 * page + 17][:b], jnp.int32)
    if int8:
        kf = r.standard_normal((n, hk, page, d))
        vf = r.standard_normal((n, hk, page, d))
        kp, ks = R.quantize_int8_rows(jnp.asarray(kf, jnp.float32))
        vp, vs = R.quantize_int8_rows(jnp.asarray(vf, jnp.float32))
        return q, kn, vn, kp, vp, bt, pos, ks, vs
    kp = jnp.asarray(r.standard_normal((n, hk, page, d)), kv_dtype)
    vp = jnp.asarray(r.standard_normal((n, hk, page, d)), kv_dtype)
    return q, kn, vn, kp, vp, bt, pos, None, None


def test_decode_rope_ref_matches_model_apply_rope_bitwise():
    """The kernel-side RoPE reference must be BITWISE the model's
    ``apply_rope`` (no-mrope branch) — the fused path's fp parity
    guarantee hangs on this."""
    from repro.models.layers import apply_rope
    r = _rng(21)
    x = jnp.asarray(r.standard_normal((2, 1, 4, 64)), jnp.float32)
    pos = jnp.asarray([[7], [123]], jnp.int32)
    a = R.decode_rope_ref(x, pos, 10000.0)
    b = apply_rope(x, pos, 10000.0)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fused_paged_decode_ref_composes_unfused_ops_bitwise():
    """fused_paged_decode_ref == rope -> scatter -> paged_attention_ref,
    bit-for-bit (it IS that composition — this pins it)."""
    q, kn, vn, kp, vp, bt, pos, _, _ = _fused_setup(31)
    theta, page = 10000.0, kp.shape[2]
    out, nkp, nvp, _, _ = R.fused_paged_decode_ref(
        q, kn, vn, kp, vp, bt, pos, theta=theta)
    b, hk, g, d = q.shape
    qr = R.decode_rope_ref(q.reshape(b, 1, hk * g, d), pos[:, None],
                           theta).reshape(b, hk, g, d)
    kr = R.decode_rope_ref(kn[:, None], pos[:, None], theta)[:, 0]
    blk = jnp.clip(pos // page, 0, bt.shape[1] - 1)
    pages = jnp.take_along_axis(bt, blk[:, None], axis=1)[:, 0]
    rows = pos % page
    ekp = kp.at[pages, :, rows].set(kr, mode="drop")
    evp = vp.at[pages, :, rows].set(vn, mode="drop")
    eout = R.paged_attention_ref(qr, ekp, evp, bt, pos + 1)
    assert np.array_equal(np.asarray(nkp), np.asarray(ekp))
    assert np.array_equal(np.asarray(nvp), np.asarray(evp))
    assert np.array_equal(np.asarray(out), np.asarray(eout))


@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_fused_paged_decode_kernel_matches_ref(softcap):
    from repro.kernels.paged_attention import fused_paged_decode_grouped
    q, kn, vn, kp, vp, bt, pos, _, _ = _fused_setup(32)
    kw = dict(theta=10000.0, softcap=softcap)
    ro, rkp, rvp, _, _ = R.fused_paged_decode_ref(q, kn, vn, kp, vp, bt,
                                                  pos, **kw)
    io_, ikp, ivp, _, _ = fused_paged_decode_grouped(
        q, kn, vn, kp, vp, bt, pos, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(io_), np.asarray(ro),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(ikp), np.asarray(rkp),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(ivp), np.asarray(rvp),
                               atol=2e-5, rtol=2e-5)


def test_fused_paged_decode_kernel_int8_matches_ref():
    """int8 pools: the kernel's in-kernel quantization must produce the
    same int8 rows and scales as the reference (both quantize the same
    roped fp row with the same symmetric rule), and the attention output
    must match on the dequantized values."""
    from repro.kernels.paged_attention import fused_paged_decode_grouped
    q, kn, vn, kp, vp, bt, pos, ks, vs = _fused_setup(33, int8=True)
    kw = dict(theta=10000.0, k_scales=ks, v_scales=vs)
    ro, rkp, rvp, rks, rvs = R.fused_paged_decode_ref(q, kn, vn, kp, vp,
                                                      bt, pos, **kw)
    io_, ikp, ivp, iks, ivs = fused_paged_decode_grouped(
        q, kn, vn, kp, vp, bt, pos, interpret=True, **kw)
    assert ikp.dtype == jnp.int8 and iks.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(ikp), np.asarray(rkp))
    np.testing.assert_array_equal(np.asarray(ivp), np.asarray(rvp))
    np.testing.assert_allclose(np.asarray(iks), np.asarray(rks),
                               atol=1e-7, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ivs), np.asarray(rvs),
                               atol=1e-7, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(io_), np.asarray(ro),
                               atol=2e-5, rtol=2e-5)


def test_fused_paged_decode_int8_tracks_fp_within_quant_noise():
    """int8 dequant attention stays within a small bounded error of the
    fp path on the same values (per-row symmetric int8: |err| <=
    scale/2 per element, softmax-averaged)."""
    q, kn, vn, kp, vp, bt, pos, _, _ = _fused_setup(34)
    ks_q, ks = R.quantize_int8_rows(kp)
    vs_q, vs = R.quantize_int8_rows(vp)
    fp, *_ = R.fused_paged_decode_ref(q, kn, vn, kp, vp, bt, pos,
                                      theta=10000.0)
    q8, *_ = R.fused_paged_decode_ref(q, kn, vn, ks_q, vs_q, bt, pos,
                                      theta=10000.0, k_scales=ks,
                                      v_scales=vs)
    err = np.abs(np.asarray(fp) - np.asarray(q8)).max()
    assert err < 0.1, err                 # N(0,1) values, scale ~ 4/127


def test_int8_quantize_roundtrip_error_bound():
    r = _rng(35)
    x = jnp.asarray(r.standard_normal((5, 7, 128)), jnp.float32)
    q, s = R.quantize_int8_rows(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    back = R.dequantize_int8(q, s)
    # symmetric rounding: elementwise error <= scale/2
    bound = np.asarray(s)[..., None] * 0.5 + 1e-7
    assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound).all()
    # zero rows must not divide by zero
    q0, s0 = R.quantize_int8_rows(jnp.zeros((2, 3, 8)))
    assert np.asarray(q0).max() == 0 and np.isfinite(np.asarray(s0)).all()


@pytest.mark.parametrize("s,offset", [(300, 0), (40, 37)])
def test_paged_prefill_kernel_query_tiles_match_ref(s, offset):
    """Chunks longer than one query tile (300 rows: two tiles, the second
    padded) and a short warm chunk at an unaligned offset match the
    reference in interpret mode."""
    from repro.kernels.paged_attention import (
        PREFILL_TQ, paged_prefill_attention_grouped)
    assert s <= PREFILL_TQ or s % PREFILL_TQ
    r = _rng(41)
    hk, g, d, page = 2, 2, 128, 16
    nb = -(-(offset + s) // page) + 1
    n = nb + 3
    q = jnp.asarray(r.standard_normal((1, hk, g, s, d)), jnp.float32)
    kp = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    vp = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    bt = jnp.asarray(r.permutation(n)[:nb][None], jnp.int32)
    out = paged_prefill_attention_grouped(q, kp, vp, bt, offset,
                                          interpret=True)
    ref = R.paged_prefill_attention_ref(q, kp, vp, bt, offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

