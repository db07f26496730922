"""The version-portability layer: the shim must pick working symbols on the
installed jax, the dispatch front door must fall back to interpret/ref
off-TPU with ref-parity, and no jax version probe / pltpu construction may
exist outside ``repro.backend``."""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.backend import compat, dispatch
from repro.kernels import ref as R

REPO = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# shim sanity on the installed jax
# ---------------------------------------------------------------------------

def test_version_parses_into_supported_range():
    v = compat.jax_version()
    assert len(v) >= 2 and v >= (0, 4), v


def test_tpu_compiler_params_constructs():
    p = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert tuple(p.dimension_semantics) == ("parallel", "arbitrary")


def test_vmem_scratch_constructs():
    s = compat.vmem_scratch((8, 128), jnp.float32)
    assert s is not None


def test_make_mesh_and_use_mesh_context():
    mesh = compat.make_mesh((len(jax.devices()), 1), ("data", "model"))
    assert mesh.shape["model"] == 1
    with compat.use_mesh(mesh):
        y = jax.jit(lambda x: x * 2)(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(y), 2.0)


def test_make_abstract_mesh_both_signatures():
    m = compat.make_abstract_mesh((4, 2), ("data", "model"))
    assert m.shape["data"] == 4 and m.shape["model"] == 2
    assert tuple(m.axis_names) == ("data", "model")
    assert compat.mesh_axis_size(m, ("data", "model")) == 8
    assert compat.mesh_axis_size(m, "absent") == 1


def test_shard_map_single_device_roundtrip():
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((len(jax.devices()),), ("data",))
    f = compat.shard_map(lambda x: x + 1, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"),
                         manual_axes=frozenset({"data"}))
    y = f(jnp.zeros((len(jax.devices()) * 2,)))
    np.testing.assert_allclose(np.asarray(y), 1.0)


def test_pcast_varying_is_safe_identity_semantics():
    # outside shard_map nothing varies: no axes, and the cast is identity
    x = jnp.ones((2, 2))
    assert compat.varying_axes(x, {"w": x}) == ()
    np.testing.assert_allclose(np.asarray(compat.pcast_varying(x, ())),
                               np.asarray(x))
    # inside, a constant cast to the manual axis carries it like the input
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((len(jax.devices()),), ("s",))

    def body(a):
        assert compat.varying_axes(a) == ("s",)
        z = compat.pcast_varying(jnp.zeros((), jnp.float32),
                                 compat.varying_axes(a))
        assert compat.varying_axes(z) == ("s",)
        return a + z

    f = compat.shard_map(body, mesh=mesh, in_specs=P("s"), out_specs=P("s"))
    y = f(jnp.ones((len(jax.devices()) * 2,)))
    np.testing.assert_allclose(np.asarray(y), 1.0)


# ---------------------------------------------------------------------------
# dispatch front door: off-TPU fallback + ref parity
# ---------------------------------------------------------------------------

@pytest.fixture
def force_path(monkeypatch):
    def _force(path):
        monkeypatch.setenv("REPRO_KERNELS", path)
        jax.clear_caches()
    yield _force
    jax.clear_caches()


def test_kernel_path_defaults_off_tpu(monkeypatch):
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    expected = "pallas" if compat.on_tpu() else "ref"
    assert dispatch.kernel_path() == expected


def test_kernel_path_env_override(monkeypatch):
    for path in ("ref", "interpret", "pallas"):
        monkeypatch.setenv("REPRO_KERNELS", path)
        assert dispatch.kernel_path() == path


def test_kernel_path_rejects_unknown_value_listing_choices(monkeypatch):
    """A typo'd REPRO_KERNELS must fail loudly (silently falling back to
    the jnp oracle would fake a kernel benchmark), naming the choices —
    paths AND the per-kernel override names."""
    monkeypatch.setenv("REPRO_KERNELS", "garbage")
    with pytest.raises(ValueError, match=r"garbage.*auto.*pallas.*"
                                         r"interpret.*ref.*"
                                         r"fused_paged_decode"):
        dispatch.kernel_path()


def test_kernel_path_per_kernel_override(monkeypatch):
    """REPRO_KERNELS is a comma-separated list: one base path plus
    per-kernel 'name=path' overrides routed by kernel name."""
    monkeypatch.setenv("REPRO_KERNELS",
                       "ref,fused_paged_decode=interpret,matmul=pallas")
    assert dispatch.kernel_path() == "ref"
    assert dispatch.kernel_path("paged_attention") == "ref"
    assert dispatch.kernel_path("fused_paged_decode") == "interpret"
    assert dispatch.kernel_path("matmul") == "pallas"
    # override-only form: base stays auto
    monkeypatch.setenv("REPRO_KERNELS", "fused_paged_decode=ref")
    assert dispatch.kernel_path("fused_paged_decode") == "ref"


def test_kernel_path_rejects_bad_override(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref,not_a_kernel=ref")
    with pytest.raises(ValueError, match=r"not_a_kernel.*fused_paged"):
        dispatch.kernel_path()
    monkeypatch.setenv("REPRO_KERNELS", "ref,matmul=fast")
    with pytest.raises(ValueError, match=r"matmul=fast"):
        dispatch.kernel_path()


@pytest.mark.parametrize("path", ["ref", "interpret"])
def test_dispatch_matmul_ref_parity(path, force_path):
    force_path(path)
    r = np.random.default_rng(0)
    x = jnp.asarray(r.standard_normal((128, 128)) * 0.1, jnp.float32)
    w = jnp.asarray(r.standard_normal((128, 128)) * 0.1, jnp.float32)
    b = jnp.asarray(r.standard_normal((128,)) * 0.1, jnp.float32)
    out = dispatch.dispatch_matmul(x, w, b, activation="gelu")
    ref = R.matmul_fused_ref(x, w, b, activation="gelu")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("path", ["ref", "interpret"])
def test_dispatch_flash_attention_ref_parity(path, force_path):
    force_path(path)
    r = np.random.default_rng(1)
    b, h, s, d = 1, 2, 128, 128
    # model layout (B, S, H, D)
    q = jnp.asarray(r.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(r.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(r.standard_normal((b, s, h, d)), jnp.float32)
    pos = jnp.arange(s)
    out = dispatch.dispatch_flash_attention(q, k, v, q_pos=pos, k_pos=pos,
                                            causal=True)
    ref = R.flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        pos, pos, jnp.ones((s,), jnp.int32), causal=True)
    ref = jnp.swapaxes(ref, 1, 2).reshape(b, s, h * d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("path", ["ref", "interpret"])
def test_dispatch_paged_attention_ref_parity(path, force_path):
    """The paged decode kernel (block-table gather via scalar prefetch)
    matches the jnp oracle on CPU — including unmapped table entries and
    short lengths."""
    force_path(path)
    r = np.random.default_rng(4)
    b, h, hk, d = 3, 4, 2, 128
    n, page, nb = 8, 16, 4
    q = jnp.asarray(r.standard_normal((b, 1, h, d)), jnp.float32)
    kp = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    vp = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    bt = jnp.asarray([[3, 1, n, n], [5, 2, 7, n], [0, n, n, n]], jnp.int32)
    lens = jnp.asarray([20, 37, 3], jnp.int32)
    out = dispatch.dispatch_paged_attention(q, kp, vp, bt, lens)
    qg = q[:, 0].reshape(b, hk, h // hk, d)
    ref = R.paged_attention_ref(qg, kp, vp, jnp.clip(bt, 0, n - 1),
                                lens).reshape(b, 1, h * d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("path", ["ref", "interpret"])
@pytest.mark.parametrize("offset", [0, 10])
def test_dispatch_paged_prefill_attention_ref_parity(path, offset,
                                                    force_path):
    """The paged prefill kernel (suffix queries attending the full mapped
    prefix through the block table) matches the jnp oracle — cold
    (offset=0) and warm (offset>0), with a sentinel pad entry whose rows
    the causal mask must exclude."""
    force_path(path)
    r = np.random.default_rng(5)
    b, h, hk, d = 1, 4, 2, 128
    n, page = 8, 8
    s = 8
    q = jnp.asarray(r.standard_normal((b, s, h, d)), jnp.float32)
    kp = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    vp = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    bt = jnp.asarray([[3, 1, 6, n]], jnp.int32)  # covers offset+s, 1 pad
    out = dispatch.dispatch_paged_prefill_attention(q, kp, vp, bt, offset)
    qg = jnp.swapaxes(q, 1, 2).reshape(b, hk, h // hk, s, d)
    ref = R.paged_prefill_attention_ref(qg, kp, vp, bt, offset)
    ref = jnp.swapaxes(ref.reshape(b, h, s, d), 1, 2).reshape(b, s, h * d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("path", ["ref", "interpret"])
def test_dispatch_linear_scan_ref_parity(path, force_path):
    force_path(path)
    r = np.random.default_rng(2)
    a = jnp.asarray(r.uniform(0.5, 0.999, (2, 128, 128)), jnp.float32)
    b = jnp.asarray(r.standard_normal((2, 128, 128)), jnp.float32)
    out = dispatch.dispatch_linear_scan(a, b)
    ref = R.linear_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("path", ["ref", "interpret"])
def test_dispatch_layernorm_ref_parity(path, force_path):
    force_path(path)
    r = np.random.default_rng(3)
    x = jnp.asarray(r.standard_normal((128, 256)), jnp.float32)
    s = jnp.asarray(r.standard_normal((256,)), jnp.float32)
    b = jnp.asarray(r.standard_normal((256,)), jnp.float32)
    for kind in ("rmsnorm", "layernorm"):
        out = dispatch.dispatch_layernorm(x, s, b, kind=kind)
        ref = R.norm_onepass_ref(x, s, b, kind=kind)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_use_scan_kernel_follows_path(force_path):
    force_path("ref")
    assert not dispatch.use_scan_kernel()
    force_path("interpret")
    assert dispatch.use_scan_kernel()


# ---------------------------------------------------------------------------
# source guard: version probes / pltpu stay inside the compat layer
# ---------------------------------------------------------------------------

# constructed so this file doesn't match its own patterns
_FORBIDDEN = [
    ("pltpu" + ".", "pallas-TPU symbol construction"),
    ("Axis" + "Type", "jax.sharding.AxisType probe"),
    ("hasattr(jax" + ",", "jax API version probe"),
    ("hasattr(jax" + ".", "jax API version probe"),
    ("jax" + ".__version__", "jax version read"),
    ("default_" + "backend", "backend probe"),
    ("pallas import" + " tpu", "pltpu import"),
]
_ALLOWED = {os.path.join("src", "repro", "backend", "compat.py")}


def _py_sources():
    for root in ("src", "tests", "benchmarks", "examples"):
        base = os.path.join(REPO, root)
        for dirpath, _, files in os.walk(base):
            for fn in files:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def test_no_version_probes_outside_compat():
    this_file = os.path.abspath(__file__)
    offenders = []
    for path in _py_sources():
        rel = os.path.relpath(path, REPO)
        if rel in _ALLOWED or os.path.abspath(path) == this_file:
            continue
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for i, line in enumerate(text.splitlines(), 1):
            for pat, why in _FORBIDDEN:
                if pat in line:
                    offenders.append(f"{rel}:{i}: {why}: {line.strip()}")
    assert not offenders, "\n".join(offenders)


@pytest.mark.parametrize("path", ["ref", "interpret"])
def test_dispatch_fused_paged_decode_ref_parity(path, force_path):
    """The fused RoPE+write+attend decode kernel matches the jnp oracle
    through the dispatch front door — output AND the written pools.
    Tables are disjoint per slot (the engine's ownership invariant the
    in-place kernel writes rely on)."""
    force_path(path)
    r = np.random.default_rng(6)
    b, h, hk, d = 2, 4, 2, 128
    page, nb = 32, 2
    n = b * nb + 1                                   # + sink page
    q = jnp.asarray(r.standard_normal((b, 1, h, d)), jnp.float32)
    kn = jnp.asarray(r.standard_normal((b, 1, hk, d)), jnp.float32)
    vn = jnp.asarray(r.standard_normal((b, 1, hk, d)), jnp.float32)
    kp = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    vp = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    bt = jnp.asarray(r.permutation(b * nb).reshape(b, nb), jnp.int32)
    pos = jnp.asarray([page - 1, page + 7], jnp.int32)
    out, nkp, nvp, ks, vs = dispatch.dispatch_fused_paged_decode(
        q, kn, vn, kp, vp, bt, pos, theta=10000.0)
    assert ks is None and vs is None
    qg = q[:, 0].reshape(b, hk, h // hk, d)
    ro, rkp, rvp, _, _ = R.fused_paged_decode_ref(
        qg, kn[:, 0], vn[:, 0], kp, vp, bt, pos, theta=10000.0)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ro.reshape(b, 1, h * d)),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(nkp), np.asarray(rkp),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(nvp), np.asarray(rvp),
                               atol=2e-5, rtol=2e-5)


def test_dispatch_paged_attention_int8_dequant_parity(force_path):
    """int8 pools route through the dequantizing oracle on every path
    and match an fp pool holding the dequantized values exactly."""
    force_path("interpret")     # int8 still forces the ref fallback here
    r = np.random.default_rng(7)
    b, h, hk, d = 2, 4, 2, 128
    n, page, nb = 5, 16, 2
    q = jnp.asarray(r.standard_normal((b, 1, h, d)), jnp.float32)
    kf = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    vf = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    kq, ks = R.quantize_int8_rows(kf)
    vq, vs = R.quantize_int8_rows(vf)
    bt = jnp.asarray([[3, 1], [0, 2]], jnp.int32)
    lens = jnp.asarray([20, 9], jnp.int32)
    out = dispatch.dispatch_paged_attention(q, kq, vq, bt, lens,
                                            k_scales=ks, v_scales=vs)
    deq = dispatch.dispatch_paged_attention(
        q, R.dequantize_int8(kq, ks), R.dequantize_int8(vq, vs), bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(deq),
                               atol=2e-6, rtol=2e-6)


# ---------------------------------------------------------------------------
# pallas path: no silent fallback; every path taken is recorded
# ---------------------------------------------------------------------------

def _paged_operands(d, page, hk=2, h=4, b=2, nb=2):
    r = np.random.default_rng(8)
    n = b * nb + 1
    q = jnp.asarray(r.standard_normal((b, 1, h, d)), jnp.float32)
    kp = jnp.asarray(r.standard_normal((n, hk, page, d)), jnp.float32)
    bt = jnp.asarray(np.arange(b * nb).reshape(b, nb), jnp.int32)
    return q, kp, bt


@pytest.mark.parametrize("kernel", ["paged_attention", "fused_paged_decode",
                                    "paged_prefill_attention"])
def test_pallas_path_off_tile_raises_naming_kernel(kernel, force_path):
    """head_dim 64 is off the TPU lane tile: the pallas path must refuse
    with the kernel's name and the shape, not run the jnp reference."""
    force_path("pallas")
    q, kp, bt = _paged_operands(d=64, page=16)
    lens = jnp.asarray([5, 9], jnp.int32)
    off_tile = kernel + r".*\(2, 1, 4, 64\).*\(5, 2, 16, 64\)"
    with pytest.raises(ValueError, match=off_tile):
        if kernel == "paged_attention":
            dispatch.dispatch_paged_attention(q, kp, kp, bt, lens)
        elif kernel == "fused_paged_decode":
            kn = q[:, :, :2]
            dispatch.dispatch_fused_paged_decode(q, kn, kn, kp, kp, bt,
                                                 lens, theta=1e4)
        else:
            dispatch.dispatch_paged_prefill_attention(q, kp, kp, bt[:1],
                                                      jnp.int32(3))


def test_record_paths_reports_reference_runs(force_path):
    """int8 pools and paged verify run the jnp reference by design, on
    every path; the recorder names them ``ref`` next to the kernels
    that really ran."""
    force_path("interpret")
    q, kp, bt = _paged_operands(d=128, page=16)
    kq, ks = R.quantize_int8_rows(kp)
    lens = jnp.asarray([5, 20], jnp.int32)
    seen = {}
    with dispatch.record_paths(seen):
        dispatch.dispatch_paged_attention(q, kp, kp, bt, lens)
        dispatch.dispatch_paged_prefill_attention(
            q[:1], kq, kq, bt[:1], jnp.int32(0), k_scales=ks, v_scales=ks)
        dispatch.dispatch_paged_verify_attention(q, kp, kp, bt, lens)
    assert seen == {"paged_attention": {"interpret"},
                    "paged_prefill_attention": {"ref"},
                    "paged_verify_attention": {"ref"}}
    # outside the block nothing is recorded
    dispatch.dispatch_paged_attention(q, kp, kp, bt, lens)
    assert seen["paged_attention"] == {"interpret"}


def test_use_compile_cache_honours_env_else_fixed_path(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compat.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None   # not ours
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compat.use_compile_cache()
        assert path == os.path.join(os.path.abspath(REPO), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compat.use_compile_cache() == path           # fixed, stable
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
