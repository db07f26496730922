"""Compile rehearsals: the main path's Pallas kernels at real widths,
compiled for one described TPU v5e chip (no chip attached).

Interpret mode runs a kernel's body on the CPU but not the TPU compiler's
rules (block tiling, VMEM limits, supported ops).  These tests lower and
compile each kernel for a v5e described by ``get_topology_desc`` and
check that the program really holds a Mosaic kernel
(``tpu_custom_call``).  Shapes are yi-6b decode/prefill widths (kv heads
4, 8 query heads per kv head, head_dim 128) and xlstm-125m for the
linear scan.  The topology is described inside a fixture, never at
import, and every test skips where it cannot be described.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# yi-6b: 32 query heads over 4 kv heads, head_dim 128
HKV, G, D = 4, 8, 128
SLOTS, MAX_SEQ = 8, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    compile for a described device is written to the cache but cannot be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_has_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool(dev, page, dtype, nb):
    n = SLOTS * nb + 1                      # + sink page
    return (_sds((n, HKV, page, D), dtype, dev),
            _sds((SLOTS, nb), jnp.int32, dev))


def test_paged_attention_grouped_compiles(one_chip):
    from repro.kernels.paged_attention import paged_attention_grouped
    page = 16
    pool, bt = _pool(one_chip, page, jnp.bfloat16, MAX_SEQ // page)
    q = _sds((SLOTS, HKV, G, D), jnp.bfloat16, one_chip)
    lens = _sds((SLOTS,), jnp.int32, one_chip)
    _compile_has_kernel(paged_attention_grouped, q, pool, pool, bt, lens)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_fused_paged_decode_grouped_compiles(one_chip, kv):
    from repro.kernels.paged_attention import fused_paged_decode_grouped
    page = 16 if kv == "bf16" else 32       # int8 tiles are (32, 128)
    dtype = jnp.bfloat16 if kv == "bf16" else jnp.int8
    pool, bt = _pool(one_chip, page, dtype, MAX_SEQ // page)
    q = _sds((SLOTS, HKV, G, D), jnp.bfloat16, one_chip)
    fresh = _sds((SLOTS, HKV, D), jnp.bfloat16, one_chip)
    pos = _sds((SLOTS,), jnp.int32, one_chip)
    if kv == "int8":
        sc = _sds(pool.shape[:-1], jnp.float32, one_chip)

        def fn(q, kn, vn, kp, vp, bt, pos, ks, vs):
            return fused_paged_decode_grouped(
                q, kn, vn, kp, vp, bt, pos, theta=5e6, k_scales=ks,
                v_scales=vs)
        _compile_has_kernel(fn, q, fresh, fresh, pool, pool, bt, pos, sc,
                            sc)
    else:
        def fn(q, kn, vn, kp, vp, bt, pos):
            return fused_paged_decode_grouped(q, kn, vn, kp, vp, bt, pos,
                                              theta=5e6)
        _compile_has_kernel(fn, q, fresh, fresh, pool, pool, bt, pos)


@pytest.mark.parametrize("chunk", [128, 1024, 1008])
def test_paged_prefill_attention_grouped_compiles(one_chip, chunk):
    """Chunk lengths the engine prefills at (prompts padded to the
    16-token bucket); 1008 is not a multiple of the query tile."""
    from repro.kernels.paged_attention import (
        paged_prefill_attention_grouped)
    page = 16
    nb = MAX_SEQ // page
    pool = _sds((nb + 1, HKV, page, D), jnp.bfloat16, one_chip)
    bt = _sds((1, nb), jnp.int32, one_chip)
    q = _sds((1, HKV, G, chunk, D), jnp.bfloat16, one_chip)
    off = _sds((), jnp.int32, one_chip)
    compiled = _compile_has_kernel(paged_prefill_attention_grouped, q, pool,
                                   pool, bt, off)
    # VMEM per grid step is bounded by the query tile, not the chunk
    assert compiled.memory_analysis() is not None


def test_flash_attention_bhsd_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention_bhsd
    s = 1024
    q = _sds((1, HKV * G, s, D), jnp.bfloat16, one_chip)
    kv = _sds((1, HKV, s, D), jnp.bfloat16, one_chip)
    pos = _sds((s,), jnp.int32, one_chip)

    def fn(q, k, v, qp, kp, kvalid):
        return flash_attention_bhsd(q, k, v, qp, kp, kvalid, causal=True)
    _compile_has_kernel(fn, q, kv, kv, pos, pos, pos)


def test_linear_scan_compiles(one_chip):
    """xlstm-125m: mLSTM inner width 2 * 768 = 1536 features."""
    from repro.kernels.linear_scan import linear_scan
    a = _sds((2, 512, 1536), jnp.float32, one_chip)
    _compile_has_kernel(linear_scan, a, a)


def test_flash_attention_compiles_inside_shard_map(topo, one_chip):
    """The spatial plan runs the model's attention inside ``shard_map``,
    which checks varying mesh axes: the kernel's output must declare the
    axes its inputs vary over, or tracing fails before the compiler."""
    from jax.sharding import PartitionSpec as P
    from repro.backend import compat
    from repro.kernels.flash_attention import flash_attention_bhsd
    mesh = compat.make_mesh_on(topo.devices[:1], ("stage",))
    spec = P("stage")
    s = 256
    q = _sds((1, HKV * G, s, D), jnp.bfloat16,
             jax.sharding.NamedSharding(mesh, spec))
    kv = _sds((1, HKV, s, D), jnp.bfloat16,
              jax.sharding.NamedSharding(mesh, spec))

    def attend(q, k, v):
        pos = jnp.arange(s)
        return flash_attention_bhsd(q, k, v, pos, pos,
                                    jnp.ones((s,), jnp.int32), causal=True)
    fn = compat.shard_map(attend, mesh=mesh, in_specs=(spec,) * 3,
                          out_specs=spec)
    _compile_has_kernel(fn, q, kv, kv)      # one_chip: cache off
