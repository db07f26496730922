"""jax surface used by the repo, pinned to the installed jax 0.9.0.

The rest of the repo imports these helpers instead of reaching into
``jax.experimental.pallas.tpu``, ``jax.shard_map`` or the mesh
constructors directly, so a future jax move touches this file only.
There is one branch per surface: the API of jax 0.9.0.

Surfaces:
  * ``pltpu.CompilerParams`` and the Pallas TPU memory spaces
  * ``jax.make_mesh`` / ``Mesh`` with explicit ``AxisType.Auto`` axes
  * ``jax.set_mesh`` as the ambient-mesh context
  * ``jax.shard_map(..., axis_names=...)`` (partial-manual)
  * ``lax.pcast(..., to="varying")`` and the carried varying axes
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

SUPPORTED_RANGE = ("0.9.0", "0.9.0")


def jax_version() -> Tuple[int, ...]:
    """Parsed jax version, numeric components only (e.g. (0, 4, 37)).
    A pre-release/dev suffix contributes its leading digits then ends the
    parse: "0.7.0rc1" -> (0, 7, 0), "0.4.38.dev2025" -> (0, 4, 38)."""
    import re
    parts = []
    for p in jax.__version__.split("."):
        m = re.match(r"\d+", p)
        if not m:
            break
        parts.append(int(m.group()))
        if m.end() != len(p):
            break
    return tuple(parts)


# ---------------------------------------------------------------------------
# backend identity
# ---------------------------------------------------------------------------

def backend() -> str:
    """The default jax backend platform name ("cpu" / "tpu" / "gpu")."""
    return jax.default_backend()


def on_tpu() -> bool:
    return backend() == "tpu"


# ---------------------------------------------------------------------------
# Pallas TPU symbols
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pltpu():
    from jax.experimental.pallas import tpu as pltpu_mod
    return pltpu_mod


def tpu_compiler_params(*, dimension_semantics=None, **kwargs):
    """``pltpu.CompilerParams`` for ``pallas_call(compiler_params=...)``."""
    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = tuple(dimension_semantics)
    return _pltpu().CompilerParams(**kwargs)


def vmem_scratch(shape, dtype):
    """VMEM scratch allocation for ``pallas_call(scratch_shapes=...)``."""
    return _pltpu().VMEM(tuple(shape), dtype)


def smem_scratch(shape, dtype):
    """SMEM scratch allocation for ``pallas_call(scratch_shapes=...)``."""
    return _pltpu().SMEM(tuple(shape), dtype)


def prefetch_grid_spec(*, num_scalar_prefetch, grid, in_specs, out_specs,
                       scratch_shapes=()):
    """``pltpu.PrefetchScalarGridSpec`` — scalar-prefetch grid spec whose
    index maps can read int32 operands (e.g. block tables) before the
    kernel body runs.  Stable across the supported jax range; shimmed here
    so only ``repro.backend`` touches the pltpu namespace."""
    return _pltpu().PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=tuple(grid),
        in_specs=list(in_specs), out_specs=out_specs,
        scratch_shapes=list(scratch_shapes))


# ---------------------------------------------------------------------------
# meshes
# ---------------------------------------------------------------------------

def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Device mesh over all devices with explicit-Auto axis types (GSPMD
    propagation + sharding constraints)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_on(devices, axes) -> Mesh:
    """Mesh over an explicit device array (subset meshes — e.g. the
    plan-mesh's ``S * width`` devices out of a larger pod), with the same
    explicit-Auto axis types as ``make_mesh``."""
    arr = np.asarray(devices)
    return Mesh(arr, tuple(axes),
                axis_types=(jax.sharding.AxisType.Auto,) * arr.ndim)


def use_mesh(mesh):
    """Context manager placing ``mesh`` in ambient context."""
    return jax.set_mesh(mesh)


def make_abstract_mesh(shape: Sequence[int], names: Sequence[str]):
    """``jax.sharding.AbstractMesh`` of ``shape`` over axes ``names``."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(names))


def mesh_axis_size(mesh, name) -> int:
    """Product of the sizes of ``name`` (str or sequence of str) on any
    mesh flavour (Mesh / AbstractMesh) — 1 for absent axes."""
    names = (name,) if isinstance(name, str) else tuple(name)
    sizes = [mesh.shape[n] for n in names if n in mesh.shape]
    return int(np.prod(sizes)) if sizes else 1


# ---------------------------------------------------------------------------
# shard_map / varying-axes
# ---------------------------------------------------------------------------

def shard_map(f, *, mesh, in_specs, out_specs,
              manual_axes: Optional[frozenset] = None):
    """``jax.shard_map`` with only ``manual_axes`` manual (all mesh axes
    when None); the other axes stay auto (GSPMD)."""
    if manual_axes is None:
        manual_axes = frozenset(mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset(manual_axes))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it at
    start-up, and the cache stays there.  Otherwise it goes to one fixed
    directory of the checkout, ``<repo>/.jax_cache`` (git-ignored): the
    path is part of what a later run must find again, so it never comes
    from a temporary name, a pid or the time.  Call once at program
    start-up, never at import."""
    import os
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        path = os.path.join(repo, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def donating_jit(fun, *, donate_argnums=(), static_argnames=None):
    """``jax.jit(fun, donate_argnums=...)`` without the advisory
    "Some donated buffers were not usable" warning.

    Donation lets the serving hot loop update its cache in place, which
    halves the cache's peak footprint.  Callers rebind every donated
    argument to the returned value and never read the argument again.
    Where XLA cannot reuse a donated buffer for an output it warns on
    every call; that warning is silenced here."""
    import warnings

    kwargs = {}
    if static_argnames is not None:
        kwargs["static_argnames"] = static_argnames
    jitted = jax.jit(fun, donate_argnums=tuple(donate_argnums), **kwargs)

    @functools.wraps(fun)
    def call(*args, **kw):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*[Dd]onated buffers.*",
                category=UserWarning)
            return jitted(*args, **kw)

    call.lower = jitted.lower
    return call


def pcast_varying(x, axes):
    """``lax.pcast(x, axes, to="varying")``: mark a value as varying over
    the manual mesh ``axes`` (a no-op for an empty ``axes``)."""
    from jax import lax
    axes = tuple(axes)
    return lax.pcast(x, axes, to="varying") if axes else x


def kernel_out(shape, dtype, *inputs) -> jax.ShapeDtypeStruct:
    """``pallas_call`` output shape that varies over the manual mesh axes
    its ``inputs`` vary over.  Under ``shard_map`` (which checks varying
    axes) a kernel's output must say so; outside, the axes are empty."""
    return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                vma=frozenset(varying_axes(*inputs)))


def varying_axes(*trees) -> Tuple[str, ...]:
    """The manual mesh axes any leaf of ``trees`` varies over — empty
    outside ``shard_map``.  A scan carry built from constants must start
    with these axes when its body mixes it with such leaves."""
    axes = set()
    for leaf in jax.tree.leaves(trees):
        axes |= set(getattr(jax.typeof(leaf), "vma", ()))
    return tuple(sorted(axes))


__all__ = [
    "SUPPORTED_RANGE", "jax_version", "backend", "on_tpu",
    "tpu_compiler_params", "vmem_scratch", "smem_scratch",
    "prefetch_grid_spec",
    "make_mesh", "make_mesh_on", "use_mesh", "make_abstract_mesh",
    "mesh_axis_size",
    "shard_map", "pcast_varying", "varying_axes", "kernel_out",
    "PartitionSpec",
    "donating_jit", "use_compile_cache",
]
