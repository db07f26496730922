"""Version-portability + kernel-dispatch subsystem.

``repro.backend.compat``   — the ONLY place that touches jax APIs which
                             move between versions (pltpu symbols, meshes,
                             shard_map, varying axes; jax 0.9.0).
``repro.backend.dispatch`` — the kernel-dispatch front door selecting
                             Pallas-TPU / Pallas-interpret / jnp-reference
                             per detected backend (``REPRO_KERNELS``
                             overrides).
"""
from repro.backend import compat, dispatch
from repro.backend.compat import (make_abstract_mesh, make_mesh,
                                  mesh_axis_size, use_mesh)
from repro.backend.dispatch import (dispatch_flash_attention,
                                    dispatch_layernorm, dispatch_linear_scan,
                                    dispatch_matmul, kernel_path)

__all__ = [
    "compat", "dispatch", "make_mesh", "make_abstract_mesh",
    "mesh_axis_size", "use_mesh", "kernel_path", "dispatch_matmul",
    "dispatch_flash_attention", "dispatch_linear_scan", "dispatch_layernorm",
]
