"""Production meshes.

``make_production_mesh`` builds the assignment's target meshes:
  * single-pod: (16, 16) over ("data", "model") — 256 chips;
  * multi-pod:  (2, 16, 16) over ("pod", "data", "model") — 512 chips.

``make_pipeline_mesh`` carves an SSR ``stage`` axis out of the data axis for
the spatial/hybrid executor.

These are FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before first jax init while tests/benches see 1 device.

All mesh construction and ambient-mesh context handling routes through
``repro.backend.compat`` (jax 0.9.0 mesh APIs).
"""
from __future__ import annotations

import jax

from repro.backend import compat
from repro.backend.compat import use_mesh  # re-export (public API)


def _make_mesh(shape, axes):
    # Auto axis types (where the installed jax has them): we rely on GSPMD
    # propagation + constraints.
    return compat.make_mesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_pipeline_mesh(n_stages: int, *, model: int = 16, total: int = 256,
                       multi_pod: bool = False):
    """SSR spatial/hybrid mesh: ("stage", "data", "model").  The stage axis
    is carved out of the data axis of the production mesh."""
    if multi_pod:
        total = 512
    data = total // (n_stages * model)
    if data < 1 or n_stages * data * model != total:
        raise ValueError(
            f"make_pipeline_mesh: n_stages={n_stages} x model={model} does "
            f"not evenly divide the {total}-device budget (would silently "
            f"mis-factor the mesh); pick n_stages from the divisors of "
            f"{total // model}")
    return _make_mesh((n_stages, data, model), ("stage", "data", "model"))


def make_plan_mesh(plan, devices=None):
    """Stage-major mesh for an ``ExecutionPlan``: ("stage", data, model)
    with a *uniform* slot width per stage (a rectangular device mesh cannot
    give stages different widths — the plan records the replicate-padding
    waste of stages that asked for less, see ``StagePlan.replica_waste``).

    The slot width is ``len(devices) // n_stages`` capped at the plan's own
    ``stage_width``; leftover devices (budget not divisible by the stage
    count) are simply left out of the mesh.  The (data, model) split is the
    plan's common factorization (gcd of the stage tp's)."""
    import numpy as np

    devs = list(devices if devices is not None else jax.devices())
    S = plan.n_stages
    if len(devs) < S:
        raise ValueError(
            f"make_plan_mesh: the plan has {S} stages but only "
            f"{len(devs)} device(s) are available — every stage needs its "
            f"own mesh slot")
    width = max(len(devs) // S, 1)
    if plan.stage_width and plan.stage_width <= width:
        width = plan.stage_width
    assert S * width <= len(devs), (S, width, len(devs))
    data, model = plan.mesh_factors(width)
    arr = np.array(devs[:S * data * model]).reshape(S, data, model)
    return compat.make_mesh_on(arr, ("stage", "data", "model"))


def make_host_mesh(axes=("data", "model")):
    """Whatever devices exist locally, as a small mesh (tests/examples)."""
    n = len(jax.devices())
    if len(axes) == 1:
        return _make_mesh((n,), axes)
    # put everything on data
    return _make_mesh((n, 1), axes)
