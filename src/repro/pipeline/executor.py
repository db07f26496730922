"""SSR spatial/hybrid runtime executor — plan-driven microbatch pipeline.

This is the *execution* counterpart of the SSR scheduler.  The unit of
work is an ``ExecutionPlan`` (``repro.plan``): ordered stage slices over
the scanned layer stack — **not necessarily equal** — each on one slot of
a ``("stage", "data", "model")`` mesh, with ``n_microbatches`` in flight
per round (spatial) and ``n_rounds`` rounds streamed back-to-back
(sequential).  Microbatches move between stages via ``collective_permute``
over the ICI — the on-chip-forwarding analogue (no host round trip).
Stage-internal sharding still uses the data/model axes (they are `auto`
axes inside the shard_map), so each "SSR accelerator" is itself a DPxTP
submesh — exactly the paper's Acc-Customization degree of freedom.

Uneven stages: every stage's parameter stack is padded to
``plan.max_groups`` entries by a clamped gather (repeating the stage's
last real group, so padded compute stays finite) and the dead entries are
masked inside ``run_stack`` — a dead group passes activations through
unchanged.

Bubble accounting matches the paper's Fig. 1(b): M microbatches through S
stages take (M + S - 1) stage-times.

The legacy ``(n_stages, n_microbatches)`` scalar API survives as thin
shims that lower a uniform plan.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.backend import compat
from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.plan.ir import ExecutionPlan, uniform_plan


def stage_params_reshape(stack_params, n_stages: int):
    """(num_groups, ...) stacked params -> (n_stages, groups_per_stage, ...).
    Uniform-split legacy helper; uneven plans use ``plan_stage_params``."""
    def f(x):
        g = x.shape[0]
        assert g % n_stages == 0, (g, n_stages)
        return x.reshape((n_stages, g // n_stages) + x.shape[1:])
    return jax.tree.map(f, stack_params)


def plan_stage_params(stack_params, plan: ExecutionPlan):
    """(num_groups, ...) stacked params -> (S, max_groups, ...) per the
    plan's stage slices: a clamped gather pads short stages by repeating
    their last real group (masked out at runtime)."""
    idx = plan.group_index_matrix()
    if np.array_equal(idx.ravel(), np.arange(plan.num_groups)):
        # equal contiguous stages: a reshape, which keeps a stage sharding
        # of the group axis where it is (a gather would copy the stack)
        return jax.tree.map(
            lambda x: jnp.asarray(x).reshape(idx.shape + x.shape[1:]),
            stack_params)
    return jax.tree.map(lambda x: jnp.asarray(x)[jnp.asarray(idx)],
                        stack_params)


def run_stage(cfg: ModelConfig, stage_params, x, *, cache=None,
              cache_index=None, positions=None, collect_state: bool = False,
              group_mask=None, attend_cache: bool = False,
              block_tables=None, write_tables=None):
    """Execute ONE plan stage's (unpadded) group slice — the per-stage
    entry the serving engine steps instead of the whole-plan
    ``plan_forward``.  Returns (y, new_cache, aux).

    stage_params: the stage's group slice of the stacked params (leading
      axis = the stage's n_groups, exact — not padded to max_groups).
    cache / cache_index: the stage's group-range cache slice and token
      offset(s) for prefill / decode stepping (``collect_state=True``
      returns the updated slice).  ``attend_cache=True`` is the
      chunked-prefill continuation mode (fresh chunk attends the cached
      tokens — see ``models.layers.multi_head_attention``).
    group_mask: stateless padded-stage masking (the pipelined forward
      path) — mutually exclusive with ``cache``.
    block_tables: logical->physical page map when ``cache`` is a paged
      (pool-backed) slice — the paged decode stage walk.
    write_tables: fresh-blocks-only page map for paged chunked prefill
      (shared warm blocks carry the sentinel so the chunk's page writes
      drop on them — see ``models.layers.multi_head_attention``).
    """
    return T.run_stack(stage_params, x, cfg, positions=positions,
                       causal=True, cache=cache, cache_index=cache_index,
                       collect_state=collect_state, group_mask=group_mask,
                       attend_cache=attend_cache, block_tables=block_tables,
                       write_tables=write_tables)


def pipeline_spec(stack_params_staged, mesh: Mesh):
    """Shard the leading stage axis over 'stage'; leave the rest to auto."""
    def f(x):
        return NamedSharding(mesh, P("stage"))
    return jax.tree.map(f, stack_params_staged)


def make_plan_runner(cfg: ModelConfig, mesh: Mesh, plan: ExecutionPlan
                     ) -> Callable:
    """Returns pipelined(params_staged, group_mask, x_mb) -> y_mb.

    params_staged: stack params gathered to (S, max_groups, ...) via
      ``plan_stage_params``, stage-sharded.
    group_mask: (S, max_groups) 0/1 — live vs padded groups per stage.
    x_mb: (M_total, mb, seq, d_model) microbatched embedded activations,
      M_total = plan.n_microbatches * plan.n_rounds.
    y_mb: (M_total, mb, seq, d_model) final hidden states.
    """
    S = plan.n_stages
    M = plan.total_microbatches

    def stage_apply(p_local, m_local, x):
        y, _, _ = T.run_stack(p_local, x, cfg, group_mask=m_local)
        return y

    if S == 1:
        # one stage: nothing to forward between stages, so no manual
        # axis — GSPMD shards the stage over the whole mesh.  (A
        # collective over a size-1 manual axis is refused by XLA's SPMD
        # partitioner when the other axes stay auto.)
        def sequential(p_staged, mask, x_all):
            p0 = jax.tree.map(lambda a: a[0], p_staged)
            return lax.map(lambda x: stage_apply(p0, mask[0], x), x_all)
        return sequential

    def inner(p_local, m_local, x_all):
        # p_local leaves: (1, max_groups, ...) — this stage's padded groups.
        p_local = jax.tree.map(lambda a: a[0], p_local)
        m_local = m_local[0]
        stage_id = lax.axis_index("stage")
        state = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros_like(x_all)
        # carries become stage-varying inside the loop: mark them up front
        state = compat.pcast_varying(state, ("stage",))
        outputs = compat.pcast_varying(outputs, ("stage",))

        def tick(t, carry):
            state, outputs = carry
            inp = lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            cur = jnp.where(stage_id == 0, inp, state)
            out = stage_apply(p_local, m_local, cur)
            # last stage banks its finished microbatch t-(S-1)
            oidx = jnp.clip(t - (S - 1), 0, M - 1)
            prev = lax.dynamic_index_in_dim(outputs, oidx, 0, keepdims=False)
            write = jnp.where((stage_id == S - 1) & (t >= S - 1), out, prev)
            outputs = lax.dynamic_update_index_in_dim(outputs, write, oidx, 0)
            # forward over ICI to the next stage (on-chip forwarding)
            state = lax.ppermute(out, "stage",
                                 [(i, (i + 1) % S) for i in range(S)])
            return state, outputs

        state, outputs = lax.fori_loop(0, M + S - 1, tick, (state, outputs))
        # only the last stage holds real outputs: mask + sum-replicate.
        outputs = jnp.where(stage_id == S - 1, outputs, 0.0)
        outputs = lax.psum(outputs, "stage")
        return outputs

    # Only the manual 'stage' axis appears in specs; data/model sharding of
    # activations is handled by GSPMD (auto axes) inside each stage.  Axes
    # of size 1 are manual too: GSPMD has nothing to split over them, and
    # a Pallas (Mosaic) kernel compiles only where no axis is auto.
    batch_in = P(None, None, None, None)
    manual = {"stage"} | {a for a in mesh.axis_names if mesh.shape[a] == 1}
    pipelined = compat.shard_map(
        inner, mesh=mesh,
        in_specs=(P("stage"), P("stage"), batch_in),
        out_specs=batch_in,
        manual_axes=frozenset(manual),
    )
    return pipelined


def plan_forward(model, params, batch, mesh: Mesh, plan: ExecutionPlan):
    """End-to-end plan execution: embed (data-parallel) -> pipelined
    uneven stages -> head.  batch: {'tokens' | 'embeds': ...}."""
    from repro.models import layers as L
    cfg = model.cfg
    assert plan.num_groups == cfg.num_groups, (plan.num_groups,
                                               cfg.num_groups)
    if "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
    else:
        x = L.embed(params["embed"], batch["tokens"], cfg).astype(cfg.dtype)
    B, seq, d = x.shape
    M = plan.total_microbatches
    assert B % M == 0, (B, M)
    x_mb = x.reshape(M, B // M, seq, d)

    staged = plan_stage_params(params["stack"], plan)
    mask = jnp.asarray(plan.group_mask_matrix())
    runner = make_plan_runner(cfg, mesh, plan)
    y_mb = runner(staged, mask, x_mb)
    y = y_mb.reshape(B, seq, d)
    y = L.apply_norm(params["final_norm"], y, cfg)
    return L.logits_head(params.get("embed"), params.get("head"), y, cfg)


# ---------------------------------------------------------------------------
# legacy scalar API — thin shims over a uniform plan
# ---------------------------------------------------------------------------

def make_pipeline_runner(cfg: ModelConfig, mesh: Mesh, n_stages: int,
                         n_microbatches: int) -> Callable:
    """Legacy runner: pipelined(params_staged, x_mb) -> y_mb with equal
    (S, G/S, ...) stage slices — a uniform plan under the hood."""
    plan = uniform_plan(cfg.num_groups, n_stages, n_microbatches)
    runner = make_plan_runner(cfg, mesh, plan)
    mask = jnp.asarray(plan.group_mask_matrix())

    def pipelined(params_staged, x_mb):
        return runner(params_staged, mask, x_mb)
    return pipelined


def pipeline_forward(model, params, batch, mesh: Mesh, n_stages: int,
                     n_microbatches: int):
    """Legacy end-to-end forward: lowers to a uniform plan."""
    plan = uniform_plan(model.cfg.num_groups, n_stages, n_microbatches)
    return plan_forward(model, params, batch, mesh, plan)
