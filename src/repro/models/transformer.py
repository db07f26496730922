"""Block assembly and full-model forward passes.

The layer stack is organized as ``num_groups`` repetitions of the config's
``block_pattern`` (one period).  Per-position parameters are stacked with a
leading group axis and the stack is executed with ``jax.lax.scan`` — this
keeps the lowered HLO size O(pattern) instead of O(num_layers), which is what
makes 80-layer 72B dry-run compiles tractable and is also the deployment-
grade structure (MaxText-style).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.backend import compat
from repro.configs.base import BlockSpec, ModelConfig
from repro.models import layers as L
from repro.models import ssm as S

# ---------------------------------------------------------------------------
# sharding constraint helper (mesh-agnostic: no-op without an ambient mesh)
# ---------------------------------------------------------------------------

BATCH_AXES = ("pod", "data")


def constrain(x, spec_axes):
    """Apply a sharding constraint using only the Auto axes of the ambient
    abstract mesh (inside ``shard_map`` its manual axes are not
    constrainable).  spec_axes: tuple of axis-name-or-None per dim."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return x
    if mesh is None or not mesh.axis_names:
        return x
    names = set(mesh.auto_axes)
    if not names:
        return x

    def _filt(a):
        if a is None:
            return None
        if isinstance(a, tuple):
            kept = tuple(n for n in a if n in names)
            return kept if kept else None
        return a if a in names else None

    spec = P(*[_filt(a) for a in spec_axes])
    return jax.lax.with_sharding_constraint(x, spec)


def shard_act(x):
    """(B, S, D) activations: batch over (pod,data); with REPRO_SP=1 the
    residual stream is additionally SEQUENCE-sharded over 'model' between
    blocks (Megatron sequence parallelism): norms/elementwise run on 1/tp of
    the tokens and the per-block TP all-reduce becomes reduce-scatter +
    all-gather — half the ICI traffic (§Perf iteration)."""
    import os
    if x.ndim == 3:
        if os.environ.get("REPRO_SP", "0") == "1" and x.shape[1] > 1:
            try:
                mesh = jax.sharding.get_abstract_mesh()
                if mesh is not None and "model" in (mesh.axis_names or ()) \
                        and x.shape[1] % mesh.shape["model"] == 0:
                    return constrain(x, (BATCH_AXES, "model", None))
            except Exception:
                pass
        return constrain(x, (BATCH_AXES, None, None))
    return x


# ---------------------------------------------------------------------------
# per-block init / apply
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, blk: BlockSpec, cross: bool = False):
    ks = L.split_keys(key, 4)
    p: Dict[str, Any] = {"norm1": L.init_norm(cfg)}
    m = blk.mixer
    if m.startswith("attn"):
        p["mixer"] = L.init_attention(ks[0], cfg)
    elif m == "mamba":
        p["mixer"] = S.init_mamba(ks[0], cfg)
    elif m == "mlstm":
        p["mixer"] = S.init_mlstm(ks[0], cfg)
    elif m == "slstm":
        p["mixer"] = S.init_slstm(ks[0], cfg)
    else:
        raise ValueError(m)
    if cross:
        p["norm_x"] = L.init_norm(cfg)
        p["cross"] = L.init_attention(ks[3], cfg, cross=True)
    if blk.ffn != "none":
        p["norm2"] = L.init_norm(cfg)
        p["ffn"] = (L.init_moe(ks[1], cfg) if blk.ffn == "moe"
                    else L.init_mlp(ks[1], cfg))
    if cfg.post_block_norm:
        p["post_norm1"] = L.init_norm(cfg)
        if blk.ffn != "none":
            p["post_norm2"] = L.init_norm(cfg)
    return p


def apply_block(p, x, cfg: ModelConfig, blk: BlockSpec, *,
                positions=None, causal=True, state=None, cache_index=None,
                enc_out=None, attend_cache=False, block_tables=None,
                write_tables=None):
    """Returns (x, new_state, aux_loss)."""
    m = blk.mixer
    h = L.apply_norm(p["norm1"], x, cfg)
    new_state = None
    if m.startswith("attn"):
        window = cfg.window_size if m == "attn_local" else 0
        attn_cache = state.get("kv") if state else None
        h, new_kv = L.multi_head_attention(
            p["mixer"], h, cfg, positions=positions, causal=causal,
            window=window, kv_cache=attn_cache, cache_index=cache_index,
            attend_cache=attend_cache, block_tables=block_tables,
            write_tables=write_tables)
        new_state = {"kv": new_kv} if new_kv is not None else None
    elif m == "mamba":
        h, st = S.apply_mamba(p["mixer"], h, cfg,
                              state=state.get("ssm_state") if state else None)
        new_state = {"ssm_state": st}
    elif m == "mlstm":
        h, st = S.apply_mlstm(p["mixer"], h, cfg,
                              state=state.get("ssm_state") if state else None)
        new_state = {"ssm_state": st}
    elif m == "slstm":
        h, st = S.apply_slstm(p["mixer"], h, cfg,
                              state=state.get("ssm_state") if state else None)
        new_state = {"ssm_state": st}
    if cfg.post_block_norm:
        h = L.apply_norm(p["post_norm1"], h, cfg)
    x = shard_act(x + h)

    if "cross" in p:
        h = L.apply_norm(p["norm_x"], x, cfg)
        # enc_out present => training/prefill: compute cross-K/V fresh from
        # the encoder (and cache it).  Only decode (no enc_out) may use the
        # cached cross_kv — a zero-initialized prefill cache must NOT
        # shadow the encoder (XLA would DCE the whole encoder).
        if enc_out is not None:
            h, _ = L.multi_head_attention(
                p["cross"], h, cfg, causal=False, kv_source=enc_out,
                use_rope=False)
            if new_state is not None:
                # prefill: cache cross K/V so decode skips the projections.
                ck, cv = L.cross_kv(p["cross"], enc_out, cfg)
                new_state["cross_kv"] = {"k": ck, "v": cv}
        elif state is not None and "cross_kv" in state:
            pkv = state["cross_kv"]
            h, _ = L.multi_head_attention(
                p["cross"], h, cfg, causal=False, use_rope=False,
                precomputed_kv=(pkv["k"], pkv["v"]))
            if new_state is not None:
                new_state["cross_kv"] = pkv
        else:
            raise ValueError("cross-attention block needs enc_out or cache")
        x = shard_act(x + h)

    aux = jnp.zeros((), jnp.float32)
    if blk.ffn != "none":
        h = L.apply_norm(p["norm2"], x, cfg)
        if blk.ffn == "moe":
            h, aux = L.apply_moe(p["ffn"], h, cfg)
        else:
            h = L.apply_mlp(p["ffn"], h, cfg)
        if cfg.post_block_norm:
            h = L.apply_norm(p["post_norm2"], h, cfg)
        x = shard_act(x + h)
    return x, new_state, aux


# ---------------------------------------------------------------------------
# state (KV-cache / recurrent) shape bookkeeping
# ---------------------------------------------------------------------------

def block_state_shapes(cfg: ModelConfig, blk: BlockSpec, batch: int,
                       max_seq: int, enc_len: int = 0) -> Dict[str, Any]:
    m = blk.mixer
    out: Dict[str, Any] = {}
    if m.startswith("attn"):
        kv_len = min(max_seq, cfg.window_size) if m == "attn_local" else max_seq
        shp = (batch, kv_len, cfg.num_kv_heads, cfg.head_dim)
        out["kv"] = {"k": shp, "v": shp}
    elif m == "mamba":
        out["ssm_state"] = S.mamba_state_shape(cfg, batch)
    elif m == "mlstm":
        out["ssm_state"] = S.mlstm_state_shape(cfg, batch)
    elif m == "slstm":
        out["ssm_state"] = S.slstm_state_shape(cfg, batch)
    else:
        raise ValueError(m)
    if enc_len:
        shp = (batch, enc_len, cfg.num_kv_heads, cfg.head_dim)
        out["cross_kv"] = {"k": shp, "v": shp}
    return out


def _state_leaf_dtype(cfg: ModelConfig, blk: BlockSpec, key: str, dtype):
    if key == "kv" or key == "cross_kv" or blk.mixer.startswith("attn"):
        return dtype
    return jnp.float32


def _dense_block_leaves(cfg: ModelConfig, blk: BlockSpec, batch: int,
                        max_seq: int, enc_len: int, dt, factory):
    """One pattern slot's dense state leaves (group axis leading) — the
    shared builder behind ``make_cache`` and ``make_paged_cache``'s
    non-paged branch, so dtype rules and shapes cannot drift between the
    two layouts."""
    shapes = block_state_shapes(cfg, blk, batch, max_seq, enc_len)
    sub = {}
    for key, val in shapes.items():
        leaf_dt = dt if key in ("kv", "cross_kv") else jnp.float32
        sub[key] = jax.tree.map(
            lambda shp, d=leaf_dt: factory((cfg.num_groups,) + shp, d),
            val, is_leaf=lambda x: isinstance(x, tuple))
    return sub


def make_cache(cfg: ModelConfig, batch: int, max_seq: int, *, enc_len: int = 0,
               dtype=None, factory=None):
    """Decode cache pytree, stacked over groups per pattern slot.

    factory(shape, dtype) -> leaf;  defaults to jnp.zeros (concrete cache);
    pass jax.ShapeDtypeStruct for dry-run specs."""
    dt = jnp.dtype(dtype or cfg.dtype)
    factory = factory or jnp.zeros
    return {f"b{j}": _dense_block_leaves(cfg, blk, batch, max_seq, enc_len,
                                         dt, factory)
            for j, blk in enumerate(cfg.block_pattern)}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None,
               enc_len: int = 0):
    return make_cache(cfg, batch, max_seq, enc_len=enc_len, dtype=dtype)


def _is_global_attn(mixer: str) -> bool:
    """Global-attention mixers ("attn" / "attn_global") hold pageable
    max_seq KV; "attn_local" keeps a ring."""
    return mixer.startswith("attn") and mixer != "attn_local"


def has_paged_layers(cfg: ModelConfig) -> bool:
    """Whether the pattern has any global-attention KV to page.  Sliding-
    window rings (their ring wrap is position-, not block-, ordered) and
    recurrent SSM state (O(1) per slot already) always stay dense."""
    return any(_is_global_attn(b.mixer) for b in cfg.block_pattern)


def make_paged_cache(cfg: ModelConfig, batch: int, max_seq: int, *,
                     page_size: int, num_blocks: int, dtype=None,
                     factory=None, kv_dtype: str = "fp"):
    """Decode cache where global-attention KV lives in a physical block
    pool instead of a dense per-slot reservation.

    Global-attn leaves become page pools shaped
    ``(num_groups, num_blocks + 1, kv_heads, page_size, head_dim)``
    (head-major: each page of each kv head is one contiguous
    ``(page_size, head_dim)`` tile, the block the Pallas kernels DMA)
    shared by all ``batch`` slots and addressed through per-slot block
    tables (``repro.cache.PagedCacheManager``); every other leaf —
    local-window rings, mamba/mlstm/slstm state — keeps the dense
    ``(num_groups, batch, ...)`` slot layout of ``make_cache`` (paging
    auto-disables for them).  The group axis stays leading, so the plan
    runtime's ``slice_cache_groups`` stage slicing works unchanged on
    paged caches.

    The extra physical page is the **write sink**: the manager's
    unmapped-block sentinel is ``num_blocks``, which indexes it — an
    in-range page no table ever maps for reads, so code paths that
    cannot *drop* a sentinel write (Pallas output index maps have no
    drop mode) land it there harmlessly instead.  Capacity accounting
    stays in ``num_blocks`` (the sink is never allocatable).

    kv_dtype: "fp" stores K/V at ``dtype``; "int8" stores int8 rows
    plus per-(row, kv-head) f32 dequant scales (``k_scales``/
    ``v_scales`` leaves, shaped pool[:-1]) — ~``head_dim *
    itemsize / (head_dim + 4)``× more tokens per byte
    (``paged_kv_capacity_ratio``)."""
    dt = jnp.dtype(dtype or cfg.dtype)
    factory = factory or jnp.zeros
    if max_seq % page_size:
        raise ValueError(f"max_seq={max_seq} must be a multiple of "
                         f"page_size={page_size}")
    if kv_dtype not in ("fp", "int8"):
        raise ValueError(f"kv_dtype={kv_dtype!r} must be 'fp' or 'int8'")
    cache = {}
    for j, blk in enumerate(cfg.block_pattern):
        if _is_global_attn(blk.mixer):
            shp = (cfg.num_groups, num_blocks + 1, cfg.num_kv_heads,
                   page_size, cfg.head_dim)
            if kv_dtype == "int8":
                cache[f"b{j}"] = {"kv": {
                    "k_pages": factory(shp, jnp.int8),
                    "v_pages": factory(shp, jnp.int8),
                    "k_scales": factory(shp[:-1], jnp.float32),
                    "v_scales": factory(shp[:-1], jnp.float32),
                }}
            else:
                cache[f"b{j}"] = {"kv": {"k_pages": factory(shp, dt),
                                         "v_pages": factory(shp, dt)}}
        else:
            cache[f"b{j}"] = _dense_block_leaves(cfg, blk, batch, max_seq,
                                                 0, dt, factory)
    return cache


def paged_kv_capacity_ratio(cfg: ModelConfig, kv_dtype: str,
                            dtype=None) -> float:
    """Tokens-per-byte multiplier of a ``kv_dtype`` pool over the fp
    layout at the same byte budget: int8 rows cost ``head_dim`` bytes
    plus one f32 scale vs ``head_dim * itemsize`` fp bytes — 3.88× for
    f32 / 1.94× for bf16 pools at head_dim 128."""
    if kv_dtype == "fp":
        return 1.0
    dt = jnp.dtype(dtype or cfg.dtype)
    d = cfg.head_dim
    return (d * dt.itemsize) / float(d + 4)


def slice_cache_groups(cache, first_group: int, n_groups: int):
    """A stage-local view of a decode cache: leaves are (num_groups, B, ...)
    so a plan stage's slice is a static gather on axis 0 over its group
    range [first_group, first_group + n_groups) — the serving-side analogue
    of ``pipeline.plan_stage_params`` (but exact, never padded: caches are
    stateful, so dead-group masking does not apply)."""
    return jax.tree.map(lambda l: l[first_group:first_group + n_groups],
                        cache)


def merge_cache_groups(full_cache, part_cache, first_group: int):
    """Write a stage's updated group slice back into the full cache
    (static group range — the inverse of ``slice_cache_groups``)."""
    def leaf(full, part):
        return full.at[first_group:first_group + part.shape[0]].set(
            part.astype(full.dtype))
    return jax.tree.map(leaf, full_cache, part_cache)


def concat_cache_groups(slices):
    """Stitch ordered per-stage cache slices back into a full cache: the
    plan's stages tile the group axis, so concatenation on axis 0 of every
    leaf reassembles exactly ``num_groups`` entries."""
    return jax.tree.map(lambda *ls: jnp.concatenate(ls, axis=0), *slices)


def _block_is_paged(sub) -> bool:
    """Whether one pattern slot's cache entry holds pool-backed KV."""
    return "kv" in sub and isinstance(sub["kv"], dict) \
        and "k_pages" in sub["kv"]


def supports_prefix_compute_reuse(cfg: ModelConfig) -> bool:
    """Whether a warm prefix may skip its prefill *compute* (not just its
    KV memory): every mixer must be global attention (local-window rings
    and recurrent SSM state have no paged per-position cache to resume
    from) and no FFN may be MoE (expert capacity couples the routing of
    every token in a call, so a suffix-only call is not numerically the
    tail of the full-prompt call).  Block-level memory sharing stays on
    for every family regardless."""
    return all(_is_global_attn(b.mixer) and b.ffn != "moe"
               for b in cfg.block_pattern)


def make_prefill_part(cfg: ModelConfig, max_seq: int, *, dtype=None,
                      enc_len: int = 0):
    """The *dense remainder* of a paged prefill: batch-1 state for every
    non-paged pattern slot (SSM state, local-window rings) and an empty
    entry for paged ones — global-attn K/V streams straight into the
    pool, so nothing is staged for it.  Combine with the pool-backed
    cache via ``combine_prefill_parts`` to run the stack, and land the
    dense half with ``scatter_prefill_part``."""
    dt = jnp.dtype(dtype or cfg.dtype)
    return {f"b{j}": ({} if _is_global_attn(blk.mixer)
                      else _dense_block_leaves(cfg, blk, 1, max_seq,
                                               enc_len, dt, jnp.zeros))
            for j, blk in enumerate(cfg.block_pattern)}


def combine_prefill_parts(paged_cache, dense_part):
    """Assemble the cache view a paged prefill runs against: paged blocks
    contribute their live pool leaves (written in place through the write
    tables), every other block its batch-1 dense part."""
    return {bk: (sub if _block_is_paged(sub) else dense_part[bk])
            for bk, sub in paged_cache.items()}


def split_prefill_parts(view, paged_cache):
    """Inverse of ``combine_prefill_parts``: split an updated view into
    (new_paged_cache, new_dense_part).  ``paged_cache`` supplies the
    layout (and the untouched dense slot leaves of the full cache)."""
    new_paged = {bk: (view[bk] if _block_is_paged(sub) else sub)
                 for bk, sub in paged_cache.items()}
    new_part = {bk: ({} if _block_is_paged(sub) else view[bk])
                for bk, sub in paged_cache.items()}
    return new_paged, new_part


def merge_prefill_view(full_cache, new_view, slot):
    """Land a finished paged prefill: paged blocks take the view's pool
    leaves wholesale (the K/V already sits in the right physical pages —
    there is no commit-time copy), dense blocks scatter their batch-1
    part into batch row ``slot``."""
    out = {}
    for bk, sub in full_cache.items():
        if _block_is_paged(sub):
            out[bk] = new_view[bk]
        else:
            out[bk] = jax.tree.map(
                lambda f, p: lax.dynamic_update_slice_in_dim(
                    f, p.astype(f.dtype), slot, axis=1),
                sub, new_view[bk])
    return out


def scatter_prefill_part(full_cache, dense_part, slot):
    """Scatter only the *dense* half of a paged prefill (SSM state,
    local-window rings) into batch row ``slot``; paged blocks pass
    through untouched — their K/V was written directly into the pool as
    the chunks ran."""
    out = {}
    for bk, sub in full_cache.items():
        if _block_is_paged(sub):
            out[bk] = sub
        else:
            out[bk] = jax.tree.map(
                lambda f, p: lax.dynamic_update_slice_in_dim(
                    f, p.astype(f.dtype), slot, axis=1),
                sub, dense_part[bk])
    return out


def copy_cache_pages(full_cache, src, dst):
    """Copy physical block ``src`` onto ``dst`` in every paged KV leaf —
    the device half of copy-on-write (a slot diverging from a shared
    block gets a private copy before its first write).  Dense leaves are
    untouched; ``src``/``dst`` may be traced scalars."""
    def leaf(sub):
        if isinstance(sub, dict) and "k_pages" in sub:
            return {n: p.at[:, dst].set(jnp.take(p, src, axis=1,
                                                 mode="clip"))
                    for n, p in sub.items()}
        return sub
    return {bk: {key: leaf(val) for key, val in s.items()}
            for bk, s in full_cache.items()}


def rebind_pool_leaves(cache, src):
    """Rebind a cache view's paged pool leaves to ``src``'s, keeping its
    own dense slot leaves.  Pure pytree restructuring — no device work.
    This is what lets every decode replica front ONE shared physical
    pool: after a step updates the pool through one replica's cache
    (donating the input buffers), the other replicas' views re-alias the
    fresh pool arrays here instead of copying pages."""
    return {bk: (src[bk] if _block_is_paged(sub) else sub)
            for bk, sub in cache.items()}


def has_dense_slot_leaves(cache) -> bool:
    """Whether the cache keeps any per-slot dense state (SSM state,
    local-window rings).  False for all-global-attention paged caches —
    a slot's entire state then lives behind its block table, so moving a
    request between slots/replicas/plans is pure host bookkeeping."""
    return any(not _block_is_paged(sub) for sub in cache.values())


def slice_cache_slots(cache, first: int, n: int):
    """Slot-axis slice [first, first + n) of a cache: dense leaves slice
    their batch axis (axis 1), paged pool leaves pass through untouched
    (they are slot-agnostic — a slot's paged state is its block-table
    row, not a pool region)."""
    return {bk: (sub if _block_is_paged(sub)
                 else jax.tree.map(lambda l: l[:, first:first + n], sub))
            for bk, sub in cache.items()}


def concat_cache_slots(caches):
    """Inverse of per-replica slot partitioning: concatenate dense leaves
    on the slot axis (axis 1).  Paged pool leaves are SHARED physical
    arrays across the per-replica views, so the first view's are taken
    as-is — re-planning between replica layouts never copies a page."""
    out = {}
    for bk, sub in caches[0].items():
        if _block_is_paged(sub):
            out[bk] = sub
        else:
            out[bk] = jax.tree.map(
                lambda *ls: jnp.concatenate(ls, axis=1),
                *[c[bk] for c in caches])
    return out


def extract_dense_slot(cache, slot):
    """Batch row ``slot`` of the cache's DENSE leaves only, as a part
    cache ({} when the model is all-global-attention paged).  Paged
    blocks are omitted entirely — the result is safe to pass alongside a
    *donated* full cache (``scatter_cache_slot`` / the paged scatter),
    where including shared pool leaves would alias freed buffers.  This
    is the slot-migration read: a slot's paged state moves by block-table
    handoff, only its dense row rides the device."""
    return {bk: jax.tree.map(lambda l: l[:, slot:slot + 1], sub)
            for bk, sub in cache.items() if not _block_is_paged(sub)}


def scatter_cache_slot(full_cache, part_cache, slot):
    """Write a small-batch cache into batch rows [slot, slot+b) of a
    persistent slot-indexed cache, leaving every other slot untouched.

    Cache leaves are (num_groups, batch, ...), so the batch axis is axis 1
    and the write lowers to one ``dynamic_update_slice`` per leaf — the
    admission primitive of per-slot continuous batching (KV rows AND
    recurrent SSM/conv states both live on that axis, so one tree-map
    covers attention, hybrid, and pure-SSM families alike).  ``slot`` may
    be a traced scalar."""
    def leaf(full, part):
        return lax.dynamic_update_slice_in_dim(
            full, part.astype(full.dtype), slot, axis=1)
    return jax.tree.map(leaf, full_cache, part_cache)


# ---------------------------------------------------------------------------
# stacked-parameter init
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig, cross: bool = False):
    """Init all groups: returns pytree with leading group axis on leaves."""
    keys = jax.random.split(key, cfg.num_groups)

    def one_group(k):
        ks = L.split_keys(k, len(cfg.block_pattern))
        return {f"b{j}": init_block(ks[j], cfg, blk, cross=cross)
                for j, blk in enumerate(cfg.block_pattern)}
    return jax.vmap(one_group)(keys)


# ---------------------------------------------------------------------------
# stack execution (scan over groups)
# ---------------------------------------------------------------------------

def run_stack(stack_params, x, cfg: ModelConfig, *, positions=None,
              causal=True, cache=None, cache_index=None, enc_out=None,
              remat: bool = False, collect_state: bool = False,
              group_mask=None, attend_cache: bool = False,
              block_tables=None, write_tables=None):
    """Run the whole layer stack.  Returns (x, new_cache, aux_sum).

    collect_state: emit per-group state (KV cache / recurrent state) as scan
    outputs — used by prefill/decode; train leaves it off so SSM states are
    not materialized across groups.

    group_mask: optional (num_groups,) 0/1 vector scanned alongside the
    params; groups with mask 0 pass activations (and aux) through unchanged.
    This is how the ExecutionPlan executor runs *uneven* pipeline stages:
    every stage's stack is padded to the max group count and the dead
    entries are masked here.  Stateless forward only (no cache).

    attend_cache: chunked-prefill continuation — attention blocks attend
    the tokens already in ``cache`` (scalar ``cache_index`` = their count)
    in addition to the fresh chunk; recurrent blocks continue from the
    cached state either way.

    block_tables: (B, max_blocks) int32 logical->physical page map when
    ``cache`` is pool-backed (``make_paged_cache``); shared across groups
    (one table per slot addresses every layer's page pool).

    write_tables: like ``block_tables`` but naming only the blocks this
    call may WRITE (fresh suffix blocks; sentinel elsewhere) — the paged
    prefill path scatters K/V through it so shared prefix blocks are
    never touched.  ``None`` defaults to ``block_tables``."""
    if group_mask is not None:
        assert cache is None and not collect_state, (
            "group_mask is for the stateless pipelined forward path")

    def body(carry, inp):
        x, aux = carry
        gp, gc, gm = inp
        x_in, aux_in = x, aux
        new_gc = {}
        for j, blk in enumerate(cfg.block_pattern):
            st = gc[f"b{j}"] if gc is not None else None
            x, nst, a = apply_block(
                gp[f"b{j}"], x, cfg, blk, positions=positions, causal=causal,
                state=st, cache_index=cache_index, enc_out=enc_out,
                attend_cache=attend_cache, block_tables=block_tables,
                write_tables=write_tables)
            if nst is not None:
                new_gc[f"b{j}"] = nst
            aux = aux + a
        if gm is not None:
            live = gm > 0
            x = jnp.where(live, x, x_in)
            aux = jnp.where(live, aux, aux_in)
        out = new_gc if (collect_state and new_gc) else None
        return (x, aux), out

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    # under shard_map the body's aux varies over the manual axes that x
    # and the params vary over; the carry must start out that way too
    aux0 = compat.pcast_varying(jnp.zeros((), jnp.float32),
                                compat.varying_axes(x, stack_params))
    (x, aux), new_cache = lax.scan(body, (x, aux0),
                                   (stack_params, cache, group_mask))
    return x, new_cache, aux
