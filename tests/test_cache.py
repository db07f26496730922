"""Paged KV-cache subsystem: block pool / block table / prefix sharing /
copy-on-write / LRU units, the paged scatter + page-copy device helpers,
and the scatter/slice edge cases of the dense cache helpers."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cache import BlockPool, PagedCacheManager, PoolExhausted
from repro.cache.paged import _ROOT
from repro.configs import REGISTRY, reduced
from repro.models import build_model
from repro.models import transformer as T


# ---------------------------------------------------------------------------
# BlockPool
# ---------------------------------------------------------------------------

def test_pool_allocate_release_roundtrip():
    pool = BlockPool(4, page_size=2)
    blks = [pool.allocate() for _ in range(4)]
    assert sorted(blks) == [0, 1, 2, 3]
    assert pool.blocks_in_use == 4 and pool.blocks_free == 0
    with pytest.raises(PoolExhausted):
        pool.allocate()
    for b in blks:
        pool.release(b)
    assert pool.blocks_in_use == 0 and pool.blocks_free == 4
    assert pool.blocks_cached == 0        # unregistered blocks free outright


def test_pool_registered_blocks_park_in_lru_and_evict_oldest_first():
    pool = BlockPool(3, page_size=2)
    a, b = pool.allocate(), pool.allocate()
    pool.register(a, _ROOT, [1, 2])
    pool.register(b, pool.hash_of[a], [3, 4])
    pool.release(a)
    pool.release(b)
    assert pool.blocks_cached == 2 and pool.blocks_free == 1
    # drain: free block first, then LRU evictions oldest-release first
    assert pool.allocate() == 2
    assert pool.allocate() == a           # evicted + unregistered
    assert pool.evictions == 1
    assert a not in pool.hash_of
    assert b in pool.hash_of              # newer entry survives


def test_pool_register_collision_keeps_first_writer():
    pool = BlockPool(2, page_size=2)
    a, b = pool.allocate(), pool.allocate()
    assert pool.register(a, _ROOT, [7, 8])
    assert not pool.register(b, _ROOT, [7, 8])
    assert pool.registry[pool.hash_of[a]] == a
    assert b not in pool.hash_of          # stays private and writable
    assert pool.writable(b) and not pool.writable(a)


def test_pool_retain_revives_lru_block():
    pool = BlockPool(2, page_size=2)
    a = pool.allocate()
    pool.register(a, _ROOT, [1, 2])
    pool.release(a)
    assert a in pool.lru
    pool.retain(a)
    assert a not in pool.lru and pool.refcount[a] == 1
    pool.allocate()                       # only the other block remains
    with pytest.raises(PoolExhausted):    # the revived block is not
        pool.allocate()                   # evictable while referenced


# ---------------------------------------------------------------------------
# PagedCacheManager: admission / sharing / COW / release
# ---------------------------------------------------------------------------

def _mgr(slots=2, max_seq=16, page=4, blocks=8):
    return PagedCacheManager(slots, max_seq, page, blocks)


def test_manager_rejects_non_dividing_page_size():
    with pytest.raises(ValueError, match="multiple of"):
        PagedCacheManager(1, max_seq=10, page_size=4, num_blocks=4)


def test_admit_allocates_prompt_blocks_and_commit_registers_full_ones():
    m = _mgr()
    ap = m.admit(0, np.arange(1, 7))      # 6 tokens: 1 full + 1 partial
    assert ap is not None and ap.n_write == 2
    # the table row maps only at commit (a reserved slot mid-prefill must
    # ride decode with an unmapped row so stale-position writes drop)
    assert m.tables[0].n_mapped == 0
    assert not m.pool.registry            # nothing published before commit
    m.commit(0)
    assert m.tables[0].n_mapped == 2
    assert len(m.pool.registry) == 1      # only the FULL block registers
    # padded write vectors: pad entries point one past the pool (dropped)
    assert list(ap.write_logical[:2]) == [0, 1]
    assert all(p == m.pool.num_blocks for p in ap.write_phys[ap.n_write:])


def test_admit_shares_full_prefix_blocks_refcounted():
    m = _mgr()
    m.admit(0, np.arange(1, 9))           # 8 tokens = 2 full blocks
    m.commit(0)
    ap = m.admit(1, np.arange(1, 9))      # identical prompt
    m.commit(1)
    assert ap is not None and ap.n_write == 0
    assert np.array_equal(m.tables[0].blocks[:2], m.tables[1].blocks[:2])
    for blk in m.tables[0].blocks[:2]:
        assert m.pool.refcount[blk] == 2
    assert ap.shared_blocks == tuple(int(b) for b in m.tables[0].blocks[:2])


def test_partial_tail_share_then_copy_on_write():
    m = _mgr()
    m.admit(0, np.arange(1, 9))           # blocks [0..3], [4..7] tokens 1..8
    m.commit(0)
    ap = m.admit(1, np.arange(1, 7))      # 6 tokens: full match + tail [5,6]
    m.commit(1)
    assert ap is not None and ap.n_write == 0
    tail = int(m.tables[1].blocks[1])
    assert tail == int(m.tables[0].blocks[1])
    assert m.pool.refcount[tail] == 2
    # first decode write at pos 6 diverges from the registered content
    cow = m.prepare_decode(1, 6)
    assert cow is not None and cow[0] == tail
    assert m.tables[1].blocks[1] == cow[1] != tail
    assert m.pool.refcount[tail] == 1 and m.pool.cow_copies == 1


def test_decode_allocates_at_page_boundary_and_registers_filled_blocks():
    m = _mgr()
    m.admit(0, np.arange(1, 5))           # exactly one full block
    m.commit(0)
    assert m.prepare_decode(0, 4) is None     # new boundary: fresh block
    assert m.tables[0].n_mapped == 2
    before = len(m.pool.registry)
    for pos, tok in zip(range(4, 8), [9, 9, 9, 9]):
        m.note_written(0, tok, pos)
    assert len(m.pool.registry) == before + 1  # decode-filled block published
    assert m.prepare_decode(0, 8) is None      # next boundary allocates again
    assert m.tables[0].n_mapped == 3


def test_admit_defers_when_pool_cannot_supply_blocks():
    m = _mgr(slots=2, max_seq=16, page=4, blocks=2)
    assert m.admit(0, np.arange(1, 8)) is not None     # needs both blocks
    assert m.admit(1, np.arange(20, 26)) is None       # no state change
    assert m.tables[1].n_mapped == 0
    m.release_slot(0)
    assert m.admit(1, np.arange(20, 26)) is not None   # blocks came back


def test_never_fits_raises_even_when_prefix_is_shared():
    """Regression: feasibility must count the retained shared blocks —
    a shared + fresh footprint exceeding the pool would otherwise defer
    forever (livelocking the FIFO head) instead of raising."""
    m = _mgr(slots=2, max_seq=32, page=4, blocks=4)
    m.admit(0, np.arange(1, 9), max_new_tokens=0)   # 2 full blocks
    m.commit(0)
    m.release_slot(0)                               # parked in the LRU
    with pytest.raises(PoolExhausted, match="num_blocks"):
        # same prefix: 2 shared + 3 growth = 5 > 4 can never fit
        m.admit(1, np.arange(1, 9), max_new_tokens=9)


def test_deferred_admission_does_not_inflate_reuse_counters():
    """Regression: a deferred (retried) admission must count its registry
    lookups once, on the attempt that admits — not once per retry —
    or the reported reuse_hit_rate drifts toward the deferred request."""
    m = _mgr(slots=2, max_seq=16, page=4, blocks=4)
    m.admit(0, np.arange(1, 9), max_new_tokens=4)   # holds 2+1 blocks
    m.commit(0)
    for _ in range(5):                              # same prefix, no room
        assert m.admit(1, np.arange(1, 9), max_new_tokens=8) is None
    assert m.pool.prefix_queries == 1 and m.pool.prefix_hits == 0
    m.release_slot(0)
    assert m.admit(1, np.arange(1, 9), max_new_tokens=8) is not None
    assert m.pool.prefix_queries == 3 and m.pool.prefix_hits == 2


def test_never_fits_raise_restores_reuse_counters():
    """Regression: the PoolExhausted raise is still 'no admission
    happened' — its registry lookups must not count, exactly like the
    deferral path, or a never-fits request permanently skews the
    reported reuse_hit_rate."""
    m = _mgr(slots=2, max_seq=32, page=4, blocks=4)
    m.admit(0, np.arange(1, 9), max_new_tokens=0)   # 2 full blocks
    m.commit(0)
    m.release_slot(0)                               # parked in the LRU
    q0, h0 = m.pool.prefix_queries, m.pool.prefix_hits
    with pytest.raises(PoolExhausted):
        # same prefix: the lookup HITS both blocks before the raise
        m.admit(1, np.arange(1, 9), max_new_tokens=9)
    assert m.pool.prefix_queries == q0 and m.pool.prefix_hits == h0


def test_rollback_releases_rejected_tail_blocks():
    """Speculative reject path: rollback truncates the chain to the
    accepted position, releases blocks wholly past it back to the pool,
    and returns them to the slot's growth reservation — decode can
    regrow over the same positions."""
    m = _mgr(slots=1, max_seq=16, page=4, blocks=8)
    m.admit(0, np.asarray([1, 2]), max_new_tokens=10)
    m.commit(0)
    # a verify window writes positions 2..5 (current token + 3 drafts):
    # position 4 crosses into a fresh boundary block
    for pos in range(2, 6):
        m.prepare_decode(0, pos)
    assert m.tables[0].n_mapped == 2
    used = m.pool.blocks_in_use
    reserved = m.tables[0].reserved
    m.note_written(0, 7, 2)                  # one accepted input token
    m.rollback(0, 3)                         # tail 3..5 rejected
    tb = m.tables[0]
    assert tb.chain == [1, 2, 7]
    assert int(tb.blocks[0]) >= 0            # accepted block kept
    assert int(tb.blocks[1]) == -1           # rejected boundary block freed
    assert m.pool.blocks_in_use == used - 1
    assert tb.reserved == reserved + 1       # growth returned to reserve
    assert tb.hashes == []                   # no full block in the chain
    # decode regrows over the rolled-back positions
    for pos, tok in zip(range(3, 6), [8, 9, 10]):
        m.prepare_decode(0, pos)
        m.note_written(0, tok, pos)
    assert int(m.tables[0].blocks[1]) >= 0
    assert tb.chain == [1, 2, 7, 8, 9, 10]
    assert len(tb.hashes) == 1               # block 0 filled and hashed


def test_rollback_never_releases_accepted_or_shared_blocks():
    """Rollback keeps every block at or below the accepted position —
    including registered prompt blocks another slot still shares."""
    m = _mgr(slots=2, max_seq=16, page=4, blocks=8)
    m.admit(0, np.arange(1, 9))              # 2 full registered blocks
    m.commit(0)
    m.admit(1, np.arange(1, 9))              # shares both
    m.commit(1)
    shared = [int(b) for b in m.tables[1].blocks[:2]]
    # slot 1 runs a verify window at 8..10 and rejects everything past 9
    for pos in range(8, 11):
        m.prepare_decode(1, pos)
    m.note_written(1, 30, 8)
    m.rollback(1, 9)
    assert [int(b) for b in m.tables[1].blocks[:2]] == shared
    assert all(m.pool.refcount[b] == 2 for b in shared)
    assert int(m.tables[1].blocks[2]) >= 0   # holds accepted position 8
    assert m.tables[1].chain == list(range(1, 9)) + [30]


def test_admit_reuse_compute_reports_suffix_and_tables():
    """A warm admission with reuse_compute=True skips the matched prefix
    (keeping the last prompt token — its hidden state makes the first
    logits) and describes the device work through two tables: the gather
    table maps EVERY block, the write table only the fresh ones."""
    m = _mgr()
    NB = m.pool.num_blocks
    ap0 = m.admit(0, np.arange(1, 9))         # cold: 2 full blocks
    assert ap0.reused_tokens == 0             # nothing warm yet
    m.commit(0)
    ap = m.admit(1, np.arange(1, 9), reuse_compute=True)
    m.commit(1)
    assert ap.reused_tokens == 7              # 8 matched, capped at L-1
    assert ap.n_write == 0
    assert list(ap.block_table[:2]) == list(m.tables[0].blocks[:2])
    assert all(b == NB for b in ap.block_table[2:])   # unmapped: sentinel
    assert all(b == NB for b in ap.write_table)       # nothing fresh
    assert m.pool.prefill_admissions == 2
    assert m.pool.prefill_compute_hits == 1
    assert m.pool.reused_prefill_tokens == 7
    assert m.pool.suffix_prefill_tokens == 8 + 1
    st = m.stats()
    assert st["prefill_hit_rate"] == 0.5 and st["prefix_cache"]


def test_write_table_sentinels_protect_shared_blocks():
    """Partial warm match: fresh suffix blocks appear in the write table
    at their logical positions; the shared prefix blocks carry the
    sentinel so the suffix prefill's page writes drop on them."""
    m = _mgr()
    NB = m.pool.num_blocks
    m.admit(0, np.arange(1, 5))               # 1 full block [1..4]
    m.commit(0)
    suffix_prompt = np.concatenate([np.arange(1, 5), np.arange(40, 46)])
    ap = m.admit(1, suffix_prompt, reuse_compute=True)
    assert ap.reused_tokens == 4 and ap.n_write == 2
    assert ap.write_table[0] == NB            # shared block: write drops
    assert ap.write_table[1] != NB and ap.write_table[2] != NB
    assert all(ap.block_table[:3] < NB)       # gather maps all three


def test_commit_chunk_publishes_full_blocks_incrementally():
    """Chunked prefill feeds the compute cache mid-prompt: each chunk
    that lands publishes the full blocks it completed, while the table
    row stays unmapped until the final commit."""
    m = _mgr()
    m.admit(0, np.arange(1, 11))              # 10 tokens: 2 full + 1 tail
    m.commit_chunk(0, 4)                      # first chunk landed
    assert len(m.pool.registry) == 1
    assert m.tables[0].n_mapped == 0          # row mapping still deferred
    m.commit_chunk(0, 6)                      # mid-block: nothing new
    assert len(m.pool.registry) == 1
    m.commit_chunk(0, 8)
    assert len(m.pool.registry) == 2
    # a second request can hit blocks of the still-mid-prefill prompt
    ap = m.admit(1, np.arange(1, 9), reuse_compute=True)
    assert ap.reused_tokens == 7
    m.commit(0)
    assert m.tables[0].n_mapped == 3
    assert len(m.pool.registry) == 2          # chunks already published all


def test_prefix_cache_off_disables_sharing_and_parking():
    """prefix_cache=False: no registry lookups, no publication, and
    release frees blocks outright (nothing parks in the LRU)."""
    m = PagedCacheManager(2, 16, 4, 8, prefix_cache=False)
    ap0 = m.admit(0, np.arange(1, 9), reuse_compute=True)
    m.commit(0)
    assert not m.pool.registry                # commit publishes nothing
    assert ap0.reused_tokens == 0
    ap = m.admit(1, np.arange(1, 9), reuse_compute=True)
    m.commit(1)
    assert ap.n_write == 2                    # identical prompt: no share
    assert ap.reused_tokens == 0
    assert m.pool.prefix_queries == 0
    m.release_slot(0)
    m.release_slot(1)
    assert m.pool.blocks_cached == 0 and m.pool.blocks_free == 8
    assert not m.stats()["prefix_cache"]


def test_parked_compute_cache_evicts_under_pool_pressure():
    """Parked warm blocks are reclaimable capacity: a new admission that
    needs the whole pool evicts them oldest-first and still succeeds —
    the compute cache never wedges the pool."""
    m = _mgr(slots=2, max_seq=16, page=4, blocks=4)
    m.admit(0, np.arange(1, 13))              # 12 tokens = 3 full blocks
    m.commit(0)
    m.release_slot(0)                         # full blocks park in the LRU
    assert m.pool.blocks_cached == 3
    ap = m.admit(1, np.arange(50, 64))        # 14 tokens = 4 fresh blocks
    assert ap is not None and ap.n_write == 4
    assert m.pool.evictions >= 1              # parked blocks reclaimed
    assert m.pool.blocks_cached == 0
    # evicted entries left the registry: the old prefix no longer hits
    m.commit(1)
    m.release_slot(1)
    ap2 = m.admit(0, np.arange(1, 13), reuse_compute=True)
    assert ap2.reused_tokens == 0


def test_lookup_full_verifies_tokens_not_just_hash():
    """A registry hit must match stored content, so a chain-hash
    collision degrades to a miss instead of mapping foreign K/V."""
    pool = BlockPool(2, page_size=2)
    a = pool.allocate()
    pool.register(a, _ROOT, [1, 2])
    h = pool.hash_of[a]
    # same hash key, different content: force the collision directly
    _, hit = pool.lookup_full(_ROOT, [1, 2])
    assert hit == a
    pool.tokens_of[a] = np.asarray([9, 9], np.int32)   # simulate collision
    _, hit = pool.lookup_full(_ROOT, [1, 2])
    assert hit is None
    assert h in pool.registry                          # entry kept intact


def test_release_parks_registered_blocks_for_reuse():
    m = _mgr()
    m.admit(0, np.arange(1, 9))
    m.commit(0)
    m.release_slot(0)
    assert m.pool.blocks_in_use == 0 and m.pool.blocks_cached == 2
    ap = m.admit(1, np.arange(1, 9))      # retired prefix still reusable
    assert ap is not None and ap.n_write == 0
    assert m.pool.prefix_hits >= 2


# ---------------------------------------------------------------------------
# slot migration (re-plan / work stealing) + peak-tracker re-attach
# ---------------------------------------------------------------------------

def test_migrate_slot_hands_over_table_row_without_touching_pool():
    """Zero-copy slot migration: the whole move is a block-table row
    handoff — same physical blocks, same refcounts, no allocation, no
    release, no COW.  The destination row then decodes exactly as the
    source would have."""
    m = _mgr(slots=3)
    m.admit(0, np.arange(1, 10))          # 9 tokens: 2 full + 1 tail block
    m.commit(0)
    blocks = list(m.tables[0].blocks)
    chain = list(m.tables[0].chain)
    in_use = m.pool.blocks_in_use
    refs = list(m.pool.refcount)

    m.migrate_slot(0, 2)
    assert m.migrations == 1 and m.stats()["migrations"] == 1
    assert list(m.tables[2].blocks) == blocks
    assert m.tables[2].chain == chain
    assert m.tables[0].n_mapped == 0 and m.tables[0].chain == []
    assert all(b == -1 for b in m.tables[0].blocks)
    # the pool never noticed: no alloc/free/COW/refcount churn
    assert m.pool.blocks_in_use == in_use
    assert list(m.pool.refcount) == refs
    assert m.pool.cow_copies == 0 and m.pool.evictions == 0
    # decode continues seamlessly on the new row (pos 9 is mid-block 2)
    assert m.prepare_decode(2, 9) is None
    m.note_written(2, 99, 9)
    # the vacated source row is immediately admittable
    assert m.admit(0, np.arange(20, 26)) is not None

    m.migrate_slot(2, 2)                  # self-move is a no-op
    assert m.migrations == 1


def test_migrate_slot_refuses_occupied_or_pending_rows():
    m = _mgr(slots=3)
    m.admit(0, np.arange(1, 9))
    m.commit(0)
    m.admit(1, np.arange(20, 28))
    m.commit(1)
    with pytest.raises(AssertionError):   # destination row is occupied
        m.migrate_slot(0, 1)
    m.admit(2, np.arange(30, 36))         # slot 2 mid-prefill (uncommitted)
    with pytest.raises(AssertionError):   # pending slots must not move
        m.migrate_slot(2, 0)


def test_concurrent_peak_tracker_reattach_is_idempotent():
    """Regression: re-planning re-attaches the SURVIVING pool to the
    engine-lifetime tracker.  Pre-fix, attach() appended the pool again,
    so every subsequent note() summed it twice and the reported
    concurrent peak doubled."""
    from repro.cache import ConcurrentPeakTracker
    pool = BlockPool(8, page_size=2)
    tr = ConcurrentPeakTracker()
    tr.attach(pool)
    pool.allocate()
    tr.attach(pool)                       # a re-plan re-attaches
    pool.allocate()                       # 2 blocks in use
    assert len(tr.pools) == 1
    assert tr.peak == 2                   # pre-fix: 4 (pool counted twice)


# ---------------------------------------------------------------------------
# device helpers: paged scatter, page copy
# ---------------------------------------------------------------------------

def _paged_setup(arch="yi-6b", layers=1, slots=2, max_seq=16, page=4):
    cfg = reduced(REGISTRY[arch], layers=layers)
    model = build_model(cfg)
    nb = slots * (max_seq // page)
    full = model.init_paged_cache(slots, max_seq, page_size=page,
                                  num_blocks=nb)
    part = model.init_cache(1, max_seq)
    return cfg, model, full, part, nb


def test_scatter_prefill_part_scatters_dense_only_pool_passthrough():
    """Finishing a paged prefill has NO commit-time page copy: only the
    dense remainder (SSM state, rings) scatters into the slot row — the
    pool leaves pass through object-identical (their K/V was written in
    place through the write tables as the prefill ran)."""
    cfg = reduced(REGISTRY["jamba-1.5-large-398b"], layers=8)
    model = build_model(cfg)
    full = model.init_paged_cache(3, 16, page_size=4, num_blocks=12)
    part = T.make_prefill_part(cfg, 16)
    part = jax.tree.map(lambda x: jnp.ones_like(x), part)
    out = T.scatter_prefill_part(full, part, jnp.int32(1))
    for j, blk in enumerate(cfg.block_pattern):
        sub = out[f"b{j}"]
        if blk.mixer == "attn":
            assert sub is full[f"b{j}"]            # pool: untouched object
        else:
            leaf = np.asarray(jax.tree.leaves(sub)[0])
            assert np.all(leaf[:, 1] == 1.0)       # slot row landed
            assert np.all(leaf[:, 0] == 0.0)       # other slots untouched


def test_prefill_view_combine_split_roundtrip():
    """combine_prefill_parts / split_prefill_parts are exact inverses:
    paged blocks ride the pool leaves, dense blocks the batch-1 part."""
    cfg = reduced(REGISTRY["jamba-1.5-large-398b"], layers=8)
    model = build_model(cfg)
    full = model.init_paged_cache(2, 16, page_size=4, num_blocks=8)
    part = T.make_prefill_part(cfg, 16)
    view = T.combine_prefill_parts(full, part)
    paged2, part2 = T.split_prefill_parts(view, full)
    assert jax.tree.structure(paged2) == jax.tree.structure(full)
    assert jax.tree.structure(part2) == jax.tree.structure(part)
    for a, b in zip(jax.tree.leaves(paged2), jax.tree.leaves(full)):
        assert a is b
    for a, b in zip(jax.tree.leaves(part2), jax.tree.leaves(part)):
        assert a is b


def test_copy_cache_pages_copies_one_block_everywhere():
    cfg, model, full, part, nb = _paged_setup()
    full = jax.tree.map(lambda x: jnp.ones_like(x), full)
    full["b0"]["kv"]["k_pages"] = full["b0"]["kv"]["k_pages"].at[:, 3].set(7.0)
    out = T.copy_cache_pages(full, jnp.int32(3), jnp.int32(1))
    kp = np.asarray(out["b0"]["kv"]["k_pages"])
    assert kp[:, 1].min() == 7.0 and kp[:, 3].min() == 7.0
    assert kp[:, 0].max() == 1.0                       # others untouched


def test_make_paged_cache_layer_layout_mixed_family():
    """Global attention pages; local-window rings and SSM state stay dense
    (per-slot batch axis)."""
    cfg = reduced(REGISTRY["jamba-1.5-large-398b"], layers=8)
    model = build_model(cfg)
    cache = model.init_paged_cache(3, 16, page_size=4, num_blocks=12)
    mixers = [b.mixer for b in cfg.block_pattern]
    for j, mix in enumerate(mixers):
        sub = cache[f"b{j}"]
        if mix == "attn":
            assert set(sub["kv"]) == {"k_pages", "v_pages"}
            # num_blocks + 1: the pool carries a trailing sink page the
            # fused decode kernel parks unmapped-slot writes in
            # (blocks + sink, kv heads, page): head-major pages
            assert sub["kv"]["k_pages"].shape[1:4] == (
                13, cfg.num_kv_heads, 4)
        else:
            assert "ssm_state" in sub
            leaf = jax.tree.leaves(sub["ssm_state"])[0]
            assert leaf.shape[1] == 3                  # slots axis


def test_has_paged_layers_gating():
    assert T.has_paged_layers(reduced(REGISTRY["yi-6b"], layers=1))
    assert T.has_paged_layers(reduced(REGISTRY["gemma2-9b"], layers=2))
    assert not T.has_paged_layers(reduced(REGISTRY["xlstm-125m"], layers=4))


# ---------------------------------------------------------------------------
# dense helper edge cases: scatter_cache_slot / slice_cache_groups
# ---------------------------------------------------------------------------

def _dense_cache(arch, layers, slots, max_seq=8):
    cfg = reduced(REGISTRY[arch], layers=layers)
    model = build_model(cfg)
    return cfg, model, model.init_cache(slots, max_seq)


@pytest.mark.parametrize("slot", [0, 3])
def test_scatter_cache_slot_first_and_last_slot(slot):
    cfg, model, full = _dense_cache("yi-6b", 1, slots=4)
    part = jax.tree.map(lambda x: jnp.ones_like(x),
                        model.init_cache(1, 8))
    out = T.scatter_cache_slot(full, part, jnp.int32(slot))

    def check(leaf):
        a = np.asarray(leaf)
        assert a[:, slot].min() == 1.0
        others = [s for s in range(4) if s != slot]
        assert abs(a[:, others]).max() == 0.0
    jax.tree.map(check, out)


def test_scatter_cache_slot_ssm_state_only_cache():
    """Pure-SSM caches (no KV leaves at all) ride the same scatter: the
    recurrent state lives on the same (groups, slots, ...) axes."""
    cfg, model, full = _dense_cache("xlstm-125m", 4, slots=3)
    assert not any("kv" in sub for sub in full.values())
    part = jax.tree.map(lambda x: 2.0 * jnp.ones_like(x),
                        model.init_cache(1, 8))
    out = T.scatter_cache_slot(full, part, jnp.int32(2))

    def check(leaf):
        a = np.asarray(leaf)
        assert a[:, 2].min() == 2.0 and abs(a[:, :2]).max() == 0.0
    jax.tree.map(check, out)


def test_slice_cache_groups_single_group_plan_and_bounds():
    """Single-group slices (the finest stage cut), the first/last group,
    and the merge/concat round-trip."""
    cfg, model, full = _dense_cache("yi-6b", 4, slots=2)
    G = cfg.num_groups
    assert G == 4
    full = jax.tree.map(
        lambda x: x + jnp.arange(G, dtype=x.dtype).reshape(
            (G,) + (1,) * (x.ndim - 1)), full)
    for g in (0, G - 1):
        sl = T.slice_cache_groups(full, g, 1)
        jax.tree.map(lambda l, g=g: np.testing.assert_array_equal(
            np.asarray(l), g), sl)
    # round-trip: slice each group, concat, compare to the original
    slices = [T.slice_cache_groups(full, g, 1) for g in range(G)]
    back = T.concat_cache_groups(slices)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), full, back)
    # merge writes back exactly one group range
    bumped = jax.tree.map(lambda l: l + 100.0, slices[2])
    merged = T.merge_cache_groups(full, bumped, 2)
    jax.tree.map(lambda l: np.testing.assert_array_equal(
        np.asarray(l)[2], 102.0), merged)
    jax.tree.map(lambda l, o: np.testing.assert_array_equal(
        np.asarray(l)[[0, 1, 3]], np.asarray(o)[[0, 1, 3]]), merged, full)


def test_slice_cache_groups_works_on_paged_leaves():
    """Paged caches keep the leading group axis, so plan stage slicing is
    layout-agnostic (the serving plan runtime relies on this)."""
    cfg = reduced(REGISTRY["yi-6b"], layers=4)
    model = build_model(cfg)
    cache = model.init_paged_cache(2, 16, page_size=4, num_blocks=8)
    sl = T.slice_cache_groups(cache, 1, 2)
    assert sl["b0"]["kv"]["k_pages"].shape[0] == 2
    assert sl["b0"]["kv"]["k_pages"].shape[1:4] == (   # +1 sink page
        9, cfg.num_kv_heads, 4)
