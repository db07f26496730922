"""Serving engine: request completion, continuous batching, greedy decode
consistency."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, reduced
from repro.models import build_model
from repro.serving import Request, ServingEngine, make_serve_step


def setup():
    cfg = reduced(REGISTRY["yi-6b"], layers=1)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def test_engine_completes_requests():
    cfg, model, params = setup()
    eng = ServingEngine(model, params, slots=2, max_seq=48)
    for uid in range(4):
        eng.submit(Request(uid, np.arange(1, 4 + uid, dtype=np.int32), 6))
    done = eng.run()
    assert len(done) == 4
    for r in done:
        assert len(r.out_tokens) == 6
        assert r.t_done >= r.t_first >= r.t_submit


def test_greedy_decode_deterministic():
    cfg, model, params = setup()
    outs = []
    for _ in range(2):
        eng = ServingEngine(model, params, slots=1, max_seq=32)
        eng.submit(Request(0, np.array([5, 6, 7], np.int32), 5))
        done = eng.run()
        outs.append(tuple(done[0].out_tokens))
    assert outs[0] == outs[1]


def test_serve_step_greedy_matches_argmax():
    cfg, model, params = setup()
    step = jax.jit(make_serve_step(model))
    B, T = 2, 8
    toks = jnp.ones((B, T), jnp.int32)
    _, cache = jax.jit(lambda p, b: model.prefill(p, b, 32))(
        params, {"tokens": toks})
    nxt, logits, cache = step(params, cache, jnp.ones((B, 1), jnp.int32),
                              jnp.int32(T))
    np.testing.assert_array_equal(
        np.asarray(nxt[:, 0]), np.asarray(jnp.argmax(logits[:, -1], -1)))


def test_engine_stats_ordering_and_occupancy():
    """t_submit <= t_first <= t_done per request; slot occupancy is a
    fraction of `slots` (never above 1); stats name the kernel path."""
    cfg, model, params = setup()
    eng = ServingEngine(model, params, slots=2, max_seq=48)
    for uid in range(5):
        eng.submit(Request(uid, np.arange(1, 4 + uid, dtype=np.int32), 4))
    eng.run()
    st = eng.stats()
    assert st["requests"] == 5
    assert st["gen_tokens"] == sum(len(r.out_tokens) for r in eng.done) == 20
    assert 0.0 < st["slot_occupancy"] <= 1.0
    assert st["throughput_tok_s"] > 0
    assert st["kernel_path"] == eng.kernel_path
    for r in eng.done:
        assert r.t_submit <= r.t_first <= r.t_done
        assert 0 <= r.slot < eng.slots
    assert len(st["ttft_s"]) == len(st["latency_s"]) == 5
    assert all(t >= 0 for t in st["ttft_s"])


def test_engine_stats_name_each_kernels_path(monkeypatch):
    """stats()["kernels"] names the path each dispatched kernel took in
    the engine's compiled steps — per kernel, not the global setting."""
    monkeypatch.setenv("REPRO_KERNELS", "ref,fused_paged_decode=interpret")
    cfg, model, params = setup()
    eng = ServingEngine(model, params, slots=2, max_seq=32, paged=True,
                        page_size=4)
    for uid in range(2):
        eng.submit(Request(uid, np.arange(1, 6 + uid, dtype=np.int32), 3))
    eng.run()
    kernels = eng.stats()["kernels"]
    assert kernels["fused_paged_decode"] == "interpret"
    assert kernels["paged_prefill_attention"] == "ref"
    assert eng.stats()["kernel_path"] == "ref"


def test_stats_report_cache_memory_utilization():
    """stats()["cache"] reports live vs reserved tokens for the dense
    layout, plus block-pool occupancy and prefix-reuse figures when
    paging is on."""
    cfg, model, params = setup()
    eng = ServingEngine(model, params, slots=2, max_seq=48)
    eng.submit(Request(0, np.arange(1, 6, dtype=np.int32), 4))
    eng.tick()                           # admit: 5 prompt tokens live
    st = eng.stats()["cache"]
    assert st["layout"] == "dense"
    assert st["reserved_tokens"] == 2 * 48
    assert st["live_tokens"] == 6        # prompt + the first decode write
    assert st["utilization"] == pytest.approx(6 / 96)
    eng.run()
    assert eng.stats()["cache"]["live_tokens"] == 0    # all retired

    paged = ServingEngine(model, params, slots=2, max_seq=48, paged=True,
                          page_size=4)
    p = np.arange(1, 9, dtype=np.int32)
    paged.submit(Request(0, p, 4))
    paged.submit(Request(1, p.copy(), 4))    # identical prompt: full reuse
    paged.run()
    st = paged.stats()["cache"]
    assert st["layout"] == "paged"
    assert st["page_size"] == 4 and st["num_blocks"] == 2 * (48 // 4)
    assert st["blocks_in_use"] == 0          # retired -> parked or freed
    assert st["prefix_hits"] >= 2            # both full blocks reused
    assert 0.0 < st["reuse_hit_rate"] <= 1.0
    assert st["peak_blocks_in_use"] >= 2
    assert st["effective_slots_gain"] >= 1.0


def test_stats_report_per_phase_host_timing():
    """stats() breaks the host wall-clock into admission / prefill /
    decode per tick; reset_stats() zeroes the window."""
    cfg, model, params = setup()
    eng = ServingEngine(model, params, slots=2, max_seq=48)
    eng.submit(Request(0, np.arange(1, 6, dtype=np.int32), 4))
    eng.run()
    st = eng.stats()
    assert st["ticks"] > 0
    pt = st["phase_time_s"]
    assert set(pt) == {"admission", "prefill", "decode", "replan",
                       "idle", "host_sync"}
    assert all(v >= 0.0 for v in pt.values())
    assert pt["prefill"] > 0.0 and pt["decode"] > 0.0
    # host_sync overlays the phase windows: every tick blocks on at
    # least the prefill first-token / decode readback, and the blocked
    # time can never exceed the phases it sits inside
    assert pt["host_sync"] > 0.0
    assert pt["host_sync"] <= pt["admission"] + pt["prefill"] + pt["decode"]
    eng.reset_stats()
    st = eng.stats()
    assert st["ticks"] == 0
    assert all(v == 0.0 for v in st["phase_time_s"].values())


def test_phase_time_partition_sums_to_wall_including_idle():
    """The partition buckets (admission / prefill / decode / replan /
    idle) tile the first-tick..last-tick wall window: idle charges the
    host time BETWEEN ticks, so the sum matches the window even when the
    caller sleeps mid-run.  host_sync is an overlay (accrues inside open
    phases) and stays outside the partition."""
    import time

    cfg, model, params = setup()
    eng = ServingEngine(model, params, slots=2, max_seq=48)
    eng.submit(Request(0, np.arange(1, 6, dtype=np.int32), 6))
    busy = True
    while busy:
        busy = eng.tick()
        time.sleep(0.002)            # caller-side gap -> idle bucket
    pt = eng.stats()["phase_time_s"]
    assert pt["idle"] > 0.0
    ph = eng._phases
    wall = (ph.last_tick_end_ns - ph.first_tick_ns) * 1e-9
    partition = (pt["admission"] + pt["prefill"] + pt["decode"]
                 + pt["replan"] + pt["idle"])
    assert partition == pytest.approx(wall, rel=0.02, abs=1e-4)
    eng.reset_stats()
    assert ph.first_tick_ns is None and ph.last_tick_end_ns is None
    assert eng.stats()["phase_time_s"]["idle"] == 0.0


def test_stats_repeat_calls_are_idempotent():
    """stats() is a pure snapshot: calling it twice in the same window
    returns identical payloads (no double-aggregation), including the
    nested cache and utilization sub-dicts, on dense and paged engines."""
    cfg, model, params = setup()
    for kw in ({}, {"paged": True, "page_size": 4}):
        eng = ServingEngine(model, params, slots=2, max_seq=48, **kw)
        for uid in range(3):
            eng.submit(Request(uid, np.arange(1, 6, dtype=np.int32), 4))
        eng.run()
        a, b = eng.stats(), eng.stats()
        assert a == b
        assert a["cache"] == b["cache"]
        assert a["utilization"] == b["utilization"]
        assert a["spans"] == b["spans"] and a["spans"]
        # a mutated copy must not leak back into the engine's counters
        a["cache"]["layout"] = "mutated"
        a["utilization"]["pipeline_ticks"] = -1
        a["spans"].append(("engine.tick", 0, 1, None, None))
        a["phase_time_s"]["decode"] = -1.0
        c = eng.stats()
        assert c["cache"]["layout"] != "mutated"
        assert c == b


def test_warm_prefix_runs_suffix_only_and_stays_token_identical():
    """The tentpole contract end-to-end: a second request whose prompt
    extends a retired request's prefix reports a prefill-compute hit,
    prefills only the unmatched suffix, and still emits exactly the
    token stream of a cold engine."""
    cfg, model, params = setup()
    shared = np.arange(1, 9, dtype=np.int32)         # 2 full 4-token blocks
    warm_prompt = np.concatenate([shared, np.array([30, 31], np.int32)])

    cold = ServingEngine(model, params, slots=1, max_seq=48, paged=True,
                         page_size=4, prefill_bucket=4, prefix_cache=False)
    cold.submit(Request(0, warm_prompt.copy(), 6))
    gold = list(cold.run()[0].out_tokens)
    assert cold.stats()["cache"]["prefill_compute_hits"] == 0

    eng = ServingEngine(model, params, slots=1, max_seq=48, paged=True,
                        page_size=4, prefill_bucket=4)
    eng.submit(Request(0, shared.copy(), 4))         # seeds the registry
    eng.submit(Request(1, warm_prompt.copy(), 6))
    done = {r.uid: list(r.out_tokens) for r in eng.run()}
    assert done[1] == gold                           # token-identical
    st = eng.stats()["cache"]
    assert st["prefill_compute_hits"] == 1
    assert st["prefill_hit_rate"] == 0.5
    assert st["reused_prefill_tokens"] == 8          # both shared blocks
    # suffix-only: the warm admission prefilled 2 tokens (padded to the
    # bucket), not the 10-token prompt
    assert eng.prefill_token_counts[1] < len(warm_prompt)


def test_prefix_cache_flag_disables_reuse_end_to_end():
    """prefix_cache=False keeps paged memory layout but never shares or
    reuses: identical prompts prefill in full, and nothing parks."""
    cfg, model, params = setup()
    p = np.arange(1, 9, dtype=np.int32)
    eng = ServingEngine(model, params, slots=2, max_seq=48, paged=True,
                        page_size=4, prefix_cache=False)
    eng.submit(Request(0, p, 4))
    eng.submit(Request(1, p.copy(), 4))
    eng.run()
    st = eng.stats()["cache"]
    assert st["prefix_cache"] is False
    assert st["prefix_queries"] == 0 and st["prefix_hits"] == 0
    assert st["prefill_compute_hits"] == 0
    assert st["reused_prefill_tokens"] == 0
    assert st["blocks_cached"] == 0                  # no LRU parking


def test_cache_stats_report_concurrent_peak_across_replicas():
    """Regression: slots peaking on DIFFERENT ticks must report the
    concurrent maximum, not the sum of per-slot peaks (which would
    overstate the footprint and understate the slots gain).  Both decode
    replicas front ONE engine-global manager (rows = global slot ids) —
    the precondition for zero-copy slot migration."""
    from repro.plan import lower_serving, uniform_plan
    cfg4 = reduced(REGISTRY["yi-6b"], layers=4)
    model = build_model(cfg4)
    params = model.init(jax.random.key(0))
    plan = uniform_plan(cfg4.num_groups, 2, n_microbatches=2)
    eng = ServingEngine(model, params, slots=2, max_seq=32,
                        plan=lower_serving(plan, slots=2, chunk=4),
                        paged=True, page_size=4)
    assert len(eng._all_pagers()) == 1 and eng._pager.slots == 2
    p = np.arange(1, 9, dtype=np.int32)              # 2 blocks at page 4
    # replica 0's slot peaks (2 blocks), then fully releases ...
    eng._pager.admit(0, p, max_new_tokens=0)
    eng._pager.commit(0)
    eng._pager.release_slot(0)
    # ... and only afterwards does replica 1's slot peak (2 blocks)
    eng._pager.admit(1, np.arange(20, 28, dtype=np.int32),
                     max_new_tokens=0)
    eng._pager.commit(1)
    st = eng.cache_stats()
    # concurrent peak is 2; summing per-slot maxima would say 4
    assert st["peak_blocks_in_use"] == 2
    dense_blocks = eng.slots * (eng.max_seq // eng.page_size)
    assert st["effective_slots_gain"] == pytest.approx(dense_blocks / 2)


def test_reset_stats_clamps_wall_window_to_reset_time():
    """Regression: a request still active across reset_stats() keeps its
    pre-reset t_submit; the post-reset wall window must start at the
    reset, or throughput_tok_s is understated by the whole warmup."""
    cfg, model, params = setup()
    eng = ServingEngine(model, params, slots=1, max_seq=48)
    eng.submit(Request(0, np.array([5, 6, 7], np.int32), 8))
    eng.tick()                                    # request is now active
    eng._slot_req[0].t_submit -= 50.0             # pretend a long warmup
    eng.reset_stats()
    eng.run()
    st = eng.stats()
    # pre-fix the wall window spans the backdated 50 s: throughput would
    # be ~gen/50; post-fix the window starts at the reset
    assert st["throughput_tok_s"] > st["gen_tokens"] / 10.0


def test_prefill_token_counts_unpadded_and_speculative_stats_keys():
    """prefill_token_counts reports UNPADDED prompt tokens (bucket
    padding is a jit-shape artifact, not work), and stats() carries the
    speculative-decode keys: tokens_per_step is exactly 1.0 with
    speculation off and > 1.0 when drafts are being accepted."""
    cfg, model, params = setup()
    prompt = np.array([4, 5, 4, 5, 4, 5, 4], np.int32)   # repetitive
    off = ServingEngine(model, params, slots=2, max_seq=64)
    off.submit(Request(0, prompt.copy(), 10))
    off.run()
    assert off.prefill_token_counts == [len(prompt)]     # not the bucket
    st = off.stats()
    assert st["tokens_per_step"] == 1.0
    assert st["spec_steps"] == 0 and st["acceptance_rate"] == 0.0

    on = ServingEngine(model, params, slots=2, max_seq=64, speculate=3)
    on.submit(Request(0, prompt.copy(), 10))
    on.run()
    st = on.stats()
    assert st["spec_steps"] > 0
    assert st["spec_accepted"] > 0
    assert st["tokens_per_step"] > 1.0
    assert 0.0 < st["acceptance_rate"] <= 1.0
    assert st["decode_tokens"] == st["gen_tokens"] - 1   # first token is
    #                                                      prefill's
    # same stream with and without speculation (greedy verify)
    assert on.done[0].out_tokens == off.done[0].out_tokens


def test_speculation_gates_off_for_non_decomposable_families():
    """Families whose state cannot rewind a rejected tail (SSM mixers
    here) silently disable speculation rather than corrupt state."""
    cfg = reduced(REGISTRY["xlstm-125m"], layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    eng = ServingEngine(model, params, slots=1, max_seq=32, speculate=4)
    assert eng._spec_k == 0
    eng.submit(Request(0, np.array([1, 2, 1, 2, 1], np.int32), 5))
    eng.run()
    assert eng.stats()["spec_steps"] == 0


def test_admission_does_not_change_active_slots_next_token():
    """Admitting a request mid-stream must not perturb the token stream of
    already-active slots (no full-batch re-prefill, no position reset)."""
    cfg, model, params = setup()
    p0 = np.array([5, 6, 7], np.int32)
    p1 = np.arange(1, 9, dtype=np.int32)

    solo = ServingEngine(model, params, slots=2, max_seq=64)
    solo.submit(Request(0, p0, 8))
    solo_tokens = list(solo.run()[0].out_tokens)

    eng = ServingEngine(model, params, slots=2, max_seq=64)
    eng.submit(Request(0, p0, 8))
    for _ in range(3):
        eng.tick()
    before = list(eng._slot_req[0].out_tokens)
    eng.submit(Request(1, p1, 6))
    eng.tick()                       # tick that performs the admission
    after = list(eng._slot_req[0].out_tokens)
    assert after[:len(before)] == before
    assert after[len(before)] == solo_tokens[len(before)]  # next token kept
    while eng.tick():
        pass
    done = {r.uid: r.out_tokens for r in eng.done}
    assert done[0] == solo_tokens
    assert eng.prefill_batch_sizes == [1, 1]


def test_int8_kv_capacity_and_end_to_end_serving():
    """kv_dtype="int8" quantizes the paged K/V pools per row: every
    stream still completes its full budget, stats() reports the dtype and
    the >= 1.9x effective-capacity multiplier (int8 payload + f32 scale
    vs the fp row), and overlap composes with quantized pools."""
    cfg, model, params = setup()
    prompts = [np.arange(1, 7, dtype=np.int32),
               np.array([9, 8, 7, 6], np.int32)]
    fp = ServingEngine(model, params, slots=2, max_seq=48, paged=True,
                       page_size=4)
    q8 = ServingEngine(model, params, slots=2, max_seq=48, paged=True,
                       page_size=4, kv_dtype="int8", overlap=True)
    for uid, p in enumerate(prompts):
        fp.submit(Request(uid, p.copy(), 5))
        q8.submit(Request(uid, p.copy(), 5))
    dfp = {r.uid: r.out_tokens for r in fp.run()}
    dq8 = {r.uid: r.out_tokens for r in q8.run()}
    st = q8.stats()["cache"]
    assert st["kv_dtype"] == "int8"
    assert st["kv_capacity_x"] >= 1.9
    assert fp.stats()["cache"]["kv_dtype"] == "fp"
    assert fp.stats()["cache"]["kv_capacity_x"] == 1.0
    # quantization may perturb token IDENTITY (bounded-logit error; see
    # test_kernels), never the serving contract: full budgets, all retire
    for uid in dq8:
        assert len(dq8[uid]) == len(dfp[uid]) == 5


def test_int8_kv_logits_bounded_against_fp_paged_engine():
    """Engine-level numerics contract: teacher-forcing the fp engine's
    greedy stream through an int8 engine keeps every slot live to budget
    — and wherever the two streams agree it is because the fp logit
    margin exceeded the quantization error (checked at the kernel level);
    here we assert the streams agree on a clear-margin prompt."""
    cfg, model, params = setup()
    # constant prompt: the toy model's top-1 margin is widest here
    p = np.array([3, 3, 3, 3, 3, 3, 3, 3], np.int32)
    fp = ServingEngine(model, params, slots=1, max_seq=48, paged=True,
                       page_size=4)
    q8 = ServingEngine(model, params, slots=1, max_seq=48, paged=True,
                       page_size=4, kv_dtype="int8")
    fp.submit(Request(0, p.copy(), 4))
    q8.submit(Request(0, p.copy(), 4))
    out_fp = fp.run()[0].out_tokens
    out_q8 = q8.run()[0].out_tokens
    assert len(out_fp) == len(out_q8) == 4
    # first token comes from the (unquantized-activation) prefill logits
    # read before any decode-step dequant error can accumulate
    assert out_fp[0] == out_q8[0]


def test_kv_dtype_validation():
    cfg, model, params = setup()
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(model, params, slots=2, max_seq=48, paged=True,
                      kv_dtype="int4")
    with pytest.raises(ValueError, match="paged=True"):
        ServingEngine(model, params, slots=2, max_seq=48, kv_dtype="int8")


def test_overlap_reduces_host_sync_share_and_keeps_stats_coherent():
    """The overlapped runtime's observable effect on the phase clock:
    host_sync still accrues (the delayed drain still reads back), every
    stat key stays present, and tick/throughput accounting is coherent
    while in-flight steps span tick boundaries."""
    cfg, model, params = setup()
    eng = ServingEngine(model, params, slots=2, max_seq=48, overlap=True)
    for uid in range(3):
        eng.submit(Request(uid, np.arange(1, 5 + uid, dtype=np.int32), 6))
    done = eng.run()
    assert len(done) == 3
    assert all(len(r.out_tokens) == 6 for r in done)
    st = eng.stats()
    assert st["ticks"] > 0 and st["gen_tokens"] == 18
    pt = st["phase_time_s"]
    assert set(pt) == {"admission", "prefill", "decode", "replan",
                       "idle", "host_sync"}
    assert pt["host_sync"] > 0.0
    assert pt["host_sync"] <= pt["admission"] + pt["prefill"] + pt["decode"]
    for r in done:
        assert r.t_submit <= r.t_first <= r.t_done


# ---------------------------------------------------------------------------
# adaptive re-planning: stats continuity + controller
# ---------------------------------------------------------------------------

def _plan_setup(layers=4):
    cfg = reduced(REGISTRY["yi-6b"], layers=layers)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def test_stats_window_continuity_across_mid_window_swap():
    """Regression (stats-window continuity): a live plan swap mid-window
    must neither lose nor double-count anything — requests finished on
    either side of the swap all land in one window, decode token
    accounting stays exact, the swap's own wall time is charged to the
    "replan" phase bucket, and the wall window spans the swap instead of
    restarting at it."""
    from repro.plan import lower_serving, uniform_plan
    cfg, model, params = _plan_setup()
    plan = lower_serving(uniform_plan(cfg.num_groups, 2, n_microbatches=2),
                         slots=2, chunk=4)
    eng = ServingEngine(model, params, slots=2, max_seq=48,
                        paged=True, page_size=4)
    eng.submit(Request(0, np.arange(1, 5, dtype=np.int32), 8))
    for _ in range(4):
        eng.tick()                        # request 0 mid-decode
    r0 = eng._slot_req[0]
    pre_swap = list(r0.out_tokens)
    assert pre_swap and not eng.done
    eng.replan(plan)                      # mid-window, mid-request
    eng.submit(Request(1, np.arange(10, 16, dtype=np.int32), 6))
    eng.run()
    st = eng.stats()
    # no loss, no double count: every generated token is either the one
    # prefill-emitted first token or exactly one decode-tick token
    assert st["requests"] == 2
    assert st["gen_tokens"] == 8 + 6
    assert st["decode_tokens"] == st["gen_tokens"] - st["requests"]
    # request 0's stream was not restarted by the swap
    assert len(r0.out_tokens) == 8
    assert r0.out_tokens[:len(pre_swap)] == pre_swap
    assert st["replans"] == 1
    pt = st["phase_time_s"]
    assert pt["replan"] > 0.0             # the migration interval is charged
    assert pt["host_sync"] <= (pt["admission"] + pt["prefill"]
                               + pt["decode"] + pt["replan"])
    # the wall window spans the swap (t_submit of request 0 predates it)
    assert st["throughput_tok_s"] > 0.0
    # the shared pool's peak tracker survived the swap (attached once)
    assert len(eng._peak_tracker.pools) == 1
    # reset_stats covers the new counters too
    eng.reset_stats()
    st = eng.stats()
    assert st["replans"] == 0 and st["migrations"] == 0
    assert st["migration_copies"] == 0
    assert st["phase_time_s"]["replan"] == 0.0


def test_adaptive_controller_navigates_burst_then_idle():
    """End-to-end controller loop (analytic profiles, measure=False): a
    long-prompt burst drives the engine onto the pipelined plan, and the
    drained near-idle tail brings it back to the monolithic point —
    with every stream still completing."""
    from repro.plan import lower_serving, uniform_plan
    from repro.serving import AdaptiveConfig
    cfg, model, params = _plan_setup()
    plan = lower_serving(uniform_plan(cfg.num_groups, 2, n_microbatches=2),
                         slots=2, chunk=8)
    adapt = AdaptiveConfig(plans=[None, plan], measure=False,
                           interval_ticks=2, cooldown_ticks=2,
                           hysteresis=0.1, window_s=30.0, horizon_s=0.1)
    eng = ServingEngine(model, params, slots=2, max_seq=64,
                        paged=True, page_size=4, adapt=adapt)
    assert eng._ctl is not None
    # burst: 6 long prompts queue up behind 2 slots
    for uid in range(6):
        eng.submit(Request(uid, np.arange(1, 25, dtype=np.int32), 4))
    for _ in range(8):
        eng.tick()
    assert eng.plan == plan               # backlog -> pipelined point
    labels = [d[2] for d in eng._ctl.decisions]
    assert labels[0] == plan.label
    done = eng.run()                      # queue drains; idle tail
    assert len(done) == 6
    assert all(len(r.out_tokens) == 4 for r in done)
    assert eng.plan is None               # near-idle -> monolithic point
    assert eng.stats()["replans"] >= 2


def test_adaptive_config_validation():
    """Candidate ladders are validated at engine construction: slot
    mismatches and single-point ladders fail loudly."""
    from repro.plan import lower_serving, uniform_plan
    from repro.serving import AdaptiveConfig
    cfg, model, params = _plan_setup()
    wrong = lower_serving(uniform_plan(cfg.num_groups, 2, n_microbatches=2),
                          slots=4, chunk=4)
    with pytest.raises(ValueError, match="slots"):
        ServingEngine(model, params, slots=2, max_seq=48,
                      adapt=AdaptiveConfig(plans=[wrong]))
    with pytest.raises(ValueError, match="candidate design points"):
        ServingEngine(model, params, slots=2, max_seq=48,
                      adapt=AdaptiveConfig(plans=[]))
