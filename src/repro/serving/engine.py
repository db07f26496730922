"""Batched serving engine: true per-slot continuous batching (vLLM-style),
optionally plan-driven (a lowered ``ServingPlan``).

The engine owns slot-indexed KV/recurrent caches for its whole lifetime
(batch axis = slots).  Admission prefills a single request (batch 1) and
scatters its cache into the free slot via ``dynamic_update_slice`` — cost
O(prompt), never O(active batch).  Decode is one batched step over all
slots with a **per-slot position vector**: each slot writes its own cache
row, rotates RoPE at its own position, and attends under its own length
mask, so mixed-length requests decode at their correct positions.  A slot
retiring (EOS / max tokens / cache full) never interrupts the other
slots' decode — the freed slot is simply re-prefilled from the queue.

**Plan-driven mode** (``plan=lower_serving(execution_plan, slots, chunk)``)
runs the same engine on a searched ``ExecutionPlan`` (see
``repro.plan.serving``): admission becomes a *chunked prefill* — the
prompt is sliced into ``chunk``-token microbatches that stream through the
plan's stage slices, one stage-step per tick, interleaved with decode so a
long prompt never stalls decode — and the plan's spatial width becomes N
independent *decode replicas*, each owning a partition of the slots and
walking the stage slices per decode step.  This realizes the paper's
hybrid spatial-sequential tradeoff under live traffic: prefill is
pipelined spatially, decode is replicated for latency.

**Paged mode** (``paged=True``) swaps the dense per-slot KV reservation
for a fixed block pool with per-slot block tables, content-hash prefix
sharing (admitted prompts sharing a prefix map the same physical blocks,
refcounted, copy-on-write on first divergent write) and LRU reuse of
released blocks — see ``repro.cache`` and ``docs/serving.md``.  SSM state
and sliding-window rings stay dense; the parity guarantee is unchanged.

**Adaptive mode** (``adapt=AdaptiveConfig(...)``) makes the searched
Pareto front *live*: a re-plan controller (``repro.serving.adaptive``)
watches rolling-window arrival rate, queue depth and TTFT/TPOT against
SLO targets and calls ``replan()`` when a different design point
dominates — swapping between monolithic and plan-driven bindings (or
between plans) WITHOUT dropping requests.  One ``PagedCacheManager``
(global slot ids, one physical pool shared by every decode replica)
makes the swap zero-copy on the paged path: a slot's state is its
block-table row, so migration is a table handoff, never a KV copy.

Guarantee (tested by ``tests/test_serving_parity.py``): the token stream
of every request is exactly equal to an isolated one-shot greedy decode
of that request, regardless of arrival order, prompt-length mix, slot
count — or ServingPlan, or cache layout (dense / paged), or any
sequence of live re-plans.

``serve_step`` — the function the decode-shape dry-runs lower — is one
batched decode step over a fixed slot set and keeps accepting a scalar
``cache_index`` for lock-step decode.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import compat, dispatch
from repro.models.model import Model


def _upload(host):
    """Device copy of a host array the engine mutates later.  The
    transfer behind ``jnp.asarray`` may run after the call returns and
    reads the source buffer until it is done, so an overlapped step that
    is dispatched before the host bumps ``_pos``/``_cur`` must be handed
    a snapshot, not the live array."""
    return jnp.asarray(np.array(host, copy=True))


def make_serve_step(model: Model):
    """serve_step(params, cache, tokens, cache_index) ->
    (next_tokens, logits, new_cache) — one greedy decode step.
    ``cache_index``: scalar (lock-step) or (B,) per-slot positions.
    ``block_tables``: logical->physical page map when ``cache`` is
    pool-backed (paged engines)."""

    def serve_step(params, cache, tokens, cache_index, positions=None,
                   block_tables=None):
        logits, cache = model.decode_step(params, cache, tokens, cache_index,
                                          positions=positions,
                                          block_tables=block_tables)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], logits, cache

    return serve_step


def make_verify_step(model: Model):
    """verify_step(params, cache, tokens, cache_index, block_tables) ->
    (argmax_tokens, new_cache) — one speculative-verify step.  ``tokens``
    is the (B, K+1) window per slot (current token + K drafted tokens);
    the returned (B, K+1) argmaxes score every window position in one
    batched step, exactly as K+1 sequential greedy decodes would."""

    def verify_step(params, cache, tokens, cache_index, block_tables=None):
        logits, cache = model.decode_step(params, cache, tokens, cache_index,
                                          block_tables=block_tables)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return verify_step


def ngram_draft(ctx, k: int, max_ngram: int = 3) -> List[int]:
    """Model-free prompt-lookup drafting: find the most recent earlier
    occurrence of the context's trailing n-gram (longest n first) and
    propose the up-to-``k`` tokens that followed it.  Returns [] when the
    context never repeats — the tick then falls back to plain decode."""
    L = len(ctx)
    if k <= 0 or L < 2:
        return []
    for n in range(min(max_ngram, L - 1), 0, -1):
        tail = ctx[L - n:]
        for i in range(L - n - 1, -1, -1):
            if np.array_equal(ctx[i:i + n], tail):
                follow = ctx[i + n:i + n + k]
                if len(follow):
                    return [int(t) for t in follow]
    return []


def make_prefill_step(model: Model, max_seq: int):
    def prefill_step(params, batch):
        return model.prefill(params, batch, max_seq)
    return prefill_step


def make_prefill_slot_step(model: Model, max_seq: int):
    """prefill_slot_step(params, full_cache, tokens, slot, length) ->
    (next_token, new_full_cache) — admit ONE request into ONE slot."""

    def prefill_slot_step(params, full_cache, tokens, slot, length):
        logits, new_cache = model.prefill_into_slot(
            params, full_cache, tokens, slot, length, max_seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt, new_cache

    return prefill_slot_step


def make_prefill_suffix_paged_step(model: Model, max_seq: int):
    """Paged admission, end-to-end: prefill the prompt's unmatched SUFFIX
    directly into the pool (no dense staging buffer, no commit-time
    copy).  ``offset`` counts the warm-prefix tokens already sitting in
    shared pages (0 cold); ``block_tables``/``write_tables`` come from
    ``PagedCacheManager.admit`` — the gather map over all mapped blocks
    and the write map naming only the fresh ones."""

    def prefill_suffix_paged(params, full_cache, tokens, slot, offset,
                             length, block_tables, write_tables):
        logits, new_cache = model.prefill_suffix_paged(
            params, full_cache, tokens, slot, offset, length, max_seq,
            block_tables, write_tables)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt, new_cache

    return prefill_suffix_paged


@dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (prompt_len,)
    max_new_tokens: int
    eos_token: Optional[int] = None  # retire the slot on this token
    out_tokens: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_first: float = 0.0             # wall time of the first output token
    t_done: float = 0.0
    slot: int = -1


@dataclass(eq=False)
class _Inflight:
    """One dispatched-but-undrained decode step (overlap mode).

    ``in_toks[slot]`` is the token whose K/V the step wrote — host-known
    at dispatch only right after activation (the prefill's first token);
    otherwise it is the PREVIOUS step's output and is filled in when that
    step drains (always before this record's own drain)."""
    arrs: List[Any]                   # [(next_tokens_device, a, b)]
    entries: List[Any]                # [(slot, req, pos_written)]
    in_toks: Dict[int, Optional[int]]
    act: int                          # active slots at dispatch


@dataclass
class ServingEngine:
    """Continuous batching over persistent slot-indexed caches.

    prefill_bucket: admitted prompts are right-padded to the next multiple
    of this, bounding jit specializations to O(max_seq / bucket) distinct
    prefill shapes.  Padding is only exact for attention mixers (causal
    masking); patterns with recurrent blocks (mamba/mlstm/slstm) fold the
    pad tokens into the state, so the engine auto-disables bucketing for
    them and prefills at the exact prompt length.

    plan: optional ``repro.plan.ServingPlan`` — run plan-driven (chunked
    prefill through the plan's stages + slot-partitioned spatial decode
    replicas).  Plan mode prefills chunks at exact lengths (the chunk
    size itself bounds jit specializations), so ``prefill_bucket`` is
    ignored.

    paged: pool-backed slot caches — global-attention KV lives in a
    fixed block pool of ``num_blocks`` pages of ``page_size`` tokens,
    addressed through per-slot block tables with content-hash prefix
    sharing, copy-on-write, and LRU reuse (``repro.cache``).  A slot then
    costs ``ceil(live_tokens / page_size)`` blocks instead of a dense
    ``max_seq`` reservation.  SSM state and sliding-window ring caches
    stay dense; a model with no global-attention layer auto-disables
    paging entirely.  ``num_blocks=0`` sizes the pool to the dense
    reservation (sharing then only *frees* blocks); smaller pools admit
    more slots than dense could — admission defers while the pool is
    full.  Plan mode shares ONE pool (and one manager, keyed by global
    slot ids) across all decode replicas: each replica's cache is a view
    whose paged leaves alias the same physical arrays, so prefix blocks
    are shared engine-wide and slot migration between replicas or plans
    (``replan``) is a block-table handoff, never a KV copy.
    """
    model: Model
    params: Any
    slots: int
    max_seq: int
    prefill_bucket: int = 16
    plan: Optional[Any] = None       # repro.plan.ServingPlan
    paged: bool = False
    page_size: int = 16
    num_blocks: int = 0              # 0 = slots * max_seq / page_size
    prefix_cache: bool = True        # paged only: registry lookups +
    #                                  block publication + suffix-only
    #                                  prefill on warm prefixes
    speculate: int = 0               # propose up to K tokens per slot via
    #                                  prompt-lookup n-gram drafting and
    #                                  verify them in one batched step
    #                                  (greedy verify: bit-identical to
    #                                  one-shot decode); 0 = off
    overlap: bool = False            # async runtime: decode step N+1 is
    #                                  dispatched before step N's tokens
    #                                  are read back (one-step-delayed
    #                                  drain).  Token streams stay
    #                                  bit-identical to sync mode; the
    #                                  only scheduling difference is that
    #                                  EOS/budget retirement frees a slot
    #                                  one tick later.  Speculation needs
    #                                  the drafts on host each tick, so an
    #                                  effective speculate > 0 forces sync.
    kv_dtype: str = "fp"             # "int8": paged K/V pools store
    #                                  per-row symmetric int8 + f32 scale
    #                                  — ~1.9x (bf16) / ~3.9x (f32) the
    #                                  tokens per byte of the fp layout
    #                                  (see stats()["cache"]
    #                                  ["kv_capacity_x"]); fp is bit-exact
    adapt: Optional[Any] = None      # repro.serving.adaptive.AdaptiveConfig:
    #                                  traffic-adaptive re-planning — a
    #                                  controller scores the candidate
    #                                  design points each tick window and
    #                                  calls replan() when one dominates
    trace: Optional[Any] = None      # repro.obs.TraceConfig (or True for
    #                                  defaults): record request-lifecycle
    #                                  + per-stage/per-replica spans into a
    #                                  ring-buffered Tracer (write_trace()
    #                                  exports Perfetto JSON).  None = off:
    #                                  the hot path allocates no event
    #                                  records at all

    def __post_init__(self):
        from repro.models import transformer as T
        self.cfg = self.model.cfg
        # the engine's prefill/decode steps execute their hot kernels via
        # the dispatch front door (repro.backend.dispatch) inside the model;
        # record the resolved default, and collect the path every kernel
        # actually took while the engine's steps trace, so serving stats
        # name what ran.
        self.kernel_path = dispatch.kernel_path()
        self._kernel_paths: Dict[str, set] = {}
        # every step that consumes the engine's cache donates it: the
        # engine always rebinds ``self._cache``/``self._caches[r]`` to the
        # step's output, so the input buffers are dead on return and the
        # runtime may update pages in place (a no-op where the backend
        # ignores donation — compat suppresses the advisory warning)
        self.serve_step = compat.donating_jit(make_serve_step(self.model),
                                              donate_argnums=(1,))
        self._prefill_slot = compat.donating_jit(
            make_prefill_slot_step(self.model, self.max_seq),
            donate_argnums=(1,))
        if any(not b.mixer.startswith("attn") or b.ffn == "moe"
               for b in self.cfg.block_pattern):
            # pad tokens are only exactly neutral under causal attention +
            # dense FFN: recurrent mixers fold them into the state, and MoE
            # routing lets them compete for expert capacity.  Prefill those
            # families at the exact prompt length instead.
            self.prefill_bucket = 1
        # smallest sliding-window ring among the mixers: a padded prompt
        # may never spill past it (the ring's tail write would keep pad
        # k/v and evict real tokens the gold decode still attends).
        self._ring_min = min(
            (min(self.max_seq, self.cfg.window_size)
             for b in self.cfg.block_pattern if b.mixer == "attn_local"),
            default=0)
        if self.paged and not T.has_paged_layers(self.cfg):
            # nothing to page: every mixer keeps dense state (SSM) or a
            # dense ring (local windows) — run the dense engine wholesale.
            self.paged = False
        # suffix-only prefill on warm prefixes: only when the prefill is
        # suffix-decomposable (all-global-attention, no MoE) — block-level
        # MEMORY sharing stays on for every paged family regardless.
        self._suffix_reuse = (self.paged and self.prefix_cache
                              and T.supports_prefix_compute_reuse(self.cfg))
        # speculative decode: same decomposability gate as suffix reuse —
        # a verify window replays K+1 positions through the cache, which
        # is exact only when every mixer is global attention and the FFN
        # is dense (ring wraps lose overwritten rows, SSM state cannot
        # rewind a rejected tail, MoE capacity couples window rows).
        self._spec_k = (self.speculate
                        if (self.speculate > 0
                            and T.supports_prefix_compute_reuse(self.cfg))
                        else 0)
        if self._spec_k:
            # built regardless of plan mode: a re-plan to monolithic must
            # be able to verify without mid-traffic setup (jit compiles
            # lazily, so an unused wrapper costs nothing)
            self._verify_step = compat.donating_jit(
                make_verify_step(self.model), donate_argnums=(1,))
        if self.kv_dtype not in ("fp", "int8"):
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r} must be 'fp' or 'int8'")
        if self.kv_dtype != "fp" and not self.paged:
            raise ValueError(
                "kv_dtype='int8' quantizes paged K/V pools — pass "
                "paged=True (and use a model with global-attention "
                "layers to page)")
        if self.paged:
            if self.max_seq % self.page_size:
                raise ValueError(
                    f"paged serving needs max_seq ({self.max_seq}) "
                    f"divisible by page_size ({self.page_size})")
            self._prefill_suffix_paged = compat.donating_jit(
                make_prefill_suffix_paged_step(self.model, self.max_seq),
                donate_argnums=(1,))
            self._copy_pages = compat.donating_jit(T.copy_cache_pages,
                                                   donate_argnums=(0,))
            self._scatter_paged = compat.donating_jit(T.scatter_prefill_part,
                                                      donate_argnums=(0,))
        # engine-lifetime state -------------------------------------------
        self._pf = None
        self._pager = None               # ONE PagedCacheManager for the
        #                                  whole engine, monolithic AND
        #                                  plan mode: rows are global slot
        #                                  ids and every decode replica's
        #                                  cache fronts the same physical
        #                                  pool, so prefix blocks share
        #                                  engine-wide and slot migration
        #                                  (replan / work stealing) is a
        #                                  table handoff, never a KV copy
        self._admit_plans = {}           # slot -> AdmitPlan (mid-prefill)
        self._rt = None
        self._rt_cache = {}              # ServingPlan -> PlanRuntime: a
        #                                  re-plan returning to a seen
        #                                  point reuses its compiled fns
        self._caches = None
        bps = self.max_seq // self.page_size if self.paged else 0
        self._total_blocks = (self.num_blocks or self.slots * bps
                              if self.paged else 0)
        if self.paged:
            from repro.cache import PagedCacheManager
            self._pager = PagedCacheManager(
                self.slots, self.max_seq, self.page_size,
                self._total_blocks,
                prefix_cache=self.prefix_cache, kv_dtype=self.kv_dtype,
                kv_capacity_ratio=T.paged_kv_capacity_ratio(
                    self.cfg, self.kv_dtype))
        if self.plan is not None:
            from repro.plan.serving import PrefillPipeline
            if self.plan.slots != self.slots:
                raise ValueError(
                    f"ServingPlan was lowered for {self.plan.slots} slots "
                    f"but the engine has {self.slots}; re-lower via "
                    f"lower_serving(plan, slots={self.slots})")
            self._rt = self._runtime_for(self.plan)
            self._pf = PrefillPipeline(self._rt, self.params)
            # one cache VIEW per decode replica (its slot partition is
            # the dense batch axis); paged views alias one shared pool
            self._caches = self._replica_views(self.plan, self._full_init())
            self._cache = None
            self.prefill_bucket = 1       # chunks run at exact lengths
        else:
            self._cache = self._full_init()
        self._pos = np.zeros((self.slots,), np.int32)    # tokens in cache
        self._cur = np.zeros((self.slots, 1), np.int32)  # next input token
        # overlap (async) runtime state: an effective speculate forces
        # sync, plan-less and plan-driven engines both support overlap
        self._overlap = bool(self.overlap) and self._spec_k == 0
        self._inflight: List[_Inflight] = []   # dispatched, undrained steps
        self._cur_dev = None             # device-side token chain: the last
        #                                  dispatched step's output array
        #                                  (so step N+1's inputs never
        #                                  round-trip through the host)
        self._cur_known = np.ones((self.slots,), bool)  # _cur[s] current?
        self._slot_req: List[Optional[Request]] = [None] * self.slots
        self._reserved = set()           # slots mid-(chunked)-prefill
        self.queue: List[Request] = []
        self.done: List[Request] = []
        # CONCURRENT peak across the per-replica pools: per-pool peaks
        # occur on different ticks, so summing them would overstate the
        # true footprint (and understate effective_slots_gain)
        from repro.cache import ConcurrentPeakTracker
        self._peak_tracker = ConcurrentPeakTracker()
        for pager in self._all_pagers():
            self._peak_tracker.attach(pager.pool)
        # stats ------------------------------------------------------------
        self.decode_steps = 0
        self.decode_tokens = 0            # tokens emitted by decode ticks
        self._occupied_step_sum = 0       # sum over steps of occupied slots
        self._decode_slot_steps = 0       # active slots at decode DISPATCH:
        #   each dispatched slot emits >= 1 token, so decode_tokens over
        #   this is exactly 1.0 for plain decode and > 1 when speculative
        #   verify accepts drafts ( _occupied_step_sum is sampled after
        #   retirement, so it undercounts the step that finishes a slot)
        self.prefill_batch_sizes: List[int] = []  # always 1 per admission
        self.prefill_token_counts: List[int] = []
        self.prefill_chunk_counts: List[int] = []  # chunks per admission
        self.ticks = 0
        self.spec_steps = 0               # decode ticks that ran a verify
        self.spec_proposed = 0            # drafted tokens offered to verify
        self.spec_accepted = 0            # drafted tokens accepted
        self.replans = 0                  # live plan swaps this window
        self.migrations = 0               # slots moved (work stealing)
        self.migration_copies = 0         # device row moves those cost —
        #                                  stays 0 on the paged path for
        #                                  models with no dense slot
        #                                  leaves (the zero-copy claim)
        # host wall-clock per engine phase, from the always-on phase
        # recorder (repro.obs.PhaseRecorder): every host phase of a tick
        # is one span, whose own time accrues to its bucket (admission,
        # prefill, decode, replan: the admission loop less the prefills
        # it runs, the rest one after another), and whose name shows in a
        # profile as a TraceAnnotation.  "host_sync" is an OVERLAY
        # bucket, not a partition member: it accrues inside whichever
        # phase is open and measures how much of that phase the host
        # spent blocked on device readback (the quantity the async
        # runtime shrinks) — see _sync().  "replan" charges controller
        # decisions + live swaps, so the migration interval is accounted,
        # never lost or double-counted.  "idle" is the host wall between
        # one tick's end and the next tick's start (queue-empty waits,
        # caller think time), so the partition buckets (all but the
        # host_sync overlay) sum to the first-tick-start → last-tick-end
        # wall, less the tick's few statements between its phases.
        from repro.obs import PhaseRecorder
        self._phases = PhaseRecorder()
        self._t_window = time.perf_counter()  # stats window start (reset_stats)
        self.submitted = 0                # lifetime submissions (monotonic)
        self._arrival_log = []            # (t_submit, prompt_len, max_new)
        #                                  ring consumed by the controller
        self._ctl = None
        if self.adapt is not None:
            from repro.serving.adaptive import ReplanController
            self._ctl = ReplanController(self.adapt)
            self._ctl.validate(self)
        # observability ----------------------------------------------------
        # Always on: the phase recorder above, a MetricsRegistry (one
        # histogram observe + two counter incs per retirement) and
        # per-stage / per-replica utilization accumulators (integer adds
        # per decode dispatch).  Strictly opt-in: the Tracer — self._tr
        # stays None unless trace= is passed (or enable_trace() is
        # called); the phase recorder forwards to it only when attached
        # and every other emission site is guarded on it, so the disabled
        # hot path allocates no event records.
        from repro.obs import MetricsRegistry, TPOT_BUCKETS, TTFT_BUCKETS
        self.metrics = MetricsRegistry()
        self._h_ttft = self.metrics.histogram(
            "repro_ttft_seconds", TTFT_BUCKETS,
            help="time to first token per retired request")
        self._h_tpot = self.metrics.histogram(
            "repro_tpot_seconds", TPOT_BUCKETS,
            help="time per output token per retired request")
        self._c_requests = self.metrics.counter(
            "repro_requests_total", help="requests retired")
        self._c_gen = self.metrics.counter(
            "repro_tokens_generated_total", help="tokens generated")
        self._stage_busy = {}             # stage idx -> busy stage-steps
        self._pipeline_ticks = 0          # ticks the prefill pipeline ran
        self._replica_busy = {}           # replica -> occupied slot-steps
        self._replica_cap = {}            # replica -> dispatched capacity
        self._tr = None
        self.trace_config = None
        if self.trace:
            self.enable_trace(self.trace)

    # -- public API --------------------------------------------------------
    def submit(self, req: Request):
        if len(req.prompt) >= self.max_seq:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens cannot fit a "
                f"max_seq={self.max_seq} slot cache")
        req.t_submit = time.perf_counter()
        self.queue.append(req)
        self.submitted += 1
        self._arrival_log.append((req.t_submit, len(req.prompt),
                                  req.max_new_tokens))
        if len(self._arrival_log) > 4 * self.slots + 256:
            del self._arrival_log[:len(self._arrival_log) // 2]
        if self._tr is not None:
            self._tr.instant("requests", "submit", t=req.t_submit, args={
                "uid": req.uid, "prompt_tokens": len(req.prompt),
                "max_new": req.max_new_tokens})

    def enable_trace(self, cfg: Any = True):
        """Attach a fresh ring-buffered Tracer (``repro.obs.Tracer``) to
        the engine and its prefill pipeline.  ``cfg`` is a
        ``repro.obs.TraceConfig`` (or ``True`` for defaults).  Can be
        called mid-serve — e.g. benchmarks measure with tracing off, then
        trace one extra round for the artifact.  Returns the tracer."""
        from repro.obs import TraceConfig, Tracer
        if cfg is True or cfg is None:
            cfg = TraceConfig()
        self.trace_config = cfg
        self._tr = Tracer(cfg.capacity)
        self._phases.tracer = self._tr
        if self._pf is not None:
            self._pf.tracer = self._tr
        return self._tr

    @property
    def active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    def _active_in(self, a: int, b: int) -> int:
        return sum(self._slot_req[s] is not None for s in range(a, b))

    @property
    def _label(self) -> str:
        return self.plan.label if self.plan is not None else "mono"

    def tick(self) -> bool:
        """Admit whatever fits, advance any in-flight chunked prefills by
        one stage-step, then run one batched decode step per replica.
        Returns True while there is (or may be) work in flight.

        Each phase is a span of the phase recorder, and its host
        wall-clock accrues in ``phase_time_s`` (the prefill compute
        launched inside admission is credited to "prefill", so
        "admission" is pure bookkeeping — block matching, allocation,
        padding, slot choice, activation).

        Overlap mode reorders the decode phase: this tick's step is
        DISPATCHED first (its inputs are the previous step's output array,
        still on device), and only then is the previous step's result
        read back — so the device computes step N while the host drains
        step N-1 and runs the next tick's admission bookkeeping.  The
        drained tokens retire slots exactly as sync mode does, one tick
        later; the per-request token streams are identical."""
        with dispatch.record_paths(self._kernel_paths), self._phases.tick():
            return self._tick()

    def _tick(self) -> bool:
        ph = self._phases
        tr = self._tr
        if tr is not None:
            tr.counter("tick", "engine", {"queue": len(self.queue),
                                          "active": self.active})
        if self._ctl is not None and not self._ctl.paused:
            with ph.span("engine.control"):
                # None = keep, (plan,) = swap
                decision = self._ctl.observe(self)
            if tr is not None and self._ctl.last_scores is not None:
                # a decision tick: record what the controller weighed
                tr.instant("tick", "replan_decision", args={
                    "scores": self._ctl.last_scores,
                    "decision": ("keep" if decision is None else
                                 (decision[0].label
                                  if decision[0] is not None else "mono"))})
            if decision is not None:
                self.replan(decision[0])
        if self.queue:
            q0 = len(self.queue)
            with ph.span("engine.admission", args=lambda: {
                    "queued": q0, "admitted": q0 - len(self.queue),
                    "plan": self._label}):
                self._admit()
        if self._pf is not None and self._pf.busy:
            with ph.span("engine.prefill.step"):
                # paged stage steps thread the replica caches; after a
                # re-plan to monolithic the drained items route through a
                # one-entry list wrapping the monolithic cache (item.replica
                # remapped 0)
                if self.paged:
                    clist = self._caches if self.plan is not None \
                        else [self._cache]
                else:
                    clist = self._caches if self.plan is not None else None
                finished = self._pf.step(caches=clist,
                                         on_chunk=self._chunk_committed)
                self._pipeline_ticks += 1
                for s in self._pf.last_stages_run:
                    self._stage_busy[s] = self._stage_busy.get(s, 0) + 1
                if self.paged and self.plan is None:
                    self._cache = clist[0]
                for item in finished:
                    with ph.span("engine.prefill.finish", item.req.uid):
                        self._finish_prefill(item)
        if self._pf is not None and self.plan is None and not self._pf.busy:
            self._pf = None       # old pipeline fully drained post-re-plan
        if self.active or self._inflight:
            with ph.span("engine.decode"):
                dispatched = False
                if self.active:
                    if self._overlap:
                        self._dispatch_decode()
                        dispatched = True
                    else:
                        self._decode_once()
                # one-step-delayed drain: the newest dispatch stays in
                # flight while its predecessor's tokens come back; once
                # nothing new dispatches, drain everything so the last
                # slots retire.
                while len(self._inflight) > (1 if dispatched else 0):
                    self._drain_one()
        self.ticks += 1
        return bool(self.active or self.queue or self._inflight
                    or (self._pf is not None and self._pf.busy))

    def run(self, max_steps: int = 10_000):
        """Drive ticks until every submitted request retires."""
        steps = 0
        while self.tick() and steps < max_steps:
            steps += 1
        return self.done

    def replan(self, plan, *, rebalance: bool = True):
        """Swap the engine onto a different ``ServingPlan`` (None =
        monolithic) WITHOUT dropping requests — the online Pareto move.

        What moves, and what does not:
          * paged K/V: never.  Every replica fronts ONE physical pool and
            ONE ``PagedCacheManager`` whose rows are global slot ids, so
            a slot's paged state is plan-independent — re-binding decode
            to a new replica partition is pure pytree restructuring
            (``slice_cache_slots`` / ``concat_cache_slots`` pass pool
            leaves through untouched).  ``migration_copies`` stays 0.
          * dense slot leaves (SSM state, local-window rings; every leaf
            on a dense engine): re-layout between partitions by slot-axis
            concat/slice.  All-global-attention paged models have none —
            their swap does no device work at all.
          * in-flight chunked prefills: drain-and-rebind — remaining
            chunks finish on the runtime they were admitted under
            (bit-exact stage walk), only their replica-cache routing is
            remapped; decode re-binds to the new plan immediately.
          * overlap mode: the undrained steps land first (the same drain
            the final ticks run — token streams are unaffected).

        Stats windows are CONTINUOUS across the swap: no counter resets,
        the swap's own wall time accrues in ``phase_time_s["replan"]``
        (an ``engine.replan`` span, ``engine.rebalance`` for work
        stealing), and the surviving pool re-attaches to the
        engine-lifetime peak tracker (idempotent), so the concurrent peak
        spans the swap.

        ``replan(current_plan)`` degenerates to pure cross-replica work
        stealing: re-balance the active slots over the replicas with
        zero-copy migrations (``rebalance=False`` suppresses it)."""
        if plan is not None and plan.slots != self.slots:
            raise ValueError(
                f"ServingPlan was lowered for {plan.slots} slots "
                f"but the engine has {self.slots}; re-lower via "
                f"lower_serving(plan, slots={self.slots})")
        old = self._label
        if plan == self.plan:
            if rebalance and self.plan is not None:
                with self._phases.span("engine.rebalance", args=lambda: {
                        "plan": old, "migrations": self.migrations}):
                    self._drain_inflight()
                    self._rebalance_slots()
            return
        with self._phases.span("engine.replan", args=lambda: {
                "from": old, "to": self._label,
                "migrations": self.migrations,
                "migration_copies": self.migration_copies}):
            self._swap(plan, rebalance)

    def _swap(self, plan, rebalance: bool):
        """``replan``'s body for a plan other than the current one."""
        from repro.models import transformer as T
        # 1. land everything in flight on the old binding
        self._drain_inflight()
        # 2. collect the full slot-axis cache (dense leaves concat in
        #    replica order — partitions are contiguous ascending slot
        #    ranges; shared paged pool leaves pass through as-is)
        if self.plan is not None:
            full = T.concat_cache_slots(self._caches)
        else:
            full = self._cache
        # 3. drain-and-rebind the prefill pipeline: in-flight items keep
        #    their admission runtime (item.rt); only the replica-cache
        #    routing is remapped to wherever their slot lives now
        items = list(self._pf.items) if self._pf is not None else []
        if plan is not None:
            from repro.plan.serving import PrefillPipeline
            self._rt = self._runtime_for(plan)
            pf = PrefillPipeline(self._rt, self.params, tracer=self._tr)
            pf.adopt(items)
            self._pf = pf
        else:
            self._rt = None
            if not items:
                self._pf = None
            # else: the old pipeline survives solely to drain its items
            # (tick() routes it the monolithic cache and drops it dry)
        for it in items:
            it.replica, it.local_slot = ((0, it.slot) if plan is None
                                         else plan.replica_of_slot(it.slot))
        # 4. re-bind decode to the new replica partition
        self.plan = plan
        if plan is not None:
            self._caches = self._replica_views(plan, full)
            self._cache = None
        else:
            self._cache = full
            self._caches = None
        # 5. the pool and its manager survive verbatim (rows are global
        #    slot ids) — re-attach to the engine-lifetime peak tracker
        for pager in self._all_pagers():
            self._peak_tracker.attach(pager.pool)
        # 6. spread the surviving decode slots over the new replicas
        if rebalance and plan is not None:
            self._rebalance_slots()
        self.replans += 1

    def warm_replans(self):
        """Exercise every adaptive candidate once (measured profiles,
        runtimes, and a tiny end-to-end request per candidate) so jitted
        paths compile outside the measured window, then restore the
        initial binding.  Call before ``reset_stats()`` in benchmarks."""
        if self._ctl is None:
            return
        initial = self.plan
        self._ctl.paused = True
        try:
            self._ctl.warm(self)
            uid = -1
            for cand in self._ctl.cfg.plans:
                self.replan(cand, rebalance=False)
                chunk = cand.chunk if cand is not None else 4
                prompt = np.ones((max(2 * chunk, 4),), np.int32)
                self.submit(Request(uid=uid, prompt=prompt,
                                    max_new_tokens=3))
                uid -= 1
                self.run()
            self.replan(initial, rebalance=False)
        finally:
            self._ctl.paused = False

    def _drain_inflight(self):
        """Retire the async runtime's undrained steps (overlap mode): a
        re-plan re-binds the decode caches, so every dispatched step must
        land first.  The same drain the final ticks run — token streams
        are unaffected."""
        while self._inflight:
            self._drain_one("engine.replan.drain")
        self._cur_dev = None

    def _rebalance_slots(self):
        """Cross-replica work stealing: migrate active decode slots from
        overloaded replicas onto free slots of underloaded ones until no
        replica holds 2+ more than another.  Mid-prefill (reserved) slots
        stay put — their chunks are still streaming.  Paged moves are
        block-table handoffs (zero KV copies)."""
        plan = self.plan
        R = plan.n_replicas
        while True:
            load = [0] * R
            for s in range(self.slots):
                if self._slot_req[s] is not None or s in self._reserved:
                    load[plan.replica_of_slot(s)[0]] += 1
            hi = max(range(R), key=lambda r: load[r])
            lo = min(range(R), key=lambda r: load[r])
            if load[hi] - load[lo] <= 1:
                return
            a, b = plan.replica_range(hi)
            movable = [s for s in range(a, b)
                       if self._slot_req[s] is not None
                       and s not in self._reserved]
            la, lb = plan.replica_range(lo)
            dsts = [s for s in range(la, lb)
                    if self._slot_req[s] is None
                    and s not in self._reserved]
            if not movable or not dsts:
                return        # surplus is all mid-prefill: nothing to steal
            self._migrate_slot(movable[-1], dsts[0])

    def _migrate_slot(self, src: int, dst: int):
        """Move one ACTIVE decode request between slots (and thus
        replicas).  Paged path: the slot's state IS its block-table row —
        ``PagedCacheManager.migrate_slot`` hands the row over and no KV
        moves.  Dense slot leaves (SSM state, rings; all leaves on a
        dense engine) move one batch row on device; ``migration_copies``
        counts those and stays 0 for all-global-attention paged models."""
        from repro.models import transformer as T
        assert self._slot_req[dst] is None and dst not in self._reserved
        assert src not in self._reserved
        req = self._slot_req[src]
        if self._pager is not None:
            self._pager.migrate_slot(src, dst)
        rs, ls = self.plan.replica_of_slot(src)
        rd, ld = self.plan.replica_of_slot(dst)
        part = T.extract_dense_slot(self._caches[rs], ls)
        if part:
            if self.paged:
                self._caches[rd] = self._scatter_paged(
                    self._caches[rd], part, jnp.int32(ld))
                self._share_pool(rd)
            else:
                self._caches[rd] = T.scatter_cache_slot(
                    self._caches[rd], part, jnp.int32(ld))
            self.migration_copies += 1
        self._slot_req[dst] = req
        self._slot_req[src] = None
        req.slot = dst
        self._pos[dst] = self._pos[src]
        self._pos[src] = 0
        self._cur[dst] = self._cur[src]
        self._cur_known[dst] = self._cur_known[src]
        self._cur_known[src] = True
        self.migrations += 1

    def reset_stats(self):
        """Zero the counters (e.g. after a compile-warmup run) so stats()
        reports only the measured window.  Active slots (and the blocks
        they reference) are untouched; pool *counters* reset so reuse and
        copy-on-write rates cover only the measured window."""
        self.done = []
        self.decode_steps = 0
        self.decode_tokens = 0
        self._occupied_step_sum = 0
        self._decode_slot_steps = 0
        self.prefill_batch_sizes = []
        self.prefill_token_counts = []
        self.prefill_chunk_counts = []
        self.ticks = 0
        self.spec_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.replans = 0
        self.migrations = 0
        self.migration_copies = 0
        self._phases.reset()         # buckets, span log; idle accrues only
        #                              BETWEEN ticks of the new window
        self._stage_busy = {}
        self._pipeline_ticks = 0
        self._replica_busy = {}
        self._replica_cap = {}
        self.metrics.reset()
        # requests already in flight keep their pre-reset t_submit; the
        # stats() wall window clamps to this timestamp so the measured
        # window never reaches back before the reset
        self._t_window = time.perf_counter()
        self._peak_tracker.reset()
        for pager in self._all_pagers():
            p = pager.pool
            p.prefix_queries = p.prefix_hits = 0
            p.cow_copies = p.evictions = 0
            p.peak_in_use = p.blocks_in_use
            p.prefill_admissions = p.prefill_compute_hits = 0
            p.reused_prefill_tokens = p.suffix_prefill_tokens = 0
            pager.migrations = 0

    def _all_pagers(self):
        return [self._pager] if self._pager is not None else []

    def cache_stats(self) -> Dict[str, Any]:
        """Cache memory utilization: live vs reserved tokens, and for
        paged engines the block-pool picture (occupancy, prefix-reuse hit
        rate, copy-on-write/eviction counts, and the effective-slots gain
        — how many dense slot reservations the peak paged footprint
        actually amounted to)."""
        live = int(sum(int(self._pos[s]) for s in range(self.slots)
                       if self._slot_req[s] is not None))
        reserved = self.slots * self.max_seq
        out: Dict[str, Any] = {
            "layout": "paged" if self.paged else "dense",
            "live_tokens": live,
            "reserved_tokens": reserved,
            "utilization": live / reserved if reserved else 0.0,
        }
        pagers = self._all_pagers()
        if pagers:
            # kv layout keys are properties of the (shared) pool dtype,
            # not additive counters — every replica pool carries the same
            agg = {k: sum(p.stats()[k] for p in pagers)
                   for k in pagers[0].stats()
                   if k not in ("kv_dtype", "kv_capacity_x")}
            agg["kv_dtype"] = pagers[0].kv_dtype
            agg["kv_capacity_x"] = pagers[0].kv_capacity_ratio
            agg["page_size"] = self.page_size
            agg["reuse_hit_rate"] = (
                agg["prefix_hits"] / max(agg["prefix_queries"], 1))
            # non-additive keys: recompute over the aggregate
            agg["prefix_cache"] = self.prefix_cache
            agg["prefill_hit_rate"] = (
                agg["prefill_compute_hits"]
                / max(agg["prefill_admissions"], 1))
            # per-pool peaks occur on different ticks: report the tracked
            # CONCURRENT peak, not the sum of per-pool maxima
            agg["peak_blocks_in_use"] = self._peak_tracker.peak
            dense_blocks = self.slots * (self.max_seq // self.page_size)
            agg["effective_slots_gain"] = (
                dense_blocks / max(agg["peak_blocks_in_use"], 1))
            out.update(agg)
        return out

    def utilization_stats(self) -> Dict[str, Any]:
        """Windowed pipeline/replica utilization from the always-on
        accumulators — the paper's utilization story in numbers.  A pure
        read (snapshot): repeated calls in one window return equal dicts.

          * ``stage_bubble_frac[s]``: fraction of busy-pipeline ticks on
            which prefill stage ``s`` ran NO chunk (pipeline bubbles);
          * ``replica_occupancy[r]``: occupied slot-steps / dispatched
            slot-step capacity of decode replica ``r`` (mono = replica 0
            over all slots);
          * ``replica_load_spread``: max-min occupancy gap (0 = balanced
            — what ``_rebalance_slots`` drives toward);
          * speculation acceptance + prefix compute-hit rates, the two
            signals ROADMAP item 3 wants priced into the cost model."""
        pt = self._pipeline_ticks
        n_stages = self.plan.n_stages if self.plan is not None else 0
        if self._stage_busy:
            n_stages = max(n_stages, max(self._stage_busy) + 1)
        bubbles = ({s: 1.0 - self._stage_busy.get(s, 0) / pt
                    for s in range(n_stages)} if pt else {})
        occ = {r: self._replica_busy.get(r, 0) / c
               for r, c in sorted(self._replica_cap.items()) if c}
        spread = (max(occ.values()) - min(occ.values())) if occ else 0.0
        hits = queries = 0
        for p in self._all_pagers():
            ps = p.stats()
            hits += ps["prefill_compute_hits"]
            queries += ps["prefill_admissions"]
        return {
            "pipeline_ticks": pt,
            "stage_busy_ticks": dict(sorted(self._stage_busy.items())),
            "stage_bubble_frac": bubbles,
            "replica_occupancy": occ,
            "replica_load_spread": spread,
            "spec_acceptance_rate": (self.spec_accepted
                                     / max(self.spec_proposed, 1)),
            "prefix_hit_rate": hits / max(queries, 1),
        }

    def traffic_snapshot(self, window_s: float = 2.0, *,
                         slo_ttft_s: float = 0.0, slo_tpot_s: float = 0.0,
                         horizon_s: float = 0.0):
        """One typed observation of live traffic (``TrafficSnapshot``), or
        None when the engine is idle — what the adaptive controller reads
        each decision window instead of poking engine internals."""
        from repro.obs import TrafficSnapshot
        now = time.perf_counter()
        w = max(window_s, 1e-6)
        recent = [(t, pl, mn) for t, pl, mn in self._arrival_log
                  if t >= now - w]
        lam = len(recent) / w
        avg_prompt = (float(np.mean([pl for _, pl, _ in recent]))
                      if recent else 0.0)
        avg_new = (float(np.mean([mn for _, _, mn in recent]))
                   if recent else 0.0)
        queued_tok = float(sum(len(r.prompt) for r in self.queue))
        rem = [r.max_new_tokens - len(r.out_tokens)
               for r in self._slot_req if r is not None]
        depth = float(np.mean(rem)) if rem else 0.0
        # forecast decode depth for work that has not prefilled yet
        incoming = len(self.queue) + lam * horizon_s
        if incoming > 0 and avg_new > 0:
            depth = max(depth, avg_new)
        if not rem and not self.queue and not recent:
            return None                      # idle: nothing to navigate
        violated = False
        if slo_ttft_s > 0:
            tail = self.done[-8:]
            if any(r.t_first - r.t_submit > slo_ttft_s for r in tail):
                violated = True
            if self.queue and now - self.queue[0].t_submit > slo_ttft_s:
                violated = True
        if slo_tpot_s > 0:
            for r in self.done[-8:]:
                n = max(len(r.out_tokens) - 1, 1)
                if (r.t_done - r.t_first) / n > slo_tpot_s:
                    violated = True
        return TrafficSnapshot(
            lam=lam, avg_prompt=avg_prompt, avg_new=avg_new,
            queued_tok=queued_tok, depth=depth, queue_len=len(self.queue),
            active=self.active, violated=violated, window_s=w)

    def export_metrics(self):
        """Fold the current ``stats()`` snapshot into the engine's
        ``MetricsRegistry`` as gauges (idempotent — gauges are set, never
        accrued) and return the registry, ready for ``to_prometheus()``
        or ``repro.obs.write_metrics``."""
        from repro.obs import fold_engine_metrics
        fold_engine_metrics(self.metrics, self.stats())
        return self.metrics

    def write_trace(self, path: str):
        """Export the tracer's retained records as Perfetto trace_event
        JSON (open at ui.perfetto.dev).  Requires tracing on."""
        from repro.obs import write_trace
        if self._tr is None:
            raise ValueError(
                "tracing is off: pass trace=TraceConfig() at construction "
                "or call enable_trace() first")
        write_trace(self._tr, path)

    def stats(self) -> Dict[str, Any]:
        """Serving-side latency/throughput numbers for the SSR story."""
        reqs = self.done
        gen = sum(len(r.out_tokens) for r in reqs)
        if reqs:
            # clamp submits to the measurement-window start: a request
            # active across reset_stats() keeps its pre-reset t_submit,
            # which must not stretch the post-reset wall window
            t0 = min(max(r.t_submit, self._t_window) for r in reqs)
            wall = max(r.t_done for r in reqs) - t0
        else:
            wall = 0.0
        cap = max(self.decode_steps * self.slots, 1)
        out = {
            "kernel_path": self.kernel_path,
            # path each dispatched kernel took in this engine's compiled
            # steps ("pallas" / "interpret" / "ref"; "+"-joined if mixed)
            "kernels": {k: "+".join(sorted(v))
                        for k, v in sorted(self._kernel_paths.items())},
            "requests": len(reqs),
            "gen_tokens": gen,
            "decode_steps": self.decode_steps,
            "decode_tokens": self.decode_tokens,
            # per-slot tokens per decode step: exactly 1.0 for plain
            # decode, > 1 when speculation is accepting drafted tokens
            "tokens_per_step": (self.decode_tokens
                                / max(self._decode_slot_steps, 1)),
            "spec_steps": self.spec_steps,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "acceptance_rate": (self.spec_accepted
                                / max(self.spec_proposed, 1)),
            "slot_occupancy": self._occupied_step_sum / cap,
            "replans": self.replans,
            "migrations": self.migrations,
            "migration_copies": self.migration_copies,
            "throughput_tok_s": gen / wall if wall > 0 else 0.0,
            "ttft_s": [r.t_first - r.t_submit for r in reqs],
            "latency_s": [r.t_done - r.t_submit for r in reqs],
            "ticks": self.ticks,
            "phase_time_s": dict(self._phases.time),
            # the phase spans of the window, (name, t0_ns, t1_ns, tick,
            # uid) on the perf_counter_ns clock, still-queued requests
            # with their wait so far (repro.obs.PhaseRecorder.spans)
            "spans": self._phases.spans(
                [(r.uid, r.t_submit) for r in self.queue]),
            "spans_dropped": self._phases.dropped,
            "cache": self.cache_stats(),
            "utilization": self.utilization_stats(),
        }
        out["plan_label"] = self._label
        if self.plan is not None:
            out["plan_stages"] = self.plan.n_stages
            out["decode_replicas"] = self.plan.n_replicas
            out["prefill_chunk"] = self.plan.chunk
        return out

    # -- internals ---------------------------------------------------------
    def _sync(self, x) -> np.ndarray:
        """Block on a device value and charge the wait to the
        ``host_sync`` phase bucket.  The bucket overlays the phase
        windows (the wait also sits inside whichever phase is open): it
        measures how much host wall-clock was spent blocked on device
        readback — the quantity the overlap runtime removes from the
        critical path by dispatching the next step first."""
        t0 = time.perf_counter()
        arr = np.asarray(x)
        self._phases.time["host_sync"] += time.perf_counter() - t0
        return arr

    def _padded_len(self, n: int) -> int:
        b = max(self.prefill_bucket, 1)
        pp = min(-(-n // b) * b, self.max_seq - 1)
        if self._ring_min:
            # never pad past a sliding window; a prompt longer than the
            # window prefills at its exact length (its own tail legally
            # wraps the ring, exactly as the gold one-shot prefill does).
            pp = n if n > self._ring_min else min(pp, self._ring_min)
        return pp

    def _free_slots(self):
        return [s for s in range(self.slots)
                if self._slot_req[s] is None and s not in self._reserved]

    def _pager_of(self, slot: int):
        """(PagedCacheManager, manager row) for an engine slot — (None,
        slot) when the engine is dense.  The manager is engine-global
        (rows = global slot ids) in BOTH monolithic and plan mode."""
        return self._pager, slot

    def _full_init(self):
        """A fresh full-width cache (batch axis = all slots): the paged
        pool sized to the engine-global total (one physical pool,
        whatever the replica layout)."""
        if self.paged:
            return self.model.init_paged_cache(
                self.slots, self.max_seq, page_size=self.page_size,
                num_blocks=self._total_blocks, kv_dtype=self.kv_dtype)
        return self.model.init_cache(self.slots, self.max_seq)

    def _replica_views(self, plan, full):
        """Split a full-width cache into per-replica views: dense leaves
        slice on the slot axis, paged pool leaves pass through — every
        view fronts the SAME physical pool."""
        from repro.models import transformer as T
        return [T.slice_cache_slots(full, a, b - a)
                for a, b in (plan.replica_range(r)
                             for r in range(plan.n_replicas))]

    def _share_pool(self, r: int):
        """Re-alias every other replica cache's pool leaves to replica
        ``r``'s, which a step just rebound (the step donated the shared
        pool buffers it consumed).  Host-side pytree restructuring only —
        this is what keeps N replica views fronting one physical pool."""
        if not self.paged or self.plan is None:
            return
        from repro.models import transformer as T
        src = self._caches[r]
        for i in range(len(self._caches)):
            if i != r:
                self._caches[i] = T.rebind_pool_leaves(self._caches[i], src)

    def _runtime_for(self, splan):
        """The (cached) PlanRuntime for a ServingPlan — re-planning back
        to a previously-seen design point reuses its compiled fns."""
        rt = self._rt_cache.get(splan)
        if rt is None:
            from repro.plan.serving import PlanRuntime
            rt = PlanRuntime(self.model, splan, self.max_seq)
            self._rt_cache[splan] = rt
        return rt

    def _pick_slot(self, free):
        """Admission slot choice.  Monolithic engines take the first free
        slot; plan mode picks a free slot on the least-loaded replica so
        admissions spread over the spatial decode replicas (the same
        balance objective ``_rebalance_slots`` restores after a
        re-plan).  The shared pool makes the outcome otherwise
        slot-independent."""
        if self.plan is None:
            return free[0]
        load = [0] * self.plan.n_replicas
        for s in range(self.slots):
            if self._slot_req[s] is not None or s in self._reserved:
                load[self.plan.replica_of_slot(s)[0]] += 1
        return min(free,
                   key=lambda s: (load[self.plan.replica_of_slot(s)[0]], s))

    def _admit(self):
        while self.queue:
            req = self.queue[0]
            free = self._free_slots()
            if not free:
                return
            slot = self._pick_slot(free)
            ok = (self._admit_one_plan(req, slot)
                  if self.plan is not None else self._admit_one(req, slot))
            if not ok:
                return    # head-of-line waits for pool blocks (stays FIFO)
            self.queue.pop(0)

    # ---- monolithic admission (no plan) ----------------------------------
    def _admit_one(self, req: Request, slot: int) -> bool:
        """Prefill ONE request into ONE free slot: O(prompt) compute, no
        other slot's cache row or position is touched.  Returns False when
        a paged pool cannot supply the prompt's blocks yet.

        Paged admission is suffix-only: the pager reports how many prefix
        tokens are already sitting in warm registry blocks
        (``AdmitPlan.reused_tokens``) and the prefill runs over just the
        remaining suffix, writing fresh pages through ``write_table`` and
        attending the warm ones through ``block_table``.  Cold prompts
        are the reused=0 special case of the same path."""
        with self._phases.span("engine.admit", req.uid) as adm:
            reused = 0
            if self._pager is not None:
                # speculation headroom: a verify window may write K
                # positions past the plain-decode frontier before rolling
                # back, so the growth reservation covers them
                ap = self._pager.admit(slot, req.prompt,
                                       req.max_new_tokens + self._spec_k,
                                       reuse_compute=self._suffix_reuse)
                if ap is None:
                    adm.name = "engine.admit.blocked"  # not an admission
                    return False
                reused = ap.reused_tokens
            slen = len(req.prompt) - reused
            toks = np.zeros((1, self._padded_len(slen)), np.int32)
            toks[0, :slen] = req.prompt[reused:]
        self._phases.queued(req.uid, req.t_submit, adm.t0)
        with self._phases.span("engine.prefill", req.uid, ("stage", 0),
                               args=lambda: {
                                   "uid": req.uid, "slot": slot,
                                   "tokens": slen, "reused": reused,
                                   "plan": "mono"}):
            if self._pager is not None:
                nxt, self._cache = self._prefill_suffix_paged(
                    self.params, self._cache, jnp.asarray(toks),
                    jnp.int32(slot), jnp.int32(reused), jnp.int32(slen),
                    jnp.asarray(ap.block_table)[None],
                    jnp.asarray(ap.write_table)[None])
                self._pager.commit(slot)  # pages landed: publish for reuse
            else:
                nxt, self._cache = self._prefill_slot(
                    self.params, self._cache, jnp.asarray(toks),
                    jnp.int32(slot), jnp.int32(slen))
            tok = int(self._sync(nxt)[0])  # host sync: prefill has run
        self.prefill_batch_sizes.append(1)
        # unpadded suffix tokens, same unit as plan-mode admission:
        # bucket padding is a jit-shape artifact, not prefill work
        self.prefill_token_counts.append(slen)
        self.prefill_chunk_counts.append(1)
        self._activate(req, slot, tok)
        return True

    # ---- plan-driven admission (chunked prefill as plan stages) ----------
    def _admit_one_plan(self, req: Request, slot: int) -> bool:
        """Reserve the slot and enter the chunked-prefill pipeline: the
        prompt streams through the plan's stage slices one stage-step per
        tick (``PrefillPipeline``), so admission never stalls decode.
        Paged replicas reserve the prompt's pool blocks up front (the
        scatter at finish must not fail mid-flight)."""
        replica, local = self.plan.replica_of_slot(slot)
        reused = 0
        with self._phases.span("engine.admit", req.uid) as adm:
            if self._pager is not None:
                ap = self._pager.admit(slot, req.prompt,
                                       req.max_new_tokens + self._spec_k,
                                       reuse_compute=self._suffix_reuse)
                if ap is None:
                    adm.name = "engine.admit.blocked"  # not an admission
                    return False
                self._admit_plans[slot] = ap
                reused = ap.reused_tokens
                self._reserved.add(slot)
                self._pf.admit(req, slot, replica, local, reused=reused,
                               tables=(ap.block_table, ap.write_table))
            else:
                self._reserved.add(slot)
                self._pf.admit(req, slot, replica, local)
        self._phases.queued(req.uid, req.t_submit, adm.t0)
        self.prefill_batch_sizes.append(1)
        self.prefill_token_counts.append(len(req.prompt) - reused)
        self.prefill_chunk_counts.append(
            len(self._pf.items[-1].chunks))
        return True

    def _chunk_committed(self, slot: int, tokens_done: int):
        """A prefill chunk left the last stage with its pool pages
        written: publish the slot's newly-completed blocks for prefix
        reuse now, without waiting for the whole admission to finish."""
        pager, local = self._pager_of(slot)
        if pager is not None:
            pager.commit_chunk(local, tokens_done)
            if self._tr is not None:
                self._tr.instant("requests", "commit", args={
                    "slot": slot, "tokens_done": tokens_done})

    def _finish_prefill(self, item):
        """Last chunk left the last stage: bank the first token, scatter
        the request's batch-1 DENSE leaves (SSM state, ring caches) into
        its replica's slot partition — the paged K/V already streamed
        into the shared pool as the chunks ran — and start decoding.

        The scatter targets the CURRENT binding: after a re-plan the
        drained item's chunks ran on its admission runtime
        (drain-and-rebind), but the finished slot lands wherever the slot
        lives now (a new replica partition, or the monolithic cache)."""
        from repro.models import transformer as T
        nxt, _ = (item.rt or self._rt).finish(self.params, item.final_hidden)
        tok = int(self._sync(nxt)[0])     # host sync: prefill has run
        if self.plan is not None:
            replica, local = self.plan.replica_of_slot(item.slot)
            if self.paged:
                self._admit_plans.pop(item.slot, None)
                self._caches[replica] = self._scatter_paged(
                    self._caches[replica], item.part_cache,
                    jnp.int32(local))
                self._share_pool(replica)
                self._pager.commit(item.slot)
            else:
                self._caches[replica] = T.scatter_cache_slot(
                    self._caches[replica], item.part_cache,
                    jnp.int32(local))
        elif self.paged:
            self._admit_plans.pop(item.slot, None)
            self._cache = self._scatter_paged(
                self._cache, item.part_cache, jnp.int32(item.slot))
            self._pager.commit(item.slot)
        else:
            self._cache = T.scatter_cache_slot(
                self._cache, item.part_cache, jnp.int32(item.slot))
        self._reserved.discard(item.slot)
        self._activate(item.req, item.slot, tok)

    def _activate(self, req: Request, slot: int, first_token: int):
        req.slot = slot
        req.t_first = time.perf_counter()
        if self._tr is not None:
            # zero-width admit marker carrying the request flow start:
            # the arrow leaves here and lands on the retire marker
            self._tr.span("requests", "admit", req.t_first, req.t_first,
                          args={"uid": req.uid, "slot": slot,
                                "queued_s": req.t_first - req.t_submit},
                          flow_out=req.uid)
        req.out_tokens.append(first_token)
        self._slot_req[slot] = req
        self._pos[slot] = len(req.prompt)
        self._cur[slot, 0] = req.out_tokens[-1]
        self._cur_known[slot] = True
        if self._overlap and self._cur_dev is not None:
            # patch the fresh slot's input token into the device-side
            # token chain (the other slots' entries are the undrained
            # previous step's outputs — they must not round-trip here)
            self._cur_dev = self._cur_dev.at[slot, 0].set(
                jnp.int32(first_token))
        self._maybe_retire(slot, req.t_first)

    # ---- decode ----------------------------------------------------------
    def _prepare_paged_writes(self, first: int, last: int):
        """Before a decode step: make every active slot's target block
        writable — allocate at page boundaries, copy-on-write shared or
        registered blocks (the device page copy runs here, before the
        step's write lands)."""
        for slot in range(first, last):
            if self._slot_req[slot] is None:
                continue
            cow = self._pager.prepare_decode(slot, int(self._pos[slot]))
            if cow is not None:
                src, dst = cow
                if self.plan is None:
                    self._cache = self._copy_pages(
                        self._cache, jnp.int32(src), jnp.int32(dst))
                else:
                    r, _ = self.plan.replica_of_slot(slot)
                    self._caches[r] = self._copy_pages(
                        self._caches[r], jnp.int32(src), jnp.int32(dst))
                    self._share_pool(r)

    def _note_decode_util(self):
        """Per-replica occupied/capacity slot-step accounting at decode
        dispatch (always on — integer adds).  Mono counts as replica 0
        over all slots."""
        if self.plan is None:
            self._replica_busy[0] = self._replica_busy.get(0, 0) + self.active
            self._replica_cap[0] = self._replica_cap.get(0, 0) + self.slots
            return
        for r in range(self.plan.n_replicas):
            a, b = self.plan.replica_range(r)
            act_r = self._active_in(a, b)
            self._replica_busy[r] = self._replica_busy.get(r, 0) + act_r
            self._replica_cap[r] = self._replica_cap.get(r, 0) + (b - a)

    def _decode_once(self):
        """One batched decode step at per-slot positions.  Idle slots ride
        along at fixed shape (their rows are garbage until the admission
        scatter replaces the whole slot; paged idle slots carry unmapped
        block tables, so their page writes drop).  Plan mode decodes each
        spatial replica independently (its slot partition, its stage
        walk).

        With speculation on, a tick whose drafter finds something runs a
        batched VERIFY step instead: every slot scores a (K+1)-token
        window (current token + drafts) in one step, commits the
        accepted prefix, and rolls the rejected tail back."""
        ph = self._phases
        with ph.span("engine.decode.prep") as prep:
            act = self.active             # sampled at dispatch (see init)
            self._note_decode_util()
            drafts = self._draft_all() if self._spec_k else None
            window = None
            if drafts is not None:
                window = np.zeros((self.slots, self._spec_k + 1), np.int32)
                window[:, 0] = self._cur[:, 0]
                for slot, d in drafts.items():
                    if d:
                        window[slot, 1:1 + len(d)] = d
            pending = self._dispatch_steps(window)
        arrs = []
        for out, a, b, r in pending:
            # an attached Tracer records each replica's step from its
            # dispatch through its readback, as "decode" or "verify"
            if window is None:
                wait, args = "engine.decode.wait", lambda: {
                    "active": self._active_in(a, b), "slots": b - a,
                    "plan": self._label}
            else:
                wait, args = "engine.verify", lambda: {
                    "window": window.shape[1], "plan": self._label,
                    "drafted": sum(len(d) for s, d in drafts.items()
                                   if a <= s < b)}
            with ph.span(wait, None, ("replica", r), args, prep.t0):
                arrs.append((self._sync(out), a, b))
        with ph.span("engine.decode.collect"):
            now = time.perf_counter()
            for arr, a, b in arrs:
                if window is None:
                    self._collect_decoded(arr, a, b, now)
                else:
                    self._collect_verified(window, arr, drafts, a, b, now)
            if window is not None:
                self.spec_steps += 1
            self.decode_steps += 1
            self._decode_slot_steps += act
            self._occupied_step_sum += self.active

    def _busy_replicas(self):
        """(replica, first slot, end) of every decode replica holding an
        active slot; monolithic decode is replica 0 over all slots."""
        if self.plan is None:
            return [(0, 0, self.slots)]
        out = []
        for r in range(self.plan.n_replicas):
            a, b = self.plan.replica_range(r)
            if self._active_in(a, b):
                out.append((r, a, b))
        return out

    def _dispatch_steps(self, window=None):
        """Dispatch one decode step — one verify step of the drafted
        ``window`` when given — on every busy replica, reading nothing
        back; returns ``[(output, first, end, replica)]``.  Every
        replica's step is dispatched before any result is synced: the
        replicas are independent, so their device computations may
        overlap.  (Paged replicas chain through the shared pool: each
        step consumes the pool leaves the previous one produced — the
        rebinding in _share_pool, serialized by data dependency.)"""
        out = []
        for r, a, b in self._busy_replicas():
            bt = None
            if self._pager is not None:
                if window is None:
                    self._prepare_paged_writes(a, b)
                else:
                    self._prepare_verify_writes(a, b, window.shape[1])
                bt = jnp.asarray(self._pager.table_matrix()[a:b])
            inp = jnp.asarray(self._cur[a:b] if window is None
                              else window[a:b])
            pos = jnp.asarray(self._pos[a:b])
            if self.plan is None:
                if window is None:
                    nxt, _, self._cache = self.serve_step(
                        self.params, self._cache, inp, pos, None, bt)
                else:
                    nxt, self._cache = self._verify_step(
                        self.params, self._cache, inp, pos, bt)
            else:
                step = (self._rt.decode_step if window is None
                        else self._rt.verify_step)
                nxt, self._caches[r] = step(self.params, self._caches[r],
                                            inp, pos, bt)
                self._share_pool(r)
            out.append((nxt, a, b, r))
        return out

    # ---- overlapped (async) decode ---------------------------------------
    def _dispatch_decode(self):
        """Overlap mode: dispatch one batched decode step WITHOUT reading
        its result back.  Inputs come from ``_cur_dev`` — the previous
        step's output array, still on device — so the host never blocks
        between steps; positions and page bookkeeping advance at
        dispatch.

        A slot the pending drain is about to retire (EOS/budget known
        only once step N is read back) rides along one extra step.  That
        garbage write is safe: ``prepare_decode`` made its target block
        exclusively owned, a retired slot's blocks free only AFTER this
        dispatch (so nothing else maps them yet), and every later cache
        op is serialized behind this step by the cache's data dependency
        — any re-used page is re-written by its new owner's prefill or
        masked until its new owner's own frontier reaches it.  The
        record entry is skipped as stale at drain.

        Host arrays go up as private copies (``_upload``): a host-to-
        device transfer may still be reading its source when this method
        returns, and ``_pos``/``_cur`` are mutated right after dispatch."""
        act = self.active
        self._note_decode_util()
        if self._cur_dev is None:
            self._cur_dev = _upload(self._cur)
        arrs = []
        for r, a, b in self._busy_replicas():
            with self._phases.span(
                    "engine.decode.dispatch", None, ("replica", r),
                    args=lambda: {"active": self._active_in(a, b),
                                  "slots": b - a, "plan": self._label}):
                bt = None
                if self._pager is not None:
                    self._prepare_paged_writes(a, b)
                    bt = jnp.asarray(self._pager.table_matrix()[a:b])
                if self.plan is None:
                    nxt, _, self._cache = self.serve_step(
                        self.params, self._cache, self._cur_dev,
                        _upload(self._pos), None, bt)
                    self._cur_dev = nxt
                else:
                    nxt, self._caches[r] = self._rt.decode_step(
                        self.params, self._caches[r], self._cur_dev[a:b],
                        _upload(self._pos[a:b]), bt)
                    self._share_pool(r)
                    self._cur_dev = self._cur_dev.at[a:b].set(nxt)
            arrs.append((nxt, a, b))
        entries = []
        in_toks: Dict[int, Optional[int]] = {}
        for _, a, b in arrs:
            for slot in range(a, b):
                req = self._slot_req[slot]
                if req is None:
                    continue
                entries.append((slot, req, int(self._pos[slot])))
                # the step's input token: host-known right after
                # activation, else it is the undrained previous step's
                # output — filled at that step's drain
                in_toks[slot] = (int(self._cur[slot, 0])
                                 if self._cur_known[slot] else None)
                self._cur_known[slot] = False
                self._pos[slot] += 1
        self._inflight.append(_Inflight(arrs=arrs, entries=entries,
                                        in_toks=in_toks, act=act))
        self.decode_steps += 1
        self._decode_slot_steps += act

    def _drain_one(self, span: str = "engine.drain"):
        """Read back the OLDEST in-flight step and do everything the sync
        path does after a step: extend block chains with the step's input
        tokens, append the output tokens, retire EOS/budget slots (one
        tick later than sync mode — the per-request token STREAMS are
        identical), and hand each drained token to the next in-flight
        record, whose input it is.  ``span`` names the phase."""
        rec = self._inflight.pop(0)
        with self._phases.span(span, args=lambda: {
                "slots_drained": len(rec.entries),
                "inflight": len(self._inflight)}):
            arrs = [(self._sync(nxt), a, b) for nxt, a, b in rec.arrs]
            now = time.perf_counter()
            nxt_rec = self._inflight[0] if self._inflight else None
            nxt_req = ({s: r for s, r, _ in nxt_rec.entries}
                       if nxt_rec is not None else {})
            for arr, a, b in arrs:
                for slot, req, pos_snap in rec.entries:
                    if not (a <= slot < b):
                        continue
                    if self._slot_req[slot] is not req:
                        continue      # slot retired mid-flight: garbage step
                    pager, local = self._pager_of(slot)
                    if pager is not None:
                        tok_in = rec.in_toks[slot]
                        assert tok_in is not None, slot
                        pager.note_written(local, tok_in, pos_snap)
                    tok = int(arr[slot - a, 0])
                    req.out_tokens.append(tok)
                    self._cur[slot, 0] = tok
                    self._cur_known[slot] = True
                    if nxt_req.get(slot) is req:
                        nxt_rec.in_toks[slot] = tok
                    self.decode_tokens += 1
                    self._maybe_retire(slot, now, pos=pos_snap + 1)
            self._occupied_step_sum += self.active

    # ---- speculative decode ----------------------------------------------
    def _draft_all(self):
        """Prompt-lookup drafts for every active slot, or None when this
        tick should run plain decode instead (no slot drafted anything,
        or some active slot's window would run past the slot cache).  A
        slot's draft is clamped so accepted tokens can never overshoot
        its remaining budget."""
        K = self._spec_k
        drafts: Dict[int, List[int]] = {}
        any_draft = False
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is None:
                continue
            if int(self._pos[slot]) + K > self.max_seq - 2:
                # the window would write past the last position the gold
                # decode ever writes (max_seq - 2): plain-decode this tick
                return None
            # accepting all K drafts emits K+1 tokens: cap at the budget
            budget = min(K, req.max_new_tokens - len(req.out_tokens) - 1)
            ctx = np.concatenate([req.prompt,
                                  np.asarray(req.out_tokens, np.int32)])
            d = ngram_draft(ctx, budget)
            drafts[slot] = d
            any_draft = any_draft or bool(d)
        return drafts if any_draft else None

    def _prepare_verify_writes(self, first: int, last: int, sw: int):
        """Before a verify step: make every window position's block
        writable for every active slot (boundary blocks allocate, a
        shared or registered block copy-on-writes — only possible at the
        first window position; the rest of the window grows fresh
        blocks)."""
        for slot in range(first, last):
            if self._slot_req[slot] is None:
                continue
            pos = int(self._pos[slot])
            for j in range(sw):
                cow = self._pager.prepare_decode(slot, pos + j)
                if cow is not None:
                    src, dst = cow
                    if self.plan is None:
                        self._cache = self._copy_pages(
                            self._cache, jnp.int32(src), jnp.int32(dst))
                    else:
                        r, _ = self.plan.replica_of_slot(slot)
                        self._caches[r] = self._copy_pages(
                            self._caches[r], jnp.int32(src), jnp.int32(dst))
                        self._share_pool(r)

    def _collect_verified(self, window, outs, drafts, a: int, b: int,
                          now: float):
        """Greedy acceptance per slot: position j's argmax is what a
        sequential decode would emit after consuming window[0..j], so the
        emitted prefix extends exactly while each argmax equals the next
        drafted token (and is not EOS) — accepted streams are therefore
        bit-identical to the one-shot greedy stream."""
        for slot in range(a, b):
            req = self._slot_req[slot]
            if req is None:
                continue
            d = drafts.get(slot, [])
            pos = int(self._pos[slot])
            row = outs[slot - a]
            emitted: List[int] = []
            j = 0
            while True:
                tok = int(row[j])
                emitted.append(tok)
                if req.eos_token is not None and tok == req.eos_token:
                    break
                if j < len(d) and int(window[slot, j + 1]) == tok:
                    j += 1
                else:
                    break
            m = len(emitted)
            pager, local = self._pager_of(slot)
            if pager is not None:
                # the step wrote the window's K/V at pos..pos+K: keep the
                # accepted inputs' chain, then roll the rejected tail back
                for i in range(m):
                    pager.note_written(local, int(window[slot, i]), pos + i)
                pager.rollback(local, pos + m)
            self._pos[slot] = pos + m    # dense engines just rewind here
            req.out_tokens.extend(emitted)
            self._cur[slot, 0] = emitted[-1]
            self.decode_tokens += m
            self.spec_proposed += len(d)
            self.spec_accepted += m - 1
            self._maybe_retire(slot, now)

    def _collect_decoded(self, arr, a: int, b: int, now: float):
        for slot in range(a, b):
            req = self._slot_req[slot]
            if req is None:
                continue
            pager, local = self._pager_of(slot)
            if pager is not None:
                # the step wrote this slot's INPUT token's K/V at _pos:
                # extend the block chain (registers blocks as they fill)
                pager.note_written(local, int(self._cur[slot, 0]),
                                   int(self._pos[slot]))
            self._pos[slot] += 1
            tok = int(arr[slot - a, 0])
            req.out_tokens.append(tok)
            self._cur[slot, 0] = tok
            self.decode_tokens += 1
            self._maybe_retire(slot, now)

    def _maybe_retire(self, slot: int, now: float,
                      pos: Optional[int] = None):
        """Slot-level retirement: EOS, token budget, or a full slot cache.
        Only this slot frees — every other slot keeps decoding.  Paged
        engines release the slot's blocks; fully-released registered
        blocks park in the pool's LRU for prefix reuse.

        ``pos``: the slot's written-token count as of the step being
        accounted — overlap drains pass it explicitly because
        ``self._pos`` has already advanced past it for the next,
        still-in-flight dispatch."""
        if pos is None:
            pos = int(self._pos[slot])
        req = self._slot_req[slot]
        if (len(req.out_tokens) >= req.max_new_tokens
                or (req.eos_token is not None
                    and req.out_tokens[-1] == req.eos_token)
                or pos >= self.max_seq - 1):
            req.t_done = now
            self.done.append(req)
            self._slot_req[slot] = None
            pager, local = self._pager_of(slot)
            if pager is not None:
                pager.release_slot(local)
            # live request metrics (always on): explicit-bucket TTFT/TPOT
            # histograms + retirement counters
            self._h_ttft.observe(req.t_first - req.t_submit)
            n = max(len(req.out_tokens) - 1, 1)
            self._h_tpot.observe((req.t_done - req.t_first) / n)
            self._c_requests.inc()
            self._c_gen.inc(len(req.out_tokens))
            if self._tr is not None:
                self._tr.span("requests", "retire", now, now, args={
                    "uid": req.uid, "slot": slot,
                    "tokens": len(req.out_tokens),
                    "latency_s": req.t_done - req.t_submit},
                    flow_in=req.uid)
