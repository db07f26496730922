"""Serving launcher: batched inference through the per-slot
continuous-batching engine (the paper's application kind).

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --requests 8

Requests are admitted into slots of a persistent slot-indexed cache
(admission cost O(prompt), never O(active batch)); the printed stats are
the serving-side half of the SSR latency-throughput story.

Plan-driven serving (``--strategy pipeline:S`` / ``--strategy hybrid:N``)
runs the engine on a lowered ``ServingPlan``: chunked prefill streams
through the plan's stage slices (``--chunk``) interleaved with decode,
and the plan's spatial width (``--replicas``) becomes independent
slot-partitioned decode replicas:

    python -m repro.launch.serve --arch yi-6b --strategy pipeline:2 \
        --replicas 2 --chunk 8
    python -m repro.launch.serve --arch yi-6b --strategy hybrid:2

``--paged [--page-size N --num-blocks M]`` swaps the dense slot caches
for the pool-backed paged layout (block tables + content-hash prefix
sharing, ``repro.cache``); the stats line then reports the block-pool
picture (peak blocks, reuse-hit rate, copy-on-writes, effective-slots
gain).  Composes with plan-driven serving (each replica owns a pool
partition).

``--prefix-cache`` / ``--no-prefix-cache`` (paged only; default on)
toggles cross-request prefix compute reuse: warm prefixes are looked up
in the registered block cache on admission and only the unmatched
suffix is prefilled; the stats line adds the prefix-hit picture
(prefill hit rate, reused tokens, registry block hits).

``--speculate K`` (``--no-speculate`` to force off) turns on speculative
decode: a prompt-lookup n-gram drafter proposes up to K tokens per slot
and one batched verify step scores them all; greedy verification keeps
every stream bit-identical to plain decode, so the stats line's
tokens-per-step and acceptance rate are pure latency wins.  Families
whose verify step is not decomposable (SSM mixers, local-window rings,
MoE) gate speculation off automatically.

``--adapt`` / ``--no-adapt`` (default off) turns on online Pareto
navigation: a re-plan controller watches the rolling traffic window and
swaps the engine between the monolithic point, the requested strategy's
plan, and its re-replicated variants at runtime — without dropping
requests, zero-copy on the paged path (slot migration is a block-table
handoff).  ``--slo-ttft S`` / ``--slo-tpot S`` set the controller's SLO
targets in seconds (both require ``--adapt`` and must be positive); the
stats line then adds the swap count and the final design point.

``--trace out.json`` records per-request lifecycle spans and per-stage /
per-replica engine timeline spans (``repro.obs``) and writes a
Chrome/Perfetto ``trace_event`` JSON to open in ui.perfetto.dev;
``--metrics-out out.prom`` writes Prometheus text-format metrics (TTFT /
TPOT histograms, utilization gauges) after the run.  Both are strictly
zero-overhead when not passed (see docs/observability.md).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.backend import compat
from repro.configs import REGISTRY, ShapeConfig, reduced
from repro.models import build_model
from repro.serving import Request, ServingEngine


def _parse_strategy(strategy: str):
    """'pipeline:S' / 'hybrid:N' -> (kind, n); clear usage error otherwise."""
    kind, _, n = strategy.partition(":")
    if kind in ("pipeline", "hybrid") and n.isdigit() and int(n) >= 1:
        return kind, int(n)
    raise SystemExit(f"bad --strategy {strategy!r} "
                     f"(mono | pipeline:S | hybrid:N)")


def _build_serving_plan(cfg, strategy: str, slots: int, replicas: int,
                        chunk: int, max_seq: int):
    """Lower the requested strategy to a ServingPlan (None = monolithic)."""
    from repro.plan import lower, lower_serving, uniform_plan

    if strategy in ("mono", "sequential"):
        return None
    reps = replicas or min(2, slots)
    kind, n = _parse_strategy(strategy)
    if kind == "pipeline":
        plan = uniform_plan(cfg.num_groups, n, n_microbatches=reps)
    else:
        from repro.core import build_graph, evolutionary_search, ssr_dse
        from repro.core.assignment import contiguous_assignment
        n_acc = n
        g = build_graph(cfg, ShapeConfig("serve", max_seq, 8, "prefill"))
        res = evolutionary_search(g, 8, n_acc=n_acc, n_batches=2, n_pop=6,
                                  n_child=6, n_iter=3, seed=0)
        plan = lower(res.assignment, g, mesh_devices=8, n_microbatches=reps)
        if plan.n_stages < n_acc:
            # the EA legitimately collapses uniform stacks onto sequential;
            # serve the N-stage cut through the same customization pass
            _, _, assign = ssr_dse(
                g, contiguous_assignment(g, n_acc, 8).acc_of, 8,
                n_batches=n_acc)
            plan = lower(assign, g, mesh_devices=8, n_microbatches=reps)
    return lower_serving(plan, slots=slots, chunk=chunk)


def _adaptive_ladder(cfg, splan, slots: int, chunk: int):
    """Candidate design points for the re-plan controller: mono, the
    requested plan (or a default 2-stage cut when serving started mono),
    and its re-replicated spatial-width variants — one searched stage
    cut, several Pareto points."""
    from repro.plan import lower_serving, rereplicate_serving, uniform_plan
    if splan is None:
        n_stages = 2 if cfg.num_groups % 2 == 0 else 1
        base = lower_serving(
            uniform_plan(cfg.num_groups, n_stages,
                         n_microbatches=min(2, slots)),
            slots=slots, chunk=chunk)
    else:
        base = splan
    cands = [None, base]
    for r in sorted({1, min(2, slots), slots}):
        cand = rereplicate_serving(base, r)
        if all(cand != c for c in cands):
            cands.append(cand)
    return cands


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the smoke-size config (configs.reduced); "
                         "--no-reduced serves the published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--eos", type=int, default=-1,
                    help="retire a slot on this token id (-1: disabled)")
    ap.add_argument("--strategy", default="mono",
                    help="mono | pipeline:S | hybrid:N (plan-driven)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="spatial decode replicas for plan-driven serving "
                         "(0: min(2, slots))")
    ap.add_argument("--chunk", type=int, default=8,
                    help="prefill chunk length for plan-driven serving")
    ap.add_argument("--paged", action="store_true",
                    help="pool-backed slot caches: paged global-attention "
                         "KV with prefix sharing (repro.cache)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV block with --paged")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="block-pool size with --paged "
                         "(0: slots * max_seq / page_size)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="with --paged: share registered prefix blocks "
                         "across requests and prefill only the suffix on a "
                         "warm prefix (default: on)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decode: draft up to K tokens per slot "
                         "(prompt-lookup n-grams) and verify them in one "
                         "batched step; greedy verify keeps streams "
                         "bit-identical (0: disabled)")
    ap.add_argument("--no-speculate", action="store_const", const=0,
                    dest="speculate", help="force speculation off")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="async overlapped runtime: dispatch decode step "
                         "N+1 before draining step N's tokens (bit-exact; "
                         "falls back to sync with effective --speculate)")
    ap.add_argument("--kv-dtype", default="fp", choices=("fp", "int8"),
                    help="with --paged: K/V block-pool storage dtype; int8 "
                         "adds per-row scales for >= 1.9x effective "
                         "capacity (bounded-error token streams)")
    ap.add_argument("--adapt", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="online Pareto navigation: re-plan the engine "
                         "between mono / the requested plan / its "
                         "re-replicated variants as traffic shifts "
                         "(zero-copy slot migration on --paged)")
    ap.add_argument("--slo-ttft", type=float, default=0.0, metavar="S",
                    help="with --adapt: target time-to-first-token in "
                         "seconds the controller penalizes against "
                         "(0: no TTFT SLO)")
    ap.add_argument("--slo-tpot", type=float, default=0.0, metavar="S",
                    help="with --adapt: target time-per-output-token in "
                         "seconds the controller penalizes against "
                         "(0: no TPOT SLO)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record engine + request lifecycle spans and "
                         "write a Chrome/Perfetto trace_event JSON here "
                         "(open in ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="OUT.prom",
                    help="write Prometheus text-format metrics (TTFT/TPOT "
                         "histograms, utilization gauges) here after the "
                         "run")
    args = ap.parse_args(argv)

    if args.kv_dtype != "fp" and not args.paged:
        raise SystemExit("--kv-dtype int8 requires --paged: quantized K/V "
                         "blocks live in the paged block pool")

    if (args.slo_ttft or args.slo_tpot) and not args.adapt:
        raise SystemExit("--slo-ttft/--slo-tpot set SLO targets for the "
                         "adaptive re-plan controller: pass --adapt (a "
                         "static engine has no controller to penalize)")
    if args.slo_ttft < 0 or args.slo_tpot < 0:
        raise SystemExit("--slo-ttft/--slo-tpot are seconds and must be "
                         ">= 0 (0 disables that SLO term)")

    if args.prefix_cache and not args.paged:
        raise SystemExit("--prefix-cache requires --paged: prefix blocks "
                         "live in the paged block pool (dense slot caches "
                         "have no shareable blocks)")
    prefix_cache = True if args.prefix_cache is None else args.prefix_cache

    compat.use_compile_cache()
    cfg = REGISTRY[args.arch]
    if args.reduced:
        cfg = reduced(cfg)
    splan = _build_serving_plan(cfg, args.strategy, args.slots,
                                args.replicas, args.chunk, args.max_seq)
    if splan is not None:
        print(splan.describe())
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    adapt = None
    if args.adapt:
        from repro.serving import AdaptiveConfig
        adapt = AdaptiveConfig(
            plans=_adaptive_ladder(cfg, splan, args.slots, args.chunk),
            slo_ttft_s=args.slo_ttft, slo_tpot_s=args.slo_tpot)
    trace_cfg = None
    if args.trace:
        from repro.obs import TraceConfig
        trace_cfg = TraceConfig(path=args.trace)
    eng = ServingEngine(model, params, slots=args.slots,
                        max_seq=args.max_seq, plan=splan, paged=args.paged,
                        page_size=args.page_size,
                        num_blocks=args.num_blocks,
                        prefix_cache=prefix_cache,
                        speculate=args.speculate,
                        overlap=args.overlap, kv_dtype=args.kv_dtype,
                        adapt=adapt, trace=trace_cfg)
    if args.adapt:
        eng.warm_replans()                # compile candidates off the clock
        eng.reset_stats()
    eos = None if args.eos < 0 else args.eos
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for uid in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)
        eng.submit(Request(uid, prompt, args.new_tokens, eos_token=eos))
    done = eng.run()
    wall = time.perf_counter() - t0
    st = eng.stats()
    extra = ""
    if "plan_stages" in st:    # absent when --adapt ended on the mono point
        extra = (f", {st['plan_stages']} stages x "
                 f"{st['decode_replicas']} replicas (chunk "
                 f"{st['prefill_chunk']})")
    c = st["cache"]
    if c["layout"] == "paged":
        extra += (f", paged p{c['page_size']}: "
                  f"peak {c['peak_blocks_in_use']}/{c['num_blocks']} blocks"
                  f", reuse={c['reuse_hit_rate']:.2f}"
                  f", cow={c['cow_copies']}"
                  f", eff_slots_gain={c['effective_slots_gain']:.1f}x")
        if c["prefix_cache"]:
            extra += (f", prefix: hit_rate={c['prefill_hit_rate']:.2f}"
                      f" reused_tok={c['reused_prefill_tokens']}"
                      f" blocks_hit={c['prefix_hits']}")
        else:
            extra += ", prefix: off"
    if c["layout"] == "paged" and c.get("kv_dtype", "fp") != "fp":
        extra += (f", kv={c['kv_dtype']}"
                  f" capacity_x={c['kv_capacity_x']:.1f}")
    if args.overlap:
        extra += ", overlap=" + ("on" if eng._overlap else "sync(spec)")
    if st["spec_steps"]:
        extra += (f", spec k={args.speculate}: "
                  f"tok_per_step={st['tokens_per_step']:.2f}"
                  f" accept={st['acceptance_rate']:.2f}")
    elif args.speculate:
        extra += ", spec: gated off (family not verify-decomposable)"
    if args.adapt:
        extra += (f", adapt: replans={st['replans']}"
                  f" migrations={st['migrations']}"
                  f" (copies={st['migration_copies']})"
                  f" final={st['plan_label']}")
    kernels = ",".join(f"{k}={v}" for k, v in st["kernels"].items())
    print(f"[serve] {len(done)} requests, {st['gen_tokens']} tokens, "
          f"{st['gen_tokens']/wall:.1f} tok/s, "
          f"occupancy={st['slot_occupancy']:.2f}, "
          f"kernels={kernels or st['kernel_path']}{extra}")
    if args.trace:
        eng.write_trace(args.trace)
        print(f"[serve] trace: {args.trace} ({eng._tr.events} events"
              f", {eng._tr.dropped} dropped)")
    if args.metrics_out:
        from repro.obs import write_metrics
        write_metrics(eng.export_metrics(), args.metrics_out)
        print(f"[serve] metrics: {args.metrics_out}")


if __name__ == "__main__":
    main()
