"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.

  table5      — perf per model x batch vs paper's on-board numbers
  table6      — throughput under latency constraints (seq/spatial/hybrid)
  table7      — analytical model vs paper measurements per #accs
  fig2        — latency-throughput Pareto front
  fig10       — EA vs exhaustive search efficiency
  ablation    — §5.2.6 step-by-step feature gains
  q1          — §6 cross-platform (Stratix 10 NX) modeling
  roofline    — §Roofline terms per (arch x shape) from the dry-run JSONs
  micro       — measured CPU microbenchmarks of the runnable substrate
  serving     — measured latency/throughput under Poisson arrivals per
                slot count (continuous-batching engine)
  plan        — EA-searched assignments lowered to ExecutionPlans and
                executed: measured points next to analytic ones

``--seed N`` threads a seed through every stochastic section (serving
Poisson trace, EA searches) so sweeps are reproducible run-to-run.

``--smoke`` instead runs the fast tier-1 test subset in < 60 s: the
suite minus the ``slow``-marked 8-device subprocess tests AND minus the
two compile-heavy sweep files (test_models.py, test_perf_paths.py) —
the full gate remains ``pytest -q``.  ``--smoke-json PATH`` additionally
writes a machine-readable summary (and the plan measured-vs-analytic
rows) so CI can archive the perf trajectory per commit.

``<section> --smoke`` (e.g. ``serving --smoke``) instead runs a smoke-
sized variant of that section — for ``serving``, the plan-driven strategy
sweep (sequential / spatial / small hybrid ServingPlan) plus a
paged-vs-dense cache-layout comparison (token parity asserted, block
savings reported) on CPU jax — so plan-serving throughput and the paged
block-pool figures land in the per-commit perf artifact too.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# self-sufficient invocation (`python benchmarks/run.py ...` from anywhere):
# the repo root (for `benchmarks.*`) and src (for `repro.*`) on sys.path.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_REPO, os.path.join(_REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def micro_rows(seed: int = 0):
    """Measured wall-time microbenchmarks (CPU, reduced configs): the
    runnable-path sanity numbers."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import timed
    from repro.configs import REGISTRY, ShapeConfig, reduced
    from repro.data import SyntheticLM
    from repro.models import build_model
    from repro.training import AdamW, make_train_step

    rows = []
    for arch in ("yi-6b", "qwen2-moe-a2.7b", "xlstm-125m"):
        cfg = reduced(REGISTRY[arch])
        model = build_model(cfg)
        params = model.init(jax.random.key(seed))
        data = SyntheticLM(cfg, ShapeConfig("b", 64, 4, "train"))
        batch = jax.tree.map(jnp.asarray, data.batch_at(0))
        opt = AdamW(warmup_steps=1, total_steps=100)
        step = jax.jit(make_train_step(model, opt, remat=False))
        st = opt.init(params)
        jax.block_until_ready(step(params, st, batch))  # warmup/compile
        (_, us) = timed(lambda: jax.block_until_ready(
            step(params, st, batch)), repeat=5)
        tok = 64 * 4
        rows.append((f"micro/train_step/{arch}", us,
                     f"tokens_per_s={tok/(us/1e6):.0f} (reduced cfg; CPU)"))
    return rows


def smoke(json_path: str = "", seed: int = 0) -> int:
    """Fast tier-1 subset (< 60 s): the suite minus the ``slow``-marked
    8-device subprocess tests and the two compile-heavy sweep files
    (test_models ~2 min of jit compiles, test_perf_paths ~30 s).  The full
    tier-1 gate stays ``pytest -q``.

    json_path: optional output file recording the run (returncode, wall
    seconds, plus the measured-vs-analytic plan rows) — uploaded as a CI
    artifact so the perf trajectory is tracked per commit."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "pytest", "-q", "-m", "not slow",
           "-p", "no:cacheprovider",
           "--ignore", os.path.join("tests", "test_models.py"),
           "--ignore", os.path.join("tests", "test_perf_paths.py"),
           "tests"]
    t0 = time.perf_counter()
    rc = subprocess.run(cmd, cwd=repo, env=env).returncode
    wall = time.perf_counter() - t0
    if json_path:
        summary = {"suite": "smoke", "returncode": rc,
                   "wall_s": round(wall, 2), "seed": seed}
        try:
            from repro.backend import compat
            summary["jax"] = ".".join(map(str, compat.jax_version()))
            from benchmarks.plan_bench import rows as plan_rows
            summary["plan_rows"] = [
                {"name": n, "us_per_call": round(us, 1), "derived": d}
                for n, us, d in plan_rows(seed=seed)]
        except Exception as e:  # keep the artifact even if the bench dies
            summary["plan_error"] = f"{type(e).__name__}: {e}"
        os.makedirs(os.path.dirname(os.path.abspath(json_path)),
                    exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"[smoke] wrote {json_path}")
    return rc


def smoke_sections(sections, json_path: str = "", seed: int = 0) -> int:
    """Smoke-sized section runs (``run.py <section> --smoke``): print the
    rows and optionally archive them as JSON (CI perf artifact).  The
    ``serving`` section additionally writes ``BENCH_serving.json`` (next
    to ``json_path``, else the cwd): the headline serving numbers —
    throughput, cold vs warm TTFT, prefix-hit rate, block savings — that
    CI archives and gates on (warm TTFT must beat cold).  It also emits
    the observability smoke artifacts ``trace_smoke.json`` (Perfetto) and
    ``metrics.prom`` (Prometheus text) from one extra traced replay of
    the adaptive trace — measured numbers stay tracing-off."""
    from benchmarks.serving import serving_bench_summary
    from benchmarks.serving import smoke_rows as serving_smoke

    known = {"serving": serving_smoke}
    unknown = [s for s in sections if s not in known]
    if unknown:
        print(f"no smoke variant for section(s) {unknown}; "
              f"choose from {list(known)}")
        return 2
    rc = 0
    summary = {"suite": "smoke-sections", "seed": seed, "sections": {}}
    print("name,us_per_call,derived")
    for key in sections:
        try:
            rows = known[key](seed=seed)
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}")
            summary["sections"][key] = [
                {"name": n, "us_per_call": round(us, 1), "derived": d}
                for n, us, d in rows]
        except Exception as e:      # pragma: no cover - keep harness alive
            print(f"{key}/ERROR,0,{type(e).__name__}: {e}")
            summary["sections"][key] = {"error": f"{type(e).__name__}: {e}"}
            rc = 1
    if "serving" in sections:
        out_dir = (os.path.dirname(os.path.abspath(json_path)) if json_path
                   else os.getcwd())
        bench_path = os.path.join(out_dir, "BENCH_serving.json")
        trace_path = os.path.join(out_dir, "trace_smoke.json")
        metrics_path = os.path.join(out_dir, "metrics.prom")
        try:
            os.makedirs(out_dir, exist_ok=True)
            bench = serving_bench_summary(seed=seed, trace_out=trace_path,
                                          metrics_out=metrics_path)
            with open(bench_path, "w") as f:
                json.dump(bench, f, indent=2)
            print(f"[smoke] wrote {bench_path}")
            print(f"[smoke] wrote {trace_path} and {metrics_path}")
        except Exception as e:      # pragma: no cover - keep harness alive
            print(f"serving/BENCH_ERROR,0,{type(e).__name__}: {e}")
            rc = 1
    if json_path:
        os.makedirs(os.path.dirname(os.path.abspath(json_path)),
                    exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"[smoke] wrote {json_path}")
    return rc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("sections", nargs="*", help="sections to run (default all)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for stochastic sections (serving, EA, plan)")
    ap.add_argument("--smoke", action="store_true",
                    help="run the fast tier-1 test subset instead")
    ap.add_argument("--smoke-json", default="",
                    help="with --smoke: write a JSON summary here")
    args = ap.parse_args()

    if args.smoke:
        if args.sections:
            sys.exit(smoke_sections(args.sections,
                                    json_path=args.smoke_json,
                                    seed=args.seed))
        sys.exit(smoke(json_path=args.smoke_json, seed=args.seed))

    from benchmarks import paper_tables as P
    from benchmarks.plan_bench import rows as plan_rows
    from benchmarks.roofline import roofline_rows
    from benchmarks.serving import rows as serving_rows
    from benchmarks.tpu_tradeoff import rows as tpu_rows

    # insertion order is the run order; the bool marks seed-taking sections
    sections = {
        "table5": (P.table5, False),
        "table6": (P.table6, True),
        "table7": (P.table7, False),
        "fig2": (P.fig2, True),
        "fig10": (P.fig10, True),
        "ablation": (P.step_by_step, False),
        "q1": (P.q1_cross_platform, False),
        "tpu_tradeoff": (tpu_rows, False),
        "roofline": (roofline_rows, False),
        "micro": (micro_rows, True),
        "serving": (serving_rows, True),
        "plan": (plan_rows, True),
    }
    unknown = [k for k in args.sections if k not in sections]
    if unknown:
        sys.exit(f"unknown section(s) {unknown}; "
                 f"choose from {list(sections)}")
    only = args.sections or list(sections)
    print("name,us_per_call,derived")
    failed = []
    for key in only:
        fn, takes_seed = sections[key]
        try:
            rows = fn(seed=args.seed) if takes_seed else fn()
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # run the other sections, then fail
            failed.append(key)
            print(f"{key}/ERROR,0,{type(e).__name__}: {e}")
    if failed:
        sys.exit(f"section(s) raised: {failed}")


if __name__ == "__main__":
    main()
