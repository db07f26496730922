"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload yi6b-chat --seed 7 --seconds 51 --trace 0

Everything the run needs is found by name from ``BENCHMARK.json`` (see
``bench/spec.py``).  The run builds the configuration's weights on the
device from ``--seed``, warms the engine on every shape the cell's
traffic can produce (set-up, reported as ``setup_s``), drives the traffic
through an uncounted ramp and then a window of ``--seconds``, and checks
what the window served against the plain float32 reference.  With
``--trace 1`` the window is profiled (its first ``trace_s`` seconds, from
the cell's file) and the per-layer metrics are reported instead of the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit.  With no TPU, or fewer chips than the cell asks for, the run
exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
OUT = os.path.join(ROOT, ".bench_out")


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _setup_jax():
    """The compile cache lives in the checkout, at a fixed path, whatever
    the environment says; every program is cached, however quick."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size limit, so no LRU bookkeeping: a limit set by the environment
    # makes every write fail on entries that came without access times
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


class CompileCounter:
    """Counts lowerings and backend compiles from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def snapshot(self):
        return self.lowered, self.compiled


class Run:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def sample_rows(drv, seed: int, min_tokens: int):
    """The finished requests the reference checks: the longest; then, in
    an order drawn from the seed, one for each slot that finished a
    request and is not yet covered; then others in that order until
    ``min_tokens`` served tokens.  Every slot that served a finished
    request is checked, so a fault confined to some slots cannot pass."""
    import numpy as np
    done = [j for j, r in enumerate(drv.records) if r.done]
    if not done:
        return []
    req, rec = drv.requests, drv.records
    size = {j: len(req[j].prompt) + len(req[j].out_tokens) for j in done}
    first = max(done, key=lambda j: (size[j], -j))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    order = [int(j) for j in rng.permutation(done) if j != first]
    pick, slots = [first], {rec[first].slot}
    for j in order:
        if rec[j].slot not in slots:
            pick.append(j)
            slots.add(rec[j].slot)
    n = sum(len(req[j].out_tokens) for j in pick)
    for j in order:
        if n >= min_tokens:
            break
        if j not in pick:
            pick.append(j)
            n += len(req[j].out_tokens)
    return [(np.asarray(req[j].prompt, np.int32),
             np.asarray(req[j].out_tokens, np.int32)) for j in pick]


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             fault=None, keep_trace: str = "", control: bool = False):
    """One run of ``cell``; returns the result dict.  ``fault`` (tests
    only) is called with the engine before the traffic starts.
    ``control`` (bench/control.py) also reads the fp8 control's widest
    gap over the same served sequences."""
    jax = _setup_jax()
    from bench import driver as D
    from bench import peaks as P
    from bench import reference as R
    from bench import spec as S
    from bench import trace as TRC
    from bench import traffic as TR
    from bench import window as WIN
    from repro.backend import compat
    from repro.models import build_model
    counter = CompileCounter()
    conf, mix, st = cell.config, cell.traffic, cell.settings
    dev = jax.devices()[0]
    log(f"{cell.name}: {conf['name']} on {len(jax.devices())} x "
        f"{dev.device_kind}; seed {seed}; window {seconds}s; trace {trace}; "
        f"compile cache {compat.use_compile_cache()}")

    arch = S.architecture(conf)
    model = build_model(arch.program_config())
    params = arch.program_params(seed, model)
    jax.block_until_ready(params)
    eng = D.make_engine(model, params, st)
    cows = D.warm(eng, mix, st, conf["vocab_size"], seed)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f}s: weights, engine, {len(D.prefill_shapes(mix, st))} "
        f"prefill shapes and the decode step warmed ({cows} copy-on-write); "
        f"compiles so far (lowered, compiled) {counter.snapshot()}")
    if fault is not None:
        fault(eng)

    window_s = min(seconds, st.get("trace_s", seconds)) if trace else seconds
    ramp = float(mix["ramp_s"])
    items = TR.generate(mix, st.get("rate", 0.0), ramp, window_s, seed,
                        conf["vocab_size"])
    tdir = os.path.join(OUT, "trace-" + cell.name)

    def on_window():
        eng.reset_stats()
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
            jax.profiler.start_trace(tdir)

    before = counter.snapshot()
    drv = D.drive(eng, items, ramp_s=ramp, seconds=window_s,
                  on_window=on_window, annotate=trace)
    stats = eng.stats()
    inside = tuple(b - a for a, b in zip(before, counter.snapshot()))
    ev = None
    if trace:
        jax.profiler.stop_trace()
        ev = TRC.record(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        if keep_trace:
            import gzip
            with gzip.open(keep_trace, "wt") as f:
                json.dump(ev, f)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    win = WIN.summary(drv.records, drv.w0, drv.w1)
    log(f"compiles inside the window (lowered, compiled): {inside}")
    log("window: " + ", ".join(f"{k}={v}" for k, v in win.items()))
    log(f"engine: ticks {stats['ticks']}, phases {stats['phase_time_s']}, "
        f"kernels {stats['kernels']}")
    c = stats["cache"]
    log(f"cache: reused {c['reused_prefill_tokens']} of "
        f"{c['reused_prefill_tokens'] + c['suffix_prefill_tokens']} prompt "
        f"tokens, peak blocks {c['peak_blocks_in_use']}/{c['num_blocks']}, "
        f"evictions {c['evictions']}, cow {c['cow_copies']}")

    chk = st["check"]
    rows = sample_rows(drv, seed, chk["min_tokens"])
    # the program's state goes before the reference runs
    del eng, params
    gc.collect()
    t_ref = time.perf_counter()
    gap = R.gaps(arch, seed, rows, control=control) if rows else None
    log(f"reference: {time.perf_counter() - t_ref:.3f}s over "
        f"{len(rows)} finished requests from "
        f"{len({r.slot for r in drv.records if r.done})} slots"
        + (f", {gap['tokens']} served tokens" if gap else ""))

    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else win.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        run = Run(cell=cell, window=win, stats=stats, trace=ev,
                  ticks=drv.ticks, arch=arch,
                  peak=P.peak(dev.device_kind))
        for m in cell.per_layer:
            v = S.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    limit = float(chk["gap_limit"])
    value = gap["widest_gap"] if gap else None
    correct = bool(gap is not None and value <= limit
                   and drv.submit_errors == 0)
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": drv.submit_errors, "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": mem}}
    if trace:
        busy = TRC.busy_ns(ev) * 1e-9
        w0, w1 = TRC.window(ev)
        out["device"].update(busy_s=busy, window_s=(w1 - w0) * 1e-9)
        out["breakdown"] = TRC.breakdown(ev)
    if control and gap:
        out["control_gap"] = gap["control_gap"]
        log(f"control (fp8) widest gap: {gap['control_gap']}")
    out["checks"] = {"widest_gap": {"value": value, "limit": limit}}
    log(f"check widest_gap: {value} (limit {limit}), over "
        f"{gap['tokens'] if gap else 0} served tokens; correct {correct}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="", metavar="PATH",
                    help="with --trace 1: also write the recorded trace "
                         "events (bench/trace.py's form) as gzipped JSON")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"no program under {SRC}: nothing to measure")
        return 2
    sys.path[:0] = [ROOT, SRC]
    from bench import spec as S
    cell = S.load_cell(args.workload)
    jax = _setup_jax()
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform} device(s)")
        return 1
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   keep_trace=args.keep_trace)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
