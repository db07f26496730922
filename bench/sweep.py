"""Find a fixed-rate cell's knee once, on the chip, and write the cell's
rate as a number: 0.8 x the highest rate the engine sustains.

    python bench/sweep.py --workload yi6b-chat --rates 0.8,1.2,1.6,2.0 \\
        --seconds 30 --seed 5 --write

One process, one engine, warmed once.  Each rate drives the cell's
traffic through its ramp and a window of ``--seconds``, then the engine
is drained.  A rate is sustained when its backlog does not grow (the
requests due but still waiting for a first token at the window's end
exceed those waiting a third of the way in by at most two) and the 90th
percentile of time to first token stays within the mix's
``ttft_p90_limit_s``.  Prints one JSON
line per rate and a last line with the knee; ``--write`` puts the rate
into ``bench/cells/<workload>.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

from bench import run as RUN  # noqa: E402
from bench import spec as S  # noqa: E402

SHARE = 0.8


def waiting(records, t: float) -> int:
    return sum(1 for r in records if r.due <= t
               and not (r.stamps and r.stamps[0] <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    cell = S.load_cell(args.workload)
    jax = RUN._setup_jax()
    if jax.devices()[0].platform != "tpu":
        RUN.log("the sweep runs on the chip only")
        return 1
    from bench import driver as D
    from bench import traffic as TR
    from bench import window as WIN
    from repro.models import build_model
    conf, mix, st = cell.config, cell.traffic, cell.settings
    arch = S.architecture(conf)
    model = build_model(arch.program_config())
    params = arch.program_params(args.seed, model)
    eng = D.make_engine(model, params, st)
    D.warm(eng, mix, st, conf["vocab_size"], args.seed)
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        items = TR.generate(mix, rate, mix["ramp_s"], args.seconds,
                            args.seed, conf["vocab_size"])
        drv = D.drive(eng, items, ramp_s=mix["ramp_s"], seconds=args.seconds,
                      on_window=eng.reset_stats)
        win = WIN.summary(drv.records, drv.w0, drv.w1)
        early = waiting(drv.records, drv.w0 + (drv.w1 - drv.w0) / 3)
        late = waiting(drv.records, drv.w1)
        sustained = (late <= early + 2 and win.get("ttft_p90_s", 0.0)
                     <= mix.get("ttft_p90_limit_s", float("inf")))
        print(json.dumps({"rate": rate, "sustained": sustained,
                          "waiting_third": early, "waiting_end": late,
                          **win}), flush=True)
        if sustained:
            knee = rate
        eng.queue.clear()
        eng.run()
    fixed = round(SHARE * knee, 3) if knee else None
    print(json.dumps({"workload": cell.name, "knee": knee, "rate": fixed}))
    if args.write and fixed:
        path = os.path.join(S.BENCH_DIR, "cells", cell.name + ".json")
        data = S.load_json(path)
        data["rate"] = fixed
        with open(path, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
    return 0 if knee else 1


if __name__ == "__main__":
    sys.exit(main())
