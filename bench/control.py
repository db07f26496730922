"""The control of a cell's correctness check, on the chip at the cell's
own size: for each seed, one short run of the program (its widest gap,
the lower reading) and, over the same prompts and served tokens, the
reference computed in float8 (the token it puts first at each position,
and that token's gap under the float32 reference: the upper reading).
Every seed runs in this one process.

    python bench/control.py --workload yi6b-chat --seeds 1,2,3 --seconds 20

Prints one JSON line per seed and exits non-zero where a control gap
does not exceed the cell's limit.  The benchmark's own runs never run it.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = S.load_cell(args.workload)
    jax = RUN._setup_jax()
    if jax.devices()[0].platform != "tpu":
        RUN.log("control runs on the chip only")
        return 1
    failed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = RUN.run_cell(cell, seed, args.seconds, False,
                           control=True)
        c = out["checks"]["widest_gap"]
        line = {"seed": seed, "program_gap": c["value"],
                "control_gap": out.get("control_gap"), "limit": c["limit"],
                "correct": out["correct"]}
        print(json.dumps(line), flush=True)
        failed += not (line["control_gap"] or 0) > c["limit"]
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
