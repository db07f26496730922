"""End-to-end serving driver (the paper's application kind is inference):
serve staggered requests through the per-slot continuous-batching engine
and report latency/throughput — the measured analogue of the paper's
latency-throughput tradeoff.

    PYTHONPATH=src python examples/serve_e2e.py [--requests 12] [--slots 4]

Requests arrive mid-stream (submitted between engine ticks): admission
prefills only the admitted slot into the persistent slot cache, so
in-flight decodes are never restarted — sweeping --slots trades latency
(fewer slots = less queueing) against throughput (more slots = fuller
decode batches), the same tradeoff axis as the paper's batch sweeps
(Fig. 2), measured on the real serving path.
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import REGISTRY, reduced
from repro.models import build_model
from repro.serving import Request, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="--no-reduced serves the published widths")
    args = ap.parse_args()

    cfg = REGISTRY[args.arch]
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)

    eng = ServingEngine(model, params, slots=args.slots, max_seq=128)
    reqs = [Request(uid, rng.integers(1, cfg.vocab_size,
                                      size=rng.integers(3, 12))
                    .astype(np.int32), args.new_tokens)
            for uid in range(args.requests)]
    # staggered arrivals: half up front, the rest trickle in between ticks
    # (each admission prefills ONE slot; other slots keep decoding).
    t0 = time.perf_counter()
    pending = list(reqs)
    for _ in range(max(args.requests // 2, 1)):
        eng.submit(pending.pop(0))
    busy = True
    while busy or pending:
        if pending:
            eng.submit(pending.pop(0))
        busy = eng.tick()
    wall = time.perf_counter() - t0

    st = eng.stats()
    ttfts, lats = st["ttft_s"], st["latency_s"]
    print(f"requests={st['requests']} slots={args.slots} "
          f"tokens={st['gen_tokens']} wall={wall:.2f}s "
          f"occupancy={st['slot_occupancy']:.2f}")
    print(f"throughput: {st['gen_tokens'] / wall:.1f} tok/s")
    print(f"TTFT   p50={np.percentile(ttfts, 50)*1e3:.1f}ms "
          f"p95={np.percentile(ttfts, 95)*1e3:.1f}ms")
    print(f"latency p50={np.percentile(lats, 50)*1e3:.1f}ms "
          f"p95={np.percentile(lats, 95)*1e3:.1f}ms")


if __name__ == "__main__":
    main()
