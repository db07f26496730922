"""Distributed training launcher: pjit train loop with sharded params,
ZeRO-1 optimizer states, checkpoint/restart, and straggler-aware step
timing.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 10 \
        --seq 128 --batch 8            # local smoke (1 device)

On a real pod this is launched once per host (jax.distributed handles the
rest); the mesh comes from make_production_mesh().  Elastic restart: on
relaunch with a different device count the checkpoint is resharded onto the
new mesh (repro.checkpoint.restore with fresh shardings).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import compat
from repro.checkpoint import CheckpointManager, latest_step
from repro.configs import REGISTRY, ShapeConfig, reduced
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh, use_mesh
from repro.models import build_model
from repro.sharding import input_shardings_tree, param_shardings
from repro.training import AdamW, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the smoke-size config (configs.reduced); "
                         "--no-reduced trains the published widths")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--straggler-warn-ms", type=float, default=0.0,
                    help="warn when a step exceeds median by this margin")
    args = ap.parse_args(argv)
    compat.use_compile_cache()

    cfg = REGISTRY[args.arch]
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    mesh = make_host_mesh()
    opt = AdamW(warmup_steps=10, total_steps=max(args.steps, 100))
    shape = ShapeConfig("train", args.seq, args.batch, "train")
    data = SyntheticLM(cfg, shape)
    step_fn = make_train_step(model, opt, remat=True,
                              grad_accum=args.grad_accum)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    with use_mesh(mesh):
        params = model.init(jax.random.key(0))
        params = jax.device_put(params, param_shardings(params, mesh))
        opt_state = opt.init(params)
        start = 0
        if mgr and latest_step(args.ckpt_dir) is not None:
            restored, start = mgr.restore_latest(
                {"params": params, "opt": opt_state},
                shardings={"params": param_shardings(params, mesh),
                           "opt": None})
            params = restored["params"]
            o = restored["opt"]
            opt_state = type(opt_state)(step=jnp.asarray(o[0]), m=o[1],
                                        v=o[2]) \
                if isinstance(o, (list, tuple)) else o
            print(f"[train] resumed at step {start} "
                  f"(elastic reshard onto {mesh.devices.shape})")
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))

        times = []
        for i in range(start, args.steps):
            t0 = time.perf_counter()
            batch = jax.device_put(
                jax.tree.map(jnp.asarray, data.batch_at(i)),
                input_shardings_tree(data.batch_at(i), mesh))
            params, opt_state, metrics = jitted(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            times.append(dt)
            # straggler mitigation hook: deterministic step budget — on a
            # fleet, a step exceeding the budget triggers microbatch
            # rebalancing / hot-spare promotion by the controller.
            if args.straggler_warn_ms and len(times) > 3:
                med = float(np.median(times[-10:]))
                if dt > med + args.straggler_warn_ms / 1e3:
                    print(f"[straggler] step {i} took {dt*1e3:.0f}ms "
                          f"(median {med*1e3:.0f}ms)")
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i}: loss={float(metrics['loss']):.4f} "
                      f"{dt*1e3:.0f}ms")
            if mgr and (i + 1) % args.ckpt_every == 0:
                mgr.save({"params": params, "opt": opt_state}, i + 1)
        if mgr:
            mgr.save({"params": params, "opt": opt_state}, args.steps)
            mgr.wait()
    print("[train] done")


if __name__ == "__main__":
    main()
