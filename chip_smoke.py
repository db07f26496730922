"""Chip smoke test: yi-6b at its published widths on a TPU.

    python chip_smoke.py              # one chip: the serving path
    python chip_smoke.py --chips 4    # four chips: the SSR spatial path only

One chip.  The normal ``ServingEngine`` (paged pool, page 16, 8 slots,
max_seq 2048, prefix cache on) serves 8 seeded requests of 128-1024
prompt tokens and 64 new tokens each, on random bf16 weights from
``--seed``.  Checks: every request gets its full token count; the fused
paged decode and paged prefill kernels ran as Pallas kernels; the Pallas
kernels' logits agree with the jnp reference kernels (``REPRO_KERNELS=
ref``) on the same params; and the overlapped runtime (``overlap=True``)
emits the same token streams as the synchronous one.

Four chips (``--chips 4``).  ``pipeline.plan_forward`` runs a 4-stage
uniform plan over a plan mesh of the four devices, stage-sharded params,
and is compared with the one-chip forward's logits on the same params.

Runs in one process and starts none.  Exits non-zero without a result
line when JAX finds no TPU or any check fails; the last line of a passing
run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "yi-6b"
SLOTS, MAX_SEQ, PAGE = 8, 2048, 16
N_REQ, NEW_TOKENS = 8, 64
PROMPT_MIN, PROMPT_MAX = 128, 1024
PROBE_DECODE = 4
MUST_RUN = {"fused_paged_decode": "pallas",
            "paged_prefill_attention": "pallas"}

# Pallas vs reference logits.  The kernels and the jnp references sum the
# same f32 terms in different orders (an online softmax over pages
# against one softmax over the gathered keys, with the reference rounding
# probabilities to bf16 before P.V), so each layer's bf16 attention
# output can differ by an ulp (2^-8 relative).  Over 32 residual layers
# that stays within a few percent of the logit scale: the bound is 1/16
# of the largest reference logit.  A real kernel fault (a wrong page, a
# broken mask, a mis-rotated query) moves logits by the logit scale
# itself.  Greedy choices must agree wherever the reference's top-1
# margin exceeds twice that bound.
LOGIT_TOL_FRAC = 1.0 / 16


def log(msg):
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# one chip: the serving path
# ---------------------------------------------------------------------------

def make_requests(cfg, seed):
    """``N_REQ`` seeded prompts of ``PROMPT_MIN..PROMPT_MAX`` tokens; the
    last two reuse the first two's leading blocks, so warm-prefix
    admission (suffix-only prefill at an offset) runs too."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n = N_REQ
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, size=n)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(L)).astype(np.int32)
               for L in lens]
    for i in (n - 2, n - 1):
        src = prompts[i - (n - 2)]
        share = (min(len(src), len(prompts[i])) // 2 // PAGE) * PAGE
        prompts[i][:share] = src[:share]
    return [(p, NEW_TOKENS) for p in prompts]


def serve(model, params, reqs, *, overlap):
    """Serve ``reqs`` through a fresh engine; returns (streams, stats,
    wall seconds)."""
    from repro.serving import Request, ServingEngine
    eng = ServingEngine(model, params, slots=SLOTS, max_seq=MAX_SEQ,
                        paged=True, page_size=PAGE, prefix_cache=True,
                        overlap=overlap)
    t0 = time.perf_counter()
    for uid, (prompt, new) in enumerate(reqs):
        eng.submit(Request(uid, prompt, new))
    done = eng.run()
    wall = time.perf_counter() - t0
    streams = {r.uid: list(r.out_tokens) for r in done}
    st = eng.stats()
    del eng
    gc.collect()
    return streams, st, wall


def logits_probe(model, params, prompt, *, forced=None):
    """Prefill ``prompt`` into a one-slot paged cache in two chunks (cold,
    then a warm suffix at an offset) and decode ``PROBE_DECODE`` steps with
    the engine's own step functions, jitted afresh so the current
    ``REPRO_KERNELS`` path is traced.  Decode inputs are the greedy
    tokens, or ``forced`` ones.  Returns ([logits (V,) per step], tokens).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.engine import make_serve_step
    nb = MAX_SEQ // PAGE
    cache = model.init_paged_cache(1, MAX_SEQ, page_size=PAGE,
                                   num_blocks=nb)
    table = jnp.arange(nb, dtype=jnp.int32)[None]
    L = len(prompt)
    cut = (L // 2 // PAGE) * PAGE

    def chunk(p, c, toks, offset):
        return model.prefill_suffix_paged(p, c, toks, 0, offset,
                                          toks.shape[1], MAX_SEQ, table,
                                          table)
    prefill = jax.jit(chunk, donate_argnums=(1,))
    step = jax.jit(make_serve_step(model), donate_argnums=(1,))
    toks = jnp.asarray(prompt[None])
    _, cache = prefill(params, cache, toks[:, :cut], jnp.int32(0))
    lg, cache = prefill(params, cache, toks[:, cut:], jnp.int32(cut))
    out = [np.asarray(lg[0, -1], np.float32)]
    tokens = []
    for i in range(PROBE_DECODE):
        tok = int(np.argmax(out[-1])) if forced is None else forced[i]
        tokens.append(tok)
        _, lg, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32),
                            jnp.asarray([L + i], jnp.int32), None, table)
        out.append(np.asarray(lg[0, -1], np.float32))
    return out, tokens


def compare_logits(got, ref, what):
    """Max |difference| and greedy agreement of two logit sets against
    the tolerance in LOGIT_TOL_FRAC; raises SmokeFailure outside it."""
    import numpy as np
    got, ref = np.stack(got), np.stack(ref)
    check(np.isfinite(got).all(), f"{what}: non-finite logits")
    diff = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    tol = LOGIT_TOL_FRAC * scale
    top2 = np.sort(ref, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    agree = got.argmax(-1) == ref.argmax(-1)
    decided = margin > 2 * tol
    log(f"{what}: max|diff|={diff:.6g} (tol {tol:.6g} = "
        f"{LOGIT_TOL_FRAC:g} x max|ref| {scale:.6g}), argmax agreement "
        f"{int(agree.sum())}/{len(agree)}; {int(decided.sum())} positions "
        f"with ref top-1 margin > 2 x tol, all must agree")
    check(diff <= tol, f"{what}: max|diff| {diff} over tolerance {tol}")
    check(bool(agree[decided].all()),
          f"{what}: argmax differs where the reference margin exceeds "
          f"2 x tolerance")
    return diff


def one_chip(cfg, seed):
    import jax
    from repro.models import build_model

    model = build_model(cfg)
    t0 = time.perf_counter()
    params = model.init(jax.random.key(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{n_params} params ({cfg.param_dtype}), init "
        f"{time.perf_counter() - t0:.3f}s (compile included)")

    reqs = make_requests(cfg, seed)
    log(f"requests: prompt lengths {[len(p) for p, _ in reqs]}, "
        f"{NEW_TOKENS} new tokens each")

    # sync serving, twice: the first run compiles every shape, the second
    # (a fresh engine) runs from the compile cache
    sync, st, first_wall = serve(model, params, reqs, overlap=False)
    for uid, (_, new) in enumerate(reqs):
        check(len(sync.get(uid, ())) == new,
              f"request {uid}: {len(sync.get(uid, ()))} of {new} tokens")
    again, st, wall = serve(model, params, reqs, overlap=False)
    check(again == sync, "a second sync run gave different token streams")
    tokens = st["gen_tokens"]
    log(f"sync serving: {st['requests']} requests, {tokens} tokens; "
        f"first run {first_wall:.3f}s (compiles included), warm run "
        f"{wall:.3f}s = {tokens / wall:.3f} tok/s; compile ~ "
        f"{first_wall - wall:.3f}s")
    log("warm run host phases (s): " + ", ".join(
        f"{k}={v:.3f}" for k, v in sorted(st["phase_time_s"].items())))
    c = st["cache"]
    log(f"pool: peak {c['peak_blocks_in_use']}/{c['num_blocks']} blocks, "
        f"prefill hit rate {c['prefill_hit_rate']:.3f}, reused tokens "
        f"{c['reused_prefill_tokens']}")
    log(f"kernel paths: {st['kernels']}")
    for k, path in MUST_RUN.items():
        check(st["kernels"].get(k) == path,
              f"{k} ran as {st['kernels'].get(k)!r}, not {path!r}")
    check(c["reused_prefill_tokens"] > 0,
          "no warm-prefix admission ran (suffix prefill at an offset)")

    # Pallas kernels against the jnp references, same params
    prompt = reqs[0][0]
    got, toks = logits_probe(model, params, prompt)
    path = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = "ref"
    try:
        ref, _ = logits_probe(model, params, prompt, forced=toks)
    finally:
        if path is None:
            del os.environ["REPRO_KERNELS"]
        else:
            os.environ["REPRO_KERNELS"] = path
    compare_logits(got, ref,
                   f"pallas vs ref logits (prefill + {PROBE_DECODE} "
                   f"decode steps, prompt {len(prompt)})")

    # overlapped runtime: same streams as sync
    ovl, st_o, wall_o = serve(model, params, reqs, overlap=True)
    same = all(ovl.get(uid) == sync[uid] for uid in sync)
    log(f"overlap serving: {st_o['gen_tokens']} tokens in {wall_o:.3f}s "
        f"(compile cache warm); streams identical to sync: {same}")
    check(same, "overlap token streams differ from sync")


# ---------------------------------------------------------------------------
# four chips: the SSR spatial path
# ---------------------------------------------------------------------------

def spatial_forward(cfg, seed, devices, *, batch=4, seq=128, n_stages=4):
    """plan_forward of a uniform ``n_stages``-stage plan over ``devices``
    against the one-chip forward on the same params.  Returns
    (plan logits, one-chip logits) as host arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_plan_mesh
    from repro.models import build_model
    from repro.pipeline import plan_forward
    from repro.plan import uniform_plan

    model = build_model(cfg)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    key = jax.random.key(seed)

    # one chip first, then free its copy of the params
    one = jax.device_put(tokens, devices[0])
    with jax.default_device(devices[0]):
        params = model.init(key)
        ref = np.asarray(jax.jit(model.forward)(params, {"tokens": one})[0],
                         np.float32)
    del params
    gc.collect()

    plan = uniform_plan(cfg.num_groups, n_stages, n_microbatches=n_stages)
    mesh = make_plan_mesh(plan, devices)
    shapes = jax.eval_shape(model.init, key)
    shard = {k: jax.tree.map(
        lambda _: NamedSharding(mesh, P("stage") if k == "stack" else P()),
        v) for k, v in shapes.items()}
    params = jax.jit(model.init, out_shardings=shard)(key)
    rep = NamedSharding(mesh, P())
    fwd = jax.jit(lambda p, t: plan_forward(model, p, {"tokens": t}, mesh,
                                            plan))
    got = np.asarray(fwd(params, jax.device_put(tokens, rep)), np.float32)
    del params
    return got, ref


def four_chips(cfg, seed):
    import jax
    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    t0 = time.perf_counter()
    got, ref = spatial_forward(cfg, seed, devs)
    log(f"plan_forward: {cfg.name}, uniform 4-stage plan on a "
        f"(stage=4) mesh, {time.perf_counter() - t0:.3f}s (compiles "
        f"included)")
    compare_logits(list(got.reshape(-1, got.shape[-1])),
                   list(ref.reshape(-1, ref.shape[-1])),
                   "4-chip plan_forward vs 1-chip forward logits")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the serving path on one chip; 4: only the "
                         "SSR spatial path across four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"jax {jax.__version__}, platform {dev.platform}, kind "
        f"{dev.device_kind}, {len(devs)} device(s)")
    if dev.platform != "tpu":
        log(f"FAIL: no TPU (platform {dev.platform!r}); this smoke test "
            f"has no CPU fallback")
        return 1
    from repro.backend import compat
    from repro.configs import REGISTRY
    log(f"compile cache: {compat.use_compile_cache()}")
    cfg = REGISTRY[ARCH]
    try:
        if args.chips == 4:
            four_chips(cfg, args.seed)
        else:
            one_chip(cfg, args.seed)
    except SmokeFailure as e:
        log(f"FAIL: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
