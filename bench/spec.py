"""Find a cell's files by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; each has a data file of its own under
``bench/`` (``configs/<config>.json``, ``traffic/<mix>.json``,
``cells/<cell>.json``), and each per-layer metric a reader in
``metrics/<metric>.py``.  A configuration file names its architecture by
its published ``model_type``, and ``arch/<model_type>.py`` gives what
the harness needs of that architecture (``INTERFACE``): the program's
configuration and weights, the plain reference, and the FLOP and byte
counts.  Adding a cell, a mix or a metric adds its files; adding a
configuration adds ``configs/<config>.json``, and for a new architecture
``arch/<model_type>.py`` and, where the program needs one, an entry in
its ``REGISTRY``.  Nothing here changes."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from functools import partial
from types import SimpleNamespace
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]     # configs/<config>.json
    traffic: Dict[str, Any]    # traffic/<mix>.json
    settings: Dict[str, Any]   # cells/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bm = benchmark(root)
    work = {w["name"]: w for w in bm["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(work)}")
    w = work[name]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(root, cfg_entry["file"])),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       w["traffic"] + ".json")),
        settings=load_json(os.path.join(BENCH_DIR, "cells", name + ".json")),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, name)])


ARCH_DIR = os.path.join(BENCH_DIR, "arch")
# what every ``arch/<model_type>.py`` gives, each taking the
# configuration file's dict first
INTERFACE = ("program_config", "program_params", "hidden", "final",
             "decode_token_flops", "prefill_flops", "fused_decode_calls",
             "prefill_attn_calls", "tiny")


def architectures() -> List[str]:
    """The ``model_type``s that have a module under ``arch/``."""
    return sorted(f[:-3] for f in os.listdir(ARCH_DIR)
                  if f.endswith(".py") and not f.startswith("_"))


def architecture(conf: Dict[str, Any]) -> SimpleNamespace:
    """The module ``arch/<model_type>.py`` of a configuration file, bound
    to it: ``arch.f(*args)`` is ``module.f(conf, *args)`` for each name
    of ``INTERFACE``; ``arch.module`` is the module itself."""
    known = architectures()
    kind = conf.get("model_type")
    if kind not in known:
        raise SystemExit(f"configuration {conf.get('name')!r} has model_type "
                         f"{kind!r}; bench/arch/ has modules for {known}")
    name = "bench_arch_" + kind
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ARCH_DIR, kind + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        # one module object per process, so its jitted functions (and
        # their compiled programs) are shared by every caller
        sys.modules[name] = mod
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"bench/arch/{kind}.py lacks {missing}")
    return SimpleNamespace(module=mod, conf=conf, **{
        f: partial(getattr(mod, f), conf) for f in INTERFACE})


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
