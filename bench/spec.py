"""Find a cell's files by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; each has a data file of its own under
``bench/`` (``configs/<config>.json``, ``traffic/<mix>.json``,
``cells/<cell>.json``), and each per-layer metric a reader in
``metrics/<metric>.py``.  Adding a cell, a configuration, a mix or a
metric adds files; nothing here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# published (Hugging Face ``config.json``) keys -> the program's ModelConfig
HF_TO_PROGRAM = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
}


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


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry named by ``registry``, with every published size of the
    file written over it (so the file, not the registry, is what runs)."""
    from repro.configs import REGISTRY
    base = REGISTRY[conf["registry"]]
    sizes = {HF_TO_PROGRAM[k]: conf[k] for k in HF_TO_PROGRAM}
    dtype = conf["torch_dtype"]
    return dataclasses.replace(base, name=conf["name"], dtype=dtype,
                               head_dim=sizes["d_model"] // sizes["num_heads"],
                               param_dtype=dtype,
                               tie_embeddings=conf["tie_word_embeddings"],
                               **sizes)


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
