"""``bench/run.py`` as the benchmark's driver starts it."""
import json
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT

ARGS = ["--workload", "yi6b-chat", "--seed", str(2**33 + 1), "--seconds",
        "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_non_zero_without_a_result_line():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_a_checkout_of_the_benchmark_alone_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_json_names_a_file_for_everything():
    from bench import spec
    bm = spec.benchmark()
    for c in bm["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.settings["check"]["gap_limit"] > 0
    cells = [w["name"] for w in bm["workloads"]]
    reports = {c: {m["name"] for m in bm["end_to_end"]
                   if c in m.get("workloads", cells)} for c in cells}
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
    for m in bm["per_layer"]:
        assert callable(spec.reader(m["name"]))
        # every cell a per-layer metric is read in reports what it moves
        for c in m.get("workloads", cells):
            assert m["moves"] in reports[c] or "workloads" not in m, m
    json.dumps(bm)
