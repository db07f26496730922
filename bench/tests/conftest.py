"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``.

They import the harness as the package ``bench`` and the program from
``src``, and shrink a real cell to a tiny size (``tiny_cell``)."""
import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def shrink(cell_name, dtype="float32", **settings):
    """``cell_name`` at a size the CPU runs in seconds: its architecture
    module's tiny widths, short prompts and outputs, four slots; the
    cell's own sampling of the check.  In float32 the program and the
    reference agree to about 1e-5 here, so the stand-in limit on the
    widest gap is 0.05."""
    from bench import spec
    cell = spec.load_cell(cell_name)
    conf = spec.architecture(dict(cell.config, torch_dtype=dtype)).tiny()
    mix = dict(cell.traffic, ramp_s=1,
               prompt=dict(cell.traffic["prompt"], median=32, min=8, max=64),
               output=dict(cell.traffic["output"], median=8, min=4, max=16))
    st = dict(cell.settings, slots=4, max_seq=128, num_blocks=64,
              prefill_bucket=16, rate=4.0,
              check=dict(cell.settings["check"], gap_limit=0.05))
    st.update(settings)
    return dataclasses.replace(cell, config=conf, traffic=mix, settings=st)


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU has no published peaks; tests borrow the v5e's."""
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
