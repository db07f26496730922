"""The control at a size a test run holds: the reference computed in
float8 in the program's place, over the prompts and tokens a bf16 run of
the program served, reads a widest gap well above the program's own."""
import pytest

from bench import run as RUN
from bench.tests.conftest import shrink


@pytest.mark.parametrize("seed", [2**36 + 1, 2**36 + 2, 2**36 + 3])
def test_fp8_control_reads_far_above_the_program(seed, cpu_peaks):
    # at this size the bf16 program reads 0 to 0.01 and the control 0.15
    # to 0.22 on these seeds: the stand-in limit lies between
    cell = shrink("yi6b-chat", dtype="bfloat16", rate=30.0)
    cell.settings["check"]["gap_limit"] = 0.04
    out = RUN.run_cell(cell, seed, 2.0, trace=False, control=True)
    program = out["checks"]["widest_gap"]["value"]
    assert out["correct"], program
    assert out["control_gap"] >= 3 * program, (program, out["control_gap"])
    assert out["control_gap"] > out["checks"]["widest_gap"]["limit"]
