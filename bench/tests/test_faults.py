"""A whole run at a tiny size on the CPU (everything but the look for a
chip), sound and with the timed path broken underneath: each fault a
serving cell can have must turn ``correct`` false."""
import jax
import jax.numpy as jnp
import pytest

from bench import run as RUN
from bench.tests.conftest import shrink

SEED = 2**40 + 3


def _alter_token(eng):
    """A served token altered where it is produced: slot 0's decode
    output is shifted by one."""
    step = eng.serve_step

    def bad(params, cache, tokens, pos, positions=None, tables=None):
        nxt, logits, cache = step(params, cache, tokens, pos, positions,
                                  tables)
        return nxt.at[0, 0].set((nxt[0, 0] + 1) % 256), logits, cache
    eng.serve_step = bad


def _state_unchanged(eng):
    """The decode step returns its cache unchanged: no token's K/V lands."""
    step = eng.serve_step

    def bad(params, cache, tokens, pos, positions=None, tables=None):
        nxt, logits, _ = step(params, jax.tree.map(jnp.copy, cache), tokens,
                              pos, positions, tables)
        return nxt, logits, cache
    eng.serve_step = bad


def _half_batch(eng):
    """Half of the batch left out: the upper half of the slots is not
    decoded, each repeats its input token."""
    step = eng.serve_step

    def bad(params, cache, tokens, pos, positions=None, tables=None):
        nxt, logits, cache = step(params, cache, tokens, pos, positions,
                                  tables)
        h = tokens.shape[0] // 2
        return nxt.at[h:].set(tokens[h:]), logits, cache
    eng.serve_step = bad


CELLS = ["yi6b-chat", "yi34b8l-batch"]
# a load that keeps every slot busy (the batch cell's backlog does so)
LOAD = {"yi6b-chat": dict(rate=30.0), "yi34b8l-batch": {}}


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell, cpu_peaks):
    out = RUN.run_cell(shrink(cell, **LOAD[cell]), SEED, 2.0, trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert "host_ms_per_tick" in out["metrics"]


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_batch])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault, cpu_peaks):
    # the cell's own sampling of the check, with the stand-in gap limit
    out = RUN.run_cell(shrink(cell, **LOAD[cell]), SEED, 2.0, trace=False,
                       fault=fault)
    assert not out["correct"], out["checks"]
    c = out["checks"]["widest_gap"]
    assert c["value"] > c["limit"]


def test_the_sample_covers_every_slot_that_finished_a_request():
    from bench.driver import Drive
    from bench.window import Record

    class Req:
        def __init__(self, n):
            self.prompt, self.out_tokens = [1] * n, [2] * n

    recs = [Record(0.0, 1, 1, slot=j % 8, done=j % 9 != 0)
            for j in range(60)]
    reqs = [Req(50 + j) for j in range(60)]
    drv = Drive(recs, reqs, [], 0.0, 1.0, 0)
    rows = RUN.sample_rows(drv, 2**40 + 1, min_tokens=10)
    picked = {len(p) for p, _ in rows}
    slots = {recs[n - 50].slot for n in picked}
    assert slots == set(range(8))
    assert max(picked) == 50 + max(j for j in range(60) if j % 9)
    assert len(rows) == 8
