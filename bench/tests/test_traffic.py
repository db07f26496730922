import numpy as np
import pytest

from bench import spec
from bench import traffic as TR

MIXES = ["chat", "batch"]


def _mix(name):
    return spec.load_json(f"{spec.BENCH_DIR}/traffic/{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_generator_is_deterministic_by_seed(name):
    a = TR.generate(_mix(name), 1.5, 10.0, 30.0, 2**33 + 7, 64000)
    b = TR.generate(_mix(name), 1.5, 10.0, 30.0, 2**33 + 7, 64000)
    c = TR.generate(_mix(name), 1.5, 10.0, 30.0, 2**33 + 8, 64000)
    key = [(it.due, it.max_new, it.prompt.tobytes()) for it in a]
    assert key == [(it.due, it.max_new, it.prompt.tobytes()) for it in b]
    assert key != [(it.due, it.max_new, it.prompt.tobytes()) for it in c]


@pytest.mark.parametrize("name", MIXES)
def test_generator_stays_inside_its_ranges(name):
    mix = _mix(name)
    items = TR.generate(mix, 1.5, 10.0, 30.0, 123, 64000)
    lo, hi = TR.prompt_range(mix)
    out = mix["output"]
    assert items
    for it in items:
        assert lo <= len(it.prompt) <= hi
        assert out["min"] <= it.max_new <= out["max"]
        assert 0 <= it.due < 40.0
        assert it.prompt.min() >= 1 and it.prompt.max() < 64000
    assert [it.due for it in items] == sorted(it.due for it in items)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_window_the_same_work(name):
    """Only the order of lengths and gaps comes from the seed, and the
    window (the whole backlog, for a batch) gets the same set for every
    seed."""
    def window(seed):
        items = TR.generate(_mix(name), 1.5, 10.0, 30.0, seed, 64000)
        return [i for i in items if i.due >= 10.0] if name != "batch" \
            else items
    a, b = window(1), window(2**40 + 99)
    assert a and len(a) == len(b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    assert sorted(len(i.prompt) for i in a) == \
        sorted(len(i.prompt) for i in b)
    if name == "chat":
        assert len(a) == 45
        gaps = sorted(np.diff([i.due for i in a] + [40.0]))
        assert gaps == pytest.approx(sorted(np.diff(
            [i.due for i in window(7)] + [40.0])))


def test_quantiles_of_a_lognormal_have_its_median():
    v = TR.quantiles({"dist": "lognormal", "median": 600, "sigma": 0.6,
                      "min": 64, "max": 1536}, 1001)
    assert v[np.argsort(v)][500] == 600
    assert v.min() >= 64 and v.max() <= 1536


def test_the_seed_orders_lengths_without_constraint():
    """Long requests bunch as they would by chance: over seeds, some
    window holds a run of four consecutive prompts all above the
    median, which spreading the distribution over each run would
    forbid."""
    def longest_run(seed):
        items = TR.generate(_mix("chat"), 0.96, 12.0, 51.0, seed, 64000)
        big = [len(i.prompt) > 600 for i in items if i.due >= 12.0]
        run = best = 0
        for b in big:
            run = run + 1 if b else 0
            best = max(best, run)
        return best
    assert max(longest_run(2**33 + s) for s in range(20)) >= 4
