import pytest

from bench.window import Record, gaps, percentile, summary, ttfts


def _recs():
    return [
        Record(due=0.5, prompt_len=10, max_new=3, submitted=0.5,
               stamps=[1.0, 1.1, 1.3], done=True),      # due in the ramp
        Record(due=2.0, prompt_len=10, max_new=3, submitted=2.01,
               stamps=[2.5, 2.6, 2.8], done=True),
        Record(due=3.0, prompt_len=10, max_new=3, submitted=3.0,
               stamps=[]),                               # never answered
        Record(due=4.0, prompt_len=10, max_new=3, submitted=4.1,
               stamps=[4.5, 5.5]),                       # 2nd after w1
    ]


def test_ttft_counts_every_request_due_with_its_wait_so_far():
    # window (1.5, 5.0]: the unanswered request waits 5.0 - 3.0
    assert ttfts(_recs(), 1.5, 5.0) == pytest.approx([0.5, 2.0, 0.5])


def test_gaps_count_where_the_later_token_falls():
    assert gaps(_recs(), 1.2, 5.0) == pytest.approx([0.2, 0.1, 0.2])
    assert gaps(_recs(), 1.05, 5.0) == pytest.approx([0.1, 0.2, 0.1, 0.2])


def test_nearest_rank_percentile():
    v = list(range(1, 101))
    assert percentile(v, 90) == 90
    assert percentile(v, 95) == 95
    assert percentile([3.0], 99) == 3.0
    assert percentile(list(range(1, 11)), 95) == 10


def test_summary_of_a_window():
    s = summary(_recs(), 1.5, 5.0)
    assert s["due"] == 3 and s["unanswered"] == 1
    assert s["attempted"] == 3               # the ramp's finished before
    assert summary(_recs(), 1.05, 5.0)["attempted"] == 4
    assert s["tokens"] == 4                  # 2.5, 2.6, 2.8, 4.5
    assert s["output_tok_s"] == pytest.approx(4 / 3.5)
    assert s["ttft_p90_s"] == pytest.approx(2.0)
    assert s["itl_mean_ms"] == pytest.approx(150.0)
    assert s["submit_lag_p99_ms"] == pytest.approx(100.0)
