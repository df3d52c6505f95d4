import pytest

from bench.harness import stats


def test_percentile_interpolates():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == pytest.approx(50.5)
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_runs_from_the_due_time_not_the_send():
    due, sent, done = [0.0, 1.0], [0.5, 1.0], [0.6, 1.1]
    out = stats.open_loop_summary(due, sent, done, [True, True], 10.0)
    # the first request waited half a second before it was sent: it counts
    assert out["p95_ms"] > 500.0
    assert out["late_p99_ms"] == pytest.approx(500.0, rel=0.02)
    assert out["failed"] == 0 and out["attempted"] == 2


def test_a_failure_counts_against_attempts_and_sits_in_the_tail():
    n = 100
    due = [i * 0.1 for i in range(n)]
    done = [d + 0.01 for d in due]
    ok = [True] * n
    for i in range(10):                 # 10% fail: the p95 is a failure
        ok[i * 10] = False
    out = stats.open_loop_summary(due, due, done, ok, 10.0)
    assert out["attempted"] == n and out["failed"] == 10
    assert out["p95_ms"] == pytest.approx(10_000.0)
    assert out["p50_ms"] == pytest.approx(10.0)


def test_spread_is_the_interquartile_share_of_the_median():
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    assert stats.spread([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)
