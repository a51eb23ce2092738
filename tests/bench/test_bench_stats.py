"""End-to-end arithmetic (bench/stats.py) on synthetic event lists."""
import pytest

import bench_tiny  # noqa: F401
from bench import stats


def rec(due, times, submitted=None):
    return stats.RequestRecord(due=due, submitted=submitted,
                               token_times=list(times))


def test_percentiles_cover_every_request():
    records = [rec(float(i), [i + 0.1 * (i + 1)]) for i in range(10)]
    tt = stats.ttfts(records, deadline=100.0)
    assert tt == pytest.approx([0.1 * (i + 1) for i in range(10)])
    assert stats.percentile(tt, 50) == pytest.approx(0.55)
    assert stats.percentile(tt, 90) == pytest.approx(0.91)


def test_missing_first_token_counts_until_deadline():
    records = [rec(0.0, [0.5]), rec(1.0, [])]
    assert stats.ttfts(records, deadline=11.0) == [0.5, 10.0]


def test_rates_and_gaps_over_the_whole_window_with_a_stall():
    # one request streams a token every 0.1 s, then the engine stalls for
    # 2 s, then streams again; the rate is over the window's full length
    t = [0.1 * k for k in range(1, 11)] + [3.0 + 0.1 * k for k in range(10)]
    records = [rec(0.0, t, submitted=0.0)]
    e2e = stats.end_to_end(records, 0.0, 5.0, deadline=65.0)
    assert e2e["output_tok_per_s"] == pytest.approx(20 / 5.0)
    assert e2e["ttft_p50_ms"] == pytest.approx(100.0)
    gaps = stats.inter_token_gaps(records, 0.0, 5.0)
    assert len(gaps) == 19 and max(gaps) == pytest.approx(2.0)
    assert e2e["itl_p99_ms"] == pytest.approx(
        1e3 * stats.percentile(gaps, 99))
    # tokens after the window count for neither rate nor gaps
    assert stats.tokens_in_window(records, 0.0, 2.0) == 10


def test_lateness():
    records = [rec(1.0, [], submitted=1.25), rec(2.0, [], submitted=2.0)]
    late = stats.lateness(records)
    assert late["n"] == 2 and late["max_s"] == pytest.approx(0.25)
