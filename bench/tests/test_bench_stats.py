import pytest

from bench import stats
from bench.stats import RequestLog


def test_percentile_matches_linear_interpolation():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert stats.percentile(xs, 50) == pytest.approx(2.5)
    assert stats.percentile(xs, 95) == pytest.approx(3.85)
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 4.0
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_gaps_and_rate_over_the_whole_window():
    logs = [RequestLog(0, 4, token_s=[1.0, 1.5, 3.0]),
            RequestLog(1, 2, token_s=[2.0, 2.2]),
            RequestLog(2, 9, token_s=[9.5, 10.5])]   # one after the close
    assert sorted(stats.token_gaps(logs)) == \
        pytest.approx([0.2, 0.5, 1.0, 1.5])
    e2e = stats.end_to_end(logs, t0=0.0, t_end=10.0)
    assert e2e["output_tokens_per_s"] == pytest.approx(6 / 10.0)
    assert e2e["tpot_p95_ms"] == pytest.approx(
        1e3 * stats.percentile([0.2, 0.5, 1.0, 1.5], 95))
    assert set(e2e) == {"output_tokens_per_s", "tpot_p95_ms"}


def test_a_window_without_a_token_gap_has_no_tpot():
    logs = [RequestLog(0, 4, token_s=[1.0]), RequestLog(1, 4)]
    e2e = stats.end_to_end(logs, t0=0.0, t_end=2.0)
    assert e2e == {"output_tokens_per_s": pytest.approx(0.5)}
