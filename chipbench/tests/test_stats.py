"""Percentile and per-request arithmetic on hand-made stamps."""

import pytest

from chipbench import stats


def record(submit, stamps, n_out=None):
    return {'submit': submit, 'tokens': list(stamps),
            'n_out': len(stamps) if n_out is None else n_out}


def test_percentile_interpolates_between_order_statistics():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 75) == 4.0
    assert stats.percentile(xs, 90) == pytest.approx(4.6)
    assert stats.percentile([7.0], 99) == 7.0
    assert stats.percentile([], 50) is None


def test_ttft_counts_requests_submitted_in_the_window():
    recs = [record(0.5, [0.6, 0.7]),      # submitted before the window
            record(1.0, [1.07, 1.2]),
            record(1.9, [2.05]),          # first token after the window
            record(2.0, [2.1])]           # submitted at the close: out
    assert stats.ttft_samples(recs, 1.0, 2.0) == pytest.approx(
        [0.07, 0.15])


def test_tpot_needs_first_and_last_token_inside_and_a_whole_answer():
    recs = [record(1.0, [1.1, 1.2, 1.3, 1.4]),          # 3 gaps of 0.1
            record(0.5, [0.9, 1.2, 1.5]),                # first outside
            record(1.5, [1.6, 1.8, 2.1]),                # last outside
            record(1.0, [1.1, 1.3], n_out=5),            # cut short
            record(1.2, [1.3])]                          # one token
    assert stats.tpot_samples(recs, 1.0, 2.0) == pytest.approx([0.1])


def test_itl_is_every_gap_with_both_ends_inside():
    recs = [record(0.0, [0.9, 1.1, 1.2, 2.1])]
    assert stats.itl_samples(recs, 1.0, 2.0) == pytest.approx([0.1])
    assert stats.tokens_in_window(recs, 1.0, 2.0) == 2


def staircase_run(n_two_admissions):
    """40 requests x 100 gaps on a staircase: a tick is 72 ms, or 138
    with one admission in it, or 205 with two.  3 ticks in 10 admit
    one; ``n_two_admissions`` of the 4,000 gaps met two."""
    recs, slow = [], n_two_admissions
    for r in range(40):
        t, stamps = 10.0 + r * 0.001, []
        for g in range(100):
            stamps.append(t)
            if slow and g == 50:
                t, slow = t + 0.205, slow - 1
            else:
                t += 0.138 if g % 10 < 3 else 0.072
        recs.append(record(stamps[0] - 0.07, stamps))
    return stats.latency_family(recs, 0.0, 1e9)


def test_itl_p99_flips_on_a_staircase_where_tpot_p90_does_not():
    """Why ``tpot_p90_ms`` replaced ``itl_p99_ms`` end to end: 38 or
    40 two-admission ticks among 4,000 gaps (0.95% or 1.0%) put the
    99th percentile of the gaps on different steps; the per-request
    mean hardly moves."""
    a, b = staircase_run(39), staircase_run(40)
    assert a['n_itl'] == b['n_itl'] == 40 * 99
    assert a['itl_p99_ms'] == pytest.approx(138.0)
    assert b['itl_p99_ms'] > 140.0
    assert abs(b['tpot_p90_ms'] - a['tpot_p90_ms']) \
        < 0.01 * a['tpot_p90_ms']


def test_family_names_every_tail_the_run_prints():
    fam = stats.latency_family([record(1.0, [1.1, 1.2, 1.3])], 0, 9)
    assert {'ttft_p50_ms', 'ttft_p75_ms', 'ttft_p90_ms', 'ttft_p95_ms',
            'tpot_p50_ms', 'tpot_p90_ms', 'tpot_p95_ms', 'itl_p50_ms',
            'itl_p90_ms', 'itl_p99_ms'} <= set(fam)
    assert fam['tpot_p90_ms'] == pytest.approx(100.0)


def test_spread_is_the_drivers_quartile_distance():
    assert stats.spread([100, 101, 102, 103, 104, 105]) == \
        pytest.approx((104.25 - 100.75) / 102.5)
