"""Percentiles are taken over every sample; lost requests rank last."""
import math

import pytest

from harness.stats import percentile


def test_percentile_is_nearest_rank_over_all_samples():
    vals = list(range(1, 101))              # 1..100
    assert percentile(vals, 90) == 90
    assert percentile(vals, 95) == 95
    assert percentile(vals, 99) == 99
    assert percentile(vals, 100) == 100
    assert percentile([5.0], 90) == 5.0
    assert percentile([], 90) is None


def test_percentile_does_not_average_chunks():
    # Two chunks with different tails: the percentile of the union is not
    # the mean of the chunks' percentiles.
    a = [10.0] * 80 + [100.0] * 20
    b = [10.0] * 100
    assert percentile(a + b, 95) == 100.0
    assert (percentile(a, 95) + percentile(b, 95)) / 2 == 55.0


def test_shed_requests_count_as_infinitely_late():
    ttft = [0.1] * 18 + [float("inf")] * 2      # two of twenty shed
    assert percentile(ttft, 90) == 0.1
    assert math.isinf(percentile(ttft, 95))
    # one more lost request moves the p90 onto the lost ones
    assert math.isinf(percentile([0.1] * 17 + [float("inf")] * 3, 90))


def test_ttft_reader_reports_lower_bound_when_rank_lands_on_lost(bench_run):
    from types import SimpleNamespace
    from harness import spec
    read = spec.metric_reader("ttft_p90_ms")

    def rec(due, first, shed=False):
        return SimpleNamespace(arrival=SimpleNamespace(due_s=due),
                               token_s=[] if first is None else [first],
                               shed=shed)
    recs = [rec(0.0, 0.5)] * 8 + [rec(1.0, None, shed=True), rec(2.0, None)]
    ctx = SimpleNamespace(window=SimpleNamespace(records=recs, end_s=10.0))
    # 2 of 10 lost: the 90th percentile is a lost request; its wait to
    # the end of the run (10 s - due 1 s) is the lower bound reported.
    assert read(ctx) == pytest.approx(9000.0)
    recs = [rec(0.0, 0.5)] * 9 + [rec(1.0, None, shed=True)]
    ctx = SimpleNamespace(window=SimpleNamespace(records=recs, end_s=10.0))
    assert read(ctx) == pytest.approx(500.0)



def test_tpot_reader_takes_every_gap_inside_the_window(bench_run):
    from types import SimpleNamespace
    from harness import spec
    read = spec.metric_reader("tpot_p95_ms")
    # 19 gaps of 10 ms in one request, one of 50 ms in another; a gap
    # that ends after the window's close does not count.
    a = SimpleNamespace(token_s=[0.01 * i for i in range(20)])
    b = SimpleNamespace(token_s=[1.0, 1.05, 9.0])
    ctx = SimpleNamespace(window=SimpleNamespace(records=[a, b]),
                          seconds=5.0)
    assert read(ctx) == pytest.approx(10.0)       # rank 19 of 20 gaps
    b.token_s = [1.0, 1.05, 1.10]
    assert read(ctx) == pytest.approx(50.0)       # rank 20 of 21
    assert read(SimpleNamespace(window=SimpleNamespace(records=[]),
                                seconds=5.0)) is None
