import math
import statistics

import pytest

from benchmarks.lib import estimators as E


def test_a_stall_moves_the_window_rate_and_not_the_block_median():
    # 40 units of 10 work, 1 s each; block of 5 -> 8 blocks at 10 work/s
    stamps = [float(i) for i in range(41)]
    work = [10] * 40
    assert E.window_rate(work, (stamps[0], stamps[-1])) == pytest.approx(10.0)
    assert statistics.median(E.block_rates(stamps, work, 5)) == pytest.approx(10.0)
    # one unit stalls for 30 s: the end-to-end rate (all work over all
    # time) falls to 400/70 = 5.7; the per-layer block median does not move
    slow = stamps[:13] + [s + 30.0 for s in stamps[13:]]
    assert E.window_rate(work, (slow[0], slow[-1])) == pytest.approx(400 / 70)
    assert statistics.median(E.block_rates(slow, work, 5)) == pytest.approx(10.0)
    rates = E.block_rates(slow, work, 5)
    assert len(rates) == 8 and min(rates) == pytest.approx(50 / 35)


def test_blocks_tile_the_window_and_drop_only_a_partial_tail():
    stamps = [0.0, 1.0, 2.0, 4.0, 6.0, 7.0]
    work = [1, 1, 1, 1, 1]
    # blocks of 2: [0,2] and [2,6]; the fifth unit is a partial block
    assert E.block_rates(stamps, work, 2) == [pytest.approx(1.0), pytest.approx(0.5)]
    with pytest.raises(ValueError):
        E.block_rates(stamps, work[:-1], 2)  # stamps must be one more
    assert E.block_rates([0.0, 1.0], [1], 2) == []  # no whole block


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8), (100, 5.0)])
def test_percentile_interpolates_between_order_statistics(q, want):
    assert E.percentile([5.0, 1.0, 4.0, 2.0, 3.0], q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        E.percentile([], 95)
    with pytest.raises(ValueError):
        E.window_rate([1], (5.0, 5.0))


def test_window_edges_open_excluded_close_included():
    w = (10.0, 20.0)
    assert not E.in_window(10.0, w)
    assert E.in_window(10.000001, w)
    assert E.in_window(20.0, w)
    assert not E.in_window(20.000001, w)


def test_histogram_delta_reads_only_what_fell_between_snapshots():
    edges = [0.1, 0.2, 0.3]
    before = {"buckets": edges, "counts": [100, 0, 0, 0]}  # the warm-up
    after = {"buckets": edges, "counts": [100, 2, 7, 1]}
    assert E.histogram_delta_percentile(before, after, 50) == 0.3
    assert E.histogram_delta_percentile(before, after, 10) == 0.2
    assert E.histogram_delta_percentile(before, after, 100) == math.inf
    assert E.histogram_delta_percentile(after, after, 50) is None
