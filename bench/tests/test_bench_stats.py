"""Percentile, interval-union and window arithmetic of the benchmark."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import driver, stats  # noqa: E402


@pytest.mark.parametrize("q", [0, 10, 50, 90, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(0).lognormal(size=137)
    assert stats.percentile(xs.tolist(), q) == pytest.approx(
        float(np.percentile(xs, q)), rel=1e-12)


def test_failed_requests_count_as_infinite_in_the_tail():
    # 10 requests, 2 never got a first token: p90 interpolates between
    # the 9th and 10th sorted values, both infinite
    ttft = [0.1 * i for i in range(1, 9)] + [math.inf, math.inf]
    assert stats.percentile(ttft, 90) == math.inf
    # p50 stays finite: the infinite ones sort last
    assert stats.percentile(ttft, 50) == pytest.approx(0.55)
    # one failure out of 20: p90 interpolates below it and stays finite
    ttft = [0.1 * i for i in range(1, 20)] + [math.inf]
    assert math.isfinite(stats.percentile(ttft, 90))
    assert stats.percentile(ttft, 99) == math.inf


def test_percentile_refuses_nothing_and_bad_q():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_union_length_merges_overlaps():
    assert stats.union_length([]) == 0
    assert stats.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4


class _Req:
    def __init__(self, first, decodes):
        self.prefill_done = first
        self.decode_times = decodes


def test_window_gaps_keep_gaps_that_end_inside_the_window():
    log = driver.RunLog(w0=10.0, window_s=5.0)
    log.reqs = [driver.ReqRec(_Req(9.0, [10.5, 11.0, 15.0]), 2),
                driver.ReqRec(_Req(-1.0, []), 2)]
    # 9.0 -> 10.5 ends inside, 10.5 -> 11.0 inside, 11.0 -> 15.0 at the
    # window's end (excluded); the request with no first token has none
    assert driver.window_gaps(log) == pytest.approx([1.5, 0.5])


def test_window_ttft_counts_a_missing_first_token_as_infinite():
    log = driver.RunLog(w0=10.0, window_s=5.0)
    reqs = [_Req(10.4, []), _Req(-1.0, []), _Req(13.0, [13.2])]
    for r, arrival in zip(reqs, (10.0, 11.0, 12.5)):
        r.arrival = arrival
    log.reqs = [driver.ReqRec(r, 2) for r in reqs]
    log.reqs.append(driver.ReqRec(_Req(9.5, []), 1))    # warm-up: left out
    assert driver.window_ttft(log) == pytest.approx([0.4, math.inf, 0.5])
