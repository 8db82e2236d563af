"""The open-loop schedule (due times from the seed) and the lateness and latency
arithmetic of the readers."""

import numpy as np
import pytest

from benchmark.harness import readers
from benchmark.harness.loadgen import REQUEST_TIMEOUT_S, LoadResult, schedule


def _schedule(seed, rate=200.0, seconds=5.0, plan=99):
    return schedule(rate, seconds, np.random.default_rng(plan),
                    np.random.default_rng(seed))


def test_same_seed_same_schedule_and_rate_holds():
    a, b = _schedule(3), _schedule(3)
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) > 0) and a[-1] < 5.0
    assert len(a) == pytest.approx(1000, rel=0.1)


def test_every_seed_offers_the_same_gaps_in_another_order():
    plan = np.random.default_rng(99).exponential(1 / 200.0, int(200 * 5 * 1.5) + 64)
    a, c = _schedule(3), _schedule(4)
    assert not np.array_equal(a[:50], c[:50])
    for due in (a, c):
        gaps = np.diff(np.concatenate([[0.0], due]))
        # each gap of the schedule is one of the plan's gaps
        assert np.all(np.isin(np.round(gaps, 12), np.round(plan, 12)))


def _obs(rows):
    res = LoadResult(seconds=10.0)
    for due, sent, done, ok in rows:
        res.due.append(due)
        res.sent.append(sent)
        res.done.append(done)
        res.query.append(0)
        res.ok.append(ok)
        res.answer.append(None)
    obs = readers.Observations("idx")
    obs.window = res
    return obs


def test_latency_counts_from_due_and_lateness_from_due_to_sent():
    obs = _obs([(1.0, 1.0, 1.010, True), (2.0, 2.5, 2.520, True)])
    assert readers.read({"reader": "latency_percentile", "q": 100, "from": "due"},
                        obs) == pytest.approx(520.0)
    assert readers.read({"reader": "latency_percentile", "q": 100, "from": "sent"},
                        obs) == pytest.approx(20.0)
    assert readers.read({"reader": "lateness_percentile", "q": 100},
                        obs) == pytest.approx(500.0)


def test_a_failed_search_misses_any_limit_and_is_not_completed():
    obs = _obs([(1.0, 1.0, 1.010, True), (2.0, 2.0, 2.001, False),
                (9.99, 9.99, 10.5, True)])
    assert readers.read({"reader": "latency_percentile", "q": 100}, obs) == \
        pytest.approx(REQUEST_TIMEOUT_S * 1000.0)
    # one answered whole inside the window of 10 s; one failed; one finished after it
    assert readers.read({"reader": "completed_per_second"}, obs) == pytest.approx(0.1)


def test_span_median_takes_nested_spans_once():
    obs = _obs([(0, 0, 1, True)])
    obs.window.spans = [(0.0, 1.0, [("rest", 10.0, 10.100), ("batcher.queue", 10.010, 10.020),
                                    ("batcher.merge", 10.050, 10.080),
                                    ("device_pull", 10.055, 10.075)])]
    d = {"reader": "span_median", "span": "rest",
         "minus": ["batcher.queue", "batcher.merge", "device_pull"]}
    assert readers.read(d, obs) == pytest.approx(100.0 - 10.0 - 30.0)
    assert readers.read({"reader": "span_median", "span": "device_pull"},
                        obs) == pytest.approx(20.0)
    assert readers.read({"reader": "span_median", "span": "absent"}, obs) is None


def test_counters_read_as_deltas_and_missing_ones_give_nothing():
    obs = readers.Observations("idx")
    obs.stats_before = {"a": {"launches": 10, "coalesced": 30}, "d": {"idx": {"ms": 1500.0}}}
    obs.stats_after = {"a": {"launches": 20, "coalesced": 80}, "d": {"idx": {"ms": 1500.0}}}
    ratio = {"reader": "counter_ratio", "numerator": ["a.coalesced"],
             "denominator": ["a.launches"]}
    assert readers.read(ratio, obs) == pytest.approx(5.0)
    assert readers.read({"reader": "counter_delta", "path": "a.launches"}, obs) == 10
    assert readers.read({"reader": "counter_value", "path": "d.{index}.ms",
                         "scale": 0.001}, obs) == pytest.approx(1.5)
    assert readers.read({"reader": "counter_delta", "path": "a.absent"}, obs) is None
