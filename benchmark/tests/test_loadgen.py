"""The open-loop schedule (due times from the seed), the lateness and latency
arithmetic of the readers, and the request lines the generator sends."""

import http.server
import json
import threading

import numpy as np
import pytest

from benchmark.harness import readers, registry
from benchmark.harness.cell import search_path
from benchmark.harness.loadgen import REQUEST_TIMEOUT_S, LoadResult, run_load, schedule


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


def test_two_shares_of_one_sum_keep_its_list_in_one_file():
    """`mesh_served_share` names `device_served_share` where its denominator would
    stand: a new outcome of `search_serving` is added in that one file, and the two
    shares go on summing to what the host left."""
    served = registry.layer_metric("device_served_share")
    mesh = registry.layer_metric("mesh_served_share")
    assert mesh["denominator"] == "device_served_share"
    assert set(mesh["numerator"]) <= set(served["denominator"])
    obs = readers.Observations("idx")
    paths = [p.split(".", 1)[1] for p in served["denominator"]]
    obs.stats_before = {"search_serving": {k: 0 for k in paths}}
    obs.stats_after = {"search_serving": {k: 1 for k in paths}}
    obs.stats_after["search_serving"]["mesh_spmd"] = 1 + len(paths)
    both = readers.read(mesh, obs) + readers.read(served, obs)
    assert both == pytest.approx(100.0 * (2 * len(paths) - 1) / (2 * len(paths)))
    assert readers.read(mesh, obs) == pytest.approx(100.0 * (len(paths) + 1)
                                                    / (2 * len(paths)))


ANSWER = json.dumps({"_shards": {"total": 1, "successful": 1, "failed": 0},
                     "timed_out": False, "hits": {"total": 0, "hits": []}}).encode()


@pytest.fixture()
def request_lines():
    """A server that answers every search with no hit and keeps the request lines."""
    lines = []

    class Handler(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            lines.append(self.requestline)
            self.send_response(200)
            self.send_header("Content-Length", str(len(ANSWER)))
            self.end_headers()
            self.wfile.write(ANSWER)

        def log_message(self, *args):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address[1], lines
    server.shutdown()
    server.server_close()
    thread.join()


@pytest.mark.parametrize("config, plain, sampled", [
    ({}, "/bench/_search", "/bench/_search?trace=true"),
    ({"search": {"params": {"search_type": "dfs_query_then_fetch"}}},
     "/bench/_search?search_type=dfs_query_then_fetch",
     "/bench/_search?search_type=dfs_query_then_fetch&trace=true")])
def test_the_request_line_with_and_without_url_parameters(request_lines, config, plain,
                                                          sampled):
    """Without a `search` block the request line is the one of before the block
    existed, byte for byte; a sampled search joins `trace=true` to what is there."""
    port, lines = request_lines
    path = search_path("bench", config)
    assert path == plain
    res = run_load(port, path, [b"{}"], np.array([0]), 5.0, 1, None, trace_every=3,
                   count=6)
    assert len(res.done) == 6 and all(res.ok)
    assert lines == [f"POST {sampled if i % 3 == 0 else plain} HTTP/1.1"
                     for i in range(6)]
