"""End-to-end search tracing + node telemetry (common/tracing.py, PR 8).

Covers: HistogramMetric units (log-spaced buckets, stripes, percentiles,
Prometheus cumulative view), tracer/span units (sampling, ring bound, wire
context through the binary codec, in-flight tasks), the live-cluster
acceptance path — `_search?trace=true` through the batcher yields a
rest → coordinator → shard → batcher{queue,dispatch,merge} → device-pull
span tree with the batch's device span attributed to every coalesced member
and child durations summing to ≤ each parent — plus `/_nodes/stats/{metric}`
filtering, the Prometheus exposition (parsed with a minimal text-format
parser), the slowlog trace join, the zero-new-syncs sanitizer invariant
(warmed traced loop = 0 recompiles under transfer_guard("disallow")), and a
tpulint-clean scan over every instrumented file."""

import json
import logging
import threading
import time

import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.metrics import HistogramMetric
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.common.stream import StreamInput, StreamOutput
from elasticsearch_tpu.common.tracing import (
    NOOP_SPAN,
    TraceContext,
    Tracer,
    phase_breakdown,
    span_tree,
)
from elasticsearch_tpu.rest.controller import RestRequest, build_rest_controller

from .harness import TestCluster

WORDS = ["quick", "brown", "fox", "lazy", "dog", "summer", "red", "bear"]


# ---------------------------------------------------------------------------
# HistogramMetric
# ---------------------------------------------------------------------------


class TestHistogramMetric:
    def test_bucketing_and_percentiles(self):
        h = HistogramMetric()
        for _ in range(90):
            h.observe(0.001)  # 1ms
        for _ in range(10):
            h.observe(0.1)  # 100ms
        assert h.count == 100
        assert abs(h.sum - (90 * 0.001 + 10 * 0.1)) < 1e-9
        p50 = h.percentile(0.50)
        p99 = h.percentile(0.99)
        # p50 lands in the ~1ms bucket, p99 in the ~100ms bucket; log-spaced
        # buckets bound the relative error by the bucket ratio (2x)
        assert 0.0004 < p50 < 0.004, p50
        assert 0.04 < p99 < 0.3, p99
        assert p50 <= h.percentile(0.95) <= p99

    def test_empty_and_overflow(self):
        h = HistogramMetric()
        assert h.percentile(0.99) == 0.0
        assert h.stats()["count"] == 0
        h.observe(10_000.0)  # beyond the last bound -> overflow bucket
        buckets, total, _ = h.cumulative()
        assert total == 1
        assert buckets[-1] == (float("inf"), 1)
        assert buckets[-2][1] == 0  # nothing below the last finite bound

    def test_concurrent_observes_lose_nothing(self):
        h = HistogramMetric()

        def worker(seed):
            for i in range(500):
                h.observe(0.0001 * ((seed + i) % 7 + 1))

        threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == 8 * 500

    def test_cumulative_monotone(self):
        h = HistogramMetric()
        for v in (0.0002, 0.003, 0.04, 0.5, 6.0):
            h.observe(v)
        buckets, total, _ = h.cumulative()
        cums = [c for (_b, c) in buckets]
        assert cums == sorted(cums)
        assert cums[-1] == total == 5

    def test_stats_shape(self):
        h = HistogramMetric()
        h.observe(0.01)
        st = h.stats()
        assert set(st) == {"count", "mean_ms", "p50_ms", "p95_ms", "p99_ms"}
        assert st["count"] == 1 and st["mean_ms"] > 0


# ---------------------------------------------------------------------------
# tracer / span units
# ---------------------------------------------------------------------------


def _tracer(rate="0", ring=None):
    flat = {"search.trace.sample_rate": rate}
    if ring is not None:
        flat["search.trace.ring_size"] = str(ring)
    t = Tracer(Settings.from_flat(flat), node_name="test")
    # the unit tests pin explicit rates — neutralize the CI leg's ESTPU_TRACE
    # override so sampled/unsampled behavior is deterministic here
    t.sample_rate = float(rate)
    return t


class TestTracerUnits:
    def test_unsampled_is_noop(self):
        tr = _tracer("0")
        trace = tr.start_trace("rest")
        assert not trace
        assert trace.root is NOOP_SPAN
        assert trace.span("x") is NOOP_SPAN
        trace.root.end()
        assert tr.traces() == []
        # activating a noop span keeps tracing off for the scope (falsy
        # current span) but MARKS the sampling decision as made: a
        # downstream layer (the coordinator under REST ingress) must see the
        # noop — not None — so it does not roll the sampling dice again
        with tracing.activate(trace.root):
            cur = tracing.current_span()
            assert cur is NOOP_SPAN and not cur
            assert cur.child("coordinator") is NOOP_SPAN
        assert tracing.current_span() is None

    def test_rest_decline_suppresses_coordinator_roll(self, monkeypatch):
        # the double-roll bug: REST ingress loses its sampling roll, the
        # coordinator cannot tell "decided unsampled" from "no decision" and
        # rolls AGAIN — inflating the effective rate (1-(1-r)^2) and rooting
        # the extra traces at "coordinator" with no rest span. The first
        # roll fails (0.99 >= rate), a second roll WOULD succeed (0.0)
        rolls = iter([0.99, 0.0, 0.0])
        monkeypatch.setattr(tracing.random, "random", lambda: next(rolls))
        tr = _tracer("0")
        tr.sample_rate = 0.5
        trace = tr.start_trace("rest")  # roll 1: declined
        assert not trace
        with tracing.activate(trace.root):
            # actions.search's exact pattern: a present (noop) parent means
            # the decision is made — child, never start_trace
            parent = tracing.current_span()
            assert parent is not None
            span = parent.child("coordinator")
            assert span is NOOP_SPAN
        assert tr.stats()["sampled"] == 0
        assert next(rolls) == 0.0  # the second roll was never consumed

    def test_late_span_close_refreshes_ring(self):
        # a timed-out shard attempt's transport span ends only when the late
        # response (or transport error / in-flight backstop) resolves its
        # future — possibly AFTER the root closed. The close must refresh
        # the ring snapshot like a late add_remote does
        tr = _tracer("0")
        trace = tr.start_trace("rest", force=True)
        child = trace.root.child("transport[q]")
        trace.root.end()
        assert {s["name"] for s in tr.traces()[0]["spans"]} == {"rest"}
        child.end()
        assert {s["name"] for s in tr.traces()[0]["spans"]} == \
            {"rest", "transport[q]"}

    def test_late_remote_stitch_refreshes_ring(self):
        # a shard chain the coordinator backstop abandoned resolves AFTER
        # the root span ended: add_remote must refresh the ring snapshot so
        # the stitched spans still reach /_traces (and only grow it)
        tr = _tracer("0", ring=4)
        trace = tr.start_trace("rest", force=True)
        root_id = trace.root.span_id
        trace.root.end()
        assert len(tr.traces()[0]["spans"]) == 1
        trace.add_remote([{"id": 99, "parent": root_id, "name": "shard",
                           "t0": 0.0, "t1": 0.5, "duration_ms": 500.0,
                           "tags": {}}])
        (snap,) = tr.traces()
        assert {s["name"] for s in snap["spans"]} == {"rest", "shard"}
        assert tr.stats()["finished"] == 1  # refreshed in place, not re-added
        # an entry the bounded ring already evicted stays evicted
        for _ in range(4):
            t2 = tr.start_trace("rest", force=True)
            t2.root.end()
        trace.add_remote([{"id": 100, "parent": root_id, "name": "late",
                           "t0": 0.0, "t1": 0.1, "duration_ms": 100.0,
                           "tags": {}}])
        assert all(s["trace_id"] != trace.trace_id for s in tr.traces())

    def test_forced_trace_records_and_rings(self):
        tr = _tracer("0", ring=4)
        ids = []
        for _ in range(7):
            trace = tr.start_trace("rest", force=True)
            with trace.root.child("coordinator"):
                pass
            trace.root.end()
            ids.append(trace.trace_id)
        got = tr.traces()
        assert len(got) == 4  # bounded ring keeps the newest
        assert [t["trace_id"] for t in got] == ids[-1:-5:-1]  # newest first
        names = {s["name"] for s in got[0]["spans"]}
        assert names == {"rest", "coordinator"}

    def test_tasks_shows_in_flight(self):
        tr = _tracer("0")
        trace = tr.start_trace("rest", force=True)
        child = trace.root.child("coordinator")
        tasks = tr.tasks()
        assert len(tasks) == 1
        assert tasks[0]["trace_id"] == trace.trace_id
        assert tasks[0]["current_span"] == "coordinator"
        assert tasks[0]["cancellable"] is False
        assert tasks[0]["running_time_ms"] >= 0
        child.end()
        trace.root.end()
        assert tr.tasks() == []
        assert tr.stats()["in_flight"] == 0

    def test_wire_context_roundtrips_binary_codec(self):
        ctx = TraceContext("abcd1234abcd1234", 1234567890123)
        out = StreamOutput()
        out.write_value({"body": {"q": 1}, "_trace": ctx})
        back = StreamInput(out.bytes()).read_value()
        assert back["_trace"] == ctx
        assert back["body"] == {"q": 1}

    def test_continue_trace_stitches_parent(self):
        tr = _tracer("0")
        root_trace = tr.start_trace("rest", force=True)
        wire = tr.wire_context(root_trace.root)
        shard_trace = tr.continue_trace(wire, "shard")
        assert shard_trace.trace_id == root_trace.trace_id
        assert shard_trace.root.parent_id == root_trace.root.span_id
        shard_trace.root.end()
        root_trace.add_remote(shard_trace.span_dicts())
        root_trace.root.end()
        tree = span_tree(root_trace.span_dicts())
        assert tree["name"] == "rest"
        assert [c["name"] for c in tree["children"]] == ["shard"]
        # continuing nothing is a noop trace
        assert not tr.continue_trace(None, "shard")

    def test_record_explicit_times_and_phase_breakdown(self):
        tr = _tracer("0")
        trace = tr.start_trace("shard", force=True)
        t0 = time.monotonic()
        q = trace.root.record("batcher.queue", t0, t0 + 0.010)
        m = trace.root.record("batcher.merge", t0 + 0.012, t0 + 0.030)
        m.record("device_pull", t0 + 0.012, t0 + 0.020)
        assert q.t1 - q.t0 == pytest.approx(0.010)
        trace.root.end()
        phases = phase_breakdown(trace)
        assert phases["queue_ms"] == pytest.approx(10.0, abs=0.1)
        assert phases["device_ms"] == pytest.approx(8.0, abs=0.1)
        # merge phase is the host-side remainder (merge minus the pull)
        assert phases["merge_ms"] == pytest.approx(10.0, abs=0.1)
        # an unsampled request reads zeros + joins on "-"
        assert phase_breakdown(None) == {"queue_ms": 0.0, "device_ms": 0.0,
                                         "merge_ms": 0.0}


# ---------------------------------------------------------------------------
# live cluster: the ?trace=true contract through the batcher
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def live(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tracing")
    with TestCluster(n_nodes=1, data_root=tmp, seed=3, settings={
        # a visible linger window so two concurrent requests coalesce
        "search.batch.linger_ms": "40",
        "search.batch.max_batch": "8",
        # `traced2` goes shard by shard over the transport, as an index
        # spread over nodes does: query phase, reduce, fetch phase
        "search.mesh.enabled": "false",
    }) as cluster:
        node = next(iter(cluster.nodes.values()))
        client = node.client()
        # one shard: a search makes one trip. Two: a fetch phase follows
        for index, shards in (("traced", 1), ("traced2", 2)):
            client.create_index(index, {"settings": {
                "number_of_shards": shards, "number_of_replicas": 0}})
            cluster.ensure_green(index)
            for i in range(40):
                client.index(index, "doc",
                             {"body": f"{WORDS[i % 8]} {WORDS[(i + 1) % 8]}"},
                             id=str(i))
            client.refresh(index)
        rc = build_rest_controller(node)
        # warm occupancy-1 and occupancy-2 executables so traced passes below
        # measure bookkeeping, not XLA compiles
        _concurrent_searches(rc, 2, trace=False)
        yield cluster, node, rc


SEARCH_BODY = {"query": {"match": {"body": "quick brown"}}, "size": 5}


def _concurrent_searches(rc, n, trace=True, index="traced"):
    barrier = threading.Barrier(n)
    out = [None] * n

    def worker(i):
        barrier.wait()
        params = {"trace": "true"} if trace else {}
        out[i] = rc.dispatch(RestRequest(
            method="POST", path=f"/{index}/_search", params=params,
            body=dict(SEARCH_BODY)))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    return out


def _flatten(node, out=None):
    out = [] if out is None else out
    out.append(node)
    for c in node["children"]:
        _flatten(c, out)
    return out


def _find(node, name):
    return [n for n in _flatten(node) if n["name"] == name]


class TestLiveTraceTree:
    def test_trace_true_span_tree_through_batcher(self, live):
        _cluster, node, rc = live
        # retry the race: two requests must land in the SAME linger window for
        # coalesced attribution; each attempt is two fresh traced searches
        coalesced = None
        for _attempt in range(8):
            results = _concurrent_searches(rc, 2)
            assert all(r.status == 200 for r in results), \
                [r.body for r in results]
            trees = [r.body["trace"]["tree"] for r in results]
            dispatches = [
                _find(t, "batcher.dispatch") for t in trees]
            if all(len(d) == 1 for d in dispatches):
                tags = [d[0]["tags"] for d in dispatches]
                if (tags[0].get("occupancy", 0) >= 2
                        and tags[0].get("batch") == tags[1].get("batch")):
                    coalesced = (results, trees, tags)
                    break
        assert coalesced is not None, "requests never coalesced in 8 attempts"
        results, trees, tags = coalesced
        for resp, tree in zip(results, trees):
            # the acceptance chain: rest → coordinator → shard →
            # batcher{queue,dispatch,merge} → device_pull
            assert tree["name"] == "rest"
            names = {n["name"] for n in _flatten(tree)}
            assert {"rest", "coordinator", "shard", "batcher.queue",
                    "batcher.dispatch", "batcher.merge",
                    "device_pull"} <= names, names
            (coord,) = _find(tree, "coordinator")
            (shard,) = _find(tree, "shard")
            # the shard span nests under the coordinator's query phase with
            # no transport span between: the one shard's only copy is on the
            # coordinator's node, so its query phase ran on the asking thread
            (query,) = _find(coord, "coordinator.query")
            assert shard["id"] in {c["id"] for c in query["children"]}
            assert not any(n["name"].startswith("transport[")
                           for n in _flatten(tree))
            batcher_names = {c["name"] for c in shard["children"]}
            assert {"batcher.queue", "batcher.dispatch",
                    "batcher.merge"} <= batcher_names
            # the queue span says why its batch was taken, one of the flush
            # policy's reasons (search/batcher.py: full, alone, linger,
            # deadline, pending); a pair that coalesced did not go `alone`
            (queue,) = _find(shard, "batcher.queue")
            assert queue["tags"]["reason"] in {"full", "linger", "deadline",
                                               "pending"}
            (merge,) = _find(shard, "batcher.merge")
            assert [c["name"] for c in merge["children"]] == ["device_pull"]
            # every coalesced member carries the shared batch's device span
            (pull,) = _find(tree, "device_pull")
            assert pull["tags"]["batch"] == tags[0]["batch"]
            assert pull["duration_ms"] >= 0
            # child durations sum to ≤ the parent, all the way down
            self._assert_child_sums(tree)
            # the response trace id is findable in the node's /_traces ring
            tid = resp.body["trace"]["trace_id"]
            ring_ids = {t["trace_id"] for t in node.tracer.traces()}
            assert tid in ring_ids

    def _assert_child_sums(self, n):
        child_sum = sum(c["duration_ms"] for c in n["children"])
        assert child_sum <= n["duration_ms"] + 1.0, \
            (n["name"], child_sum, n["duration_ms"])
        for c in n["children"]:
            self._assert_child_sums(c)

    def test_scrolled_search_honors_trace_param(self, live):
        # the scroll branch returns early from the REST handler — it must
        # still root the trace: the initial scan/scroll search is a normal
        # fan-out and ?trace=true promises an inline tree
        _cluster, node, rc = live
        resp = rc.dispatch(RestRequest(
            method="POST", path="/traced/_search",
            params={"scroll": "1m", "trace": "true"},
            body=dict(SEARCH_BODY)))
        assert resp.status == 200
        assert "_scroll_id" in resp.body
        tree = resp.body["trace"]["tree"]
        assert tree["name"] == "rest"
        names = {n["name"] for n in _flatten(tree)}
        assert {"rest", "coordinator", "shard"} <= names, names
        ring_ids = {t["trace_id"] for t in node.tracer.traces()}
        assert resp.body["trace"]["trace_id"] in ring_ids

    def test_untraced_response_has_no_trace_section(self, live):
        _cluster, _node, rc = live
        (resp,) = _concurrent_searches(rc, 1, trace=False)
        assert resp.status == 200
        assert "trace" not in resp.body

    def test_traces_and_tasks_endpoints(self, live):
        _cluster, node, rc = live
        r = rc.dispatch(RestRequest(method="GET", path="/_traces", params={}))
        assert r.status == 200
        assert r.body["total"] == len(r.body["traces"])
        assert r.body["tracing"]["ring_size"] >= r.body["total"]
        for entry in r.body["traces"]:
            assert {"trace_id", "node", "name", "duration_ms",
                    "spans"} <= set(entry)
        t = rc.dispatch(RestRequest(method="GET", path="/_tasks", params={}))
        assert t.status == 200
        (node_entry,) = t.body["nodes"].values()
        assert isinstance(node_entry["tasks"], list)

    def test_slowlog_line_joins_the_trace(self, live):
        _cluster, node, rc = live
        client = node.client()
        client.update_settings("traced", {
            "index.search.slowlog.threshold.query.warn": "0ms"})
        records = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        handler = _Capture()
        logging.getLogger("estpu.action").addHandler(handler)
        try:
            (resp,) = _concurrent_searches(rc, 1)
        finally:
            logging.getLogger("estpu.action").removeHandler(handler)
            client.update_settings("traced", {
                "index.search.slowlog.threshold.query.warn": "-1"})
        assert resp.status == 200
        tid = resp.body["trace"]["trace_id"]
        slow = [m for m in records if "slowlog" in m]
        assert slow, records
        joined = [m for m in slow if f"trace[{tid}]" in m]
        assert joined, slow
        # the per-phase breakdown is on the line (joinable to /_traces)
        assert "queue[" in joined[0] and "device[" in joined[0] \
            and "merge[" in joined[0]


# ---------------------------------------------------------------------------
# /_nodes/stats/{metric} + Prometheus exposition
# ---------------------------------------------------------------------------


def _parse_prometheus(text):
    """Minimal text-format parser: {series_key: value}, {family: type}."""
    types, series = {}, {}
    for line in text.splitlines():
        if not line or line.startswith("# HELP"):
            continue
        if line.startswith("# TYPE"):
            _h, _t, name, typ = line.split()
            types[name] = typ
            continue
        key, val = line.rsplit(" ", 1)
        series[key] = float(val)
    return types, series


class TestStatsSurfaces:
    def test_nodes_stats_metric_filtering(self, live):
        _cluster, node, rc = live
        r = rc.dispatch(RestRequest(
            method="GET", path="/_nodes/stats/thread_pool,breakers", params={}))
        assert r.status == 200
        (sections,) = r.body["nodes"].values()
        assert sorted(sections) == ["breakers", "thread_pool"]
        # every section in the unfiltered response is addressable by name
        full = rc.dispatch(RestRequest(method="GET", path="/_nodes/stats",
                                       params={}))
        (all_sections,) = full.body["nodes"].values()
        for metric in all_sections:
            one = rc.dispatch(RestRequest(
                method="GET", path=f"/_nodes/stats/{metric}", params={}))
            assert one.status == 200, metric
            (s,) = one.body["nodes"].values()
            assert list(s) == [metric]

    def test_unknown_metric_is_400(self, live):
        _cluster, _node, rc = live
        r = rc.dispatch(RestRequest(method="GET", path="/_nodes/stats/bogus",
                                    params={}))
        assert r.status == 400
        assert "bogus" in json.dumps(r.body)

    def test_stats_carry_histogram_percentiles(self, live):
        _cluster, node, _rc = live
        stats = node.client().nodes_stats()["nodes"][node.node_id]
        lat = stats["search"]["latency"]
        assert lat["count"] >= 1
        assert lat["p99_ms"] >= lat["p50_ms"] >= 0
        assert "queue_wait" in stats["thread_pool"]["search"]
        assert "shard_phase" in stats["admission_control"]
        assert "batch" in stats["search"]["batcher"]
        assert stats["tracing"]["ring_size"] >= 1

    def test_prometheus_exposition_parses(self, live):
        _cluster, node, rc = live
        r = rc.dispatch(RestRequest(method="GET", path="/_prometheus/metrics",
                                    params={}))
        assert r.status == 200 and r.content_type.startswith("text/plain")
        types, series = _parse_prometheus(r.body)
        # the required families: breakers, pools, batcher, compile events,
        # search-latency histogram (+ HBM gauge)
        assert types["estpu_breaker_estimated_bytes"] == "gauge"
        assert types["estpu_threadpool_queue_wait_seconds"] == "histogram"
        assert types["estpu_batcher_launches_total"] == "counter"
        assert types["estpu_jax_compile_events_total"] == "counter"
        assert types["estpu_search_latency_seconds"] == "histogram"
        assert types["estpu_hbm_resident_bytes"] == "gauge"
        assert types["estpu_admission_shard_phase_seconds"] == "histogram"
        # adaptive routing + hedging families (PR 10) — per-copy rank gauges
        # carry a copy="node/index/shard" label per observed copy, and the
        # hedge counters are always present; family contiguity for all of
        # them is pinned by the grouping walk below
        assert types["estpu_search_hedges_issued_total"] == "counter"
        assert types["estpu_search_hedges_won_total"] == "counter"
        assert types["estpu_search_hedges_budget_exhausted_total"] == "counter"
        assert types["estpu_search_hedges_budget_tokens"] == "gauge"
        assert types["estpu_routing_probes_total"] == "counter"
        assert types["estpu_routing_quarantined"] == "gauge"
        assert types["estpu_routing_rank_ewma_seconds"] == "gauge"
        assert any(k.startswith('estpu_routing_rank_ewma_seconds{copy="')
                   for k in series), sorted(series)[:5]
        assert series['estpu_breaker_estimated_bytes{breaker="request"}'] == 0
        # histogram contract: +Inf bucket equals _count
        count = series["estpu_search_latency_seconds_count"]
        assert count >= 1
        assert series['estpu_search_latency_seconds_bucket{le="+Inf"}'] == count
        # packed device postings are resident after the searches above
        assert series["estpu_hbm_resident_bytes"] > 0
        launches = series["estpu_batcher_launches_total"]
        assert launches >= 1
        # exposition grouping: every family's samples must be CONTIGUOUS —
        # interleaved families (pool A's gauges, pool B's gauges re-opening
        # the first family) pass the classic scraper but are rejected whole
        # by promtool / OpenMetrics-strict ingesters
        seen, current = set(), None
        for line in r.body.splitlines():
            if not line or line.startswith("#"):
                continue
            name = line.split("{", 1)[0].split(" ", 1)[0]
            for suffix in ("_bucket", "_sum", "_count"):
                base = name[:-len(suffix)]
                if name.endswith(suffix) and f"# TYPE {base} histogram" in r.body:
                    name = base
                    break
            if name != current:
                assert name not in seen, f"family {name} interleaved"
                seen.add(name)
                current = name


# ---------------------------------------------------------------------------
# sanitizer: tracing adds zero device syncs / zero recompiles
# ---------------------------------------------------------------------------


class TestTracedSanitized:
    def test_warmed_traced_loop_zero_recompiles(self, tmp_path):
        """The serving invariant, with tracing fully armed: a warmed traced
        concurrent loop through the batcher performs no implicit transfers
        (hard transfer_guard) and 0 backend compiles — span end-times ride
        the batch's existing pull, so arming tracing adds NO device work."""
        import jax

        from elasticsearch_tpu.common.jaxenv import sanitize
        from elasticsearch_tpu.index import Engine
        from elasticsearch_tpu.mapper import MapperService
        from elasticsearch_tpu.search import ShardContext, parse_query
        from elasticsearch_tpu.search.batcher import DeviceBatcher
        from elasticsearch_tpu.search.execute import lower_flat
        from elasticsearch_tpu.search.similarity import SimilarityService

        settings = Settings.from_flat({})
        svc = MapperService(settings)
        e = Engine(str(tmp_path / "shard0"), svc)
        for i in range(50):
            e.index("doc", str(i),
                    {"body": f"{WORDS[i % 8]} {WORDS[(i + 2) % 8]}"})
        e.refresh()
        ctx = ShardContext(e.acquire_searcher(), svc,
                           SimilarityService(settings, mapper_service=svc))
        batcher = DeviceBatcher(Settings.from_flat(
            {"search.batch.linger_ms": "25", "search.batch.max_batch": "8"}))
        tracer = _tracer("0")
        texts = ["quick brown", "lazy dog", "red bear", "fox dog"]
        plans = [lower_flat(parse_query({"match": {"body": t}}), ctx)
                 for t in texts]

        def traced_round():
            out = [None] * len(plans)
            errs = [None] * len(plans)

            def worker(i):
                trace = tracer.start_trace("search", force=True)
                try:
                    with tracing.activate(trace.root):
                        out[i] = batcher.execute(plans[i], ctx, 10)
                except Exception as err:  # noqa: BLE001 — assert below
                    errs[i] = err
                finally:
                    trace.root.end()

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(plans))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            assert all(e2 is None for e2 in errs), errs
            return out

        try:
            warm = traced_round()
            jax.config.update("jax_transfer_guard", "disallow")
            try:
                with sanitize(max_compiles=0, transfers="disallow") as rep:
                    again = traced_round()
            finally:
                jax.config.update("jax_transfer_guard", "allow")
            assert rep.compiles == 0, rep.compile_events
            for w, a in zip(warm, again):
                assert a.hits == w.hits and a.total == w.total
            # every traced request got the batcher spans + the device pull
            for entry in tracer.traces()[:4]:
                names = {s["name"] for s in entry["spans"]}
                assert {"batcher.queue", "batcher.dispatch",
                        "batcher.merge", "device_pull"} <= names, names
        finally:
            batcher.shutdown()


# ---------------------------------------------------------------------------
# the launch timeline (PR 24): host-phase spans, dispatch stage/launch, the
# synchronous families' pull, and the counters beside them
# ---------------------------------------------------------------------------

FILTERED_BODY = {"query": {"filtered": {
    "query": {"match": {"body": "quick brown"}},
    "filter": {"term": {"body": "fox"}}}}, "size": 5}


def _traced_search(rc, body, arrived_ago: float = 0.0, index: str = "traced"):
    resp = rc.dispatch(RestRequest(
        method="POST", path=f"/{index}/_search", params={"trace": "true"},
        body=dict(body), t_arrival=time.monotonic() - arrived_ago))
    assert resp.status == 200, resp.body
    return resp.body["trace"]["tree"]


def _assert_nested(n, in_turn: bool = True):
    """Every span lies inside its parent, and children sum to no more (where
    they run in turn: the shards of a search of several run side by side)."""
    for c in n["children"]:
        assert c["t0"] >= n["t0"] - 1e-6 and c["t1"] <= n["t1"] + 1e-6, \
            (n["name"], c["name"])
        _assert_nested(c, in_turn)
    total = sum(c["duration_ms"] for c in n["children"])
    assert not in_turn or total <= n["duration_ms"] + 1.0, \
        (n["name"], total, n["duration_ms"])


class TestLaunchTimeline:
    def test_tree_holds_the_host_phase_spans(self, live):
        _cluster, _node, rc = live
        tree = _traced_search(rc, SEARCH_BODY, arrived_ago=0.002)
        names = {n["name"] for n in _flatten(tree)}
        assert {"rest.parse", "coordinator.plan", "coordinator.query",
                "coordinator.reduce", "coordinator.fetch",
                "coordinator.render", "shard.lower", "dispatch.stage",
                "dispatch.launch"} <= names, names
        _assert_nested(tree)
        # the root starts where the HTTP layer stamped the arrival, and
        # rest.parse is what ran before the handler could open a span
        (parse,) = _find(tree, "rest.parse")
        assert parse["t0"] == tree["t0"] and parse["duration_ms"] >= 2.0
        (coord,) = _find(tree, "coordinator")
        assert [c["name"] for c in coord["children"]] == [
            "coordinator.plan", "coordinator.query", "coordinator.reduce",
            "coordinator.fetch", "coordinator.render"]
        # the one shard's query phase ran on the asking thread, inside a slot
        # of the `search` pool, and built the page's hits, so the fetch has
        # no child; the round-trips of an index of several shards nest under
        # their phase (TestHostHandovers, `traced2`)
        (query,) = _find(tree, "coordinator.query")
        (fetch,) = _find(tree, "coordinator.fetch")
        assert [c["name"] for c in query["children"]] == ["pool.wait", "shard"]
        assert fetch["children"] == []
        (shard,) = _find(tree, "shard")
        assert shard["children"][0]["name"] == "shard.lower"
        (dispatch,) = _find(tree, "batcher.dispatch")
        kinds = [c["name"] for c in dispatch["children"]]
        assert kinds and set(kinds) == {"dispatch.stage", "dispatch.launch"}
        assert kinds[0] == "dispatch.stage"  # staging precedes the first call

    def test_filtered_search_pulls_under_its_dispatch(self, live):
        """The dense filtered family pulls inside batcher.dispatch: its
        device_pull is recorded there, with the stage and launch beside it."""
        _cluster, _node, rc = live
        _traced_search(rc, FILTERED_BODY)  # first sighting compiles
        tree = _traced_search(rc, FILTERED_BODY)
        _assert_nested(tree)
        (dispatch,) = _find(tree, "batcher.dispatch")
        kinds = [c["name"] for c in dispatch["children"]]
        assert kinds[:3] == ["dispatch.stage", "dispatch.launch", "device_pull"]
        (merge,) = _find(tree, "batcher.merge")
        assert merge["children"] == []

    @pytest.mark.parametrize("extra", [
        {"aggs": {"by_n": {"histogram": {"field": "n", "interval": 5}}}},
        {"sort": [{"n": "desc"}]}], ids=["aggs", "sorted"])
    def test_aggregated_and_sorted_searches_dispatch_under_the_batcher(
            self, live, extra):
        """A served aggregated or sorted search goes the one launch route:
        its stage, launch and pull are the drainer's, under batcher.dispatch;
        no dispatch.stage hangs under its shard span, and /_nodes/stats books
        the launch under its kind."""
        _cluster, node, rc = live
        self._numbered(_cluster, node)

        def search():
            resp = rc.dispatch(RestRequest(
                method="POST", path="/numbered/_search",
                params={"trace": "true"},
                body={"query": {"match": {"body": "quick brown"}}, "size": 5,
                      **extra}))
            assert resp.status == 200, resp.body
            return resp.body["trace"]["tree"]

        def batcher_stats():
            resp = rc.dispatch(RestRequest(
                method="GET", path="/_nodes/stats/search"))
            return next(iter(resp.body["nodes"].values()))["search"]["batcher"]

        search()  # first sighting compiles
        before = batcher_stats()
        tree = search()
        after = batcher_stats()
        _assert_nested(tree)
        (shard,) = _find(tree, "shard")
        assert [c["name"] for c in shard["children"]] == [
            "shard.lower", "batcher.queue", "batcher.dispatch", "batcher.hold",
            "batcher.merge", "thread.wake", "shard.fetch"]
        (dispatch,) = _find(tree, "batcher.dispatch")
        kinds = [c["name"] for c in dispatch["children"]]
        assert kinds == ["dispatch.stage", "dispatch.launch", "device_pull"]
        (merge,) = _find(tree, "batcher.merge")
        assert merge["children"] == []
        kind = "aggs" if "aggs" in extra else "sorted"
        for name in ("launches", "coalesced"):
            assert after["kinds"][kind][name] == before["kinds"][kind][name] + 1
            assert after[name] == before[name] + 1
        assert after["bypassed"] == before["bypassed"]

    def test_an_unscored_search_records_its_mask_and_its_counters(self, live):
        """A plan with no scoring clause under a filter at its second
        sighting (evaluated on the host once more, resident from then on):
        the mask's assembly is a part of the stage span, and /_nodes/stats
        books the plan, the row's bytes and (flat) the request cache's hits."""
        _cluster, _node, rc = live

        def serving():
            resp = rc.dispatch(RestRequest(
                method="GET", path="/_nodes/stats/search_serving"))
            return next(iter(resp.body["nodes"].values()))["search_serving"]

        body = {"query": {"filtered": {
            "query": {"match_all": {}},
            "filter": {"term": {"body": "lazy"}}}}, "size": 5}
        _traced_search(rc, body)  # first sighting compiles
        before = serving()
        tree = _traced_search(rc, body)
        after = serving()
        _assert_nested(tree)
        (mask,) = _find(tree, "shard.filter_mask")
        (stage,) = [n for n in _find(tree, "dispatch.stage")
                    if any(c["name"] == "shard.filter_mask"
                           for c in n["children"])]
        assert stage["t0"] <= mask["t0"] and mask["t1"] <= stage["t1"] + 1e-6
        assert after["launch"]["unscored_plans"] == \
            before["launch"]["unscored_plans"] + 1
        assert after["launch"]["mask_put_bytes"] > before["launch"]["mask_put_bytes"]
        assert after["launch"]["unscored_bytes"] > before["launch"]["unscored_bytes"]
        assert after["device_filtered"] == before["device_filtered"] + 1
        assert after["host"] == before["host"]
        assert after["request_cache_hits"] == before["request_cache_hits"]

    @pytest.mark.parametrize("function", [
        {"field_value_factor": {"field": "n", "factor": 2, "modifier": "log1p"}},
        {"script_score": {"script": "log(doc['n'].value + 2) * _score"}}],
        ids=["rows", "script"])
    def test_a_function_score_over_match_all_records_its_rows_and_its_counters(
            self, live, function):
        """function_score over a plan with no scoring clause: the host's
        evaluation of the launch's function rows (or script columns) is a
        part of the stage span, and /_nodes/stats books the rows' bytes, the
        unscored function_score launch and the batcher's kind."""
        _cluster, node, rc = live
        self._numbered(_cluster, node)

        def stats(section):
            resp = rc.dispatch(RestRequest(
                method="GET", path=f"/_nodes/stats/{section}"))
            return next(iter(resp.body["nodes"].values()))[section]

        def search():
            resp = rc.dispatch(RestRequest(
                method="POST", path="/numbered/_search", params={"trace": "true"},
                body={"query": {"function_score": {
                    "query": {"match_all": {}}, "functions": [function]}},
                    "size": 5}))
            assert resp.status == 200, resp.body
            return resp.body["trace"]["tree"]

        search()  # first sighting compiles
        before, kinds0 = stats("search_serving"), stats("search")["batcher"]["kinds"]
        tree = search()
        after, kinds1 = stats("search_serving"), stats("search")["batcher"]["kinds"]
        _assert_nested(tree)
        stages = [n for n in _find(tree, "dispatch.stage")
                  if any(c["name"] == "shard.fs_rows" for c in n["children"])]
        assert stages  # one a segment
        for stage in stages:
            (rows,) = [c for c in stage["children"] if c["name"] == "shard.fs_rows"]
            assert stage["t0"] <= rows["t0"] and rows["t1"] <= stage["t1"] + 1e-6
        launch0, launch1 = before["launch"], after["launch"]
        assert launch1["fs_row_put_bytes"] > launch0["fs_row_put_bytes"]
        assert launch1["launches_fs_unscored"] == \
            launch0["launches_fs_unscored"] + len(stages)
        assert launch1["launches_unscored"] == \
            launch0["launches_unscored"] + len(stages)
        assert launch1["unscored_bytes"] > launch0["unscored_bytes"]
        assert launch1["unscored_plans"] == launch0["unscored_plans"] + 1
        assert after["device_function_score"] == before["device_function_score"] + 1
        assert after["host"] == before["host"]
        for name in ("launches", "coalesced"):
            assert kinds1["function_score"][name] == \
                kinds0["function_score"][name] + 1

    def test_an_exact_phrase_records_its_plan_and_its_counters(self, live):
        """A `match_phrase` rides the batcher to the phrase program: the
        host's assembly of the launch's operands (each term's block slices,
        the operand plane, its one device_put) is a part of the stage span,
        and /_nodes/stats books the launch, the plan, the position blocks'
        bytes, the batcher's kind and the plane's resident bytes."""
        _cluster, _node, rc = live

        def stats(section):
            resp = rc.dispatch(RestRequest(
                method="GET", path=f"/_nodes/stats/{section}"))
            return next(iter(resp.body["nodes"].values()))[section]

        body = {"query": {"match_phrase": {"body": "quick brown"}}, "size": 5}
        _traced_search(rc, body)  # first sighting faults the plane in, compiles
        before, kinds0 = stats("search_serving"), stats("search")["batcher"]["kinds"]
        tree = _traced_search(rc, body)
        after, kinds1 = stats("search_serving"), stats("search")["batcher"]["kinds"]
        _assert_nested(tree)
        (plan,) = _find(tree, "shard.phrase_plan")
        (stage,) = [n for n in _find(tree, "dispatch.stage")
                    if any(c["name"] == "shard.phrase_plan"
                           for c in n["children"])]
        assert stage["t0"] <= plan["t0"] and plan["t1"] <= stage["t1"] + 1e-6
        (dispatch,) = _find(tree, "batcher.dispatch")
        assert [c["name"] for c in dispatch["children"]] == \
            ["dispatch.stage", "dispatch.launch", "device_pull"]
        launch0, launch1 = before["launch"], after["launch"]
        assert launch1["phrase"] == launch0["phrase"] + 1
        # two terms: the launch rode the line of two slots
        assert launch1["phrase_pair_launches"] == \
            launch0["phrase_pair_launches"] + 1
        assert launch1["phrase_searches"] == launch0["phrase_searches"] + 1
        assert launch1["position_bytes"] > launch0["position_bytes"]
        assert 0 < launch1["position_pad_bytes"] - launch0["position_pad_bytes"] \
            < launch1["position_bytes"] - launch0["position_bytes"]
        assert launch1["posting_bytes"] == launch0["posting_bytes"]
        assert after["device_sparse"] == before["device_sparse"] + 1
        assert after["host"] == before["host"]
        for name in ("launches", "coalesced"):
            assert kinds1["phrase"][name] == kinds0["phrase"][name] + 1
        assert stats("device")["indices"]["traced"]["totals"][
            "positions_plane"] > 0

    def test_a_prefix_records_its_expansion_and_its_counters(self, live):
        """A `prefix` rides the batcher as an unscored filtered plan whose
        mask row the chip builds: the host's expansion of the pattern into
        block rows and the put of the launch's operand are a part of the stage
        span, and /_nodes/stats books the launch, the plan, the terms and
        runs matched and the block rows' bytes; no mask row is put and the
        host scorer answers nothing."""
        _cluster, _node, rc = live

        def stats(section):
            resp = rc.dispatch(RestRequest(
                method="GET", path=f"/_nodes/stats/{section}"))
            return next(iter(resp.body["nodes"].values()))[section]

        body = {"query": {"prefix": {"body": "qu"}}, "size": 5}
        _traced_search(rc, body)  # first sighting compiles the mask program
        before, kinds0 = stats("search_serving"), stats("search")["batcher"]["kinds"]
        tree = _traced_search(rc, body)
        after, kinds1 = stats("search_serving"), stats("search")["batcher"]["kinds"]
        _assert_nested(tree)
        (expand,) = _find(tree, "shard.multiterm_expand")
        (stage,) = [n for n in _find(tree, "dispatch.stage")
                    if any(c["name"] == "shard.multiterm_expand"
                           for c in n["children"])]
        assert stage["t0"] <= expand["t0"] and expand["t1"] <= stage["t1"] + 1e-6
        assert not _find(tree, "shard.filter_mask")  # no row evaluated on the host
        launch0, launch1 = before["launch"], after["launch"]
        assert launch1["multiterm"] == launch0["multiterm"] + 1
        assert launch1["multiterm_searches"] == launch0["multiterm_searches"] + 1
        assert launch1["multiterm_terms"] > launch0["multiterm_terms"]
        assert launch1["multiterm_runs"] == launch0["multiterm_runs"] + 1
        assert launch1["multiterm_bytes"] - launch0["multiterm_bytes"] == 256 * 128 * 4
        assert 0 < launch1["multiterm_pad_bytes"] - launch0["multiterm_pad_bytes"] \
            < launch1["multiterm_bytes"] - launch0["multiterm_bytes"]
        assert launch1["multiterm_field_scans"] == launch0["multiterm_field_scans"]
        assert launch1["unscored_plans"] == launch0["unscored_plans"] + 1
        assert launch1["mask_put_bytes"] == launch0["mask_put_bytes"]
        assert launch1["posting_bytes"] == launch0["posting_bytes"]
        assert after["device_filtered"] == before["device_filtered"] + 1
        assert after["host"] == before["host"]
        for name in ("launches", "coalesced"):
            assert kinds1["filtered"][name] == kinds0["filtered"][name] + 1

    def test_a_dis_max_records_its_staging_and_its_counters(self, live):
        """A `dis_max` of one-field OR queries rides the batcher to the
        dis_max program: the host's staging of the group (the clauses under
        their disjuncts' accumulators, the operand plane, its one device_put)
        is a part of the stage span, and /_nodes/stats books the launch, the
        plan, its disjuncts, the bytes the program reckons it reads, the
        ladder's padding and the batcher's kind."""
        cluster, node, rc = live
        client = node.client()
        # BM25, two analyzed fields, ONE segment (under TF-IDF every disjunct
        # takes a coord and the host scorer answers)
        client.create_index("titled", {"settings": {
            "number_of_shards": 1, "number_of_replicas": 0,
            "index.refresh_interval": "-1",
            "index.similarity.default.type": "BM25"}})
        cluster.ensure_green("titled")
        for i in range(40):
            client.index("titled", "doc", {
                "title": WORDS[i % 8],
                "txt": f"{WORDS[i % 8]} {WORDS[(i + 1) % 8]} {WORDS[(i + 3) % 8]}"},
                id=str(i))
        client.refresh("titled")

        def stats(section):
            resp = rc.dispatch(RestRequest(
                method="GET", path=f"/_nodes/stats/{section}"))
            return next(iter(resp.body["nodes"].values()))[section]

        body = {"query": {"multi_match": {
            "query": "quick brown", "type": "best_fields",
            "fields": ["txt", "title"], "tie_breaker": 0.5}}, "size": 5}
        _traced_search(rc, body, index="titled")  # the first sighting compiles
        before, kinds0 = stats("search_serving"), stats("search")["batcher"]["kinds"]
        tree = _traced_search(rc, body, index="titled")
        after, kinds1 = stats("search_serving"), stats("search")["batcher"]["kinds"]
        _assert_nested(tree)
        (plan,) = _find(tree, "shard.dismax_plan")
        (stage,) = [n for n in _find(tree, "dispatch.stage")
                    if any(c["name"] == "shard.dismax_plan"
                           for c in n["children"])]
        assert stage["t0"] <= plan["t0"] and plan["t1"] <= stage["t1"] + 1e-6
        (dispatch,) = _find(tree, "batcher.dispatch")
        assert [c["name"] for c in dispatch["children"]] == \
            ["dispatch.stage", "dispatch.launch", "device_pull"]
        launch0, launch1 = before["launch"], after["launch"]
        assert launch1["dismax"] == launch0["dismax"] + 1
        assert launch1["dismax_searches"] == launch0["dismax_searches"] + 1
        assert launch1["dismax_disjuncts"] == launch0["dismax_disjuncts"] + 2
        assert launch1["dismax_blocks"] - launch0["dismax_blocks"] == 256
        assert 0 < launch1["dismax_pad_blocks"] - launch0["dismax_pad_blocks"] \
            < 256
        # the accumulators' planes and the combined one, beside the postings
        assert launch1["dismax_bytes"] - launch0["dismax_bytes"] > \
            launch1["posting_bytes"] - launch0["posting_bytes"] > 0
        assert launch1["operand_puts"] == launch0["operand_puts"] + 1
        assert after["device_sparse"] == before["device_sparse"] + 1
        assert after["host"] == before["host"]
        for name in ("launches", "coalesced"):
            assert kinds1["dis_max"][name] == kinds0["dis_max"][name] + 1
        assert stats("device")["compile"]["by_family"]["dis_max"] >= 1

    def test_an_exact_sum_counts_its_limb_rows_and_their_bytes(self, live):
        """A sum of a long column under a terms bucket: the launch counts the
        integer limb rows it reduced, and the device ledger holds their bytes
        in a tier of their own beside the float32 folds."""
        _cluster, node, rc = live
        self._numbered(_cluster, node)

        def stats(section):
            resp = rc.dispatch(RestRequest(
                method="GET", path=f"/_nodes/stats/{section}"))
            return next(iter(resp.body["nodes"].values()))[section]

        before = stats("search_serving")
        resp = rc.dispatch(RestRequest(
            method="POST", path="/numbered/_search",
            body={"query": {"match_all": {}}, "size": 0, "aggs": {
                "by_word": {"terms": {"field": "body"},
                            "aggs": {"s": {"sum": {"field": "n"}}}},
                "total": {"sum": {"field": "n"}}}}))
        assert resp.status == 200, resp.body
        assert resp.body["aggregations"]["total"]["value"] == sum(range(40))
        after = stats("search_serving")
        assert after["device_aggs"] == before["device_aggs"] + 1
        # a segment: two stacks of one limbed field, three limbs each
        rose = after["launch"]["exact_sum_rows"] - before["launch"]["exact_sum_rows"]
        assert rose and rose % 6 == 0
        totals = stats("device")["indices"]["numbered"]["totals"]
        assert totals["agg_limbs"] > 0 and totals["agg_rows"] > 0

    @staticmethod
    def _numbered(cluster, node):
        """40 numbered documents in ONE segment: the index refreshes when told
        (a refresh of the clock's own, on a slow machine, split them in two,
        and a search of two segments stages and launches twice)."""
        client = node.client()
        if not client.exists_index("numbered"):
            client.create_index("numbered", {"settings": {
                "number_of_shards": 1, "number_of_replicas": 0,
                "index.refresh_interval": "-1"}})
            cluster.ensure_green("numbered")
            for i in range(40):
                client.index("numbered", "doc", {
                    "body": f"{WORDS[i % 8]} {WORDS[(i + 1) % 8]}", "n": i},
                    id=str(i))
            client.refresh("numbered")

    def test_unsampled_search_allocates_no_span(self, live, monkeypatch):
        _cluster, node, rc = live
        made = []
        real_init = tracing.Span.__init__
        real_record = tracing.Span.record

        def counting_init(self, *a, **kw):
            made.append(a[1] if len(a) > 1 else "?")
            real_init(self, *a, **kw)

        def counting_record(self, name, *a, **kw):
            made.append(name)
            return real_record(self, name, *a, **kw)

        monkeypatch.setattr(tracing.Span, "__init__", counting_init)
        monkeypatch.setattr(tracing.Span, "record", counting_record)
        monkeypatch.setattr(node.tracer, "sample_rate", 0.0)
        for body in (SEARCH_BODY, FILTERED_BODY):
            resp = rc.dispatch(RestRequest(
                method="POST", path="/traced/_search", body=dict(body),
                t_arrival=time.monotonic()))
            assert resp.status == 200 and "trace" not in resp.body
        assert made == []
        # the pin bites: a sampled search does allocate them
        _traced_search(rc, SEARCH_BODY)
        assert "coordinator.query" in made and "dispatch.stage" in made

    def test_a_compile_names_its_launch(self, live):
        """A first sighting compiles inside dispatch.launch: the span says how
        many events and how many seconds, and device.compile keeps both."""
        _cluster, node, rc = live

        def compile_stats():
            resp = rc.dispatch(RestRequest(
                method="GET", path="/_nodes/stats/device"))
            return next(iter(resp.body["nodes"].values()))["device"]["compile"]

        before = compile_stats()
        body = dict(SEARCH_BODY, size=300)  # a k bucket nothing here has used
        tree = _traced_search(rc, body)
        after = compile_stats()
        assert after["total"] > before["total"]
        assert after["seconds"] > before["seconds"]
        assert abs(sum(after["seconds_by_family"].values())
                   - after["seconds"]) < 1e-6
        assert set(after["seconds_by_family"]) == set(after["by_family"])
        assert after["cache_hits"] + after["cache_misses"] >= \
            before["cache_hits"] + before["cache_misses"]
        compiled = [n for n in _find(tree, "dispatch.launch")
                    if n["tags"].get("compiled")]
        assert compiled and all(n["tags"]["compile_s"] > 0 for n in compiled)
        assert sum(n["tags"]["compiled"] for n in compiled) <= \
            after["total"] - before["total"]

    def test_launch_counters_and_the_profile_api_share_one_sum(self, live):
        _cluster, node, rc = live

        def launch():
            resp = rc.dispatch(RestRequest(
                method="GET", path="/_nodes/stats/search_serving"))
            return next(iter(
                resp.body["nodes"].values()))["search_serving"]["launch"]

        rc.dispatch(RestRequest(method="POST", path="/traced/_search",
                                body=dict(SEARCH_BODY)))
        warm = launch()
        assert warm["blocks_launched"] >= warm["blocks_real"] > 0
        assert warm["blocks_padding"] == \
            warm["blocks_launched"] - warm["blocks_real"]
        assert warm["launches_sparse"] > 0 and warm["posting_bytes"] > 0
        before = launch()
        resp = rc.dispatch(RestRequest(
            method="POST", path="/traced/_search",
            body=dict(SEARCH_BODY, profile=True)))
        after = launch()
        (shard,) = resp.body["profile"]["shards"]
        scanned = sum(seg["blocks_scanned"] for seg in shard["segments"])
        assert scanned == after["blocks_real"] - before["blocks_real"] > 0
        # the dense families count too, rows of the [Q, doc_pad] plane included
        rc.dispatch(RestRequest(method="POST", path="/traced/_search",
                                body=dict(FILTERED_BODY)))
        dense = launch()
        assert dense["launches_dense"] > after["launches_dense"]
        assert dense["dense_rows"] > after["dense_rows"]

    @pytest.mark.parametrize("python_tracer", [False, True])
    def test_profiler_puts_every_batch_on_the_traces_clock(
            self, live, tmp_path, python_tracer):
        import jax

        _cluster, _node, rc = live
        start = rc.dispatch(RestRequest(
            method="POST", path="/_nodes/_local/profiler/start",
            body={"dir": str(tmp_path / "prof"), **(
                {"python_tracer": True} if python_tracer else {})}))
        try:
            assert start.status == 200, start.body
            assert start.body["python_tracer"] is python_tracer
            for _ in range(2):
                rc.dispatch(RestRequest(method="POST", path="/traced/_search",
                                        body=dict(SEARCH_BODY)))
        finally:
            stop = rc.dispatch(RestRequest(
                method="POST", path="/_nodes/_local/profiler/stop"))
        assert stop.status == 200, stop.body
        for clock in (start.body["clock"], stop.body["clock"]):
            assert set(clock) == {"monotonic_s", "epoch_ns"}
        assert stop.body["clock"]["monotonic_s"] > start.body["clock"]["monotonic_s"]
        (pb,) = [f for f in stop.body["files"] if f.endswith(".xplane.pb")]
        names = [e.name for plane in jax.profiler.ProfileData.from_file(pb).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events]
        anchors = [n for n in names if n.startswith("estpu.clock monotonic_s=")]
        assert [float(a.split("=")[1]) for a in anchors] == [
            start.body["clock"]["monotonic_s"], stop.body["clock"]["monotonic_s"]]
        for phase in ("collect", "dispatch", "merge", "pull"):
            assert names.count(f"estpu.batch.{phase}") >= 2, phase
        # with the Python tracer off the host plane holds TraceMe events only
        python_events = [n for n in names if n.startswith("$")]
        assert bool(python_events) is python_tracer

    def test_gc_pause_seconds_rise_across_a_full_collection(self, live):
        import gc

        _cluster, _node, rc = live

        def gc_stats():
            resp = rc.dispatch(RestRequest(
                method="GET", path="/_nodes/stats/runtime"))
            return next(iter(resp.body["nodes"].values()))["runtime"]["gc"]

        before = gc_stats()
        junk = [[i] for i in range(200000)]  # something for the collector to walk
        gc.collect()
        after = gc_stats()
        del junk
        assert after["collections"] > before["collections"]
        assert after["pause_s"] > before["pause_s"]

    def test_http_respond_times_what_follows_the_handler(self, live):
        import urllib.request

        _cluster, node, _rc = live
        http = node.http or node.start_http(0)
        url = f"http://127.0.0.1:{http.port}"
        req = urllib.request.Request(
            url + "/traced/_search", data=json.dumps(SEARCH_BODY).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert json.loads(r.read())["hits"]["total"] > 0
        # the handler books `respond` after the client has its last byte: the
        # stats request can overtake it, so poll (bounded) until it is booked
        give_up = time.monotonic() + 10.0
        while True:
            with urllib.request.urlopen(url + "/_nodes/stats/http",
                                        timeout=30) as r:
                stats = next(iter(json.loads(r.read())["nodes"].values()))
            respond = stats["http"]["respond"]
            if respond["count"] >= 1 or time.monotonic() > give_up:
                break
            time.sleep(0.01)
        assert respond["count"] >= 1 and respond["sum_s"] > 0
        assert respond["p99_ms"] >= respond["p50_ms"] > 0


# ---------------------------------------------------------------------------
# the hand-overs between threads: pool waits, wake-ups, the local transport's
# codec, the fetch phase; the host runtime's counters (PR 37)
# ---------------------------------------------------------------------------

QUERY_ACTION = "transport[indices:data/read/search[phase/query]]"
FETCH_ACTION = "transport[indices:data/read/search[phase/fetch]]"
# the spans that name work or a wait (`host_unnamed_ms` subtracts them from
# the root); every other span is a container of these
NAMED = ("rest.parse", "coordinator.plan", "coordinator.reduce",
         "coordinator.render", "shard.lower", "shard.fetch", "pool.wait",
         "thread.wake", "transport.codec", "batcher.queue",
         "batcher.dispatch", "batcher.hold", "batcher.merge", "device_pull")
HANDOVERS = ("pool.wait", "thread.wake", "transport.codec", "shard.fetch",
             "batcher.hold")
# a search whose one shard has its only copy on the asking node sends itself
# no message (actions._query_shard_inline): every hand-over but the codec's
HANDOVERS_INLINE = tuple(h for h in HANDOVERS if h != "transport.codec")


def _descendants(n):
    return [d for c in n["children"] for d in _flatten(c)]


# a search of `traced` (one shard, its only copy here) makes one trip, on the
# asking thread: no round trip at all. One of `traced2` (two shards, hits on
# both) sends a query phase and a fetch phase to each: the cases of the
# transport's spans live where the transport is used
ROUND_TRIPS = "traced2"


class TestHostHandovers:
    @pytest.mark.parametrize("pool", ["generic", "search"])
    @pytest.mark.parametrize("action", [QUERY_ACTION, FETCH_ACTION],
                             ids=["query", "fetch"])
    def test_each_pool_hop_of_each_phase_records_its_wait(self, live, action,
                                                          pool):
        _cluster, _node, rc = live
        tree = _traced_search(rc, SEARCH_BODY, index=ROUND_TRIPS)
        tspans = _find(tree, action)
        assert len(tspans) == 2  # one a shard
        for tspan in tspans:
            waits = [c for c in tspan["children"] if c["name"] == "pool.wait"]
            assert [w["tags"]["pool"] for w in waits] == ["generic", "search"]
            (wait,) = [w for w in waits if w["tags"]["pool"] == pool]
            assert tspan["t0"] <= wait["t0"] <= wait["t1"] <= tspan["t1"]
            # the handler runs after both hops: its span starts behind them
            handler = "shard" if action == QUERY_ACTION else "shard.fetch"
            (served,) = [c for c in tspan["children"] if c["name"] == handler]
            assert wait["t1"] <= served["t0"]

    @pytest.mark.parametrize("action", [QUERY_ACTION, FETCH_ACTION],
                             ids=["query", "fetch"])
    def test_both_round_trips_of_a_phase_record_the_codec(self, live, action):
        _cluster, _node, rc = live
        tree = _traced_search(rc, SEARCH_BODY, index=ROUND_TRIPS)
        transports = [n for n in _flatten(tree)
                      if n["name"].startswith("transport[")]
        # two shards: two round trips a phase, each a request and a response
        assert len(transports) == 4
        assert len(_find(tree, "transport.codec")) == 2 * len(transports)
        for tspan in _find(tree, action):
            request, response = [c for c in tspan["children"]
                                 if c["name"] == "transport.codec"]
            # the request's before the first pool hop, the response's last:
            # the transport span ends with it
            assert request["t1"] <= tspan["children"][1]["t0"]
            assert tspan["children"][0] is request
            assert tspan["children"][-1] is response
            assert response["t1"] == tspan["t1"]

    def test_the_fetch_phase_continues_the_trace(self, live):
        _cluster, node, rc = live
        tree = _traced_search(rc, SEARCH_BODY, index="traced2")
        fetches = _find(tree, "shard.fetch")
        tspans = _find(tree, FETCH_ACTION)
        assert len(fetches) == len(tspans) == 2
        for fetch, tspan in zip(fetches, tspans):
            assert fetch["parent"] == tspan["id"] and fetch["node"] == node.name
            assert fetch in tspan["children"]

    def test_one_shard_fetches_inside_its_query_phase(self, live):
        """One trip: the shard's query handler builds the page's hits, so the
        one `shard.fetch` is the last child of the `shard` span, which hangs
        under `coordinator.query` behind the wait for the `search` pool's
        slot, with no round trip (the only copy is here: the handler ran on
        the asking thread); `coordinator.fetch` stays, with no child."""
        _cluster, node, rc = live
        tree = _traced_search(rc, SEARCH_BODY)
        assert not any(n["name"].startswith("transport[")
                       for n in _flatten(tree))
        assert _find(tree, "transport.codec") == []
        (shard,) = _find(tree, "shard")
        (query,) = _find(tree, "coordinator.query")
        (wait,) = _find(tree, "pool.wait")
        assert query["children"] == [wait, shard]
        assert wait["tags"]["pool"] == "search" and wait["t1"] <= shard["t0"]
        (fetch,) = _find(tree, "shard.fetch")
        assert fetch["parent"] == shard["id"] and fetch["node"] == node.name
        assert shard["children"][-1] is fetch
        before = shard["children"][-2]
        assert before["name"] == "thread.wake"
        assert before["t1"] <= fetch["t0"] <= fetch["t1"] <= shard["t1"]
        (cfetch,) = _find(tree, "coordinator.fetch")
        assert cfetch["children"] == [] and cfetch["tags"]["shards"] == 0
        (coord,) = _find(tree, "coordinator")
        assert [c["name"] for c in coord["children"]] == [
            "coordinator.plan", "coordinator.query", "coordinator.reduce",
            "coordinator.fetch", "coordinator.render"]

    @pytest.mark.parametrize("index,after,parents", [
        ("traced", "batcher", ["shard"]),
        # one shard, its only copy here: the asking thread ran the query
        # phase itself and waited for no round trip
        ("traced", "transport", []),
        ("traced2", "transport", ["coordinator.query", "coordinator.fetch"])],
        ids=["batcher", "no-transport-one-trip", "transport"])
    def test_a_waiting_thread_records_its_wake_up(self, live, index, after,
                                                  parents):
        _cluster, _node, rc = live
        tree = _traced_search(rc, SEARCH_BODY, index=index)
        by_id = {n["id"]: n for n in _flatten(tree)}
        wakes = [n for n in _find(tree, "thread.wake")
                 if n["tags"]["after"] == after]
        assert [by_id[w["parent"]]["name"] for w in wakes] == parents
        for w in wakes:
            waited_for = [c for c in by_id[w["parent"]]["children"]
                          if c["t0"] < w["t0"]]
            if after == "batcher":
                # from where the drainer finished the batch
                assert waited_for[-1]["name"] == "batcher.merge"
                assert w["t0"] == waited_for[-1]["t1"]
            else:
                # from where the phase's last round-trip ended
                assert all(c["name"].startswith("transport[")
                           for c in waited_for)
                assert w["t0"] == max(c["t1"] for c in waited_for)

    @pytest.mark.parametrize("body", [SEARCH_BODY, FILTERED_BODY],
                             ids=["plain", "filtered"])
    def test_named_spans_nest_and_never_overlap(self, live, body):
        """Every span lies inside its parent, and of the spans that name work
        or a wait no two cover the same instant of one search unless one
        holds the other (a pull inside its merge or its dispatch): their
        medians may be added up."""
        _cluster, _node, rc = live
        _traced_search(rc, body)
        tree = _traced_search(rc, body)
        _assert_nested(tree)
        names = {n["name"] for n in _flatten(tree)}
        assert set(HANDOVERS_INLINE) <= names
        named = [n for n in _flatten(tree) if n["name"] in NAMED]
        for i, a in enumerate(named):
            inside_a = {d["id"] for d in _descendants(a)}
            for b in named[i + 1:]:
                if b["id"] in inside_a or \
                        a["id"] in {d["id"] for d in _descendants(b)}:
                    continue
                assert a["t1"] <= b["t0"] + 1e-9 or b["t1"] <= a["t0"] + 1e-9, \
                    (a["name"], b["name"])

    def test_an_unsampled_search_records_none_and_leaves_the_ring(
            self, live, monkeypatch):
        _cluster, node, rc = live
        recorded = []
        real_init = tracing.Span.__init__
        real_record = tracing.Span.record

        def spy_init(self, trace, name, *a, **kw):
            recorded.append(name)
            real_init(self, trace, name, *a, **kw)

        def spy(self, name, *a, **kw):
            recorded.append(name)
            return real_record(self, name, *a, **kw)

        monkeypatch.setattr(tracing.Span, "__init__", spy_init)
        monkeypatch.setattr(tracing.Span, "record", spy)
        monkeypatch.setattr(node.tracer, "sample_rate", 0.0)

        def ring():
            return [(t["trace_id"], len(t["spans"]))
                    for t in node.tracer.traces()]

        before = ring()
        resp = rc.dispatch(RestRequest(
            method="POST", path="/traced/_search", body=dict(SEARCH_BODY),
            t_arrival=time.monotonic()))
        assert resp.status == 200 and "trace" not in resp.body
        assert recorded == [] and ring() == before
        _traced_search(rc, SEARCH_BODY)
        assert set(HANDOVERS_INLINE) <= set(recorded) and ring() != before
        _traced_search(rc, SEARCH_BODY, index=ROUND_TRIPS)
        assert set(HANDOVERS) <= set(recorded)

    @pytest.mark.parametrize("shards", [1, 2])
    def test_a_two_node_search_stitches_the_remote_fetch(self, tmp_path,
                                                         shards):
        """Asked of the node that holds no copy of shard 0. One shard: the
        remote query handler fetches too, inside its `shard` span. Two, one a
        node: a fetch phase follows, and the remote shard's `shard.fetch`
        hangs under the asker's transport span."""
        with TestCluster(n_nodes=2, data_root=tmp_path, seed=5) as cluster:
            first = next(iter(cluster.nodes.values()))
            client = first.client()
            client.create_index("far", {"settings": {
                "number_of_shards": shards, "number_of_replicas": 0}})
            cluster.ensure_green("far")
            for i in range(12):
                client.index("far", "doc", {"body": WORDS[i % 8]}, id=str(i))
            client.refresh("far")
            copy = first.cluster_service.state.routing_table.index("far") \
                .shard(0).active_shards()[0]
            holder = next(n for n in cluster.nodes.values()
                          if n.local_node.id == copy.node_id)
            asker = next(n for n in cluster.nodes.values() if n is not holder)
            resp = build_rest_controller(asker).dispatch(RestRequest(
                method="POST", path="/far/_search", params={"trace": "true"},
                body={"query": {"match": {"body": " ".join(WORDS)}},
                      "size": 12}))
            assert resp.status == 200, resp.body
            assert len(resp.body["hits"]["hits"]) == 12  # of every shard
            tree = resp.body["trace"]["tree"]
            _assert_nested(tree, in_turn=shards == 1)
            assert tree["name"] == "rest" and tree["node"] == asker.name
            (fetch,) = [n for n in _find(tree, "shard.fetch")
                        if n["node"] == holder.name]
            (shard,) = [n for n in _find(tree, "shard")
                        if n["node"] == holder.name]
            if shards == 1:
                assert _find(tree, FETCH_ACTION) == []
                assert fetch["parent"] == shard["id"]
                (tspan,) = _find(tree, QUERY_ACTION)
                assert shard["parent"] == tspan["id"]
            else:
                (tspan,) = [n for n in _find(tree, FETCH_ACTION)
                            if fetch in n["children"]]
                assert fetch["parent"] == tspan["id"]
            assert tspan["node"] == asker.name
            # the sender's side of the wire is named on every round trip to
            # the holder; the remote's pools have no span of the asker's to
            # record under
            remote = [n for n in _flatten(tree)
                      if n["name"].startswith("transport[")
                      and holder.name in {c["node"] for c in n["children"]}]
            assert len(remote) == (1 if shards == 1 else 2)
            for tspan in remote:
                names = [c["name"] for c in tspan["children"]]
                assert names.count("transport.codec") == 1
                assert "pool.wait" not in names


def _runtime(rc):
    resp = rc.dispatch(RestRequest(method="GET", path="/_nodes/stats/runtime"))
    return next(iter(resp.body["nodes"].values()))["runtime"]


class TestHostRuntimeCounters:
    ROLES = ("http_s", "search_s", "generic_s", "search_batcher_s", "other_s")

    def test_cpu_seconds_are_served_by_role_and_never_fall(self, live):
        _cluster, _node, rc = live
        before = _runtime(rc)["cpu"]
        assert set(before["threads"]) == set(self.ROLES)
        for _ in range(3):
            # two shards: each phase is a message to each, handled on the
            # pools (a search of `traced` runs on the thread that asks)
            _concurrent_searches(rc, 2, trace=False, index="traced2")
        after = _runtime(rc)["cpu"]
        assert after["process_s"] > before["process_s"]
        for role in self.ROLES:
            assert after["threads"][role] >= before["threads"][role] >= 0.0
        # the searches ran on the pools and through the drainer
        for role in ("search_s", "generic_s", "search_batcher_s"):
            assert after["threads"][role] > before["threads"][role], role

    def test_the_http_handlers_book_their_cpu(self, live):
        import urllib.request

        _cluster, node, rc = live
        http = node.http or node.start_http(0)
        before = _runtime(rc)["cpu"]["threads"]["http_s"]
        req = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/traced/_search",
            data=json.dumps(SEARCH_BODY).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert json.loads(r.read())["hits"]["total"] > 0
        # booked after the client has its last byte: poll, bounded
        give_up = time.monotonic() + 10.0
        while _runtime(rc)["cpu"]["threads"]["http_s"] <= before \
                and time.monotonic() < give_up:
            time.sleep(0.01)
        assert _runtime(rc)["cpu"]["threads"]["http_s"] > before

    def test_the_gil_gauge_probes_an_idle_node(self, live):
        _cluster, _node, rc = live
        first = _runtime(rc)["gil"]
        assert set(first) == {"probes", "late_s", "late_max_s"}
        give_up = time.monotonic() + 10.0
        while _runtime(rc)["gil"]["probes"] < first["probes"] + 2 \
                and time.monotonic() < give_up:
            time.sleep(0.02)
        later = _runtime(rc)["gil"]
        assert later["probes"] >= first["probes"] + 2
        assert later["late_s"] >= first["late_s"] >= 0.0
        assert later["late_max_s"] >= 0.0

    def test_compile_stall_seconds_are_the_three_parts(self, live):
        _cluster, _node, rc = live
        resp = rc.dispatch(RestRequest(method="GET",
                                       path="/_nodes/stats/device"))
        c = next(iter(resp.body["nodes"].values()))["device"]["compile"]
        assert c["trace_s"] > 0 and c["lower_s"] > 0 and c["seconds"] > 0
        assert c["stall_s"] == pytest.approx(
            c["seconds"] + c["trace_s"] + c["lower_s"])

    @pytest.mark.parametrize("event,part", [
        ("/jax/core/compile/jaxpr_trace_duration", "trace_s"),
        ("/jax/core/compile/jaxpr_to_mlir_module_duration", "lower_s")])
    def test_trace_and_lower_events_are_seconds_not_compiles(self, event,
                                                             part):
        from elasticsearch_tpu.common import jaxenv

        counter = jaxenv._CompileCounter()
        report = jaxenv.SanitizerReport()
        counter._active.append(report)
        n0, s0 = jaxenv.thread_compile_totals()
        counter._listener(event, 0.25)
        assert counter.stall_s[part] == 0.25
        assert sum(counter.stall_s.values()) == 0.25
        assert counter.total == 0 and counter.by_family == {}
        assert counter.seconds == 0.0 and counter.by_pool == {}
        assert report.compiles == 0
        assert jaxenv.thread_compile_totals() == (n0, s0)
        counter._listener("/jax/core/compile/backend_compile_duration", 0.5)
        assert counter.total == 1 and counter.seconds == 0.5
        assert counter.stall_s[part] == 0.25


# ---------------------------------------------------------------------------
# tpulint: the instrumented files stay clean
# ---------------------------------------------------------------------------


def test_observability_files_tpulint_clean():
    """Tracing touches the device hot path (batcher, execute, mesh serving):
    every instrumented file must stay free of findings so the empty baseline
    holds."""
    from tools.tpulint import lint_paths

    wanted = {
        "elasticsearch_tpu/common/tracing.py",
        "elasticsearch_tpu/common/metrics.py",
        "elasticsearch_tpu/common/stream.py",
        "elasticsearch_tpu/search/batcher.py",
        "elasticsearch_tpu/search/execute.py",
        "elasticsearch_tpu/search/service.py",
        "elasticsearch_tpu/transport/service.py",
        "elasticsearch_tpu/actions.py",
        "elasticsearch_tpu/rest/controller.py",
        "elasticsearch_tpu/threadpool.py",
        "elasticsearch_tpu/parallel/mesh_serving.py",
        "elasticsearch_tpu/monitor.py",
        "elasticsearch_tpu/http/server.py",
        "elasticsearch_tpu/ops/scoring.py",
        "elasticsearch_tpu/common/jaxenv.py",
    }
    findings = [f for f in lint_paths(None) if f.path in wanted]
    assert findings == [], [f.to_dict() for f in findings]
