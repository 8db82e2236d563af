"""Overload protection: hierarchical breakers, bounded queues with 429
backpressure, deadline-aware admission control — unit + live-cluster chaos.

The acceptance shape (ISSUE 4): with a deliberately small parent budget, a
concurrent burst of wide-agg searches yields CircuitBreakingError surfaced as
HTTP 429 with Retry-After, zero crashes, all breakers back to 0 estimated
bytes afterwards, and a subsequent plain search answers 200 with correct
hits; threadpool saturation likewise yields 429 (not deadlock) with rejected
counters visible in /_nodes/stats.
"""

import contextlib
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from elasticsearch_tpu.common.breaker import (
    CircuitBreakerService,
    MemoryCircuitBreaker,
    reserve,
)
from elasticsearch_tpu.common.deadline import NO_DEADLINE, Deadline
from elasticsearch_tpu.common.errors import (
    CircuitBreakingError,
    RejectedExecutionError,
)
from elasticsearch_tpu.common.retry import is_transient
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.search.service import SearchAdmissionController
from elasticsearch_tpu.threadpool import ThreadPool

from .harness import TestCluster


# ---------------------------------------------------------------------------
# breaker hierarchy (unit)
# ---------------------------------------------------------------------------


class TestPackEstimateMatchesLayout:
    """The fielddata breaker's segment-pack estimate must track the QUANTIZED
    layout (ops/device_index.pack_shape_math) — the old 8 B × 2 all-f32 math
    overstated every u8 segment by ~40%, inflating breaker pressure."""

    def _packed_actual_bytes(self, seg, packed):
        import numpy as np

        return (
            packed.host_docs.nbytes + packed.host_freqs.nbytes  # retained host
            + np.asarray(packed.blk_docs).nbytes  # device planes
            + np.asarray(packed.blk_tf).nbytes
            + np.asarray(packed.blk_nb).nbytes
            + np.asarray(packed.blk_tf).nbytes  # quantize staging (host)
            + np.asarray(packed.blk_nb).nbytes
            + 2 * packed.doc_pad  # live mask, host + device
            + sum(np.asarray(a).nbytes for a in packed.norm_bytes.values())
        )

    def test_estimate_matches_actual_packed_bytes(self, tmp_path):
        import numpy as np

        from elasticsearch_tpu.common.settings import Settings as S
        from elasticsearch_tpu.index.engine import Engine
        from elasticsearch_tpu.mapper.core import MapperService
        from elasticsearch_tpu.ops.device_index import (
            bytes_per_posting, pack_estimate_bytes, pack_segment,
            packed_resident_bytes)

        rng = np.random.default_rng(23)
        svc = MapperService(S.from_flat({}))
        eng = Engine(str(tmp_path / "est"), svc)
        words = [f"w{i}" for i in range(60)]
        for i in range(200):
            eng.index("doc", str(i), {"b": " ".join(rng.choice(words, size=12))})
        eng.refresh()
        seg = eng.acquire_searcher().segments[0]
        from elasticsearch_tpu.ops.device_index import PACK_TRANSIENT_SLOT_BYTES

        est = pack_estimate_bytes(seg)
        packed = pack_segment(seg)
        # text-only segment: estimate == retained/uploaded planes plus the
        # documented per-slot transient allowance, exactly (shared shape
        # math); any drift between estimate and pack is a regression
        NBpad = np.asarray(packed.blk_docs).shape[0]
        assert est == (self._packed_actual_bytes(seg, packed)
                       + NBpad * 128 * PACK_TRANSIENT_SLOT_BYTES)
        # device-resident postings are the quantized 6 B/posting (u8 ladder,
        # no dense plane until a fallback faults it in)
        assert packed.blk_freqs is None
        assert packed_resident_bytes(packed) == NBpad * 128 * bytes_per_posting(
            packed.tf_layout)
        assert bytes_per_posting(packed.tf_layout) <= 6
        eng.close()

    def test_estimate_never_under_reserves_with_dv_columns(self, tmp_path):
        import numpy as np

        from elasticsearch_tpu.common.settings import Settings as S
        from elasticsearch_tpu.index.engine import Engine
        from elasticsearch_tpu.mapper.core import MapperService
        from elasticsearch_tpu.ops.device_index import (
            pack_estimate_bytes, pack_segment)

        svc = MapperService(S.from_flat({}))
        eng = Engine(str(tmp_path / "estdv"), svc)
        rng = np.random.default_rng(29)
        for i in range(120):
            eng.index("doc", str(i), {"b": f"w{int(rng.integers(20))} text",
                                      "n": int(i), "price": float(i) * 1.5})
        eng.refresh()
        seg = eng.acquire_searcher().segments[0]
        est = pack_estimate_bytes(seg)
        packed = pack_segment(seg)
        actual = self._packed_actual_bytes(seg, packed) + sum(
            np.asarray(c).nbytes for c in packed.dv_single.values())
        # dv columns are estimated at the f64 upper bound (multi-valued
        # columns never upload) — estimate must bound actual from above,
        # within the padded-column + pack-transient slack
        from elasticsearch_tpu.ops.device_index import PACK_TRANSIENT_SLOT_BYTES

        NBpad = np.asarray(packed.blk_docs).shape[0]
        assert actual <= est
        assert est - actual <= (8 * packed.doc_pad * len(seg.dv_num)
                                + NBpad * 128 * PACK_TRANSIENT_SLOT_BYTES)
        eng.close()


class TestBreakerHierarchy:
    def test_child_trips_under_own_limit(self):
        svc = CircuitBreakerService(total_budget_bytes=1000)
        br = svc.breaker("request")  # limit 600, overhead 1.0
        br.add_estimate_and_maybe_break(500, "a")
        with pytest.raises(CircuitBreakingError):
            br.add_estimate_and_maybe_break(200, "b")
        assert br.used == 500 and br.trip_count == 1
        br.release(500)
        assert br.used == 0 and svc.parent.used == 0

    def test_parent_trips_across_children(self):
        # parent 700; request 600; fielddata 800×1.03 — each child fits its own
        # limit but together they blow the shared budget
        svc = CircuitBreakerService(total_budget_bytes=1000)
        svc.breaker("fielddata").add_estimate_and_maybe_break(500, "cols")
        with pytest.raises(CircuitBreakingError) as ei:
            svc.breaker("request").add_estimate_and_maybe_break(300, "merge")
        assert "parent" in str(ei.value)
        # the failed charge left NOTHING accounted anywhere
        assert svc.breaker("request").used == 0
        assert svc.parent.used == 500
        assert svc.parent.trip_count == 1
        svc.breaker("fielddata").release(500)
        assert svc.parent.used == 0

    def test_trip_names_the_tripped_breaker(self):
        # serving paths degrade ONLY on fielddata trips; request/parent trips
        # must shed — the error carries which breaker fired
        svc = CircuitBreakerService(total_budget_bytes=1000)
        with pytest.raises(CircuitBreakingError) as ei:
            svc.breaker("request").add_estimate_and_maybe_break(700, "x")
        assert ei.value.breaker == "request"
        svc.breaker("fielddata").add_estimate_and_maybe_break(500, "y")
        with pytest.raises(CircuitBreakingError) as ei:
            svc.breaker("request").add_estimate_and_maybe_break(300, "z")
        assert ei.value.breaker == "parent"
        svc.breaker("fielddata").release(500)

    def test_release_clamps_at_zero_and_counts_leak(self):
        svc = CircuitBreakerService(total_budget_bytes=1000)
        br = svc.breaker("request")
        br.add_estimate_and_maybe_break(100, "x")
        br.release(60)
        br.release(60)  # over-release: clamps, never goes negative
        assert br.used == 0
        assert br.leak_detected == 1
        assert svc.parent.used == 0
        # headroom was NOT inflated by the bad release: a full-limit charge
        # still fits exactly once
        br.add_estimate_and_maybe_break(600, "y")
        with pytest.raises(CircuitBreakingError):
            br.add_estimate_and_maybe_break(1, "z")
        br.release(600)

    def test_concurrent_adds_never_blow_past_limit(self):
        br = MemoryCircuitBreaker(100, 1.0, "t")
        successes = []

        def worker():
            for _ in range(50):
                try:
                    br.add_estimate_and_maybe_break(1, "w")
                    successes.append(1)
                except CircuitBreakingError:
                    pass

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # the read-modify-write is atomic: exactly `limit` units were admitted
        assert len(successes) == 100
        assert br.used == 100

    def test_reserve_scope_always_releases(self):
        svc = CircuitBreakerService(total_budget_bytes=1000)
        br = svc.breaker("request")
        with reserve(br, 200, "scope"):
            assert br.used == 200
        assert br.used == 0
        with pytest.raises(RuntimeError):
            with reserve(br, 200, "scope"):
                raise RuntimeError("boom")
        assert br.used == 0 and svc.parent.used == 0
        # None breaker and zero bytes are no-ops
        with reserve(None, 100):
            pass
        with reserve(br, 0):
            assert br.used == 0

    def test_settings_driven_limits(self):
        settings = Settings.from_flat({
            "indices.breaker.total_budget": "1kb",
            "indices.breaker.request.limit": "50%",
        })
        svc = CircuitBreakerService(settings)
        assert svc.total_budget == 1024
        assert svc.breaker("request").limit == 512
        assert svc.parent.limit == int(1024 * 0.7)
        assert svc.breaker("in_flight_requests").limit == 1024

    def test_stats_shape(self):
        svc = CircuitBreakerService(total_budget_bytes=1000)
        stats = svc.stats()
        for name in ("request", "fielddata", "in_flight_requests", "parent"):
            for key in ("limit", "estimated", "tripped", "leak_detected"):
                assert key in stats[name], (name, key)


# ---------------------------------------------------------------------------
# bounded thread pools (unit)
# ---------------------------------------------------------------------------


class TestBoundedThreadPool:
    def test_queue_full_rejects_with_429(self):
        tp = ThreadPool(Settings.from_flat({
            "threadpool.search.size": 1, "threadpool.search.queue_size": 1}))
        try:
            gate = threading.Event()
            tp.submit("search", gate.wait)
            deadline = time.monotonic() + 5.0
            while tp.stats()["search"]["active"] != 1:
                assert time.monotonic() < deadline, tp.stats()["search"]
                time.sleep(0.005)
            tp.submit("search", gate.wait)  # fills the 1-slot queue
            with pytest.raises(RejectedExecutionError) as ei:
                tp.submit("search", gate.wait)
            assert ei.value.status == 429
            st = tp.stats()["search"]
            assert st["rejected"] == 1 and st["queue"] == 1 and st["active"] == 1
            gate.set()
            deadline = time.monotonic() + 5.0
            while tp.stats()["search"]["completed"] != 2:
                assert time.monotonic() < deadline, tp.stats()["search"]
                time.sleep(0.005)
        finally:
            tp.shutdown()

    def test_rejection_is_transient_for_retry_policy(self):
        assert is_transient(RejectedExecutionError("queue full"))

    def test_shutdown_cancels_timers_and_scheduler(self):
        tp = ThreadPool()
        fired = []
        timer = tp.schedule(5.0, "generic", lambda: fired.append("timer"))
        task_ticks = []
        tp.schedule_with_fixed_delay(0.03, lambda: task_ticks.append(1))
        time.sleep(0.1)
        tp.shutdown()
        # cancelled, not left to fire into a dead node (finished is set by
        # cancel(); the timer THREAD may take a beat to exit — join it)
        assert timer.finished.is_set()
        timer.join(timeout=2.0)
        assert not timer.is_alive()
        assert not tp._scheduler_thread.is_alive()
        ticks_at_shutdown = len(task_ticks)
        time.sleep(0.12)
        assert len(task_ticks) == ticks_at_shutdown
        assert fired == []
        with pytest.raises(RejectedExecutionError):
            tp.submit("search", lambda: None)

    def test_schedule_after_shutdown_never_fires(self):
        tp = ThreadPool()
        tp.shutdown()
        fired = []
        t = tp.schedule(0.01, "generic", lambda: fired.append(1))
        time.sleep(0.05)
        assert fired == [] and not t.is_alive()


# ---------------------------------------------------------------------------
# admission control (unit)
# ---------------------------------------------------------------------------


class TestAdmissionControl:
    def test_rejects_unservable_budget(self):
        ctrl = SearchAdmissionController(min_samples=3)
        for _ in range(3):
            ctrl.observe(0.5)
        with pytest.raises(RejectedExecutionError) as ei:
            ctrl.admit(Deadline.after(0.001))
        assert ei.value.status == 429
        assert ei.value.retry_after_s == pytest.approx(0.5)
        assert ctrl.stats()["rejected"] == 1

    def test_admits_generous_and_unbounded_budgets(self):
        ctrl = SearchAdmissionController(min_samples=3)
        for _ in range(3):
            ctrl.observe(0.5)
        ctrl.admit(Deadline.after(10.0))
        ctrl.admit(NO_DEADLINE)
        assert ctrl.stats()["rejected"] == 0

    def test_slow_outlier_decays_instead_of_poisoning(self):
        # one wedged 5s failover chain must not 429 servable 500ms requests
        # for hundreds of observations: the admit() signal is an EWMA, and a
        # handful of healthy samples wash the outlier out
        ctrl = SearchAdmissionController(min_samples=3)
        for _ in range(3):
            ctrl.observe(0.01)
        ctrl.observe(5.0)
        with pytest.raises(RejectedExecutionError):
            ctrl.admit(Deadline.after(0.5))  # right after the spike: shed
        for _ in range(10):
            ctrl.observe(0.01)
        ctrl.admit(Deadline.after(0.5))  # recovered — no rejection
        assert ctrl.stats()["ewma_shard_phase_ms"] < 500

    def test_cold_node_never_rejects(self):
        ctrl = SearchAdmissionController(min_samples=10)
        for _ in range(9):
            ctrl.observe(5.0)  # even huge latencies: below min_samples
        ctrl.admit(Deadline.after(0.001))
        assert ctrl.stats()["rejected"] == 0


# ---------------------------------------------------------------------------
# live-cluster chaos (REST surface over real sockets)
# ---------------------------------------------------------------------------


def _call(base, method, path, body=None, raw_body=None, timeout=60):
    data = None
    headers = {}
    if raw_body is not None:
        data = raw_body.encode()
        headers["Content-Type"] = "application/x-ndjson"
    elif body is not None:
        data = json.dumps(body).encode()
        headers["Content-Type"] = "application/json"
    req = urllib.request.Request(base + path, data=data, method=method,
                                 headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, payload = resp.status, resp.read().decode()
            resp_headers = dict(resp.headers)
    except urllib.error.HTTPError as e:
        status, payload = e.code, e.read().decode()
        resp_headers = dict(e.headers)
    try:
        parsed = json.loads(payload) if payload else None
    except ValueError:
        parsed = payload
    return status, parsed, resp_headers


@contextlib.contextmanager
def _http_cluster(tmp_path, settings=None, n_docs=0, shards=1):
    with TestCluster(n_nodes=1, data_root=tmp_path, seed=11,
                     settings=settings or {}) as cluster:
        node = next(iter(cluster.nodes.values()))
        server = node.start_http(port=0)
        base = f"http://127.0.0.1:{server.port}"
        status, body, _h = _call(base, "PUT", "/overload", {"settings": {
            "number_of_shards": shards, "number_of_replicas": 0}})
        assert status == 200 and body["acknowledged"], body
        cluster.ensure_green("overload")
        # bulk in chunks small enough to clear even a shrunken in-flight budget
        for lo in range(0, n_docs, 200):
            lines = []
            for i in range(lo, min(lo + 200, n_docs)):
                lines.append(json.dumps(
                    {"index": {"_index": "overload", "_type": "doc",
                               "_id": str(i)}}))
                lines.append(json.dumps({"tag": f"t{i % 7}", "n": i}))
            status, body, _h = _call(base, "POST", "/_bulk",
                                     raw_body="\n".join(lines) + "\n")
            assert status == 200 and not body.get("errors"), body
        if n_docs:
            status, _b, _h = _call(base, "POST", "/overload/_refresh")
            assert status == 200
        yield cluster, node, base


WIDE_AGG_SEARCH = {
    # explain pins the HOST mask path (deterministic request-breaker charge of
    # max_doc × (5 + 16·n_aggs) bytes) — the "expensive aggregation" face
    "query": {"match_all": {}},
    "aggs": {"tags": {"terms": {"field": "tag"}}},
    "explain": True,
    "size": 3,
}


class TestOverloadChaos:
    def test_breaker_burst_yields_429_then_full_recovery(self, tmp_path):
        # 48kb parent budget: one 2000-doc wide-agg query phase estimates
        # ~42kb against a 28.8kb request limit — every burst search must shed
        with _http_cluster(tmp_path,
                           settings={"indices.breaker.total_budget": "48kb"},
                           n_docs=2000) as (cluster, node, base):
            results = []
            results_lock = threading.Lock()

            def hammer():
                st, body, headers = _call(base, "POST", "/overload/_search",
                                          WIDE_AGG_SEARCH)
                with results_lock:
                    results.append((st, body, headers))

            threads = [threading.Thread(target=hammer) for _ in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            statuses = [st for st, _b, _h in results]
            assert len(statuses) == 6
            # ≥1 breaker trip surfaced as 429 — and NOTHING crashed (no 5xx)
            assert 429 in statuses, statuses
            assert all(st < 500 for st in statuses), statuses
            for st, body, headers in results:
                if st == 429:
                    assert "Retry-After" in headers, headers
                    assert int(headers["Retry-After"]) >= 1
                    assert body["error"]["type"] in (
                        "CircuitBreakingException", "RejectedExecutionException"
                    ), body
            # graceful degradation: only the offending requests aborted, every
            # reservation was released — breakers drain to 0 estimated bytes
            deadline = time.monotonic() + 5.0
            while True:
                st, stats, _h = _call(base, "GET", "/_nodes/stats")
                assert st == 200
                node_stats = stats["nodes"][node.node_id]
                estimates = {name: b["estimated"]
                             for name, b in node_stats["breakers"].items()}
                if all(v == 0 for v in estimates.values()):
                    break
                assert time.monotonic() < deadline, estimates
                time.sleep(0.05)
            tripped = sum(b["tripped"]
                          for b in node_stats["breakers"].values())
            assert tripped >= 1, node_stats["breakers"]
            # the node keeps serving: a plain search answers green
            st, body, _h = _call(base, "POST", "/overload/_search",
                                 {"query": {"match_all": {}}, "size": 5})
            assert st == 200, body
            assert body["hits"]["total"] == 2000
            assert len(body["hits"]["hits"]) == 5

    def test_threadpool_saturation_yields_429_not_deadlock(self, tmp_path):
        with _http_cluster(tmp_path,
                           settings={"threadpool.search.size": 1,
                                     "threadpool.search.queue_size": 1},
                           n_docs=20) as (cluster, node, base):
            gate = threading.Event()
            # occupy the single search worker AND the single queue slot
            node.threadpool.submit("search", gate.wait)
            deadline = time.monotonic() + 5.0
            while node.threadpool.stats()["search"]["active"] != 1:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            node.threadpool.submit("search", gate.wait)
            try:
                st, body, headers = _call(
                    base, "POST", "/overload/_search",
                    {"query": {"match_all": {}}}, timeout=30)
                assert st == 429, body
                assert "Retry-After" in headers
                assert body["error"]["type"] == "RejectedExecutionException", body
            finally:
                gate.set()
            st, stats, _h = _call(base, "GET", "/_nodes/stats")
            pool = stats["nodes"][node.node_id]["thread_pool"]["search"]
            assert pool["rejected"] >= 1, pool
            # queue drained → the same search now answers
            deadline = time.monotonic() + 5.0
            while node.threadpool.stats()["search"]["queue"] > 0:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            st, body, _h = _call(base, "POST", "/overload/_search",
                                 {"query": {"match_all": {}}})
            assert st == 200 and body["hits"]["total"] == 20

    def test_admission_control_rejects_unservable_timeout(self, tmp_path):
        with _http_cluster(tmp_path, n_docs=20) as (cluster, node, base):
            # seed the coordinator's latency signal: shard phases "take" 500ms
            for _ in range(node.actions.admission.min_samples):
                node.actions.admission.observe(0.5)
            st, body, headers = _call(
                base, "POST", "/overload/_search?timeout=1ms",
                {"query": {"match_all": {}}})
            assert st == 429, body
            assert body["error"]["type"] == "RejectedExecutionException"
            assert headers.get("Retry-After") == "1"
            assert node.actions.admission.stats()["rejected"] >= 1
            # a generous budget sails through the same gate
            st, body, _h = _call(base, "POST", "/overload/_search?timeout=30s",
                                 {"query": {"match_all": {}}})
            assert st == 200 and body["hits"]["total"] == 20


# ---------------------------------------------------------------------------
# REST stats surface (satellite: breaker + queue stats over /_nodes/stats)
# ---------------------------------------------------------------------------


class TestRestOverloadStats:
    def test_nodes_stats_exposes_breakers_and_queues(self, tmp_path):
        with _http_cluster(tmp_path, n_docs=5) as (cluster, node, base):
            # one flat (device-lowerable) search so the batcher counters move
            st, body, _h = _call(base, "POST", "/overload/_search",
                                 {"query": {"term": {"tag": "t0"}}})
            assert st == 200, body
            st, stats, _h = _call(base, "GET", "/_nodes/stats")
            assert st == 200
            node_stats = stats["nodes"][node.node_id]
            breakers = node_stats["breakers"]
            for name in ("parent", "request", "fielddata",
                         "in_flight_requests"):
                for key in ("limit", "estimated", "tripped"):
                    assert key in breakers[name], (name, key)
                assert breakers[name]["limit"] > 0
                assert breakers[name]["estimated"] == 0
            pools = node_stats["thread_pool"]
            for name in ("search", "index", "bulk", "get"):
                for key in ("queue", "rejected", "threads", "active",
                            "queue_size", "completed"):
                    assert key in pools[name], (name, key)
            # the searches this fixture ran left latency observations behind
            assert "admission_control" in node_stats
            assert set(node_stats["admission_control"]) == {
                "observed", "mean_shard_phase_ms", "ewma_shard_phase_ms",
                "rejected", "shard_phase"}
            # the histogram twin of the EWMA (PR 8): tail percentiles ride along
            assert {"p50_ms", "p95_ms", "p99_ms"} <= set(
                node_stats["admission_control"]["shard_phase"])
            # cross-request micro-batching counters (search/batcher.py)
            batcher = node_stats["search"]["batcher"]
            for key in ("launches", "coalesced", "occupancy_mean",
                        "linger_flushes", "deadline_flushes"):
                assert key in batcher, key
            # this fixture's searches rode the batcher: the coordinator's
            # flat query phases coalesce through it even at occupancy 1
            assert batcher["launches"] >= 1
            assert batcher["coalesced"] >= batcher["launches"]
            # the drainer occupies its named pool (visible liveness signal)
            assert "search_batcher" in node_stats["thread_pool"]


class TestHttpFrontDoor:
    def test_a_burst_of_connections_is_accepted_not_reset(self, tmp_path):
        """socketserver listens with a backlog of 5: 16 clients connecting at
        once (chip_smoke.py's burst, PR 22) met ConnectionResetError before any
        handler ran. The front door queues a burst; shedding load is the
        breakers' and the pools' job (429), never the kernel's (RST)."""
        import threading

        with _http_cluster(tmp_path, n_docs=20) as (_cluster, _node, base):
            n = 48
            gate = threading.Barrier(n)
            results = [None] * n

            def one(i):
                gate.wait(30)
                try:
                    results[i] = _call(base, "POST", "/overload/_search",
                                       {"query": {"match_all": {}}, "size": 1})[0]
                except Exception as e:  # noqa: BLE001 — the reset, if it comes back
                    results[i] = repr(e)

            threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert results == [200] * n, results
