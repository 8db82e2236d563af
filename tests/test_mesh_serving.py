"""Mesh serving: a REST _search over a co-located multi-shard index executes the
SPMD shard_map program (host-summed DFS stats + all_gather top-k over the virtual 8-device CPU
mesh) and produces results identical to the transport scatter-gather path.

ref: the scatter-gather this replaces is TransportSearchTypeAction.java:117,135-216
with the reduce at SearchPhaseController.java:137."""

import numpy as np
import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.transport.local import LocalTransportRegistry

pytestmark = pytest.mark.mesh

N_SHARDS = 4
VOCAB = ("alpha beta gamma delta epsilon zeta eta theta iota kappa lamda mu nu xi "
         "omicron pi rho sigma tau upsilon phi chi psi omega").split()


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    registry = LocalTransportRegistry()
    n = Node(name="mesh_node", registry=registry,
             data_path=str(tmp_path_factory.mktemp("mesh_node")))
    n.start([n.local_node.transport_address])
    n.wait_for_master()
    client = n.client()
    client.create_index("library", {"settings": {
        "number_of_shards": N_SHARDS, "number_of_replicas": 0}})
    client.cluster_health(wait_for_status="green")
    rng = np.random.default_rng(7)
    for i in range(120):
        body = " ".join(rng.choice(VOCAB, size=rng.integers(5, 25)))
        client.index("library", "doc", {"body": body, "n": int(i)}, id=str(i))
    client.refresh("library")
    yield n, client
    n.close()


def _search_both_paths(node_, client, body, search_type="query_then_fetch"):
    """Run the same search with mesh serving on and off; return (mesh, transport)."""
    ms = node_.actions.mesh_serving
    before = ms.mesh_queries
    mesh = client.search("library", body, search_type=search_type)
    assert ms.mesh_queries == before + 1, "search did not ride the mesh program"
    ms.enabled = False
    try:
        transport = client.search("library", body, search_type=search_type)
    finally:
        ms.enabled = True
    return mesh, transport


def _assert_same_results(mesh, transport):
    assert mesh["hits"]["total"] == transport["hits"]["total"]
    m = [(h["_id"], h["_score"]) for h in mesh["hits"]["hits"]]
    t = [(h["_id"], h["_score"]) for h in transport["hits"]["hits"]]
    assert [i for i, _ in m] == [i for i, _ in t]
    assert np.allclose([s for _, s in m], [s for _, s in t], rtol=2e-6)


class TestMeshServing:
    def test_match_rides_mesh_and_agrees(self, node):
        n, client = node
        body = {"query": {"match": {"body": "alpha beta"}}, "size": 10}
        mesh, transport = _search_both_paths(n, client, body)
        assert mesh["hits"]["total"] > 0
        _assert_same_results(mesh, transport)

    def test_bool_semantics_on_mesh(self, node):
        n, client = node
        body = {"query": {"bool": {
            "must": [{"term": {"body": "alpha"}}],
            "should": [{"term": {"body": "beta"}}, {"term": {"body": "gamma"}}],
            "must_not": [{"term": {"body": "omega"}}]}}, "size": 10}
        mesh, transport = _search_both_paths(n, client, body)
        _assert_same_results(mesh, transport)

    def test_dfs_search_type_uses_global_stats(self, node):
        n, client = node
        body = {"query": {"match": {"body": "delta epsilon"}}, "size": 10}
        mesh, transport = _search_both_paths(n, client, body,
                                             search_type="dfs_query_then_fetch")
        _assert_same_results(mesh, transport)

    def test_metric_aggs_ride_mesh_and_match_transport(self, node):
        # metric aggs fuse into the SPMD program (stats + all_gather); results
        # must match the transport path within f32 kernel accumulation
        n, client = node
        ms = n.actions.mesh_serving
        before = ms.mesh_queries
        body = {"query": {"match": {"body": "alpha"}},
                "aggs": {"n_avg": {"avg": {"field": "n"}},
                         "n_stats": {"stats": {"field": "n"}}}}
        r = client.search("library", body)
        assert ms.mesh_queries == before + 1  # served by the mesh program
        ms.enabled = False
        try:
            r2 = client.search("library", body)
        finally:
            ms.enabled = True
        for name in ("n_avg", "n_stats"):
            a, b = r["aggregations"][name], r2["aggregations"][name]
            for k2 in a:
                if isinstance(a[k2], float):
                    assert abs(a[k2] - b[k2]) <= 1e-5 * max(abs(b[k2]), 1)
                else:
                    assert a[k2] == b[k2]

    def test_ineligible_aggs_fall_back_to_transport(self, node):
        # cardinality's HLL sketch can't ride the SPMD scatter; the whole
        # request declines to the transport path (which still answers)
        n, client = node
        ms = n.actions.mesh_serving
        before = ms.mesh_queries
        r = client.search("library", {
            "query": {"match": {"body": "alpha"}},
            "aggs": {"uniq": {"cardinality": {"field": "body"}}}})
        assert ms.mesh_queries == before  # ineligible: sketch agg
        assert "uniq" in r["aggregations"]

    def test_fetch_phase_hydrates_mesh_hits(self, node):
        n, client = node
        mesh, _ = _search_both_paths(
            n, client, {"query": {"term": {"body": "alpha"}}, "size": 5})
        for h in mesh["hits"]["hits"]:
            assert "body" in h["_source"] and h["_index"] == "library"

    def test_deletes_invalidate_mesh_cache(self, node):
        n, client = node
        body = {"query": {"term": {"body": "alpha"}}, "size": 30}
        mesh, _ = _search_both_paths(n, client, body)
        victims = [h["_id"] for h in mesh["hits"]["hits"]][:2]
        for vid in victims:
            client.delete("library", "doc", vid)
        client.refresh("library")
        mesh2, transport2 = _search_both_paths(n, client, body)
        _assert_same_results(mesh2, transport2)
        ids = [h["_id"] for h in mesh2["hits"]["hits"]]
        assert not (set(victims) & set(ids))

    def test_filtered_query_rides_mesh(self, node):
        n, client = node
        body = {"query": {"filtered": {
            "query": {"match": {"body": "alpha beta"}},
            "filter": {"range": {"n": {"gte": 10, "lt": 80}}}}}, "size": 10}
        mesh, transport = _search_both_paths(n, client, body)
        _assert_same_results(mesh, transport)
        assert mesh["hits"]["total"] > 0

    def test_recreated_index_never_serves_stale_cache(self, node):
        n, client = node
        for round_ in ("first", "second"):
            client.create_index("tmpidx", {"settings": {
                "number_of_shards": 2, "number_of_replicas": 0}})
            client.cluster_health(wait_for_status="green")
            for i in range(8):
                client.index("tmpidx", "doc", {"body": f"{round_} common"}, id=str(i))
            client.refresh("tmpidx")
            r = client.search("tmpidx", {"query": {"term": {"body": round_}},
                                         "size": 5})
            assert r["hits"]["total"] == 8, round_  # stale cache would return 0
            client.delete_index("tmpidx")

    def test_new_docs_visible_after_refresh(self, node):
        n, client = node
        client.index("library", "doc", {"body": "zzyzx alpha", "n": 999}, id="zz1")
        client.refresh("library")
        mesh, transport = _search_both_paths(
            n, client, {"query": {"term": {"body": "zzyzx"}}, "size": 5})
        assert mesh["hits"]["total"] == 1
        assert mesh["hits"]["hits"][0]["_id"] == "zz1"
        _assert_same_results(mesh, transport)


class TestMeshServingRound5:
    """Round-5 mesh parity: sort, post_filter, min_score, bucket aggs and
    shard-subset serving all ride the SPMD program and match the transport
    path (ref: the per-feature logic these mirror lives in
    service.execute_query_phase's device branches)."""

    def test_field_sort_rides_mesh(self, node):
        n, client = node
        for order in ("asc", "desc"):
            body = {"query": {"match": {"body": "alpha"}},
                    "sort": [{"n": {"order": order}}], "size": 10}
            mesh, transport = _search_both_paths(n, client, body)
            assert mesh["hits"]["total"] == transport["hits"]["total"]
            m = [(h["_id"], h["sort"]) for h in mesh["hits"]["hits"]]
            t = [(h["_id"], h["sort"]) for h in transport["hits"]["hits"]]
            assert m == t, order
            assert len(m) > 0

    def test_sort_with_track_scores(self, node):
        n, client = node
        body = {"query": {"match": {"body": "alpha"}},
                "sort": [{"n": "desc"}], "track_scores": True, "size": 8}
        mesh, transport = _search_both_paths(n, client, body)
        m = [(h["_id"], h["sort"]) for h in mesh["hits"]["hits"]]
        t = [(h["_id"], h["sort"]) for h in transport["hits"]["hits"]]
        assert m == t
        ms = [h["_score"] for h in mesh["hits"]["hits"]]
        ts = [h["_score"] for h in transport["hits"]["hits"]]
        assert np.allclose(ms, ts, rtol=2e-6)

    def test_post_filter_rides_mesh(self, node):
        # post_filter gates hits/totals but not aggregations
        n, client = node
        body = {"query": {"match": {"body": "alpha"}},
                "post_filter": {"range": {"n": {"lt": 40}}},
                "aggs": {"n_avg": {"avg": {"field": "n"}}}, "size": 10}
        mesh, transport = _search_both_paths(n, client, body)
        _assert_same_results(mesh, transport)
        assert abs(mesh["aggregations"]["n_avg"]["value"]
                   - transport["aggregations"]["n_avg"]["value"]) < 1e-4

    def test_min_score_rides_mesh(self, node):
        n, client = node
        probe = client.search("library", {"query": {"match": {"body": "alpha"}},
                                          "size": 5})
        # midpoint between two hit scores: robust to per-kernel f32 ulp drift
        # (an exact hit score would flip inclusion between execution paths)
        threshold = (probe["hits"]["hits"][2]["_score"]
                     + probe["hits"]["hits"][3]["_score"]) / 2.0
        body = {"query": {"match": {"body": "alpha"}},
                "min_score": threshold, "size": 10}
        mesh, transport = _search_both_paths(n, client, body)
        _assert_same_results(mesh, transport)
        assert mesh["hits"]["total"] < probe["hits"]["total"]

    def test_terms_agg_rides_mesh(self, node):
        n, client = node
        body = {"query": {"match": {"body": "alpha"}},
                "aggs": {"by_body": {"terms": {"field": "body", "size": 8}}},
                "size": 5}
        mesh, transport = _search_both_paths(n, client, body)
        _assert_same_results(mesh, transport)
        m = [(b["key"], b["doc_count"])
             for b in mesh["aggregations"]["by_body"]["buckets"]]
        t = [(b["key"], b["doc_count"])
             for b in transport["aggregations"]["by_body"]["buckets"]]
        assert m == t

    def test_histogram_with_metric_subagg_rides_mesh(self, node):
        n, client = node
        body = {"query": {"match": {"body": "alpha"}},
                "aggs": {"by_n": {"histogram": {"field": "n", "interval": 25},
                                  "aggs": {"navg": {"avg": {"field": "n"}}}}},
                "size": 0}
        mesh, transport = _search_both_paths(n, client, body)
        m = mesh["aggregations"]["by_n"]["buckets"]
        t = transport["aggregations"]["by_n"]["buckets"]
        assert [(b["key"], b["doc_count"]) for b in m] == \
            [(b["key"], b["doc_count"]) for b in t]
        for bm, bt in zip(m, t):
            assert abs(bm["navg"]["value"] - bt["navg"]["value"]) < 1e-4

    @pytest.mark.parametrize("index, value", [
        # odd values over 2^24: float32 holds none of them
        ("gazetteer", lambda i: (1 << 24) + 1 + 2 * i),
        # float32 holds each value, not the sum a shard forms of them
        ("ledger", lambda i: (1 << 22) + 256 * i),
    ], ids=["values_past_float32", "sums_past_float32"])
    def test_a_whole_number_sum_float32_would_round_declines_the_mesh(
            self, node, index, value):
        """The mesh program reduces in float32 and has no integer limbs
        (ROADMAP S13): where float32 cannot add a whole-number column up
        exactly it declines (device_index.agg_device_exact), and the transport
        path, whose one-shard program adds limbs, gives the exact sum at the
        top level and under a bucket."""
        n, client = node
        client.create_index(index, {"settings": {
            "number_of_shards": N_SHARDS, "number_of_replicas": 0},
            "mappings": {"doc": {"properties": {
                "pop": {"type": "long"}, "small": {"type": "long"},
                "body": {"type": "string"},
                "cc": {"type": "string", "index": "not_analyzed"}}}}})
        client.cluster_health(wait_for_status="green")
        pops = [value(i) for i in range(48)]
        for i, pop in enumerate(pops):
            client.index(index, "doc", {"pop": pop, "small": i, "body": "alpha",
                                        "cc": "c%d" % (i % 3)}, id=str(i))
        client.refresh(index)
        ms = n.actions.mesh_serving
        query = {"match": {"body": "alpha"}}
        body = {"query": query, "size": 0, "aggs": {
            "total": {"sum": {"field": "pop"}},
            "by_cc": {"terms": {"field": "cc"},
                      "aggs": {"s": {"sum": {"field": "pop"}}}}}}
        before = ms.mesh_queries
        r = client.search(index, body)
        assert ms.mesh_queries == before  # declined: float32 would round it
        assert sum(pops) > 1 << 24
        assert r["aggregations"]["total"]["value"] == float(sum(pops))
        assert int(r["aggregations"]["total"]["value"]) == sum(pops)
        assert {b["key"]: int(b["s"]["value"])
                for b in r["aggregations"]["by_cc"]["buckets"]} == {
            "c%d" % c: sum(pops[c::3]) for c in range(3)}
        # a column float32 adds up exactly still rides the mesh on this index
        r = client.search(index, {"query": query, "size": 0, "aggs": {
            "t": {"sum": {"field": "small"}}}})
        assert ms.mesh_queries == before + 1
        assert r["aggregations"]["t"]["value"] == float(sum(range(48)))

    def test_range_agg_rides_mesh(self, node):
        # positional buckets: every range emits (zero-count included)
        n, client = node
        body = {"query": {"match": {"body": "alpha"}},
                "aggs": {"rng": {"range": {"field": "n", "ranges": [
                    {"to": 40}, {"from": 40, "to": 90},
                    {"from": 90}, {"from": 5000}]}}}, "size": 0}
        mesh, transport = _search_both_paths(n, client, body)
        m = mesh["aggregations"]["rng"]["buckets"]
        t = transport["aggregations"]["rng"]["buckets"]
        assert [(b.get("key"), b["doc_count"]) for b in m] == \
            [(b.get("key"), b["doc_count"]) for b in t]
        assert m[-1]["doc_count"] == 0  # zero-count range still emitted

    def test_filters_agg_rides_mesh(self, node):
        n, client = node
        body = {"query": {"match": {"body": "alpha"}},
                "aggs": {"f": {"filters": {"filters": {
                    "low": {"range": {"n": {"lt": 60}}},
                    "high": {"range": {"n": {"gte": 60}}}}}}}, "size": 0}
        mesh, transport = _search_both_paths(n, client, body)
        m = {k: b["doc_count"]
             for k, b in mesh["aggregations"]["f"]["buckets"].items()}
        t = {k: b["doc_count"]
             for k, b in transport["aggregations"]["f"]["buckets"].items()}
        assert m == t and set(m) == {"low", "high"}

    def test_significant_terms_declines_mesh(self, node):
        # per-segment background counts don't survive the shard-level merge
        n, client = node
        ms = n.actions.mesh_serving
        before = ms.mesh_queries
        r = client.search("library", {
            "query": {"match": {"body": "alpha"}},
            "aggs": {"sig": {"significant_terms": {"field": "body"}}}})
        assert ms.mesh_queries == before
        assert "sig" in r["aggregations"]

    def test_shard_subset_preference_rides_mesh(self, node):
        # routing/preference selecting a subset serves via the active mask
        n, client = node
        ms = n.actions.mesh_serving
        body = {"query": {"match": {"body": "alpha beta"}}, "size": 10}
        full = client.search("library", body)
        before = ms.mesh_queries
        subset = client.search("library", body, preference="_shards:0,2")
        assert ms.mesh_queries == before + 1
        ms.enabled = False
        try:
            subset_t = client.search("library", body, preference="_shards:0,2")
        finally:
            ms.enabled = True
        assert subset["hits"]["total"] == subset_t["hits"]["total"]
        assert [h["_id"] for h in subset["hits"]["hits"]] == \
            [h["_id"] for h in subset_t["hits"]["hits"]]
        assert subset["hits"]["total"] < full["hits"]["total"]

    def test_sort_asc_missing_last(self, node):
        # (k > doc_pad declines the mesh, so keep the result window small and
        # the query selective enough that the missing-value doc is in-window)
        n, client = node
        client.index("library", "doc", {"body": "zzyzx nofield"}, id="nm1")
        client.refresh("library")
        try:
            body = {"query": {"term": {"body": "zzyzx"}},
                    "sort": [{"n": {"order": "asc", "missing": "_last"}}],
                    "size": 10}
            mesh, transport = _search_both_paths(n, client, body)
            m = [(h["_id"], h["sort"]) for h in mesh["hits"]["hits"]]
            t = [(h["_id"], h["sort"]) for h in transport["hits"]["hits"]]
            assert m == t
            assert m[-1][0] == "nm1"  # missing ranks last
            assert len(m) >= 2
        finally:
            client.delete("library", "doc", "nm1")
            client.refresh("library")

    def test_sort_plus_post_filter_plus_min_score_composes(self, node):
        n, client = node
        body = {"query": {"match": {"body": "alpha"}},
                "post_filter": {"range": {"n": {"gte": 10}}},
                "min_score": 0.01,
                "sort": [{"n": "desc"}], "size": 10}
        mesh, transport = _search_both_paths(n, client, body)
        assert mesh["hits"]["total"] == transport["hits"]["total"]
        assert [h["_id"] for h in mesh["hits"]["hits"]] == \
            [h["_id"] for h in transport["hits"]["hits"]]


class TestRepackLockDiscipline:
    def test_repack_runs_outside_the_service_lock_and_racers_dedup(self, node,
                                                                   monkeypatch):
        """PR-6 TPU004 fix: the device repack (build_sharded_index +
        executor construction) must run with MeshServingService._lock
        RELEASED — under the lock it serialized every search on the node
        behind a multi-second pack — and concurrent searches racing the same
        rebuild must dedup onto ONE in-flight build (the rest park on its
        future, lock-free)."""
        import threading
        import time

        from elasticsearch_tpu.parallel import mesh_serving as ms_mod

        n, client = node
        ms = n.actions.mesh_serving
        real_build = ms_mod.build_sharded_index
        calls = []
        lock_free = []

        def spy(*args, **kwargs):
            calls.append(1)
            # timed acquire, NOT a non-blocking probe: a racing search thread
            # legitimately holds _lock for microseconds inside its own cache
            # check, which a blocking=False probe conflates with the bug. The
            # bug shape is the BUILDER thread holding the non-reentrant lock
            # across this whole call — then this same-thread acquire times out.
            got = ms._lock.acquire(timeout=2.0)
            if got:
                ms._lock.release()
            lock_free.append(got)
            time.sleep(0.3)  # widen the race window for the dedup half
            return real_build(*args, **kwargs)

        monkeypatch.setattr(ms_mod, "build_sharded_index", spy)
        with ms._lock:
            ms._executors.clear()  # force a rebuild on the next search
            ms._building.clear()

        body = {"query": {"match": {"body": "alpha"}}, "size": 5}
        results = []

        def run():
            results.append(client.search("library", body))

        threads = [threading.Thread(target=run) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert len(results) == 3
        assert all(r["hits"]["total"] > 0 for r in results)
        assert lock_free and all(lock_free), \
            "repack ran while holding MeshServingService._lock"
        assert len(calls) == 1, f"racers did not dedup: {len(calls)} builds"

    def test_stale_builder_does_not_clobber_newer_build(self, monkeypatch):
        """A refresh mid-pack lets a NEWER freshness register its own build;
        the stale builder's cleanup must neither overwrite the newer cache
        entry nor pop the newer in-flight record — but its own waiters still
        get answered. (Code-review finding on the PR-6 fix.)"""
        import threading

        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.parallel.mesh_serving import MeshServingService

        class FakeSearcher:
            def __init__(self, max_doc):
                self.segments = []
                self.max_doc = max_doc

        ms = MeshServingService(None, Settings.from_flat({}))
        svc = object()
        builds = []
        stale_started = threading.Event()
        release_stale = threading.Event()

        def fake_build(searchers, kind, default_sim):
            builds.append(searchers[0].max_doc)
            if searchers[0].max_doc == 1:  # the stale generation
                stale_started.set()
                assert release_stale.wait(10.0)
                return {False: "OLD", True: "OLD"}
            return {False: "NEW", True: "NEW"}

        monkeypatch.setattr(ms, "_build_executors", fake_build)
        out = {}
        t = threading.Thread(target=lambda: out.__setitem__(
            "stale", ms._executor_for("idx", svc, [FakeSearcher(1)],
                                      "bm25", None, False)))
        t.start()
        assert stale_started.wait(10.0)
        # a newer freshness registers AND completes while the stale pack runs
        assert ms._executor_for("idx", svc, [FakeSearcher(2)],
                                "bm25", None, False) == "NEW"
        release_stale.set()
        t.join(10.0)
        assert out["stale"] == "OLD"  # stale waiters still answered
        # the newer cache entry survived the stale finally: no third build
        assert ms._executor_for("idx", svc, [FakeSearcher(2)],
                                "bm25", None, False) == "NEW"
        assert builds == [1, 2], builds
        assert ms._building == {}
