"""Multi-tier caching (ISSUE 11): shard request cache, device-resident filter
cache, and cache-affinity replica routing.

Unit half: fingerprint stability (key order / volatile knobs), the
size==0-unless-opted-in cache policy, LRU byte bounds + breaker accounting
(trip at store time skips caching; eviction/clear releases), view-keyed
invalidation, filter-mask sighting promotion + shared-holder eviction
semantics, and rendezvous affinity (same fingerprint → same copy within the
healthy spread set; health dominates; probes unchanged).

Chaos half (live cluster): repeated hot queries hit before the device (the
warmed hit loop is pinned at 0 device launches / 0 recompiles / 0 syncs under
hard transfer_guard("disallow")), a bulk write + refresh invalidates (a stale
hit is NEVER served), `POST /_cache/clear` drains both tiers' breaker bytes
to 0, filter-cache warm hits score bitwise-identically to the cold path, and
the observability surfaces (`/_nodes/stats` indices.request_cache /
indices.filter_cache, `/_cat/caches`, `estpu_request_cache_*` /
`estpu_filter_cache_*` Prometheus families, `?profile=true` cache events)
all report the traffic.
"""

from __future__ import annotations

import threading
import time

import pytest

from elasticsearch_tpu.cluster.routing import OperationRouting
from elasticsearch_tpu.cluster.state import STARTED, ShardRouting
from elasticsearch_tpu.cluster.stats import AdaptiveReplicaSelector
from elasticsearch_tpu.common.breaker import CircuitBreakerService
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.ops.device_index import DeviceFilterCache
from elasticsearch_tpu.rest.controller import (RestRequest,
                                               build_rest_controller)
from elasticsearch_tpu.search.request_cache import (ShardRequestCache,
                                                    cache_policy,
                                                    request_fingerprint)

from .harness import TestCluster

pytestmark = pytest.mark.caching


# ---------------------------------------------------------------------------
# fingerprint + policy units
# ---------------------------------------------------------------------------


class TestFingerprint:
    def test_key_order_invariant(self):
        a = {"query": {"match": {"body": "x"}}, "size": 0, "from": 0}
        b = {"from": 0, "size": 0, "query": {"match": {"body": "x"}}}
        assert request_fingerprint(a) == request_fingerprint(b)

    def test_volatile_knobs_do_not_change_identity(self):
        base = {"query": {"match": {"body": "x"}}, "size": 0}
        assert request_fingerprint(base) == request_fingerprint(
            {**base, "profile": True, "timeout": "50ms",
             "request_cache": True})

    def test_semantic_changes_change_identity(self):
        base = {"query": {"match": {"body": "x"}}, "size": 0}
        for variant in (
            {**base, "size": 5},
            {**base, "from": 10},
            {**base, "query": {"match": {"body": "y"}}},
            {**base, "aggs": {"m": {"max": {"field": "n"}}}},
            {**base, "sort": [{"n": "asc"}]},
        ):
            assert request_fingerprint(variant) != request_fingerprint(base)

    def test_policy_size_zero_default_and_overrides(self):
        assert cache_policy({"query": {}, "size": 0})
        assert not cache_policy({"query": {}, "size": 10})
        assert not cache_policy({"query": {}})  # size defaults to 10
        assert cache_policy({"query": {}, "size": 10, "request_cache": True})
        assert not cache_policy({"query": {}, "size": 0,
                                 "request_cache": False})


# ---------------------------------------------------------------------------
# request-cache units: LRU bound, breaker accounting, invalidation
# ---------------------------------------------------------------------------


def _svc(budget="1mb"):
    return CircuitBreakerService(Settings.from_flat(
        {"indices.breaker.total_budget": budget}))


class TestShardRequestCacheUnits:
    def test_store_hit_and_breaker_accounting(self):
        svc = _svc()
        rc = ShardRequestCache(Settings.EMPTY, breaker=svc.breaker("request"),
                               total_budget=1 << 20)
        key = ("i", 0, 1, "fp")
        assert rc.get(key) is None
        assert rc.put(key, b"x" * 100)
        assert svc.breaker("request").used == 100 + rc.ENTRY_OVERHEAD
        assert rc.get(key) == b"x" * 100
        st = rc.stats()
        assert st["hits"] == 1 and st["misses"] == 1 and st["stores"] == 1

    def test_lru_eviction_releases_breaker(self):
        svc = _svc()
        rc = ShardRequestCache(
            Settings.from_flat({"indices.requests.cache.size": "2kb"}),
            breaker=svc.breaker("request"), total_budget=1 << 20)
        for i in range(10):
            assert rc.put(("i", 0, 1, f"fp{i}"), b"v" * 512)
        st = rc.stats()
        assert st["evictions"] > 0
        assert st["memory_size_in_bytes"] <= rc.size_bytes
        # breaker tracks exactly the resident bytes
        assert svc.breaker("request").used == st["memory_size_in_bytes"]
        # oldest entries gone, newest present
        assert rc.get(("i", 0, 1, "fp0")) is None
        assert rc.get(("i", 0, 1, "fp9")) is not None

    def test_breaker_trip_skips_store(self):
        svc = _svc(budget="4kb")  # request child = 60% of 70% parent
        rc = ShardRequestCache(
            Settings.from_flat({"indices.requests.cache.size": "1mb"}),
            breaker=svc.breaker("request"), total_budget=1 << 20)
        # fill the breaker so the store trips; incompressible bytes — a
        # compressible value would (correctly) deflate under the floor and
        # fit, which is the compression feature, not the trip under test
        import os as _os
        svc.breaker("request").add_estimate_and_maybe_break(1500, "pin")
        assert not rc.put(("i", 0, 1, "fp"), _os.urandom(1200))
        assert rc.stats()["rejections"] == 1
        assert rc.get(("i", 0, 1, "fp")) is None
        svc.breaker("request").release(1500)
        assert svc.breaker("request").used == 0

    def test_view_invalidation_is_selective(self):
        rc = ShardRequestCache(Settings.EMPTY, total_budget=1 << 20)
        rc.put(("i", 0, 1, "a"), b"old")
        rc.put(("i", 0, 2, "a"), b"new")
        rc.put(("i", 1, 1, "a"), b"other-shard")
        rc.put(("j", 0, 1, "a"), b"other-index")
        assert rc.invalidate_shard("i", 0, current_view=2) == 1
        assert rc.get(("i", 0, 2, "a")) == b"new"
        assert rc.get(("i", 1, 1, "a")) == b"other-shard"
        assert rc.get(("j", 0, 1, "a")) == b"other-index"
        # shard removal drops every view
        assert rc.invalidate_shard("i", 0, current_view=None) == 1
        assert rc.stats()["invalidations"] == 2

    def test_clear_drains_to_zero(self):
        svc = _svc()
        rc = ShardRequestCache(Settings.EMPTY, breaker=svc.breaker("request"),
                               total_budget=1 << 20)
        for i in range(5):
            rc.put(("i", 0, 1, f"fp{i}"), b"v" * 64)
        assert svc.breaker("request").used > 0
        rc.clear()
        assert rc.stats()["memory_size_in_bytes"] == 0
        assert rc.stats()["entries"] == 0
        assert svc.breaker("request").used == 0

    def test_disabled_by_setting(self):
        rc = ShardRequestCache(Settings.from_flat(
            {"indices.requests.cache.enable": "false"}))
        assert rc.enabled is False


# ---------------------------------------------------------------------------
# filter-cache units: sighting promotion, shared-holder eviction
# ---------------------------------------------------------------------------


class _FakeSeg:
    def __init__(self):
        self._device_cache = {}


class TestDeviceFilterCacheUnits:
    def test_second_sighting_promotes(self):
        import numpy as np

        svc = _svc()
        fc = DeviceFilterCache(Settings.EMPTY,
                               breaker=svc.breaker("fielddata"))
        seg = _FakeSeg()
        mask = np.zeros(128, dtype=bool)
        mask[3] = True
        assert fc.lookup(seg, "term:f:v") is None  # sighting 1
        assert fc.maybe_store(seg, "term:f:v", mask) is None  # still cold
        assert fc.lookup(seg, "term:f:v") is None  # sighting 2
        row = fc.maybe_store(seg, "term:f:v", mask)
        assert row is not None
        assert svc.breaker("fielddata").used == mask.nbytes
        got = fc.lookup(seg, "term:f:v")
        assert got is row
        st = fc.stats()
        assert st["builds"] == 1 and st["hits"] == 1 and st["misses"] == 2
        assert st["memory_size_in_bytes"] == mask.nbytes

    def test_shared_holder_survives_tombstone_view(self):
        """with_deletes shallow-copies _device_cache: the successor view
        SHARES the filter-mask holder, so dropping the predecessor segment
        must NOT evict masks the live view still serves."""
        import numpy as np

        svc = _svc()
        fc = DeviceFilterCache(Settings.EMPTY,
                               breaker=svc.breaker("fielddata"))
        old = _FakeSeg()
        mask = np.ones(128, dtype=bool)
        fc.lookup(old, "k")
        fc.lookup(old, "k")
        assert fc.maybe_store(old, "k", mask) is not None
        new = _FakeSeg()
        new._device_cache = dict(old._device_cache)  # the with_deletes copy
        assert fc.evict_dropped([old], [new]) == 0  # holder still referenced
        assert fc.lookup(new, "k") is not None
        assert svc.breaker("fielddata").used == mask.nbytes
        # now the view drops it for real (merge) — bytes come back and the
        # dead holder refuses re-population from stale searchers
        assert fc.evict_dropped([new], []) == 1
        assert svc.breaker("fielddata").used == 0
        assert fc.maybe_store(new, "k", mask) is None
        assert fc.stats()["memory_size_in_bytes"] == 0

    def test_breaker_trip_serves_host_mask(self):
        import numpy as np

        svc = _svc(budget="1kb")
        fc = DeviceFilterCache(Settings.EMPTY,
                               breaker=svc.breaker("fielddata"))
        seg = _FakeSeg()
        big = np.zeros(1 << 20, dtype=bool)
        fc.lookup(seg, "k")
        fc.lookup(seg, "k")
        assert fc.maybe_store(seg, "k", big) is None  # tripped, not stored
        assert fc.stats()["rejections"] == 1
        assert svc.breaker("fielddata").used == 0


# ---------------------------------------------------------------------------
# affinity units: rendezvous within the spread set, health dominance
# ---------------------------------------------------------------------------


def _copies(n=3, index="i", shard=0):
    return [ShardRouting(index, shard, f"n{i + 1}", i == 0, STARTED)
            for i in range(n)]


def _warm(sel, copies, seconds=0.01, n=None):
    for _ in range(n if n is not None else sel.min_samples):
        for c in copies:
            sel.observe(c, seconds)


class TestAffinityRouting:
    def test_same_fingerprint_same_copy(self):
        sel = AdaptiveReplicaSelector(Settings.from_flat(
            {"search.adaptive.min_samples": 2,
             "search.adaptive.probe_every": 10**9}))
        copies = _copies(3)
        _warm(sel, copies)
        fp = request_fingerprint({"query": {"match": {"b": "hot"}},
                                  "size": 0})
        picks = {sel.select(copies, affinity=fp).node_id for _ in range(20)}
        assert len(picks) == 1
        assert sel.stats()["selections"]["affinity"] >= 20

    def test_different_fingerprints_spread(self):
        sel = AdaptiveReplicaSelector(Settings.from_flat(
            {"search.adaptive.min_samples": 2,
             "search.adaptive.probe_every": 10**9}))
        copies = _copies(3)
        _warm(sel, copies)
        targets = {sel.select(
            copies,
            affinity=request_fingerprint({"q": i})).node_id
            for i in range(32)}
        assert len(targets) >= 2  # rendezvous partitions the fingerprints

    def test_health_dominates_affinity(self):
        """The affinity target going sick moves the fingerprint to the next
        healthy copy — and recovery moves it back (rendezvous stability)."""
        sel = AdaptiveReplicaSelector(Settings.from_flat(
            {"search.adaptive.min_samples": 2,
             "search.adaptive.probe_every": 10**9}))
        copies = _copies(3)
        _warm(sel, copies)
        fp = request_fingerprint({"query": {"match": {"b": "hot"}},
                                  "size": 0})
        home = sel.select(copies, affinity=fp)
        # the home copy turns slow: its score leaves the spread set
        for _ in range(6):
            sel.observe(home, 2.0)
        moved = sel.select(copies, affinity=fp)
        assert moved.node_id != home.node_id
        # recovery: fast samples decay the EWMA back into the spread
        for _ in range(40):
            sel.observe(home, 0.01)
        back = sel.select(copies, affinity=fp)
        assert back.node_id == home.node_id

    def test_probe_turns_still_fire_with_affinity(self):
        sel = AdaptiveReplicaSelector(Settings.from_flat(
            {"search.adaptive.min_samples": 2,
             "search.adaptive.probe_every": 4}))
        copies = _copies(3)
        _warm(sel, copies)
        sick = copies[2]
        for _ in range(6):
            sel.observe(sick, 5.0)  # excluded from the spread set
        fp = request_fingerprint({"q": "hot"})
        before = sel.stats()["probes"]
        for _ in range(16):
            sel.select(copies, affinity=fp)
        assert sel.stats()["probes"] > before

    def test_cold_group_round_robins_despite_affinity(self):
        routing = OperationRouting(selector=AdaptiveReplicaSelector(
            Settings.from_flat({"search.adaptive.min_samples": 5})))
        copies = _copies(3)
        picks = {routing._pick(copies, affinity="fp").node_id
                 for _ in range(9)}
        assert len(picks) == 3  # RR warms every copy; affinity waits

    def test_selectorless_rendezvous_is_stable(self):
        routing = OperationRouting(selector=None)
        copies = _copies(3)
        fp = request_fingerprint({"q": "x"})
        picks = {routing._pick(copies, affinity=fp).node_id
                 for _ in range(10)}
        assert len(picks) == 1
        # and None affinity keeps plain round-robin
        rr = {routing._pick(copies).node_id for _ in range(6)}
        assert len(rr) == 3


# ---------------------------------------------------------------------------
# live cluster: hit path, invalidation-under-writes, clear, observability
# ---------------------------------------------------------------------------


HOT = {"query": {"match": {"body": "alpha"}}, "size": 0,
       "aggs": {"m": {"max": {"field": "n"}}}}
HOT_HITS = {"query": {"match": {"body": "alpha"}}, "size": 5,
            "request_cache": True}
FILTERED = {"query": {"filtered": {"query": {"match": {"body": "alpha"}},
                                   "filter": {"term": {"tag": "t1"}}}},
            "size": 8}


def _boot(tmp_path, nodes=1, settings=None):
    # the warmer's post-refresh re-prime (warmer.py, ISSUE 14) would
    # asynchronously re-store hot entries this suite populates/invalidates
    # BY HAND — these tests pin the raw tier mechanics, so the re-prime is
    # off here (tests/test_writes.py covers the warmed behavior)
    cluster = TestCluster(n_nodes=nodes, data_root=tmp_path, seed=11,
                          settings={"indices.warmer.enabled": "false",
                                    **(settings or {})})
    cluster.start()
    c = cluster.client()
    c.create_index("hot", {"settings": {"number_of_shards": 1,
                                        "number_of_replicas": nodes - 1}})
    cluster.ensure_green("hot")
    for i in range(60):
        c.index("hot", "doc",
                {"body": f"alpha beta{i % 4}", "n": i, "tag": f"t{i % 3}"},
                id=str(i))
    c.refresh("hot")
    return cluster, c


class TestLiveRequestCache:
    def test_hit_path_zero_launches_zero_recompiles_zero_syncs(
            self, tmp_path, monkeypatch):
        """The acceptance pin: a warmed hot-query loop is served entirely
        from the request cache — execute_query_phase never runs, the batcher
        never launches, no pending handle syncs, and the loop holds 0
        compiles under hard transfer_guard("disallow")."""
        import jax

        from elasticsearch_tpu import actions as actions_mod
        from elasticsearch_tpu.common.jaxenv import sanitize
        from elasticsearch_tpu.search import execute as execute_mod
        from elasticsearch_tpu.search.service import SERVING_COUNTERS

        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            exec_calls = []
            orig_exec = actions_mod.execute_query_phase
            monkeypatch.setattr(
                actions_mod, "execute_query_phase",
                lambda *a, **k: (exec_calls.append(1),
                                 orig_exec(*a, **k))[1])
            sync_calls = []
            orig_sync = execute_mod._PendingFlat.sync
            monkeypatch.setattr(
                execute_mod._PendingFlat, "sync",
                lambda self: (sync_calls.append(1), orig_sync(self))[1])

            for body in (HOT, HOT_HITS):
                warm = c.search("hot", body)  # miss + store
                again = c.search("hot", body)  # hit
                assert again["hits"]["total"] == warm["hits"]["total"]
            assert node.request_cache.stats()["hits"] >= 2

            exec_calls.clear()
            sync_calls.clear()
            serving_before = dict(SERVING_COUNTERS)
            launches_before = node.search_batcher.stats()["launches"]
            results = []
            jax.config.update("jax_transfer_guard", "disallow")
            try:
                with sanitize(max_compiles=0, transfers="disallow") as rep:
                    for _ in range(10):
                        results.append(c.search("hot", HOT))
                        results.append(c.search("hot", HOT_HITS))
            finally:
                jax.config.update("jax_transfer_guard", "allow")
            assert rep.compiles == 0, rep.compile_events
            assert exec_calls == [], "hit path reached execute_query_phase"
            assert sync_calls == [], "hit path synced"
            assert node.search_batcher.stats()["launches"] == launches_before
            assert dict(SERVING_COUNTERS) == serving_before
            # every cached answer is the warmed answer
            for r in results[::2]:
                assert r["aggregations"]["m"]["value"] == 59.0
            for r in results[1::2]:
                assert len(r["hits"]["hits"]) == 5
        finally:
            cluster.close()

    def test_writes_invalidate_and_clear_drains_breaker(self, tmp_path):
        """index → search → hit → bulk write + refresh → the next search
        sees the new doc (a stale hit is NEVER served) → _cache/clear
        returns the request breaker to 0."""
        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            r1 = c.search("hot", HOT)
            assert r1["hits"]["total"] == 60
            r2 = c.search("hot", HOT)
            assert r2["hits"]["total"] == 60
            st = node.request_cache.stats()
            assert st["hits"] >= 1 and st["stores"] >= 1

            c.bulk([{"action": {"index": {"_index": "hot", "_type": "doc",
                                          "_id": "new1"}},
                     "source": {"body": "alpha fresh", "n": 100,
                                "tag": "t9"}}])
            c.refresh("hot")
            r3 = c.search("hot", HOT)
            assert r3["hits"]["total"] == 61, "stale cached partial served!"
            assert r3["aggregations"]["m"]["value"] == 100.0
            assert node.request_cache.stats()["invalidations"] >= 1

            # repopulate, then clear both tiers over REST with selectors
            c.search("hot", HOT)
            c.search("hot", FILTERED)
            c.search("hot", FILTERED)
            c.search("hot", FILTERED)
            req_br = node.breakers.breaker("request")
            assert req_br.used > 0
            rc = build_rest_controller(node)
            resp = rc.dispatch(RestRequest(
                method="POST", path="/hot/_cache/clear",
                params={"request": "true", "filter": "true"}, body=None))
            assert resp.status == 200
            assert resp.body["_shards"]["successful"] >= 1
            assert node.request_cache.stats()["memory_size_in_bytes"] == 0
            assert node.filter_cache.stats()["memory_size_in_bytes"] == 0
            assert req_br.used == 0
            assert node.breakers.breaker("fielddata").used == 0
            # the node still answers correctly after the clear
            r4 = c.search("hot", HOT)
            assert r4["hits"]["total"] == 61
        finally:
            cluster.close()

    def test_opt_out_and_default_policy_live(self, tmp_path):
        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            stores0 = node.request_cache.stats()["stores"]
            # hit-bearing without opt-in: never cached
            body = {"query": {"match": {"body": "alpha"}}, "size": 5}
            c.search("hot", body)
            c.search("hot", body)
            assert node.request_cache.stats()["stores"] == stores0
            # size==0 with explicit opt-OUT: never cached
            c.search("hot", {**HOT, "request_cache": False})
            assert node.request_cache.stats()["stores"] == stores0
        finally:
            cluster.close()

    def test_profile_records_cache_events_and_still_executes(self, tmp_path):
        cluster, c = _boot(tmp_path)
        try:
            c.search("hot", HOT)  # store
            r = c.search("hot", {**HOT, "profile": True})
            shard = r["profile"]["shards"][0]
            events = [e for e in shard["cache"]["events"]
                      if e["kind"] == "request_cache"]
            assert events and events[0]["cache"] == "hit", shard["cache"]
            # profiled requests execute for real: the plan section is present
            assert shard["plan"]["outcome"] != "unknown"
            # a profiled MISS records miss + store
            r2 = c.search("hot", {"query": {"match": {"body": "beta1"}},
                                  "size": 0, "profile": True})
            ev2 = [e for e in r2["profile"]["shards"][0]["cache"]["events"]
                   if e["kind"] == "request_cache"]
            kinds = [e["cache"] for e in ev2]
            assert kinds == ["miss", "store"], kinds
        finally:
            cluster.close()


class TestLiveFilterCache:
    def test_warm_hits_bitwise_identical_and_evicted_on_merge(
            self, tmp_path):
        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            cold = c.search("hot", FILTERED)
            st0 = node.filter_cache.stats()
            warm1 = c.search("hot", FILTERED)  # 2nd sighting: builds
            warm2 = c.search("hot", FILTERED)  # resident hit
            st = node.filter_cache.stats()
            assert st["builds"] > st0["builds"]
            assert st["hits"] >= 1
            # bitwise-identical hits + scores cold vs resident-mask warm
            for warm in (warm1, warm2):
                assert warm["hits"]["total"] == cold["hits"]["total"]
                assert [(h["_id"], h["_score"]) for h in
                        warm["hits"]["hits"]] == \
                    [(h["_id"], h["_score"]) for h in cold["hits"]["hits"]]
            assert node.breakers.breaker("fielddata").used > 0
            # optimize merges segments away → masks evicted with them,
            # breaker drains, and the query still answers identically
            c.index("hot", "doc", {"body": "alpha tail", "n": 200,
                                   "tag": "t1"}, id="tail")
            c.refresh("hot")
            c.optimize("hot")
            st2 = node.filter_cache.stats()
            assert st2["evictions"] > st0["evictions"]
            after = c.search("hot", FILTERED)
            assert after["hits"]["total"] == cold["hits"]["total"] + 1
        finally:
            cluster.close()


FS_ROWS = {"query": {"function_score": {
    "query": {"match_all": {}},
    "functions": [{"field_value_factor": {"field": "n", "factor": 2,
                                          "modifier": "log1p"}}],
    "boost_mode": "replace"}}, "size": 8}


def _ids_scores(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


class TestLiveFunctionRows:
    """The store's second key space on a live node: the rows a
    function_score launch reads of a segment (execute._fs_segment_rows)."""

    def test_resident_from_the_second_sighting_and_cleared_over_rest(
            self, tmp_path):
        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            def rows():
                return node.filter_cache.stats()["function_rows"]

            fielddata = node.breakers.breaker("fielddata")
            cold = c.search("hot", FS_ROWS)  # first sighting: counted
            used0 = fielddata.used
            assert rows()["entries"] == 0 and rows()["misses"] >= 1
            stored = c.search("hot", FS_ROWS)  # second: put once, published
            held = rows()["memory_size_in_bytes"]
            assert rows()["entries"] == rows()["builds"] >= 1 and held > 0
            assert fielddata.used == used0 + held
            hits0 = rows()["hits"]
            resident = c.search("hot", FS_ROWS)
            assert rows()["hits"] > hits0 and rows()["builds"] == rows()["entries"]
            assert _ids_scores(stored) == _ids_scores(resident) == \
                _ids_scores(cold)
            # the masks' own tallies never moved: match_all has no mask
            assert node.filter_cache.stats()["masks"] == 0
            assert node.filter_cache.stats()["memory_size_in_bytes"] == 0

            resp = build_rest_controller(node).dispatch(RestRequest(
                method="POST", path="/hot/_cache/clear",
                params={"filter": "true"}, body=None))
            assert resp.status == 200
            assert rows()["entries"] == 0
            assert rows()["memory_size_in_bytes"] == 0
            assert rows()["evictions"] >= 1
            assert fielddata.used == used0
            # cleared, not dead: the spec earns its entry again
            assert _ids_scores(c.search("hot", FS_ROWS)) == _ids_scores(cold)
            c.search("hot", FS_ROWS)
            assert rows()["entries"] >= 1
        finally:
            cluster.close()

    def test_rows_outlive_a_delete_and_leave_with_their_segment(
            self, tmp_path):
        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            def rows():
                return node.filter_cache.stats()["function_rows"]

            cold = c.search("hot", FS_ROWS)
            c.search("hot", FS_ROWS)
            entries = rows()["entries"]
            assert entries >= 1
            # a tombstone view shares its predecessor's holder: the resident
            # row serves on (it holds a value for every document; liveness is
            # the kernel's own gate) and the deleted document is gone
            top = cold["hits"]["hits"][0]["_id"]
            c.delete("hot", "doc", top)
            c.refresh("hot")
            hits0, evictions0 = rows()["hits"], rows()["evictions"]
            after = c.search("hot", FS_ROWS)
            assert rows()["hits"] > hits0 and rows()["entries"] == entries
            assert rows()["evictions"] == evictions0
            assert after["hits"]["total"] == cold["hits"]["total"] - 1
            assert _ids_scores(after)[:7] == _ids_scores(cold)[1:]
            # a merge drops the segment: its rows go with it, bytes and all
            c.index("hot", "doc", {"body": "alpha tail", "n": 200,
                                   "tag": "t1"}, id="tail")
            c.refresh("hot")
            c.optimize("hot")
            assert rows()["evictions"] >= evictions0 + entries
            assert rows()["entries"] == 0
            assert rows()["memory_size_in_bytes"] == 0
            merged = c.search("hot", FS_ROWS)
            assert merged["hits"]["hits"][0]["_id"] == "tail"
            assert merged["hits"]["total"] == cold["hits"]["total"]
        finally:
            cluster.close()


class TestObservabilitySurfaces:
    def test_nodes_stats_cat_and_prometheus(self, tmp_path):
        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            c.search("hot", HOT)
            c.search("hot", HOT)
            c.search("hot", FILTERED)
            c.search("hot", FILTERED)
            rc = build_rest_controller(node)
            r = rc.dispatch(RestRequest(method="GET", path="/_nodes/stats",
                                        params={}))
            assert r.status == 200
            indices = r.body["nodes"][node.node_id]["indices"]
            for tier, keys in (
                ("request_cache", ("memory_size_in_bytes", "hits", "misses",
                                   "stores", "evictions", "invalidations",
                                   "hit_rate", "entries")),
                ("filter_cache", ("memory_size_in_bytes", "hits", "misses",
                                  "builds", "evictions", "hit_rate",
                                  "masks")),
            ):
                assert tier in indices, sorted(indices)
                for k in keys:
                    assert k in indices[tier], (tier, k)
            assert indices["request_cache"]["hits"] >= 1
            # narrow metric filter still works with the tier keys inside
            r = rc.dispatch(RestRequest(method="GET",
                                        path="/_nodes/stats/indices",
                                        params={}))
            assert r.status == 200
            assert "request_cache" in r.body["nodes"][node.node_id]["indices"]

            r = rc.dispatch(RestRequest(method="GET", path="/_cat/caches",
                                        params={"v": ""}))
            assert r.status == 200
            lines = r.body.strip().splitlines()
            assert lines[0].split()[:3] == ["host", "ip", "tier"]
            tiers = {ln.split()[2] for ln in lines[1:]}
            assert tiers == {"request", "filter"}
            r = rc.dispatch(RestRequest(method="GET", path="/_cat/caches",
                                        params={"help": ""}))
            assert r.status == 200 and "tier" in r.body

            r = rc.dispatch(RestRequest(method="GET",
                                        path="/_prometheus/metrics",
                                        params={}))
            assert r.status == 200
            for fam in ("estpu_request_cache_hits_total",
                        "estpu_request_cache_misses_total",
                        "estpu_request_cache_stores_total",
                        "estpu_request_cache_evictions_total",
                        "estpu_request_cache_bytes",
                        "estpu_filter_cache_hits_total",
                        "estpu_filter_cache_builds_total",
                        "estpu_filter_cache_bytes"):
                assert f"# TYPE {fam} " in r.body, fam
        finally:
            cluster.close()

    def test_trace_tags_cache_served_shard(self, tmp_path):
        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            rc = build_rest_controller(node)
            rc.dispatch(RestRequest(method="POST", path="/hot/_search",
                                    params={}, body=HOT))
            r = rc.dispatch(RestRequest(method="POST", path="/hot/_search",
                                        params={"trace": "true"}, body=HOT))
            assert r.status == 200

            def walk(n):
                yield n
                for ch in n.get("children", []):
                    yield from walk(ch)

            spans = [s for s in walk(r.body["trace"]["tree"])
                     if s.get("name") == "shard"]
            assert spans, r.body["trace"]
            assert any(s.get("tags", {}).get("request_cache") == "hit"
                       for s in spans), spans
        finally:
            cluster.close()


class TestLiveAffinity:
    def test_replica_affinity_and_hit_rate_piggyback(self, tmp_path):
        """2-node, 1 shard + 1 replica: warmed cache-eligible traffic for ONE
        fingerprint lands on one copy (selections.affinity moves), and the
        piggybacked per-copy request-cache hit rate surfaces in
        /_nodes/stats adaptive_routing."""
        cluster, c = _boot(tmp_path, nodes=2)
        coord = next(iter(cluster.nodes.values()))
        try:
            sel = coord.adaptive_routing
            # warm every copy's stats with DIVERSE eligible traffic (RR)
            for i in range(24):
                c2 = coord.client()
                c2.search("hot", {"query": {"match": {"body": f"beta{i % 4}"}},
                                  "size": 0})
                copies = sel.stats()["copies"]
                if len(copies) >= 2 and all(
                        v["samples"] >= sel.min_samples
                        for v in copies.values()):
                    break
            before = sel.stats()["selections"]["affinity"]
            served = set()
            for _ in range(12):
                coord.client().search("hot", HOT)
            after = sel.stats()
            assert after["selections"]["affinity"] > before
            # the hot fingerprint concentrated on one copy: at most one
            # copy's selected count moved by more than the probe floor
            served = {k: v["selected"] for k, v in after["copies"].items()}
            assert len(served) == 2
            # piggybacked hit rate reported per copy
            assert all("rc_hit_rate" in v for v in after["copies"].values())
            assert any(v["rc_hit_rate"] > 0 for v in
                       after["copies"].values()), after["copies"]
        finally:
            cluster.close()


# ---------------------------------------------------------------------------
# lint: the new cache modules stay clean
# ---------------------------------------------------------------------------


def test_cache_modules_scan_clean():
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.tpulint import lint_paths

    paths = [os.path.join(repo, "elasticsearch_tpu", p) for p in (
        "search/request_cache.py", "ops/device_index.py",
        "search/execute.py", "cluster/routing.py", "cluster/stats.py",
        "index/engine.py", "indices_service.py",
    )]
    findings = lint_paths(paths)
    assert not findings, [f.to_dict() for f in findings]
