"""Search Profile API (common/profile.py, PR 9).

Covers: ProfileCollector units (phase accumulation, additive per-segment
counters, event/reservation caps), the fallback-reason vocabulary
(execute.lower_fallback_reason), the live-cluster acceptance path —
`?profile=true` against a multi-shard cluster returns a merged `profile`
section with per-shard per-segment path/counters/cache attribution, the
explicit batcher bypass (`reason: profile`), precise per-phase device timings
— the mesh path's plan/repack attribution, the real `/_segments` +
`/_cat/segments` views (packed-layout report), the `_cat` table renderer
contract (`?help`, `?v`, `?h=` with aliases), the rewritten two-snapshot
`hot_threads`, tracer ring-eviction counters (+ Prometheus family), the
zero-new-syncs/zero-recompile unprofiled invariant under hard
transfer_guard("disallow"), and a tpulint-clean scan over every instrumented
file."""

import threading
import time

import pytest

from elasticsearch_tpu.common import profile as profiling
from elasticsearch_tpu.common.profile import ProfileCollector
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.common.tracing import Tracer
from elasticsearch_tpu.rest.controller import RestRequest, build_rest_controller

from .harness import TestCluster

WORDS = ["quick", "brown", "fox", "lazy", "dog", "summer", "red", "bear"]


# ---------------------------------------------------------------------------
# collector units
# ---------------------------------------------------------------------------


class TestCollectorUnits:
    def test_current_is_none_off_thread(self):
        assert profiling.current() is None
        prof = ProfileCollector(node="n", index="i", shard=3)
        with profiling.activate(prof):
            assert profiling.current() is prof
        assert profiling.current() is None

    def test_phases_accumulate_and_round(self):
        prof = ProfileCollector()
        prof.phase_s("lower", 0.001)
        prof.phase_s("lower", 0.002)
        d = prof.to_dict()
        assert d["phases_ms"]["lower"] == pytest.approx(3.0, abs=0.01)
        assert d["phases_ms"]["total"] >= 0

    def test_segment_counters_additive_identity_overwrites(self):
        prof = ProfileCollector()
        prof.segment(7, docs=100, path="sparse_composed", blocks_scanned=3,
                     ms=1.0)
        prof.segment(7, docs=100, path="dense_filtered", blocks_scanned=2,
                     ms=0.5)
        (seg,) = prof.to_dict()["segments"]
        assert seg["generation"] == 7
        assert seg["blocks_scanned"] == 5  # additive across launches
        assert seg["ms"] == pytest.approx(1.5, abs=0.01)
        assert seg["docs"] == 100  # identity overwrites, not 200
        assert seg["path"] == "dense_filtered"  # last launch wins

    def test_event_and_reservation_caps(self):
        prof = ProfileCollector()
        for i in range(ProfileCollector.MAX_EVENTS + 5):
            prof.event("scratch", cache="reuse")
        for i in range(ProfileCollector.MAX_RESERVATIONS + 3):
            prof.breaker_reserve("request", "<x>", 10)
        d = prof.to_dict()
        assert len(d["cache"]["events"]) == ProfileCollector.MAX_EVENTS
        assert d["cache"]["dropped"] == 5
        assert len(d["breakers"]["reservations"]) == \
            ProfileCollector.MAX_RESERVATIONS
        assert d["breakers"]["dropped"] == 3
        # the byte total keeps counting past the cap
        assert d["breakers"]["reserved_bytes_total"] == \
            (ProfileCollector.MAX_RESERVATIONS + 3) * 10

    def test_first_writer_wins_for_plan_outcome_fallback(self):
        prof = ProfileCollector()
        prof.outcome("device_sparse")
        prof.outcome("host")
        prof.set_plan({"query_type": "A"})
        prof.set_plan({"query_type": "B"})
        prof.fallback("numeric_term")
        prof.fallback("fuzzy_match")
        d = prof.to_dict()
        assert d["plan"]["outcome"] == "device_sparse"
        assert d["plan"]["query_type"] == "A"
        assert d["plan"]["fallback_reason"] == "numeric_term"


# ---------------------------------------------------------------------------
# fallback-reason vocabulary
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def shard_ctx(tmp_path_factory):
    from elasticsearch_tpu.index import Engine
    from elasticsearch_tpu.mapper import MapperService
    from elasticsearch_tpu.search import ShardContext
    from elasticsearch_tpu.search.similarity import SimilarityService

    settings = Settings.from_flat({})
    svc = MapperService(settings)
    svc.put_mapping("doc", {"doc": {"properties": {"n": {"type": "long"}}}})
    e = Engine(str(tmp_path_factory.mktemp("profctx") / "shard0"), svc)
    for i in range(30):
        e.index("doc", str(i),
                {"body": f"{WORDS[i % 8]} {WORDS[(i + 1) % 8]}", "n": i})
    e.refresh()
    return ShardContext(e.acquire_searcher(), svc,
                        SimilarityService(settings, mapper_service=svc))


class TestFallbackReasons:
    def _reason(self, ctx, qdict):
        from elasticsearch_tpu.search import parse_query
        from elasticsearch_tpu.search.execute import (lower_flat,
                                                      lower_fallback_reason)

        q = parse_query(qdict)
        assert lower_flat(q, ctx) is None, "query unexpectedly lowered flat"
        return lower_fallback_reason(q, ctx)

    def test_vocabulary(self, shard_ctx):
        # an exact phrase lowers (tests/test_device_phrase.py); the phrase
        # forms that stay on the host have reasons of their own
        assert self._reason(shard_ctx, {"match_phrase": {"body": {
            "query": "a b", "slop": 1}}}) == "sloppy_phrase"
        assert self._reason(shard_ctx, {"match_phrase_prefix": {
            "body": "a b"}}) == "phrase_prefix"
        # a prefix, wildcard or regexp lowers (tests/test_device_multiterm.py);
        # the multi-term forms that stay on the host have reasons of their own
        assert self._reason(shard_ctx, {"prefix": {"body": {
            "value": "qui", "rewrite": "scoring_boolean"}}}) == "scoring_rewrite"
        assert self._reason(shard_ctx, {"wildcard": {"_uid": "doc#*"}}) \
            == "host_only_field"
        assert self._reason(shard_ctx, {"fuzzy": {"body": "quik"}}) \
            == "fuzzy_query"
        assert self._reason(shard_ctx, {"span_multi": {"match": {
            "prefix": {"body": "qui"}}}}) == "span_multi"
        assert self._reason(shard_ctx, {"ids": {"values": ["1"]}}) \
            == "unsupported_query:IdsQuery"
        assert self._reason(
            shard_ctx, {"match": {"body": {"query": "quik",
                                           "fuzziness": "AUTO"}}}) \
            == "fuzzy_match"
        assert self._reason(
            shard_ctx, {"bool": {"must": [{"term": {"body": "quick"}}],
                                 "filter": {"term": {"body": "fox"}}}}) \
            == "bool_filter_clause"
        assert self._reason(
            shard_ctx, {"bool": {"must": [
                {"match_phrase": {"body": "quick brown"}}]}}) \
            == "non_term_subclause"
        assert self._reason(
            shard_ctx, {"function_score": {
                "query": {"range": {"n": {"gte": 3}}},
                "functions": [
                    {"script_score": {"script": "_score * 2"}},
                    {"script_score": {"script": "_score + 1"}}]}}) \
            == "function_score_ineligible"
        assert self._reason(
            shard_ctx, {"function_score": {
                "query": {"match_phrase": {"body": "quick brown"}},
                "functions": [{"weight": 2.0}]}}) == "non_flat_subquery"


    @pytest.mark.parametrize("qdict", [
        {"term": {"n": 3}},
        {"bool": {"must_not": [{"term": {"body": "quick"}}]}},
        {"match_all": {}},
        {"filtered": {"query": {"match_all": {}},
                      "filter": {"range": {"n": {"gte": 3}}}}},
        {"function_score": {"query": {"range": {"n": {"gte": 3}}},
                            "functions": [{"weight": 2.0}]}},
    ], ids=["numeric_term", "must_not_only", "match_all", "filtered_match_all",
            "function_score_over_range"])
    def test_unscored_plans_lower(self, shard_ctx, qdict):
        """What used to fall back as `numeric_term` / `must_not_only` lowers to
        a plan with no scoring clause, and the plan's profile says so."""
        from elasticsearch_tpu.search import parse_query
        from elasticsearch_tpu.search.execute import lower_flat, plan_profile

        q = parse_query(qdict)
        plan = lower_flat(q, shard_ctx)
        assert plan is not None and plan.const is not None
        shape = plan_profile(plan, q)
        assert shape["unscored"] is True and shape["clauses"] == []
        assert shape["function_score"] == (
            "rows" if "function_score" in qdict else None)


# ---------------------------------------------------------------------------
# live cluster: the ?profile=true contract (transport path, 2 nodes)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def live(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("profile")
    with TestCluster(n_nodes=2, data_root=tmp, seed=11, settings={
        # profiles must come from the per-shard transport path here; the
        # mesh path has its own fixture below
        "search.mesh.enabled": "false",
    }) as cluster:
        node = next(iter(cluster.nodes.values()))
        client = node.client()
        client.create_index("profiled", {"settings": {
            "number_of_shards": 2, "number_of_replicas": 0}})
        cluster.ensure_green("profiled")
        for i in range(60):
            client.index("profiled", "doc",
                         {"body": f"{WORDS[i % 8]} {WORDS[(i + 1) % 8]}",
                          "n": i},
                         id=str(i))
        client.refresh("profiled")
        rc = build_rest_controller(node)
        yield cluster, node, rc


SEARCH_BODY = {"query": {"match": {"body": "quick brown"}}, "size": 5}


def _search(rc, params=None, body=None):
    return rc.dispatch(RestRequest(
        method="POST", path="/profiled/_search", params=params or {},
        body=dict(body or SEARCH_BODY)))


class TestLiveProfile:
    def test_profile_true_merges_every_shard(self, live):
        _cluster, node, rc = live
        resp = _search(rc, params={"profile": "true"})
        assert resp.status == 200, resp.body
        prof = resp.body.get("profile")
        assert prof is not None and len(prof["shards"]) == 2, resp.body
        for shard in prof["shards"]:
            plan = shard["plan"]
            assert plan["outcome"] == "device_sparse", shard
            assert plan["fallback_reason"] is None
            assert {c["term"] for c in plan["clauses"]} == {"quick", "brown"}
            assert plan["msm"] == 1 and plan["coord"] is True
            # per-segment execution counters + cache attribution
            assert shard["segments"], shard
            for seg in shard["segments"]:
                assert seg["path"] == "sparse_composed"
                assert seg["tf_layout"] == "u8"
                assert seg["blocks_scanned"] >= 1
                assert seg["postings_scanned"] >= 1
                assert seg["staged_bytes"] > 0
            kinds = {(e["kind"], e["cache"])
                     for e in shard["cache"]["events"]}
            assert any(k == "packed_segment" for k, _c in kinds)
            assert any(k == "sim_tables" for k, _c in kinds)
            assert any(k == "scratch" for k, _c in kinds)
            # precise per-phase device attribution (the per-request sync)
            phases = shard["phases_ms"]
            for name in ("parse", "lower", "dispatch", "device", "pull",
                         "merge", "total"):
                assert name in phases and phases[name] >= 0, phases
            # the explicit batcher interaction
            assert shard["batcher"] == {"bypassed": True, "reason": "profile"}
            # breaker attribution: the sparse staging reservation is visible
            labels = {r["label"] for r in
                      shard["breakers"]["reservations"]}
            assert "<sparse_staging>" in labels, labels
        # the two entries are distinct shards
        assert {s["shard"] for s in prof["shards"]} == {0, 1}

    def test_profile_body_flag_equivalent(self, live):
        _cluster, _node, rc = live
        resp = _search(rc, body={**SEARCH_BODY, "profile": True})
        assert resp.status == 200
        assert len(resp.body["profile"]["shards"]) == 2

    def test_unprofiled_has_no_profile_section(self, live):
        _cluster, _node, rc = live
        resp = _search(rc)
        assert resp.status == 200
        assert "profile" not in resp.body

    def test_hits_identical_with_and_without_profile(self, live):
        _cluster, _node, rc = live
        plain = _search(rc).body
        profiled = _search(rc, params={"profile": "true"}).body
        assert profiled["hits"]["total"] == plain["hits"]["total"]
        assert [h["_id"] for h in profiled["hits"]["hits"]] == \
            [h["_id"] for h in plain["hits"]["hits"]]

    def test_host_fallback_reasons(self, live):
        _cluster, _node, rc = live
        # a sloppy phrase never lowers flat — vocabulary reason
        resp = _search(rc, params={"profile": "true"},
                       body={"query": {"match_phrase": {
                           "body": {"query": "quick brown", "slop": 1}}}})
        for shard in resp.body["profile"]["shards"]:
            assert shard["plan"]["outcome"] == "host"
            assert shard["plan"]["fallback_reason"] == "sloppy_phrase"
            assert any(s["path"] == "host" for s in shard["segments"])
        # a lowerable query forced host by a mask-needing feature
        resp = _search(rc, params={"profile": "true"},
                       body={**SEARCH_BODY, "rescore": {"query": {
                           "rescore_query": {"match": {"body": "fox"}}}}})
        for shard in resp.body["profile"]["shards"]:
            assert shard["plan"]["outcome"] == "host"
            assert shard["plan"]["fallback_reason"] == "features:rescore"

    def test_batcher_counts_profile_bypass(self, live):
        cluster, _node, rc = live
        before = [n.search_batcher.stats()["profile_bypassed"]
                  for n in cluster.nodes.values()]
        resp = _search(rc, params={"profile": "true"})
        assert resp.status == 200
        after = [n.search_batcher.stats()["profile_bypassed"]
                 for n in cluster.nodes.values()]
        assert sum(after) >= sum(before) + 2  # one bypass per shard


# ---------------------------------------------------------------------------
# mesh path: plan/repack attribution
# ---------------------------------------------------------------------------


class TestMeshProfile:
    def test_mesh_profile_attribution(self, tmp_path):
        with TestCluster(n_nodes=1, data_root=tmp_path, seed=5) as cluster:
            node = next(iter(cluster.nodes.values()))
            client = node.client()
            client.create_index("meshed", {"settings": {
                "number_of_shards": 2, "number_of_replicas": 0}})
            cluster.ensure_green("meshed")
            for i in range(40):
                client.index("meshed", "doc",
                             {"body": f"{WORDS[i % 8]} {WORDS[(i + 2) % 8]}"},
                             id=str(i))
            client.refresh("meshed")
            rc = build_rest_controller(node)
            resp = rc.dispatch(RestRequest(
                method="POST", path="/meshed/_search",
                params={"profile": "true"}, body=dict(SEARCH_BODY)))
            assert resp.status == 200, resp.body
            shards = resp.body["profile"]["shards"]
            assert len(shards) == 2
            assert {s["shard"] for s in shards} == {0, 1}
            for shard in shards:
                assert shard["plan"]["outcome"] == "mesh_spmd", shard
                mesh = shard["mesh"]
                assert mesh["shards"] == 2
                assert mesh["tf_layout"] in ("u8", "i16", "f32")
                assert mesh["resident_postings_bytes"] > 0
                assert shard["phases_ms"]["mesh_launch"] > 0
                execs = [e for e in shard["cache"]["events"]
                         if e["kind"] == "mesh_executor"]
                assert execs and execs[0]["cache"] in ("hit", "build")
                # a plain profiled mesh search skips the coalescing queue —
                # recorded exactly like the transport path's bypass
                assert shard["batcher"] == {"bypassed": True,
                                            "reason": "profile"}
            # filtered queries must report the REQUEST's shape (the mesh
            # rebinds to the inner query and applies the filter via masks)
            resp_f = rc.dispatch(RestRequest(
                method="POST", path="/meshed/_search",
                params={"profile": "true"},
                body={"query": {"filtered": {
                    "query": {"match": {"body": "quick brown"}},
                    "filter": {"term": {"body": "fox"}}}}}))
            assert resp_f.status == 200, resp_f.body
            for shard in resp_f.body["profile"]["shards"]:
                assert shard["plan"]["outcome"] == "mesh_spmd", shard
                assert shard["plan"]["query_type"] == "FilteredQuery"
                assert shard["plan"]["filtered"] is True
            # second profiled search hits the cached executor
            resp2 = rc.dispatch(RestRequest(
                method="POST", path="/meshed/_search",
                params={"profile": "true"}, body=dict(SEARCH_BODY)))
            execs = [e for e in
                     resp2.body["profile"]["shards"][0]["cache"]["events"]
                     if e["kind"] == "mesh_executor"]
            assert execs[0]["cache"] == "hit"


# ---------------------------------------------------------------------------
# /_segments + /_cat/segments (+ the _cat renderer contract)
# ---------------------------------------------------------------------------


class TestSegmentsApi:
    def test_segments_reports_packed_layout(self, live):
        cluster, _node, rc = live
        # a device search packs the segments first
        assert _search(rc).status == 200
        # /_segments is node-local (like _stats): union both nodes' views to
        # cover every shard of the 2-node cluster
        seen_shards: set = set()
        seen_packed = 0
        for n in cluster.nodes.values():
            node_rc = build_rest_controller(n)
            resp = node_rc.dispatch(RestRequest(
                method="GET", path="/_segments", params={}))
            assert resp.status == 200
            shards = resp.body["indices"]["profiled"]["shards"]
            # total counts every assigned copy CLUSTER-WIDE while the body is
            # node-local: shards hosted on the other node show up as
            # unreported (total > successful), never as silently complete
            hdr = resp.body["_shards"]
            assert hdr["total"] == 2 and hdr["failed"] == 0, hdr
            assert hdr["successful"] == len(shards), hdr
            seen_shards |= set(shards)
            for copies in shards.values():
                (copy,) = copies
                assert copy["routing"]["primary"] is True
                assert copy["num_search_segments"] == len(copy["segments"])
                for seg in copy["segments"].values():
                    assert seg["num_docs"] > 0
                    assert seg["postings"] > 0
                    assert seg["deleted_docs"] == 0
                    dev = seg["device"]
                    if dev["packed"]:
                        seen_packed += 1
                        assert dev["tf_layout"] == "u8"
                        assert dev["bytes_per_posting"] == 6
                        assert dev["resident_bytes"] > 0
                        assert dev["dense_plane"] in ("lazy", "resident")
                        assert dev["sim_tables"] is None or \
                            isinstance(dev["sim_tables"]["fields"], list)
        assert seen_shards == {"0", "1"}
        # the profiled searches above packed every serving shard copy
        assert seen_packed >= 2
        # index-scoped variant
        scoped = rc.dispatch(RestRequest(
            method="GET", path="/profiled/_segments", params={}))
        assert scoped.status == 200
        assert list(scoped.body["indices"]) == ["profiled"]

    def test_cat_segments_view(self, live):
        _cluster, _node, rc = live
        assert _search(rc).status == 200
        resp = rc.dispatch(RestRequest(method="GET", path="/_cat/segments",
                                       params={"v": ""}))
        assert resp.status == 200
        lines = resp.body.strip().splitlines()
        header, rows = lines[0].split(), lines[1:]
        assert header[:4] == ["index", "shard", "prirep", "segment"]
        assert rows and all(r.split()[0] == "profiled" for r in rows)

    def test_cat_table_renderer_contract(self, live):
        """?help lists columns, ?v adds the header, ?h= selects by name OR
        alias — the shared RestTable contract, exercised on /_cat/segments."""
        _cluster, _node, rc = live
        help_resp = rc.dispatch(RestRequest(
            method="GET", path="/_cat/segments", params={"help": ""}))
        assert help_resp.status == 200
        help_lines = help_resp.body.strip().splitlines()
        assert any(l.startswith("tf.layout | tf |") for l in help_lines), \
            help_lines
        assert all("|" in l for l in help_lines)
        # no ?v: no header row
        plain = rc.dispatch(RestRequest(
            method="GET", path="/_cat/segments", params={}))
        assert not plain.body.startswith("index")
        # ?h= selects columns by ALIAS; unknown names are ignored
        sel = rc.dispatch(RestRequest(
            method="GET", path="/_cat/segments",
            params={"v": "", "h": "i,s,tf,bp,nosuchcol"}))
        header = sel.body.splitlines()[0].split()
        assert header == ["i", "s", "tf", "bp"]
        # selecting by full name works too
        sel2 = rc.dispatch(RestRequest(
            method="GET", path="/_cat/segments",
            params={"v": "", "h": "index,generation"}))
        assert sel2.body.splitlines()[0].split() == ["index", "generation"]


# ---------------------------------------------------------------------------
# hot_threads: two-snapshot sampling
# ---------------------------------------------------------------------------


class TestHotThreads:
    def test_busy_thread_ranks_and_idle_skipped(self, live):
        _cluster, _node, rc = live
        stop = threading.Event()

        def burn():
            x = 0
            while not stop.is_set():
                x = (x * 31 + 7) % 1000003
            return x

        t = threading.Thread(target=burn, name="estpu[hot-burner]",
                             daemon=True)
        t.start()
        try:
            resp = rc.dispatch(RestRequest(
                method="GET", path="/_nodes/hot_threads",
                params={"interval": "250ms", "threads": "4"}))
        finally:
            stop.set()
            t.join(5)
        assert resp.status == 200
        assert resp.content_type.startswith("text/plain")
        assert resp.body.startswith(":::")
        assert "idle/parked skipped" in resp.body
        # the spinning thread must make the busiest list, with real cpu%
        assert "estpu[hot-burner]" in resp.body, resp.body
        burner_line = next(l for l in resp.body.splitlines()
                           if "hot-burner" in l)
        pct = float(burner_line.strip().split("%")[0])
        assert pct > 0.0, burner_line

    def test_threads_param_bounds_report(self, live):
        _cluster, _node, rc = live
        resp = rc.dispatch(RestRequest(
            method="GET", path="/_nodes/hot_threads",
            params={"interval": "50ms", "threads": "1"}))
        assert resp.status == 200
        # exactly one thread entry (lines starting with cpu%)
        entries = [l for l in resp.body.splitlines()
                   if "% cpu usage" in l]
        assert len(entries) <= 1

    def test_bad_interval_is_400(self, live):
        _cluster, _node, rc = live
        resp = rc.dispatch(RestRequest(
            method="GET", path="/_nodes/hot_threads",
            params={"interval": "bogus"}))
        assert resp.status == 400


# ---------------------------------------------------------------------------
# tracer ring observability
# ---------------------------------------------------------------------------


class TestTracerRingStats:
    def test_ring_eviction_counted(self):
        tr = Tracer(Settings.from_flat({"search.trace.ring_size": "2"}),
                    node_name="t")
        tr.sample_rate = 0.0
        for _ in range(5):
            trace = tr.start_trace("rest", force=True)
            trace.root.end()
        st = tr.stats()
        assert st["ring"] == 2
        assert st["finished"] == 5
        assert st["ring_evicted"] == 3
        assert st["late_stitch_dropped"] == 0

    def test_late_stitch_drop_counted(self):
        tr = Tracer(Settings.from_flat({"search.trace.ring_size": "2"}),
                    node_name="t")
        tr.sample_rate = 0.0
        trace = tr.start_trace("rest", force=True)
        root_id = trace.root.span_id
        trace.root.end()
        for _ in range(2):  # evict the first trace
            t2 = tr.start_trace("rest", force=True)
            t2.root.end()
        trace.add_remote([{"id": 9, "parent": root_id, "name": "late",
                           "t0": 0.0, "t1": 0.1, "duration_ms": 100.0,
                           "tags": {}}])
        assert tr.stats()["late_stitch_dropped"] == 1

    def test_prometheus_traces_family(self, live):
        _cluster, _node, rc = live
        resp = rc.dispatch(RestRequest(
            method="GET", path="/_prometheus/metrics", params={}))
        assert resp.status == 200
        for family in ("estpu_traces_sampled_total",
                       "estpu_traces_finished_total",
                       "estpu_traces_in_flight",
                       "estpu_traces_ring_evicted_total",
                       "estpu_traces_late_stitch_dropped_total"):
            assert family in resp.body, family


# ---------------------------------------------------------------------------
# sanitizer: the unprofiled path adds zero syncs / zero recompiles
# ---------------------------------------------------------------------------


class TestUnprofiledSanitized:
    def test_warmed_unprofiled_loop_zero_syncs_zero_recompiles(
            self, tmp_path, monkeypatch):
        """The serving invariant: a warmed UNPROFILED concurrent loop through
        the batcher performs 0 backend compiles under hard
        transfer_guard("disallow") AND never calls the pending handle's
        sync() — the per-request sync belongs exclusively to profiled
        requests, which bypass the batcher and opt in."""
        import jax

        from elasticsearch_tpu.common.jaxenv import sanitize
        from elasticsearch_tpu.index import Engine
        from elasticsearch_tpu.mapper import MapperService
        from elasticsearch_tpu.search import ShardContext, parse_query
        from elasticsearch_tpu.search import execute as execute_mod
        from elasticsearch_tpu.search.batcher import DeviceBatcher
        from elasticsearch_tpu.search.execute import lower_flat
        from elasticsearch_tpu.search.similarity import SimilarityService

        sync_calls = []
        orig_sync = execute_mod._PendingFlat.sync
        monkeypatch.setattr(
            execute_mod._PendingFlat, "sync",
            lambda self: (sync_calls.append(1), orig_sync(self))[1])

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
        texts = ["quick brown", "lazy dog", "red bear", "fox dog"]
        plans = [lower_flat(parse_query({"match": {"body": t}}), ctx)
                 for t in texts]

        def unprofiled_round():
            out = [None] * len(plans)
            errs = [None] * len(plans)

            def worker(i):
                try:
                    out[i] = batcher.execute(plans[i], ctx, 10)
                except Exception as err:  # noqa: BLE001 — assert below
                    errs[i] = err

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(plans))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            assert all(e2 is None for e2 in errs), errs
            return out

        try:
            warm = unprofiled_round()
            jax.config.update("jax_transfer_guard", "disallow")
            try:
                with sanitize(max_compiles=0, transfers="disallow") as rep:
                    again = unprofiled_round()
            finally:
                jax.config.update("jax_transfer_guard", "allow")
            assert rep.compiles == 0, rep.compile_events
            assert sync_calls == [], "unprofiled serving path called sync()"
            for w, a in zip(warm, again):
                assert a.hits == w.hits and a.total == w.total

            # ...and a PROFILED request of the same plan syncs exactly
            # because it opted in, returning identical results
            prof = ProfileCollector(node="n", index="i", shard=0)
            with profiling.activate(prof):
                from elasticsearch_tpu.search.execute import \
                    execute_flat_batch

                got = execute_flat_batch([plans[0]], ctx, 10)[0]
            assert len(sync_calls) >= 1
            assert got.hits == warm[0].hits and got.total == warm[0].total
            d = prof.to_dict()
            assert d["phases_ms"]["device"] >= 0
            assert d["segments"] and \
                d["segments"][0]["path"].startswith("sparse")
        finally:
            batcher.shutdown()


# ---------------------------------------------------------------------------
# tpulint: the instrumented files stay clean
# ---------------------------------------------------------------------------


def test_profile_files_tpulint_clean():
    """The profiler hooks sit in the device hot path (execute, scoring,
    device_index, mesh serving): every instrumented file must stay free of
    findings so the empty baseline holds."""
    from tools.tpulint import lint_paths

    wanted = {
        "elasticsearch_tpu/common/profile.py",
        "elasticsearch_tpu/common/breaker.py",
        "elasticsearch_tpu/common/tracing.py",
        "elasticsearch_tpu/ops/device_index.py",
        "elasticsearch_tpu/ops/scoring.py",
        "elasticsearch_tpu/search/execute.py",
        "elasticsearch_tpu/search/service.py",
        "elasticsearch_tpu/search/batcher.py",
        "elasticsearch_tpu/search/controller.py",
        "elasticsearch_tpu/parallel/mesh_serving.py",
        "elasticsearch_tpu/actions.py",
        "elasticsearch_tpu/rest/controller.py",
        "elasticsearch_tpu/node.py",
    }
    findings = [f for f in lint_paths(None) if f.path in wanted]
    assert findings == [], [f.to_dict() for f in findings]
