"""A search that meets one shard makes one trip (actions._search_inner /
_s_query_phase: the reference's QUERY_AND_FETCH for shardCount == 1): the
shard's query handler hydrates the page it chose and no fetch phase follows.

Covers: the one-trip response equals, but for `took`, what the query phase
followed by the fetch phase builds for the same body on the same index; a
fetch that fails inside the one-trip handler fails the attempt (the replica
answers, or the shard's failure is recorded: never a successful empty page);
`/_nodes/stats` `search.phases` counts searches by the trips they made."""

import contextlib

import pytest

import elasticsearch_tpu.actions as actions_mod
from elasticsearch_tpu.actions import A_FETCH_PHASE, A_QUERY_PHASE
from elasticsearch_tpu.rest.controller import RestRequest, build_rest_controller

from .harness import TestCluster

WORDS = ["quick", "brown", "fox", "lazy", "dog", "summer", "red", "bear"]
MATCH = {"match": {"body": "quick brown"}}


def _fill(cluster, client, index, shards, replicas=0):
    client.create_index(index, {
        "settings": {"number_of_shards": shards,
                     "number_of_replicas": replicas},
        "mappings": {"doc": {"properties": {
            "body": {"type": "string"}, "n": {"type": "integer"},
            "tag": {"type": "string", "index": "not_analyzed"}}}}})
    cluster.ensure_green(index)
    for i in range(48):
        client.index(index, "doc", {
            "body": f"{WORDS[i % 8]} {WORDS[(i + 1) % 8]} {WORDS[(i * 3) % 8]}",
            "n": i, "tag": f"t{i % 3}"}, id=str(i))
    client.refresh(index)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One node (eight devices: an index of two shards rides the mesh), an
    index of one shard and one of two."""
    tmp = tmp_path_factory.mktemp("one_trip")
    with TestCluster(n_nodes=1, data_root=tmp, seed=11) as cluster:
        node = next(iter(cluster.nodes.values()))
        client = node.client()
        _fill(cluster, client, "one", 1)
        _fill(cluster, client, "two", 2)
        client.update_aliases({"actions": [{"add": {
            "index": "one", "alias": "low",
            "filter": {"range": {"n": {"lt": 24}}}}}]})
        yield cluster, node, client


@contextlib.contextmanager
def _watched(node, monkeypatch, two_trips: bool):
    """The actions this node sends, or runs on the asking thread in a
    message's place (TransportService.call_local: the query phase of a search
    whose one shard has its only copy here), while the scope runs; with
    `two_trips` a query phase goes out without its page, as to an index of
    several shards, so the coordinator has to follow it with a fetch phase."""
    sent = []
    real_send = node.transport.send_request
    real_call = node.transport.call_local

    def asked(action, payload):
        sent.append(action)
        if two_trips and action == A_QUERY_PHASE:
            return {k: v for k, v in payload.items() if k != "fetch"}
        return payload

    def send(target, action, payload, *args, **kwargs):
        return real_send(target, action, asked(action, payload), *args,
                         **kwargs)

    def call(action, payload):
        return real_call(action, asked(action, payload))

    with monkeypatch.context() as m:
        m.setattr(node.transport, "send_request", send)
        m.setattr(node.transport, "call_local", call)
        yield sent


def _but_took(resp: dict) -> dict:
    return {k: v for k, v in resp.items() if k != "took"}


BODIES = {
    "plain": {"query": MATCH, "size": 5},
    "filtered": {"query": {"filtered": {
        "query": MATCH, "filter": {"range": {"n": {"gte": 5, "lt": 40}}}}},
        "size": 5},
    "sorted": {"query": MATCH, "sort": [{"n": "desc"}], "size": 5},
    # whole groups tie on the key: the order within one is the reduce's
    "sorted_ties": {"query": {"match_all": {}}, "sort": [{"tag": "asc"}],
                    "from": 14, "size": 6},
    "aggregated": {"query": MATCH, "size": 5, "aggs": {
        "by_n": {"histogram": {"field": "n", "interval": 8}},
        "mean": {"avg": {"field": "n"}}}},
    "faceted": {"query": MATCH, "size": 3,
                "facets": {"tags": {"terms": {"field": "tag"}}}},
    "function_score": {"query": {"function_score": {
        "query": {"match_all": {}},
        "field_value_factor": {"field": "n", "missing": 1}}}, "size": 5},
    "from": {"query": MATCH, "from": 3, "size": 4},
    "past_the_end": {"query": MATCH, "from": 400, "size": 4},
    "size_0": {"query": MATCH, "size": 0,
               "aggs": {"mean": {"avg": {"field": "n"}}}},
    "highlight": {"query": MATCH, "size": 5,
                  "highlight": {"fields": {"body": {}}}},
    "fields_explain_version": {"query": MATCH, "size": 3, "explain": True,
                               "version": True, "_source": ["n"]},
    "suggest": {"query": MATCH, "size": 2, "suggest": {
        "s": {"text": "quik", "term": {"field": "body"}}}},
    "request_cache_hit": {"query": MATCH, "size": 5, "request_cache": True},
}


class TestParity:
    @pytest.mark.parametrize("name", sorted(BODIES))
    def test_one_trip_answers_what_query_then_fetch_answers(
            self, served, monkeypatch, name):
        _cluster, node, client = served
        body = BODIES[name]
        if name == "request_cache_hit":
            client.search("one", dict(body))  # stores the partial
        cache_hits = node.request_cache.hits
        before = dict(node.actions.search_phases)
        with _watched(node, monkeypatch, two_trips=False) as sent:
            one = client.search("one", dict(body))
        assert sent == [A_QUERY_PHASE]
        assert node.actions._pinned == {}
        with _watched(node, monkeypatch, two_trips=True) as sent:
            two = client.search("one", dict(body))
        # a page of no hits asks no shard for any: the pinned context is freed
        page = bool(two["hits"]["hits"])
        assert sent == [A_QUERY_PHASE,
                        A_FETCH_PHASE if page else actions_mod.A_FREE_CONTEXT]
        # both query phases ran on the asking thread: the only copy is here
        assert node.actions.search_phases == {
            "one_trip": before["one_trip"] + 1,
            "two_trip": before["two_trip"] + 1,
            "inline_query": before["inline_query"] + 2}
        assert _but_took(one) == _but_took(two)
        assert one["_shards"] == {"total": 1, "successful": 1, "degraded": 0,
                                  "failed": 0}
        if name not in ("size_0", "past_the_end"):
            assert one["hits"]["hits"], one
        if name == "request_cache_hit":
            assert node.request_cache.hits == cache_hits + 2

    @pytest.mark.parametrize("extra", [{}, {"explain": True}, {
        "highlight": {"fields": {"body": {}}}}], ids=["plain", "explain",
                                                      "highlight"])
    def test_a_filtered_alias_fetches_with_the_body_as_sent(
            self, served, monkeypatch, extra):
        """The query phase searches under the alias's filter; the hits are
        built from the body the client sent, as the fetch phase builds them."""
        _cluster, node, client = served
        body = {"query": MATCH, "size": 6, **extra}
        with _watched(node, monkeypatch, two_trips=False) as sent:
            one = client.search("low", dict(body))
        assert sent == [A_QUERY_PHASE]
        with _watched(node, monkeypatch, two_trips=True):
            two = client.search("low", dict(body))
        assert _but_took(one) == _but_took(two)
        assert one["hits"]["hits"]
        assert all(int(h["_id"]) < 24 for h in one["hits"]["hits"])

    def test_a_scrolled_search_of_one_shard_makes_one_trip(
            self, served, monkeypatch):
        _cluster, node, _client = served
        rc = build_rest_controller(node)
        with _watched(node, monkeypatch, two_trips=False) as sent:
            resp = rc.dispatch(RestRequest(
                method="POST", path="/one/_search", params={"scroll": "1m"},
                body={"query": {"match_all": {}}, "size": 7}))
        assert resp.status == 200, resp.body
        assert sent == [A_QUERY_PHASE]
        assert len(resp.body["hits"]["hits"]) == 7
        page = rc.dispatch(RestRequest(
            method="POST", path="/_search/scroll",
            body={"scroll_id": resp.body["_scroll_id"]}))
        assert len(page.body["hits"]["hits"]) == 7
        assert not {h["_id"] for h in page.body["hits"]["hits"]} & \
            {h["_id"] for h in resp.body["hits"]["hits"]}


def _phases(node) -> dict:
    resp = build_rest_controller(node).dispatch(RestRequest(
        method="GET", path="/_nodes/stats/search"))
    assert resp.status == 200, resp.body
    return next(iter(resp.body["nodes"].values()))["search"]["phases"]


class TestPhaseCounters:
    @pytest.mark.parametrize("index,body,params,trips,mesh", [
        ("one", {"query": MATCH, "size": 5}, {}, "one_trip", 0),
        ("one", {"query": MATCH, "size": 0}, {}, "one_trip", 0),
        # the mesh program answers for both shards at once and a fetch
        # phase hydrates the winners
        ("two", {"query": MATCH, "size": 5}, {}, "two_trip", 1),
        # shard by shard over the transport (the mesh declines `explain`)
        ("two", {"query": MATCH, "size": 5, "explain": True}, {}, "two_trip", 0),
        ("two", {"query": MATCH, "size": 0, "explain": True}, {}, "two_trip", 0),
        # a preference or a routing value that narrows the index to one shard
        ("two", {"query": MATCH, "size": 5}, {"preference": "_shards:1"},
         "one_trip", 0),
        ("two", {"query": MATCH, "size": 5}, {"routing": "7"}, "one_trip", 0),
    ], ids=["one-shard", "one-shard-size-0", "mesh-served", "two-shards",
            "two-shards-size-0", "narrowed-by-preference",
            "narrowed-by-routing"])
    def test_nodes_stats_count_searches_by_their_trips(
            self, served, monkeypatch, index, body, params, trips, mesh):
        _cluster, node, _client = served
        rc = build_rest_controller(node)
        before = _phases(node)
        mesh_before = node.actions.mesh_serving.mesh_queries
        with _watched(node, monkeypatch, two_trips=False) as sent:
            resp = rc.dispatch(RestRequest(
                method="POST", path=f"/{index}/_search", params=dict(params),
                body=dict(body)))
        assert resp.status == 200, resp.body
        assert resp.body["_shards"]["failed"] == 0
        assert node.actions.mesh_serving.mesh_queries == mesh_before + mesh
        other = "two_trip" if trips == "one_trip" else "one_trip"
        after = _phases(node)
        assert after[trips] == before[trips] + 1
        assert after[other] == before[other]
        # one trip is one message; no fetch and no context to free behind it
        if trips == "one_trip":
            assert sent == [A_QUERY_PHASE]
        else:
            assert len(sent) > 1 or mesh

    def test_a_count_of_one_shard_is_one_trip(self, served):
        _cluster, node, client = served
        before = _phases(node)
        assert client.count("one", {"query": MATCH})["count"] > 0
        after = _phases(node)
        assert after == {"one_trip": before["one_trip"] + 1,
                         "two_trip": before["two_trip"],
                         "inline_query": before["inline_query"] + 1}


class TestFetchFailure:
    @staticmethod
    def _flaky(monkeypatch, times: int):
        """execute_fetch_phase as the shard handlers see it, raising the
        first `times` calls; returns the list of calls' outcomes."""
        real = actions_mod.execute_fetch_phase
        calls = []

        def flaky(ctx, req, docs, index_name="index", shard_id=0):
            calls.append("raised" if len(calls) < times else "served")
            if calls[-1] == "raised":
                raise RuntimeError("source lost under the fetch")
            return real(ctx, req, docs, index_name=index_name,
                        shard_id=shard_id)

        monkeypatch.setattr(actions_mod, "execute_fetch_phase", flaky)
        return calls

    def test_the_replica_answers_where_the_fetch_failed(self, tmp_path,
                                                        monkeypatch):
        with TestCluster(n_nodes=2, data_root=tmp_path, seed=13) as cluster:
            client = cluster.client()
            _fill(cluster, client, "pair", 1, replicas=1)
            expected = client.search("pair", {"query": MATCH, "size": 5})
            calls = self._flaky(monkeypatch, times=1)
            got = client.search("pair", {"query": MATCH, "size": 5})
            assert calls == ["raised", "served"]
            assert _but_took(got) == _but_took(expected)
            assert got["_shards"]["successful"] == 1 and got["hits"]["hits"]

    def test_with_no_replica_the_shard_is_failed_not_empty(self, tmp_path,
                                                           monkeypatch):
        with TestCluster(n_nodes=1, data_root=tmp_path, seed=13) as cluster:
            client = cluster.client()
            _fill(cluster, client, "lone", 1)
            calls = self._flaky(monkeypatch, times=10)
            got = client.search("lone", {"query": MATCH, "size": 5})
            assert calls == ["raised"]
            assert got["_shards"]["total"] == 1
            assert got["_shards"]["successful"] == 0
            assert got["_shards"]["failed"] == 1
            (failure,) = got["_shards"]["failures"]
            assert failure["index"] == "lone" and failure["shard"] == 0
            assert "source lost under the fetch" in failure["reason"]
            assert got["hits"]["hits"] == [] and got["hits"]["total"] == 0
            # and the next search, whose fetch works, is whole again
            monkeypatch.undo()
            again = client.search("lone", {"query": MATCH, "size": 5})
            assert again["_shards"]["failed"] == 0 and again["hits"]["hits"]
