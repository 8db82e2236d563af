"""A search that met ONE shard whose only copy is on the coordinator's node
runs its query phase on the thread that asked, inside one of the `search`
pool's slots (actions._inline_node / _query_shard_inline over
TransportService.call_local over threadpool._BoundedPool.run_inline): the
pool lends a slot, not a thread, and no message is sent to oneself.

Covers: the inline answer is the chain's, bit for bit; request and answer are
shared, not copied, so neither side mutates them, and both still cross the
wire codec unchanged (the assertion the served path no longer pays); the
pool's admission, bound, order and counters hold for inline tasks as for
pooled ones; a full pool's 429; a fault rule, another copy, several shards and
a DFS round each take the chain as before; the selector and admission control
are fed; the sampled tree's new shape; the two per-layer metrics that read the
counter, through the benchmark's own readers."""

import copy
import threading
import time

import pytest

from benchmark.harness import readers, registry
from elasticsearch_tpu.actions import A_QUERY_PHASE
from elasticsearch_tpu.common.errors import RejectedExecutionError
from elasticsearch_tpu.common.jaxenv import pool_label
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.rest.controller import RestRequest, build_rest_controller
from elasticsearch_tpu.threadpool import ThreadPool
from elasticsearch_tpu.transport.service import _roundtrip

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
    """One node that answers an index of two shards shard by shard over the
    transport (no mesh), an index of one shard, and a filtered alias of it."""
    tmp = tmp_path_factory.mktemp("inline")
    with TestCluster(n_nodes=1, data_root=tmp, seed=17, settings={
            "search.mesh.enabled": "false"}) as cluster:
        node = next(iter(cluster.nodes.values()))
        client = node.client()
        _fill(cluster, client, "one", 1)
        _fill(cluster, client, "two", 2)
        client.update_aliases({"actions": [{"add": {
            "index": "one", "alias": "low",
            "filter": {"range": {"n": {"lt": 24}}}}}]})
        yield cluster, node, client


def _inline(node) -> int:
    return node.actions.search_phases["inline_query"]


class _Watch:
    """What a node sends (`sent`) and what it runs on the asking thread in a
    message's place (`called`: action, the request handed over, the answer
    handed back, and a deep copy of each as it was at the hand-over)."""

    def __init__(self, node, monkeypatch):
        self.sent, self.called = [], []
        real_send, real_call = node.transport.send_request, \
            node.transport.call_local

        def send(target, action, payload, *args, **kwargs):
            self.sent.append(action)
            return real_send(target, action, payload, *args, **kwargs)

        def call(action, payload):
            before = copy.deepcopy(payload)
            answer = real_call(action, payload)
            self.called.append((action, payload, before, answer,
                                copy.deepcopy(answer)))
            return answer

        monkeypatch.setattr(node.transport, "send_request", send)
        monkeypatch.setattr(node.transport, "call_local", call)


def _chain_only(node, monkeypatch):
    """The same node with the inline path taken away: every query phase
    builds _query_shard_async's chain, as before this path existed."""
    monkeypatch.setattr(node.actions, "_inline_node",
                        lambda *a, **kw: None)


def _but_took(resp: dict) -> dict:
    return {k: v for k, v in resp.items() if k != "took"}


def _same_types(a, b, path="$"):
    """`a` and `b` are equal AND built of the same types all the way down
    (a tuple is no list, a numpy scalar no float): what the codec's round
    trip used to guarantee of everything a shard answered."""
    assert type(a) is type(b), (path, type(a), type(b))
    if isinstance(a, dict):
        assert list(a) == list(b), (path, list(a), list(b))
        for k in a:
            _same_types(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _same_types(x, y, f"{path}[{i}]")
    else:
        assert a == b or (a != a and b != b), (path, a, b)


BODIES = {
    "plain": {"query": MATCH, "size": 5},
    "filtered": {"query": {"filtered": {
        "query": MATCH, "filter": {"range": {"n": {"gte": 5, "lt": 40}}}}},
        "size": 5},
    "sorted": {"query": MATCH, "sort": [{"n": "desc"}], "size": 5},
    "sorted_ties": {"query": {"match_all": {}},
                    "sort": [{"tag": "asc"}, "_score"], "from": 14, "size": 6},
    "aggregated": {"query": MATCH, "size": 5, "aggs": {
        "by_n": {"histogram": {"field": "n", "interval": 8}},
        "tags": {"terms": {"field": "tag"},
                 "aggs": {"mean": {"avg": {"field": "n"}}}},
        "stats": {"extended_stats": {"field": "n"}}}},
    "faceted": {"query": MATCH, "size": 3,
                "facets": {"tags": {"terms": {"field": "tag"}}}},
    "function_score": {"query": {"function_score": {
        "query": {"match_all": {}},
        "field_value_factor": {"field": "n", "missing": 1}}}, "size": 5},
    "past_the_end": {"query": MATCH, "from": 100, "size": 4},
    "size_0": {"query": MATCH, "size": 0,
               "aggs": {"mean": {"avg": {"field": "n"}}}},
    "highlight_explain": {"query": MATCH, "size": 3, "explain": True,
                          "version": True, "_source": ["n"],
                          "highlight": {"fields": {"body": {}}}},
    "suggest": {"query": MATCH, "size": 2, "suggest": {
        "s": {"text": "quik", "term": {"field": "body"}}}},
    "request_cache_hit": {"query": MATCH, "size": 5, "request_cache": True},
    "terms_lookup_free_bool": {"query": {"bool": {
        "must": [MATCH], "must_not": [{"term": {"tag": "t1"}}],
        "should": [{"match": {"body": "fox"}}]}}, "size": 7,
        "min_score": 0.01},
}


class TestTheAnswerIsTheChains:
    @pytest.mark.parametrize("index", ["one", "low"])
    @pytest.mark.parametrize("name", sorted(BODIES))
    def test_inline_and_chain_answer_alike(self, served, monkeypatch, name,
                                           index):
        """(a) hits, total, max_score, sort values, aggregations, facets and
        suggestions, of the index and of its filtered alias: equal, and of
        the same types all the way down."""
        _cluster, node, client = served
        body = BODIES[name]
        if name == "request_cache_hit":
            client.search(index, copy.deepcopy(body))  # stores the partial
        before = _inline(node)
        watch = _Watch(node, monkeypatch)
        inline = client.search(index, copy.deepcopy(body))
        assert _inline(node) == before + 1
        assert watch.sent == [] and [c[0] for c in watch.called] == \
            [A_QUERY_PHASE]
        _chain_only(node, monkeypatch)
        chain = client.search(index, copy.deepcopy(body))
        assert _inline(node) == before + 1
        assert watch.sent == [A_QUERY_PHASE] and len(watch.called) == 1
        _same_types(_but_took(inline), _but_took(chain))
        assert inline["_shards"] == {"total": 1, "successful": 1,
                                     "degraded": 0, "failed": 0}
        if name not in ("size_0", "past_the_end"):
            assert inline["hits"]["hits"], inline
        if index == "low":
            assert all(int(h["_id"]) < 24 for h in inline["hits"]["hits"])

    def test_a_profiled_search_names_its_winner(self, served, monkeypatch):
        _cluster, node, client = served
        body = {"query": MATCH, "size": 5, "profile": True}
        before = _inline(node)
        inline = client.search("one", dict(body))
        assert _inline(node) == before + 1
        _chain_only(node, monkeypatch)
        chain = client.search("one", dict(body))
        for resp in (inline, chain):
            (shard,) = resp["profile"]["shards"]
            assert shard["winner"] == "primary"
        (a,), (b,) = inline["profile"]["shards"], chain["profile"]["shards"]
        assert a["plan"] == b["plan"] and set(a) == set(b)
        assert set(a["phases_ms"]) == set(b["phases_ms"])
        _same_types(inline["hits"], chain["hits"])

    def test_scroll_count_and_more_like_this_answer_alike(self, served,
                                                          monkeypatch):
        """Whatever comes through actions.search meets the predicate alike:
        the REST search with `scroll`, `_count`, `_mlt`."""
        _cluster, node, client = served
        rc = build_rest_controller(node)

        def ask():
            before = _inline(node)
            scroll = rc.dispatch(RestRequest(
                method="POST", path="/one/_search", params={"scroll": "1m"},
                body={"query": {"match_all": {}}, "size": 7}))
            assert scroll.status == 200, scroll.body
            out = ([h["_id"] for h in scroll.body["hits"]["hits"]],
                   client.count("one", {"query": MATCH})["count"],
                   [h["_id"] for h in node.actions.more_like_this(
                       "one", "doc", "3", min_term_freq=1,
                       min_doc_freq=1)["hits"]["hits"]])
            return out, _inline(node) - before

        inline, n_inline = ask()
        assert n_inline == 3
        _chain_only(node, monkeypatch)
        chain, n_chain = ask()
        assert n_chain == 0 and inline == chain
        assert len(inline[0]) == 7 and inline[1] > 0 and inline[2]


class TestNothingIsMutated:
    @pytest.mark.parametrize("name", sorted(BODIES))
    def test_request_and_answer_are_shared_and_left_alone(self, served,
                                                          monkeypatch, name):
        """(b) The handler reads the client's body object itself and the
        coordinator reads the handler's answer object itself: after the
        search each equals its copy from the hand-over, and each crosses the
        wire codec unchanged (what send_request asserts of every message and
        this path no longer pays)."""
        _cluster, node, client = served
        sent_body = copy.deepcopy(BODIES[name])
        as_sent = copy.deepcopy(sent_body)
        watch = _Watch(node, monkeypatch)
        resp = client.search("low", sent_body)
        assert sent_body == as_sent
        ((action, request, request_before, answer, answer_then),) = watch.called
        assert action == A_QUERY_PHASE
        assert request["body"] is sent_body or request["body"] == as_sent
        assert request == request_before
        assert answer == answer_then
        _same_types(_roundtrip(request), request)
        _same_types(_roundtrip(answer), answer)
        # and the response the client got holds the hits the shard built
        if answer.get("hits"):
            assert resp["hits"]["hits"] == answer["hits"]


class TestTheChainStillRuns:
    @pytest.mark.parametrize("kind", ["error", "delay", "drop"])
    def test_a_fault_rule_on_the_query_phase_takes_the_chain(
            self, served, monkeypatch, kind):
        """(e) A rule that could match the message (either direction) sends
        the message: the rule behaves as it did, and the counter stands."""
        cluster, node, client = served
        policy = cluster.fault_policy(next(iter(cluster.nodes)), seed=5)
        try:
            if kind == "error":
                rule = policy.error(RuntimeError("injected: shard is ill"),
                                    action=A_QUERY_PHASE)
            elif kind == "delay":
                rule = policy.delay(0.3, action=A_QUERY_PHASE,
                                    direction="recv")
            else:
                rule = policy.drop(action=A_QUERY_PHASE)
            before = _inline(node)
            watch = _Watch(node, monkeypatch)
            t0 = time.monotonic()
            body = {"query": MATCH, "size": 5}
            if kind == "drop":
                body["timeout"] = "400ms"
            resp = client.search("one", body)
            took = time.monotonic() - t0
            assert _inline(node) == before and watch.called == []
            assert watch.sent[0] == A_QUERY_PHASE and rule.hits == 1
            if kind == "delay":
                assert took >= 0.3 and resp["_shards"]["failed"] == 0
                assert resp["hits"]["hits"]
            else:
                assert resp["_shards"]["failed"] == 1
                reasons = [f["reason"] for f in resp["_shards"]["failures"]]
                assert resp["hits"]["hits"] == []
                if kind == "error":
                    assert reasons == ["injected: shard is ill"]
                else:
                    # the chain's attempt timer, clamped to the budget, fails
                    # the lost attempt over; no copy and no budget are left
                    assert reasons == [
                        f"query phase attempt to [{node.local_node.id}] "
                        "timed out",
                        "search budget exhausted after 1 attempt(s) on "
                        f"[one][0]: query phase attempt to "
                        f"[{node.local_node.id}] timed out"]
                    assert resp["timed_out"] is True and took < 5.0
        finally:
            cluster.clear_faults()
            node.transport.fault_policy = None
        # with the rules gone the next search runs inline again
        before = _inline(node)
        assert client.search("one", {"query": MATCH})["hits"]["total"] > 0
        assert _inline(node) == before + 1

    def test_a_rule_on_another_action_leaves_the_inline_path(self, served):
        cluster, node, client = served
        policy = cluster.fault_policy(next(iter(cluster.nodes)), seed=6)
        try:
            rule = policy.drop(action="indices:data/write/*")
            before = _inline(node)
            assert client.search("one", {"query": MATCH})["hits"]["total"] > 0
            assert _inline(node) == before + 1 and rule.hits == 0
        finally:
            cluster.clear_faults()
            node.transport.fault_policy = None

    @pytest.mark.parametrize("index,search_type", [
        ("two", "query_then_fetch"),
        ("one", "dfs_query_then_fetch"),
        ("one", "dfs_query_and_fetch")],
        ids=["two-shards", "dfs-then-fetch", "dfs-and-fetch"])
    def test_several_shards_and_a_dfs_round_take_the_chain(
            self, served, monkeypatch, index, search_type):
        """(f) What the predicate reads off the search itself."""
        _cluster, node, client = served
        before = _inline(node)
        watch = _Watch(node, monkeypatch)
        resp = client.search(index, {"query": MATCH, "size": 5},
                             search_type=search_type)
        assert resp["_shards"]["failed"] == 0 and resp["hits"]["hits"]
        assert _inline(node) == before and watch.called == []
        assert watch.sent.count(A_QUERY_PHASE) == (2 if index == "two" else 1)

    def test_a_replica_on_a_second_node_takes_the_chain(self, tmp_path,
                                                        monkeypatch):
        """(f) Another copy on a live node could take a failover or a hedge:
        the chain is built, whichever node is asked. Once the second node is
        gone, the survivor's copy is the only one and the search runs inline."""
        with TestCluster(n_nodes=2, data_root=tmp_path, seed=19, settings={
                "search.mesh.enabled": "false"}) as cluster:
            client = cluster.client()
            _fill(cluster, client, "pair", 1, replicas=1)
            for node in cluster.nodes.values():
                before = _inline(node)
                with monkeypatch.context() as m:
                    watch = _Watch(node, m)
                    resp = node.client().search("pair", {"query": MATCH})
                assert resp["_shards"]["failed"] == 0 and resp["hits"]["hits"]
                assert _inline(node) == before and watch.called == []
                assert watch.sent == [A_QUERY_PHASE]
            names = list(cluster.nodes)
            survivor = cluster.nodes[names[0]]
            cluster.kill_node(names[1])
            deadline = time.monotonic() + 20.0
            while len(survivor.cluster_service.state.routing_table.index("pair")
                      .shard(0).active_shards()) != 1 or \
                    len(survivor.cluster_service.state.nodes.nodes) != 1:
                assert time.monotonic() < deadline
                time.sleep(0.05)
            before = _inline(survivor)
            resp = survivor.client().search("pair", {"query": MATCH})
            assert resp["_shards"]["failed"] == 0 and resp["hits"]["hits"]
            assert _inline(survivor) == before + 1


class TestWhatTheChainFedIsFed:
    def test_the_selector_the_hedge_budget_and_admission_observe(self, served):
        """(g) One attempt begun, observed with its piggybacked load, ended;
        a token's share accrued; the shard phase's latency admitted."""
        _cluster, node, client = served
        selector = node.actions.routing.selector
        (copy_,) = node.cluster_service.state.routing_table.index("one") \
            .shard(0).active_shards()
        entry = selector._copy(selector.key(copy_))
        with selector.hedges._lock:
            selector.hedges.tokens = 0.0
        samples, failures, observed = entry.samples, entry.failures, \
            node.actions.admission.stats()["observed"]
        for _ in range(3):
            client.search("one", {"query": MATCH, "size": 5})
        assert entry.samples == samples + 3 and entry.outstanding == 0
        assert entry.queue == 0 and entry.failures <= failures
        assert node.actions.admission.stats()["observed"] == observed + 3
        assert selector.hedges.tokens == pytest.approx(
            3 * selector.hedges.ratio)

    def test_a_failing_shard_is_a_failure_entry_and_a_selector_failure(
            self, served, monkeypatch):
        """The one attempt's exception is the chain's terminal error."""
        _cluster, node, client = served
        selector = node.actions.routing.selector
        (copy_,) = node.cluster_service.state.routing_table.index("one") \
            .shard(0).active_shards()
        entry = selector._copy(selector.key(copy_))

        def broken(request, channel):
            raise RuntimeError("the shard is ill")

        before, observed = _inline(node), \
            node.actions.admission.stats()["observed"]
        monkeypatch.setattr(node.transport.handlers[A_QUERY_PHASE], "fn",
                            broken)
        resp = client.search("one", {"query": MATCH, "size": 5})
        monkeypatch.undo()
        assert resp["_shards"] == {
            "total": 1, "successful": 0, "degraded": 0, "failed": 1,
            "failures": [{"index": "one", "shard": 0,
                          "node": node.local_node.id,
                          "reason": "the shard is ill"}]}
        assert resp["hits"] == {"total": 0, "max_score": None, "hits": []}
        assert _inline(node) == before + 1
        assert entry.failures > 0 and entry.outstanding == 0
        assert node.actions.admission.stats()["observed"] == observed + 1
        assert client.search("one", {"query": MATCH})["hits"]["total"] > 0


class TestAFullPool:
    @pytest.mark.parametrize("inline", [True, False], ids=["inline", "chain"])
    def test_a_full_search_pool_sheds_with_the_429(self, tmp_path, monkeypatch,
                                                   inline):
        """(c) The pool's bound stands before an inline call as before a
        pooled one: the same 429, the same Retry-After, `rejected` counted,
        and the search booked under no phase."""
        with TestCluster(n_nodes=1, data_root=tmp_path, seed=23, settings={
                "threadpool.search.size": 1,
                "threadpool.search.queue_size": 1}) as cluster:
            node = next(iter(cluster.nodes.values()))
            _fill(cluster, node.client(), "one", 1)
            if not inline:
                _chain_only(node, monkeypatch)
            rc = build_rest_controller(node)
            gate = threading.Event()
            try:
                node.threadpool.submit("search", gate.wait)
                node.threadpool.submit("search", gate.wait)
                pool = node.threadpool.stats()["search"]
                assert pool["active"] == 1 and pool["queue"] == 1
                phases = dict(node.actions.search_phases)
                resp = rc.dispatch(RestRequest(
                    method="POST", path="/one/_search",
                    body={"query": MATCH, "size": 5}))
            finally:
                gate.set()
            assert resp.status == 429, resp.body
            assert resp.headers == {"Retry-After": "1"}
            assert resp.body["error"]["type"] == "RejectedExecutionException"
            assert "queue capacity [1] full" in resp.body["error"]["reason"]
            assert node.threadpool.stats()["search"]["rejected"] == 1
            assert node.actions.search_phases == phases
            deadline = time.monotonic() + 5.0
            while node.threadpool.stats()["search"]["completed"] != 2:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            resp = rc.dispatch(RestRequest(
                method="POST", path="/one/_search",
                body={"query": MATCH, "size": 5}))
            assert resp.status == 200 and resp.body["hits"]["hits"]


# ---------------------------------------------------------------------------
# the pool: a slot, not a thread (d)
# ---------------------------------------------------------------------------


def _until(predicate, what=""):
    deadline = time.monotonic() + 10.0
    while not predicate():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


class _Tasks:
    """Named tasks that say when they run and hold their slot until let go."""

    def __init__(self):
        self.lock = threading.Lock()
        self.running, self.most, self.order, self.labels = 0, 0, [], {}
        self.gates: dict = {}

    def task(self, name):
        gate = self.gates[name] = threading.Event()

        def run():
            with self.lock:
                self.running += 1
                self.most = max(self.most, self.running)
                self.order.append(name)
                self.labels[name] = pool_label()
            gate.wait(10.0)
            with self.lock:
                self.running -= 1
            return name

        return run


class TestTheSlot:
    def test_pooled_and_inline_tasks_share_the_slots_in_arrival_order(self):
        tp = ThreadPool(Settings.from_flat({"threadpool.search.size": 2,
                                            "threadpool.search.queue_size": 8}))
        tasks, results, threads = _Tasks(), {}, []

        def inline(name):
            def run():
                results[name] = tp.run_inline("search", tasks.task(name))
            t = threading.Thread(target=run, name=f"asker-{name}")
            t.start()
            threads.append(t)

        def stats():
            return tp.stats()["search"]

        try:
            # both slots: one to a pooled task, one to an inline one
            futures = {"p0": tp.submit("search", tasks.task("p0"))}
            inline("i0")
            _until(lambda: tasks.running == 2)
            assert stats()["active"] == 2 and stats()["queue"] == 0
            # three arrivals, in this order, each behind the one before
            inline("i1")
            _until(lambda: stats()["queue"] == 1)
            futures["p1"] = tp.submit("search", tasks.task("p1"))
            assert stats()["queue"] == 2
            inline("i2")
            _until(lambda: stats()["queue"] == 3)
            # the load signal counts inline waiters as it counts queued tasks
            assert tp.queue_depth("search") == 3
            assert stats()["active"] == 2 and tasks.order == ["p0", "i0"]
            # a slot given up goes to the head of the line, whatever its kind
            for leaves, then in (("i0", "i1"), ("p0", "p1"), ("i1", "i2")):
                tasks.gates[leaves].set()
                _until(lambda: tasks.order[-1] == then, (tasks.order, then))
                assert stats()["active"] == 2 and tasks.running == 2
            assert tasks.order == ["p0", "i0", "i1", "p1", "i2"]
            assert stats()["queue"] == 0 and tp.queue_depth("search") == 0
            for gate in tasks.gates.values():
                gate.set()
            for t in threads:
                t.join(10.0)
            assert {n: f.result(10.0) for n, f in futures.items()} == \
                {"p0": "p0", "p1": "p1"}
            assert results == {"i0": "i0", "i1": "i1", "i2": "i2"}
            _until(lambda: stats()["completed"] == 5)
            final = stats()
            assert final["active"] == 0 and final["queue"] == 0
            assert final["rejected"] == 0 and final["queue_wait"]["count"] == 5
            assert tasks.most == 2  # never more than `size` at once
            # while it runs, an inline task's thread answers to the pool's
            # name where work is attributed to pools, and not after
            assert set(tasks.labels.values()) == {"search"}
            assert pool_label() == "other"
        finally:
            for gate in tasks.gates.values():
                gate.set()
            tp.shutdown()

    def test_many_askers_and_submitters_never_pass_the_bound(self):
        """Time-bounded stress, more workers than cores and a short switch
        interval: a lost update of the slot count would let a task past
        `size`, lose a completion, or leave a slot held at the end."""
        import sys

        size, askers, each = 3, 24, 60
        tp = ThreadPool(Settings.from_flat({
            "threadpool.search.size": size, "threadpool.search.queue_size": -1}))
        lock = threading.Lock()
        seen = {"running": 0, "most": 0, "ran": 0}

        def task():
            with lock:
                seen["running"] += 1
                seen["most"] = max(seen["most"], seen["running"])
            time.sleep(0)  # let another thread in while the slot is held
            with lock:
                seen["running"] -= 1
                seen["ran"] += 1

        def asker(i):
            for j in range(each):
                if (i + j) % 2:
                    tp.run_inline("search", task)
                else:
                    tp.submit("search", task).result(30.0)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=asker, args=(i,))
                       for i in range(askers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
            stats = tp.stats()["search"]
            tp.shutdown()
        assert seen["ran"] == askers * each and seen["running"] == 0
        assert 1 <= seen["most"] <= size
        assert stats["completed"] == askers * each
        assert stats["active"] == 0 and stats["queue"] == 0
        assert stats["rejected"] == 0
        assert stats["queue_wait"]["count"] == askers * each

    def test_an_inline_call_is_rejected_at_the_bound_and_counted(self):
        tp = ThreadPool(Settings.from_flat({"threadpool.search.size": 1,
                                            "threadpool.search.queue_size": 1}))
        gate = threading.Event()
        try:
            tp.submit("search", gate.wait)
            tp.submit("search", gate.wait)  # fills the one place in the line
            with pytest.raises(RejectedExecutionError) as inline:
                tp.run_inline("search", lambda: "ran")
            with pytest.raises(RejectedExecutionError) as pooled:
                tp.submit("search", lambda: "ran")
            assert str(inline.value) == str(pooled.value)
            assert inline.value.status == 429
            st = tp.stats()["search"]
            assert st["rejected"] == 2 and st["queue"] == 1 and st["active"] == 1
            gate.set()
            _until(lambda: tp.stats()["search"]["completed"] == 2)
            assert tp.run_inline("search", lambda: "ran") == "ran"
            assert tp.stats()["search"]["completed"] == 3
        finally:
            gate.set()
            tp.shutdown()

    def test_an_inline_task_raises_to_its_caller_and_frees_its_slot(self):
        tp = ThreadPool(Settings.from_flat({"threadpool.search.size": 1}))
        try:
            def ill():
                raise ValueError("ill")

            with pytest.raises(ValueError, match="ill"):
                tp.run_inline("search", ill)
            st = tp.stats()["search"]
            assert st["active"] == 0 and st["completed"] == 1
            assert tp.run_inline("search", lambda a, b: a + b, 1, 2) == 3
            assert tp.run_inline("same", lambda: pool_label()) == "other"
        finally:
            tp.shutdown()

    def test_a_shut_down_pool_lets_its_waiters_go(self):
        tp = ThreadPool(Settings.from_flat({"threadpool.search.size": 1}))
        gate, errors = threading.Event(), []

        def waiter():
            try:
                tp.run_inline("search", lambda: None)
            except RejectedExecutionError as e:
                errors.append(str(e))

        try:
            tp.submit("search", gate.wait)
            _until(lambda: tp.stats()["search"]["active"] == 1)
            t = threading.Thread(target=waiter)
            t.start()
            _until(lambda: tp.stats()["search"]["queue"] == 1)
            tp.shutdown()
            t.join(10.0)
            assert not t.is_alive()
            assert errors and "shut down" in errors[0]
            with pytest.raises(RejectedExecutionError, match="shut down"):
                tp.run_inline("search", lambda: None)
        finally:
            gate.set()


# ---------------------------------------------------------------------------
# the sampled tree (h) and the metrics that read the counter
# ---------------------------------------------------------------------------


def _flatten(n, out=None):
    out = [] if out is None else out
    out.append(n)
    for c in n["children"]:
        _flatten(c, out)
    return out


class TestTheTree:
    def test_the_shard_hangs_under_the_query_phase_behind_the_slot(self,
                                                                   served):
        _cluster, node, _client = served
        rc = build_rest_controller(node)
        resp = rc.dispatch(RestRequest(
            method="POST", path="/one/_search", params={"trace": "true"},
            body={"query": MATCH, "size": 5}))
        assert resp.status == 200, resp.body
        spans = _flatten(resp.body["trace"]["tree"])
        names = [s["name"] for s in spans]
        assert not [n for n in names if n.startswith("transport[")]
        assert "transport.codec" not in names
        (query,) = [s for s in spans if s["name"] == "coordinator.query"]
        assert [c["name"] for c in query["children"]] == ["pool.wait", "shard"]
        wait, shard = query["children"]
        assert wait["tags"] == {"pool": "search"}
        assert query["t0"] <= wait["t0"] <= wait["t1"] <= shard["t0"]
        assert shard["node"] == node.name and shard["t1"] <= query["t1"]
        assert names.count("pool.wait") == 1
        # the one wake-up left is the batcher's; none follows a round trip
        wakes = [s["tags"]["after"] for s in spans if s["name"] == "thread.wake"]
        assert wakes == ["batcher"]
        # the trace is in the node's ring once, whole
        tid = resp.body["trace"]["trace_id"]
        rings = [t for t in node.tracer.traces() if t["trace_id"] == tid]
        assert any(len(t["spans"]) >= len(spans) for t in rings)


def _stats(node) -> dict:
    resp = build_rest_controller(node).dispatch(RestRequest(
        method="GET", path="/_nodes/stats"))
    assert resp.status == 200, resp.body
    return next(iter(resp.body["nodes"].values()))


class TestTheMetricsReadIt:
    """A rehearsal of the reading, on the CPU: `/_nodes/stats` before and
    after a handful of searches, as the harness takes them around its window,
    through the readers and the definition files the benchmark uses."""

    @pytest.mark.parametrize("metric", ["inline_query_share",
                                        "inline_query_share.rate"])
    def test_100_on_one_shard(self, served, metric):
        _cluster, node, client = served
        obs = readers.Observations("one")
        obs.stats_before = _stats(node)
        for _ in range(5):
            client.search("one", {"query": MATCH, "size": 5})
        obs.stats_after = _stats(node)
        definition = registry.layer_metric(metric)
        assert readers.read(definition, obs) == 100.0
        assert readers.read(registry.layer_metric("one_trip_share"), obs) \
            == 100.0
        # a program before the counter serves none: nothing is reported
        for stats in (obs.stats_before, obs.stats_after):
            del stats["search"]["phases"]["inline_query"]
        assert readers.read(definition, obs) is None

    @pytest.mark.parametrize("metric", ["inline_query_share",
                                        "inline_query_share.rate"])
    def test_0_where_the_mesh_answers(self, tmp_path, metric):
        """Four shards on four of the virtual devices: every search is one
        mesh launch (the shape of `passage.mesh4.single`), never reaches the
        transport's query phase, and fetches over send_request."""
        with TestCluster(n_nodes=1, data_root=tmp_path, seed=29) as cluster:
            node = next(iter(cluster.nodes.values()))
            client = node.client()
            _fill(cluster, client, "four", 4)
            client.search("four", {"query": MATCH, "size": 5})
            obs = readers.Observations("four")
            obs.stats_before = _stats(node)
            meshed = node.actions.mesh_serving.mesh_queries
            for _ in range(5):
                assert client.search("four", {"query": MATCH, "size": 5})[
                    "hits"]["hits"]
            obs.stats_after = _stats(node)
            assert node.actions.mesh_serving.mesh_queries == meshed + 5
            assert readers.read(registry.layer_metric(metric), obs) == 0.0
            assert readers.read(registry.layer_metric("one_trip_share"),
                                obs) == 0.0

    def test_the_entries_sit_beside_one_trip_shares(self):
        bench = registry.benchmark()
        by_name = {m["name"]: m for m in bench["per_layer"]}
        for name, twin in (("inline_query_share", "one_trip_share"),
                           ("inline_query_share.rate", "one_trip_share.rate")):
            assert {k: v for k, v in by_name[name].items() if k != "name"} == \
                {k: v for k, v in by_name[twin].items() if k != "name"}
        # appended together by PR 42; what later PRs add follows them
        names = [m["name"] for m in bench["per_layer"]]
        at = names.index("inline_query_share")
        assert names[at + 1] == "inline_query_share.rate"
        assert "one_trip_share.rate" in names[:at]
