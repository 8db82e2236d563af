"""Cross-request device micro-batching (search/batcher.py).

Covers the flush triad (full / linger / deadline-leaves-merge-budget), fan-out
ordering parity with per-request execution, the breaker-split rule (a trip
inside a coalesced launch fails ONLY the oversized request), the
staging-scratch pool (a warmed repeat batch performs 0 new host allocations
and the request breaker drains to 0), mesh coalescing through a live cluster,
and the serving invariant: a WARMED concurrent serving loop through the
batcher neither recompiles nor implicitly transfers under
transfer_guard("disallow")."""

import threading
import time

import pytest

from elasticsearch_tpu.common.breaker import CircuitBreakerService
from elasticsearch_tpu.common.deadline import NO_DEADLINE, Deadline
from elasticsearch_tpu.common.errors import CircuitBreakingError
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index import Engine
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.search import ShardContext, parse_query
from elasticsearch_tpu.search.batcher import (_RECORD_HEADS, DeviceBatcher, _Item,
                                              _k_bucket)
from elasticsearch_tpu.search.execute import execute_flat_batch, lower_flat
from elasticsearch_tpu.search.similarity import SimilarityService

from .harness import run_as_one_batch, run_query_phases

pytestmark = pytest.mark.serving

WORDS = ["quick", "brown", "fox", "lazy", "dog", "summer", "red", "bear",
         "snack", "cat"]


@pytest.fixture
def shard_ctx(tmp_path):
    settings = Settings.from_flat({})
    svc = MapperService(settings)
    e = Engine(str(tmp_path / "shard0"), svc)
    for i in range(60):
        text = f"{WORDS[i % 10]} {WORDS[(i + 1) % 10]} {WORDS[(i + 3) % 10]}"
        e.index("doc", str(i), {"body": text})
    e.refresh()
    return ShardContext(e.acquire_searcher(), svc,
                        SimilarityService(settings, mapper_service=svc))


def make_batcher(**flat):
    return DeviceBatcher(Settings.from_flat(
        {str(k): str(v) for k, v in flat.items()}))


def plan_for(ctx, text):
    plan = lower_flat(parse_query({"match": {"body": text}}), ctx)
    assert plan is not None
    return plan


def run_concurrent(batcher, ctx, texts, k=10, deadline=None):
    """Submit one plan per text from its own thread; returns TopDocs per text."""
    plans = [plan_for(ctx, t) for t in texts]
    out = [None] * len(plans)
    errs = [None] * len(plans)

    def worker(i):
        try:
            out[i] = batcher.execute(plans[i], ctx, k,
                                     deadline=deadline or NO_DEADLINE)
        except Exception as e:  # noqa: BLE001 — surfaced by the assert below
            errs[i] = e

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(plans))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert all(e is None for e in errs), errs
    return out


# ---------------------------------------------------------------------------
# flush triggers
# ---------------------------------------------------------------------------


class TestFlushTriggers:
    def test_flush_on_full(self, shard_ctx):
        # linger far beyond the test horizon: only batch-full can flush
        b = make_batcher(**{"search.batch.linger_ms": 5000,
                            "search.batch.max_batch": 4})
        try:
            texts = ["quick brown", "lazy dog", "red bear", "summer snack"]
            out = run_concurrent(b, shard_ctx, texts)
            assert all(td is not None for td in out)
            st = b.stats()
            assert st["full_flushes"] >= 1, st
            assert st["coalesced"] == 4 and st["launches"] >= 1
        finally:
            b.shutdown()

    def test_flush_on_linger(self, shard_ctx):
        b = make_batcher(**{"search.batch.linger_ms": 40,
                            "search.batch.max_batch": 64})
        try:
            t0 = time.monotonic()
            out = run_concurrent(b, shard_ctx, ["quick brown", "lazy dog"])
            elapsed = time.monotonic() - t0
            assert all(td is not None for td in out)
            st = b.stats()
            assert st["linger_flushes"] >= 1, st
            # nothing else could flush a 2-item batch below max_batch=64
            assert st["full_flushes"] == 0 and st["deadline_flushes"] == 0
            assert elapsed < 20.0
        finally:
            b.shutdown()

    def test_flush_on_deadline_leaves_merge_budget(self, shard_ctx):
        # warm the executable cache first so the flush timing, not a cold XLA
        # compile, dominates the measured latency
        warm_plan = plan_for(shard_ctx, "quick brown")
        execute_flat_batch([warm_plan], shard_ctx, _k_bucket(10))
        # linger 10s: only the deadline flush can release the batch
        b = make_batcher(**{"search.batch.linger_ms": 10_000,
                            "search.batch.max_batch": 64})
        try:
            budget_s = 0.4
            t0 = time.monotonic()
            td = b.execute(warm_plan, shard_ctx, 10,
                           deadline=Deadline.after(budget_s))
            elapsed = time.monotonic() - t0
            assert td.total > 0
            st = b.stats()
            assert st["deadline_flushes"] == 1, st
            # flushed at deadline - EWMA(batch service): the answer lands
            # BEFORE the budget expires (launch + merge fit in what was left),
            # and the batch demonstrably waited (didn't flush immediately)
            assert elapsed < budget_s + 0.25, elapsed
            assert elapsed > 0.05, elapsed
        finally:
            b.shutdown()

    def test_lone_request_pays_at_most_linger(self, shard_ctx):
        plan = plan_for(shard_ctx, "quick brown")
        execute_flat_batch([plan], shard_ctx, _k_bucket(10))  # warm
        t0 = time.monotonic()
        direct = execute_flat_batch([plan], shard_ctx, 10)[0]
        direct_s = time.monotonic() - t0
        linger_s = 0.05
        b = make_batcher(**{"search.batch.linger_ms": linger_s * 1000})
        try:
            t0 = time.monotonic()
            td = b.execute(plan, shard_ctx, 10)
            batched_s = time.monotonic() - t0
            assert td.hits == direct.hits[:10]
            # a lone request pays at most the linger (plus scheduling slack)
            assert batched_s <= direct_s + linger_s + 0.5, (batched_s, direct_s)
        finally:
            b.shutdown()


# ---------------------------------------------------------------------------
# fan-out correctness
# ---------------------------------------------------------------------------


class TestFanOut:
    def test_fanout_matches_per_request_ordering(self, shard_ctx):
        texts = ["quick brown", "lazy dog", "red bear", "summer snack",
                 "fox dog", "cat bear"]
        b = make_batcher(**{"search.batch.linger_ms": 60,
                            "search.batch.max_batch": 8})
        try:
            out = run_concurrent(b, shard_ctx, texts, k=10)
        finally:
            b.shutdown()
        for text, td in zip(texts, out):
            plan = plan_for(shard_ctx, text)
            direct = execute_flat_batch([plan], shard_ctx, 10)[0]
            assert td.total == direct.total, text
            assert td.hits == direct.hits[:10], text
            assert (td.max_score == direct.max_score
                    or (td.max_score != td.max_score
                        and direct.max_score != direct.max_score)), text

    def test_post_shutdown_serves_inline(self, shard_ctx):
        b = make_batcher(**{"search.batch.linger_ms": 20})
        plan = plan_for(shard_ctx, "quick brown")
        assert b.execute(plan, shard_ctx, 5).total > 0
        b.shutdown()
        # a shut-down batcher must not strand searches — they serve directly
        td = b.execute(plan, shard_ctx, 5)
        assert td.total > 0
        assert b.stats()["bypassed"] >= 1


# ---------------------------------------------------------------------------
# breaker split: a trip inside a coalesced launch fails only the oversized item
# ---------------------------------------------------------------------------


class _TrippingFamily:
    """Batch dispatch always trips the breaker; individually only the marked
    payload does — the exact shape of one oversized request coalesced with
    healthy neighbors."""

    name = "fake"

    def dispatch(self, items, kb):
        raise CircuitBreakingError(
            "[request] coalesced batch would exceed the limit")

    def fan_out(self, handle, items):  # pragma: no cover — dispatch raises
        raise AssertionError("unreachable")

    def execute_single(self, item):
        if item.payload == "oversized":
            err = CircuitBreakingError("[request] data would be larger than limit")
            err.breaker = "request"
            raise err
        return f"ok:{item.payload}"


class TestBreakerSplit:
    def test_trip_fails_only_the_oversized_request(self):
        b = make_batcher(**{"search.batch.linger_ms": 5000,
                            "search.batch.max_batch": 3})
        fam = _TrippingFamily()
        try:
            payloads = ["a", "oversized", "b"]
            out = [None] * 3
            errs = [None] * 3

            def worker(i):
                item = _Item(fam, ("fake", "key"), payloads[i], 10, 16,
                             NO_DEADLINE)
                try:
                    out[i] = b._submit(item)
                except Exception as e:  # noqa: BLE001
                    errs[i] = e

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            assert out[0] == "ok:a" and out[2] == "ok:b", (out, errs)
            assert isinstance(errs[1], CircuitBreakingError), errs
            assert errs[0] is None and errs[2] is None
            assert b.stats()["splits"] == 1
        finally:
            b.shutdown()


# ---------------------------------------------------------------------------
# staging scratch pool (satellite bugfix): warmed repeat = 0 new allocations
# ---------------------------------------------------------------------------


class TestStagingScratch:
    def test_warmed_repeat_batch_zero_new_host_allocations(self, shard_ctx):
        from elasticsearch_tpu.ops.device_index import packed_for

        # wire real breakers so the staging reserve rides the accounting path
        breakers = CircuitBreakerService(Settings.from_flat({}))
        shard_ctx.breakers = breakers
        plans = [plan_for(shard_ctx, t) for t in
                 ("quick brown", "lazy dog", "red bear")]
        execute_flat_batch(plans, shard_ctx, 10)  # warm: pools fill here
        seg = shard_ctx.searcher.segments[0]
        pool = packed_for(seg).sparse_scratch
        assert pool is not None and pool.allocs >= 1
        allocs_before = pool.allocs
        for _ in range(3):  # warmed repeats re-pad pooled arrays in place
            execute_flat_batch(plans, shard_ctx, 10)
        assert pool.allocs == allocs_before, (
            f"warmed repeat batch allocated {pool.allocs - allocs_before} new "
            "staging arrays — the scratch pool regressed")
        assert pool.reuses >= 3
        # transient accounting: the per-batch staging reservation fully drains
        assert breakers.breaker("request").stats()["estimated"] == 0

    def test_results_identical_with_and_without_pool_reuse(self, shard_ctx):
        plans = [plan_for(shard_ctx, t) for t in ("quick brown", "fox dog")]
        first = execute_flat_batch(plans, shard_ctx, 10)
        again = execute_flat_batch(plans, shard_ctx, 10)  # pooled arrays
        for a, c in zip(first, again):
            assert a.hits == c.hits and a.total == c.total


# ---------------------------------------------------------------------------
# mesh path rides the same queue
# ---------------------------------------------------------------------------


class TestMeshCoalescing:
    def test_concurrent_mesh_searches_coalesce(self, tmp_path):
        from tests.harness import TestCluster

        with TestCluster(n_nodes=1, data_root=tmp_path, seed=7) as cluster:
            node = next(iter(cluster.nodes.values()))
            c = node.client()
            c.create_index("meshidx", {"settings": {
                "number_of_shards": 2, "number_of_replicas": 0}})
            cluster.ensure_green("meshidx")
            for i in range(40):
                c.index("meshidx", "doc",
                        {"body": f"{WORDS[i % 10]} {WORDS[(i + 2) % 10]}"},
                        id=str(i))
            c.refresh("meshidx")
            body = {"query": {"match": {"body": "quick brown"}}}
            expected = c.search("meshidx", body)  # warm + reference answer
            assert node.actions.mesh_serving.mesh_queries >= 1
            st0 = node.search_batcher.stats()
            out = [None] * 8

            def worker(i):
                out[i] = c.search("meshidx", body)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            for r in out:
                assert r["hits"]["total"] == expected["hits"]["total"]
                assert ([h["_id"] for h in r["hits"]["hits"]]
                        == [h["_id"] for h in expected["hits"]["hits"]])
            st1 = node.search_batcher.stats()
            served = st1["coalesced"] - st0["coalesced"]
            launches = st1["launches"] - st0["launches"]
            assert served == 8, (st0, st1)
            # coalescing happened: fewer launches than requests
            assert launches < served, (st0, st1)


# ---------------------------------------------------------------------------
# serving invariant: warmed concurrent loop = 0 recompiles, no implicit pulls
# ---------------------------------------------------------------------------


class TestSanitized:
    def test_warmed_concurrent_loop_zero_recompiles(self, shard_ctx):
        import jax

        from elasticsearch_tpu.common.jaxenv import sanitize

        texts = ["quick brown", "lazy dog", "red bear", "summer snack",
                 "fox dog", "cat bear", "quick fox", "brown dog"]
        b = make_batcher(**{"search.batch.linger_ms": 30,
                            "search.batch.max_batch": 8})
        try:
            warm = run_concurrent(b, shard_ctx, texts, k=10)
            # the transfer guard context is thread-local; the drainer thread
            # needs the GLOBAL config so its dispatch half is guarded too
            jax.config.update("jax_transfer_guard", "disallow")
            try:
                with sanitize(max_compiles=0, transfers="disallow") as rep:
                    again = run_concurrent(b, shard_ctx, texts, k=10)
            finally:
                jax.config.update("jax_transfer_guard", "allow")
            assert rep.compiles == 0, rep.compile_events
            for w, a in zip(warm, again):
                assert a.hits == w.hits and a.total == w.total
        finally:
            b.shutdown()

    def test_batcher_module_tpulint_clean(self):
        """search/batcher.py is a registered hot-path file: the dispatch half
        must stay free of implicit pulls so the baseline stays empty."""
        from tools.tpulint import lint_paths
        from tools.tpulint.engine import HOT_FILES

        assert "elasticsearch_tpu/search/batcher.py" in HOT_FILES
        findings = [f for f in lint_paths(None)
                    if f.path == "elasticsearch_tpu/search/batcher.py"]
        assert findings == [], [f.to_dict() for f in findings]


# ---------------------------------------------------------------------------
# pending-merge flush (PR 6): batch N's merge must not wait out batch N+1's
# linger window
# ---------------------------------------------------------------------------


class TestPendingMergeFlush:
    def test_merge_not_delayed_by_next_batch_linger(self, shard_ctx):
        """With batch N dispatched and awaiting its merge, the collector must
        flush the queue IMMEDIATELY (reason `pending`) instead of lingering
        for batch N+1 — before the fix, batch N's already-answered futures
        waited out the full linger window behind the next batch's collect.

        Giant linger (1.5 s floor 1.2 s) makes the two behaviors unambiguous:
        old code cannot finish 3 requests under ~1.2 s, fixed code finishes in
        launch time. The pending window depends on thread scheduling, so the
        attempt retries; the old behavior can never pass any attempt (a lone
        third item always pays the full linger)."""
        from elasticsearch_tpu.search.execute import execute_flat_batch

        b = make_batcher(**{"search.batch.linger_ms": 1500,
                            "search.batch.min_linger_ms": 1200,
                            "search.batch.max_batch": 2})
        try:
            texts = ["quick brown", "lazy dog", "red bear"]
            plans = [plan_for(shard_ctx, t) for t in texts]
            # warm BOTH drainer shapes (Q=2 batch, Q=1 batch) at the k bucket
            # the batcher will use, so the timed runs measure flush policy,
            # not XLA compiles
            kb = _k_bucket(10)
            execute_flat_batch(plans[:2], shard_ctx, kb)
            execute_flat_batch(plans[2:], shard_ctx, kb)
            ok = False
            # (five attempts under 1.0 s since PR 35: on a loaded machine, six
            # workers beside it, three of 0.8 s were once not enough; the old
            # behaviour still needs 1.2 s in every attempt)
            for _attempt in range(5):
                t0 = time.monotonic()
                out = run_concurrent(b, shard_ctx, texts)
                elapsed = time.monotonic() - t0
                assert all(td is not None for td in out)
                if elapsed < 1.0 and b.stats()["pending_flushes"] >= 1:
                    ok = True
                    break
            assert ok, (elapsed, b.stats())
        finally:
            b.shutdown()


# ---------------------------------------------------------------------------
# drainer state-seconds (PR 24): where the one thread that feeds the device
# spent its wall time, for every batch
# ---------------------------------------------------------------------------


def _drainer(b):
    d = b.stats()["drainer"]
    return d, sum(v for k, v in d.items() if k.endswith("_s"))


class TestDrainerStates:
    def test_states_partition_the_drainers_wall_time(self, shard_ctx):
        b = make_batcher(**{"search.batch.linger_ms": 20,
                            "search.batch.max_batch": 4})
        try:
            texts = ["quick brown", "lazy dog", "red bear", "summer snack"]
            run_concurrent(b, shard_ctx, texts)  # starts the drainer, compiles
            d0, s0 = _drainer(b)
            launches0 = b.stats()["launches"]  # one, or two on a slow start
            t0 = time.monotonic()
            for _ in range(5):
                run_concurrent(b, shard_ctx, texts)
                time.sleep(0.05)
            time.sleep(0.25)  # an idle tick closes the last wait
            d1, s1 = _drainer(b)
            elapsed = time.monotonic() - t0
            assert set(d1) == {"wait_s", "linger_s", "dispatch_s", "merge_s",
                               "pull_s", "cpu", "batches"}
            # every second since the first reading is in exactly one state;
            # the reading itself is at most one idle tick (0.1 s) stale
            assert abs((s1 - s0) - elapsed) <= 0.12, (s1 - s0, elapsed)
            for k in ("linger_s", "dispatch_s", "merge_s", "pull_s"):
                assert d1[k] > d0[k] >= 0.0, (k, d0, d1)
            assert d1["batches"] - d0["batches"] == \
                b.stats()["launches"] - launches0 >= 5
        finally:
            b.shutdown()

    def test_an_idle_drainer_waits(self, shard_ctx):
        b = make_batcher()
        try:
            run_concurrent(b, shard_ctx, ["quick brown"])
            time.sleep(0.15)
            d0, _ = _drainer(b)
            time.sleep(0.45)
            d1, _ = _drainer(b)
            assert d1["wait_s"] - d0["wait_s"] >= 0.3
            for k in ("linger_s", "dispatch_s", "merge_s", "pull_s", "batches"):
                assert d1[k] == d0[k], k
        finally:
            b.shutdown()

    def test_a_failed_dispatch_is_dispatch_time(self):
        """The per-item replay of a batch whose coalesced launch failed is
        booked too: no second of the drainer goes missing."""
        fam = _TrippingFamily()
        b = make_batcher(**{"search.batch.linger_ms": 5000,
                            "search.batch.max_batch": 2})
        try:
            items = [_Item(fam, ("fake", "key"), p, 10, 16, NO_DEADLINE)
                     for p in ("a", "b")]
            threads = [threading.Thread(target=b._submit, args=(it,))
                       for it in items]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            assert [it.future.result(0) for it in items] == ["ok:a", "ok:b"]
            d, _total = _drainer(b)
            assert b.stats()["splits"] == 1 and d["batches"] == 1
            assert d["dispatch_s"] > 0.0 and d["merge_s"] == 0.0
        finally:
            b.shutdown()


    def test_cpu_seconds_rise_with_the_wall_seconds_and_stay_under_them(
            self, shard_ctx):
        """The drainer's thread CPU seconds, booked beside the wall seconds
        (PR 37): both rise over a batch, the CPU never passes the wall by
        more than a tick of the clock, and an idle drainer is given none."""
        tick = 0.02  # CLOCK_THREAD_CPUTIME_ID is accounted a scheduler tick late
        busy = ("linger_s", "dispatch_s", "merge_s", "pull_s")
        b = make_batcher(**{"search.batch.linger_ms": 20,
                            "search.batch.max_batch": 4})
        try:
            texts = ["quick brown", "lazy dog", "red bear", "summer snack"]
            run_concurrent(b, shard_ctx, texts)  # starts the drainer, compiles
            d0, _ = _drainer(b)
            assert set(d0["cpu"]) == {"wait_s", *busy}
            for _ in range(3):
                run_concurrent(b, shard_ctx, texts)
            time.sleep(0.25)  # an idle tick closes the last wait
            d1, _ = _drainer(b)
            wall = sum(d1[k] - d0[k] for k in busy)
            cpu = sum(d1["cpu"][k] - d0["cpu"][k] for k in busy)
            # the pull is carved out of dispatch and merge on the wall side
            # alone, so the states compare as a sum (and cpu.pull_s stays 0)
            assert 0.0 < cpu <= wall + tick, (cpu, wall)
            assert d1["cpu"]["pull_s"] == 0.0 and d1["pull_s"] > 0.0
            for k in ("wait_s", *busy):
                assert d1["cpu"][k] >= d0["cpu"][k] >= 0.0, k
            assert d1["cpu"]["wait_s"] - d0["cpu"]["wait_s"] <= \
                d1["wait_s"] - d0["wait_s"] + tick
        finally:
            b.shutdown()

    @pytest.mark.parametrize("sampled", [True, False],
                             ids=["sampled", "unsampled"])
    def test_a_sampled_waiter_records_its_wake_up(self, shard_ctx, sampled):
        """thread.wake (PR 37): from where the drainer finished the item's
        batch, the end of its batcher.merge, to the request's thread running
        again; an unsampled item is not stamped and records nothing."""
        from elasticsearch_tpu.common import tracing
        from elasticsearch_tpu.common.tracing import Tracer

        tracer = Tracer(Settings.from_flat({"search.trace.sample_rate": "0"}),
                        node_name="test")
        b = make_batcher()
        stamped = []
        real_submit = b._submit

        def submit(item):
            try:
                return real_submit(item)
            finally:
                stamped.append(item.t_done)

        b._submit = submit
        try:
            trace = tracer.start_trace("shard", force=sampled)
            with tracing.activate(trace.root):
                b.execute(plan_for(shard_ctx, "quick brown"), shard_ctx, 10)
            trace.root.end()
        finally:
            b.shutdown()
        spans = {s["name"]: s for s in trace.span_dicts()}
        if not sampled:
            assert spans == {} and stamped == [None]
            return
        wake, merge = spans["thread.wake"], spans["batcher.merge"]
        # what lies between the dispatch and the merge has a name too
        hold = spans["batcher.hold"]
        assert hold["t0"] == spans["batcher.dispatch"]["t1"]
        assert hold["t1"] == merge["t0"] and hold["parent"] == merge["parent"]
        assert wake["tags"] == {"after": "batcher"}
        assert wake["parent"] == merge["parent"] == trace.root.span_id
        assert stamped == [merge["t1"]] and wake["t0"] == merge["t1"]
        assert wake["t1"] >= wake["t0"] and wake["t1"] <= spans["shard"]["t1"]


# ---------------------------------------------------------------------------
# one served launch route (PR 33): aggregated and sorted searches ride the
# flat family's items beside plain and filtered ones — one collect, and a
# launch for each group of the batch
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def feature_ctx(tmp_path_factory):
    """Two segments, a text field and two integer columns."""
    settings = Settings.from_flat({})
    svc = MapperService(settings)
    e = Engine(str(tmp_path_factory.mktemp("features") / "shard0"), svc)
    for i in range(240):
        text = f"{WORDS[i % 10]} {WORDS[(i + 1) % 10]} {WORDS[(i + 3) % 10]}"
        e.index("doc", str(i), {"body": text, "rank": (i * 37) % 101,
                                "day": i % 12})
        if i == 119:
            e.refresh()
    e.refresh()
    yield ShardContext(e.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc))
    e.close()


AGGS = {"by_day": {"histogram": {"field": "day", "interval": 3}},
        "rank_stats": {"stats": {"field": "rank"}}}


def _match(i):
    return {"match": {"body": f"{WORDS[i % 10]} {WORDS[(i + 4) % 10]}"}}


def _rank_filter(i):
    return {"range": {"rank": {"gte": 7 * i}}}


def _mixed_bodies(n_tail: int) -> list:
    """Two plain, two filtered and one unscored search, and `n_tail`
    aggregated and `n_tail` sorted ones (scored and unscored, with and
    without filters)."""
    bodies = [{"query": _match(0), "size": 5}, {"query": _match(1), "size": 10},
              {"query": {"filtered": {"query": _match(2),
                                      "filter": _rank_filter(2)}}, "size": 7},
              {"query": {"filtered": {"query": _match(3),
                                      "filter": _rank_filter(5)}}, "size": 3},
              {"query": {"constant_score": {"filter": _rank_filter(4)}},
               "size": 4}]
    for i in range(n_tail):
        scored = {"filtered": {"query": _match(i), "filter": _rank_filter(i)}} \
            if i % 2 else _match(i)
        unscored = {"match_all": {}} if i % 3 == 0 else \
            {"constant_score": {"filter": _rank_filter(i)}}
        query = unscored if i % 4 == 3 else scored
        bodies.append({"query": query, "size": 2 + i % 5, "aggs": AGGS})
        bodies.append({"query": query, "size": 2 + i % 5,
                       "sort": [{"rank": "desc"}]})
    return bodies


def assert_answers_as_alone(ctx, bodies, got):
    """Each result is what the same search answers with no batcher on its
    context (a launch of its own): hits, totals, max_score, every bucket and
    metric, `sort` values."""
    from elasticsearch_tpu.search.aggregations import reduce_aggs
    from elasticsearch_tpu.search.service import (execute_query_phase,
                                                  parse_search_body)

    def same(a, b):
        return a == b or (a != a and b != b)  # NaN: an untracked score

    for body, res in zip(bodies, got):
        assert not isinstance(res, Exception), (body, res)
        req = parse_search_body(body)
        alone = execute_query_phase(ctx, req, use_device=True)
        assert res.total == alone.total and not res.degraded, body
        assert same(res.max_score, alone.max_score), body
        assert len(res.docs) == len(alone.docs), body
        for (gs, gd, gv), (ws, wd, wv) in zip(res.docs, alone.docs):
            assert (gd, gv) == (wd, wv) and same(gs, ws), body
        if req.aggs:
            assert reduce_aggs(req.aggs, res.agg_partials) == \
                reduce_aggs(req.aggs, alone.agg_partials), body


def _kind_sums(stats):
    kinds = stats["kinds"].values()
    return (sum(k["launches"] for k in kinds),
            sum(k["coalesced"] for k in kinds))


@pytest.mark.parametrize("n, widths", [(1, [1]), (2, [4]), (3, [4]), (4, [4]),
                                       (5, [4, 1]), (8, [4, 4]), (9, [4, 4, 1])])
def test_a_groups_query_count_rides_its_ladder(feature_ctx, monkeypatch, n,
                                               widths):
    """A lone sorted (or aggregated) search keeps the one-query program, any
    company launches at 4, and a group of more is launched four at a time:
    two programs a group key, both common enough for a warm-up to meet."""
    import elasticsearch_tpu.ops.scoring as scoring

    launched = []
    real = scoring.score_sorted_batch_async

    def spy(packed, batch, *args, **kwargs):
        launched.append(batch.n_queries)
        return real(packed, batch, *args, **kwargs)

    monkeypatch.setattr(scoring, "score_sorted_batch_async", spy)
    bodies = [{"query": _match(i), "size": 3, "sort": [{"rank": "desc"}]}
              for i in range(n)]
    got, stats = run_as_one_batch(feature_ctx, bodies)
    assert stats["kinds"]["sorted"] == {"launches": 1, "coalesced": n}
    segments = len(feature_ctx.searcher.segments)
    assert launched == [w for w in widths for _ in range(segments)]
    assert_answers_as_alone(feature_ctx, bodies, got)


class TestKindsShareACollect:
    @pytest.mark.parametrize("n_tail", [1, 2, 3, 5, 8])
    def test_a_mixed_collect_launches_each_group_once(self, feature_ctx, n_tail):
        """Plain, filtered, unscored, aggregated and sorted searches on one
        view are ONE collect (no kind in the flat key), and the batch
        launches a group for each kind and key it holds."""
        bodies = _mixed_bodies(n_tail)
        b = make_batcher(**{"search.batch.linger_ms": 5000,
                            "search.batch.max_batch": len(bodies)})
        try:
            got = run_query_phases(b, feature_ctx, bodies)
            stats = b.stats()
        finally:
            b.shutdown()
        assert_answers_as_alone(feature_ctx, bodies, got)
        assert stats["launches"] == 1 and stats["full_flushes"] == 1
        assert stats["coalesced"] == len(bodies)
        assert stats["bypassed"] == 0 and stats["splits"] == 0
        kinds = stats["kinds"]
        assert kinds["plain"] == {"launches": 1, "coalesced": 2}
        # scored filtered plans and unscored ones are a launch each
        assert kinds["filtered"] == {"launches": 2, "coalesced": 3}
        # scored and unscored members of one key launch apart
        groups = 2 if n_tail >= 4 else 1
        assert kinds["aggs"] == {"launches": groups, "coalesced": n_tail}
        assert kinds["sorted"] == {"launches": groups, "coalesced": n_tail}
        assert kinds["mesh"] == kinds["function_score"] == \
            {"launches": 0, "coalesced": 0}
        # the members add up to `coalesced`; the groups to `launches` plus
        # the extra groups of a batch that holds more than one
        n_groups, n_members = _kind_sums(stats)
        assert n_members == stats["coalesced"]
        assert n_groups == stats["launches"] + (2 + 2 * groups)

    def test_kind_counters_add_up_over_batches_of_one_kind(self, feature_ctx):
        """Where no batch mixes kinds, the per-kind launches sum to
        `launches` and the per-kind members to `coalesced`."""
        b = make_batcher(**{"search.batch.linger_ms": 5000,
                            "search.batch.max_batch": 3})
        try:
            for extra in ({}, {"aggs": AGGS}, {"sort": [{"day": "asc"}]},
                          {"aggs": AGGS}):
                bodies = [{"query": _match(i), "size": 5, **extra}
                          for i in range(3)]
                got = run_query_phases(b, feature_ctx, bodies)
                assert_answers_as_alone(feature_ctx, bodies, got)
            stats = b.stats()
        finally:
            b.shutdown()
        assert _kind_sums(stats) == (stats["launches"], stats["coalesced"]) \
            == (4, 12)
        assert stats["kinds"]["aggs"] == {"launches": 2, "coalesced": 6}
        assert stats["kinds"]["sorted"] == {"launches": 1, "coalesced": 3}
        assert stats["kinds"]["plain"] == {"launches": 1, "coalesced": 3}

    @pytest.mark.parametrize("where", ["launch", "finish"])
    @pytest.mark.parametrize("extra", [{"aggs": AGGS},
                                       {"sort": [{"rank": "asc"}]}],
                             ids=["aggs", "sorted"])
    def test_a_failing_group_is_replayed_per_item(self, feature_ctx,
                                                  monkeypatch, extra, where):
        """A group whose launch fails, or whose outputs fail after the
        batch's one pull, is replayed a member at a time (execute_single is
        the one-plan call): every member still gets its own answer from the
        device, and no kind counter books the batch."""
        import elasticsearch_tpu.search.execute as ex

        kind = "aggs" if "aggs" in extra else "sorted"
        real = ex.GROUP_KINDS[kind].launch
        calls = []

        def failing(pulled):
            raise RuntimeError("XLA: the coalesced launch's outputs failed")

        def failing_in_company(plans, *a, **kw):
            calls.append(len(plans))
            if len(plans) > 1 and where == "launch":
                raise RuntimeError("XLA: the coalesced launch failed")
            refs, finish = real(plans, *a, **kw)
            return refs, failing if len(plans) > 1 else finish

        monkeypatch.setitem(ex.GROUP_KINDS, kind, ex.GROUP_KINDS[kind]._replace(
            launch=failing_in_company))
        bodies = [{"query": _match(i), "size": 4, **extra} for i in range(3)]
        b = make_batcher(**{"search.batch.linger_ms": 5000,
                            "search.batch.max_batch": 3})
        try:
            got = run_query_phases(b, feature_ctx, bodies)
            stats = b.stats()
        finally:
            b.shutdown()
        monkeypatch.undo()
        assert calls == [3, 1, 1, 1]
        assert_answers_as_alone(feature_ctx, bodies, got)
        assert stats["splits"] == 1 and stats["launches"] == 0
        assert _kind_sums(stats) == (0, 0)

    def test_a_poisoned_member_degrades_alone(self, feature_ctx, monkeypatch):
        """The replay's verdict is per request: the one member whose own
        launch fails is served by the host, marked degraded; its neighbours
        keep their device answers."""
        import elasticsearch_tpu.search.execute as ex
        from elasticsearch_tpu.search.service import (SERVING_COUNTERS,
                                                      execute_query_phase,
                                                      parse_search_body)

        real = ex.GROUP_KINDS["aggs"].launch

        def poisoned(plans, *a, **kw):
            if any(p.filt is not None for p in plans):
                raise RuntimeError("XLA: this plan's launch fails")
            return real(plans, *a, **kw)

        monkeypatch.setitem(ex.GROUP_KINDS, "aggs",
                            ex.GROUP_KINDS["aggs"]._replace(launch=poisoned))
        bodies = [{"query": _match(0), "size": 4, "aggs": AGGS},
                  {"query": {"filtered": {"query": _match(1),
                                          "filter": _rank_filter(3)}},
                   "size": 4, "aggs": AGGS},
                  {"query": _match(2), "size": 4, "aggs": AGGS}]
        before = dict(SERVING_COUNTERS)
        b = make_batcher(**{"search.batch.linger_ms": 5000,
                            "search.batch.max_batch": 3})
        try:
            got = run_query_phases(b, feature_ctx, bodies)
            stats = b.stats()
        finally:
            b.shutdown()
        monkeypatch.undo()
        from elasticsearch_tpu.common.devicehealth import DEVICE_HEALTH

        DEVICE_HEALTH.reset()
        assert stats["splits"] == 1
        assert SERVING_COUNTERS["device_errors"] == before["device_errors"] + 1
        assert SERVING_COUNTERS["device_aggs"] == before["device_aggs"] + 2
        assert [r.degraded for r in got] == [False, True, False]
        assert_answers_as_alone(feature_ctx, [bodies[0], bodies[2]],
                                [got[0], got[2]])
        host = execute_query_phase(feature_ctx, parse_search_body(bodies[1]),
                                   use_device=False)
        assert got[1].total == host.total and \
            [d for _s, d, _v in got[1].docs] == [d for _s, d, _v in host.docs]

    @pytest.mark.parametrize("extra", [{"aggs": AGGS},
                                       {"sort": [{"rank": "asc"}]}],
                             ids=["aggs", "sorted"])
    def test_profiled_and_dfs_requests_launch_directly(self, feature_ctx,
                                                       extra):
        """What a shared batch cannot serve keeps launching on its request
        thread: a profiled request (counted as a profile bypass) and one
        that carries DFS statistics (counted as nothing)."""
        from elasticsearch_tpu.common import profile
        from elasticsearch_tpu.search.service import (execute_query_phase,
                                                      parse_search_body)

        body = {"query": _match(1), "size": 4, **extra}
        req = parse_search_body(body)
        alone = execute_query_phase(feature_ctx, req)
        b = make_batcher()
        try:
            ctx = ShardContext(feature_ctx.searcher, feature_ctx.mapper_service,
                               feature_ctx.similarity_service, batcher=b)
            prof = profile.ProfileCollector()
            with profile.activate(prof):
                profiled = execute_query_phase(ctx, req)
            assert b.stats()["profile_bypassed"] == 1
            dfs = ShardContext(
                feature_ctx.searcher, feature_ctx.mapper_service,
                feature_ctx.similarity_service, batcher=b,
                global_stats={"max_doc": feature_ctx.searcher.max_doc})
            with_dfs = execute_query_phase(dfs, req)
            stats = b.stats()
        finally:
            b.shutdown()
        assert stats["launches"] == 0 and stats["coalesced"] == 0
        assert stats["bypassed"] == 0 and stats["profile_bypassed"] == 1
        assert _kind_sums(stats) == (0, 0)
        for res in (profiled, with_dfs):
            assert res.total == alone.total
            assert [(d, v) for _s, d, v in res.docs] == \
                [(d, v) for _s, d, v in alone.docs]

    def test_a_sampled_member_gets_the_batchers_spans(self, feature_ctx):
        """An aggregated and a sorted search in one collect: each sampled
        member's trace holds batcher.queue, batcher.dispatch with the stages
        and the launches under it and then the batch's ONE pull, and
        batcher.merge — and no dispatch.stage of its own thread."""
        from elasticsearch_tpu.common import tracing
        from elasticsearch_tpu.common.tracing import Tracer, span_tree
        from elasticsearch_tpu.search.service import (execute_query_phase,
                                                      parse_search_body)

        tracer = Tracer(Settings.from_flat({"search.trace.sample_rate": "0"}),
                        node_name="test")
        bodies = [{"query": _match(0), "size": 4, "aggs": AGGS},
                  {"query": _match(1), "size": 4, "sort": [{"rank": "asc"}]}]
        b = make_batcher(**{"search.batch.linger_ms": 5000,
                            "search.batch.max_batch": 2})
        ctx = ShardContext(feature_ctx.searcher, feature_ctx.mapper_service,
                           feature_ctx.similarity_service, batcher=b)
        errs = []

        def worker(body):
            trace = tracer.start_trace("shard", force=True)
            try:
                with tracing.activate(trace.root):
                    execute_query_phase(ctx, parse_search_body(body))
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)
            finally:
                trace.root.end()

        try:
            threads = [threading.Thread(target=worker, args=(body,))
                       for body in bodies]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            b.shutdown()
        assert errs == []
        trees = [span_tree(t["spans"]) for t in tracer.traces()]
        assert len(trees) == 2
        for tree in trees:
            top = [c["name"] for c in tree["children"]]
            assert top == ["shard.lower", "batcher.queue", "batcher.dispatch",
                           "batcher.hold", "batcher.merge", "thread.wake"], top
            (dispatch,) = [c for c in tree["children"]
                           if c["name"] == "batcher.dispatch"]
            assert dispatch["tags"]["occupancy"] == 2
            kinds = [c["name"] for c in dispatch["children"]]
            # two groups, two segments each: a stage and a launch for each
            # of the four launches, in order, then one pull for them all
            assert kinds == ["dispatch.stage", "dispatch.launch"] * 4 \
                + ["device_pull"], kinds


# ---------------------------------------------------------------------------
# the linger's record: a lone search does not wait for companions that never come
# ---------------------------------------------------------------------------


class _EchoFamily:
    """Answers each item with its payload and keeps, a batch, its size and
    its head's wait from enqueue to dispatch. Where `gate` is set a dispatch
    stands in it until the test opens it (`entered` says one stands there)."""

    name = "fake"

    def __init__(self):
        self.sizes: list = []
        self.waits: list = []
        self.gate = None
        self.entered = threading.Event()

    def dispatch(self, items, kb):
        self.waits.append(time.monotonic() - items[0].t_enq)
        self.sizes.append(len(items))
        if self.gate is not None:
            self.entered.set()
            self.gate.wait(30)
        return [it.payload for it in items]

    def fan_out(self, handle, items):
        return handle

    def execute_single(self, item):
        return item.payload


def _echo_batcher(**flat):
    """(batcher, its fake family, the reasons of its flushes in order)."""
    b = make_batcher(**flat)
    reasons: list = []
    note = b._note_flush

    def noting(reason):
        reasons.append(reason)
        note(reason)

    b._note_flush = noting
    return b, _EchoFamily(), reasons


def _search(b, fam, payload="p"):
    return b._submit(_Item(fam, ("fake", "key"), payload, 10, 16, NO_DEADLINE))


def _together(b, fam, n):
    threads = [threading.Thread(target=_search, args=(b, fam, i))
               for i in range(n)]
    for t in threads:
        t.start()
    return threads


LINGER_S = 0.2


@pytest.fixture(scope="module")
def gone_lonely():
    """A batcher under a linger of 200 ms after a stream of lone searches,
    one at a time, eight more than its record holds: (batcher, family,
    reasons, its stats there)."""
    b, fam, reasons = _echo_batcher(**{"search.batch.linger_ms": LINGER_S * 1000})
    for i in range(_RECORD_HEADS + 8):
        assert _search(b, fam, i) == i
    yield b, fam, reasons, b.stats()
    b.shutdown()


class TestLingerRecord:
    def test_a_lone_stream_stops_paying_the_linger(self, gone_lonely):
        _b, fam, reasons, st = gone_lonely
        n = _RECORD_HEADS
        # while the record fills, the bet stands: every head waits it out
        assert reasons[:n] == ["linger"] * n
        assert min(fam.waits[:n]) >= LINGER_S * 0.9
        # a full record of heads that nobody joined: the later ones go at once
        assert reasons[n:n + 8] == ["alone"] * 8
        assert max(fam.waits[n:n + 8]) < LINGER_S / 4
        assert st["linger_flushes"] == n and st["alone_flushes"] == 8, st
        assert st["linger_bought"] == 0.0
        assert fam.sizes[:n + 8] == [1] * (n + 8)

    def test_a_burst_on_a_lonely_batcher_turns_the_linger_back_on(
            self, gone_lonely):
        b, fam, reasons, _st = gone_lonely
        at = len(reasons)
        fam.gate = threading.Event()
        fam.entered.clear()
        try:
            threads = _together(b, fam, 1)
            assert fam.entered.wait(10)  # the first of the burst, in dispatch
            threads += _together(b, fam, 7)
            t_end = time.monotonic() + 10
            while b.stats()["queue"] < 7 and time.monotonic() < t_end:
                time.sleep(0.001)
        finally:
            fam.gate.set()
            fam.gate = None
        for t in threads:
            t.join(30)
        # the first went alone, the seven behind it in ONE batch that waited
        # for no linger: the drainer had a batch to merge
        assert reasons[at:] == ["alone", "pending"]
        assert fam.sizes[at:] == [1, 7]
        # the head that did not wait was counted all the same, and the NEXT
        # head finds a record that says lingers buy companions here
        assert _search(b, fam) == "p"
        assert reasons[at + 2:] == ["linger"]
        assert fam.waits[-1] >= LINGER_S * 0.9
        assert b.stats()["linger_bought"] == round(7 / _RECORD_HEADS, 3)

    def test_groups_of_four_keep_their_batches(self):
        """Four searches that arrive inside one linger, a hundred times over:
        each head's line reads three companions, so the record never tips
        and the four keep leaving as one batch."""
        b, fam, reasons = _echo_batcher(**{"search.batch.linger_ms": 30})
        rounds = 100
        gate = threading.Barrier(4)

        def client():
            for _ in range(rounds):
                gate.wait(30)
                _search(b, fam)

        try:
            threads = [threading.Thread(target=client) for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            st = b.stats()
        finally:
            b.shutdown()
        assert st["coalesced"] == 4 * rounds
        assert st["occupancy_mean"] >= 3.5, st
        assert st["alone_flushes"] == 0 and "alone" not in reasons, st
        assert st["linger_bought"] >= 2.0, st

    @pytest.mark.parametrize("linger_ms, searches", [
        (40, 3),  # a fresh batcher has no record: the bet stands
        (0, _RECORD_HEADS + 8),  # `linger_ms: 0` never lingers: no bet, no record
    ])
    def test_what_never_goes_alone(self, linger_ms, searches):
        b, fam, reasons = _echo_batcher(**{"search.batch.linger_ms": linger_ms})
        try:
            for i in range(searches):
                assert _search(b, fam, i) == i
            st = b.stats()
        finally:
            b.shutdown()
        assert reasons == ["linger"] * searches
        assert st["alone_flushes"] == 0 and st["linger_flushes"] == searches
        if linger_ms:
            assert min(fam.waits) >= linger_ms / 1000.0 * 0.9
        else:
            assert max(fam.waits) < 0.05
            assert len(b._bought) == 0 and st["linger_bought"] == 0.0
