"""Plans with no scoring clause, and exact integer sort keys, on the device.

`match_all`, `constant_score`, a `range` or numeric `term` query, a bool with
no must or should, and `filtered` over any of them lower to a FlatPlan with no
clause (execute._unscored): match = filter mask & live, score = the constant
the host scorer gives, bitwise, plain hits in document order. The sorted and
aggregated tails take the same plan. A column of whole numbers that float32
cannot hold (epoch milliseconds) sorts on the device by each document's dense
rank among the segment's values (sorting.device_sort_rank_row). Every case
runs through `execute_query_phase` on both paths and must agree exactly.
"""

from __future__ import annotations

import contextlib
import math
import tempfile

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.mapper.core import MapperService
from elasticsearch_tpu.ops.scoring import LAUNCHES
from elasticsearch_tpu.search import ShardContext
from elasticsearch_tpu.search.aggregations import reduce_aggs
from elasticsearch_tpu.search.service import (
    SERVING_COUNTERS,
    execute_query_phase,
    parse_search_body,
)
from elasticsearch_tpu.search.similarity import SimilarityService

T0 = 893_980_800_000  # 1998-05-01T00:00:00Z in epoch milliseconds
WORDS = ["alpha", "beta", "gamma", "delta"]
MAPPING = {"doc": {"properties": {
    "body": {"type": "string"},
    "ts": {"type": "date"},
    "status": {"type": "integer"},
    "frac": {"type": "double"},
}}}


def _build(flat_settings: dict, refresh_at=(), delete=()):
    settings = Settings.from_flat(flat_settings)
    svc = MapperService(settings)
    svc.put_mapping("doc", MAPPING)
    eng = Engine(tempfile.mkdtemp(), svc)
    rng = np.random.default_rng(1998)
    for i in range(400):
        # seconds-resolution timestamps out of arrival order, dense ties: many
        # documents share a second, and neighbours differ by less than the
        # 65,536 ms that one float32 step spans at this magnitude
        d = {"body": " ".join(rng.choice(WORDS, size=4)),
             "ts": T0 + 1000 * int(rng.integers(0, 90)),
             "status": int(rng.choice([200, 200, 200, 304, 404, 500])),
             "frac": float(np.round(rng.uniform(1, 99), 3))}
        if i % 9 == 0:
            del d["ts"]  # missing values
        eng.index("doc", str(i), d)
        if i in refresh_at:
            eng.refresh()
    for i in delete:
        eng.delete("doc", str(i))
    eng.refresh()
    ctx = ShardContext(eng.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc))
    return eng, ctx


@pytest.fixture(scope="module", params=[
    ("one_segment_tfidf", {}, (), ()),
    ("segments_deletes_bm25", {"index.similarity.default.type": "BM25"},
     (99, 250), (3, 120, 121, 399)),
], ids=lambda p: p[0])
def ctx(request):
    _name, settings, refresh_at, delete = request.param
    eng, out = _build(settings, refresh_at, delete)
    assert len(out.searcher.segments) == len(refresh_at) + 1
    yield out
    eng.close()


def _counters() -> dict:
    return {**SERVING_COUNTERS, **LAUNCHES.snapshot()}


def _both(ctx, body, outcome: str):
    """Device against host: totals, ids in order, scores and sort values
    exactly; the device outcome counted once, the host scorer not at all."""
    req = parse_search_body(body)
    before = _counters()
    dev = execute_query_phase(ctx, req, use_device=True)
    after = _counters()
    host = execute_query_phase(ctx, req, use_device=False)
    assert after["host"] == before["host"], "the host scorer answered"
    assert after[outcome] == before[outcome] + 1
    assert after["device_errors"] == before["device_errors"]
    assert dev.total == host.total
    assert [g for _s, g, _v in dev.docs] == [g for _s, g, _v in host.docs]
    for (ds, _g, dv), (hs, _hg, hv) in zip(dev.docs, host.docs):
        assert dv == hv
        assert (math.isnan(ds) and math.isnan(hs)) or \
            np.float32(ds).tobytes() == np.float32(hs).tobytes()
    assert (math.isnan(dev.max_score) and math.isnan(host.max_score)) or \
        np.float32(dev.max_score).tobytes() == np.float32(host.max_score).tobytes()
    return req, dev, host, {k: after[k] - before[k] for k in after}


WINDOW = {"range": {"ts": {"gte": T0 + 10_000, "lt": T0 + 60_000}}}
UNSCORED = {
    "match_all": {"match_all": {}},
    "match_all_boost": {"match_all": {"boost": 2.5}},
    "constant_score_filter": {"constant_score": {"filter": WINDOW, "boost": 1.7}},
    "constant_score_query": {"constant_score": {
        "query": {"match": {"body": "alpha"}}, "boost": 0.3}},
    "range_query": {"range": {"ts": {"gte": T0 + 5_000, "lte": T0 + 30_000,
                                     "boost": 3.0}}},
    "numeric_term": {"term": {"status": {"value": 404, "boost": 1.3}}},
    "filtered_match_all": {"filtered": {"query": {"match_all": {}},
                                        "filter": WINDOW}},
    "filtered_range_under_range": {"filtered": {
        "query": {"range": {"status": {"gte": 400, "lt": 500}}},
        "filter": WINDOW, "boost": 1.9}},
    "filtered_cache_key_ignored": {"filtered": {
        "query": {"match_all": {}},
        "filter": {"range": {"ts": {"gte": T0 + 20_000, "lt": T0 + 70_000},
                             "_cache": False}}}},
    "must_not_only": {"bool": {"must_not": [{"term": {"body": "alpha"}},
                                            {"match": {"body": "beta"}}]}},
    "bool_filter_only": {"bool": {"filter": [WINDOW], "boost": 1.4}},
    "matches_nothing": {"range": {"ts": {"gte": T0 - 5_000, "lt": T0 - 1_000}}},
}


@pytest.mark.parametrize("shape", sorted(UNSCORED))
def test_unscored_plan_gives_the_hosts_answer(ctx, shape):
    """Plain hits: document order (every score is the same constant)."""
    _req, dev, _host, delta = _both(
        ctx, {"query": UNSCORED[shape], "size": 25}, "device_filtered")
    assert delta["unscored_plans"] == 1
    assert delta["launches_unscored"] == len(ctx.searcher.segments)
    assert delta["blocks_launched"] == 0  # no postings block was read
    ids = [g for _s, g, _v in dev.docs]
    assert ids == sorted(ids)
    if shape == "matches_nothing":
        assert dev.total == 0
    else:
        assert dev.total > 0 and len({s for s, _g, _v in dev.docs}) == 1


def test_a_scored_plan_counts_no_unscored_launch(ctx):
    _req, _dev, _host, delta = _both(
        ctx, {"query": {"filtered": {"query": {"match": {"body": "alpha"}},
                                     "filter": WINDOW}}, "size": 10},
        "device_filtered")
    assert delta["unscored_plans"] == 0 and delta["launches_unscored"] == 0


FS_SUBS = {
    "match_all": {"match_all": {}},
    "constant_score": {"constant_score": {"filter": WINDOW, "boost": 1.7}},
    "range": {"range": {"status": {"gte": 200, "lt": 404, "boost": 1.5}}},
}
# kind "rows": no function reads _score; kind "script": one script that does
FS_FUNCTIONS = {
    "rows": [{"field_value_factor": {"field": "status", "factor": 0.5,
                                     "modifier": "log1p"}},
             {"gauss": {"frac": {"origin": 40, "scale": 15, "decay": 0.3}},
              "weight": 1.25}],
    "script": [{"script_score": {
        "script": "abs(log(doc['status'].value + 1) - doc['frac'].value) * _score"}}],
}


MAX_BOOST = {"rows": 2.6, "script": 40.0}
MIN_SCORE = {"rows": 2.3, "script": 10.0}


def _fs_both(ctx, body, script: bool):
    """Device against host for a function_score: totals and ids in order; the
    scores bit for bit for host-combined rows, to 1e-6 for a script the
    device evaluates in float32. Counted as device_function_score, the host
    scorer not at all."""
    req = parse_search_body(body)
    before = _counters()
    dev = execute_query_phase(ctx, req, use_device=True)
    after = _counters()
    host = execute_query_phase(ctx, req, use_device=False)
    assert after["host"] == before["host"], "the host scorer answered"
    assert after["device_function_score"] == before["device_function_score"] + 1
    assert after["device_errors"] == before["device_errors"]
    assert dev.total == host.total
    if script:
        np.testing.assert_allclose([s for s, _g, _v in dev.docs],
                                   [s for s, _g, _v in host.docs], rtol=1e-6)
    else:
        assert [(np.float32(s).tobytes(), g) for s, g, _v in dev.docs] == \
            [(np.float32(s).tobytes(), g) for s, g, _v in host.docs]
    return dev, {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("boost_mode",
                         ["multiply", "replace", "sum", "avg", "max", "min"])
@pytest.mark.parametrize("kind", sorted(FS_FUNCTIONS))
@pytest.mark.parametrize("sub", sorted(FS_SUBS))
def test_function_score_over_an_unscored_query(ctx, sub, kind, boost_mode):
    """function_score over a query with no scoring clause launches the fused
    function tails behind the unscored ABI: `_score` is the sub query's
    constant, the match set its mask."""
    dev, delta = _fs_both(ctx, {"query": {"function_score": {
        "query": FS_SUBS[sub], "functions": FS_FUNCTIONS[kind],
        "score_mode": "sum", "boost_mode": boost_mode, "boost": 1.3}},
        "size": 25}, script=kind == "script")
    assert dev.total > 0
    assert delta["unscored_plans"] == 1
    assert delta["launches_fs_unscored"] == len(ctx.searcher.segments)
    assert delta["launches_unscored"] == len(ctx.searcher.segments)
    assert delta["blocks_launched"] == 0  # no postings block was read
    assert delta["fs_row_put_bytes"] > 0


@pytest.mark.parametrize("kind", sorted(FS_FUNCTIONS))
def test_function_score_over_an_unscored_query_min_score_and_max_boost(ctx, kind):
    body = {"query": {"function_score": {
        "query": {"match_all": {}}, "functions": FS_FUNCTIONS[kind],
        "score_mode": "sum", "max_boost": MAX_BOOST[kind],
        "min_score": MIN_SCORE[kind]}}, "size": 25}
    dev, _delta = _fs_both(ctx, body, script=kind == "script")
    everything = execute_query_phase(ctx, parse_search_body(
        {"query": {"match_all": {}}}), use_device=False).total
    assert 0 < dev.total < everything  # min_score gates the total
    scores = [s for s, _g, _v in dev.docs]
    assert all(MIN_SCORE[kind] <= s <= MAX_BOOST[kind] + 1e-6 for s in scores)
    assert scores[0] == pytest.approx(MAX_BOOST[kind])  # max_boost caps


def test_function_score_groups_keep_scored_and_unscored_apart(ctx):
    from elasticsearch_tpu.search.execute import _flat_groups, lower_flat
    from elasticsearch_tpu.search.queries import parse_query

    def fs(sub):
        return lower_flat(parse_query({"function_score": {
            "query": sub, "functions": [{"boost_factor": 2.0}]}}), ctx)

    plans = [fs({"match_all": {}}), fs({"match": {"body": "alpha"}}),
             fs({"range": {"status": {"gte": 300}}})]
    assert [p.const is not None for p in plans] == [True, False, True]
    groups = _flat_groups(plans)
    assert sorted(groups.values()) == [[0, 2], [1]]
    assert all(g[0] == "function_score" for g in groups)


@pytest.mark.parametrize("order", ["asc", "desc"])
@pytest.mark.parametrize("missing", ["_last", "_first", T0 + 30_500, T0 + 31_000])
@pytest.mark.parametrize("query", ["match", "match_all", "window"])
def test_sort_on_epoch_milliseconds(ctx, order, missing, query):
    """Dense ties, documents out of time order, missing values under every
    policy (a custom fill between two values, and one equal to a value)."""
    q = {"match": {"match": {"body": "gamma"}}, "match_all": {"match_all": {}},
         "window": {"filtered": {"query": {"match_all": {}}, "filter": WINDOW}}}
    _req, dev, _host, delta = _both(
        ctx, {"query": q[query], "size": 80,
              "sort": [{"ts": {"order": order, "missing": missing}}]},
        "device_sort")
    assert delta["unscored_plans"] == (0 if query == "match" else 1)
    keys = [v[0] for _s, _g, v in dev.docs if v[0] is not None]
    assert keys == sorted(keys, reverse=order == "desc")
    assert len(set(keys)) < len(keys), "the column was built with ties"


def test_float32_keys_would_misorder_this_column(ctx):
    """The float32-key path fails the same check: at 8.9e11 one float32 step
    is 65,536 ms, so the column's 90 distinct seconds collapse to two keys."""
    from elasticsearch_tpu.search.sorting import (
        SortSpec, device_sort_key_row, device_sort_rank_row, exact_sort_keys)

    spec = SortSpec("ts", "asc")
    for seg in ctx.searcher.segments:
        exact = exact_sort_keys(spec, seg)
        has = np.isfinite(exact)
        as_f32 = exact[has].astype(np.float32)
        assert len(np.unique(as_f32)) < len(np.unique(exact[has])) / 10
        by_f32 = np.lexsort((np.arange(has.sum()), as_f32))
        by_exact = np.lexsort((np.arange(has.sum()), exact[has]))
        assert list(by_f32[:40]) != list(by_exact[:40])
        # so the value row refuses the column, and the rank row orders as exact
        assert device_sort_key_row(spec, seg, seg.doc_count) is None
        ranks = device_sort_rank_row(spec, seg, seg.doc_count)
        assert ranks.dtype == np.float32
        assert list(np.lexsort((np.arange(has.sum()), ranks[has]))) == list(by_exact)


def test_rank_rows_are_resident_and_on_the_ledger(ctx):
    from elasticsearch_tpu.ops.device_index import (packed_for,
                                                    segment_capacity)

    _both(ctx, {"query": {"match_all": {}}, "size": 5,
                "sort": [{"ts": "desc"}]}, "device_sort")
    before = LAUNCHES.snapshot()["operand_puts"]
    _both(ctx, {"query": {"match_all": {}}, "size": 5,
                "sort": [{"ts": "desc"}]}, "device_sort")
    # a warmed sorted launch of an unscored plan puts ONE leaf: its plane,
    # the constant's bits (the key row and the no-op mask are resident)
    assert LAUNCHES.snapshot()["operand_puts"] - before == \
        len(ctx.searcher.segments)
    for seg in ctx.searcher.segments:
        packed = packed_for(seg)
        assert ("ts", None, "desc", "'_last'") in packed.sort_rows
        row = segment_capacity(seg)
        assert row["sort_key_rows"] == len(packed.sort_rows) >= 1
        assert row["tiers"]["sort_keys"] == \
            len(packed.sort_rows) * packed.doc_pad * 4


def test_a_fractional_column_still_sorts_on_the_host(ctx):
    req = parse_search_body({"query": {"match_all": {}}, "size": 10,
                             "sort": [{"frac": "asc"}]})
    before = _counters()
    dev = execute_query_phase(ctx, req, use_device=True)
    after = _counters()
    assert after["device_sort"] == before["device_sort"]
    assert after["host"] == before["host"] + 1
    host = execute_query_phase(ctx, req, use_device=False)
    assert [g for _s, g, _v in dev.docs] == [g for _s, g, _v in host.docs]


@pytest.mark.parametrize("query", ["match_all", "window", "status_under_window"])
def test_hourly_histogram_under_a_window_equals_the_host_collectors(ctx, query):
    q = {"match_all": {"match_all": {}},
         "window": {"filtered": {"query": {"match_all": {}}, "filter": WINDOW}},
         "status_under_window": {"filtered": {
             "query": {"range": {"status": {"gte": 200, "lt": 300}}},
             "filter": WINDOW}}}
    body = {"query": q[query], "size": 0, "aggs": {
        "by_minute": {"date_histogram": {"field": "ts", "interval": "minute"}},
        "by_hour": {"date_histogram": {"field": "ts", "interval": "hour"}},
        "sizes": {"stats": {"field": "status"}}}}
    req, dev, host, delta = _both(ctx, body, "device_aggs")
    assert delta["unscored_plans"] == 1
    got = reduce_aggs(req.aggs, dev.agg_partials)
    assert got == reduce_aggs(req.aggs, host.agg_partials)
    assert sum(b["doc_count"] for b in got["by_minute"]["buckets"]) > 0


# ---------------------------------------------------------------------------
# what the log deployment forced beside the plan: a query's mask as a filter,
# multi-fields, the mask's span and counters
# ---------------------------------------------------------------------------


def test_a_querys_mask_is_admitted_as_a_filters_is(ctx):
    """A `range` or numeric `term` query lowers to the plain filter: what the
    filter caches keep of its mask is decided by recurrence, as for any other
    (below). ES 1.x's `"_cache"` key is accepted and decides nothing."""
    from elasticsearch_tpu.search import parse_query
    from elasticsearch_tpu.search.execute import lower_flat
    from elasticsearch_tpu.search.filters import RangeFilter, TermFilter

    bounds = {"gte": T0 + 21_000, "lt": T0 + 71_000}
    plan = lower_flat(parse_query({"range": {"ts": bounds}}), ctx)
    assert isinstance(plan.filt, RangeFilter) and plan.filt.cacheable()
    plan = lower_flat(parse_query({"term": {"status": 404}}), ctx)
    assert isinstance(plan.filt, TermFilter) and plan.filt.cacheable()
    keys = [lower_flat(parse_query({"filtered": {
        "query": {"match_all": {}},
        "filter": {"range": {"ts": bounds, **extra}}}}), ctx).filt.key()
        for extra in ({}, {"_cache": False}, {"_cache": True})]
    assert len(set(keys)) == 1


def test_the_masks_assembly_is_a_part_of_the_stage_span():
    """`shard.filter_mask` is a note inside the running `dispatch.stage`
    interval: the marks stay gap-free and measure what they measured."""
    from elasticsearch_tpu.common import tracing

    class Parent:
        def __init__(self, name="root"):
            self.name, self.children = name, []

        def record(self, name, t0, t1, **tags):
            child = Parent(name)
            child.t0, child.t1 = t0, t1
            self.children.append(child)
            return child

    import time
    with tracing.timing_dispatch() as clock:
        t0 = time.monotonic()
        tracing.note("shard.filter_mask", t0)
        tracing.mark("dispatch.stage")
        tracing.mark("dispatch.launch")
    root = Parent()
    clock.record_under(root)
    assert [c.name for c in root.children] == ["dispatch.stage", "dispatch.launch"]
    stage, launch = root.children
    (part,) = stage.children
    assert part.name == "shard.filter_mask" and launch.children == []
    assert stage.t0 <= part.t0 <= part.t1 <= stage.t1 <= launch.t0


def test_a_multi_field_indexes_its_sub_field_and_numeric_masks_stay_exact():
    settings = Settings.from_flat({"index.similarity.default.type": "BM25"})
    svc = MapperService(settings)
    svc.put_mapping("doc", {"doc": {"properties": {
        "request": {"type": "string",
                    "fields": {"raw": {"type": "string", "index": "not_analyzed"}}},
        "sizes": {"type": "integer"}}}})
    eng = Engine(tempfile.mkdtemp(), svc)
    lines = ["GET /a/b.gif HTTP/1.0", "GET /a/c.gif HTTP/1.0", "POST /a/b.gif HTTP/1.1"]
    rng = np.random.default_rng(5)
    sizes = []
    for i in range(90):
        sizes.append([int(v) for v in rng.integers(0, 50, size=i % 4)])
        eng.index("doc", str(i), {"request": lines[i % 3], "sizes": sizes[-1]})
    eng.refresh()
    ctx = ShardContext(eng.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc))
    try:
        (seg,) = ctx.searcher.segments
        assert sorted(seg.term_dict["request.raw"]) == sorted(lines)
        assert "gif" in seg.term_dict["request"]  # the parent stays analysed
        _req, dev, _host, _delta = _both(
            ctx, {"query": {"term": {"request.raw": {"value": lines[2]}}},
                  "size": 40}, "device_sparse")
        assert dev.total == 30 and all(g % 3 == 2 for _s, g, _v in dev.docs)
        # a multi-valued column: a document matches when any value does
        _req, dev, _host, _delta = _both(
            ctx, {"query": {"range": {"sizes": {"gte": 10, "lt": 20}}},
                  "size": 100}, "device_filtered")
        want = [i for i, vs in enumerate(sizes) if any(10 <= v < 20 for v in vs)]
        assert [g for _s, g, _v in dev.docs] == want
    finally:
        eng.close()


def test_a_coalesced_batch_of_every_kind_of_plan(ctx):
    """What the batcher hands execute_flat_batch: plain, filtered and unscored
    plans in one batch, each kind its own launch; three unscored plans ride the
    four-query program (the count is padded up the pow-2 ladder)."""
    from elasticsearch_tpu.search import parse_query
    from elasticsearch_tpu.search.execute import search_shard_batch

    queries = [parse_query(q) for q in (
        UNSCORED["range_query"],
        {"match": {"body": "alpha beta"}},
        UNSCORED["filtered_cache_key_ignored"],
        {"filtered": {"query": {"match": {"body": "gamma"}}, "filter": WINDOW}},
        UNSCORED["match_all_boost"],
    )]
    before = LAUNCHES.snapshot()
    dev = search_shard_batch(ctx, queries, 15, use_device=True)
    after = LAUNCHES.snapshot()
    host = search_shard_batch(ctx, queries, 15, use_device=False)
    assert after["unscored_plans"] - before["unscored_plans"] == 3
    assert after["launches_unscored"] - before["launches_unscored"] == \
        len(ctx.searcher.segments)
    # the launch's reckoning is of the padded four queries
    assert after["unscored_bytes"] - before["unscored_bytes"] == sum(
        4 * 5 * _doc_pad(seg) for seg in ctx.searcher.segments)
    for d, h in zip(dev, host):
        assert d.total == h.total
        assert [g for _s, g in d.hits] == [g for _s, g in h.hits]
        assert [np.float32(s).tobytes() for s, _g in d.hits] == \
            [np.float32(s).tobytes() for s, _g in h.hits]


def test_resident_rows_ride_the_same_ladder_of_query_counts(ctx):
    """Three coalesced unscored plans of which one filter's row is resident
    (the dashboard's whole-index window beside two windows that never recur):
    the mask matrix comes as a device stack, and the launch is still the
    four-query program's, the padding rows a resident row that matches nothing."""
    from elasticsearch_tpu.ops.device_index import DeviceFilterCache
    from elasticsearch_tpu.search import parse_query
    from elasticsearch_tpu.search.execute import search_shard_batch

    def window(lo):
        return {"filtered": {"query": {"match_all": {}}, "filter": {"range": {
            "ts": {"gte": T0 + lo, "lt": T0 + lo + 30_000}}}}}

    fc = DeviceFilterCache()
    old, ctx.filter_cache = ctx.filter_cache, fc
    try:
        for _ in range(2):  # the second sighting keeps the recurring row
            search_shard_batch(ctx, [parse_query(window(11_000))], 15,
                               use_device=True)
        assert fc.stats()["masks"] == len(ctx.searcher.segments)
        queries = [parse_query(window(lo)) for lo in (11_000, 33_000, 44_000)]
        before = LAUNCHES.snapshot()
        dev = search_shard_batch(ctx, queries, 15, use_device=True)
        after = LAUNCHES.snapshot()
        host = search_shard_batch(ctx, queries, 15, use_device=False)
        # two host rows put, one resident; the reckoning is of four queries
        assert after["mask_put_bytes"] - before["mask_put_bytes"] == sum(
            2 * _doc_pad(seg) for seg in ctx.searcher.segments)
        assert after["unscored_bytes"] - before["unscored_bytes"] == sum(
            4 * 5 * _doc_pad(seg) for seg in ctx.searcher.segments)
        for d, h in zip(dev, host):
            assert d.total == h.total > 0
            assert [g for _s, g in d.hits] == [g for _s, g in h.hits]
            assert [np.float32(s).tobytes() for s, _g in d.hits] == \
                [np.float32(s).tobytes() for s, _g in h.hits]
    finally:
        ctx.filter_cache = old


def _doc_pad(seg) -> int:
    from elasticsearch_tpu.ops.device_index import packed_for

    return packed_for(seg).doc_pad


def test_a_pack_is_in_flight_while_its_device_program_runs():
    """The host enqueues a compaction's concat in seconds and the device runs it
    for a minute and more: the ledger calls the pack in flight until the
    program's output is ready, asking without waiting."""
    from elasticsearch_tpu.ops.device_index import PackLedger

    class Output:
        ready = False

        def is_ready(self):
            return self.ready

    ledger = PackLedger()
    assert ledger.idle_s() is None  # never packed
    ledger.begin()
    assert ledger.idle_s() == 0.0  # the host is at it
    out = Output()
    ledger.end(out)
    assert ledger.idle_s() == 0.0  # the device still is
    out.ready = True
    assert 0.0 <= ledger.idle_s() < 1.0
    ledger.begin()
    ledger.end()
    assert 0.0 <= ledger.idle_s() < 1.0


def test_a_bare_count_stays_on_the_host(ctx):
    """`_count` is a search of size 0 with no aggregation: it launches nothing
    (on the device it would pack every segment of a fresh index)."""
    req = parse_search_body({"query": {"match_all": {}}, "size": 0})
    before = _counters()
    got = execute_query_phase(ctx, req, use_device=True)
    after = _counters()
    assert after["host"] == before["host"] + 1
    assert after["unscored_plans"] == before["unscored_plans"]
    assert got.total == execute_query_phase(ctx, req, use_device=False).total > 0


# ---------------------------------------------------------------------------
# what the log deployment's size forced: the meta fields' postings stay on the
# host, and a filter earns its cache entries by recurring
# ---------------------------------------------------------------------------


def test_the_meta_fields_take_no_block_of_the_device_planes(ctx):
    """`_id` and `_uid` hold one document a term: packed, a 128-slot block
    each (two blocks a document, what refused a segment of a million)."""
    from elasticsearch_tpu.ops.device_index import (
        BLOCK, HOST_ONLY_FIELDS, device_counts, pack_estimate_bytes, pack_segment,
        pack_shape_math, packed_resident_bytes)

    for seg in ctx.searcher.segments:
        full = np.diff(seg.post_offsets)
        held = device_counts(seg)
        packed = pack_segment(seg)
        for f in HOST_ONLY_FIELDS:
            assert len(seg.term_dict[f]) == seg.doc_count
            for tid in list(seg.term_dict[f].values())[:5]:
                b0, b1 = packed.blocks_for_term(tid)
                assert b0 == b1 and held[tid] == 0 and full[tid] == 1
        for term, tid in seg.term_dict["body"].items():
            b0, b1 = packed.blocks_for_term(tid)
            docs = packed.host_docs[b0 * BLOCK: b1 * BLOCK]
            assert sorted(docs[docs < seg.doc_count].tolist()) == \
                sorted(seg.postings("body", term)[0].tolist())
        blocks = int(((held + BLOCK - 1) // BLOCK).sum())
        assert blocks < seg.doc_count  # not two a document
        NBpad = pack_shape_math(seg)[0]
        assert NBpad == packed.blk_docs.shape[0] > blocks
        # the breaker's estimate is reckoned from the planes as packed (the
        # same shape math): it covers what is resident, and even padded the
        # planes are smaller than two blocks a document more would have been
        assert packed_resident_bytes(packed) <= pack_estimate_bytes(seg)
        assert NBpad < blocks + 2 * seg.doc_count


@pytest.mark.parametrize("field,value", [("_id", "17"), ("_uid", "doc#17")])
def test_a_term_on_a_meta_field_is_the_hosts(ctx, field, value):
    from elasticsearch_tpu.search import parse_query
    from elasticsearch_tpu.search.execute import lower_fallback_reason, lower_flat

    body = {"query": {"term": {field: value}}, "size": 3}
    req = parse_search_body(body)
    assert lower_flat(req.query, ctx) is None
    assert lower_fallback_reason(req.query, ctx) == "host_only_field"
    # inside a bool beside a scored clause as well
    both = parse_query({"bool": {"must": [{"term": {field: value}},
                                          {"match": {"body": "alpha beta gamma delta"}}]}})
    assert lower_flat(both, ctx) is None
    before = _counters()
    got = execute_query_phase(ctx, req, use_device=True)
    after = _counters()
    assert got.total == 1 and after["host"] == before["host"] + 1
    assert after["device_errors"] == before["device_errors"]


def test_a_filter_earns_its_cache_entries_by_recurring(ctx):
    """Neither filter cache keeps a mask whose filter was sighted once among
    the last 256: a dashboard's windows never recur and fill nothing, a
    recurring filter is kept from its second sighting on, as before."""
    from elasticsearch_tpu.ops.device_index import DeviceFilterCache, RecentKeys

    seen = RecentKeys(horizon=4)
    assert [seen.sight(k) for k in "abacdef"] == [1, 1, 2, 1, 1, 1, 1]
    assert seen.get("a") == 0 and seen.get("c") == 1 and len(seen.items()) == 4
    assert seen.sight("g", 2) == 2

    from elasticsearch_tpu.search.filters import RangeFilter, segment_mask

    seg = ctx.searcher.segments[0]
    once = RangeFilter("ts", T0 + 123, None, None, T0 + 45_678)
    first = segment_mask(seg, once, ctx)
    assert once.key() not in seg._device_cache["filters"]  # evaluated, dropped
    again = segment_mask(seg, once, ctx)
    assert seg._device_cache["filters"][once.key()] is again  # kept from now on
    assert segment_mask(seg, once, ctx) is again and (first == again).all()

    fc = DeviceFilterCache()
    old, ctx.filter_cache = ctx.filter_cache, fc
    try:
        def search(lo):
            """Through the device path alone (the host scorer would sight the
            window a second time at once); the bytes of host rows it put."""
            req = parse_search_body({"query": {"filtered": {
                "query": {"match_all": {}}, "filter": {"range": {"ts": {
                    "gte": T0 + lo, "lt": T0 + lo + 40_000}}}}}, "size": 3})
            before = _counters()
            got = execute_query_phase(ctx, req, use_device=True)
            after = _counters()
            assert after["host"] == before["host"]
            assert got.total == execute_query_phase(ctx, req, use_device=False).total
            return after["mask_put_bytes"] - before["mask_put_bytes"]

        for i in range(300):  # windows that never recur
            assert search(1_000 + 2 * i) > 0
        # (the comparison above asked the host scorer once a window as well:
        # two sightings on the host, one in the device cache's history)
        assert fc.stats()["masks"] == 0
        for seg in ctx.searcher.segments:
            assert len(seg._device_cache["filter_sightings"].items()) <= 256
            assert len(seg._device_cache["filter_masks"].seen.items()) <= 256
        assert search(7) > 0
        assert fc.stats()["masks"] == 0
        assert search(7) > 0  # the second sighting evaluates, puts and keeps
        assert fc.stats()["masks"] == len(ctx.searcher.segments)
        assert search(7) == 0  # resident rows: nothing evaluated, nothing put
    finally:
        ctx.filter_cache = old


def test_a_rung_of_unfiltered_plans_is_one_program_full_or_padded(ctx):
    """Two, three and four function scores over match_all in one batch launch
    ONE program at the rung of four: the rung's resident mask stands in for a
    filter whether the rung is full or padded (a full rung under the [1, 1]
    no-op was a second program, first met inside a measured window)."""
    from elasticsearch_tpu.common.jaxenv import thread_compile_totals as compile_totals
    from elasticsearch_tpu.search.execute import execute_flat_batch, lower_flat
    from elasticsearch_tpu.search.queries import parse_query

    plan = lower_flat(parse_query({"function_score": {
        "query": {"match_all": {}},
        "functions": [{"field_value_factor": {"field": "status"}}]}}), ctx)
    one = execute_flat_batch([plan], ctx, 10)[0]
    execute_flat_batch([plan] * 3, ctx, 10)  # the rung of four, padded
    before = compile_totals()
    for n in (2, 4, 3, 4):
        got = execute_flat_batch([plan] * n, ctx, 10)
        assert all(r.total == one.total and r.hits == one.hits for r in got)
    assert compile_totals() == before


# ---------------------------------------------------------------------------
# function rows in the device row store (DeviceFilterCache's FUNCTION_ROWS key
# space): looked up before they are evaluated, admitted as the masks are
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _row_store(ctx, budget="64mb"):
    """A fresh row store on `ctx` that charges a fielddata breaker of its
    own. The holders live on the module's segments, so the way out clears
    them (what `_cache/clear` does a segment) and the breaker has to be back
    at zero."""
    from elasticsearch_tpu.common.breaker import CircuitBreakerService
    from elasticsearch_tpu.ops.device_index import DeviceFilterCache

    breaker = CircuitBreakerService(Settings.from_flat(
        {"indices.breaker.total_budget": budget})).breaker("fielddata")
    fc = DeviceFilterCache(breaker=breaker)
    old, ctx.filter_cache = ctx.filter_cache, fc
    try:
        yield fc, breaker
    finally:
        ctx.filter_cache = old
        for seg in ctx.searcher.segments:
            fc.clear_segment(seg)
        assert breaker.used == 0
        assert fc.stats()["function_rows"]["memory_size_in_bytes"] == 0


def _row_bytes(ctx, kind: str) -> int:
    """What a launch group's rows weigh over the searcher's segments: a
    float32 function row and a bool applies row, or the script's two float32
    column rows and three bool rows."""
    per_doc = {"rows": 4 + 1, "script": 2 * 4 + 3}[kind]
    return sum(per_doc * _doc_pad(seg) for seg in ctx.searcher.segments)


def _three_times(ctx, body, kind: str):
    """A function_score search a first, a second and a third time on a fresh
    row store: miss, store, hit. Every answer is the first's bit for bit
    (and the host's: _fs_both), the rows go down once more with the store
    and never again."""
    n_segs = len(ctx.searcher.segments)
    with _row_store(ctx) as (fc, breaker):
        answers, deltas = zip(*(_fs_both(ctx, body, script=kind == "script")
                                for _ in range(3)))
        for dev in answers[1:]:
            assert dev.total == answers[0].total
            assert [(np.float32(s).tobytes(), g) for s, g, _v in dev.docs] == \
                [(np.float32(s).tobytes(), g) for s, g, _v in answers[0].docs]
        assert [d["fs_rows_evaluated"] for d in deltas] == [n_segs, n_segs, 0]
        assert [d["fs_rows_resident"] for d in deltas] == [0, 0, n_segs]
        assert [d["fs_row_put_bytes"] for d in deltas] == \
            [_row_bytes(ctx, kind), _row_bytes(ctx, kind), 0]
        rows = fc.stats()["function_rows"]
        assert rows["entries"] == rows["builds"] == n_segs
        assert rows["hits"] == n_segs and rows["misses"] == 2 * n_segs
        assert rows["memory_size_in_bytes"] == _row_bytes(ctx, kind)
        assert breaker.used >= _row_bytes(ctx, kind)  # and the sub query's mask
        return deltas


@pytest.mark.parametrize("boost_mode",
                         ["multiply", "replace", "sum", "avg", "max", "min"])
@pytest.mark.parametrize("kind", sorted(FS_FUNCTIONS))
def test_function_rows_go_resident_at_their_second_sighting(ctx, kind, boost_mode):
    deltas = _three_times(ctx, {"query": {"function_score": {
        "query": FS_SUBS["constant_score"], "functions": FS_FUNCTIONS[kind],
        "score_mode": "sum", "boost_mode": boost_mode, "boost": 1.3}},
        "size": 25}, kind)
    assert all(d["launches_fs_unscored"] == len(ctx.searcher.segments)
               for d in deltas)


@pytest.mark.parametrize("kind", sorted(FS_FUNCTIONS))
def test_resident_function_rows_under_min_score_and_max_boost(ctx, kind):
    _three_times(ctx, {"query": {"function_score": {
        "query": {"match_all": {}}, "functions": FS_FUNCTIONS[kind],
        "score_mode": "sum", "max_boost": MAX_BOOST[kind],
        "min_score": MIN_SCORE[kind]}}, "size": 25}, kind)


@pytest.mark.parametrize("kind", sorted(FS_FUNCTIONS))
def test_resident_function_rows_behind_the_scored_abi(ctx, kind):
    """A text query under the function_score: the tail gathers the same rows
    beside BM25 or TF-IDF, and finds them in the same store."""
    deltas = _three_times(ctx, {"query": {"function_score": {
        "query": {"match": {"body": "alpha gamma"}},
        "functions": FS_FUNCTIONS[kind], "score_mode": "sum",
        "boost_mode": "sum"}}, "size": 25}, kind)
    assert all(d["launches_fs_unscored"] == 0 and d["unscored_plans"] == 0
               for d in deltas)


def test_the_launch_scalars_are_no_part_of_a_rows_key(ctx):
    """boost_mode, boost, max_boost and min_score are scalars of the launch:
    specs that differ in them alone read one resident row."""
    def body(**scalars):
        return {"query": {"function_score": {
            "query": {"match_all": {}}, "functions": FS_FUNCTIONS["rows"],
            "score_mode": "sum", **scalars}}, "size": 25}

    n_segs = len(ctx.searcher.segments)
    with _row_store(ctx) as (fc, _breaker):
        _fs_both(ctx, body(boost_mode="sum"), script=False)
        _fs_both(ctx, body(boost_mode="max", boost=2.0), script=False)
        _dev, delta = _fs_both(
            ctx, body(boost_mode="replace", max_boost=2.6, min_score=2.3),
            script=False)
        assert delta["fs_rows_resident"] == n_segs
        assert fc.stats()["function_rows"]["entries"] == n_segs
        # another score_mode combines another row
        _dev, delta = _fs_both(ctx, {"query": {"function_score": {
            "query": {"match_all": {}}, "functions": FS_FUNCTIONS["rows"],
            "score_mode": "multiply"}}, "size": 25}, script=False)
        assert delta["fs_rows_resident"] == 0


def test_two_origins_keep_two_rows(ctx):
    """Specs that differ in a decay's origin alone are two keys: each earns
    its own entry and neither ever reads the other's row."""
    def near(origin):
        return {"query": {"function_score": {
            "query": {"match_all": {}},
            "functions": [{"gauss": {"frac": {
                "origin": origin, "scale": 9, "decay": 0.2}}}],
            "boost_mode": "replace"}}, "size": 25}

    n_segs = len(ctx.searcher.segments)
    with _row_store(ctx) as (fc, _breaker):
        answers = {}
        for _ in range(3):
            for origin in (20, 70):
                dev, delta = _fs_both(ctx, near(origin), script=False)
                answers.setdefault(origin, []).append(
                    [(np.float32(s).tobytes(), g) for s, g, _v in dev.docs])
        assert delta["fs_rows_resident"] == n_segs
        assert fc.stats()["function_rows"]["entries"] == 2 * n_segs
        for got in answers.values():
            assert got[0] == got[1] == got[2]
        assert answers[20][0] != answers[70][0]


@pytest.mark.parametrize("origin", [None, "now-1h"], ids=["none", "now"])
def test_a_decay_that_reads_the_clock_is_never_stored(ctx, origin):
    """A date decay with no origin, or with `now` in it, resolves its origin
    against the clock at every evaluation (functions._parse_origin): its row
    is not the segment's own, and the store is never asked."""
    decay = {"scale": "30s", "decay": 0.5}
    if origin is not None:
        decay["origin"] = origin
    req = parse_search_body({"query": {"function_score": {
        "query": {"match_all": {}}, "functions": [{"gauss": {"ts": decay}}],
        "boost_mode": "replace"}}, "size": 5})
    with _row_store(ctx) as (fc, breaker):
        for _ in range(3):
            before = _counters()
            execute_query_phase(ctx, req, use_device=True)
            after = _counters()
            assert after["device_function_score"] == \
                before["device_function_score"] + 1
            assert after["fs_rows_evaluated"] - before["fs_rows_evaluated"] == \
                len(ctx.searcher.segments)
            assert after["fs_rows_resident"] == before["fs_rows_resident"]
        rows = fc.stats()["function_rows"]
        assert rows["misses"] == rows["entries"] == 0 and breaker.used == 0


def test_a_filter_that_is_not_the_segments_own_keeps_its_rows_off_the_store(ctx):
    """The rule segment_mask asks (Filter.cacheable()) decides for a function
    filter too: a join's mask spans the shard, so a row gated by it is
    evaluated for every launch."""
    from elasticsearch_tpu.search.execute import _fs_rows_key
    from elasticsearch_tpu.search.filters import HasChildFilter
    from elasticsearch_tpu.search.queries import (HasChildQuery,
                                                  MatchAllQuery, parse_query)

    fsq = parse_query({"function_score": {
        "query": {"match_all": {}},
        "functions": [{"filter": WINDOW, "boost_factor": 2.0}]}})
    assert _fs_rows_key(fsq, "rows", None, ctx) is not None
    assert _fs_rows_key(fsq, "script", ("status",), ctx) is not None
    join = HasChildFilter(HasChildQuery("doc", MatchAllQuery()))
    assert not join.cacheable()
    fsq.functions[0].filter = join
    assert _fs_rows_key(fsq, "rows", None, ctx) is None
    assert _fs_rows_key(fsq, "script", ("status",), ctx) is None


def test_a_store_the_breaker_refuses_serves_the_hosts_rows(ctx):
    body = {"query": {"function_score": {
        "query": {"match_all": {}}, "functions": FS_FUNCTIONS["rows"],
        "score_mode": "sum"}}, "size": 25}
    with _row_store(ctx, budget="100b") as (fc, breaker):
        answers = [_fs_both(ctx, body, script=False) for _ in range(3)]
        assert [d["fs_rows_resident"] for _dev, d in answers] == [0, 0, 0]
        assert all(d["fs_row_put_bytes"] == _row_bytes(ctx, "rows")
                   for _dev, d in answers)
        rows = fc.stats()["function_rows"]
        assert rows["rejections"] == 2 * len(ctx.searcher.segments)
        assert rows["entries"] == 0 and breaker.used == 0
