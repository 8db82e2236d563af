"""Device metric aggregations: differential tests vs the host collectors.

Eligible requests (metric aggs on numeric columns, no other mask consumers) are
served by ONE fused device program per segment — scoring + top-k + masked stat
reductions (ops/scoring.score_agg_batch_async over device_index.agg_doc_rows) — instead
of host-side mask materialization. Results must match the host collectors within
float32 kernel accumulation (double-typed columns round to 7 significant digits;
int/float columns are exact).

ref: search/aggregations/AggregationPhase.java + metrics collectors; SURVEY §5.7
"shard-level parallel reduce of aggregations".
"""

from __future__ import annotations

import tempfile

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.mapper.core import MapperService
from elasticsearch_tpu.search import ShardContext
from elasticsearch_tpu.search.aggregations import reduce_aggs
from elasticsearch_tpu.search.service import (
    _try_device_aggs,
    execute_query_phase,
    parse_search_body,
)
from elasticsearch_tpu.search.similarity import SimilarityService

from .harness import run_as_one_batch


@pytest.fixture(scope="module")
def ctx():
    tmp = tempfile.mkdtemp()
    settings = Settings.from_flat({"index.similarity.default.type": "BM25"})
    svc = MapperService(settings)
    eng = Engine(tmp, svc)
    rng = np.random.default_rng(17)
    words = ["alpha", "beta", "gamma", "delta", "epsilon"]
    for i in range(400):
        d = {"body": " ".join(rng.choice(words, size=5)),
             "price": float(np.round(rng.uniform(1, 99), 2)),
             "label": words[i % 5]}
        if i % 3 == 0:
            d["tags_n"] = [int(x) for x in rng.integers(1, 10, size=3)]
        if i % 7 != 0:
            d["pop"] = int(rng.integers(1, 100))
        eng.index("doc", str(i), d)
        if i == 199:
            eng.refresh()  # second segment
    for i in (4, 44, 250):
        eng.delete("doc", str(i))
    eng.refresh()
    out = ShardContext(eng.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc))
    yield out
    eng.close()


def _agg_equal(a, b, path=""):
    if isinstance(a, dict) and isinstance(b, dict):
        assert set(a) == set(b), (path, a, b)
        for k2 in a:
            _agg_equal(a[k2], b[k2], f"{path}.{k2}")
    elif isinstance(a, list) and isinstance(b, list):
        assert len(a) == len(b), (path, a, b)
        for i, (x, y) in enumerate(zip(a, b)):
            _agg_equal(x, y, f"{path}[{i}]")
    elif a is None or b is None:
        assert a is None and b is None, (path, a, b)
    elif isinstance(a, float) or isinstance(b, float):
        assert a == pytest.approx(b, rel=1e-5), (path, a, b)
    else:
        assert a == b, (path, a, b)


def _both(ctx, body):
    req = parse_search_body(body)
    dev = execute_query_phase(ctx, req, use_device=True)
    host = execute_query_phase(ctx, req, use_device=False)
    assert dev.total == host.total
    assert [(round(s, 5), d) for s, d, _ in dev.docs] == \
        [(round(s, 5), d) for s, d, _ in host.docs]
    dr = reduce_aggs(req.aggs, dev.agg_partials)
    hr = reduce_aggs(req.aggs, host.agg_partials)
    for name in dr:
        _agg_equal(dr[name], hr[name], name)
    return req


def test_all_metric_types_parity(ctx):
    req = _both(ctx, {
        "query": {"match": {"body": "alpha beta"}}, "size": 5,
        "aggs": {"p_avg": {"avg": {"field": "price"}},
                 "p_sum": {"sum": {"field": "price"}},
                 "p_stats": {"stats": {"field": "price"}},
                 "pop_min": {"min": {"field": "pop"}},
                 "pop_max": {"max": {"field": "pop"}},
                 "p_count": {"value_count": {"field": "price"}}}})
    # and the device path really served it
    assert _try_device_aggs(ctx, req, 5, None, 0) is not None


def test_multivalued_column_exact(ctx):
    # per-doc folds happen host-side, so multi-valued sums/counts are exact
    req = _both(ctx, {
        "query": {"match": {"body": "gamma"}}, "size": 3,
        "aggs": {"t_sum": {"sum": {"field": "tags_n"}},
                 "t_cnt": {"value_count": {"field": "tags_n"}},
                 "t_min": {"min": {"field": "tags_n"}},
                 "t_max": {"max": {"field": "tags_n"}}}})
    assert _try_device_aggs(ctx, req, 3, None, 0) is not None


def test_missing_column_docs(ctx):
    # `pop` is absent on every 7th doc: masked counts skip them on both paths
    _both(ctx, {
        "query": {"match": {"body": "delta epsilon"}}, "size": 3,
        "aggs": {"s": {"stats": {"field": "pop"}}}})


def test_no_matches_yields_empty_stats(ctx):
    req = _both(ctx, {
        "query": {"match": {"body": "zzzznope"}}, "size": 3,
        "aggs": {"s": {"stats": {"field": "price"}},
                 "m": {"min": {"field": "price"}}}})
    r = reduce_aggs(req.aggs, execute_query_phase(ctx, req).agg_partials)
    assert r["s"]["count"] == 0 and r["s"]["min"] is None
    assert r["m"]["value"] is None


@pytest.mark.parametrize("aggs", [
    {"x": {"extended_stats": {"field": "price"}}},  # variance: host-only
    {"x": {"avg": {"script": "doc['price'].value * 2"}}},  # script agg
    {"x": {"terms": {"field": "label"},
           "aggs": {"s": {"cardinality": {"field": "pop"}}}}},  # sketch sub-agg
    {"x": {"terms": {"field": "label"},
           "aggs": {"s": {"terms": {"field": "pop"}}}}},  # bucket sub-agg
    {"x": {"value_count": {"field": "label"}}},  # string column
    {"x": {"cardinality": {"field": "pop"}}},  # sketch agg
    {"x": {"percentiles": {"field": "pop"}}},  # sketch agg
])
def test_ineligible_aggs_fall_back(ctx, aggs):
    body = {"query": {"match": {"body": "alpha"}}, "size": 3, "aggs": aggs}
    req = parse_search_body(body)
    assert _try_device_aggs(ctx, req, 3, None, 0) is None
    # and the host path still serves them correctly end to end
    res = execute_query_phase(ctx, req, use_device=True)
    assert reduce_aggs(req.aggs, res.agg_partials)["x"] is not None


def test_terms_agg_parity(ctx):
    # terms on a string column AND on a numeric column, plus multi-valued docs
    # (duplicate values in one doc must count the doc ONCE)
    req = _both(ctx, {
        "query": {"match": {"body": "alpha"}}, "size": 0,
        "aggs": {"by_label": {"terms": {"field": "label", "size": 20}},
                 "by_pop": {"terms": {"field": "pop", "size": 50}},
                 "by_tag": {"terms": {"field": "tags_n", "size": 20}}}})
    assert _try_device_aggs(ctx, req, 1, None, 0) is not None


def test_histogram_parity(ctx):
    req = _both(ctx, {
        "query": {"match": {"body": "beta gamma"}}, "size": 0,
        "aggs": {"h": {"histogram": {"field": "price", "interval": 10}},
                 "hm": {"histogram": {"field": "tags_n", "interval": 2}}}})
    assert _try_device_aggs(ctx, req, 1, None, 0) is not None


def test_range_agg_parity(ctx):
    # overlapping + unbounded + keyed + empty ranges; zero-count buckets survive
    req = _both(ctx, {
        "query": {"match": {"body": "alpha"}}, "size": 0,
        "aggs": {"r": {"range": {"field": "price", "ranges": [
            {"to": 30}, {"from": 20, "to": 60}, {"from": 50},
            {"key": "none", "from": 4000, "to": 5000}]}},
                 "rm": {"range": {"field": "tags_n", "ranges": [
                     {"from": 1, "to": 5}, {"from": 5}]}}}})
    assert _try_device_aggs(ctx, req, 1, None, 0) is not None


def test_mixed_metric_and_bucket_aggs(ctx):
    req = _both(ctx, {
        "query": {"match": {"body": "delta"}}, "size": 3,
        "aggs": {"by_label": {"terms": {"field": "label"}},
                 "p_avg": {"avg": {"field": "price"}},
                 "h": {"histogram": {"field": "price", "interval": 25}}}})
    assert _try_device_aggs(ctx, req, 3, None, 0) is not None


def test_filtered_query_with_aggs(ctx):
    # the classic analytics shape: query + filter + aggs, fused in one launch
    req = _both(ctx, {
        "query": {"filtered": {"query": {"match": {"body": "alpha"}},
                               "filter": {"range": {"pop": {"gte": 50}}}}},
        "size": 3,
        "aggs": {"p_avg": {"avg": {"field": "price"}},
                 "by_label": {"terms": {"field": "label"}}}})
    assert _try_device_aggs(ctx, req, 3, None, 0) is not None


def test_filtered_query_device_topk(ctx):
    from elasticsearch_tpu.search.execute import lower_flat, search_shard
    from elasticsearch_tpu.search import parse_query

    qd = {"filtered": {"query": {"match": {"body": "beta gamma"}},
                       "filter": {"term": {"label": "L3"}}, "boost": 1.5}}
    q = parse_query(qd)
    plan = lower_flat(q, ctx)
    assert plan is not None and plan.filt is not None
    dev = search_shard(ctx, q, 10, use_device=True)
    host = search_shard(ctx, q, 10, use_device=False)
    assert dev.total == host.total and dev.hits == host.hits


def test_date_histogram_parity():
    import tempfile

    svc = MapperService(Settings.from_flat({}))
    eng = Engine(tempfile.mkdtemp(), svc)
    for i in range(90):
        eng.index("doc", str(i), {"body": "alpha",
                                  "ts": f"2014-{(i % 3) + 1:02d}-{(i % 27) + 1:02d}"})
    eng.refresh()
    c = ShardContext(eng.acquire_searcher(), svc,
                     SimilarityService(Settings.from_flat({}), mapper_service=svc))
    req = _both(c, {"query": {"match": {"body": "alpha"}}, "size": 0,
                    "aggs": {"d": {"date_histogram": {"field": "ts",
                                                      "interval": "month"}}}})
    assert _try_device_aggs(c, req, 1, None, 0) is not None
    eng.close()


def test_trailing_valueless_docs_dont_truncate_minmax():
    # regression: reduceat index clipping truncated the PREVIOUS doc's value run
    # when trailing docs lacked the field — max([1, 9]) came back as 1
    import tempfile

    from elasticsearch_tpu.ops.device_index import agg_doc_rows

    svc = MapperService(Settings.from_flat({}))
    eng = Engine(tempfile.mkdtemp(), svc)
    eng.index("doc", "0", {"body": "alpha", "v": [1, 9]})
    eng.index("doc", "1", {"body": "alpha"})  # no v — trailing value-less doc
    eng.refresh()
    seg = eng.acquire_searcher().segments[0]
    rows = agg_doc_rows(seg, "v")
    assert rows[3][0] == 9.0 and rows[2][0] == 1.0
    ctx2 = ShardContext(eng.acquire_searcher(), svc,
                        SimilarityService(Settings.from_flat({}), mapper_service=svc))
    _ = ctx2
    req = parse_search_body({"query": {"match": {"body": "alpha"}},
                             "aggs": {"m": {"max": {"field": "v"}}}})
    res = execute_query_phase(ctx2, req, use_device=True)
    assert reduce_aggs(req.aggs, res.agg_partials)["m"]["value"] == 9.0
    eng.close()


def test_f32_inexact_column_falls_back_to_host():
    # values past 2^24 (longs/dates) are not float32-exact: the device path must
    # refuse and the host collectors serve the exact numbers
    import tempfile

    from elasticsearch_tpu.search.service import _try_device_aggs as try_dev

    svc = MapperService(Settings.from_flat({}))
    eng = Engine(tempfile.mkdtemp(), svc)
    big = 1_700_000_000_123  # epoch-millis-sized long
    for i in range(5):
        eng.index("doc", str(i), {"body": "alpha", "ts_l": big + i})
    eng.refresh()
    c = ShardContext(eng.acquire_searcher(), svc,
                     SimilarityService(Settings.from_flat({}), mapper_service=svc))
    req = parse_search_body({"query": {"match": {"body": "alpha"}},
                             "aggs": {"m": {"max": {"field": "ts_l"}}}})
    assert try_dev(c, req, 3, None, 0) is None  # refused at row build
    res = execute_query_phase(c, req, use_device=True)
    assert reduce_aggs(req.aggs, res.agg_partials)["m"]["value"] == big + 4  # exact
    eng.close()


def test_match_all_aggs_ride_the_device(ctx):
    # a plan with no scoring clause takes the same fused aggregation tail
    req = parse_search_body({
        "query": {"match_all": {}},
        "aggs": {"a": {"avg": {"field": "pop"}},
                 "by_label": {"terms": {"field": "tags_n"}}}})
    dev = _try_device_aggs(ctx, req, 3, None, 0)
    assert dev is not None
    host = execute_query_phase(ctx, req, use_device=False)
    assert dev.total == host.total
    _agg_equal(reduce_aggs(req.aggs, dev.agg_partials),
               reduce_aggs(req.aggs, host.agg_partials))


def test_unlowerable_query_falls_back(ctx):
    req = parse_search_body({
        "query": {"match_phrase": {"body": "alpha beta"}},
        "aggs": {"a": {"avg": {"field": "price"}}}})
    assert _try_device_aggs(ctx, req, 3, None, 0) is None
    # host path agrees with itself (sanity that fallback serves)
    res = execute_query_phase(ctx, req, use_device=True)
    assert reduce_aggs(req.aggs, res.agg_partials)["a"]["value"] is not None


def test_date_math_range_bounds_stay_host(ctx):
    # "now"-relative bounds re-resolve per query on the host; the device pair
    # cache is per segment generation, so such specs must refuse the device
    from elasticsearch_tpu.search.aggregations import device_bucket_eligible, parse_aggs

    aggs = parse_aggs({"r": {"date_range": {"field": "pop", "ranges": [
        {"from": "now-1h"}]}}})
    assert not device_bucket_eligible(aggs["r"])
    aggs2 = parse_aggs({"r": {"range": {"field": "pop", "ranges": [
        {"from": 10, "to": 20}]}}})
    assert device_bucket_eligible(aggs2["r"])


def test_mask_shaped_bucket_aggs_parity(ctx):
    # filter / filters / missing ride the device scatter with host-built masks
    req = _both(ctx, {
        "query": {"match": {"body": "alpha"}}, "size": 0,
        "aggs": {"f": {"filter": {"range": {"pop": {"gte": 50}}}},
                 "fs": {"filters": {"filters": {
                     "cheap": {"range": {"price": {"lte": 30}}},
                     "tagged": {"exists": {"field": "tags_n"}}}}},
                 "no_pop": {"missing": {"field": "pop"}}}})
    assert _try_device_aggs(ctx, req, 1, None, 0) is not None


def test_mask_bucket_with_date_math_stays_host(ctx):
    from elasticsearch_tpu.search.aggregations import device_bucket_eligible, parse_aggs

    aggs = parse_aggs({"f": {"filter": {"range": {"pop": {"gte": "now-1h"}}}}})
    assert not device_bucket_eligible(aggs["f"])


def test_geo_bucket_aggs_parity():
    import tempfile

    svc = MapperService(Settings.from_flat({}))
    svc.put_mapping("doc", {"properties": {"loc": {"type": "geo_point"}}})
    eng = Engine(tempfile.mkdtemp(), svc)
    rng = np.random.default_rng(9)
    for i in range(150):
        eng.index("doc", str(i), {
            "body": "alpha" if i % 2 else "alpha beta",
            "loc": {"lat": float(rng.uniform(40, 60)),
                    "lon": float(rng.uniform(-5, 25))}})
    eng.refresh()
    c = ShardContext(eng.acquire_searcher(), svc,
                     SimilarityService(Settings.from_flat({}), mapper_service=svc))
    req = _both(c, {
        "query": {"match": {"body": "alpha"}}, "size": 0,
        "aggs": {"d": {"geo_distance": {"field": "loc",
                                        "origin": {"lat": 50, "lon": 10},
                                        "unit": "km",
                                        "ranges": [{"to": 300},
                                                   {"from": 300, "to": 900},
                                                   {"from": 900}]}},
                 "g": {"geohash_grid": {"field": "loc", "precision": 2}}}})
    assert _try_device_aggs(c, req, 1, None, 0) is not None
    eng.close()


def test_significant_terms_parity(ctx):
    req = _both(ctx, {
        "query": {"match": {"body": "alpha beta"}}, "size": 0,
        "aggs": {"sig": {"significant_terms": {"field": "label", "size": 10}}}})
    assert _try_device_aggs(ctx, req, 1, None, 0) is not None
    # bg_count present in the reduced output
    r = reduce_aggs(req.aggs, execute_query_phase(ctx, req).agg_partials)
    assert all("bg_count" in b and b["bg_count"] >= b["doc_count"] >= 1
               for b in r["sig"]["buckets"])


def test_metric_sub_aggs_under_buckets_parity(ctx):
    # the canonical analytics tree: buckets with metric sub-aggs, all in-kernel
    req = _both(ctx, {
        "query": {"match": {"body": "alpha beta"}}, "size": 0,
        "aggs": {
            "by_label": {"terms": {"field": "label", "size": 20},
                         "aggs": {"p_avg": {"avg": {"field": "price"}},
                                  "p_stats": {"stats": {"field": "price"}},
                                  "pop_max": {"max": {"field": "pop"}}}},
            "by_range": {"range": {"field": "price",
                                   "ranges": [{"to": 40}, {"from": 40}]},
                         "aggs": {"t_sum": {"sum": {"field": "tags_n"}}}},
            "no_pop": {"missing": {"field": "pop"},
                       "aggs": {"p_min": {"min": {"field": "price"}}}},
        }})
    assert _try_device_aggs(ctx, req, 1, None, 0) is not None


def test_sub_agg_empty_buckets_parity(ctx):
    # zero-count range buckets must carry the same empty sub partials as host
    _both(ctx, {
        "query": {"match": {"body": "gamma"}}, "size": 0,
        "aggs": {"r": {"range": {"field": "price",
                                 "ranges": [{"from": 5000, "to": 6000}]},
                       "aggs": {"a": {"avg": {"field": "pop"}},
                                "m": {"min": {"field": "pop"}}}}}})


def test_sub_agg_multivalued_exact(ctx):
    # multi-valued sub-agg sums within buckets stay exact (per-doc host folds)
    _both(ctx, {
        "query": {"match": {"body": "delta"}}, "size": 0,
        "aggs": {"by_label": {"terms": {"field": "label"},
                              "aggs": {"t": {"sum": {"field": "tags_n"}},
                                       "tc": {"value_count": {"field": "tags_n"}}}}}})


def test_post_filter_device_parity(ctx):
    # hits post-filtered, aggs over the FULL match set — the faceting idiom
    req = _both(ctx, {
        "query": {"match": {"body": "alpha"}}, "size": 10,
        "post_filter": {"range": {"pop": {"gte": 50}}},
        "aggs": {"by_label": {"terms": {"field": "label"}},
                 "p_avg": {"avg": {"field": "price"}}}})
    # total reflects the post filter; aggs don't
    full = execute_query_phase(ctx, parse_search_body(
        {"query": {"match": {"body": "alpha"}}, "size": 0}))
    res = execute_query_phase(ctx, req)
    assert res.total < full.total
    dr = reduce_aggs(req.aggs, res.agg_partials)
    assert sum(b["doc_count"] for b in dr["by_label"]["buckets"]) == full.total


def test_post_filter_with_filtered_query(ctx):
    _both(ctx, {
        "query": {"filtered": {"query": {"match": {"body": "beta"}},
                               "filter": {"range": {"price": {"lte": 70}}}}},
        "size": 8,
        "post_filter": {"term": {"label": "gamma"}},
        "aggs": {"s": {"stats": {"field": "pop"}}}})


def test_min_score_device_parity(ctx):
    body = {"query": {"match": {"body": "alpha beta"}}, "size": 10,
            "min_score": 0.8}
    req = parse_search_body(body)
    dev = execute_query_phase(ctx, req, use_device=True)
    host = execute_query_phase(ctx, req, use_device=False)
    assert dev.total == host.total and dev.total > 0
    assert [(round(s, 5), d) for s, d, _ in dev.docs] == \
        [(round(s, 5), d) for s, d, _ in host.docs]
    loose = execute_query_phase(ctx, parse_search_body(
        {"query": {"match": {"body": "alpha beta"}}, "size": 0}))
    assert dev.total < loose.total  # the threshold really trims


def test_batched_device_percolation_parity():
    # many registered queries percolate as ONE kernel batch; results must match
    # the pure host loop exactly
    from elasticsearch_tpu.mapper.core import MapperService
    from elasticsearch_tpu.percolator import PercolatorRegistry
    from elasticsearch_tpu.search.service import SERVING_COUNTERS

    svc = MapperService(Settings.from_flat({}))
    reg = PercolatorRegistry()
    rng = np.random.default_rng(13)
    words = ["alpha", "beta", "gamma", "delta", "epsilon"]
    for i in range(200):
        kind = i % 4
        if kind == 0:
            q = {"match": {"body": str(rng.choice(words))}}
        elif kind == 1:
            q = {"bool": {"must": [{"term": {"body": str(rng.choice(words))}}],
                          "must_not": [{"term": {"body": str(rng.choice(words))}}]}}
        elif kind == 2:
            q = {"term": {"body": str(rng.choice(words))}}
        else:  # not flat-lowerable → host within the same percolation
            q = {"match_phrase": {"body": f"{rng.choice(words)} {rng.choice(words)}"}}
        reg.register(f"q{i}", {"query": q})
    assert reg.count() >= reg.DEVICE_BATCH_MIN

    doc = {"body": "alpha beta gamma"}
    before = SERVING_COUNTERS["device_percolate"]
    batched = reg.percolate(doc, svc)
    # the device batch really ran (the wholesale fallback would otherwise make
    # this test compare host against host)
    assert SERVING_COUNTERS["device_percolate"] == before + 1
    assert SERVING_COUNTERS["device_percolate_fallbacks"] == 0
    # force the pure host loop by lowering the gate
    orig = PercolatorRegistry.DEVICE_BATCH_MIN
    PercolatorRegistry.DEVICE_BATCH_MIN = 10**9
    try:
        host = reg.percolate(doc, svc)
    finally:
        PercolatorRegistry.DEVICE_BATCH_MIN = orig
    assert batched == host and len(batched) > 0


def test_device_failure_falls_back_to_host(ctx, monkeypatch):
    # a broken device backend (failed init, OOM) must degrade
    # to the host scorer, visibly (device_errors counter), never fail searches
    import elasticsearch_tpu.search.service as svc_mod
    from elasticsearch_tpu.search.service import SERVING_COUNTERS

    def boom(*a, **k):
        raise RuntimeError("device backend unavailable")

    monkeypatch.setattr(svc_mod, "execute_flat_batch", boom)
    monkeypatch.setattr(svc_mod, "_try_device_aggs", boom)
    monkeypatch.setattr(svc_mod, "_try_device_sort", boom)
    before = SERVING_COUNTERS["device_errors"]
    for body in (
        {"query": {"match": {"body": "alpha"}}, "size": 5},
        {"query": {"match": {"body": "alpha"}}, "size": 0,
         "aggs": {"m": {"max": {"field": "pop"}}}},
        {"query": {"match": {"body": "alpha"}}, "sort": [{"pop": "asc"}],
         "size": 5},
    ):
        req = parse_search_body(body)
        res = execute_query_phase(ctx, req, use_device=True)
        host = execute_query_phase(ctx, req, use_device=False)
        assert res.total == host.total
    assert SERVING_COUNTERS["device_errors"] >= before + 3


# ---------------------------------------------------------------------------
# aggregated searches join the batch (PR 33): a group of plans under one set
# of aggregations is one launch a segment, and every member is answered as
# the one-plan call answers it
# ---------------------------------------------------------------------------

GROUP_AGGS = {
    "p_stats": {"stats": {"field": "price"}},
    "pop_max": {"max": {"field": "pop"}},
    "by_label": {"terms": {"field": "label"},
                 "aggs": {"pop_sum": {"sum": {"field": "pop"}}}},
    "pop_hist": {"histogram": {"field": "pop", "interval": 20}},
}

_GROUP_WORDS = ["alpha", "beta", "gamma", "delta", "epsilon"]


def _group_query(variant: str, i: int) -> dict:
    match = {"match": {"body": f"{_GROUP_WORDS[i % 5]} {_GROUP_WORDS[(i + 2) % 5]}"}}
    price = {"range": {"price": {"gte": 5 + 9 * i}}}
    if variant == "scored":
        return match
    if variant == "scored_some_filtered":
        return match if i % 2 else {"filtered": {"query": match, "filter": price}}
    if variant == "unscored":
        return {"constant_score": {"filter": price}}
    assert variant == "unscored_some_filtered"
    return {"match_all": {}} if i % 2 else {"constant_score": {"filter": price}}


def _group_bodies(variant: str, Q: int, **extra) -> list:
    return [{"query": _group_query(variant, i), "size": 2 + i % 4, **extra}
            for i in range(Q)]


def _assert_same_answer(req, got, want):
    """`got` answers as `want` does: totals, hits, max_score, and every
    aggregation reduced (bucket keys and counts exact, float stats to float32
    accumulation)."""
    assert not isinstance(got, Exception), got
    assert got.total == want.total
    assert len(got.docs) == len(want.docs)
    for (gs, gd, gv), (ws, wd, wv) in zip(got.docs, want.docs):
        assert (gd, gv) == (wd, wv)
        assert gs == ws or (gs != gs and ws != ws)  # NaN: an untracked score
    assert got.max_score == want.max_score or (
        got.max_score != got.max_score and want.max_score != want.max_score)
    assert not got.degraded
    if req.aggs:
        gr = reduce_aggs(req.aggs, got.agg_partials)
        wr = reduce_aggs(req.aggs, want.agg_partials)
        for name in wr:
            _agg_equal(gr[name], wr[name], name)


@pytest.mark.parametrize("variant", ["scored", "scored_some_filtered",
                                     "unscored", "unscored_some_filtered"])
@pytest.mark.parametrize("Q", [1, 2, 3, 5, 8])
def test_a_group_answers_each_member_as_the_one_plan_call_does(ctx, Q, variant):
    from elasticsearch_tpu.search.service import SERVING_COUNTERS

    bodies = _group_bodies(variant, Q, aggs=GROUP_AGGS)
    host_before = SERVING_COUNTERS["host"]
    got, stats = run_as_one_batch(ctx, bodies)
    assert SERVING_COUNTERS["host"] == host_before
    # one collect, one group, one launch a segment for all Q
    assert stats["launches"] == 1 and stats["coalesced"] == Q
    assert stats["kinds"]["aggs"] == {"launches": 1, "coalesced": Q}
    assert stats["bypassed"] == 0 and stats["splits"] == 0
    for body, res in zip(bodies, got):
        req = parse_search_body(body)
        # the one-plan call: no batcher on the context, a launch a search
        _assert_same_answer(req, res, execute_query_phase(ctx, req))
        assert len(res.docs) == min(body["size"], res.total)


def test_groups_split_by_their_aggregations_and_scoring(ctx):
    """One collect, three launches: what cannot share a program does not —
    another set of aggregations, and unscored plans beside scored ones."""
    other = {"pop_hist": {"histogram": {"field": "pop", "interval": 10}}}
    bodies = (_group_bodies("scored", 3, aggs=GROUP_AGGS)
              + _group_bodies("scored", 2, aggs=other)
              + _group_bodies("unscored", 2, aggs=GROUP_AGGS))
    got, stats = run_as_one_batch(ctx, bodies)
    assert stats["launches"] == 1 and stats["coalesced"] == 7
    assert stats["kinds"]["aggs"] == {"launches": 3, "coalesced": 7}
    for body, res in zip(bodies, got):
        req = parse_search_body(body)
        _assert_same_answer(req, res, execute_query_phase(ctx, req))


def test_members_differ_in_what_the_request_thread_applies(ctx):
    """size, order and min_doc_count of a terms aggregation are no part of
    the group key: one launch, and each member's own finalize."""
    def body(i, **terms):
        return {"query": _group_query("scored", i), "size": 1,
                "aggs": {"by_label": {"terms": {"field": "label", **terms}}}}

    bodies = [body(0), body(1, size=2), body(2, order={"_term": "desc"}),
              body(3, min_doc_count=30)]
    got, stats = run_as_one_batch(ctx, bodies)
    assert stats["kinds"]["aggs"] == {"launches": 1, "coalesced": 4}
    for b, res in zip(bodies, got):
        req = parse_search_body(b)
        _assert_same_answer(req, res, execute_query_phase(ctx, req))
    assert len(reduce_aggs(parse_search_body(bodies[1]).aggs,
                           got[1].agg_partials)["by_label"]["buckets"]) == 2


def test_a_refused_column_sends_every_member_to_the_host(ctx, monkeypatch):
    """None from the executor (a column not f32-exact) reaches EVERY member:
    each falls to the host collectors, none is degraded, none fails."""
    import elasticsearch_tpu.ops.device_index as di
    from elasticsearch_tpu.search.service import SERVING_COUNTERS

    bodies = _group_bodies("scored", 3, aggs=GROUP_AGGS)
    want = [execute_query_phase(ctx, parse_search_body(b), use_device=False)
            for b in bodies]
    monkeypatch.setattr(di, "ensure_agg_rows", lambda *a, **k: None)
    before = dict(SERVING_COUNTERS)
    got, stats = run_as_one_batch(ctx, bodies)
    assert stats["kinds"]["aggs"] == {"launches": 1, "coalesced": 3}
    assert SERVING_COUNTERS["host"] == before["host"] + 3
    assert SERVING_COUNTERS["device_aggs"] == before["device_aggs"]
    assert SERVING_COUNTERS["device_errors"] == before["device_errors"]
    for b, res, host in zip(bodies, got, want):
        req = parse_search_body(b)
        assert res.total == host.total and not res.degraded
        assert [d for _s, d, _v in res.docs] == [d for _s, d, _v in host.docs]
        _agg_equal(reduce_aggs(req.aggs, res.agg_partials),
                   reduce_aggs(req.aggs, host.agg_partials))


def test_sort_with_aggs_makes_two_submissions(ctx):
    """A sorted request with aggregations goes the one route twice: its
    aggregated launch and its sorted launch each join their own group."""
    bodies = _group_bodies("scored", 3, aggs=GROUP_AGGS,
                           sort=[{"pop": "desc"}])
    got, stats = run_as_one_batch(ctx, bodies)
    assert stats["kinds"]["aggs"]["coalesced"] == 3
    assert stats["kinds"]["sorted"]["coalesced"] == 3
    assert stats["coalesced"] == 6
    for b, res in zip(bodies, got):
        req = parse_search_body(b)
        _assert_same_answer(req, res, execute_query_phase(ctx, req))


# -- exact integer sums (ISSUE 35) -------------------------------------------
#
# A column of whole numbers adds up in integer limbs on the device
# (device_index.agg_int_limbs): its sum, average and count are exact whatever
# its magnitude, at the top level and under a bucket; nothing adds whole
# numbers up in float32.

LONGS = {
    # odd values over 2^24: float32 holds none of them; a label's sum passes 2^31
    "odd_over_2p24": lambda rng, i: int((1 << 24) + 1 + 2 * rng.integers(0, 1 << 25)),
    # epoch milliseconds: the wide rung of limbs
    "epoch_ms": lambda rng, i: int(1_700_000_000_123 + rng.integers(0, 10**9)),
    # float32-exact values whose sums pass 2^24: the float32 rows rounded these
    "small_values_big_sums": lambda rng, i: int(rng.integers(60_000, 65_536)) * 256,
    "negative_and_multi": lambda rng, i: [
        int(x) for x in rng.integers(-(1 << 40), 1 << 40, size=1 + i % 3)],
}


@pytest.fixture(scope="module", params=sorted(LONGS))
def longs(request):
    svc = MapperService(Settings.from_flat({}))
    svc.put_mapping("doc", {"doc": {"properties": {
        "body": {"type": "string"}, "n": {"type": "long"},
        "label": {"type": "string", "index": "not_analyzed"},
        "price": {"type": "double"}}}})
    eng = Engine(tempfile.mkdtemp(), svc)
    rng = np.random.default_rng(35)
    docs = {}
    for i in range(300):
        d = {"body": "alpha beta" if i % 3 else "alpha", "label": "l%d" % (i % 4),
             "price": float(np.round(rng.uniform(1, 99), 2))}
        if i % 11:
            d["n"] = LONGS[request.param](rng, i)
        docs[str(i)] = d
        eng.index("doc", str(i), d)
        if i == 170:
            eng.refresh()  # two segments
    for i in ("7", "200"):
        eng.delete("doc", i)
        del docs[i]
    eng.refresh()
    c = ShardContext(eng.acquire_searcher(), svc,
                     SimilarityService(Settings.from_flat({}), mapper_service=svc))
    assert len(c.searcher.segments) == 2
    yield request.param, c, docs
    eng.close()


def _values(doc) -> list:
    v = doc.get("n", [])
    return v if isinstance(v, list) else [v]


@pytest.mark.parametrize("query", ["match_all", "match"])
def test_integer_sums_are_exact_at_the_top_and_under_terms(longs, query):
    from elasticsearch_tpu.ops.scoring import LAUNCHES
    from elasticsearch_tpu.search.service import SERVING_COUNTERS

    _kind, c, docs = longs
    q = {"match_all": {}} if query == "match_all" else {"match": {"body": "beta"}}
    hit = [d for d in docs.values() if query == "match_all" or "beta" in d["body"]]
    req = parse_search_body({"query": q, "size": 0, "aggs": {
        "total": {"sum": {"field": "n"}}, "mean": {"avg": {"field": "n"}},
        "values": {"value_count": {"field": "n"}},
        "by_label": {"terms": {"field": "label"}, "aggs": {
            "s": {"sum": {"field": "n"}}, "a": {"avg": {"field": "n"}},
            "p": {"sum": {"field": "price"}}}}}})
    before = {**SERVING_COUNTERS, **LAUNCHES.snapshot()}
    res = execute_query_phase(c, req, use_device=True)
    after = {**SERVING_COUNTERS, **LAUNCHES.snapshot()}
    assert after["device_aggs"] == before["device_aggs"] + 1
    assert after["host"] == before["host"]
    # one limbed field at the top and one under the bucket, a launch a segment
    assert after["exact_sum_rows"] - before["exact_sum_rows"] in (2 * 2 * 3, 2 * 2 * 6)
    got = reduce_aggs(req.aggs, res.agg_partials)
    want = sum(v for d in hit for v in _values(d))  # a Python integer
    n_values = sum(len(_values(d)) for d in hit)
    assert abs(want) > 1 << 31
    assert got["total"]["value"] == float(want) and int(got["total"]["value"]) == want
    assert got["values"]["value"] == n_values
    assert got["mean"]["value"] == float(want) / n_values
    assert len(got["by_label"]["buckets"]) == 4
    for b in got["by_label"]["buckets"]:
        mine = [d for d in hit if d["label"] == b["key"]]
        s = sum(v for d in mine for v in _values(d))
        assert b["doc_count"] == len(mine)
        assert int(b["s"]["value"]) == s and b["s"]["value"] == float(s)
        assert b["a"]["value"] == float(s) / sum(len(_values(d)) for d in mine)
        # the fractional column keeps its float32 rows
        assert b["p"]["value"] == pytest.approx(sum(d["price"] for d in mine), rel=1e-5)
    host = execute_query_phase(c, req, use_device=False)
    _agg_equal(got, reduce_aggs(req.aggs, host.agg_partials))


def test_a_value_of_a_column_float32_cannot_hold_stays_with_the_host(longs):
    from elasticsearch_tpu.ops.device_index import (agg_device_exact,
                                                    ensure_agg_rows, packed_for)

    kind, c, docs = longs
    exact = kind == "small_values_big_sums"
    segs = c.searcher.segments
    assert agg_device_exact(segs, "n", needs_values=True) is exact
    # a sum asks nothing of float32; a float32 accumulator (the mesh program's)
    # would round every one of these columns, and a fractional one has no
    # exact answer to lose
    assert agg_device_exact(segs, "n", needs_values=False)
    assert not agg_device_exact(segs, "n", needs_values=True, f32_sums=True)
    assert agg_device_exact(segs, "price", needs_values=True, f32_sums=True)
    for name in ("min", "max", "stats"):
        req = parse_search_body({"query": {"match": {"body": "alpha"}}, "aggs": {
            "by_label": {"terms": {"field": "label"},
                         "aggs": {"m": {name: {"field": "n"}}}}}})
        assert (_try_device_aggs(c, req, 3, None, 0) is not None) is exact
        res = execute_query_phase(c, req, use_device=True)
        host = execute_query_phase(c, req, use_device=False)
        _agg_equal(reduce_aggs(req.aggs, res.agg_partials),
                   reduce_aggs(req.aggs, host.agg_partials))
    # the limbs: a whole-number column has them, a fractional one its float32 sum
    seg = c.searcher.segments[0]
    stack = ensure_agg_rows(seg, packed_for(seg), ["n", "price"])
    assert stack.limbed == (True, False)
    assert stack.limbs.shape[:2] == (2, 3 if kind in (
        "odd_over_2p24", "small_values_big_sums") else 6)
    rows = np.asarray(stack.rows)
    assert not rows[0, 1].any() and not rows[0, 4].any()  # no float32 integer sum
    assert rows[1, 1].any()


def test_limbs_put_any_int64_together_again():
    from elasticsearch_tpu.ops.device_index import LIMB_BITS, limb_totals

    rng = np.random.default_rng(3)
    sums = np.concatenate([rng.integers(-(1 << 62), 1 << 62, 500),
                           [0, -1, 1, (1 << 62) - 1, -(1 << 62)]]).astype(np.int64)
    for n in (6,):
        limbs = np.stack([(sums >> (LIMB_BITS * i)) & ((1 << LIMB_BITS) - 1)
                          for i in range(n - 1)] + [sums >> (LIMB_BITS * (n - 1))])
        assert limb_totals(limbs, 0).tolist() == sums.tolist()
        # and their int32 totals over many documents, as the program adds them
        assert int(limb_totals(limbs.sum(axis=1, keepdims=True), 0)[0]) == \
            sum(sums.tolist())


def _shard_of_longs(values: list):
    """One segment, a document a value of `values` (an int, or a list of them)."""
    svc = MapperService(Settings.from_flat({}))
    svc.put_mapping("doc", {"doc": {"properties": {
        "n": {"type": "long"},
        "label": {"type": "string", "index": "not_analyzed"}}}})
    eng = Engine(tempfile.mkdtemp(), svc)
    for i, v in enumerate(values):
        eng.index("doc", str(i), {"n": v, "label": "l%d" % (i % 2)})
    eng.refresh()
    return eng, ShardContext(
        eng.acquire_searcher(), svc,
        SimilarityService(Settings.from_flat({}), mapper_service=svc))


@pytest.mark.parametrize("values, rides", [
    ([(1 << 62) - (1 << 10)] * 32, True),   # the largest the limbs hold
    ([1 << 62] * 40, False),                # one value past them
    ([[1 << 61, 1 << 61]] * 40, False),     # a document's own sum past them
    ([-(1 << 62)] * 39 + [5], False),
], ids=["under_2p62", "at_2p62", "a_document_sum_at_2p62", "minus_2p62"])
def test_a_sum_the_limbs_cannot_hold_stays_with_the_host(values, rides):
    """No whole-number sum falls back to the float32 row: where a document's
    sum could pass an int64 the column is refused, at the top level and under
    a bucket, and the host collectors answer (ISSUE 35's review)."""
    from elasticsearch_tpu.ops.device_index import (agg_device_exact,
                                                    ensure_agg_rows, packed_for)
    from elasticsearch_tpu.search.service import SERVING_COUNTERS

    eng, c = _shard_of_longs(values)
    try:
        seg = c.searcher.segments[0]
        assert agg_device_exact([seg], "n", needs_values=False) is rides
        assert (ensure_agg_rows(seg, packed_for(seg), ["n"]) is not None) is rides
        req = parse_search_body({"size": 0, "aggs": {
            "total": {"sum": {"field": "n"}},
            "by_label": {"terms": {"field": "label"},
                         "aggs": {"s": {"sum": {"field": "n"}}}}}})
        assert (_try_device_aggs(c, req, 1, None, 0) is not None) is rides
        before = dict(SERVING_COUNTERS)
        res = execute_query_phase(c, req, use_device=True)
        assert SERVING_COUNTERS["host"] - before["host"] == (0 if rides else 1)
        assert SERVING_COUNTERS["device_aggs"] - before["device_aggs"] == \
            (1 if rides else 0)
        got = reduce_aggs(req.aggs, res.agg_partials)
        flat = [v for d in values for v in (d if isinstance(d, list) else [d])]
        assert got["total"]["value"] == float(sum(flat))
        if rides:  # the Python integer itself, past an int64
            assert int(got["total"]["value"]) == sum(flat) > 1 << 63
        host = execute_query_phase(c, req, use_device=False)
        _agg_equal(got, reduce_aggs(req.aggs, host.agg_partials))
    finally:
        eng.close()
