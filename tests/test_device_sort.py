"""Device field sort: differential tests vs the host mask path.

Single numeric field sorts ride the fused kernel (top-k over pre-folded key
rows — ops/scoring._dense_sort_impl); only exactly-f32-representable columns
are eligible, so ordering is bit-identical to the host lexsort. Everything
else (multi-key, _score/geo/script sorts, avg/sum modes, fractional columns)
falls back to the host path.
"""

from __future__ import annotations

import math
import tempfile

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.mapper.core import MapperService
from elasticsearch_tpu.search import ShardContext
from elasticsearch_tpu.search.service import (
    _try_device_sort,
    execute_query_phase,
    parse_search_body,
)
from elasticsearch_tpu.search.similarity import SimilarityService

from .harness import run_as_one_batch


@pytest.fixture(scope="module")
def ctx():
    tmp = tempfile.mkdtemp()
    svc = MapperService(Settings.from_flat({}))
    eng = Engine(tmp, svc)
    rng = np.random.default_rng(31)
    words = ["alpha", "beta", "gamma", "delta"]
    for i in range(300):
        d = {"body": " ".join(rng.choice(words, size=5)),
             "rank": int(rng.integers(0, 5000)),
             "price_frac": float(np.round(rng.uniform(1, 99), 2))}
        if i % 6 == 0:
            del d["rank"]  # missing values
        if i % 5 == 0:
            d["multi"] = [int(x) for x in rng.integers(0, 100, size=3)]
        eng.index("doc", str(i), d)
        if i == 149:
            eng.refresh()
    for i in (7, 70, 200):
        eng.delete("doc", str(i))
    eng.refresh()
    out = ShardContext(eng.acquire_searcher(), svc,
                       SimilarityService(Settings.from_flat({}), mapper_service=svc))
    yield out
    eng.close()


def _both(ctx, body, expect_device=True):
    req = parse_search_body(body)
    if expect_device:
        assert _try_device_sort(ctx, req, req.from_ + req.size, None, 0) is not None
    dev = execute_query_phase(ctx, req, use_device=True)
    host = execute_query_phase(ctx, req, use_device=False)
    assert dev.total == host.total
    assert len(dev.docs) == len(host.docs)
    for (ds, dg, dv), (hs, hg, hv) in zip(dev.docs, host.docs):
        assert dg == hg, (body, dev.docs[:5], host.docs[:5])
        assert dv == hv
        if not (math.isnan(ds) and math.isnan(hs)):
            assert ds == pytest.approx(hs, rel=1e-6)
    if not (math.isnan(dev.max_score) and math.isnan(host.max_score)):
        assert dev.max_score == pytest.approx(host.max_score, rel=1e-6)
    return req


@pytest.mark.parametrize("order", ["asc", "desc"])
def test_basic_field_sort(ctx, order):
    _both(ctx, {"query": {"match": {"body": "alpha beta"}},
                "sort": [{"rank": order}], "size": 25})


@pytest.mark.parametrize("missing", ["_last", "_first", 42])
def test_missing_policies(ctx, missing):
    _both(ctx, {"query": {"match": {"body": "gamma"}},
                "sort": [{"rank": {"order": "asc", "missing": missing}}],
                "size": 30})


@pytest.mark.parametrize("mode,order", [("min", "desc"), ("max", "asc")])
def test_multivalued_modes(ctx, mode, order):
    _both(ctx, {"query": {"match": {"body": "delta"}},
                "sort": [{"multi": {"order": order, "mode": mode}}], "size": 20})


def test_filtered_query_with_sort(ctx):
    _both(ctx, {"query": {"filtered": {"query": {"match": {"body": "alpha"}},
                                       "filter": {"range": {"rank": {"lte": 2500}}}}},
                "sort": [{"rank": "desc"}], "size": 15})


def test_sort_with_aggs_combined(ctx):
    # sort + aggs both device-eligible: ordering from the sort launch, partials
    # from the agg launch (same match set)
    from elasticsearch_tpu.search.aggregations import reduce_aggs

    body = {"query": {"match": {"body": "alpha"}},
            "sort": [{"rank": "asc"}], "size": 10,
            "aggs": {"m": {"max": {"field": "rank"}},
                     "by_label": {"terms": {"field": "multi"}}}}
    req = _both(ctx, body)
    dev = execute_query_phase(ctx, req, use_device=True)
    host = execute_query_phase(ctx, req, use_device=False)
    dr = reduce_aggs(req.aggs, dev.agg_partials)
    hr = reduce_aggs(req.aggs, host.agg_partials)
    assert dr == hr


def test_sort_with_host_only_agg_falls_back(ctx):
    # an ineligible agg sends the whole request host-side, still correct
    _both(ctx, {"query": {"match": {"body": "alpha"}},
                "sort": [{"rank": "asc"}], "size": 5,
                "aggs": {"c": {"cardinality": {"field": "rank"}}}},
          expect_device=False)


def test_track_scores(ctx):
    _both(ctx, {"query": {"match": {"body": "beta"}},
                "sort": [{"rank": "asc"}], "size": 10, "track_scores": True})


@pytest.mark.parametrize("body", [
    # fractional column: not f32-exact → host (ordering must still agree)
    {"query": {"match": {"body": "alpha"}}, "sort": [{"price_frac": "asc"}],
     "size": 10},
    # multi-key → host
    {"query": {"match": {"body": "alpha"}},
     "sort": [{"rank": "asc"}, {"price_frac": "desc"}], "size": 10},
    # avg mode → host
    {"query": {"match": {"body": "alpha"}},
     "sort": [{"multi": {"order": "asc", "mode": "avg"}}], "size": 10},
    # _score sort → host
    {"query": {"match": {"body": "alpha"}}, "sort": ["_score"], "size": 10},
])
def test_host_fallbacks_agree(ctx, body):
    req = parse_search_body(body)
    if len(req.sort) == 1:
        assert _try_device_sort(ctx, req, 10, None, 0) is None
    _both(ctx, body, expect_device=False)


def test_serving_counters_track_paths(ctx):
    from elasticsearch_tpu.search.service import SERVING_COUNTERS

    cases = [
        ({"query": {"match": {"body": "alpha"}}, "size": 3}, "device_sparse"),
        ({"query": {"filtered": {"query": {"match": {"body": "alpha"}},
                                 "filter": {"range": {"rank": {"gte": 1}}}}},
          "size": 3}, "device_filtered"),
        ({"query": {"function_score": {"query": {"match": {"body": "alpha"}},
                                       "boost_factor": 2}}, "size": 3},
         "device_function_score"),
        ({"query": {"match": {"body": "alpha"}}, "size": 0,
          "aggs": {"m": {"max": {"field": "rank"}}}}, "device_aggs"),
        ({"query": {"match": {"body": "alpha"}}, "sort": [{"rank": "asc"}],
          "size": 3}, "device_sort"),
        ({"query": {"match": {"body": "alpha"}}, "sort": ["_score", {"rank": "asc"}],
          "size": 3}, "host"),
    ]
    for body, path in cases:
        before = SERVING_COUNTERS[path]
        execute_query_phase(ctx, parse_search_body(body), use_device=True)
        assert SERVING_COUNTERS[path] == before + 1, (path, body)


# ---------------------------------------------------------------------------
# sorted searches join the batch (PR 33): the searches in flight under one
# sort are one launch a segment, and every member is answered as the
# one-plan call answers it
# ---------------------------------------------------------------------------

_GROUP_WORDS = ["alpha", "beta", "gamma", "delta"]


def _group_query(variant: str, i: int) -> dict:
    match = {"match": {"body": f"{_GROUP_WORDS[i % 4]} {_GROUP_WORDS[(i + 1) % 4]}"}}
    rank = {"range": {"rank": {"gte": 300 * i}}}
    if variant == "scored":
        return match
    if variant == "scored_some_filtered":
        return match if i % 2 else {"filtered": {"query": match, "filter": rank}}
    if variant == "unscored":
        return {"constant_score": {"filter": rank}}
    assert variant == "unscored_some_filtered"
    return {"match_all": {}} if i % 2 else {"constant_score": {"filter": rank}}


def _group_bodies(variant: str, Q: int, sort, **extra) -> list:
    return [{"query": _group_query(variant, i), "size": 3 + 4 * (i % 3),
             "sort": [sort], **extra} for i in range(Q)]


def _assert_same_answer(got, want):
    """Totals, max_score, and every hit's document, `sort` values and score."""
    assert not isinstance(got, Exception), got
    assert got.total == want.total and not got.degraded
    assert len(got.docs) == len(want.docs)
    for (gs, gd, gv), (ws, wd, wv) in zip(got.docs, want.docs):
        assert (gd, gv) == (wd, wv)
        assert gs == ws or (math.isnan(gs) and math.isnan(ws))
    assert got.max_score == want.max_score or (
        math.isnan(got.max_score) and math.isnan(want.max_score))


@pytest.mark.parametrize("variant", ["scored", "scored_some_filtered",
                                     "unscored", "unscored_some_filtered"])
@pytest.mark.parametrize("Q", [1, 2, 3, 5, 8])
def test_a_group_answers_each_member_as_the_one_plan_call_does(ctx, Q, variant):
    from elasticsearch_tpu.search.service import SERVING_COUNTERS

    sort = {"rank": {"order": "desc" if Q % 2 else "asc", "missing": "_last"}}
    bodies = _group_bodies(variant, Q, sort, track_scores=True)
    host_before = SERVING_COUNTERS["host"]
    got, stats = run_as_one_batch(ctx, bodies)
    assert SERVING_COUNTERS["host"] == host_before
    # one collect, one group, one launch a segment for all Q
    assert stats["launches"] == 1 and stats["coalesced"] == Q
    assert stats["kinds"]["sorted"] == {"launches": 1, "coalesced": Q}
    assert stats["bypassed"] == 0 and stats["splits"] == 0
    for body, res in zip(bodies, got):
        req = parse_search_body(body)
        # the one-plan call: no batcher on the context, a launch a search
        _assert_same_answer(res, execute_query_phase(ctx, req))
        assert len(res.docs) == min(body["size"], res.total)
        # and the host's own order
        host = execute_query_phase(ctx, req, use_device=False)
        assert [(d, v) for _s, d, v in res.docs] == \
            [(d, v) for _s, d, v in host.docs]


def test_groups_split_by_their_sort(ctx):
    """One collect, a launch for each (field, mode, order, missing): the
    order is a static of the program and the key row another row."""
    sorts = [{"rank": "asc"}, {"rank": "desc"},
             {"rank": {"order": "asc", "missing": "_first"}},
             {"multi": {"order": "asc", "mode": "min"}}]
    bodies = [b for sort in sorts for b in _group_bodies("scored", 2, sort)]
    got, stats = run_as_one_batch(ctx, bodies)
    assert stats["launches"] == 1 and stats["coalesced"] == 8
    assert stats["kinds"]["sorted"] == {"launches": 4, "coalesced": 8}
    for body, res in zip(bodies, got):
        _assert_same_answer(res, execute_query_phase(ctx, parse_search_body(body)))


def test_a_refused_sort_row_sends_every_member_to_the_host(ctx, monkeypatch):
    """None from the executor (a segment's column refuses device keys)
    reaches EVERY member: each is sorted on the host, none is degraded."""
    import elasticsearch_tpu.search.execute as ex
    from elasticsearch_tpu.search.service import SERVING_COUNTERS

    bodies = _group_bodies("scored", 3, {"rank": "asc"})
    want = [execute_query_phase(ctx, parse_search_body(b), use_device=False)
            for b in bodies]
    monkeypatch.setattr(ex, "_sort_key_row", lambda *a, **k: None)
    before = dict(SERVING_COUNTERS)
    got, stats = run_as_one_batch(ctx, bodies)
    assert stats["kinds"]["sorted"] == {"launches": 1, "coalesced": 3}
    assert SERVING_COUNTERS["host"] == before["host"] + 3
    assert SERVING_COUNTERS["device_sort"] == before["device_sort"]
    assert SERVING_COUNTERS["device_errors"] == before["device_errors"]
    for res, host in zip(got, want):
        _assert_same_answer(res, host)
