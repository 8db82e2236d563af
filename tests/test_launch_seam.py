"""The seam between a request and its launch (ISSUE 45): one table of group
kinds with one launch protocol behind it (search/execute.py GROUP_KINDS,
_run_flat_groups), and one device-attempt envelope around the query phase's
five device routes (search/service.py _device_attempt, _MASK_ROUTES)."""

import pytest

from elasticsearch_tpu.common.devicehealth import DEVICE_HEALTH
from elasticsearch_tpu.common.errors import CircuitBreakingError, ScriptError
from elasticsearch_tpu.common.jaxenv import COMPILE_FAMILIES
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index import Engine
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.ops import device_index, scoring
from elasticsearch_tpu.search import ShardContext, parse_query
from elasticsearch_tpu.search import batcher as batcher_mod
from elasticsearch_tpu.search import execute as ex
from elasticsearch_tpu.search.service import (SERVING_COUNTERS,
                                              execute_query_phase,
                                              parse_search_body)
from elasticsearch_tpu.search.similarity import SimilarityService
from elasticsearch_tpu.transport.faults import (DEVICE_FAULTS,
                                                make_device_error)

from .harness import run_as_one_batch

pytestmark = pytest.mark.device

WORDS = ["quick", "brown", "fox", "lazy", "dog", "summer", "red", "bear",
         "snack", "cat"]
MATCH = {"match": {"body": "quick dog"}}
AGGS = {"by_day": {"histogram": {"field": "day", "interval": 3}},
        "rank_stats": {"stats": {"field": "rank"}}}
FS = {"function_score": {
    "query": MATCH, "boost_mode": "replace",
    "field_value_factor": {"field": "rank", "modifier": "log1p"}}}


@pytest.fixture
def ctx(tmp_path):
    """Two segments nothing has packed yet: a text field and three integer
    columns, `zero` holding 0 in every document."""
    settings = Settings.from_flat({})
    svc = MapperService(settings)
    e = Engine(str(tmp_path / "shard0"), svc)
    for i in range(80):
        text = f"{WORDS[i % 10]} {WORDS[(i + 1) % 10]} {WORDS[(i + 3) % 10]}"
        e.index("doc", str(i), {"body": text, "rank": (i * 37) % 101,
                                "day": i % 12, "zero": 0})
        if i == 39:
            e.refresh()
    e.refresh()
    yield ShardContext(e.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc),
                       index_name="seam")
    e.close()


@pytest.fixture(autouse=True)
def _device_state_hygiene():
    DEVICE_FAULTS.disarm()
    DEVICE_HEALTH.reset()
    yield
    DEVICE_FAULTS.disarm()
    DEVICE_HEALTH.reset()


# ---------------------------------------------------------------------------
# the kinds' table
# ---------------------------------------------------------------------------


def test_every_kind_a_plan_can_have_has_a_row_and_the_vocabularies_hold_it(ctx):
    def lowered(query, **kw):
        plan = ex.lower_flat(parse_query(query), ctx, **kw)
        assert plan is not None, query
        return plan

    rank = {"range": {"rank": {"gte": 10}}}
    spec = parse_search_body({"sort": [{"rank": "asc"}]}).sort[0]
    # a dis_max plan is BM25's: under TF-IDF every disjunct takes a coord
    bm25 = Settings.from_flat({"index.similarity.default.type": "BM25"})
    dis_max = ex.lower_flat(
        parse_query({"dis_max": {"queries": [{"match": {"body": "quick dog"}},
                                             {"term": {"body": "fox"}}]}}),
        ShardContext(ctx.searcher, ctx.mapper_service, SimilarityService(
            bm25, mapper_service=ctx.mapper_service)), phrases=True)
    named = {ex.plan_kind(plan, tail) for plan, tail in (
        (lowered(MATCH), None),
        (lowered(FS), None),
        (lowered({"filtered": {"query": MATCH, "filter": rank}}), None),
        (lowered({"constant_score": {"filter": rank}}), None),
        (lowered({"match_phrase": {"body": "quick brown"}}, phrases=True),
         None),
        (dis_max, None),
        (lowered(MATCH), ex.aggs_tail(["rank"], [])),
        (lowered(MATCH), ex.sort_tail(spec)))}
    assert named == set(ex.GROUP_KINDS)
    assert named == set(batcher_mod._KINDS) - {"mesh"}
    for kind, row in ex.GROUP_KINDS.items():
        assert set(row.families) <= set(COMPILE_FAMILIES), kind
        assert row.served in SERVING_COUNTERS, kind
        # one protocol, but for the plain group's own
        assert (row.launch is None) == (kind == "plain"), kind
        assert kind == "plain" or row.width >= 1, kind
    # every group _flat_groups names leads with a key of the table
    groups = ex._flat_groups([lowered(MATCH), lowered(FS)], None)
    assert [g[0] for g in groups] == ["plain", "function_score"]
    assert ex._PLAIN_GROUP in groups


# ---------------------------------------------------------------------------
# one pull a batch
# ---------------------------------------------------------------------------


def _answer(res):
    return (res.total, [(round(s, 5), d) for s, d, _v in res.docs],
            res.agg_partials)


def test_a_function_score_and_an_aggregated_search_share_one_pull(
        ctx, monkeypatch):
    bodies = [{"query": FS, "size": 5},
              {"query": MATCH, "size": 5, "aggs": AGGS}]
    alone = [execute_query_phase(ctx, parse_search_body(b)) for b in bodies]
    pulls = []
    real = scoring._pull
    monkeypatch.setattr(scoring, "_pull",
                        lambda out: pulls.append(len(out)) or real(out))
    got, stats = run_as_one_batch(ctx, bodies)
    monkeypatch.undo()
    # one device_get for the two groups' launches, a launch a segment each
    assert pulls == [2], pulls
    assert stats["kinds"]["function_score"] == {"launches": 1, "coalesced": 1}
    assert stats["kinds"]["aggs"] == {"launches": 1, "coalesced": 1}
    for res, ref in zip(got, alone):
        assert not isinstance(res, Exception), res
        assert not res.degraded
        assert repr(_answer(res)) == repr(_answer(ref))


def test_a_script_error_in_a_groups_rows_sends_every_member_to_the_host(ctx):
    """log(0) in a doc-only function: the host's evaluation of the rows
    raises, launch_flat_fs hands back a handle with nothing to pull, and its
    finish answers from the host scorer with the host's error."""
    query = parse_query({"function_score": {
        "query": MATCH,
        "script_score": {"script": "log(doc['zero'].value)"}}})
    plan = ex.lower_flat(query, ctx)
    assert plan is not None and plan.fs_kind == "rows"
    refs, finish = ex.launch_flat_fs([plan, plan], ctx, 10, None)
    assert len(refs) == 0
    with pytest.raises(ScriptError):
        finish(refs)
    with pytest.raises(ScriptError):
        ex.search_shard(ctx, query, 10, use_device=False)
    with pytest.raises(ScriptError):
        ex.execute_flat_batch([plan], ctx, 10)


# ---------------------------------------------------------------------------
# the envelope, a route at a time
# ---------------------------------------------------------------------------

ROUTES = {
    "no_mask": {"query": MATCH, "size": 5},
    "aggs": {"query": MATCH, "size": 5, "aggs": AGGS},
    "min_score": {"query": MATCH, "size": 5, "min_score": 0.05},
    "post_filter": {"query": MATCH, "size": 5,
                    "post_filter": {"range": {"rank": {"gte": 20}}}},
    "sort": {"query": MATCH, "size": 5, "sort": [{"rank": "asc"}]},
}
SERVED = {"no_mask": "device_sparse", "aggs": "device_aggs",
          "min_score": "device_filtered", "post_filter": "device_filtered",
          "sort": "device_sort"}


def _breaker_trip(name):
    err = CircuitBreakingError(f"[{name}] injected: data too large")
    err.breaker = name
    return err


def _raising(err):
    def packed_for(*a, **kw):
        raise err
    return packed_for


@pytest.mark.parametrize("trouble", ["none", "device_fault", "fielddata",
                                     "request", "open_domain"])
@pytest.mark.parametrize("route", list(ROUTES))
def test_the_envelope_holds_on_every_route(ctx, monkeypatch, route, trouble):
    req = parse_search_body(ROUTES[route])
    host = execute_query_phase(ctx, req, use_device=False)
    assert host.total > 0 and not host.degraded
    if trouble == "device_fault":
        # the pack's own seam: nothing has packed these segments yet
        DEVICE_FAULTS.arm("internal", domain="pack:seam", times=1)
    elif trouble in ("fielddata", "request"):
        monkeypatch.setattr(device_index, "packed_for",
                            _raising(_breaker_trip(trouble)))
    elif trouble == "open_domain":
        assert DEVICE_HEALTH.record_failure(
            "pull:seam", make_device_error("launch")) == "persistent"
        monkeypatch.setattr(device_index, "packed_for", _raising(
            AssertionError("an open domain launches nothing")))
    before = dict(SERVING_COUNTERS)
    if trouble == "request":
        with pytest.raises(CircuitBreakingError):  # the 429: load is shed
            execute_query_phase(ctx, req)
        assert SERVING_COUNTERS == before
        return
    got = execute_query_phase(ctx, req)
    delta = {name: n - before[name] for name, n in SERVING_COUNTERS.items()
             if n != before[name]}
    if trouble == "none":
        assert not got.degraded and delta == {SERVED[route]: 1}
    else:
        assert got.degraded
        assert delta == {"host": 1, "degraded": 1, **(
            {} if trouble == "open_domain" else {"device_errors": 1})}
    assert got.total == host.total
    assert [d for _s, d, _v in got.docs] == [d for _s, d, _v in host.docs]
    assert repr(got.agg_partials) == repr(host.agg_partials) \
        or trouble == "none"
