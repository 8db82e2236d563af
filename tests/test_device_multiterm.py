"""Prefix, wildcard and regexp searches on the device (search/multiterm.py the
expansion, ops/scoring.py the mask program, search/execute.py `_unscored` and
`_filter_mask_matrix`).

On the CPU, seeded and small: the device's answer against the host scorer
(`HostScorer._multi_term_mask`, which shares the expansion) AND against a brute
force over the raw documents' tokens that reads no dictionary (plain string tests
and `fnmatch` / `re` a token): totals, ids, the order of ties, scores bitwise
under BM25 and under the default similarity. As a whole query, under
`constant_score`, under `filtered{match, filter: prefix}`, under
`function_score`, with a sort and with a `terms` aggregation; with deletes,
several segments and nested documents; an empty expansion; each rung's edge;
every form that stays on the host under its named reason; a cacheable filter's
built row admitted on its second sighting and a whole query's never; and
`mask_put_bytes` flat throughout."""

import fnmatch
import json
import re

import numpy as np
import pytest

from benchmark.harness import registry
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index import Engine
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.ops.device_index import DeviceFilterCache, packed_for
from elasticsearch_tpu.search import ShardContext, multiterm, parse_query, search_shard
from elasticsearch_tpu.search.execute import (
    lower_fallback_reason, lower_flat, search_shard_batch)
from elasticsearch_tpu.search.filters import (
    PrefixFilter, RegexpFilter, WildcardFilter)
from elasticsearch_tpu.search.service import (
    SERVING_COUNTERS, execute_query_phase, parse_search_body)
from elasticsearch_tpu.search.similarity import SimilarityService
from elasticsearch_tpu.transport.local import LocalTransportRegistry

pytestmark = pytest.mark.serving

CORPUS = {"vocabulary": 3000, "mean_length": 30, "min_length": 5, "max_length": 90,
          "zipf_a": 1.25, "text_field": "body",
          "date": {"field": "date", "first_day": "2006-01-01", "days": 3653}}
N_DOCS = 600  # doc_pad 1024 over three segments
MAPPING = {"doc": {"properties": {
    "body": {"type": "string"}, "date": {"type": "date"},
    "n": {"type": "integer"},
    "notes": {"type": "nested", "properties": {"text": {"type": "string"}}}}}}


def _docs():
    corpus = registry.module("corpora", "zipf_text").generate(CORPUS, 41, N_DOCS)
    docs = [json.loads(s) for s in corpus.sources(0, N_DOCS)]
    for i, d in enumerate(docs):
        d["n"] = i % 50
        if i % 7 == 0:  # nested children: documents no search may return
            d["notes"] = [{"text": f"w12{i % 10} w9{i % 10}"}, {"text": "w777"}]
    return docs


def _shard(tmp, sim, delete=(), filter_cache=None):
    settings = Settings.from_flat({"index.similarity.default.type": sim})
    svc = MapperService(settings)
    svc.put_mapping("doc", MAPPING)
    eng = Engine(str(tmp), svc)
    docs = _docs()
    for i, d in enumerate(docs):
        eng.index("doc", str(i), d)
        if i in (199, 399):
            eng.refresh()
    eng.refresh()
    for i in delete:
        eng.delete("doc", str(i))
    eng.refresh()
    sims = SimilarityService(settings, mapper_service=svc)
    ctx = ShardContext(eng.acquire_searcher(), svc, sims, index_name="idx",
                       filter_cache=filter_cache)
    assert len(ctx.searcher.segments) == 3
    return eng, ctx, docs


DELETED = (0, 3, 120, 121, 340, 599)


@pytest.fixture(scope="module", params=["BM25", "default"])
def shard(request, tmp_path_factory):
    eng, ctx, docs = _shard(tmp_path_factory.mktemp("mt_" + request.param),
                            request.param, delete=DELETED)
    yield ctx, docs
    eng.close()


def _counters() -> dict:
    return {**SERVING_COUNTERS, **scoring.LAUNCHES.snapshot()}


def _delta(before: dict) -> dict:
    after = _counters()
    return {k: after[k] - before[k] for k in after}


def _brute(docs, test, field="body"):
    """The ids of the live documents one of whose own tokens passes `test`,
    in document order: no dictionary, no postings."""
    return [i for i, d in enumerate(docs)
            if i not in DELETED and any(test(t) for t in d[field].split())]


def _token_test(body: dict):
    """A plain predicate over one token for a prefix / wildcard / regexp body."""
    (kind, spec), = body.items()
    (_field, opts), = spec.items()
    value = opts["value"] if isinstance(opts, dict) else opts
    if kind == "prefix":
        return lambda t: t.startswith(value)
    if kind == "wildcard":
        return lambda t: fnmatch.fnmatchcase(t, value)
    rex = re.compile(value)
    return lambda t: rex.fullmatch(t) is not None


def _bitwise(a, b):
    assert np.float32(a).tobytes() == np.float32(b).tobytes()


def _hit_ids(ctx, hits):
    """The `_id` of every (score, global doc) hit, as integers."""
    out = []
    for _s, g in hits:
        si = int(np.searchsorted(ctx.searcher.bases, g, side="right")) - 1
        out.append(int(ctx.searcher.segments[si].ids[g - ctx.searcher.bases[si]]))
    return out


PATTERNS = {
    "prefix_narrow": {"prefix": {"body": "w12"}},
    "prefix_wide_boost": {"prefix": {"body": {"value": "w1", "boost": 1.7}}},
    "prefix_everything": {"prefix": {"body": "w"}},
    "prefix_one_term": {"prefix": {"body": {"value": "w2999", "boost": 0.3}}},
    "wildcard_star_inside": {"wildcard": {"body": {"value": "w1*5", "boost": 2.3}}},
    "wildcard_question": {"wildcard": {"body": "w?2*"}},
    "wildcard_several_stars": {"wildcard": {"body": "w*1*3"}},
    "wildcard_no_head": {"wildcard": {"body": {"value": "*77", "boost": 1.1}}},
    "wildcard_trailing_question": {"wildcard": {"body": "w12?"}},
    "wildcard_literal": {"wildcard": {"body": "w12"}},
    "wildcard_star_last": {"wildcard": {"body": "w20*"}},
    "regexp_class": {"regexp": {"body": {"value": "w1[0-3].*", "boost": 1.3}}},
    "regexp_optional": {"regexp": {"body": "w12?3"}},
    "regexp_alternation": {"regexp": {"body": "(w5|w6)1.*"}},
    "regexp_no_head": {"regexp": {"body": ".*99"}},
    "regexp_counted": {"regexp": {"body": "w1{2}[0-9]*"}},
}


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_device_is_host_is_brute_force(shard, name):
    """A whole-query multi-term search: the device's totals, ids and order of
    ties are the host scorer's and the brute force's, its scores the host's
    bit for bit; the row was built on the chip and nothing was put."""
    ctx, docs = shard
    body = PATTERNS[name]
    query = parse_query(body)
    plan = lower_flat(query, ctx, phrases=True)
    assert plan is not None and plan.const is not None
    assert not plan.filt.cacheable()  # a query is never kept in a filter cache
    before = _counters()
    dev = search_shard(ctx, query, 10, use_device=True)
    moved = _delta(before)
    host = search_shard(ctx, query, 10, use_device=False)
    want = _brute(docs, _token_test(body))
    assert dev.total == host.total == len(want) > 0
    assert [d for _s, d in dev.hits] == [d for _s, d in host.hits]
    # equal scores rank by ascending document: the first matches, in order
    assert _hit_ids(ctx, dev.hits) == want[:10]
    for (ds, _d), (hs, _h) in zip(dev.hits, host.hits):
        _bitwise(ds, hs)
    assert len({s for s, _d in dev.hits}) == 1
    assert moved["mask_put_bytes"] == 0 and moved["multiterm_searches"] == 1
    assert moved["multiterm"] >= 1 and moved["unscored_plans"] == 1
    assert moved["multiterm_bytes"] > moved["multiterm_pad_bytes"] >= 0
    # an expansion a segment whose pattern has no literal head (an alternation
    # has none that bisection may use) tests the whole field's dictionary
    assert moved["multiterm_field_scans"] == (
        3 if "no_head" in name or "alternation" in name else 0)


@pytest.mark.parametrize("name", ["prefix_narrow", "wildcard_star_inside",
                                  "wildcard_no_head", "regexp_alternation"])
def test_ties_come_in_document_order_of_the_raw_documents(shard, name):
    """The hits are the FIRST live matching documents by `_id` order of
    ingest (deleted ones and nested children left out), from the raw text."""
    ctx, docs = shard
    body = PATTERNS[name]
    dev = search_shard(ctx, parse_query(body), 10, use_device=True)
    want = _brute(docs, _token_test(body))
    assert _hit_ids(ctx, dev.hits) == want[:10]
    assert dev.total == len(want)


def _phase(ctx, body, outcome):
    req = parse_search_body(body)
    before = _counters()
    dev = execute_query_phase(ctx, req, use_device=True)
    moved = _delta(before)
    host = execute_query_phase(ctx, req, use_device=False)
    assert moved["host"] == 0, "the host scorer answered"
    assert moved[outcome] == 1 and moved["mask_put_bytes"] == 0
    assert dev.total == host.total
    assert [g for _s, g, _v in dev.docs] == [g for _s, g, _v in host.docs]
    for (ds, _g, dv), (hs, _hg, hv) in zip(dev.docs, host.docs):
        assert dv == hv
        assert (ds != ds and hs != hs) or \
            np.float32(ds).tobytes() == np.float32(hs).tobytes()
    return dev, host, moved


FORMS = {
    "whole_query": ({"query": {"prefix": {"body": {"value": "w13", "boost": 1.3}}}},
                    "device_filtered"),
    "constant_score_filter": ({"query": {"constant_score": {
        "filter": {"prefix": {"body": "w14"}}, "boost": 2.3}}}, "device_filtered"),
    "constant_score_regexp_filter": ({"query": {"constant_score": {
        "filter": {"regexp": {"body": "w1[5-6].*"}}}}}, "device_filtered"),
    "filtered_match_prefix": ({"query": {"filtered": {
        "query": {"match": {"body": "w1 w2 w3"}},
        "filter": {"prefix": {"body": "w2"}}}}}, "device_filtered"),
    "filtered_match_all_prefix": ({"query": {"filtered": {
        "query": {"match_all": {}}, "filter": {"prefix": {"body": "w17"}}}}},
        "device_filtered"),
    "function_score_over_wildcard": ({"query": {"function_score": {
        "query": {"wildcard": {"body": "w1*7"}},
        "functions": [{"field_value_factor": {"field": "n", "factor": 1.5}}],
        "boost_mode": "sum"}}}, "device_function_score"),
    "sorted": ({"query": {"prefix": {"body": "w1"}},
                "sort": [{"n": "desc"}], "size": 7}, "device_sort"),
    "terms_aggregation": ({"query": {"wildcard": {"body": "w2*1"}}, "size": 3,
                           "aggs": {"by_n": {"terms": {"field": "n", "size": 5}}}},
                          "device_aggs"),
    "stats_aggregation_under_prefix_filter": ({"query": {"filtered": {
        "query": {"match": {"body": "w1"}}, "filter": {"prefix": {"body": "w3"}}}},
        "size": 0, "aggs": {"n": {"stats": {"field": "n"}}}}, "device_aggs"),
}


@pytest.mark.parametrize("name", sorted(FORMS))
def test_every_form_rides_the_device_with_a_built_row(shard, name):
    ctx, _docs_ = shard
    body, outcome = FORMS[name]
    dev, host, moved = _phase(ctx, body, outcome)
    assert dev.total > 0
    assert moved["multiterm_searches"] == 1 and moved["multiterm"] >= 1
    if body.get("aggs"):
        from elasticsearch_tpu.search.aggregations import reduce_aggs

        req = parse_search_body(body)
        assert reduce_aggs(req.aggs, dev.agg_partials) \
            == reduce_aggs(req.aggs, host.agg_partials)


def test_a_prefix_on_a_nested_field_and_nested_children_never_hit(shard):
    """Nested children hold `w12x` and `w777` in `notes.text`: a prefix on
    `body` never returns a child, and a prefix on the nested field matches
    what the host scorer says it does (nothing at the root)."""
    ctx, docs = shard
    for body in ({"prefix": {"body": "w777"}}, {"prefix": {"notes.text": "w12"}},
                 {"wildcard": {"notes.text": "w9*"}}):
        q = parse_query(body)
        dev = search_shard(ctx, q, 10, use_device=True)
        host = search_shard(ctx, q, 10, use_device=False)
        assert dev.total == host.total
        assert dev.hits == host.hits
    want = _brute(docs, lambda t: t.startswith("w777"))
    assert search_shard(ctx, parse_query({"prefix": {"body": "w777"}}), 10).total \
        == len(want)


def test_an_empty_expansion_launches_nothing(shard):
    ctx, _docs_ = shard
    before = _counters()
    dev = search_shard(ctx, parse_query({"prefix": {"body": "zebra"}}), 10)
    moved = _delta(before)
    assert dev.total == 0 and dev.hits == []
    assert moved["multiterm"] == 0 and moved["multiterm_bytes"] == 0
    assert moved["multiterm_searches"] == 1 and moved["multiterm_terms"] == 0
    assert moved["host"] == 0 and moved["mask_put_bytes"] == 0


def test_a_batch_shares_the_first_rungs_launch(shard):
    """Three first-rung searches of one batch: one launch a segment at a
    query count of four, every answer its own."""
    ctx, _docs_ = shard
    queries = [parse_query({"prefix": {"body": p}}) for p in ("w21", "w22", "w23")]
    before = _counters()
    out = search_shard_batch(ctx, queries, 10)
    moved = _delta(before)
    assert moved["multiterm"] == len(ctx.searcher.segments)
    assert moved["multiterm_searches"] == 3
    assert moved["multiterm_bytes"] == \
        len(ctx.searcher.segments) * 4 * scoring.MULTITERM_RUNGS[0] * 128 * 4
    for q, td in zip(queries, out):
        host = search_shard(ctx, q, 10, use_device=False)
        assert td.total == host.total and td.hits == host.hits


# ---------------------------------------------------------------------------
# the expansion: bisection and head ranges against a scan of the dictionary
# ---------------------------------------------------------------------------

EXPANSIONS = [
    ("prefix", "w1"), ("prefix", "w"), ("prefix", ""), ("prefix", "w29"),
    ("prefix", "x"), ("prefix", "a"), ("prefix", "w\U0010ffff"),
    ("wildcard", "w1*5"), ("wildcard", "w1*"), ("wildcard", "*"),
    ("wildcard", "w?"), ("wildcard", "w1?*9"), ("wildcard", "w123"),
    ("wildcard", "w1*5*"), ("wildcard", "*5"), ("wildcard", "w12*2"),
    ("regexp", "w1.*"), ("regexp", "w12?"), ("regexp", "w1*"), ("regexp", "w(1|2)3"),
    ("regexp", "w1|w22"), ("regexp", "w1{1,2}"), ("regexp", "w1+"),
    ("regexp", "[vw]12.?"), ("regexp", "w12"), ("regexp", r"w1\d"),
]


@pytest.mark.parametrize("kind,pattern", EXPANSIONS,
                         ids=[f"{k}:{p!r}" for k, p in EXPANSIONS])
def test_the_expansion_is_a_scan_of_the_dictionary(shard, kind, pattern):
    ctx, _docs_ = shard
    seg = ctx.searcher.segments[0]
    terms, first = seg.sorted_terms("body")
    assert terms == sorted(seg.term_dict["body"]) and terms is seg.terms_for_field("body")
    assert [seg.term_dict["body"][t] for t in terms[:5]] == list(range(first, first + 5))
    if kind == "prefix":
        test = lambda t: t.startswith(pattern)  # noqa: E731
    elif kind == "wildcard":
        test = lambda t: fnmatch.fnmatchcase(t, pattern)  # noqa: E731
    else:
        test = lambda t: re.fullmatch(pattern, t) is not None  # noqa: E731
    want = [first + i for i, t in enumerate(terms) if test(t)]
    got = multiterm.expand(seg, "body", kind, pattern)
    assert got.tids.tolist() == want
    head = multiterm.literal_head(kind, pattern)
    assert all(terms[t - first].startswith(head) for t in want)
    rows, _runs = multiterm.ranges_of(packed_for(seg).term_blk_start, got.tids)
    assert got.rows == len(rows)  # the one count: what a launch would gather
    places, runs = multiterm.ranges_of(seg.post_offsets, got.tids)
    assert places.tolist() == [p for t in want for p in range(
        int(seg.post_offsets[t]), int(seg.post_offsets[t + 1]))]
    assert runs == sum(1 for i, t in enumerate(want) if i == 0 or want[i - 1] != t - 1)


def test_a_field_the_segment_lacks_expands_to_nothing(shard):
    ctx, _docs_ = shard
    seg = ctx.searcher.segments[0]
    assert seg.sorted_terms("nowhere") == ([], 0)
    assert multiterm.expand(seg, "nowhere", "prefix", "a").tids.tolist() == []
    assert not multiterm.host_mask(seg, "nowhere", "wildcard", "*").any()


# ---------------------------------------------------------------------------
# the program: each rung's edge
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,rung", [
    (1, 256), (256, 256), (257, 2048), (2048, 2048), (2049, 8192), (8192, 8192)])
def test_a_rungs_edge_builds_the_union_of_its_rows(shard, rows, rung):
    ctx, _docs_ = shard
    seg = ctx.searcher.segments[1]
    packed = packed_for(seg)
    assert scoring.multiterm_rung(rows) == rung
    assert scoring.multiterm_rung(scoring.MULTITERM_RUNGS[-1] + 1) is None
    real = int(packed.term_blk_start[-1])
    named = np.resize(np.arange(real, dtype=np.int32), rows)
    before = scoring.LAUNCHES.snapshot()
    ((places, matrix, (row,)),) = scoring.build_multiterm_rows(packed, [named])
    after = scoring.LAUNCHES.snapshot()
    assert places == [0] and matrix.shape == (1, packed.doc_pad)
    assert (np.asarray(matrix[0]) == np.asarray(row)).all()
    plane = np.asarray(packed.blk_docs)[named].reshape(-1)
    want = np.zeros(packed.doc_pad, bool)
    want[plane[plane < packed.doc_pad]] = True
    assert (np.asarray(row) == want).all() and row.dtype == bool
    assert after["multiterm"] - before["multiterm"] == 1
    assert after["multiterm_bytes"] - before["multiterm_bytes"] == rung * 128 * 4
    assert after["multiterm_pad_bytes"] - before["multiterm_pad_bytes"] \
        == (rung - rows) * 128 * 4


def test_the_launches_of_a_batch_by_rung():
    lists = [np.zeros(n, np.int32) for n in (3, 0, 300, 256, 9000 - 808, 1, 2, 4, 5,
                                             6, 7, 8, 9)]
    launches = scoring.multiterm_launches(lists)
    assert launches == [
        ([0, 3, 5, 6, 7, 8, 9, 10], 8, 256), ([11, 12], 2, 256),
        ([2], 1, 2048), ([4], 1, 8192)]


# ---------------------------------------------------------------------------
# what stays on the host, each under its reason
# ---------------------------------------------------------------------------

HOST = {
    "fuzzy_query": {"fuzzy": {"body": {"value": "w123", "fuzziness": 1}}},
    "fuzzy_match": {"match": {"body": {"query": "w123", "fuzziness": 1}}},
    "scoring_rewrite:scoring_boolean": {"prefix": {"body": {
        "value": "w12", "rewrite": "scoring_boolean"}}},
    "scoring_rewrite:top_terms_5": {"wildcard": {"body": {
        "value": "w1*2", "rewrite": "top_terms_5"}}},
    "scoring_rewrite:top_terms_boost_5": {"regexp": {"body": {
        "value": "w12.*", "rewrite": "top_terms_boost_5"}}},
    "non_term_subclause": {"bool": {"must": [{"match": {"body": "w1"}}],
                                    "should": [{"prefix": {"body": "w12"}}]}},
    "span_multi": {"span_multi": {"match": {"prefix": {"body": "w12"}}}},
    "phrase_prefix": {"match_phrase_prefix": {"body": "w1 w2"}},
    "host_only_field": {"prefix": {"_uid": "doc#1"}},
    "multiterm_numeric_field": {"prefix": {"n": "1"}},
}


@pytest.mark.parametrize("name", sorted(HOST))
def test_what_stays_on_the_host_says_why(shard, name):
    ctx, _docs_ = shard
    query = parse_query(HOST[name])
    assert lower_flat(query, ctx, phrases=True) is None
    assert lower_fallback_reason(query, ctx) == name.split(":")[0]
    before = _counters()
    dev = search_shard(ctx, query, 10, use_device=True)
    moved = _delta(before)
    host = search_shard(ctx, query, 10, use_device=False)
    assert moved["multiterm"] == 0 and moved["multiterm_searches"] == 0
    assert dev.total == host.total and dev.hits == host.hits


@pytest.mark.parametrize("rewrite", [None, "constant_score_auto",
                                     "constant_score_boolean",
                                     "constant_score_filter"])
def test_a_constant_score_rewrite_lowers(shard, rewrite):
    ctx, _docs_ = shard
    spec = {"value": "w12"} if rewrite is None else {"value": "w12", "rewrite": rewrite}
    assert lower_flat(parse_query({"prefix": {"body": spec}}), ctx) is not None


def test_an_expansion_past_the_last_rung_stays_on_the_host(shard, monkeypatch):
    """With the ladder cut to 1 / 2 / 4 block rows a wide prefix names more
    rows than the last rung: as a QUERY it goes to the host scorer under its
    reason (the lowering counts the expansion's rows), as a FILTER its row is
    evaluated on the host and put; a prefix that fits still builds."""
    ctx, _docs_ = shard
    monkeypatch.setattr(scoring, "MULTITERM_RUNGS", (1, 2, 4))
    wide, narrow = {"prefix": {"body": "w1"}}, {"prefix": {"body": "w2999"}}
    assert lower_fallback_reason(parse_query(wide), ctx) == "multiterm_expansion"
    assert lower_flat(parse_query(wide), ctx) is None
    # a wide head, an expansion that fits: expanded at the lowering, once, and
    # handed to the launch with the plan
    tail = parse_query({"wildcard": {"body": "w*2999"}})
    plan = lower_flat(tail, ctx)
    assert plan is not None and [held for held, _exp in plan.filt.expanded] == ctx.searcher.segments
    assert lower_flat(parse_query(narrow), ctx) is not None
    before = _counters()
    dev = search_shard(ctx, parse_query(wide), 10, use_device=True)
    assert _delta(before)["multiterm"] == 0
    host = search_shard(ctx, parse_query(wide), 10, use_device=False)
    assert dev.total == host.total and dev.hits == host.hits
    body = {"query": {"constant_score": {"filter": wide}}}
    before = _counters()
    dev = execute_query_phase(ctx, parse_search_body(body), use_device=True)
    moved = _delta(before)
    assert moved["device_filtered"] == 1 and moved["host"] == 0
    assert moved["multiterm"] == 0 and moved["mask_put_bytes"] > 0
    assert dev.total == execute_query_phase(
        ctx, parse_search_body(body), use_device=False).total


# ---------------------------------------------------------------------------
# the filter cache: a filter's built row is admitted, a query's never
# ---------------------------------------------------------------------------


@pytest.fixture()
def cached(tmp_path):
    cache = DeviceFilterCache()
    eng, ctx, _docs_ = _shard(tmp_path, "BM25", filter_cache=cache)
    yield ctx, cache
    eng.close()


def test_a_filters_built_row_is_admitted_on_its_second_sighting(cached):
    ctx, cache = cached
    n_seg = len(ctx.searcher.segments)
    body = parse_search_body({"query": {"constant_score": {
        "filter": {"prefix": {"body": "w15"}}, "boost": 1.7}}})
    host = execute_query_phase(ctx, body, use_device=False)
    seen = []
    for _ in range(3):
        before = _counters()
        dev = execute_query_phase(ctx, body, use_device=True)
        seen.append((_delta(before), cache.stats()["masks"]))
        assert dev.total == host.total and dev.docs == host.docs
    (first, masks1), (second, masks2), (third, masks3) = seen
    assert (masks1, masks2, masks3) == (0, n_seg, n_seg)
    assert first["multiterm"] == second["multiterm"] == n_seg  # built twice
    assert third["multiterm"] == 0 and third["multiterm_searches"] == 0  # resident
    assert cache.stats()["builds"] == n_seg and cache.stats()["hits"] == n_seg
    assert all(m["mask_put_bytes"] == 0 and m["host"] == 0 for m, _n in seen)
    # the row the cache holds is the row the chip built: on the device, bool
    holder = ctx.searcher.segments[0]._device_cache["filter_masks"]
    (row, nbytes), = holder.entries.values()
    assert row.dtype == bool and not isinstance(row, np.ndarray)
    assert nbytes == packed_for(ctx.searcher.segments[0]).doc_pad


def test_a_whole_querys_row_is_never_admitted(cached):
    ctx, cache = cached
    n_seg = len(ctx.searcher.segments)
    query = parse_query({"prefix": {"body": "w15"}})
    for _ in range(3):
        before = _counters()
        search_shard(ctx, query, 10, use_device=True)
        moved = _delta(before)
        assert moved["multiterm"] == n_seg and moved["mask_put_bytes"] == 0
    stats = cache.stats()
    assert stats["masks"] == 0 and stats["builds"] == 0
    assert stats["hits"] == stats["misses"] == 0  # never looked up


def _view(eng, ctx, cache):
    return ShardContext(eng.acquire_searcher(), ctx.mapper_service,
                        ctx.similarity_service, index_name="idx",
                        filter_cache=cache)


@pytest.mark.parametrize("cached_on", ["newer_view", "older_view"])
def test_a_cached_built_row_serves_every_view_of_its_segment(tmp_path, cached_on):
    """Views of a segment before and after a delete share the cache's holder.
    A row built on the view WITH the tombstone lacks the dead document, which
    the older view still holds: it is built again each search and never
    admitted there. A row admitted on the older view holds every document,
    and the newer view's live gate takes the dead one out."""
    cache = DeviceFilterCache()
    eng, ctx_a, docs = _shard(tmp_path, "BM25", filter_cache=cache)
    try:
        body = parse_search_body({"query": {"constant_score": {
            "filter": {"prefix": {"body": "w1"}}}}, "size": 1000})
        gone = next(i for i, d in enumerate(docs)
                    if any(t.startswith("w1") for t in d["body"].split()))
        host_a = execute_query_phase(ctx_a, body, use_device=False)
        # the filter's first sighting: the holder exists before the delete
        assert execute_query_phase(ctx_a, body, use_device=True).docs == host_a.docs
        eng.delete("doc", str(gone))
        eng.refresh()
        ctx_b = _view(eng, ctx_a, cache)
        seg_a, seg_b = ctx_a.searcher.segments[0], ctx_b.searcher.segments[0]
        assert seg_a is not seg_b and seg_a.live.all() and not seg_b.live.all()
        holder = seg_a._device_cache["filter_masks"]
        assert holder is seg_b._device_cache["filter_masks"] and not holder.entries
        host_b = execute_query_phase(ctx_b, body, use_device=False)
        assert host_b.total == host_a.total - 1

        def searched(ctx, host):
            before = _counters()
            dev = execute_query_phase(ctx, body, use_device=True)
            moved = _delta(before)
            assert moved["host"] == 0 and moved["mask_put_bytes"] == 0
            assert dev.total == host.total and dev.docs == host.docs
            return moved["multiterm"]

        if cached_on == "newer_view":
            # the view with the tombstone builds its segment's row every time
            assert [searched(ctx_b, host_b) for _ in range(3)] == [3, 1, 1]
            assert not holder.entries and cache.stats()["masks"] == 2
            # the older view still holds the document: it admits its own row
            assert [searched(ctx_a, host_a) for _ in range(2)] == [1, 0]
        else:
            assert [searched(ctx_a, host_a) for _ in range(2)] == [3, 0]
        assert len(holder.entries) == 1 and cache.stats()["masks"] == 3
        # either view, from the row the older one admitted
        assert searched(ctx_b, host_b) == 0 and searched(ctx_a, host_a) == 0
    finally:
        eng.close()


def _percolator_docs(n):
    rng = np.random.default_rng(5)
    return [{"body": " ".join(f"w{t}" for t in rng.integers(1, 400, size=12))}
            for _ in range(n)]


@pytest.mark.parametrize("device_batch", [False, True], ids=["host_loop", "device_batch"])
def test_a_registered_filter_meets_a_new_dictionary_every_document(device_batch):
    """A percolator parses a query once and keeps its filter; every document
    is a new one-document segment with a dictionary of its own (and, soon, an
    address an earlier one had). The filter expands over THAT dictionary: the
    host loop (HostScorer -> segment_mask) and the batched device launch
    (_filter_mask_matrix -> block_rows) both say what the raw tokens say."""
    from elasticsearch_tpu.percolator import PercolatorRegistry

    svc = MapperService(Settings.from_flat({}))
    svc.put_mapping("doc", MAPPING)
    reg = PercolatorRegistry()
    wanted = {}
    for i in range(100 if device_batch else 12):
        pre, word = f"w{1 + i % 9}", f"w{10 + i}"
        form = i % 3
        if form == 0:
            query = {"filtered": {"query": {"match": {"body": word}},
                                  "filter": {"prefix": {"body": pre}}}}
            wanted[f"q{i}"] = lambda ts, pre=pre, word=word: (
                word in ts and any(t.startswith(pre) for t in ts))
        elif form == 1:
            query = {"filtered": {"query": {"match": {"body": word}},
                                  "filter": {"regexp": {"body": pre + "[0-4].?"}}}}
            wanted[f"q{i}"] = lambda ts, pre=pre, word=word: (
                word in ts and any(re.fullmatch(pre + "[0-4].?", t) for t in ts))
        else:
            query = {"wildcard": {"body": pre + "*7"}}
            wanted[f"q{i}"] = lambda ts, pre=pre: any(
                fnmatch.fnmatchcase(t, pre + "*7") for t in ts)
        reg.register(f"q{i}", {"query": query})
    before = _counters()
    hits = 0
    for doc in _percolator_docs(60):
        tokens = doc["body"].split()
        want = sorted(q for q, test in wanted.items() if test(tokens))
        assert reg.percolate(doc, svc) == want
        hits += len(want)
    assert hits > (50 if device_batch else 20)
    moved = _delta(before)
    assert moved["device_percolate"] == (60 if device_batch else 0)
    assert moved["device_percolate_fallbacks"] == 0
    # nothing of a document's segment stays with the registered filters
    for _body, query in reg._queries.values():
        assert not getattr(getattr(query, "filter", None), "expanded", ())


def test_a_batchs_rows_keep_their_places_whatever_their_source(cached, monkeypatch):
    """One batch whose rows come from everywhere: the first rung's shared
    launch, a longer rung's own, a resident row and no filter at all. Each
    search's answer is its own (with the ladder cut so that `w29` leaves the
    first rung)."""
    ctx, cache = cached
    monkeypatch.setattr(scoring, "MULTITERM_RUNGS", (4, 64, 8192))
    resident = {"constant_score": {"filter": {"prefix": {"body": "w31"}}}}
    for _ in range(3):
        search_shard(ctx, parse_query(resident), 10, use_device=True)
    assert cache.stats()["masks"] == len(ctx.searcher.segments)
    bodies = [{"prefix": {"body": "w2999"}}, {"prefix": {"body": "w29"}},
              {"prefix": {"body": "w2998"}}, resident, {"match_all": {}},
              {"wildcard": {"body": "w299?"}}, {"prefix": {"body": "zebra"}},
              {"prefix": {"body": "w1"}}]
    queries = [parse_query(b) for b in bodies]
    assert scoring.multiterm_rung(multiterm.expand(
        ctx.searcher.segments[0], "body", "prefix", "w29").rows) == 64
    before = _counters()
    out = search_shard_batch(ctx, queries, 10)
    moved = _delta(before)
    assert moved["host"] == 0 and moved["mask_put_bytes"] > 0  # match_all's row
    assert moved["multiterm_searches"] == 6
    for q, td in zip(queries, out):
        host = search_shard(ctx, q, 10, use_device=False)
        assert td.total == host.total and td.hits == host.hits


def test_one_launchs_matrix_is_the_mask_matrix_itself(shard, monkeypatch):
    """Where one build launch built every row of the batch in place, the
    matrix it returned IS the mask matrix (nothing stacked); a row from
    elsewhere, an empty expansion or a second launch, and the rows are
    handed over as a tuple of device rows, which the launch's program
    stacks (scoring._mask_matrix): nothing is stacked on the drainer."""
    from elasticsearch_tpu.search import execute

    ctx, _docs_ = shard
    seg = ctx.searcher.segments[0]
    packed = packed_for(seg)
    handed = []
    real = scoring.build_multiterm_rows

    def watched(*args):
        handed.append(real(*args))
        return handed[-1]

    monkeypatch.setattr(scoring, "build_multiterm_rows", watched)

    def matrix(filters, n_rows):
        del handed[:]
        out = execute._filter_mask_matrix(filters, seg, packed, ctx, n_rows=n_rows)
        want = np.zeros((n_rows, packed.doc_pad), bool)
        for q, f in enumerate(filters):
            want[q, : seg.doc_count] = True if f is None else f.evaluate(seg, ctx)
        want &= np.asarray(packed.live_parent)  # the plane holds the live alone
        if isinstance(out, tuple):
            assert all(row.shape == (packed.doc_pad,) for row in out)
        got = np.asarray(out) & np.asarray(packed.live_parent)
        assert got.shape == want.shape and (got == want).all()
        return out

    three = [PrefixFilter("body", p, cached=False) for p in ("w21", "w22", "w23")]
    assert matrix(three, 4) is handed[0][0][1]
    assert matrix(three + three[:1], 4) is handed[0][0][1]
    assert matrix(three, 3) is not handed[0][0][1]  # a matrix of four rows
    assert matrix(three + [None], 4) is not handed[0][0][1]
    empty = PrefixFilter("body", "zebra", cached=False)
    assert matrix(three[:1] + [empty] + three[1:], 4) is not handed[0][0][1]
    monkeypatch.setattr(scoring, "MULTITERM_RUNGS", (4, 64, 8192))
    wide = PrefixFilter("body", "w2", cached=False)
    out = matrix([three[0], wide, three[1]], 4)  # several launches
    assert len(handed[0]) >= 2 and all(out is not m for _p, m, _r in handed[0])
    assert matrix([wide], 1) is handed[0][0][1]  # a longer rung, alone


def test_the_filters_share_the_expansion_and_their_keys():
    assert PrefixFilter("body", "w1").key() == "prefix:body:w1"
    assert RegexpFilter("body", "w1.*").key() == "regexp:body:w1.*"
    assert WildcardFilter("body", "w1*").key() == "wildcard:body:w1*"
    assert PrefixFilter("body", "w1").cacheable()
    assert not PrefixFilter("body", "w1", cached=False).cacheable()
    assert PrefixFilter("body", "w1") == PrefixFilter("body", "w1")


# ---------------------------------------------------------------------------
# the normal path: REST, coordinator, batcher, drainer
# ---------------------------------------------------------------------------


def _node(tmp, name, shards):
    n = Node(name=name, registry=LocalTransportRegistry(), data_path=str(tmp),
             settings={"index.similarity.default.type": "BM25"})
    n.start([n.local_node.transport_address])
    n.wait_for_master()
    client = n.client()
    client.create_index("lib", {"settings": {
        "number_of_shards": shards, "number_of_replicas": 0,
        "index.similarity.default.type": "BM25"}})
    client.cluster_health(wait_for_status="green")
    for i, d in enumerate(_docs()[:200]):
        client.index("lib", "doc", {"body": d["body"]}, id=str(i))
    client.refresh("lib")
    return n, client


def _stats(client):
    (stats,) = client.nodes_stats()["nodes"].values()
    return stats


@pytest.mark.parametrize("body", [
    {"prefix": {"body": {"value": "w12", "boost": 1.3}}},
    {"wildcard": {"body": "w1*5"}}, {"regexp": {"body": "w1[0-3].*"}}],
    ids=["prefix", "wildcard", "regexp"])
def test_over_rest_the_device_answers_and_nothing_is_put(tmp_path, body):
    n, client = _node(tmp_path, "mt_node", 1)
    try:
        s0 = _stats(client)
        got = client.search("lib", {"query": body, "size": 10})
        s1 = _stats(client)
        serving0, serving1 = s0["search_serving"], s1["search_serving"]
        assert serving1["host"] == serving0["host"]
        assert serving1["device_filtered"] == serving0["device_filtered"] + 1
        launch0, launch1 = serving0["launch"], serving1["launch"]
        assert launch1["mask_put_bytes"] == launch0["mask_put_bytes"]
        assert launch1["multiterm_searches"] == launch0["multiterm_searches"] + 1
        assert launch1["multiterm"] == launch0["multiterm"] + 1
        assert launch1["multiterm_terms"] > launch0["multiterm_terms"]
        assert launch1["multiterm_runs"] > launch0["multiterm_runs"]
        kinds0, kinds1 = (s["search"]["batcher"]["kinds"] for s in (s0, s1))
        assert kinds1["filtered"]["launches"] == kinds0["filtered"]["launches"] + 1
        test = _token_test(body)
        want = [i for i, d in enumerate(_docs()[:200])
                if any(test(t) for t in d["body"].split())]
        assert got["hits"]["total"] == len(want) > 0
        assert [int(h["_id"]) for h in got["hits"]["hits"]] == want[:10]
        (opts,) = next(iter(body.values())).values()
        boost = opts.get("boost", 1.0) if isinstance(opts, dict) else 1.0
        assert {h["_score"] for h in got["hits"]["hits"]} == {float(np.float32(boost))}
        prof = client.search("lib", {"query": body, "profile": True})
        (shard,) = prof["profile"]["shards"]
        assert shard["plan"]["unscored"] and shard["plan"]["outcome"] == "device_filtered"
        fuzzy = client.search("lib", {"query": {"fuzzy": {"body": "w123"}},
                                      "profile": True})
        assert fuzzy["profile"]["shards"][0]["plan"]["fallback_reason"] == "fuzzy_query"
    finally:
        n.close()


@pytest.mark.mesh
def test_the_mesh_declines_a_multi_term_query(tmp_path):
    """Four shards on four virtual devices: a match rides the mesh program, a
    prefix goes the transport path to each shard's own programs, and
    `mesh_fallbacks` says so."""
    n, client = _node(tmp_path, "mt_mesh", 4)
    try:
        ms = n.actions.mesh_serving
        client.search("lib", {"query": {"match": {"body": "w1 w2"}}})
        assert ms.mesh_queries >= 1
        queries, fallbacks = ms.mesh_queries, ms.mesh_fallbacks
        before = scoring.LAUNCHES.snapshot()["multiterm_searches"]
        got = client.search("lib", {"query": {"prefix": {"body": "w1"}}, "size": 10})
        assert ms.mesh_queries == queries and ms.mesh_fallbacks == fallbacks + 1
        assert scoring.LAUNCHES.snapshot()["multiterm_searches"] == before + 4
        want = [i for i, d in enumerate(_docs()[:200])
                if any(t.startswith("w1") for t in d["body"].split())]
        assert got["hits"]["total"] == len(want)
        assert {h["_score"] for h in got["hits"]["hits"]} == {1.0}
    finally:
        n.close()
