"""A dis_max of one-field OR queries on the device (search/execute.py
_multi_field_lowering and launch_flat_dismax, ops/scoring.py the dis_max program).

On the CPU, seeded and small: the device's answer to a `multi_match` of type
best_fields and to the equal explicit `dis_max` against the host scorer
(`HostScorer.eval`'s DisMaxQuery branch, the semantics) and against the benchmark's
plain reference (`benchmark/queries/bestfields_terms.py` `expected`: numpy over the
token streams, nothing of the program): totals and ids in order exactly, scores to
1e-5 relative. `most_fields` is a flat sum and rides the plain group; the forms that
stay on the host reach it under a named reason; a mixed collect launches each group
once; and every counter the benchmark reads moves as stated."""

import json

import numpy as np
import pytest

from benchmark.harness import registry
from benchmark.harness.reference import Reference, word
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index import Engine
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.ops import device_index, scoring
from elasticsearch_tpu.search import ShardContext, parse_query, search_shard
from elasticsearch_tpu.search import execute as ex
from elasticsearch_tpu.search.service import SERVING_COUNTERS
from elasticsearch_tpu.search.similarity import SimilarityService
from elasticsearch_tpu.transport.local import LocalTransportRegistry

from .harness import run_as_one_batch

pytestmark = pytest.mark.serving

FIELDS = ["txt", "title", "tag"]
N_DOCS = 600  # doc_pad 1,024; w0 is a head term (in most documents)


def _words(rng, n, vocab):
    return " ".join(word(int(t)) for t in (rng.zipf(1.3, n) - 1) % vocab)


def _documents(seed, n=N_DOCS):
    """Three analyzed fields a document: a text of 5-60 words, a title of 1-4
    and a tag of 2 over a smaller vocabulary; `only_txt` appears in the text
    alone and `rank` is an integer."""
    rng = np.random.default_rng(seed)
    return [{"txt": _words(rng, int(rng.integers(5, 60)), 300)
             + (" onlytxt" if i % 7 == 0 else ""),
             "title": _words(rng, int(rng.integers(1, 5)), 300),
             "tag": _words(rng, 2, 50), "rank": i % 11}
            for i in range(n)]


def _shard(tmp, docs, sim="BM25", refresh_at=()):
    settings = Settings.from_flat({"index.similarity.default.type": sim})
    svc = MapperService(settings)
    eng = Engine(str(tmp), svc)
    for i, d in enumerate(docs):
        eng.index("doc", str(i), d)
        if i in refresh_at:
            eng.refresh()
    eng.refresh()
    sims = SimilarityService(settings, mapper_service=svc)

    def ctx():
        return ShardContext(eng.acquire_searcher(), svc, sims, index_name="dm")

    return eng, ctx


@pytest.fixture(scope="module")
def seeded(tmp_path_factory):
    """One segment and three (a delta each third), the same documents."""
    docs = _documents(46)
    _e1, one = _shard(tmp_path_factory.mktemp("one"), docs)
    _e3, three = _shard(tmp_path_factory.mktemp("three"), docs,
                        refresh_at=(199, 399))
    return {"one": one(), "three": three()}


def _same(dev, host, rtol=1e-5):
    assert dev.total == host.total
    assert [d for _s, d in dev.hits] == [d for _s, d in host.hits]
    np.testing.assert_allclose([s for s, _d in dev.hits],
                               [s for s, _d in host.hits], rtol=rtol)


def _both(ctx, body, k=10, disjuncts=None):
    """(device answer, host answer) of a query the dis_max program serves."""
    query = parse_query(body)
    plan = ex.lower_flat(query, ctx, phrases=True)
    assert plan is not None, ex.lower_fallback_reason(query, ctx)
    assert ex.plan_kind(plan) == "dis_max"
    if disjuncts is not None:
        assert plan.n_disjuncts == disjuncts
    before = scoring.LAUNCHES.snapshot()["dismax_searches"]
    dev = search_shard(ctx, query, k, use_device=True)
    assert scoring.LAUNCHES.snapshot()["dismax_searches"] == before + 1
    return dev, search_shard(ctx, query, k, use_device=False)


def _best_fields(text, fields, tie, **more):
    return {"multi_match": {"query": text, "type": "best_fields",
                            "fields": fields, "tie_breaker": tie, **more}}


TEXTS = ["w3 w17 w0 w120", "w1 w2", "w250 w9 w44 w0 w7 w13", "w5"]


# ---------------------------------------------------------------------------
# device against the host scorer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("segments", ["one", "three"])
@pytest.mark.parametrize("tie", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("fields", [FIELDS[:2], FIELDS], ids=["two", "three"])
def test_best_fields_answers_as_the_host(seeded, segments, tie, fields):
    ctx = seeded[segments]
    for text in TEXTS:
        dev, host = _both(ctx, _best_fields(text, fields, tie),
                          disjuncts=len(fields))
        assert host.total > 0
        _same(dev, host)


@pytest.mark.parametrize("tie", [0.0, 0.5, 1.0])
def test_an_explicit_dis_max_is_the_multi_match_it_spells_out(seeded, tie):
    ctx = seeded["three"]
    spelled = {"dis_max": {"tie_breaker": tie, "queries": [
        {"match": {"txt": "w3 w17 w0"}}, {"match": {"title": "w3 w17 w0"}}]}}
    dev, host = _both(ctx, spelled, disjuncts=2)
    _same(dev, host)
    multi, _host = _both(ctx, _best_fields("w3 w17 w0", ["txt", "title"], tie))
    assert dev.total == multi.total and dev.hits == multi.hits


def test_term_and_match_disjuncts_with_boosts_of_their_own(seeded):
    ctx = seeded["three"]
    body = {"dis_max": {"tie_breaker": 0.3, "boost": 1.7, "queries": [
        {"match": {"txt": {"query": "w5 w9 w0", "boost": 0.6}}},
        {"term": {"title": {"value": "w9", "boost": 2.5}}},
        {"term": {"tag": "w5"}},
        {"match": {"title": {"query": "w1 w0", "minimum_should_match": 1}}}]}}
    dev, host = _both(ctx, body, disjuncts=4)
    _same(dev, host)


@pytest.mark.parametrize("fields", [["txt^0.7", "title^2.5"],
                                    ["txt^3", "title", "tag^0.25"]],
                         ids=["two", "three"])
def test_field_boosts_and_the_querys_own_fold_as_the_host_folds_them(
        seeded, fields):
    for text in TEXTS[:3]:
        dev, host = _both(seeded["one"],
                          _best_fields(text, fields, 0.4, boost=1.3))
        _same(dev, host)
    # the folded boost is the clause's, the plan's own stays 1
    plan = ex.lower_flat(parse_query(_best_fields("w1", fields, 0.4, boost=1.3)),
                         seeded["one"], phrases=True)
    assert plan.boost == 1.0
    assert [c.boost for c in plan.clauses][:2] == \
        [1.3 * float(f.partition("^")[2] or 1.0) for f in fields[:2]]


def test_a_term_absent_from_one_field(seeded):
    """`onlytxt` is in no title: that disjunct's sum is zero wherever the
    other term is missing too, and the document still matches by the text."""
    ctx = seeded["three"]
    dev, host = _both(ctx, _best_fields("onlytxt w44", ["txt", "title"], 0.5))
    _same(dev, host)
    assert host.total >= N_DOCS // 7
    # a term NO document holds in any field: nothing matches, nothing fails
    dev, host = _both(ctx, _best_fields("nosuchword", ["txt", "title"], 0.5))
    assert dev.total == host.total == 0 and dev.hits == []


def test_a_head_term_rides_the_head_rows(seeded):
    """w0 is in most documents: the segment keeps a row of it for each field
    that holds it so often, and the launch adds the row under the disjunct's
    accumulator instead of scattering its blocks."""
    ctx = seeded["one"]
    before = scoring.LAUNCHES.snapshot()
    dev, host = _both(ctx, _best_fields("w0 w17", ["txt", "title"], 0.5))
    after = scoring.LAUNCHES.snapshot()
    assert after["head_slots"] > before["head_slots"]
    _same(dev, host)


def test_deletes_and_a_delta_segment(tmp_path):
    docs = _documents(7, 300)
    eng, ctx = _shard(tmp_path, docs[:200])
    body = _best_fields("w3 w0 w17", ["txt", "title"], 0.5)
    first = _both(ctx(), body)
    _same(*first)
    for i in range(0, 200, 3):
        eng.delete("doc", str(i))
    for i, d in enumerate(docs[200:], 200):
        eng.index("doc", str(i), d)
    eng.refresh()
    dev, host = _both(ctx(), body, k=25)
    _same(dev, host)
    assert host.total != first[1].total
    assert not {d for _s, d in dev.hits} & set(range(0, 200, 3))
    eng.close()


def test_a_batch_of_mixed_disjunct_counts_shares_launches(seeded):
    """Two, three and four disjuncts in one group: one launch a segment at
    the widest member's count, the narrower members' last rows empty."""
    ctx = seeded["three"]
    bodies = [
        _best_fields("w3 w17", ["txt", "title"], 0.5),
        _best_fields("w0 w9 w120", FIELDS, 0.2),
        {"dis_max": {"tie_breaker": 1.0, "queries": [
            {"match": {"txt": "w1"}}, {"match": {"title": "w1 w2"}},
            {"term": {"tag": "w1"}}, {"term": {"txt": "w2"}}]}},
    ]
    queries = [parse_query(b) for b in bodies]
    plans = [ex.lower_flat(q, ctx, phrases=True) for q in queries]
    assert [p.n_disjuncts for p in plans] == [2, 3, 4]
    before = scoring.LAUNCHES.snapshot()
    got = ex.execute_flat_batch(plans, ctx, 10)
    after = scoring.LAUNCHES.snapshot()
    assert after["dismax"] - before["dismax"] == len(ctx.searcher.segments)
    assert after["dismax_searches"] - before["dismax_searches"] == 3
    assert after["dismax_disjuncts"] - before["dismax_disjuncts"] == 9
    for q, td in zip(queries, got):
        _same(td, search_shard(ctx, q, 10, use_device=False))


@pytest.mark.parametrize("n_plans, width", [(1, 1), (2, 4), (4, 4), (6, 4)])
def test_the_query_count_rides_the_groups_ladder(seeded, monkeypatch, n_plans,
                                                 width):
    """One plan launches alone, two to four at a width of four; a larger
    group launches four at a time (GROUP_KINDS' width)."""
    ctx = seeded["one"]
    queries = [parse_query(_best_fields(f"w{i} w{2 * i + 1}", ["txt", "title"],
                                        0.5)) for i in range(n_plans)]
    plans = [ex.lower_flat(q, ctx, phrases=True) for q in queries]
    keys = []
    real = scoring._get_dismax_compiled
    monkeypatch.setattr(scoring, "_get_dismax_compiled",
                        lambda *key: keys.append(key) or real(*key))
    blocks = scoring.LAUNCHES.snapshot()["dismax_blocks"]
    got = ex.execute_flat_batch(plans, ctx, 10)
    assert [key[:2] for key in keys] == \
        [(width, 2)] * (1 if n_plans <= 4 else 2)
    # the triples' rung is the width's too, whatever the plans' blocks sum to
    assert scoring.LAUNCHES.snapshot()["dismax_blocks"] - blocks == \
        len(keys) * width * scoring.TAIL_FLOOR
    for q, td in zip(queries, got):
        _same(td, search_shard(ctx, q, 10, use_device=False))


def test_a_plan_of_many_blocks_launches_alone_at_its_own_rung(seeded,
                                                             monkeypatch):
    """With the floor at 2 blocks a plan: plans of at most 2 tail blocks
    launch together at 2 a plan, one of more alone at the rung of its own
    blocks, so no program's shape follows what a batch happens to hold."""
    ctx = seeded["one"]
    monkeypatch.setattr(scoring, "TAIL_FLOOR", 2)
    (seg,) = ctx.searcher.segments
    from elasticsearch_tpu.ops.device_index import packed_for

    packed = packed_for(seg)

    def tail_blocks(words, fields=("txt", "title")):
        n = 0
        for f in fields:
            for w in words.split():
                tid = seg.term_id(f, w)
                if tid is not None and tid not in packed.head_row_of:
                    b0, b1 = packed.blocks_for_term(tid)
                    n += b1 - b0
        return n

    small = ["w250 w0", "w120 w0", "w299 w0"]
    big = "w5 w7 w9 w11 w13 w17"
    assert all(tail_blocks(t) <= 2 for t in small) and tail_blocks(big) > 2
    queries = [parse_query(_best_fields(t, ["txt", "title"], 0.5))
               for t in small[:2] + [big] + small[2:]]
    plans = [ex.lower_flat(q, ctx, phrases=True) for q in queries]
    keys = []
    real = scoring._get_dismax_compiled
    monkeypatch.setattr(scoring, "_get_dismax_compiled",
                        lambda *key: keys.append(key) or real(*key))
    before = scoring.LAUNCHES.snapshot()
    got = ex.execute_flat_batch(plans, ctx, 10)
    after = scoring.LAUNCHES.snapshot()
    assert [key[:2] for key in keys] == [(4, 2), (1, 2)]
    assert after["dismax_blocks"] - before["dismax_blocks"] == \
        4 * 2 + device_index._pow2_bucket(tail_blocks(big), 2)
    for q, td in zip(queries, got):
        _same(td, search_shard(ctx, q, 10, use_device=False))


def test_the_programs_own_reckoning_of_its_bytes(seeded):
    """`dismax_bytes` is the formula the benchmark keeps beside its reference,
    and `dismax_pad_blocks` the triples' rung past the blocks named."""
    ctx = seeded["one"]
    fam = registry.module("queries", "bestfields_terms")
    (seg,) = ctx.searcher.segments
    before = scoring.LAUNCHES.snapshot()
    _both(ctx, _best_fields("w250 w0 w120", ["txt", "title"], 0.5))
    after = scoring.LAUNCHES.snapshot()
    delta = {k: after[k] - before[k] for k in after}
    from elasticsearch_tpu.ops.device_index import packed_for

    packed = packed_for(seg)
    assert delta["dismax"] == 1 and delta["dismax_disjuncts"] == 2
    assert delta["dismax_blocks"] == scoring.TAIL_FLOOR
    assert delta["dismax_pad_blocks"] == \
        scoring.TAIL_FLOOR - delta["blocks_real"] > 0
    # one plan of two disjuncts: two accumulator rows; w0 alone has head rows
    assert delta["dismax_bytes"] == fam.dismax_launch_bytes(
        triples=scoring.TAIL_FLOOR, rows=2, head_trips=1,
        doc_pad=packed.doc_pad, queries=1,
        head_row_itemsize=packed.head_rows.dtype.itemsize)
    assert delta["dismax_bytes"] == delta["posting_bytes"] + packed.doc_pad * 4
    assert delta["operand_puts"] == 1  # the one packed plane, ties aboard


# ---------------------------------------------------------------------------
# device against the benchmark's plain reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def titled(tmp_path_factory):
    bench = registry.benchmark()
    config = registry.config(bench, "beir-nq-1shard")
    gen = registry.module("corpora", config["corpus"]["generator"])
    params = dict(config["corpus"]["params"], vocabulary=400, mean_length=30)
    corpus = gen.generate(params, 46, 500)
    docs = [json.loads(s) for s in corpus.sources(0, 500)]
    _eng, ctx = _shard(tmp_path_factory.mktemp("titled"), docs)
    sim = config["similarity"]
    return corpus, Reference(corpus, sim["k1"], sim["b"]), ctx()


@pytest.mark.parametrize("tie", [0.0, 0.5, 1.0])
def test_device_answers_as_the_plain_reference(titled, tie):
    corpus, ref, ctx = titled
    fam = registry.module("queries", "bestfields_terms")
    params = dict(registry.mix("bestfields")["families"][0]["params"],
                  tie_breaker=tie)
    plans = fam.plan(params, np.random.default_rng(5), 12)
    for q in fam.build(params, ref, plans):
        assert q["body"]["query"]["multi_match"]["tie_breaker"] == tie
        dev, host = _both(ctx, q["body"]["query"], disjuncts=2)
        _same(dev, host)
        scores, matched = fam.expected(ref, q)
        total, ranked = ref.top(scores, matched, 10)
        assert dev.total == total
        assert [d for _s, d in dev.hits] == [int(d) for d in ranked[:10]]
        np.testing.assert_allclose([s for s, _d in dev.hits],
                                   scores[ranked[:10]], rtol=1e-5)


# ---------------------------------------------------------------------------
# what lowers as a plain plan, and what stays on the host
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("segments", ["one", "three"])
def test_most_fields_is_a_flat_sum_and_rides_the_plain_group(seeded, segments):
    ctx = seeded[segments]
    for fields in (["txt", "title"], ["txt^0.5", "title^2", "tag"]):
        query = parse_query({"multi_match": {
            "query": "w3 w17 w0 w120", "type": "most_fields", "fields": fields,
            "boost": 1.5}})
        plan = ex.lower_flat(query, ctx)  # no phrases=True needed: it is plain
        assert ex.plan_kind(plan) == "plain" and plan.n_disjuncts == 0
        assert len(plan.clauses) == 4 * len(fields)
        _same(search_shard(ctx, query, 10, use_device=True),
              search_shard(ctx, query, 10, use_device=False))
    # under a filter the flat sum lowers too: it is a plain sub plan
    filtered = parse_query({"filtered": {
        "query": {"multi_match": {"query": "w3 w0", "type": "most_fields",
                                  "fields": ["txt", "title"]}},
        "filter": {"range": {"rank": {"gte": 4}}}}})
    assert ex.plan_kind(ex.lower_flat(filtered, ctx)) == "filtered"
    _same(search_shard(ctx, filtered, 10, use_device=True),
          search_shard(ctx, filtered, 10, use_device=False))


def test_one_disjunct_is_the_plain_plan_it_is(seeded):
    ctx = seeded["one"]
    for body in (_best_fields("w3 w17", ["txt"], 0.5),
                 # the title analyzes to nothing the index holds... and one
                 # sub-query alone holds a term at all
                 {"dis_max": {"tie_breaker": 0.7, "queries": [
                     {"match": {"txt": "w3 w17"}}, {"match": {"title": ""}}]}}):
        query = parse_query(body)
        plan = ex.lower_flat(query, ctx)
        assert ex.plan_kind(plan) == "plain" and plan.n_disjuncts == 0
        _same(search_shard(ctx, query, 10, use_device=True),
              search_shard(ctx, query, 10, use_device=False))
    empty = ex.lower_flat(parse_query(_best_fields("", ["txt", "title"], 0.5)),
                          ctx)
    assert empty is not None and empty.clauses == []


STAYS = [
    ({"multi_match": {"query": "w1 w2", "type": "phrase",
                      "fields": ["txt", "title"]}}, "multi_match_type"),
    ({"multi_match": {"query": "w1 w2", "type": "phrase_prefix",
                      "fields": ["txt", "title"]}}, "multi_match_type"),
    ({"multi_match": {"query": "w1 w2", "type": "cross_fields",
                      "fields": ["txt", "title"]}}, "multi_match_type"),
    ({"multi_match": {"query": "w1 w2", "operator": "and",
                      "fields": ["txt", "title"]}}, "dismax_subquery"),
    ({"multi_match": {"query": "w1 w2 w3", "minimum_should_match": 2,
                      "fields": ["txt", "title"]}}, "dismax_subquery"),
    ({"dis_max": {"queries": [
        {"match": {"txt": "w1"}},
        {"bool": {"should": [{"term": {"title": "w1"}}]}}]}},
     "dismax_subquery"),
    ({"dis_max": {"queries": [
        {"match": {"txt": "w1"}},
        {"match": {"title": {"query": "w1", "fuzziness": 1}}}]}},
     "dismax_subquery"),
    ({"dis_max": {"queries": [
        {"match": {"txt": "w1"}}, {"term": {"rank": 3}}]}}, "dismax_subquery"),
    ({"dis_max": {"queries": [
        {"match": {"txt": "w1"}},
        {"match": {"title": {"query": "w1", "boost": -1.0}}}]}},
     "dismax_subquery"),
    ({"dis_max": {"queries": [{"term": {f: "w1"}} for f in
                              ("txt", "title", "tag", "txt", "title")]}},
     "dismax_disjuncts"),
    ({"filtered": {"query": _best_fields("w1 w2", ["txt", "title"], 0.5),
                   "filter": {"range": {"rank": {"gte": 4}}}}}, "dismax_tail"),
    ({"function_score": {"query": _best_fields("w1 w2", ["txt", "title"], 0.5),
                         "boost_factor": 2.0}}, "dismax_tail"),
    ({"bool": {"must": [_best_fields("w1 w2", ["txt", "title"], 0.5)]}},
     "non_term_subclause"),
]


@pytest.mark.parametrize("body, reason", STAYS,
                         ids=[f"{i}-{r}" for i, (_b, r) in enumerate(STAYS)])
def test_the_forms_that_stay_on_the_host(seeded, body, reason):
    ctx = seeded["three"]
    query = parse_query(body)
    assert ex.lower_flat(query, ctx, phrases=True) is None
    assert ex.lower_fallback_reason(query, ctx) == reason
    host_before = scoring.LAUNCHES.snapshot()["dismax"]
    _same(search_shard(ctx, query, 10, use_device=True),
          search_shard(ctx, query, 10, use_device=False))
    assert scoring.LAUNCHES.snapshot()["dismax"] == host_before


@pytest.mark.parametrize("mtype", ["best_fields", "most_fields"])
def test_under_tf_idf_every_disjunct_takes_a_coord_and_stays_on_the_host(
        tmp_path, mtype):
    _eng, ctx = _shard(tmp_path, _documents(3, 120), sim="default")
    query = parse_query({"multi_match": {"query": "w3 w0 w17", "type": mtype,
                                         "fields": ["txt", "title"],
                                         "tie_breaker": 0.5}})
    assert ex.lower_flat(query, ctx(), phrases=True) is None
    assert ex.lower_fallback_reason(query, ctx()) == "dismax_similarity"


def test_a_field_of_another_similarity_stays_on_the_host(seeded, monkeypatch):
    from elasticsearch_tpu.search.similarity import TFIDFSimilarity

    ctx = seeded["one"]
    query = parse_query(_best_fields("w3 w0", ["txt", "title"], 0.5))
    assert ex.lower_flat(query, ctx, phrases=True) is not None
    default = ctx.similarity_service.for_field
    monkeypatch.setattr(
        ctx.similarity_service, "for_field",
        lambda f: TFIDFSimilarity() if f == "title" else default(f))
    assert ex.lower_flat(query, ctx, phrases=True) is None
    assert ex.lower_fallback_reason(query, ctx) == "dismax_similarity"


def test_only_the_tail_less_consumer_is_given_a_dis_max_plan(seeded):
    """lower_flat(phrases=False) is what the sorted, aggregated and mesh
    callers use: a dis_max plan carries no tail, so they are declined it and
    the host answers (the profile then reads `features:...`)."""
    ctx = seeded["one"]
    query = parse_query(_best_fields("w1 w2", ["txt", "title"], 0.5))
    assert ex.lower_flat(query, ctx) is None
    assert ex.lower_flat(query, ctx, phrases=True).n_disjuncts == 2
    profile = ex.plan_profile(ex.lower_flat(query, ctx, phrases=True), query)
    assert profile["dis_max"] == {"disjuncts": 2, "tie_breaker": 0.5}
    assert [c["disjunct"] for c in profile["clauses"]] == [0, 0, 1, 1]


# ---------------------------------------------------------------------------
# through the batcher and over REST
# ---------------------------------------------------------------------------


def test_a_mixed_collect_launches_each_group_once(seeded):
    """A plain search, two dis_max searches and a filtered one in ONE batch:
    the plain group and the dis_max group launch once each, and every answer
    is the one the search gets alone."""
    from elasticsearch_tpu.search.service import (execute_query_phase,
                                                  parse_search_body)

    ctx = seeded["three"]
    bodies = [
        {"query": {"match": {"txt": "w3 w17"}}, "size": 5},
        {"query": _best_fields("w3 w17 w0", ["txt", "title"], 0.5), "size": 5},
        {"query": {"dis_max": {"tie_breaker": 0.2, "queries": [
            {"match": {"txt": "w9"}}, {"term": {"tag": "w9"}}]}}, "size": 5},
        {"query": {"filtered": {"query": {"match": {"txt": "w1"}},
                                "filter": {"range": {"rank": {"gte": 4}}}}},
         "size": 5},
    ]
    alone = [execute_query_phase(ctx, parse_search_body(b)) for b in bodies]
    host0, sparse0 = SERVING_COUNTERS["host"], SERVING_COUNTERS["device_sparse"]
    before = scoring.LAUNCHES.snapshot()
    got, stats = run_as_one_batch(ctx, bodies)
    after = scoring.LAUNCHES.snapshot()
    assert stats["kinds"]["plain"] == {"launches": 1, "coalesced": 1}
    assert stats["kinds"]["dis_max"] == {"launches": 1, "coalesced": 2}
    assert stats["kinds"]["filtered"] == {"launches": 1, "coalesced": 1}
    assert after["dismax"] - before["dismax"] == len(ctx.searcher.segments)
    assert after["dismax_searches"] - before["dismax_searches"] == 2
    assert SERVING_COUNTERS["host"] == host0
    assert SERVING_COUNTERS["device_sparse"] == sparse0 + 3  # plain + 2 dis_max
    for res, ref in zip(got, alone):
        assert not isinstance(res, Exception), res
        assert res.total == ref.total
        assert [(round(s, 5), d) for s, d, _v in res.docs] == \
            [(round(s, 5), d) for s, d, _v in ref.docs]


def _launch_stats(client):
    (stats,) = client.nodes_stats()["nodes"].values()
    return stats


def test_best_fields_over_rest_reaches_the_dis_max_program(tmp_path):
    n = Node(name="dismax_node", registry=LocalTransportRegistry(),
             data_path=str(tmp_path),
             settings={"index.similarity.default.type": "BM25"})
    n.start([n.local_node.transport_address])
    n.wait_for_master()
    client = n.client()
    try:
        client.create_index("lib", {"settings": {
            "number_of_shards": 1, "number_of_replicas": 0,
            "index.similarity.default.type": "BM25"}})
        client.cluster_health(wait_for_status="green")
        for i, d in enumerate(_documents(9, 150)):
            client.index("lib", "doc", d, id=str(i))
        client.refresh("lib")
        s0 = _launch_stats(client)
        multi = _best_fields("w3 w0 w17", ["txt", "title"], 0.5)
        spelled = {"dis_max": {"tie_breaker": 0.5, "queries": [
            {"match": {"txt": "w3 w0 w17"}}, {"match": {"title": "w3 w0 w17"}}]}}
        got = client.search("lib", {"query": multi, "size": 10})
        same = client.search("lib", {"query": spelled, "size": 10})
        s1 = _launch_stats(client)
        launch0, launch1 = (s["search_serving"]["launch"] for s in (s0, s1))
        assert launch1["dismax_searches"] - launch0["dismax_searches"] == 2
        assert launch1["dismax"] - launch0["dismax"] == 2
        assert launch1["dismax_disjuncts"] - launch0["dismax_disjuncts"] == 4
        assert launch1["dismax_bytes"] > launch0["dismax_bytes"]
        assert s1["search_serving"]["device_sparse"] \
            - s0["search_serving"]["device_sparse"] == 2
        assert s1["search_serving"]["host"] == s0["search_serving"]["host"]
        kinds0, kinds1 = (s["search"]["batcher"]["kinds"] for s in (s0, s1))
        for name in ("launches", "coalesced"):
            assert kinds1["dis_max"][name] - kinds0["dis_max"][name] == 2
        assert "dis_max" in s1["device"]["compile"]["by_family"]
        assert got["hits"]["hits"] == same["hits"]["hits"]
        # the host's answer: the same query where only the host can go
        host = client.search("lib", {"query": {"bool": {"must": [multi]}},
                                     "size": 10})
        assert _launch_stats(client)["search_serving"]["host"] \
            == s1["search_serving"]["host"] + 1
        assert got["hits"]["total"] == host["hits"]["total"] > 0
        assert [h["_id"] for h in got["hits"]["hits"]] == \
            [h["_id"] for h in host["hits"]["hits"]]
        np.testing.assert_allclose([h["_score"] for h in got["hits"]["hits"]],
                                   [h["_score"] for h in host["hits"]["hits"]],
                                   rtol=1e-5)
        # a profiled request names the plan, a declined one its reason
        prof = client.search("lib", {"query": multi, "size": 10,
                                     "profile": True})
        (shard,) = prof["profile"]["shards"]
        assert shard["plan"]["dis_max"] == {"disjuncts": 2, "tie_breaker": 0.5}
        assert shard["plan"]["outcome"] == "device_sparse"
        cross = client.search("lib", {"query": {"multi_match": {
            "query": "w3 w0", "type": "cross_fields",
            "fields": ["txt", "title"]}}, "profile": True})
        assert cross["profile"]["shards"][0]["plan"]["fallback_reason"] == \
            "multi_match_type"
        # with a sort the plan has no tail to carry it: the host sorts
        host_before = _launch_stats(client)["search_serving"]["host"]
        client.search("lib", {"query": multi, "sort": [{"rank": "asc"}]})
        assert _launch_stats(client)["search_serving"]["host"] == host_before + 1
    finally:
        n.close()
