"""Head terms as resident rows (ops/device_index.py head_rows, ops/scoring.py).

Counts and values only, on the CPU: a dense launch that adds a head term's
row over documents answers what the same launch answers when it scatters the
term's postings — ids and totals exactly, scores to 1e-6 relative (only the
order in which a document's per-term contributions are added differs). Rows
are disabled BY CONSTRUCTION in the comparison: the same documents packed
with the threshold above every df (HEAD_DF_SHARE 0), never by a switch."""

import functools

import numpy as np
import pytest

from elasticsearch_tpu.common.jaxenv import compile_tag
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index import Engine
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.ops import device_index, scoring
from elasticsearch_tpu.ops.device_index import (
    TF_F32, ensure_blk_freqs, ensure_head_rows, packed_for,
    packed_resident_bytes, packed_tier_bytes)
from elasticsearch_tpu.ops.scoring import (
    GROUP_MUST, GROUP_MUST_NOT, GROUP_SHOULD, HEAD_SLOTS, MODE_BM25,
    MODE_CONST, build_term_batch, score_term_batch)
from elasticsearch_tpu.search import (
    ShardContext, parse_query, search_shard)
from elasticsearch_tpu.search.execute import (
    _assemble_batch, _dense_entries, _ensure_norm_rows, execute_flat_batch,
    finalize_flat, lower_flat)
from elasticsearch_tpu.search.aggregations import reduce_aggs
from elasticsearch_tpu.search.service import (
    execute_query_phase, parse_search_body)
from elasticsearch_tpu.search.similarity import SimilarityService

pytestmark = pytest.mark.serving

N_DOCS = 600  # doc_pad 1024: a term has a row from df 64 on


def _docs():
    for i in range(N_DOCS):
        words = ["common"] * (1 + i % 3)  # every document, tf 1-3
        if i % 2 == 0:
            words.append("half")
        if i % 3 == 0:
            words += ["third", "third"]
        if i % 5 == 0:
            words.append("fifth")  # df 120
        if i % 50 == 0:
            words.append("rare")  # df 12: no row
        words.append(f"w{i % 40}")  # df 15 each: no row
        yield {"body": " ".join(words), "rank": i % 97, "day": i % 30}


def _context(tmp, sim: str, share: int):
    """A shard of _docs() whose segments are packed with HEAD_DF_SHARE =
    `share`: 0 puts the threshold above every df, so no term has a row."""
    settings = Settings.from_flat({"index.similarity.default.type": sim})
    svc = MapperService(settings)
    eng = Engine(str(tmp), svc)
    for i, d in enumerate(_docs()):
        eng.index("doc", str(i), d)
    eng.refresh()
    ctx = ShardContext(eng.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(device_index, "HEAD_DF_SHARE", share)
        for seg in ctx.searcher.segments:
            packed = packed_for(seg)
            assert bool(packed.head_row_of) == bool(share)
    return ctx


@pytest.fixture(scope="module")
def shards(tmp_path_factory):
    """(similarity, rows?) -> ShardContext over the same documents."""
    return {(sim, rows): _context(
        tmp_path_factory.mktemp(f"{sim}{int(rows)}"), sim,
        device_index.HEAD_DF_SHARE if rows else 0)
        for sim in ("BM25", "default") for rows in (True, False)}


@pytest.fixture(autouse=True)
def every_plain_search_overflows(monkeypatch):
    """tb_max 0: a plain search with any block takes the dense program, as a
    search of head terms does at the real size."""
    monkeypatch.setattr(scoring, "launch_flat_sparse", functools.partial(
        scoring.launch_flat_sparse, tb_max=0))


def _flat(ctx, query):
    plan = lower_flat(parse_query(query), ctx)
    assert plan is not None
    (top,) = execute_flat_batch([plan], ctx, 25)
    return top.total, [d for (_s, d) in top.hits], [s for (s, _d) in top.hits], None


def _phase(ctx, body):
    req = parse_search_body(body)
    res = execute_query_phase(ctx, req, use_device=True)
    aggs = reduce_aggs(req.aggs, res.agg_partials) if req.aggs else None
    return (res.total, [d for (_s, d, _v) in res.docs],
            [s for (s, _d, _v) in res.docs], aggs)


RANGE = {"range": {"rank": {"gte": 10, "lt": 70}}}
# name -> (similarity, runner, query or body)
CASES = {
    "simple": ("BM25", _flat, {"match": {"body": "common half rare w7"}}),
    "bool_must_and_must_not": ("BM25", _flat, {"bool": {
        "must": [{"term": {"body": "common"}}, {"term": {"body": "w3"}}],
        "must_not": [{"term": {"body": "third"}}, {"term": {"body": "rare"}}],
        "should": [{"term": {"body": "half"}}]}}),
    "minimum_should_match_with_coord": ("default", _flat, {"bool": {
        "should": [{"term": {"body": t}}
                   for t in ("common", "half", "third", "fifth", "rare")],
        "minimum_should_match": 3}}),
    "match_and_operator": ("BM25", _flat, {"match": {"body": {
        "query": "half third w12", "operator": "and"}}}),
    "tfidf_on_a_normless_field": ("default", _flat, {"bool": {
        "must": [{"term": {"_type": "doc"}}],
        "should": [{"term": {"body": "fifth"}}]}}),
    "filtered": ("BM25", _phase, {"size": 25, "query": {"filtered": {
        "query": {"match": {"body": "common third w5"}}, "filter": RANGE}}}),
    "aggs": ("BM25", _phase, {
        "size": 25, "query": {"match": {"body": "half fifth rare"}},
        "aggs": {"r": {"stats": {"field": "rank"}},
                 "d": {"histogram": {"field": "day", "interval": 7}}}}),
    "sorted": ("default", _phase, {
        "size": 25, "query": {"match": {"body": "common w9"}},
        "sort": [{"rank": "desc"}], "track_scores": True}),
    "function_score_rows": ("BM25", _flat, {"function_score": {
        "query": {"match": {"body": "half rare"}},
        "functions": [{"field_value_factor": {"field": "rank"}}],
        "boost_mode": "sum"}}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_launch_with_head_rows_answers_what_the_scatter_answers(shards, case):
    sim, run, body = CASES[case]
    before = scoring.LAUNCHES.snapshot()
    total, ids, scores, aggs = run(shards[sim, True], body)
    rows_added = scoring.LAUNCHES.snapshot()["head_slots"] - before["head_slots"]
    assert rows_added >= 1, "the case must exercise a head row"
    mid = scoring.LAUNCHES.snapshot()
    p_total, p_ids, p_scores, p_aggs = run(shards[sim, False], body)
    assert scoring.LAUNCHES.snapshot()["head_slots"] == mid["head_slots"]
    assert total == p_total and total > 0
    assert ids == p_ids
    np.testing.assert_allclose(scores, p_scores, rtol=1e-6)
    assert aggs == p_aggs
    # and both are the host scorer's answer
    if run is _flat:
        host = search_shard(shards[sim, True], parse_query(body), 25,
                            use_device=False)
        assert host.total == total
        np.testing.assert_allclose([s for (s, _d) in host.hits], scores,
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# at the launch: constant score, slots, counters
# ---------------------------------------------------------------------------


def _one_segment(shards):
    ctx = shards["BM25", True]
    (seg,) = ctx.searcher.segments
    packed = packed_for(seg)
    _ensure_norm_rows(packed, ["body"])
    return ctx, seg, packed


def _entry(seg, packed, q, term, w, group, mode):
    tid = seg.term_id("body", term)
    b0, b1 = packed.blocks_for_term(tid)
    return (q, b0, b1, w, 0, group, mode, packed.head_row_of.get(tid, -1))


def _launch(packed, entries, Q, n_must=None, msm=None, k=20):
    n_must = np.zeros(Q, np.int64) if n_must is None else n_must
    msm = np.ones(Q, np.int64) if msm is None else msm
    coord = np.ones((Q, 4), np.float32)
    cache = np.linspace(0.25, 2.0, 256, dtype=np.float32)[None, :]
    batch = build_term_batch(entries, Q, n_must, msm, coord, ["body"], cache,
                             nb_pad_row=packed.blk_docs.shape[0] - 1,
                             head_pad_row=packed.head_rows.shape[0] - 1)
    with compile_tag("dense"):
        return batch, score_term_batch(packed, batch, k)


def _assert_same_result(a, b):
    assert np.array_equal(a.total_hits, b.total_hits)
    assert np.array_equal(a.docs, b.docs)
    np.testing.assert_allclose(a.scores, b.scores, rtol=1e-6)


def test_constant_score_clause_adds_its_weight_only_where_the_term_is(shards):
    """A MODE_CONST clause on a head term: `w` where tf > 0 and nothing
    elsewhere, a weight of 0 still matches, and a must_not head clause adds
    its counter and no score."""
    _ctx, seg, packed = _one_segment(shards)
    as_rows = [_entry(seg, packed, 0, "half", 2.5, GROUP_SHOULD, MODE_CONST),
               _entry(seg, packed, 0, "rare", 1.25, GROUP_SHOULD, MODE_BM25),
               _entry(seg, packed, 1, "third", 0.0, GROUP_SHOULD, MODE_CONST),
               _entry(seg, packed, 1, "fifth", 9.0, GROUP_MUST_NOT, MODE_BM25)]
    assert [e[7] >= 0 for e in as_rows] == [True, False, True, True]
    batch, got = _launch(packed, as_rows, 2)
    assert (batch.head_slots, batch.blocks_real) == (3, 1)
    _b, want = _launch(packed, [(*e[:7], -1) for e in as_rows], 2)
    _assert_same_result(got, want)
    # query 1: every third document that is no fifth one, each scoring 0
    assert got.total_hits.tolist()[1] == N_DOCS // 3 - N_DOCS // 15
    assert not got.scores[1][np.isfinite(got.scores[1])].any()
    half_only = got.scores[0][np.isin(got.docs[0] % 50, range(1, 50))]
    assert np.all(half_only == np.float32(2.5))


def test_head_clauses_past_a_querys_slots_fall_back_to_their_blocks(shards):
    """HEAD_SLOTS + 3 head clauses in one query: the first HEAD_SLOTS take
    rows, the rest their block ranges, and the answer is the all-blocks one."""
    _ctx, seg, packed = _one_segment(shards)
    terms = ["common", "half", "third", "fifth"]
    entries = [_entry(seg, packed, 0, terms[i % 4], 0.5 + i, GROUP_SHOULD,
                      MODE_BM25) for i in range(HEAD_SLOTS + 3)]
    entries.append(_entry(seg, packed, 1, "half", 1.0, GROUP_MUST, MODE_BM25))
    assert all(e[7] >= 0 for e in entries)
    n_must = np.array([0, 1])
    batch, got = _launch(packed, entries, 2, n_must=n_must)
    assert batch.head_slots == HEAD_SLOTS + 1
    past = entries[HEAD_SLOTS: HEAD_SLOTS + 3]
    assert batch.blocks_real == sum(b1 - b0 for (_q, b0, b1, *_r) in past)
    assert batch.blocks_as_rows == sum(
        b1 - b0 for (_q, b0, b1, *_r) in entries) - batch.blocks_real
    _b, want = _launch(packed, [(*e[:7], -1) for e in entries], 2,
                       n_must=n_must)
    _assert_same_result(got, want)


def test_counters_count_what_the_launch_did(shards):
    """head_slots / blocks_as_rows / blocks_real / operand_puts /
    posting_bytes of one warmed dense launch, and the rows in the index's
    resident bytes."""
    ctx, seg, packed = _one_segment(shards)
    plans = [lower_flat(parse_query({"match": {"body": t}}), ctx)
             for t in ("common rare", "half third w3")]
    execute_flat_batch(plans, ctx, 10)  # warm: tables put, programs compiled
    finals = [finalize_flat(p, ctx) for p in plans]
    field_idx = _assemble_batch(plans, finals)[1]
    entries = _dense_entries(finals, seg, packed, field_idx)
    rows = [e for e in entries if e[7] >= 0]
    assert len(rows) == 3 and len(entries) == 5  # common | half, third
    before = scoring.LAUNCHES.snapshot()
    execute_flat_batch(plans, ctx, 10)
    d = {key: v - before[key] for key, v in scoring.LAUNCHES.snapshot().items()}
    assert (d["launches_dense"], d["launches_sparse"]) == (1, 0)
    assert d["operand_puts"] == 1  # the packed plane: tri | qplane | head
    assert d["head_slots"] == 3
    assert d["blocks_as_rows"] == sum(b1 - b0 for (_q, b0, b1, *_r) in rows)
    assert d["blocks_real"] == 2  # rare, w3: a block each
    m = d["blocks_launched"]
    assert m == scoring.TAIL_FLOOR and d["blocks_padding"] == m - 2
    itemsize = np.asarray(packed.head_rows).dtype.itemsize
    # the head loop runs to the fullest query: two trips ("half third")
    assert d["posting_bytes"] == (
        m * 128 * 12 + 2 * 2 * packed.doc_pad * (itemsize + 4)
        + 2 * packed.doc_pad * 4)
    # resident: a row a head term, padded up the pow-2 ladder, in the dense tier
    n_rows = packed.head_rows.shape[0]
    assert n_rows >= len(packed.head_row_of) + 1 and n_rows & (n_rows - 1) == 0
    assert not np.asarray(packed.head_rows)[len(packed.head_row_of):].any()
    plane = n_rows * packed.doc_pad * itemsize
    tiers = packed_tier_bytes(packed)
    assert tiers["dense_plane"] == plane + np.asarray(packed.blk_freqs).nbytes
    assert tiers["postings"] + tiers["dense_plane"] == \
        packed_resident_bytes(packed)


def test_rows_hold_each_head_terms_frequencies_by_document(shards):
    _ctx, seg, packed = _one_segment(shards)
    rows = np.asarray(ensure_head_rows(packed))
    assert ensure_head_rows(packed) is packed.head_rows
    for term, want in (("common", lambda i: 1 + i % 3),
                       ("third", lambda i: 2 * (i % 3 == 0)),
                       ("fifth", lambda i: int(i % 5 == 0))):
        row = rows[packed.head_row_of[seg.term_id("body", term)]]
        assert row[:N_DOCS].tolist() == [want(i) for i in range(N_DOCS)]
        assert not row[N_DOCS:].any()
    assert seg.term_id("body", "rare") not in packed.head_row_of
    # df 64 of doc_pad 1024 is the foot: w-terms (df 15) stay postings
    assert all(int(seg.post_offsets[t + 1] - seg.post_offsets[t])
               * device_index.HEAD_DF_SHARE >= packed.doc_pad
               for t in packed.head_row_of)


# ---------------------------------------------------------------------------
# segments that run as before
# ---------------------------------------------------------------------------


def _small_engine(tmp_path, n=240):
    settings = Settings.from_flat({"index.similarity.default.type": "BM25"})
    svc = MapperService(settings)
    eng = Engine(str(tmp_path / "shard"), svc)
    ctx = lambda: ShardContext(  # noqa: E731
        eng.acquire_searcher(), svc,
        SimilarityService(settings, mapper_service=svc))
    for i in range(n):
        eng.index("doc", str(i), {"body": f"common w{i % 9}"
                                  + (" half" if i % 2 else "")})
    eng.refresh()
    return eng, ctx


QUERIES = [{"match": {"body": "common half w3"}},
           {"bool": {"must": [{"term": {"body": "half"}}],
                     "must_not": [{"term": {"body": "w2"}}]}}]


def _assert_device_is_host(ctx):
    for q in QUERIES:
        dev = search_shard(ctx, parse_query(q), 20, use_device=True)
        host = search_shard(ctx, parse_query(q), 20, use_device=False)
        assert dev.total == host.total > 0
        assert [d for (_s, d) in dev.hits] == [d for (_s, d) in host.hits]
        np.testing.assert_allclose([s for (s, _d) in dev.hits],
                                   [s for (s, _d) in host.hits], rtol=1e-5)


def test_a_tf_f32_segment_gets_no_rows_and_answers_as_before(tmp_path):
    eng, ctx = _small_engine(tmp_path)
    c = ctx()
    (seg,) = c.searcher.segments
    seg.post_freqs = seg.post_freqs + np.float32(0.5)  # before the first pack
    seg._device_cache.clear()
    packed = packed_for(seg)
    assert packed.tf_layout == TF_F32 and packed.head_row_of == {}
    before = scoring.LAUNCHES.snapshot()
    _assert_device_is_host(c)
    after = scoring.LAUNCHES.snapshot()
    assert after["launches_dense"] > before["launches_dense"]
    assert after["head_slots"] == before["head_slots"]
    # the same program: one zero row stands where the rows would
    assert np.asarray(packed.head_rows).shape == (1, packed.doc_pad)
    assert not np.asarray(packed.head_rows).any()
    eng.close()


def test_a_base_and_delta_view_builds_each_packs_own_rows(tmp_path):
    """A refresh-frozen increment beside the base segment, then a delete:
    each pack maps its own head terms from its own postings, and a live-mask
    change re-bakes no row (every dense program ends in `& live_parent`)."""
    eng, ctx = _small_engine(tmp_path)
    _assert_device_is_host(ctx())
    for i in range(240, 300):
        eng.index("doc", str(i), {"body": "common late" + (" half" if i % 4 else "")})
    eng.refresh()
    c = ctx()
    base, delta = c.searcher.segments
    _assert_device_is_host(c)
    p_base, p_delta = packed_for(base), packed_for(delta)
    assert base.term_id("body", "late") is None
    assert delta.term_id("body", "late") in p_delta.head_row_of
    assert len(p_base.head_row_of) > len(p_delta.head_row_of) >= 3
    rows_before = p_base.head_rows
    eng.delete("doc", "3")  # a "half" document of the base segment
    eng.refresh()
    c = ctx()
    _assert_device_is_host(c)
    assert packed_for(c.searcher.segments[0]).head_rows is rows_before
    eng.close()


def test_the_f32_plane_and_the_rows_fault_in_together_under_the_breaker(tmp_path):
    eng, ctx = _small_engine(tmp_path)
    (seg,) = ctx().searcher.segments
    packed = packed_for(seg)
    assert packed.blk_freqs is None and packed.head_rows is None
    assert packed.head_row_of  # the map is pack-time arithmetic

    class Breaker:
        def __init__(self):
            self.labels = []

        def add_estimate_and_maybe_break(self, n, label):
            self.labels.append((label, n))

        def release(self, n):
            pass

    b = Breaker()
    _ensure_norm_rows(packed, ["body"], breaker=b)
    assert [label for (label, _n) in b.labels] == ["<dense_freqs>", "<head_rows>"]
    assert b.labels[1][1] == np.asarray(packed.head_rows).nbytes
    assert ensure_blk_freqs(packed) is packed.blk_freqs
    eng.close()
