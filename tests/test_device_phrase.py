"""Exact phrases on the device (ops/device_index.py positions plane, ops/scoring.py
phrase program, search/execute.py launch_flat_phrase).

On the CPU, seeded and small: the device's answer to a `match_phrase` against the
host scorer (`HostScorer._eval_phrase`, the semantics) and against the benchmark's
plain reference (`benchmark/queries/phrase_terms.py` `expected`: numpy over the
token stream, nothing of the program): totals and ids in order exactly, scores to
1e-6 relative. A launch names, of every term's block rows, those that hold a
document of the phrase's rarest term: its answer is the whole lists' bit for bit,
the rung follows the rows it names, and the counters say what it left out. A
launch whose plans are all pairs rides a line of two slots, one merge: its answer
is the line of four's bit for bit, alone on every rung, four in a launch, of one
term twice, over a deleted document's marker and across block rows. The
forms that stay on the host reach it under a named reason, the plane is faulted
in by the first phrase and not before, and every counter the benchmark reads
moves as stated."""

import json
import threading

import numpy as np
import pytest

from benchmark.harness import registry
from benchmark.harness.reference import Reference, word
from elasticsearch_tpu.common.breaker import CircuitBreakerService
from elasticsearch_tpu.common.deadline import NO_DEADLINE
from elasticsearch_tpu.common.errors import CircuitBreakingError
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index import Engine
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.ops.device_index import (
    POS_DEAD_CODE, POS_SENTINEL, PositionsPlane, docs_below, ensure_positions,
    packed_for, packed_tier_bytes, positions_mark_base)
from elasticsearch_tpu.search import ShardContext, parse_query, search_shard
from elasticsearch_tpu.search.batcher import DeviceBatcher
from elasticsearch_tpu.search.execute import (
    execute_flat_batch, lower_fallback_reason, lower_flat, plan_profile,
    search_shard_batch)
from elasticsearch_tpu.search.similarity import SimilarityService
from elasticsearch_tpu.transport.local import LocalTransportRegistry

pytestmark = pytest.mark.serving

CORPUS = {"vocabulary": 400, "mean_length": 30, "min_length": 5, "max_length": 90,
          "zipf_a": 1.25, "text_field": "body",
          "collocations": {"count": 40, "lengths": {"2": 0.5, "3": 0.3, "4": 0.2},
                           "df_share": [0.01, 0.2]}}
N_DOCS = 500  # doc_pad 512


def _shard(tmp, docs, sim="BM25", refresh_at=(), breakers=None):
    """A shard over `docs` (a `_source` each), a segment a refresh."""
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
        return ShardContext(eng.acquire_searcher(), svc, sims, index_name="idx",
                            breakers=breakers)

    return eng, ctx


def _phrase(text, field="body", **more):
    return parse_query({"match_phrase": {field: {"query": text, **more}}})


def _same(dev, host, rtol=1e-6):
    assert dev.total == host.total
    assert [d for _s, d in dev.hits] == [d for _s, d in host.hits]
    np.testing.assert_allclose([s for s, _d in dev.hits],
                               [s for s, _d in host.hits], rtol=rtol)


def _both(ctx, query, k=10):
    assert lower_flat(query, ctx, phrases=True).phrase is not None
    before = scoring.LAUNCHES.snapshot()["phrase_searches"]
    dev = search_shard(ctx, query, k, use_device=True)
    assert scoring.LAUNCHES.snapshot()["phrase_searches"] == before + 1
    return dev, search_shard(ctx, query, k, use_device=False)


def _line_of_four(entries):
    """scoring.phrase_slots as it was when every launch rode four slots: the
    program every two-slot launch is held to, on the same plans."""
    return scoring.PHRASE_SLOTS


LINES = ["own", "four"]  # a launch's own line, and the line of four for all


def _ride(monkeypatch, line):
    if line == "four":
        monkeypatch.setattr(scoring, "phrase_slots", _line_of_four)


def _same_on_the_line_of_four(monkeypatch, ctx, queries, got, k=10):
    """The plans of `queries` launched again with every line four slots long:
    no launch of two slots, and totals, documents and float32 scores are
    `got`'s bit for bit."""
    with monkeypatch.context() as m:
        m.setattr(scoring, "phrase_slots", _line_of_four)
        before = scoring.LAUNCHES.snapshot()
        four = search_shard_batch(ctx, queries, k)
        after = scoring.LAUNCHES.snapshot()
    assert after["phrase"] > before["phrase"]
    assert after["phrase_pair_launches"] == before["phrase_pair_launches"]
    for td, want in zip(four, got):
        assert td.total == want.total
        assert td.hits == want.hits


def _slots_of(texts):
    """The slots of the line a launch of these phrases rides."""
    return 2 if all(len(t.split()) == 2 for t in texts) else scoring.PHRASE_SLOTS


def _named_rows(seg, plane, words, field="body"):
    """The block rows a launch of the phrase `words` names, a list a term, by
    the rule and by hand: the lead is the term of the fewest postings, and a
    term's row stays where a document of the lead lies between the documents
    of the row's first and last key, both included."""
    docs, _freqs = seg.postings(
        field, min(words, key=lambda w: seg.doc_freq(field, w)))
    named = []
    for w in words:
        rows = []
        for r in range(*plane.blocks_for_term(seg.term_id(field, w))):
            keys = plane.host_keys[r]
            held = keys[keys != POS_SENTINEL] >> plane.pos_bits
            if ((docs >= held[0]) & (docs <= held[-1])).any():
                rows.append(r)
        named.append(rows)
    return named


def _whole_rows(seg, plane, words, field="body"):
    """The block rows of the whole lists of the phrase `words`."""
    return sum(b1 - b0 for b0, b1 in (
        plane.blocks_for_term(seg.term_id(field, w)) for w in words))


def _every_row(plane, tid, _below):
    """PositionsPlane.rows_holding as it would be with no lead term: every
    block row of the term (the launch every pruned one is held to)."""
    b0, b1 = plane.blocks_for_term(tid)
    return np.arange(b0, b1, dtype=np.int32)


# ---------------------------------------------------------------------------
# a seeded corpus with planted collocations: host scorer and plain reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def planted(tmp_path_factory):
    gen = registry.module("corpora", "zipf_collocations")
    corpus = gen.generate(CORPUS, 39, N_DOCS)
    docs = [json.loads(s) for s in corpus.sources(0, N_DOCS)]
    _eng, ctx = _shard(tmp_path_factory.mktemp("planted"), docs)
    return corpus, Reference(corpus, 1.2, 0.75), ctx()


def _pick(planted, n_terms):
    """The corpus' collocations of `n_terms` terms that some document holds."""
    corpus, ref, _ctx = planted
    fam = registry.module("queries", "phrase_terms")
    out = [t for t in corpus.collocations
           if len(t) == n_terms and fam.phrase_freq(ref, t).any()]
    assert out
    return out[:6]


@pytest.mark.parametrize("n_terms,line", [(2, "own"), (2, "four"), (3, "own"),
                                          (4, "own")])
def test_device_answers_as_the_host_and_the_plain_reference(
        planted, monkeypatch, n_terms, line):
    corpus, ref, ctx = planted
    fam = registry.module("queries", "phrase_terms")
    _ride(monkeypatch, line)
    for terms in _pick(planted, n_terms):
        query = _phrase(" ".join(word(t) for t in terms))
        pairs = scoring.LAUNCHES.snapshot()["phrase_pair_launches"]
        dev, host = _both(ctx, query)
        _same(dev, host)
        # a pair alone rides two slots, and answers as on the line of four
        assert scoring.LAUNCHES.snapshot()["phrase_pair_launches"] - pairs == \
            int(n_terms == 2 and line == "own")
        if n_terms == 2 and line == "own":
            _same_on_the_line_of_four(monkeypatch, ctx, [query], [dev])
        scores, matched = fam.expected(ref, {"terms": list(terms)})
        total, ranked = ref.top(scores, matched, 10)
        assert dev.total == total > 0
        got = np.array([d for _s, d in dev.hits])
        want = ranked[:10]
        # ids in the reference's order wherever its scores are apart
        apart = np.abs(np.diff(scores[want])) > 1e-6 * scores[want][:-1]
        clear = np.concatenate([[True], apart]) & np.concatenate([apart, [True]])
        assert (got[clear] == want[clear]).all()
        np.testing.assert_allclose([s for s, _d in dev.hits], scores[want],
                                   rtol=1e-6)
        np.testing.assert_allclose([s for s, _d in dev.hits], scores[got],
                                   rtol=1e-6)


@pytest.mark.parametrize("line", LINES)
def test_head_term_bigrams_answer_as_the_host(planted, monkeypatch, line):
    """The nine ordered pairs of the three top terms, a term twice among
    them: on the line of two slots and on the line of four."""
    corpus, ref, ctx = planted
    head = [int(t) for t in ref.by_df[:3]]
    _ride(monkeypatch, line)
    for a in head:
        for b in head:
            query = _phrase(f"{word(a)} {word(b)}")
            pairs = scoring.LAUNCHES.snapshot()["phrase_pair_launches"]
            dev, host = _both(ctx, query)
            _same(dev, host)
            assert scoring.LAUNCHES.snapshot()["phrase_pair_launches"] - pairs \
                == int(line == "own")
            if line == "own":
                _same_on_the_line_of_four(monkeypatch, ctx, [query], [dev])


@pytest.mark.parametrize("mix", ["pairs", "mixed"])
@pytest.mark.parametrize("n_plans", [1, 4, 5])
def test_a_batch_launches_at_both_widths(planted, monkeypatch, n_plans, mix):
    """1 plan launches alone, 4 together, 5 as 4 and 1 (_GROUP_WIDTH): each
    plan's answer is what it is alone. A launch of pairs alone rides a line of
    two slots, four of them as one; a pair beside a phrase of three or four
    rides their line of four, and the pair left over after such a launch its
    own line of two."""
    corpus, ref, ctx = planted
    lengths = (2, 2, 2, 2, 2) if mix == "pairs" else (3, 2, 4, 3, 2)
    picked = {n: [" ".join(word(t) for t in terms) for terms in _pick(planted, n)]
              for n in set(lengths)}
    texts = [picked[n].pop(0) for n in lengths[:n_plans]]
    queries = [_phrase(t) for t in texts]
    before = scoring.LAUNCHES.snapshot()
    got = search_shard_batch(ctx, queries, 10)
    after = scoring.LAUNCHES.snapshot()
    assert after["phrase_searches"] - before["phrase_searches"] == n_plans
    assert after["phrase"] - before["phrase"] == (2 if n_plans == 5 else 1)
    rows = scoring.PHRASE_RUNGS[0]
    # (plans a launch at its width, the slots of its line)
    shapes = [(1 if len(group) == 1 else 4, _slots_of(group))
              for group in (texts[:4], texts[4:]) if group]
    assert after["phrase_pair_launches"] - before["phrase_pair_launches"] == \
        sum(slots == 2 for _w, slots in shapes)
    gathered = sum(w * slots for w, slots in shapes) * rows
    assert after["position_bytes"] - before["position_bytes"] == \
        gathered * 128 * 4
    # the padding is every row of those that no term of a plan named, and
    # what the plans named is what the lead term left of their whole lists
    (seg,) = ctx.searcher.segments
    plane = packed_for(seg).positions["body"]
    named = sum(len(rows) for text in texts
                for rows in _named_rows(seg, plane, text.split()))
    whole = sum(_whole_rows(seg, plane, text.split()) for text in texts)
    assert after["position_pad_bytes"] - before["position_pad_bytes"] == \
        (gathered - named) * 128 * 4
    assert after["position_list_bytes"] - before["position_list_bytes"] == \
        whole * 512
    assert after["position_skip_bytes"] - before["position_skip_bytes"] == \
        (whole - named) * 512
    for q, td in zip(queries, got):
        _same(td, search_shard(ctx, q, 10, use_device=False))
    _same_on_the_line_of_four(monkeypatch, ctx, queries, got)


@pytest.mark.parametrize("line", LINES)
def test_longer_lists_ride_longer_rungs_alone_and_the_longest_go_to_the_host(
        planted, monkeypatch, line):
    """With the ladder cut down to 2 / 4 / 8 block rows a slot the corpus'
    own pairs meet every rung, each on its line of two slots and on the line
    of four (bit for bit the same answers), and the rung follows the rows the lead term
    LEAVES, not the lists: plans of the first rung launch together, those of
    a longer rung one a launch, the top term beside a rare one rides the
    first rung though its whole list passes the last, and only a pair whose
    kept rows pass the last rung sends its batch to the host; every answer is
    the host's."""
    corpus, ref, ctx = planted
    monkeypatch.setattr(scoring, "PHRASE_RUNGS", (2, 4, 8))
    _ride(monkeypatch, line)
    slots = 2 if line == "own" else scoring.PHRASE_SLOTS
    (seg,) = ctx.searcher.segments
    plane = ensure_positions(seg, packed_for(seg), "body")
    words = [word(int(t)) for t in ref.by_df[:24]]
    by_rung: dict = {}
    for i, a in enumerate(words):
        for b in words[i + 1:]:
            kept = max(len(rows) for rows in _named_rows(seg, plane, [a, b]))
            by_rung.setdefault(scoring.phrase_rung(kept), []).append(f"{a} {b}")
    assert set(by_rung) == {None, 2, 4, 8}
    for rung in (2, 4, 8):
        queries = [_phrase(t) for t in by_rung[rung][:2]]
        before = scoring.LAUNCHES.snapshot()
        got = search_shard_batch(ctx, queries, 10)
        after = scoring.LAUNCHES.snapshot()
        launches = after["phrase"] - before["phrase"]
        if rung == 8:
            # the last rung is no launch: a list that long is cut by document
            # ranges into tiles of the rung before, two or three a plan here
            assert 4 <= launches <= 6
            rows = 4
        else:
            assert launches == (1 if rung == 2 else 2)
            rows = rung
        assert after["phrase_pair_launches"] - before["phrase_pair_launches"] \
            == (launches if line == "own" else 0)
        assert after["position_bytes"] - before["position_bytes"] == \
            (4 if rung == 2 else launches) * slots * rows * 128 * 4
        assert after["phrase_searches"] - before["phrase_searches"] == 2
        for q, td in zip(queries, got):
            _same(td, search_shard(ctx, q, 10, use_device=False))
        if line == "own":
            _same_on_the_line_of_four(monkeypatch, ctx, queries, got)
        if rung == 8:
            # a ladder of two rungs cuts nothing: the same plans, one launch
            # each at eight rows a slot, answer bit for bit as the tiles did
            with monkeypatch.context() as m:
                m.setattr(scoring, "PHRASE_RUNGS", (2, 8))
                before = scoring.LAUNCHES.snapshot()
                whole = search_shard_batch(ctx, queries, 10)
                after = scoring.LAUNCHES.snapshot()
            assert after["phrase"] - before["phrase"] == 2
            assert after["position_bytes"] - before["position_bytes"] == \
                2 * slots * 8 * 128 * 4
            for td, want in zip(whole, got):
                assert td.total == want.total
                assert td.hits == want.hits
    # the top term's list alone passes the last rung; beside a rare term the
    # launch names a row or two of it and rides the first
    top, rare = words[0], word(int(ref.by_df[ref.n_present - 1]))
    b0, b1 = plane.blocks_for_term(seg.term_id("body", top))
    assert scoring.phrase_rung(b1 - b0) is None
    for text in (f"{top} {rare}", f"{rare} {top}"):
        named = _named_rows(seg, plane, text.split())
        assert scoring.phrase_rung(max(map(len, named))) == 2
        before = scoring.LAUNCHES.snapshot()
        _same(*_both(ctx, _phrase(text)))
        after = scoring.LAUNCHES.snapshot()
        assert after["position_bytes"] - before["position_bytes"] == \
            slots * 2 * 128 * 4
        assert after["position_skip_bytes"] - before["position_skip_bytes"] == \
            (b1 - b0 + 1 - sum(map(len, named))) * 512 > 0
    # two head terms keep every row of each other: past the last rung, so the
    # batch, its rare phrase with it, is the host's
    queries = [_phrase(by_rung[None][0]), _phrase(f"{rare} {rare}")]
    before = scoring.LAUNCHES.snapshot()
    got = search_shard_batch(ctx, queries, 10)
    after = scoring.LAUNCHES.snapshot()
    assert (after["phrase"], after["phrase_searches"]) == \
        (before["phrase"], before["phrase_searches"])
    for q, td in zip(queries, got):
        _same(td, search_shard(ctx, q, 10, use_device=False), rtol=0)


def _head_phrases(planted, n_terms):
    """Phrases of `n_terms` of the corpus' four most frequent terms, whose
    lists keep each other's rows: windows of the documents' own tokens that
    hold nothing else (they match somewhere), a term twice among them."""
    corpus, ref, _ctx = planted
    head = [int(t) for t in ref.by_df[:4]]
    ends = np.cumsum(corpus.lengths)
    of_head = np.isin(corpus.tokens, head)
    run = np.convolve(of_head, np.ones(n_terms, int), "valid") == n_terms
    texts = []
    for lo in np.flatnonzero(run):
        doc = int(np.searchsorted(ends, lo, "right"))
        if lo + n_terms <= ends[doc]:
            texts.append(" ".join(
                word(int(t)) for t in corpus.tokens[lo: lo + n_terms]))
    texts = list(dict.fromkeys(texts))
    assert len(texts) >= 4
    return texts[:6]


@pytest.mark.parametrize("n_terms,line", [(2, "own"), (2, "four"), (3, "own"),
                                          (4, "own")])
def test_a_list_past_the_second_rung_is_cut_into_tiles_by_document_ranges(
        planted, monkeypatch, n_terms, line):
    """With the ladder cut to 1 / 2 / 32 block rows a slot, a phrase of head
    terms keeps more rows than a launch of the second rung holds and is cut
    by document ranges into tiles of two rows a slot, one launch each: more
    launches than plans, every one at the tile's shape, a pair's on its line
    of two slots. Totals, documents and float32 scores are the host's, and
    bit for bit those of ONE launch of the whole lists (a ladder of two rungs
    cuts nothing). A row at a tile's edge holds keys of both tiles'
    documents: with every tile answering for every document some phrase here
    counts a document twice, so the range each tile answers for does work."""
    corpus, ref, ctx = planted
    monkeypatch.setattr(scoring, "PHRASE_RUNGS", (1, 2, 32))
    _ride(monkeypatch, line)
    slots = 2 if (n_terms == 2 and line == "own") else scoring.PHRASE_SLOTS
    texts = _head_phrases(planted, n_terms)
    tiled = []
    for text in texts:
        before = scoring.LAUNCHES.snapshot()
        dev, host = _both(ctx, _phrase(text))
        after = scoring.LAUNCHES.snapshot()
        _same(dev, host)
        assert dev.total > 0
        launches = after["phrase"] - before["phrase"]
        assert launches >= 2
        assert after["phrase_pair_launches"] - before["phrase_pair_launches"] \
            == (launches if slots == 2 else 0)
        gathered = after["position_bytes"] - before["position_bytes"]
        assert gathered == launches * slots * 2 * 512
        named = gathered - (after["position_pad_bytes"]
                            - before["position_pad_bytes"])
        listed = after["position_list_bytes"] - before["position_list_bytes"]
        skipped = after["position_skip_bytes"] - before["position_skip_bytes"]
        assert listed - skipped == named > 0
        tiled.append(dev)
    assert max(td.total for td in tiled) > 10  # more matches than one page
    with monkeypatch.context() as m:
        m.setattr(scoring, "PHRASE_RUNGS", (1, 32))
        for text, dev in zip(texts, tiled):
            before = scoring.LAUNCHES.snapshot()
            whole, _host = _both(ctx, _phrase(text))
            after = scoring.LAUNCHES.snapshot()
            assert after["phrase"] - before["phrase"] == 1
            assert whole.total == dev.total
            assert whole.hits == dev.hits  # ids and float32 scores, bit for bit
    # every tile made to answer for every document: a document at an edge is
    # counted by both tiles whose rows hold its keys
    operands = scoring.phrase_operands

    def for_every_document(entries, *shape):
        return operands([(w, fid, terms, scoring.PHRASE_ALL_DOCS)
                         for w, fid, terms, _docs in entries], *shape)

    monkeypatch.setattr(scoring, "phrase_operands", for_every_document)
    over = [search_shard(ctx, _phrase(text), 10, use_device=True).total - td.total
            for text, td in zip(texts, tiled)]
    assert min(over) >= 0 and max(over) > 0


def _phrases_of_a_head_term(planted, n_terms):
    """Phrases of `n_terms` terms that hold one of the corpus' three most
    frequent terms: windows of the documents' own tokens with the head term
    first or last (they match somewhere), and a head and a rare term by turns
    in each order (`a b`, `b a`, `a b a`, `b a b a`: a repeated term, seldom a
    match, the lists merged all the same)."""
    corpus, ref, _ctx = planted
    head = [int(t) for t in ref.by_df[:3]]
    rare = [int(t) for t in ref.by_df[ref.n_present - 3: ref.n_present]]
    texts = []
    ends = np.cumsum(corpus.lengths)
    for at in np.flatnonzero(np.isin(corpus.tokens, head)):
        for lo in (at, at - n_terms + 1):
            doc = int(np.searchsorted(ends, lo, "right"))
            if lo >= 0 and lo + n_terms <= ends[doc]:
                texts.append(" ".join(
                    word(int(t)) for t in corpus.tokens[lo: lo + n_terms]))
    texts = list(dict.fromkeys(texts))[:: max(1, len(set(texts)) // 8)][:8]
    for h, r in zip(head, rare):
        for pair in ((h, r), (r, h)):
            texts.append(" ".join(word(pair[i % 2]) for i in range(n_terms)))
    return texts


@pytest.mark.parametrize("ladder", [None, (2, 8, 32)], ids=["whole", "cut"])
@pytest.mark.parametrize("n_terms,line", [(2, "own"), (2, "four"), (3, "own"),
                                          (4, "own")])
def test_the_lead_term_changes_no_answer(planted, monkeypatch, n_terms, line,
                                         ladder):
    """Every phrase with a head term, launched over the rows the lead term
    leaves and launched again with every row listed: totals, documents and
    float32 scores bit for bit the same, both the host's. With the ladder cut
    to 2 / 8 / 32 rows the two launches ride different rungs, so different
    programs, and still agree; so do the pairs on their line of two slots
    and on the line of four."""
    corpus, ref, ctx = planted
    if ladder is not None:
        monkeypatch.setattr(scoring, "PHRASE_RUNGS", ladder)
    _ride(monkeypatch, line)
    texts = _phrases_of_a_head_term(planted, n_terms)
    assert len(texts) >= 10
    pruned = []
    skipped = matched = 0
    for text in texts:
        before = scoring.LAUNCHES.snapshot()
        dev, host = _both(ctx, _phrase(text))
        after = scoring.LAUNCHES.snapshot()
        _same(dev, host)
        pruned.append(dev)
        skipped += after["position_skip_bytes"] - before["position_skip_bytes"]
        matched += dev.total
    assert skipped > 0 and matched > 0
    monkeypatch.setattr(PositionsPlane, "rows_holding", _every_row)
    if ladder is not None:
        # every row of a head term passes a tile; a ladder of two rungs cuts
        # nothing, so the whole list rides ONE launch of the last rung
        monkeypatch.setattr(scoring, "PHRASE_RUNGS", (ladder[0], ladder[-1]))
    for text, dev in zip(texts, pruned):
        before = scoring.LAUNCHES.snapshot()
        whole, _host = _both(ctx, _phrase(text))
        after = scoring.LAUNCHES.snapshot()
        assert after["position_skip_bytes"] == before["position_skip_bytes"]
        assert after["phrase"] == before["phrase"] + 1
        assert whole.total == dev.total
        assert whole.hits == dev.hits  # ids and float32 scores, bit for bit
    if n_terms == 2 and line == "own":  # every row listed, on the line of four
        _same_on_the_line_of_four(monkeypatch, ctx,
                                  [_phrase(text) for text in texts], pruned)


def test_the_counters_of_the_lead_term(planted):
    """Two head terms hold each other's documents in every row: nothing is
    skipped. Beside a rare term the launch names the rows that hold the rare
    term's documents, and `position_list_bytes` less `position_skip_bytes` is
    those rows' bytes. What is gathered is the launched shape: one plan on
    the first rung, two slots for a pair and four for a phrase of three, and
    the padding is what of it no term named."""
    corpus, ref, ctx = planted
    (seg,) = ctx.searcher.segments
    plane = ensure_positions(seg, packed_for(seg), "body")
    a, b = (word(int(t)) for t in ref.by_df[:2])
    rare = word(int(ref.by_df[ref.n_present - 2]))
    for text, skips in ((f"{a} {b}", False), (f"{a} {rare}", True),
                        (f"{rare} {b} {a}", True)):
        named = sum(map(len, _named_rows(seg, plane, text.split())))
        whole = _whole_rows(seg, plane, text.split())
        before = scoring.LAUNCHES.snapshot()
        _same(*_both(ctx, _phrase(text)))
        after = scoring.LAUNCHES.snapshot()
        listed = after["position_list_bytes"] - before["position_list_bytes"]
        skipped = after["position_skip_bytes"] - before["position_skip_bytes"]
        assert listed == whole * 512
        assert listed - skipped == named * 512
        assert (skipped > 0) is skips
        slots = _slots_of([text])
        gathered = after["position_bytes"] - before["position_bytes"]
        assert gathered == slots * scoring.PHRASE_RUNGS[0] * 512
        assert after["position_pad_bytes"] - before["position_pad_bytes"] == \
            gathered - named * 512
        assert after["phrase"] - before["phrase"] == 1
        assert after["phrase_pair_launches"] - before["phrase_pair_launches"] \
            == int(slots == 2)


def test_a_phrase_beside_plain_and_filtered_plans_in_one_batch(planted):
    corpus, ref, ctx = planted
    (terms,) = _pick(planted, 2)[:1]
    a, b = word(terms[0]), word(terms[1])
    queries = [
        _phrase(f"{a} {b}"),
        parse_query({"match": {"body": f"{a} {b}"}}),
        parse_query({"filtered": {"query": {"match": {"body": a}},
                                  "filter": {"term": {"body": b}}}}),
        _phrase(f"{b} {a}"),
    ]
    plans = [lower_flat(q, ctx, phrases=True) for q in queries]
    assert [p.phrase is not None for p in plans] == [True, False, False, True]
    for q, td in zip(queries, execute_flat_batch(plans, ctx, 10)):
        _same(td, search_shard(ctx, q, 10, use_device=False))


# ---------------------------------------------------------------------------
# edge cases by hand
# ---------------------------------------------------------------------------

HAND = [
    {"body": "a b c d"},
    {"body": "a a a b"},
    {"body": "x a b a b"},
    {"body": "b a"},
    {"body": "c d a b c d"},
    {"body": ["a b", "c d"]},          # multi-valued: b and c are not neighbours
    {"body": "q r s d"},               # ends in d ...
    {"body": "a y z"},                 # ... and the next starts with a
    {"body": "the quick brown fox"},
] + [{"body": f"pad{i} a x{i} b"} for i in range(40)]

PHRASES = ["a b", "a a", "a a a", "a b c d", "b c", "d a", "a nosuch", "b a b",
           "c d a b", "a b a b"]


@pytest.fixture(scope="module", params=["BM25", "default"])
def hand(request, tmp_path_factory):
    eng, ctx = _shard(tmp_path_factory.mktemp("hand" + request.param), HAND,
                      sim=request.param, refresh_at=(4, 20))
    return eng, ctx


@pytest.mark.parametrize("text", PHRASES)
def test_edge_cases_answer_as_the_host(hand, monkeypatch, text):
    """Repeated terms, an absent term (no match), a phrase that would straddle
    two documents or two values (no match), three segments; BM25 and TF-IDF.
    A pair's line of two slots answers as the line of four."""
    _eng, ctx = hand
    c = ctx()
    assert len(c.searcher.segments) == 3
    dev, host = _both(c, _phrase(text))
    _same(dev, host, rtol=0)  # the host's own float operations: bitwise
    if len(text.split()) == 2 and text != "a nosuch":
        _same_on_the_line_of_four(monkeypatch, c, [_phrase(text)], [dev])
    if text == "a nosuch":
        assert dev.total == 0
    if text == "d a":  # inside document 4; never from document 6 into 7
        assert [d for _s, d in dev.hits] == [4]
    if text == "b c":  # inside one document, never across two values
        assert [d for _s, d in dev.hits] == [0, 4]


@pytest.mark.parametrize("line", LINES)
def test_deletes_and_a_delta_segment(tmp_path, monkeypatch, line):
    eng, ctx = _shard(tmp_path, HAND, refresh_at=(20,))
    _ride(monkeypatch, line)
    q = _phrase("a b")
    c = c_old = ctx()
    dev, host = _both(c, q)
    _same(dev, host)
    planes = [packed_for(s).positions["body"] for s in c.searcher.segments]
    eng.delete("doc", "0")
    eng.delete("doc", "2")
    eng.refresh()
    c = ctx()
    dev2, host2 = _both(c, q)
    _same(dev2, host2)
    assert dev2.total == dev.total - 2
    # a tombstone re-masks the plane from its raw host copy, as the postings:
    # no second build, the deleted documents' markers under the dead code
    seg = c.searcher.segments[0]
    plane = packed_for(seg).positions["body"]
    assert plane is not planes[0] and plane.host_keys is planes[0].host_keys
    assert ensure_positions(seg, packed_for(seg), "body") is plane
    field = np.asarray(plane.keys) & ((1 << plane.pos_bits) - 1)
    dead = field == plane.mark_base + (POS_DEAD_CODE << 4)
    gone = np.flatnonzero(~seg.live)
    assert len(gone) == 2
    assert sorted(set((np.asarray(plane.keys)[dead] >> plane.pos_bits).tolist())) \
        == gone.tolist()
    assert dead.sum() == sum(  # a marker a posting of a deleted document
        int(np.isin(seg.post_docs[seg.post_offsets[t]: seg.post_offsets[t + 1]],
                    gone).sum()) for t in seg.term_dict["body"].values())
    assert not (np.asarray(planes[0].keys) != plane.host_keys).any()
    # the searcher acquired before the deletes keeps its documents and its plane
    dev_old, host_old = _both(c_old, q)
    _same(dev_old, host_old)
    assert dev_old.total == dev.total
    eng.index("doc", "new", {"body": "fresh a b"})
    eng.refresh()
    c = ctx()
    assert len(c.searcher.segments) == 3
    dev3, host3 = _both(c, q)
    _same(dev3, host3)
    assert dev3.total == dev2.total + 1


@pytest.mark.parametrize("line", LINES)
def test_a_deleted_lead_document(tmp_path, monkeypatch, line):
    """`c` leads `c d` (documents 0, 4, 5 of the first segment). With document
    4 deleted its rows are still named (the lead's postings keep a tombstone,
    a superset is enough), its marker carries the dead code and it matches
    nothing; the re-mask leaves the rows' bounds as they were. On the pair's
    line of two slots and on the line of four."""
    eng, ctx = _shard(tmp_path, HAND, refresh_at=(20,))
    _ride(monkeypatch, line)
    q = _phrase("c d")
    c = ctx()
    dev, host = _both(c, q)
    _same(dev, host, rtol=0)
    assert [d for _s, d in sorted(dev.hits, key=lambda h: h[1])] == [0, 4, 5]
    seg = c.searcher.segments[0]
    plane = packed_for(seg).positions["body"]
    eng.delete("doc", "4")
    eng.refresh()
    c = ctx()
    pairs = scoring.LAUNCHES.snapshot()["phrase_pair_launches"]
    dev2, host2 = _both(c, q)
    _same(dev2, host2, rtol=0)
    assert sorted(d for _s, d in dev2.hits) == [0, 5]
    # a launch a segment that holds both terms, every one the pair's own line
    assert scoring.LAUNCHES.snapshot()["phrase_pair_launches"] - pairs == \
        (1 if line == "own" else 0)
    if line == "own":
        _same_on_the_line_of_four(monkeypatch, c, [q], [dev2])
    seg2 = c.searcher.segments[0]
    plane2 = packed_for(seg2).positions["body"]
    assert plane2 is not plane
    assert plane2.blk_first is plane.blk_first
    assert plane2.blk_last is plane.blk_last
    assert 4 in seg2.postings("body", "c")[0]


@pytest.mark.parametrize("line", LINES)
def test_a_document_that_straddles_block_rows_keeps_them_all(
        tmp_path, monkeypatch, line):
    """`x` fills nine block rows; `y`, the lead of `y x`, stands in two
    documents: one whose 300 occurrences of `x` and their marker lie across
    three rows, one whose 100 lie across two. The launch names exactly those
    rows (the third is shared), in order, and every occurrence counts, on the
    pairs' line of two slots as on the line of four."""
    docs = [{"body": "x " * 50},
            {"body": "y " + "x " * 300},
            {"body": "x " * 20},
            {"body": "x y " + "x " * 99},
            ] + [{"body": "x " * 200} for _ in range(3)]
    eng, ctx = _shard(tmp_path, docs)
    c = ctx()
    (seg,) = c.searcher.segments
    _ride(monkeypatch, line)
    for text, total in (("y x", 2), ("x x", 7), ("x y x", 1), ("x y", 1)):
        pairs = scoring.LAUNCHES.snapshot()["phrase_pair_launches"]
        dev, host = _both(c, _phrase(text))
        _same(dev, host, rtol=0)
        assert dev.total == total
        assert scoring.LAUNCHES.snapshot()["phrase_pair_launches"] - pairs == \
            int(line == "own" and _slots_of([text]) == 2)
        if line == "own":
            _same_on_the_line_of_four(monkeypatch, c, [_phrase(text)], [dev])
    plane = packed_for(seg).positions["body"]
    x = seg.term_id("body", "x")
    b0, b1 = plane.blocks_for_term(x)
    assert b1 - b0 == 9
    # keys 51..351 are document 1's (300 occurrences and a marker), keys
    # 373..473 document 3's (100 and a marker): rows 0-2 and rows 2-3
    held = plane.host_keys[b0: b1] >> plane.pos_bits
    assert [sorted(set(np.flatnonzero((held == d).any(axis=1)).tolist()))
            for d in (1, 3)] == [[0, 1, 2], [2, 3]]
    lead_docs, _freqs = seg.postings("body", "y")
    assert lead_docs.tolist() == [1, 3]
    named = plane.rows_holding(x, docs_below(lead_docs, seg.doc_count))
    assert named.dtype == np.int32
    assert named.tolist() == [b0, b0 + 1, b0 + 2, b0 + 3]
    assert _named_rows(seg, plane, ["y", "x"])[1] == named.tolist()

    def rows(*docs):
        return plane.rows_holding(
            x, docs_below(np.array(docs, np.int32), seg.doc_count)).tolist()

    # a candidate names every row its keys lie in, and a row between whose
    # first and last document it lies (the range, not the keys); no
    # candidate names nothing, all of them every row
    assert rows(2) == [b0 + 2]
    assert rows(0) == [b0]
    assert rows(6) == (b0 + np.flatnonzero((held == 6).any(axis=1))).tolist()
    assert rows() == []
    assert rows(*range(7)) == list(range(b0, b1))


@pytest.mark.parametrize("line", LINES)
def test_tiles_keep_a_straddling_document_whole_and_a_deleted_one_out(
        tmp_path, monkeypatch, line):
    """The corpus of the test above with the ladder cut to 1 / 4 / 16 rows:
    `x x` keeps all nine rows of `x` and is cut into tiles of four rows, whose
    cuts fall where a row starts, inside documents that lie across rows: a
    tile that starts at such a document names the rows before the cut that
    hold its keys, so every occurrence counts once. Deleting a document takes
    it out of its tile, and a delta segment is one more launch. A document
    is never cut: with tiles of two rows document 1, three rows long, fits
    none, and the host answers."""
    docs = [{"body": "x " * 50},
            {"body": "y " + "x " * 300},
            {"body": "x " * 20},
            {"body": "x y " + "x " * 99},
            ] + [{"body": "x " * 200} for _ in range(3)]
    eng, ctx = _shard(tmp_path, docs)
    monkeypatch.setattr(scoring, "PHRASE_RUNGS", (1, 4, 16))
    _ride(monkeypatch, line)
    c = ctx()
    for text, total in (("x x", 7), ("x x x", 7), ("x x x x", 7)):
        before = scoring.LAUNCHES.snapshot()
        dev, host = _both(c, _phrase(text))
        after = scoring.LAUNCHES.snapshot()
        _same(dev, host, rtol=0)
        assert dev.total == total
        assert after["phrase"] - before["phrase"] >= 2  # tiles
        assert after["position_bytes"] - before["position_bytes"] == \
            (after["phrase"] - before["phrase"]) \
            * (2 if line == "own" and len(text.split()) == 2 else 4) * 4 * 512
    with monkeypatch.context() as m:
        m.setattr(scoring, "PHRASE_RUNGS", (1, 2, 16))
        before = scoring.LAUNCHES.snapshot()
        host = search_shard(c, _phrase("x x"), 10, use_device=True)
        assert scoring.LAUNCHES.snapshot()["phrase"] == before["phrase"]
        assert host.total == 7
    eng.delete("doc", "1")
    eng.delete("doc", "5")
    eng.index("doc", "new", {"body": "y x x x x"})
    eng.refresh()
    c = ctx()
    assert len(c.searcher.segments) == 2
    for text, total in (("x x", 6), ("x x x", 6), ("x x x x", 6)):
        dev, host = _both(c, _phrase(text))
        _same(dev, host, rtol=0)
        assert dev.total == total
        assert not {1, 5} & {d for _s, d in dev.hits}


def test_a_merged_segment_faults_its_own_plane(tmp_path):
    eng, ctx = _shard(tmp_path, HAND, refresh_at=(4, 20))
    q = _phrase("a b")
    dev, _host = _both(ctx(), q)
    eng.optimize(max_num_segments=1)
    c = ctx()
    (seg,) = c.searcher.segments
    assert packed_for(seg).positions == {}
    dev2, host2 = _both(c, q)
    _same(dev2, host2)
    assert dev2.total == dev.total
    assert packed_for(seg).positions["body"].keys is not None


def test_analyzed_gaps_keep_their_places(tmp_path):
    settings = {"index.analysis.analyzer.default.type": "standard",
                "index.analysis.analyzer.default.stopwords": "of,the"}
    svc = MapperService(Settings.from_flat(settings))
    eng = Engine(str(tmp_path), svc)
    for i, text in enumerate(["king of the hill", "king hill", "king x y hill",
                              "hill of the king"]):
        eng.index("doc", str(i), {"body": text})
    eng.refresh()
    ctx = ShardContext(eng.acquire_searcher(), svc, SimilarityService(
        Settings.from_flat(settings), mapper_service=svc))
    q = _phrase("king of the hill")
    plan = lower_flat(q, ctx, phrases=True)
    assert plan.phrase.terms == ("king", "hill")
    assert plan.phrase.rel_pos == (0, 3)
    dev, host = _both(ctx, q)
    _same(dev, host, rtol=0)
    assert [d for _s, d in dev.hits] == [0, 2]


# ---------------------------------------------------------------------------
# the plane: absent until the first phrase, booked under the breaker, ranged
# ---------------------------------------------------------------------------


def test_the_plane_is_faulted_in_by_the_first_phrase(tmp_path):
    breakers = CircuitBreakerService(Settings.from_flat({}))
    fielddata = breakers.breaker("fielddata")
    eng, ctx = _shard(tmp_path, HAND, breakers=breakers)
    c = ctx()
    (seg,) = c.searcher.segments
    search_shard(c, parse_query({"match": {"body": "a b"}}), 10)
    packed = packed_for(seg)
    assert packed.positions == {}
    assert packed_tier_bytes(packed)["positions_plane"] == 0
    seen = []
    grant = fielddata.add_estimate_and_maybe_break

    def watch(n, label=""):
        seen.append((label, n))
        return grant(n, label)

    fielddata.add_estimate_and_maybe_break = watch
    try:
        search_shard(c, _phrase("a b"), 10)
    finally:
        fielddata.add_estimate_and_maybe_break = grant
    plane = packed.positions["body"]
    resident = int(np.prod(plane.keys.shape)) * 4
    assert packed_tier_bytes(packed)["positions_plane"] == resident > 0
    (booked,) = [n for label, n in seen if label == "<positions>body"]
    assert booked >= 2 * resident  # the host's staging and the device's copy
    assert fielddata.used == 0  # transient, as every fault-in's
    # a key an occurrence and a marker a posting, ascending inside each term
    keys = np.asarray(plane.keys).reshape(-1)
    tid = seg.term_id("body", "a")
    b0, b1 = plane.blocks_for_term(tid)
    mine = keys[b0 * 128: b1 * 128]
    real = mine[mine != np.int32(2**31 - 1)]
    docs, freqs = seg.postings("body", "a")
    assert len(real) == int(freqs.sum()) + len(docs)
    assert (np.diff(real) > 0).all()
    markers = (real & ((1 << plane.pos_bits) - 1)) >= plane.mark_base
    assert markers.sum() == len(docs)


def test_a_tripped_breaker_sends_the_phrase_to_the_host(tmp_path):
    eng, ctx = _shard(tmp_path, HAND)
    c = ctx()
    search_shard(c, parse_query({"match": {"body": "a b"}}), 10)  # packs
    c.breakers = CircuitBreakerService(Settings.from_flat(
        {"indices.breaker.total_budget": "64kb"}))
    with pytest.raises(CircuitBreakingError) as err:
        search_shard(c, _phrase("a b"), 10)
    assert err.value.breaker == "fielddata"  # service degrades this to the host
    assert packed_for(c.searcher.segments[0]).positions == {}


def test_a_position_past_the_keys_range_goes_to_the_host(tmp_path, monkeypatch):
    eng, ctx = _shard(tmp_path, HAND)
    c = ctx()
    (seg,) = c.searcher.segments
    packed = packed_for(seg)
    base = positions_mark_base(31 - 6)  # doc_pad 128 leaves 24 bits
    seg.positions = seg.positions.copy()
    seg.positions[int(np.argmax(seg.positions))] = base + 5
    plane = ensure_positions(seg, packed, "body")
    assert plane.keys is None and not plane.room_for(0)
    q = _phrase("a b")
    before = scoring.LAUNCHES.snapshot()
    td = search_shard(c, q, 10, use_device=True)
    assert scoring.LAUNCHES.snapshot()["phrase"] == before["phrase"]
    _same(td, search_shard(c, q, 10, use_device=False), rtol=0)
    ok = PositionsPlane(24, 100, np.zeros(2, np.int64), keys=object())
    assert ok.room_for(15) and not ok.room_for(16)
    assert not PositionsPlane(24, base - 3, None, keys=object()).room_for(3)


def test_the_two_ceilings_of_the_plane_in_documents_a_segment():
    """What the documents state (PERF.md section 6, ROADMAP S14, ARCHITECTURE.md),
    held to the code: one int32 key leaves positions of up to 1,000 tokens room
    beside 262,144 documents a segment and no further; and a phrase goes to
    the host when the block rows of one of its terms that hold a document of
    the LEAD term pass 32,768 (4,194,304 occurrences and markers), whatever
    the term's whole list holds: a list of 40,000 rows rides the first rung
    beside a lead of 500 documents and goes to the host beside a lead that
    stands in every row."""
    longest = 1000 + 15  # a position moved up by the largest shift
    for doc_pad, fits in ((131_072, True), (262_144, True), (524_288, False)):
        pos_bits = 31 - (doc_pad - 1).bit_length()
        base = positions_mark_base(pos_bits)
        assert (base > longest) is fits
        if fits:  # the dead code and the largest shift stay under the sentinel
            top = ((doc_pad - 1) << pos_bits) | (base + (POS_DEAD_CODE << 4) + 15)
            assert top < 2**31 - 1
    assert scoring.phrase_rung(scoring.PHRASE_RUNGS[-1]) == 32_768
    assert scoring.phrase_rung(scoring.PHRASE_RUNGS[-1] + 1) is None
    assert scoring.PHRASE_RUNGS[-1] * 128 == 4_194_304
    # a head term of 40,000 block rows, three documents a row
    n_rows = 40_000
    first = np.arange(n_rows, dtype=np.int32) * 3
    plane = PositionsPlane(13, 100, np.array([0, n_rows], np.int64),
                           blk_first=first, blk_last=first + 2)
    assert scoring.phrase_rung(n_rows) is None

    def named(docs):
        return plane.rows_holding(0, docs_below(docs, 3 * n_rows))

    rare = named(np.arange(500, dtype=np.int32) * 240 + 7)
    assert len(rare) == 500 and (np.diff(rare) > 0).all()
    assert scoring.phrase_rung(len(rare)) == scoring.PHRASE_RUNGS[0]
    everywhere = np.arange(n_rows, dtype=np.int32) * 3 + 1
    assert scoring.phrase_rung(len(named(everywhere))) is None
    assert scoring.phrase_rung(len(named(everywhere[:: 2]))) == 32_768


# ---------------------------------------------------------------------------
# what stays on the host, and why
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("body,reason", [
    ({"match_phrase": {"body": {"query": "a b", "slop": 1}}}, "sloppy_phrase"),
    ({"match_phrase_prefix": {"body": "a b"}}, "phrase_prefix"),
    ({"match_phrase": {"body": "a b c d a"}}, "long_phrase"),
    ({"bool": {"must": [{"match_phrase": {"body": "a b"}}]}},
     "non_term_subclause"),
    ({"dis_max": {"queries": [{"match_phrase": {"body": "a b"}}]}},
     "dismax_subquery"),
    ({"multi_match": {"query": "a b", "fields": ["body"], "type": "phrase"}},
     "multi_match_type"),
    ({"span_near": {"clauses": [{"span_term": {"body": "a"}},
                                {"span_term": {"body": "b"}}],
                    "slop": 0, "in_order": True}},
     "unsupported_query:SpanNearQuery"),
    ({"filtered": {"query": {"match_phrase": {"body": "a b"}},
                   "filter": {"term": {"body": "c"}}}}, "non_flat_subquery"),
    ({"function_score": {"query": {"match_phrase": {"body": "a b"}},
                         "boost_factor": 2}}, "non_flat_subquery"),
])
def test_the_forms_that_stay_on_the_host(hand, body, reason):
    _eng, ctx = hand
    c = ctx()
    q = parse_query(body)
    assert lower_flat(q, c, phrases=True) is None
    if reason is not None:
        assert lower_fallback_reason(q, c) == reason
    else:
        assert lower_fallback_reason(q, c).startswith("unsupported_query:")
    before = scoring.LAUNCHES.snapshot()["phrase_searches"]
    dev = search_shard(c, q, 10, use_device=True)
    assert scoring.LAUNCHES.snapshot()["phrase_searches"] == before
    _same(dev, search_shard(c, q, 10, use_device=False), rtol=0)


def test_one_term_is_a_term_plan_and_the_profile_names_the_phrase(hand):
    _eng, ctx = hand
    c = ctx()
    one = lower_flat(_phrase("fox"), c)  # a term plan for every caller
    assert one.phrase is None and [x.term for x in one.clauses] == ["fox"]
    q = _phrase("a b", boost=2.0)
    # a phrase plan only for the caller that can run one (the query phase)
    assert lower_flat(q, c) is None
    plan = lower_flat(q, c, phrases=True)
    assert plan.boost == 2.0 and plan.clauses == []
    prof = plan_profile(plan, q)
    assert prof["phrase"] == {"field": "body", "terms": ["a", "b"],
                              "rel_pos": [0, 1]}
    assert plan_profile(one, _phrase("fox"))["phrase"] is None
    _same(*_both(c, q), rtol=0)


# ---------------------------------------------------------------------------
# through the batcher and through REST: the counters the benchmark reads
# ---------------------------------------------------------------------------


def test_the_batcher_counts_the_kind(planted):
    corpus, ref, ctx = planted
    texts = [" ".join(word(t) for t in terms) for terms in _pick(planted, 2)[:3]]
    plans = [lower_flat(_phrase(t), ctx, phrases=True) for t in texts] + [
        lower_flat(parse_query({"match": {"body": texts[0]}}), ctx)]
    b = DeviceBatcher(Settings.from_flat({"search.batch.linger_ms": "5000",
                                          "search.batch.max_batch": "4"}))
    out = [None] * len(plans)

    def worker(i):
        out[i] = b.execute(plans[i], ctx, 10, deadline=NO_DEADLINE)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        stats = b.stats()
    finally:
        b.shutdown()
    assert stats["kinds"]["phrase"] == {"launches": 1, "coalesced": 3}
    assert stats["kinds"]["plain"] == {"launches": 1, "coalesced": 1}
    for t, td in zip(texts, out):
        _same(td, search_shard(ctx, _phrase(t), 10, use_device=False))


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
    for i, d in enumerate(HAND):
        client.index("lib", "doc", d, id=str(i))
    client.refresh("lib")
    return n, client


def _launch_stats(client):
    (stats,) = client.nodes_stats()["nodes"].values()
    return stats


def test_a_match_phrase_over_rest_reaches_the_phrase_program(tmp_path):
    n, client = _node(tmp_path, "phrase_node", 1)
    try:
        s0 = _launch_stats(client)
        assert s0["device"]["indices"].get("lib", {}).get(
            "totals", {}).get("positions_plane", 0) == 0
        body = {"query": {"match_phrase": {"body": "a b"}}, "size": 10}
        got = client.search("lib", body)
        s1 = _launch_stats(client)
        launch0, launch1 = (s["search_serving"]["launch"] for s in (s0, s1))
        assert launch1["phrase"] - launch0["phrase"] == 1
        assert launch1["phrase_searches"] - launch0["phrase_searches"] == 1
        # a pair: a line of two slots of the first rung
        assert launch1["phrase_pair_launches"] \
            - launch0["phrase_pair_launches"] == 1
        assert launch1["position_bytes"] - launch0["position_bytes"] == \
            2 * scoring.PHRASE_RUNGS[0] * 128 * 4
        assert s1["search_serving"]["device_sparse"] \
            - s0["search_serving"]["device_sparse"] == 1
        assert s1["search_serving"]["host"] == s0["search_serving"]["host"]
        kinds0, kinds1 = (s["search"]["batcher"]["kinds"] for s in (s0, s1))
        assert kinds1["phrase"]["launches"] - kinds0["phrase"]["launches"] == 1
        assert s1["device"]["indices"]["lib"]["totals"]["positions_plane"] > 0
        # the host's answer: the same phrase where only the host can go
        host = client.search("lib", {"query": {"bool": {"must": [
            {"match_phrase": {"body": "a b"}}]}}, "size": 10})
        assert _launch_stats(client)["search_serving"]["host"] \
            == s1["search_serving"]["host"] + 1
        assert got["hits"]["total"] == host["hits"]["total"] > 0
        assert [h["_id"] for h in got["hits"]["hits"]] == \
            [h["_id"] for h in host["hits"]["hits"]]
        np.testing.assert_allclose([h["_score"] for h in got["hits"]["hits"]],
                                   [h["_score"] for h in host["hits"]["hits"]],
                                   rtol=1e-6)
        # a profiled request names the plan, and a sloppy one its reason
        prof = client.search("lib", dict(body, profile=True))
        (shard,) = prof["profile"]["shards"]
        assert shard["plan"]["phrase"]["terms"] == ["a", "b"]
        sloppy = client.search("lib", {"query": {"match_phrase": {"body": {
            "query": "a b", "slop": 2}}}, "profile": True})
        assert sloppy["profile"]["shards"][0]["plan"]["fallback_reason"] == \
            "sloppy_phrase"
    finally:
        n.close()


@pytest.mark.mesh
def test_the_mesh_declines_a_phrase(tmp_path):
    """Four shards on four virtual devices: a match rides the mesh program, a
    phrase goes the transport path to each shard's own phrase program, and
    `mesh_fallbacks` says so; the answer is the host's."""
    n, client = _node(tmp_path, "phrase_mesh", 4)
    try:
        ms = n.actions.mesh_serving
        client.search("lib", {"query": {"match": {"body": "a b"}}})
        assert ms.mesh_queries >= 1
        queries, fallbacks = ms.mesh_queries, ms.mesh_fallbacks
        before = scoring.LAUNCHES.snapshot()["phrase_searches"]
        body = {"query": {"match_phrase": {"body": "a b"}}, "size": 10}
        got = client.search("lib", body, search_type="dfs_query_then_fetch")
        assert ms.mesh_queries == queries
        assert ms.mesh_fallbacks == fallbacks + 1
        assert scoring.LAUNCHES.snapshot()["phrase_searches"] == before + 4
        host = client.search("lib", {"query": {"bool": {"must": [
            {"match_phrase": {"body": "a b"}}]}}, "size": 10},
            search_type="dfs_query_then_fetch")
        assert got["hits"]["total"] == host["hits"]["total"] > 0
        assert [h["_id"] for h in got["hits"]["hits"]] == \
            [h["_id"] for h in host["hits"]["hits"]]
        np.testing.assert_allclose([h["_score"] for h in got["hits"]["hits"]],
                                   [h["_score"] for h in host["hits"]["hits"]],
                                   rtol=1e-6)
    finally:
        n.close()
