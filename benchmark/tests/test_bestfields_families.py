"""The corpus generator and query family of the cell `beir.bestfields`:
`titled_passages` (passages of a page under the page's one title) and `bestfields_terms`
(`multi_match` `best_fields` over the text and the title) against a scan of the
documents' own tokens, both fields; each fault on the number that names it (the
reference in bfloat16 -> `rel_dev` and `ids_off`; a response scored with `tie_breaker`
0 in the program's place, a program that dropped the combine -> `rel_dev` or
`ids_off`); the pool's make-up; and the cell's CPU rehearsal, which never says
correct."""

import argparse
import json
import time

import numpy as np
import pytest

from benchmark.harness import cell, registry
from benchmark.harness.cell import Compared, Pool
from benchmark.harness.reference import Reference, hits_answer, word

BASE = dict(registry.settings()["limits"], rel_dev=1e-5)
CELL = "beir.bestfields"


def _cell(docs: int, pool: int, seed: int):
    bench = registry.benchmark()
    cell_ = registry.cell(bench, CELL)
    config = registry.config(bench, cell_["config"])
    corpus = registry.module("corpora", config["corpus"]["generator"]).generate(
        config["corpus"]["params"], seed, docs)
    sim = config["similarity"]
    ref = Reference(corpus, sim["k1"], sim["b"])
    mix = dict(registry.mix(cell_["traffic"]), pool=pool)
    return ref, Pool(mix, ref, "/bench/_search", BASE), config, mix


@pytest.fixture(scope="module")
def small():
    return _cell(1500, 96, 2**31 + 46)


@pytest.fixture(scope="module")
def fam():
    return registry.module("queries", "bestfields_terms")


def _passed(pool, ref, i, resp):
    got = Compared(pool.limits)
    numbers = pool.compare(ref, i, resp, 1e-5)
    got.add(numbers)
    return numbers, got.passed


# ---------------------------------------------------------------------------
# the corpus
# ---------------------------------------------------------------------------


def test_pages_share_one_title_and_it_names_their_text(small):
    ref, _pool, config, _mix = small
    corpus, params = ref.corpus, config["corpus"]["params"]
    assert corpus.text_field == "txt" and corpus.title_field == "title"
    first = corpus.page_first
    sizes = np.diff(np.append(first, corpus.n_docs))
    assert first[0] == 0 and sizes.min() >= 1
    assert sizes.max() <= params["pages"]["max_passages"]
    assert 5 < sizes.mean() < 11  # geometric around 8
    t_starts = np.zeros(corpus.n_docs + 1, np.int64)
    np.cumsum(corpus.title_lengths, out=t_starts[1:])
    starts = corpus.starts()
    in_text = 0
    for lo, hi in zip(first, np.append(first[1:], corpus.n_docs)):
        title = corpus.title_tokens[t_starts[lo]: t_starts[lo + 1]]
        assert 1 <= len(title) <= params["title"]["max_words"]
        for d in range(lo, hi):  # every passage of the page carries it
            assert (corpus.title_tokens[t_starts[d]: t_starts[d + 1]] == title).all()
        page = set(corpus.tokens[starts[lo]: starts[hi]].tolist())
        own = (len(title) + 1) // 2
        assert all(int(t) in page for t in title[:own])
        in_text += own
    assert in_text >= len(first)  # a title's terms recur in its page's text
    # the documents as sent: both fields, as words
    src = json.loads(corpus.sources(3, 4)[0])
    assert set(src) == {"txt", "title"}
    assert src["txt"].split() == [
        word(t) for t in corpus.tokens[starts[3]: starts[4]]]
    assert src["title"].split() == [
        word(t) for t in corpus.title_tokens[t_starts[3]: t_starts[4]]]


def test_the_same_corpus_for_the_same_seed_and_late_writes_keep_both_fields(small):
    ref, _pool, config, _mix = small
    gen = registry.module("corpora", config["corpus"]["generator"])
    params = config["corpus"]["params"]
    again = gen.generate(params, 2**31 + 46, 1500)
    assert (again.tokens == ref.corpus.tokens).all()
    assert (again.title_tokens == ref.corpus.title_tokens).all()
    other = gen.generate(params, 7, 1500)
    assert not (other.title_tokens[:50] == ref.corpus.title_tokens[:50]).all()
    docs, columns = gen.late_documents(params, ref.corpus, 9, 10)
    grown = ref.corpus.extended(docs, columns)
    assert grown.n_docs == 1510 and grown.text_field == "txt"
    assert len(grown.title_lengths) == 1510
    assert grown.n_vocab == ref.corpus.n_vocab + 10
    late = [json.loads(s) for s in grown.sources(1500, 1510)]
    assert all(word(ref.corpus.n_vocab + j) in d["txt"].split()
               and len(d["title"].split()) == 2 for j, d in enumerate(late))
    assert grown.titles().n_docs == 1510


# ---------------------------------------------------------------------------
# the family's reference against a scan of the documents
# ---------------------------------------------------------------------------


def _scan(corpus, lengths, tokens, terms, k1=1.2, b=0.75):
    """One field's BM25 sum of `terms` a document, from the documents' own tokens
    in float64 (a loop over documents: the plain test of `Reference.score_all`)."""
    from benchmark.harness.reference import byte315_to_float, float_to_byte315

    n = corpus.n_docs
    starts = np.zeros(n + 1, np.int64)
    np.cumsum(lengths, out=starts[1:])
    docs = [tokens[starts[i]: starts[i + 1]] for i in range(n)]
    avgdl = lengths.sum() / n
    out = np.zeros(n)
    matched = np.zeros(n, bool)
    for t in terms:
        tf = np.array([(d == t).sum() for d in docs], np.float64)
        df = (tf > 0).sum()
        if not df:
            continue
        idf = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
        norm = byte315_to_float(float_to_byte315(
            (1.0 / np.sqrt(lengths.astype(np.float64))).astype(np.float32)))
        dl = 1.0 / (norm.astype(np.float64) ** 2)
        out += np.where(tf > 0, idf * (k1 + 1) * tf / (
            tf + k1 * (1 - b + b * dl / avgdl)), 0.0)
        matched |= tf > 0
    return out, matched


def test_expected_against_a_scan_of_both_fields(small, fam):
    ref, pool, _config, _mix = small
    corpus = ref.corpus
    for i in range(0, len(pool.queries), 8):
        q = pool.queries[i]
        scores, matched = fam.expected(ref, q)
        s_txt, m_txt = _scan(corpus, corpus.lengths, corpus.tokens, q["terms"])
        s_title, m_title = _scan(corpus, corpus.title_lengths, corpus.title_tokens,
                                 q["terms"])
        best, total = np.maximum(s_txt, s_title), s_txt + s_title
        want = best + q["tie_breaker"] * (total - best)
        assert (matched == (m_txt | m_title)).all()
        np.testing.assert_allclose(scores[matched], want[matched], rtol=2e-6)
        assert (scores[~matched] == 0).all()


def test_the_pool_is_the_sources_one_idiom(small):
    ref, pool, config, mix = small
    params = mix["families"][0]["params"]
    assert params["fields"] == ["txt", "title"] and params["tie_breaker"] == 0.5
    assert params["b"] == config["similarity"]["b"]
    from_title = 0
    titles = set(ref.corpus.title_tokens.tolist())
    for q in pool.queries:
        mm = q["body"]["query"]["multi_match"]
        assert set(q["body"]) == {"query", "_source", "size"}
        assert q["body"]["size"] == 10 and q["body"]["_source"] is False
        assert mm["type"] == "best_fields" and mm["fields"] == ["txt", "title"]
        assert mm["tie_breaker"] == 0.5
        assert mm["query"].split() == [word(t) for t in q["terms"]]
        assert 2 <= len(q["terms"]) <= 12 == params["max_terms"]
        assert len(set(q["terms"])) == len(q["terms"])
        from_title += sum(t in titles for t in q["terms"])
    n_terms = sum(len(q["terms"]) for q in pool.queries)
    assert 4.0 < n_terms / len(pool.queries) < 6.2  # Poisson(5) clipped 2-12
    assert from_title / n_terms > 0.3  # a term in three is some title's word


def test_the_same_shapes_on_every_seed():
    a = _cell(1500, 48, 11)[1]
    b = _cell(1500, 48, 2**31 + 12)[1]
    assert [len(q["terms"]) for q in a.queries] == [len(q["terms"]) for q in b.queries]
    assert [q["terms"] for q in a.queries] != [q["terms"] for q in b.queries]


# ---------------------------------------------------------------------------
# the comparison sees the precision and the combine
# ---------------------------------------------------------------------------


def test_the_reference_passes_its_own_answer(small):
    ref, pool, _config, _mix = small
    for i in range(len(pool.queries)):
        numbers, passed = _passed(pool, ref, i, pool.answer(ref, i))
        assert passed and numbers["rel_dev"] == 0.0


@pytest.mark.parametrize("seed", [2**31 + 11, 12, 13])
def test_the_control_is_not_correct(seed):
    from benchmark import control

    line = control.read(CELL, seed, 1500, "bfloat16")
    assert not line["passed"]
    assert line["numbers"]["rel_dev"]["value"] > 1e-3  # a hundred times the limit
    assert line["numbers"]["total_off"]["value"] == 0  # precision moves no match
    assert line["searches_past_the_limit"] >= 48


@pytest.mark.parametrize("tie", [0.0, 1.0])
def test_a_response_scored_with_another_tie_breaker_is_not_correct(small, fam, tie):
    """A program that ranked by the best field alone (tie_breaker 0) or by the flat
    sum (1) serves the same matches and totals: the comparison has to see the
    combine in the scores or the order."""
    ref, pool, _config, _mix = small
    got = Compared(pool.limits)
    failed = 0
    for i, q in enumerate(pool.queries):
        wrong = hits_answer(ref, *fam.expected(ref, q, tie_breaker=tie), q["size"])
        numbers = pool.compare(ref, i, wrong, 1e-5)
        got.add(numbers)
        assert numbers["total_off"] == numbers["not_matching"] == 0
        failed += numbers["rel_dev"] > 1e-5 or numbers["ids_off"] > 0
    assert not got.passed
    assert got.numbers["rel_dev"] > 1e-2 or got.numbers["ids_off"] > 0
    assert failed >= len(pool.queries) // 2  # most searches, not a rare one


# ---------------------------------------------------------------------------
# the program's bytes, and the rehearsal
# ---------------------------------------------------------------------------


def test_the_launch_bytes_formula(fam):
    # one plan of two disjuncts over 131,072 documents, 256 triples, one head trip
    # over uint8 rows: the triples' slots, the head planes, two accumulators and
    # the combined plane
    assert fam.dismax_launch_bytes(256, 2, 1, 131072, 1, 1) == \
        256 * 128 * 12 + 2 * 131072 * 5 + 2 * 131072 * 4 + 131072 * 4
    assert fam.dismax_launch_bytes(0, 8, 0, 1024, 4, 2) == 8 * 4096 + 4 * 4096


def test_the_cells_rehearsal_never_says_correct(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    args = argparse.Namespace(workload=CELL, seed=2**31 + 5, seconds=4.0,
                              trace=1, docs=1500)
    rc = cell.run(args, time.perf_counter(),
                  settings={"warmup": {"pool_pass_max_seconds": 60, "rehearsals": 1}})
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    result = lines[-1]
    assert rc == 2 and result["correct"] is False and result["failed"] == 0
    assert all(line["rehearsal"] is True for line in lines[:-1])
    checks = next(line for line in lines if line.get("phase") == "checks")
    assert checks["passed"], checks["problems"]
    assert all(v[0] <= v[1] for v in result["compared"].values()), result["compared"]
    metrics = result["metrics"]
    assert metrics["dismax_served_share"]["value"] == 100.0
    assert metrics["device_served_share"]["value"] == 100.0
    assert metrics["dismax_plan_ms"]["value"] > 0
    assert metrics["dismax_mb_per_search"]["value"] > 0
    assert 0 < metrics["dismax_pad_share"]["value"] < 100
    assert metrics["compiles_in_window"]["value"] == 0
    compared = result["compared"]
    assert compared["rose.search_serving.host"] == [0, 0]
    assert compared["window.ids_off"] == [0, 0]
    # the traced line holds every metric the cell is listed under and no other
    # (on the CPU no device trace: the three that read one are silent)
    bench = registry.benchmark()
    assert set(metrics) == {
        m["name"] for m in bench["per_layer"]
        if CELL in m.get("workloads", ()) and m["source"] != "device_trace"}
