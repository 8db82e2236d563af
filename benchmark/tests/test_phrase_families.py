"""The query family of the cell `wiki.phrase`: `phrase_terms` (exact phrases, classed
by the phrase's own document frequency) against a brute-force scan of the text the
generator renders; the fast count that classes the phrases against the plain one a
response is compared with; each fault `check_hits` catches on a phrase response (a
frequency one short moves the score past the tolerance); the control in bfloat16,
which fails on the scores; the generator's classes, none empty at 3,000 and at 50,000
documents on three seeds; and the cell's CPU rehearsal, which never says correct."""

import argparse
import copy
import json
import time

import numpy as np
import pytest

from benchmark.harness import cell, registry
from benchmark.harness.cell import Compared, Pool
from benchmark.harness.reference import Reference, hits_answer, word

K1, B = 1.2, 0.75
BASE = dict(registry.settings()["limits"], rel_dev=1e-5)


def _cell(docs: int, pool: int, seed: int):
    bench = registry.benchmark()
    cell_ = registry.cell(bench, "wiki.phrase")
    config = registry.config(bench, cell_["config"])
    corpus = registry.module("corpora", config["corpus"]["generator"]).generate(
        config["corpus"]["params"], seed, docs)
    ref = Reference(corpus, K1, B)
    mix = dict(registry.mix(cell_["traffic"]), pool=pool)
    return ref, Pool(mix, ref, "/bench/_search", BASE), config, mix


@pytest.fixture(scope="module")
def small():
    return _cell(3000, 96, 2**31 + 39)


@pytest.fixture(scope="module")
def fam():
    return registry.module("queries", "phrase_terms")


def _numbers(pool, ref, i, resp):
    got = Compared(pool.limits)
    numbers = pool.compare(ref, i, resp, 1e-5)
    got.add(numbers)
    return numbers, got.passed


def test_the_mix_is_the_three_phrase_tasks_in_equal_parts(small):
    ref, pool, config, mix = small
    tasks = [q["task"] for q in pool.queries]
    assert set(tasks) == {"HighPhrase", "MedPhrase", "LowPhrase"}
    assert min(tasks.count(t) for t in set(tasks)) >= 96 // 3 - 12
    lengths = [len(q["terms"]) for q in pool.queries]
    assert set(lengths) == {2, 3, 4} and lengths.count(2) > lengths.count(3) \
        > lengths.count(4)
    for q in pool.queries:
        assert set(q) >= {"body", "terms", "must_all", "size", "allowed"}
        text = " ".join(word(t) for t in q["terms"])
        assert q["body"] == {"query": {"match_phrase": {"body": text}}, "size": 10}
    assert pool.limits == BASE and pool.keeps is None  # scored hits: check_hits
    assert config["guarantees"]["score_rel_tol"] == 1e-5
    assert config["reduced"] == ["documents"] and config["documents"] == 50000
    assert (mix["clients"], mix["loop"], mix["pool"]) == (8, "closed", 96)


def test_expected_against_a_brute_force_scan_of_the_rendered_text(small, fam):
    ref, pool, _config, _mix = small
    texts = [json.loads(s)["body"].split() for s in ref.corpus.sources(0, ref.n_docs)]
    for q in pool.queries[:24]:
        words = [word(t) for t in q["terms"]]
        n = len(words)
        freq = np.array([sum(doc[i: i + n] == words for i in range(len(doc) - n + 1))
                         for doc in texts], np.int64)
        assert (fam.phrase_freq(ref, q["terms"]) == freq).all()
        scores, matched = fam.expected(ref, q)
        assert (matched == (freq > 0)).all() and matched.any()
        idf = np.float32(sum(float(ref.idf[t]) for t in q["terms"]))
        w = np.float32(idf * np.float32(K1 + 1.0))
        f = freq[matched].astype(np.float32)
        want = w * (f / (f + ref.denom[matched]))
        np.testing.assert_array_equal(scores[matched], want.astype(np.float32))
        assert (scores[~matched] == 0).all()


def test_the_fast_count_classes_as_the_plain_one_counts(small, fam):
    ref, pool, _config, mix = small
    params = mix["families"][0]["params"]
    occ = fam._Occurrences(ref)
    pools = fam.candidates(params, ref)
    for name, (lo, hi) in params["classes"].items():
        assert pools[name], name
        for terms in [t for by_n in pools[name].values() for t in by_n][:40]:
            df = int((fam.phrase_freq(ref, terms) > 0).sum())
            assert occ.doc_freq(terms) == df
            assert lo < df / ref.n_docs <= hi
    # a phrase never starts in one document and ends in the next
    starts = ref.corpus.starts()
    a, b = int(ref.corpus.tokens[starts[1] - 1]), int(ref.corpus.tokens[starts[1]])
    across = fam.phrase_starts(ref, (a, b))
    assert starts[1] - 1 not in across


def test_the_references_own_answer_passes_and_each_fault_fails(small, fam):
    ref, pool, _config, _mix = small
    i = next(j for j, q in enumerate(pool.queries)
             if fam.expected(ref, q)[1].sum() >= 12)
    q = pool.queries[i]
    scores, matched = fam.expected(ref, q)
    sound = hits_answer(ref, scores, matched, 10)
    assert _numbers(pool, ref, i, sound)[1]
    faults = {}
    # a phrase frequency one short in the best document: its score falls
    total, ranked = ref.top(scores, matched, 10)
    d = int(ranked[0])
    f = fam.phrase_freq(ref, q["terms"])[d].astype(np.float32)
    short = copy.deepcopy(sound)
    idf = np.float32(sum(float(ref.idf[t]) for t in q["terms"]))
    w = np.float32(idf * np.float32(K1 + 1.0))
    short["hits"]["hits"][0]["_score"] = float(
        w * ((f + 1) / ((f + 1) + ref.denom[d])))
    faults["rel_dev"] = short
    lost = copy.deepcopy(sound)
    lost["hits"]["total"] -= 1
    faults["total_off"] = lost
    fewer = copy.deepcopy(sound)
    fewer["hits"]["hits"].pop()
    faults["hits_off"] = fewer
    wrong = copy.deepcopy(sound)
    wrong["hits"]["hits"][3]["_id"] = str(int(np.flatnonzero(~matched)[0]))
    faults["not_matching"] = wrong
    partial = copy.deepcopy(sound)
    partial["_shards"]["successful"] = 0
    faults["not_whole"] = partial
    for number, resp in faults.items():
        numbers, passed = _numbers(pool, ref, i, resp)
        assert not passed and numbers[number] > pool.limits[number], number


def test_the_control_in_bfloat16_fails_on_the_scores(small):
    ref, pool, _config, _mix = small
    low = Reference(ref.corpus, K1, B, precision="bfloat16")
    got = Compared(pool.limits)
    for i in range(len(pool.queries)):
        got.add(pool.compare(ref, i, pool.answer(low, i), 1e-5))
    assert not got.passed
    assert got.numbers["rel_dev"] > 1e-4  # a hundred times the limit and more


@pytest.mark.parametrize("docs", [3000, 50000])
@pytest.mark.parametrize("seed", [11, 2**31 + 12, 3900000013])
def test_no_class_is_empty(fam, docs, seed):
    bench = registry.benchmark()
    config = registry.config(bench, "wikimedium-phrase-1shard")
    params = registry.mix("phrase")["families"][0]["params"]
    gen = registry.module("corpora", config["corpus"]["generator"])
    corpus = gen.generate(config["corpus"]["params"], seed, docs)
    again = gen.generate(config["corpus"]["params"], seed, docs)
    assert (corpus.tokens == again.tokens).all()
    assert corpus.collocations == again.collocations
    # the stream before the writes is zipf_text's own: the lengths are
    plain = registry.module("corpora", "zipf_text").generate(
        config["corpus"]["params"], seed, docs)
    assert (plain.lengths == corpus.lengths).all()
    assert 0.05 < (plain.tokens != corpus.tokens).mean() < 0.4
    pools = fam.candidates(params, Reference(corpus, K1, B))
    for name in params["classes"]:
        assert sum(len(v) for v in pools[name].values()) >= 20, (name, pools[name])
        assert set(pools[name]) == {2, 3, 4}


def test_the_rehearsal_never_says_correct(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    args = argparse.Namespace(workload="wiki.phrase", seed=2**31 + 39, seconds=3.0,
                              trace=1, docs=2000)
    rc = cell.run(args, time.perf_counter(),
                  settings={"warmup": {"pool_pass_max_seconds": 60, "rehearsals": 1}})
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    result = lines[-1]
    assert rc == 2 and result["correct"] is False
    assert all(line["rehearsal"] is True for line in lines[:-1])
    assert result["failed"] == 0
    assert all(v[0] <= v[1] for v in result["compared"].values()), result["compared"]
    assert result["metrics"]["phrase_served_share"]["value"] == 100.0
    assert result["metrics"]["device_served_share"]["value"] == 100.0
    assert result["metrics"]["position_mb_per_search"]["value"] > 0
    assert result["metrics"]["phrase_plan_ms"]["value"] > 0
    assert 0 < result["metrics"]["phrase_pad_share"]["value"] < 100
    assert result["metrics"]["one_trip_share"]["value"] == 100.0
    # the traced line holds every metric the cell is listed under and no other
    # (on the CPU no device trace: the three that read one are silent)
    bench = registry.benchmark()
    assert set(result["metrics"]) == {
        m["name"] for m in bench["per_layer"]
        if "wiki.phrase" in m.get("workloads", ())
        and m["source"] != "device_trace"}
