"""The numpy reference against a brute-force scorer, document by document, on a
200-document corpus, with and without a filter; and the control, which has to fail."""

import math

import numpy as np
import pytest

from benchmark.harness import registry
from benchmark.harness.reference import (Reference, byte315_to_float, check_hits,
                                         float_to_byte315, hits_answer)

PARAMS = {"vocabulary": 300, "mean_length": 30, "min_length": 5, "max_length": 100,
          "zipf_a": 1.35, "text_field": "body",
          "date": {"field": "date", "first_day": "2006-01-01", "days": 100}}
K1, B = 1.2, 0.75


@pytest.fixture(scope="module")
def corpus():
    return registry.module("corpora", "zipf_text").generate(PARAMS, 7, 200)


def brute_force(corpus, terms, must_all, allowed=None):
    """BM25 as Lucene 4.x defines it, one document at a time, in Python floats; only
    the norm byte and the final float32 are the reference's."""
    starts = corpus.starts()
    docs = [corpus.tokens[starts[i]: starts[i + 1]].tolist()
            for i in range(corpus.n_docs)]
    n = len(docs)
    avgdl = sum(len(d) for d in docs) / n
    out = {}
    for i, d in enumerate(docs):
        if allowed is not None and not allowed(i):
            continue
        present = [t for t in terms if t in d]
        if (must_all and len(present) < len(terms)) or not present:
            continue
        byte = float_to_byte315(np.float32(1.0 / math.sqrt(len(d))))
        f = float(byte315_to_float(byte))
        dl = np.float32(1.0 / (f * f))
        score = np.float32(0)
        for t in present:
            df = sum(1 for other in docs if t in other)
            idf = np.float32(math.log(1.0 + (n - df + 0.5) / (df + 0.5)))
            tf = np.float32(d.count(t))
            denom = np.float32(K1 * (1.0 - B + B * dl / np.float32(avgdl)))
            score += np.float32(idf * np.float32(K1 + 1.0)) * (tf / (tf + denom))
        out[i] = float(score)
    return out


@pytest.mark.parametrize("must_all", [False, True])
@pytest.mark.parametrize("filtered", [False, True])
def test_reference_matches_brute_force(corpus, must_all, filtered):
    ref = Reference(corpus, K1, B)
    terms = [int(t) for t in ref.by_df[[0, 3, 40]]]
    col = corpus.columns["date"]
    mask = (col >= 20) & (col < 70) if filtered else None
    scores, matched = ref.score_all(terms, must_all, mask)
    want = brute_force(corpus, terms, must_all,
                       (lambda i: 20 <= col[i] < 70) if filtered else None)
    assert set(np.flatnonzero(matched).tolist()) == set(want)
    assert want, "the case matches nothing: it tests nothing"
    for i, s in want.items():
        assert scores[i] == pytest.approx(s, rel=2e-6)


def test_check_hits_passes_its_own_answer_and_catches_each_fault(corpus):
    ref = Reference(corpus, K1, B)
    terms = [int(t) for t in ref.by_df[[1, 5]]]
    scores, matched = ref.score_all(terms, False)
    good = hits_answer(ref, scores, matched, 10)
    assert not any(check_hits(ref, scores, matched, 10, good, 1e-5).values())

    def numbers(resp):
        return check_hits(ref, scores, matched, 10, resp, 1e-5)

    import copy
    bad = copy.deepcopy(good)
    bad["hits"]["total"] += 1
    assert numbers(bad)["total_off"] == 1
    bad = copy.deepcopy(good)
    bad["hits"]["hits"][0]["_score"] *= 1.001
    assert numbers(bad)["rel_dev"] > 1e-5
    bad = copy.deepcopy(good)
    bad["hits"]["hits"].pop()
    assert numbers(bad)["hits_off"] == 1
    bad = copy.deepcopy(good)
    bad["_shards"]["failed"] = 1
    assert numbers(bad)["not_whole"] == 1
    bad = copy.deepcopy(good)
    bad["timed_out"] = True
    assert numbers(bad)["not_whole"] == 1
    bad = copy.deepcopy(good)
    unmatched = int(np.flatnonzero(~matched)[0])
    bad["hits"]["hits"][3]["_id"] = str(unmatched)
    assert numbers(bad)["not_matching"] == 1
    # an approximate top-k: a lower-ranked document in a hit's place
    _total, ranked = ref.top(scores, matched, 10)
    bad = copy.deepcopy(good)
    bad["hits"]["hits"][2] = {"_id": str(int(ranked[40])),
                              "_score": float(scores[ranked[40]])}
    n = numbers(bad)
    assert n["rel_dev"] > 1e-5 or n["ids_off"] > 0


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("workload", [w["name"] for w in
                                      registry.benchmark()["workloads"]])
def test_the_control_fails(workload, seed):
    """The reference in bfloat16, put in the program's place, is not correct; in
    float32 (the control of the control) it is."""
    from benchmark import control

    low = control.read(workload, seed, 3000, "bfloat16")
    assert not low["passed"]
    assert low["numbers"]["rel_dev"]["value"] > 100 * low["numbers"]["rel_dev"]["limit"]
    same = control.read(workload, seed, 3000, "float32")
    assert same["passed"] and same["numbers"]["rel_dev"]["value"] == 0
