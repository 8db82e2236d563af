"""Answers that are not scored hits: a query family's own comparison and numbers
(`facet_terms`: a sound aggregation response passes, and a count off by one, a bucket
missing and a bucket under a wrong key each fail on the number that names it), the two
helpers of the plain reference (bucket counts, the ranking by a column and its check,
on responses recorded from the server), and the window's compact answer, which keeps
what the family asks for and nothing else."""

import copy
import datetime
import json
import os

import numpy as np
import pytest

from benchmark.harness import registry
from benchmark.harness.cell import Compared, Pool
from benchmark.harness.loadgen import _digest, as_response
from benchmark.harness.reference import (Reference, bucket_counts, check_sorted_hits,
                                         rank_by_column)

HERE = os.path.dirname(os.path.abspath(__file__))
K1, B = 1.2, 0.75
BASE = dict(registry.settings()["limits"], rel_dev=1e-5)


@pytest.fixture(scope="module")
def wiki():
    """The configuration of the cell `wiki.aggs` at 3,000 documents, and its pool."""
    bench = registry.benchmark()
    cell = registry.cell(bench, "wiki.aggs")
    config = registry.config(bench, cell["config"])
    corpus = registry.module("corpora", config["corpus"]["generator"]).generate(
        config["corpus"]["params"], 31, 3000)
    ref = Reference(corpus, K1, B)
    mix = dict(registry.mix(cell["traffic"]), pool=64)
    return ref, Pool(mix, ref, "/bench/_search", BASE), config


def _passes(pool, numbers):
    got = Compared(pool.limits)
    got.add(numbers)
    return got.passed


def test_the_family_brings_its_numbers_and_limits(wiki):
    ref, pool, _config = wiki
    assert pool.limits == {**BASE, "agg_buckets_off": 0, "agg_counts_off": 0}
    assert list(pool.limits)[:len(BASE)] == list(BASE)  # the shared numbers first
    assert pool.keeps == [{"response": ["aggregations"]}] * len(pool.queries)
    for i, q in enumerate(pool.queries):
        body = q["body"]
        assert body["size"] == 10 and "filtered" not in body["query"]
        assert {a["date_histogram"]["interval"] for a in body["aggs"].values()} == \
            {"year", "month"}
        assert all(a["date_histogram"]["min_doc_count"] == 1
                   for a in body["aggs"].values())
        numbers = pool.compare(ref, i, pool.answer(ref, i), 1e-5)
        assert set(numbers) == set(pool.limits)
        assert not any(numbers.values()) and _passes(pool, numbers)


def test_the_reference_buckets_are_the_calendars(wiki):
    """The buckets against a count made document by document with `datetime`."""
    ref, pool, config = wiki
    first = datetime.date.fromisoformat(config["corpus"]["params"]["date"]["first_day"])
    days = ref.corpus.columns["date"]
    q = pool.queries[0]
    _scores, matched = pool.family[0].expected(ref, q)
    want = {"by_year": {}, "by_month": {}}
    for d in np.flatnonzero(matched):
        day = first + datetime.timedelta(days=int(days[d]))
        for name, start in (("by_year", day.replace(month=1, day=1)),
                            ("by_month", day.replace(day=1))):
            key = int(datetime.datetime(start.year, start.month, 1,
                                        tzinfo=datetime.timezone.utc).timestamp()) * 1000
            want[name][key] = want[name].get(key, 0) + 1
    served = pool.answer(ref, 0)["aggregations"]
    for name in want:
        got = {b["key"]: b["doc_count"] for b in served[name]["buckets"]}
        assert got == want[name] and len(got) > 1
        assert [b["key"] for b in served[name]["buckets"]] == sorted(got)
    assert served["by_year"]["buckets"][0]["key_as_string"].endswith("-01-01T00:00:00.000Z")


def _doctored(wiki, change):
    ref, pool, _config = wiki
    i = max(range(len(pool.queries)),
            key=lambda j: len(pool.answer(ref, j)["aggregations"]["by_month"]["buckets"]))
    resp = copy.deepcopy(pool.answer(ref, i))
    change(resp["aggregations"])
    numbers = pool.compare(ref, i, resp, 1e-5)
    assert not _passes(pool, numbers)
    # the hits of the response are untouched: only the family's numbers move
    assert not any(v for k, v in numbers.items() if not k.startswith("agg_"))
    return numbers


def test_a_count_off_by_one_fails_on_the_counts(wiki):
    def change(aggs):
        aggs["by_month"]["buckets"][3]["doc_count"] += 1
    assert _doctored(wiki, change) == {**{k: 0 for k in BASE}, "rel_dev": 0.0,
                                       "agg_buckets_off": 0, "agg_counts_off": 1}


def test_a_missing_bucket_fails_on_the_buckets(wiki):
    lost = []

    def change(aggs):
        lost.append(aggs["by_year"]["buckets"].pop(2))
    numbers = _doctored(wiki, change)
    assert numbers["agg_buckets_off"] == 1
    assert numbers["agg_counts_off"] == lost[0]["doc_count"]


def test_a_bucket_under_a_wrong_key_fails_on_the_buckets(wiki):
    def change(aggs):
        aggs["by_month"]["buckets"][1]["key"] += 86_400_000.0  # a day late: local time
    assert _doctored(wiki, change)["agg_buckets_off"] == 2  # one missing, one extra


@pytest.mark.parametrize("change", [
    lambda aggs: aggs.pop("by_year"),
    lambda aggs: aggs["by_month"]["buckets"].reverse(),
    lambda aggs: aggs["by_month"]["buckets"].append(aggs["by_month"]["buckets"][-1])])
def test_no_aggregation_buckets_out_of_order_or_twice_fail(wiki, change):
    assert _doctored(wiki, change)["agg_buckets_off"] > 0


def test_bucket_counts_are_exact_and_refuse_a_value_outside_the_edges():
    column = np.array([0, 5, 5, 9, 10, 19, 20, 3])
    matched = np.array([1, 1, 0, 1, 1, 1, 0, 1], bool)
    assert bucket_counts(matched, column, [0, 5, 10, 20]).tolist() == [2, 2, 2]
    assert bucket_counts(~matched, column, [0, 10, 20, 30]).tolist() == [1, 0, 1]
    assert bucket_counts(np.zeros(8, bool), column, [0, 5]).tolist() == [0]
    with pytest.raises(ValueError):
        bucket_counts(matched, column, [0, 5, 10])


# -- hits sorted by a column ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """Responses of the server itself to `TermDTSort` searches (a term query sorted by
    the date field), with the corpus they were made over."""
    with open(os.path.join(HERE, "recorded", "sorted_hits.json")) as f:
        rec = json.load(f)
    corpus = registry.module("corpora", rec["generator"]).generate(
        rec["params"], rec["seed"], rec["documents"])
    first = datetime.date.fromisoformat(rec["params"]["date"]["first_day"])
    epoch_day = first.toordinal() - datetime.date(1970, 1, 1).toordinal()
    keys = (corpus.columns["date"] + epoch_day) * 86_400_000  # as a hit's `sort` states
    return Reference(corpus, K1, B), keys, rec["searches"]


def _sorted_numbers(recorded, search, resp):
    ref, keys, _ = recorded
    _scores, matched = ref.score_all([search["term"]], False)
    return check_sorted_hits(keys, matched, search["request"]["size"], resp,
                             search["order"] == "desc")


def test_the_servers_sorted_hits_pass(recorded):
    for search in recorded[2]:
        hits = search["response"]["hits"]["hits"]
        assert len(hits) == search["request"]["size"] and hits[0]["_score"] is None
        assert not any(_sorted_numbers(recorded, search, search["response"]).values())


def test_rank_by_column_breaks_ties_by_doc_id_both_ways():
    keys = np.array([5, 3, 5, 1, 3, 9])
    matched = np.array([1, 1, 1, 1, 1, 0], bool)
    assert rank_by_column(matched, keys, False)[1].tolist() == [3, 1, 4, 0, 2]
    assert rank_by_column(matched, keys, True)[1].tolist() == [0, 2, 1, 4, 3]
    assert rank_by_column(matched, keys, True)[0] == 5


def _ranks(recorded, search):
    """(a rank whose key is clear of both neighbours' and whose successor is too, a
    rank that ties with its successor) in the recorded response."""
    sort = [h["sort"][0] for h in search["response"]["hits"]["hits"]]
    clear = [i for i in range(1, len(sort) - 2)
             if len({sort[i - 1], sort[i], sort[i + 1], sort[i + 2]}) == 4]
    tied = [i for i in range(len(sort) - 1) if sort[i] == sort[i + 1]]
    return clear, tied


def test_two_hits_swapped_at_clear_ranks_fail(recorded):
    search = next(s for s in recorded[2] if _ranks(recorded, s)[0])
    i = _ranks(recorded, search)[0][0]
    resp = copy.deepcopy(search["response"])
    hits = resp["hits"]["hits"]
    hits[i], hits[i + 1] = hits[i + 1], hits[i]
    numbers = _sorted_numbers(recorded, search, resp)
    assert numbers["sort_ids_off"] == 2 and numbers["sort_keys_off"] == 2
    assert numbers["sort_ties_off"] == 0


def test_a_tie_broken_against_doc_id_fails(recorded):
    search = next(s for s in recorded[2] if _ranks(recorded, s)[1])
    i = _ranks(recorded, search)[1][0]
    resp = copy.deepcopy(search["response"])
    hits = resp["hits"]["hits"]
    assert int(hits[i]["_id"]) < int(hits[i + 1]["_id"])  # Lucene's order
    hits[i], hits[i + 1] = hits[i + 1], hits[i]
    numbers = _sorted_numbers(recorded, search, resp)
    assert numbers["sort_ties_off"] == 2
    assert numbers["sort_ids_off"] == 0 and numbers["sort_keys_off"] == 0


def test_a_sorted_answer_short_of_a_hit_or_with_a_stranger_fails(recorded):
    ref, _keys, searches = recorded
    search = next(s for s in searches if s["response"]["hits"]["total"] < ref.n_docs)
    resp = copy.deepcopy(search["response"])
    resp["hits"]["hits"].pop()
    assert _sorted_numbers(recorded, search, resp)["hits_off"] == 1
    resp = copy.deepcopy(search["response"])
    _scores, matched = ref.score_all([search["term"]], False)
    resp["hits"]["hits"][0]["_id"] = str(int(np.flatnonzero(~matched)[0]))
    assert _sorted_numbers(recorded, search, resp)["not_matching"] == 1


# -- the window's compact answer --------------------------------------------------

def test_the_compact_answer_round_trips_what_the_family_asked_to_keep(wiki, recorded):
    ref, pool, _config = wiki
    resp = pool.answer(ref, 0)
    resp["took"] = 3
    for h in resp["hits"]["hits"]:
        h["_source"] = {"body": "w1 w2"}
    whole, answer, spans = _digest(200, json.dumps(resp).encode(), pool.keeps[0])
    assert whole and spans is None and len(answer) == 4
    assert isinstance(answer[3], str)  # one object, whatever the number of buckets
    back = as_response(answer)
    assert back["aggregations"] == resp["aggregations"]
    assert "took" not in back and "_source" not in back["hits"]["hits"][0]
    assert not any(pool.compare(ref, 0, back, 1e-5).values())
    # each hit's sort values
    search = recorded[2][0]
    _w, answer, _s = _digest(200, json.dumps(search["response"]).encode(),
                             {"hit": ["sort"]})
    back = as_response(answer)
    assert [h["sort"] for h in back["hits"]["hits"]] == \
        [h["sort"] for h in search["response"]["hits"]["hits"]]
    assert not any(_sorted_numbers(recorded, search, back).values())


def test_a_family_that_asks_for_nothing_is_kept_as_before(wiki):
    ref, pool, _config = wiki
    body = json.dumps(pool.answer(ref, 0)).encode()
    _whole, answer, _spans = _digest(200, body)
    total, ids, scores = answer  # three parts, no more
    back = as_response(answer)
    assert set(back) == {"_shards", "timed_out", "hits"}
    assert all(set(h) == {"_id", "_score"} for h in back["hits"]["hits"])
    assert back["hits"]["total"] == total and len(ids) == len(scores) == 10
    bench = registry.benchmark()
    for w in bench["workloads"]:
        mix = registry.mix(w["traffic"])
        mods = [registry.module("queries", f["family"]) for f in mix["families"]]
        if not any(hasattr(m, "KEEP") for m in mods):
            small = Pool(dict(mix, pool=4), ref, "/bench/_search", BASE)
            assert small.keeps is None and small.limits == BASE
