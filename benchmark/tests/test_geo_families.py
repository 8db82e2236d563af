"""The query family of the cell `geonames.scoring`: `geo_ops` (five operations of the
Rally track `geonames`) against the host scorer's own responses, recorded from the
server at 3,000 places (`recorded/geo_ops.json`), and against a brute-force pass over
the `_source` lines the generator renders; the control in bfloat16, which fails on the
scores; a response whose one bucket sum is float32-rounded, which fails on
`agg_sum_off` alone; each other fault of the facet on the number that names it; and
the generator, which is deterministic in `--seed` and always holds an odd population
over 2^24."""

import copy
import json
import math
import os

import numpy as np
import pytest

from benchmark.harness import registry
from benchmark.harness.cell import Compared, Pool
from benchmark.harness.loadgen import _digest, as_response
from benchmark.harness.reference import Reference

HERE = os.path.dirname(os.path.abspath(__file__))
K1, B = 1.2, 0.75
BASE = dict(registry.settings()["limits"], rel_dev=1e-5)
OPS = ("field_value", "gauss", "expression", "country_agg", "term")
EARTH_M = 6371008.7714


def _cell(docs: int, pool: int, seed: int):
    bench = registry.benchmark()
    cell = registry.cell(bench, "geonames.scoring")
    config = registry.config(bench, cell["config"])
    corpus = registry.module("corpora", config["corpus"]["generator"]).generate(
        config["corpus"]["params"], seed, docs)
    ref = Reference(corpus, K1, B)
    mix = dict(registry.mix(cell["traffic"]), pool=pool)
    return ref, Pool(mix, ref, "/bench/_search", BASE), config


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded", "geo_ops.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def geo(recorded):
    return _cell(recorded["documents"], recorded["pool"], recorded["seed"])


@pytest.fixture(scope="module")
def places(geo):
    """The corpus as a client would read it back: one parsed `_source` a place."""
    ref, _pool, _config = geo
    return [json.loads(s) for s in ref.corpus.sources(0, ref.n_docs)]


def _numbers(pool, ref, i, resp):
    got = Compared(pool.limits)
    numbers = pool.compare(ref, i, resp, 1e-5)
    got.add(numbers)
    return numbers, got.passed


def _of(pool, op):
    return [i for i, q in enumerate(pool.queries) if q["op"] == op]


def test_the_mix_is_five_of_the_tracks_operations_in_equal_parts(geo):
    ref, pool, config = geo
    ops = [q["op"] for q in pool.queries]
    assert set(ops) == set(OPS) and all(ops.count(o) == 12 for o in OPS)
    assert pool.limits == {**BASE, "agg_buckets_off": 0, "agg_counts_off": 0,
                           "agg_sum_off": 0}
    assert pool.keeps == [{"response": ["aggregations"]}] * len(pool.queries)
    assert config["guarantees"]["score_rel_tol"] == 1e-5
    assert config["search"]["params"] == {"request_cache": "false"}
    assert config["must_not_rise"] == ["search_serving.request_cache_hits"]
    origins = set()
    for q in pool.queries:
        body = q["body"]
        assert body["size"] == (0 if q["op"] == "country_agg" else 10)
        if q["op"] in ("field_value", "gauss", "expression"):
            fs = body["query"]["function_score"]
            assert fs["query"] == {"match_all": {}} and len(fs["functions"]) == 1
            if q["op"] == "gauss":
                spec = fs["functions"][0]["gauss"]["location"]
                assert (spec["scale"], spec["offset"], spec["decay"]) == \
                    ("500km", "0km", 0.1)
                origins.add((spec["origin"]["lat"], spec["origin"]["lon"]))
        elif q["op"] == "country_agg":
            assert body["aggs"] == {"country_population": {
                "terms": {"field": "country_code.raw"},
                "aggs": {"sum_pop": {"sum": {"field": "population"}}}}}
        else:
            assert list(body["query"]) == ["term"]
    # an origin a search, each one of the corpus' own places but for the track's own,
    # which the pool's first gauss search sends; a large place may be drawn twice, and
    # two searches from one place are one body (at 3,000 places the three largest hold
    # most of the weight; at the cell's 80,000 and pool of 512, 82-88 of the 102 are distinct)
    own = set(zip(ref.corpus.degrees("lat").tolist(), ref.corpus.degrees("lon").tolist()))
    assert (52.37, 4.8951) in origins and origins - {(52.37, 4.8951)} <= own
    assert 4 <= len(origins) <= 12
    drawn = [int(np.flatnonzero((ref.corpus.degrees("lat") == lat)
                                & (ref.corpus.degrees("lon") == lon))[0])
             for lat, lon in origins - {(52.37, 4.8951)}]
    assert (ref.corpus.columns["population"][drawn] > 0).all()
    for op in ("field_value", "expression", "country_agg"):
        assert len({json.dumps(pool.queries[i]["body"]) for i in _of(pool, op)}) == 1
    assert len({json.dumps(pool.queries[i]["body"]) for i in _of(pool, "term")}) > 1


def test_the_generator_keeps_the_tracks_shapes(geo, places):
    ref, _pool, config = geo
    properties = set(config["index"]["mappings"]["doc"]["properties"])
    assert set().union(*places) == properties  # every field of the mapping occurs
    always = {"geonameid", "name", "asciiname", "feature_class", "feature_code",
              "country_code", "population", "dem", "timezone", "location"}
    assert all(always <= set(p) for p in places)
    assert ref.corpus.lengths.tolist() == [1] * ref.n_docs
    assert all(p["country_code"] == ref.corpus.country(int(t))
               for p, t in zip(places, ref.corpus.tokens))
    pops = np.array([p["population"] for p in places])
    assert 0.55 < (pops == 0).mean() < 0.7 and pops.max() <= 25_000_000
    # float32 cannot hold the column: an odd value over 2^24, in every corpus
    assert ((pops > 1 << 24) & (pops % 2 == 1)).any()
    points = {tuple(p["location"]) for p in places}
    assert len(points) == len(places)  # no two places on one point
    assert all(-180 <= lon <= 180 and -90 <= lat <= 90 for lon, lat in points)
    # the double a server parses is the double the reference divides out
    assert [p["location"][1] for p in places] == ref.corpus.degrees("lat").tolist()
    assert [p["location"][0] for p in places] == ref.corpus.degrees("lon").tolist()
    share = np.bincount(ref.corpus.tokens) / ref.n_docs
    assert 0.1 < share.max() < 0.25 and (share > 0).sum() > 100


@pytest.mark.parametrize("seed", [35, 2**31 + 19, 3_500_000_001])
def test_the_generator_is_deterministic_and_always_holds_a_large_odd_population(seed):
    gen = registry.module("corpora", "geonames")
    params = registry.config(registry.benchmark(), "geonames-1shard")["corpus"]["params"]
    a, b = gen.generate(params, seed, 500), gen.generate(params, seed, 500)
    assert a.sources(0, 500) == b.sources(0, 500)
    pop = a.columns["population"]
    assert ((pop > 1 << 24) & (pop % 2 == 1)).sum() >= 1
    assert not np.array_equal(pop.astype(np.float32).astype(np.int64), pop)
    other = gen.generate(params, seed + 1, 500)
    assert other.sources(0, 50) != a.sources(0, 50)
    docs, columns = gen.late_documents(params, a, seed + 2, 10)
    grown = a.extended(docs, columns)
    late = [json.loads(s) for s in grown.sources(500, 510)]
    assert [p["country_code"] for p in late] == ["w%d" % (250 + j) for j in range(10)]


def test_the_host_scorers_recorded_responses_pass(geo, recorded):
    """Every operation of the mix, as the server itself answered it on the host scorer
    and the host collectors: every number 0, the scores within the tolerance."""
    ref, pool, _config = geo
    assert [s["op"] for s in recorded["searches"]] == [q["op"] for q in pool.queries]
    got = Compared(pool.limits)
    for s in recorded["searches"]:
        numbers = pool.compare(ref, s["pool_index"], s["response"], 1e-5)
        assert not any(v for k, v in numbers.items() if k != "rel_dev"), (s["op"], numbers)
        got.add(numbers)
    assert got.passed and got.numbers["rel_dev"] < 1e-6
    # and through the window's compact answer
    for s in recorded["searches"][::7]:
        i = s["pool_index"]
        _whole, answer, _spans = _digest(
            200, json.dumps(s["response"]).encode(), pool.keeps[i])
        numbers, passed = _numbers(pool, ref, i, as_response(answer))
        assert passed, (s["op"], numbers)


def _brute_function(q, place):
    """The operation's function of one place, from its `_source` and math alone."""
    pop = place["population"]
    lon, lat = place["location"]
    if q["op"] == "field_value":
        return math.log10(2 * pop + 1)
    if q["op"] == "expression":
        return abs(math.log(abs(pop) + 1) + lon + lat)
    origin = q["body"]["query"]["function_score"]["functions"][0]["gauss"][
        "location"]["origin"]
    p0, p1 = math.radians(origin["lat"]), math.radians(lat)
    a = math.sin((p1 - p0) / 2) ** 2 + math.cos(p0) * math.cos(p1) * math.sin(
        math.radians(lon - origin["lon"]) / 2) ** 2
    d = 2 * EARTH_M * math.asin(math.sqrt(min(1.0, a)))
    sigma2 = -(500_000.0 ** 2) / (2 * math.log(0.1))
    return math.exp(-d * d / (2 * sigma2))


def test_every_operation_against_a_brute_force_pass(geo, places):
    ref, pool, _config = geo
    for i, q in enumerate(pool.queries):
        resp = pool.answer(ref, i)
        hits = resp["hits"]["hits"]
        if q["op"] == "term":
            code = q["body"]["query"]["term"]["country_code.raw"]
            hit = [d for d, p in enumerate(places) if p["country_code"] == code]
            idf = math.log(1.0 + (ref.n_docs - len(hit) + 0.5) / (len(hit) + 0.5))
            assert resp["hits"]["total"] == len(hit)
            assert [int(h["_id"]) for h in hits] == hit[:10]
            assert all(h["_score"] == pytest.approx(idf, rel=1e-6) for h in hits)
        elif q["op"] == "country_agg":
            assert resp["hits"]["total"] == len(places) and hits == []
            by = {}
            for p in places:
                c, s = by.get(p["country_code"], (0, 0))
                by[p["country_code"]] = (c + 1, s + p["population"])
            want = sorted(by.items(), key=lambda kv: (-kv[1][0], kv[0]))[:10]
            got = resp["aggregations"]["country_population"]["buckets"]
            assert [(b["key"], (b["doc_count"], int(b["sum_pop"]["value"])))
                    for b in got] == want
        else:
            values = [_brute_function(q, p) for p in places]
            assert resp["hits"]["total"] == len(places)
            for h in hits:
                assert h["_score"] == pytest.approx(values[int(h["_id"])], rel=1e-6)
            top = sorted(values, reverse=True)[:10]
            assert [h["_score"] for h in hits] == pytest.approx(top, rel=1e-6)
        numbers, passed = _numbers(pool, ref, i, resp)
        assert passed and not any(numbers.values()), (q["op"], numbers)


@pytest.fixture(scope="module")
def larger():
    """30,000 places: the largest countries' summed populations pass 2^24."""
    return _cell(30_000, 20, 36)


def test_a_float32_rounded_bucket_sum_fails_on_agg_sum_off_alone(larger):
    """One bucket's populations added up in float32, as a program without exact
    integer sums adds them (the control's own sum): every other number sound."""
    ref, pool, _config = larger
    i = _of(pool, "country_agg")[0]
    resp = pool.answer(ref, i)
    low = pool.answer(Reference(ref.corpus, K1, B, precision="bfloat16"), i)
    pairs = list(zip(resp["aggregations"]["country_population"]["buckets"],
                     low["aggregations"]["country_population"]["buckets"]))
    b, rounded = next((b, r["sum_pop"]["value"]) for b, r in pairs
                      if r["sum_pop"]["value"] != b["sum_pop"]["value"])
    exact = int(b["sum_pop"]["value"])
    assert exact > 1 << 24 and rounded == float(np.float32(rounded))
    b["sum_pop"]["value"] = rounded
    numbers, passed = _numbers(pool, ref, i, resp)
    off = numbers.pop("agg_sum_off")
    assert not passed and off == abs(int(rounded) - exact) > 0
    assert not any(numbers.values())


@pytest.mark.parametrize("change, number", [
    (lambda b: b[0].update(doc_count=b[0]["doc_count"] + 1), "agg_counts_off"),
    (lambda b: b.pop(0), "agg_buckets_off"),
    (lambda b: b[0].update(key=b[0]["key"].lower()), "agg_buckets_off"),
    (lambda b: b.reverse(), "agg_buckets_off"),
    (lambda b: b[3]["sum_pop"].update(value=b[3]["sum_pop"]["value"] + 1.0),
     "agg_sum_off"),
    (lambda b: b[3]["sum_pop"].update(value=b[3]["sum_pop"]["value"] + 0.5),
     "agg_sum_off"),
    (lambda b: b[3].pop("sum_pop"), "agg_sum_off"),
], ids=["count_off_by_one", "bucket_missing", "bucket_under_another_key",
        "out_of_order", "sum_off_by_one", "sum_not_whole", "sum_missing"])
def test_facet_faults_fail_on_the_number_that_names_them(geo, change, number):
    ref, pool, _config = geo
    i = _of(pool, "country_agg")[0]
    resp = pool.answer(ref, i)
    change(resp["aggregations"]["country_population"]["buckets"])
    numbers, passed = _numbers(pool, ref, i, resp)
    assert not passed and numbers[number] > 0
    assert not any(numbers[k] for k in BASE)


def test_the_control_in_bfloat16_fails_on_the_scores(larger):
    ref, pool, _config = larger
    low = Reference(ref.corpus, K1, B, precision="bfloat16")
    got = Compared(pool.limits)
    by_op = {}
    for i, q in enumerate(pool.queries):
        numbers = pool.compare(ref, i, pool.answer(low, i), 1e-5)
        got.add(numbers)
        by_op[q["op"]] = max(by_op.get(q["op"], 0.0), numbers["rel_dev"])
    assert not got.passed and got.numbers["rel_dev"] > 100 * 1e-5
    assert got.numbers["total_off"] == 0 and got.numbers["agg_counts_off"] == 0
    assert got.numbers["agg_buckets_off"] == 0
    # each function score is off by itself; the control's float32 sums are off too
    assert all(by_op[op] > 1e-4 for op in ("field_value", "gauss", "expression"))
    assert got.numbers["agg_sum_off"] > 0


def test_a_swapped_pair_of_clear_hits_fails_on_ids_off(geo):
    ref, pool, _config = geo
    for i in _of(pool, "gauss"):
        resp = pool.answer(ref, i)
        s = [h["_score"] for h in resp["hits"]["hits"]]
        clear = [j for j in range(1, 8)
                 if all(abs(s[a] - s[a + 1]) > 1e-4 * s[a] for a in (j - 1, j, j + 1))]
        if not clear:
            continue
        j = clear[0]
        hits = copy.deepcopy(resp)["hits"]["hits"]
        hits[j]["_id"], hits[j + 1]["_id"] = hits[j + 1]["_id"], hits[j]["_id"]
        numbers, passed = _numbers(pool, ref, i, {**resp, "hits": {
            **resp["hits"], "hits": hits}})
        assert not passed and numbers["ids_off"] == 2
        return
    raise AssertionError("no gauss search of the pool has a clear pair of hits")
