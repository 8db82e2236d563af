"""The query family of the cell `wiki.multiterm`: `multiterm_terms` (prefixes and
wildcards over the harness's `w<n>` spellings) against a brute-force scan of the text
the generator renders; each fault on the number that names it (a term short →
`total_off`, a document that does not match → `not_matching`, ties out of order →
`order_ids_off`, a bfloat16 boost → `rel_dev`); the control in bfloat16, which fails
on the boosted searches' scores on three seeds; the pool's make-up and its block rows
at the cell's own size, every search under the ladder's last rung; and the cell's CPU
rehearsal, which never says correct."""

import argparse
import copy
import fnmatch
import json
import time

import numpy as np
import pytest

from benchmark.harness import cell, registry
from benchmark.harness.cell import Compared, Pool
from benchmark.harness.reference import Reference, hits_answer, word

K1, B = 1.2, 0.75
BASE = dict(registry.settings()["limits"], rel_dev=1e-5)
LIMITS = dict(BASE, order_ids_off=0)


def _cell(docs: int, pool: int, seed: int):
    bench = registry.benchmark()
    cell_ = registry.cell(bench, "wiki.multiterm")
    config = registry.config(bench, cell_["config"])
    corpus = registry.module("corpora", config["corpus"]["generator"]).generate(
        config["corpus"]["params"], seed, docs)
    ref = Reference(corpus, K1, B)
    mix = dict(registry.mix(cell_["traffic"]), pool=pool)
    return ref, Pool(mix, ref, "/bench/_search", BASE), config, mix


@pytest.fixture(scope="module")
def small():
    return _cell(3000, 96, 2**31 + 41)


@pytest.fixture(scope="module")
def fam():
    return registry.module("queries", "multiterm_terms")


def _numbers(pool, ref, i, resp):
    got = Compared(pool.limits)
    numbers = pool.compare(ref, i, resp, 1e-5)
    got.add(numbers)
    return numbers, got.passed


def _one(pool, task, boosted=None):
    """A search of `task` (with a boost or without), and one that matches more than
    its page holds."""
    return next(i for i, q in enumerate(pool.queries)
                if q["task"] == task and len(q["terms"]) > 3
                and (boosted is None or (q["boost"] != 1.0) == boosted))


def test_the_mix_is_the_three_tasks_by_weight(small):
    ref, pool, config, mix = small
    tasks = [q["task"] for q in pool.queries]
    assert set(tasks) == {"PrefixNarrow", "PrefixWide", "Wildcard"}
    # 3 : 1 : 4 of 96, by the plan's own draw
    assert 24 <= tasks.count("PrefixNarrow") <= 48
    assert 4 <= tasks.count("PrefixWide") <= 22
    assert 36 <= tasks.count("Wildcard") <= 60
    boosted = [q for q in pool.queries if q["boost"] != 1.0]
    assert 30 <= len(boosted) <= 66
    assert {q["boost"] for q in boosted} == {1.1, 1.3, 1.7, 2.3}
    for q in pool.queries:
        assert set(q) >= {"body", "terms", "must_all", "size", "allowed"}
        (kind, spec), = q["body"]["query"].items()
        opts = spec["body"]
        assert q["body"]["size"] == 10 and set(opts) <= {"value", "boost"}
        digits = q["head"][1:]
        if q["task"] == "PrefixNarrow":
            assert kind == "prefix" and opts["value"] == q["head"] and len(digits) == 3
        elif q["task"] == "PrefixWide":
            assert kind == "prefix" and opts["value"] == q["head"] and len(digits) == 2
        else:
            assert kind == "wildcard" and len(digits) == 2 and len(q["tail"]) == 1
            assert opts["value"] == f"{q['head']}*{q['tail']}"
        assert opts.get("boost", 1.0) == q["boost"]
    assert pool.limits == LIMITS and pool.keeps == [{"hit": []}] * len(pool.queries)
    assert config["guarantees"]["score_rel_tol"] == 1e-5
    assert config["reduced"] == ["documents"] and config["documents"] == 50000
    assert config["must_not_rise"] == ["search_serving.launch.mask_put_bytes"]
    assert (mix["clients"], mix["loop"], mix["pool"], mix["warmup_clients"]) \
        == (8, "closed", 96, 8)
    source = registry.config(registry.benchmark(), "wikimedium-1shard")
    for key in ("index", "corpus", "similarity", "bulk_documents_per_request"):
        assert config[key] == source[key]  # wikimedium-1shard's, letter for letter


def test_the_same_patterns_on_every_seed():
    _ref, pool, _config, _mix = _cell(500, 48, 7)
    _ref2, pool2, _config2, _mix2 = _cell(500, 48, 8)
    assert [q["body"] for q in pool.queries] == [q["body"] for q in pool2.queries]


def test_expected_against_a_brute_force_scan_of_the_rendered_text(small, fam):
    """No dictionary and no postings: every document's own tokens against the
    pattern, by `fnmatch` (another matcher than the family's string tests)."""
    ref, pool, _config, _mix = small
    texts = [json.loads(s)["body"].split() for s in ref.corpus.sources(0, ref.n_docs)]
    for q in pool.queries[:32]:
        (_kind, spec), = q["body"]["query"].items()
        value = spec["body"]["value"]
        pattern = value if "*" in value else value + "*"
        want = np.array([any(fnmatch.fnmatchcase(t, pattern) for t in doc)
                         for doc in texts])
        scores, matched = fam.expected(ref, q)
        assert (matched == want).all()
        assert set(np.unique(scores[matched]).tolist()) <= {float(np.float32(q["boost"]))}
        assert not scores[~matched].any()
        named = {word(t) for t in q["terms"]}
        assert named == {t for doc in texts for t in doc
                         if fnmatch.fnmatchcase(t, pattern)}


def test_the_reference_passes_its_own_answer(small):
    ref, pool, _config, _mix = small
    for i in range(len(pool.queries)):
        numbers, passed = _numbers(pool, ref, i, pool.answer(ref, i))
        assert passed and set(numbers) == set(LIMITS)


@pytest.mark.parametrize("task", ["PrefixNarrow", "PrefixWide", "Wildcard"])
def test_a_term_short_shows_on_the_total(small, fam, task):
    """An expansion that loses one term: the documents only that term brought are
    gone from the total."""
    ref, pool, _config, _mix = small
    i = _one(pool, task)
    q = pool.queries[i]
    lone = np.zeros(ref.n_docs, int)
    for t in q["terms"]:
        lone[ref.postings(t)[0]] += 1
    short = next(t for t in reversed(q["terms"])
                 if (lone[ref.postings(t)[0]] == 1).any())
    matched = np.zeros(ref.n_docs, bool)
    for t in q["terms"]:
        if t != short:
            matched[ref.postings(t)[0]] = True
    scores = np.where(matched, np.float32(q["boost"]), np.float32(0))
    numbers, passed = _numbers(pool, ref, i, hits_answer(ref, scores, matched, 10))
    assert numbers["total_off"] > 0 and not passed


def test_a_document_that_does_not_match_shows(small, fam):
    ref, pool, _config, _mix = small
    i = _one(pool, "PrefixNarrow")
    q = pool.queries[i]
    scores, matched = fam.expected(ref, q)
    resp = copy.deepcopy(pool.answer(ref, i))
    resp["hits"]["hits"][0]["_id"] = str(int(np.flatnonzero(~matched)[0]))
    numbers, passed = _numbers(pool, ref, i, resp)
    assert numbers["not_matching"] == 1 and not passed


def test_ties_out_of_order_show_on_order_ids_off_alone(small):
    """Every hit of a search scores the same: `check_hits` clears no rank, and a
    page of the right documents in another order passes all of its numbers."""
    ref, pool, _config, _mix = small
    i = _one(pool, "Wildcard")
    resp = copy.deepcopy(pool.answer(ref, i))
    hits = resp["hits"]["hits"]
    assert len(hits) > 2
    hits[0], hits[1] = hits[1], hits[0]
    numbers, passed = _numbers(pool, ref, i, resp)
    assert numbers["order_ids_off"] == 2 and not passed
    assert all(v == 0 for k, v in numbers.items() if k != "order_ids_off")
    # a later match in an earlier one's place
    resp = copy.deepcopy(pool.answer(ref, i))
    _scores, matched = pool.family[i].expected(ref, pool.queries[i])
    later = np.flatnonzero(matched)
    if len(later) > 10:
        resp["hits"]["hits"][-1]["_id"] = str(int(later[10]))
        numbers, passed = _numbers(pool, ref, i, resp)
        assert numbers["order_ids_off"] == 1 and numbers["not_matching"] == 0
        assert not passed


def test_a_bfloat16_boost_shows_on_rel_dev(small):
    ref, pool, _config, _mix = small
    low = Reference(ref.corpus, K1, B, precision="bfloat16")
    i = _one(pool, "PrefixNarrow", boosted=True)
    numbers, passed = _numbers(pool, ref, i, pool.answer(low, i))
    assert numbers["rel_dev"] > 1e-3 and not passed
    assert all(v == 0 for k, v in numbers.items() if k != "rel_dev")
    # a search with no boost scores 1.0, which bfloat16 holds: nothing to see
    j = _one(pool, "PrefixNarrow", boosted=False)
    numbers, passed = _numbers(pool, ref, j, pool.answer(low, j))
    assert numbers["rel_dev"] == 0 and passed


@pytest.mark.parametrize("seed", [2**31 + 11, 12, 13])
def test_the_control_is_not_correct(seed):
    from benchmark import control

    line = control.read("wiki.multiterm", seed, 3000, "bfloat16")
    assert not line["passed"]
    numbers = line["numbers"]
    assert numbers["rel_dev"]["value"] > 1e-3
    assert all(v["value"] == 0 for k, v in numbers.items() if k != "rel_dev")
    assert line["searches_past_the_limit"] >= 16  # half of the sample is boosted


def test_the_pool_at_the_cells_own_size_sits_under_the_last_rung():
    """50,000 documents and the whole pool of 512: the block rows a search names (a
    term's postings in blocks of 128) by task, the widest under 8,192."""
    t0 = time.perf_counter()
    ref, pool, _config, mix = _cell(50000, 512, 2**31 + 7)
    assert time.perf_counter() - t0 < 120
    assert registry.mix("multiterm")["pool"] == len(pool.queries) == 512
    rows = {}
    for q in pool.queries:
        n = int(((ref.df[q["terms"]] + 127) // 128).sum())
        rows.setdefault(q["task"], []).append(n)
    assert 40 <= min(rows["PrefixNarrow"]) and max(rows["PrefixNarrow"]) <= 1000
    assert 500 <= min(rows["PrefixWide"]) and max(rows["PrefixWide"]) < 8192
    assert 40 <= min(rows["Wildcard"]) and max(rows["Wildcard"]) <= 1000
    everything = sum(rows.values(), [])
    first = sum(n <= 256 for n in everything) / len(everything)
    assert 0.65 <= first <= 0.9  # most searches ride the first rung's shared launch
    assert sum(n > 2048 for n in everything) >= 1  # and the pool meets the last


@pytest.mark.parametrize("seed", [2**31 + 7, 12, 2026, 4100000031])
def test_the_widest_prefix_keeps_its_room_under_the_last_rung(seed):
    """`w10` names the most block rows of anything the mix can draw. A run whose
    seed pushed it past the ladder's last rung (8,192) would answer it on the host
    and read `correct: false` on `search_serving.host`. Over 24 seeds at the cell's
    size it reads 7,419-7,594 (mean 7,515, a standard deviation of 42: PERF.md
    section 6, PR 41): the ceiling is 16 deviations up. Held here to 5% of room."""
    bench = registry.benchmark()
    config = registry.config(bench, registry.cell(bench, "wiki.multiterm")["config"])
    params, n_docs = config["corpus"]["params"], config["documents"]
    corpus = registry.module("corpora", config["corpus"]["generator"]).generate(
        params, seed, n_docs)
    vocabulary = params["vocabulary"]
    pairs = np.unique(np.repeat(np.arange(n_docs), corpus.lengths) * vocabulary
                      + corpus.tokens)
    df = np.bincount(pairs % vocabulary, minlength=vocabulary)
    rows = (df + 127) // 128
    widest = max(
        int(rows[[n for n in range(vocabulary) if word(n).startswith(f"w{dd}")]].sum())
        for dd in range(10, 20))
    assert 7000 < widest <= 0.95 * 8192


def test_the_cells_rehearsal_never_says_correct(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    args = argparse.Namespace(workload="wiki.multiterm", seed=2**31 + 5, seconds=4.0,
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
    assert metrics["multiterm_served_share"]["value"] == 100.0
    assert metrics["unscored_plan_share"]["value"] == 100.0
    assert metrics["device_served_share"]["value"] == 100.0
    assert metrics["mask_mb_per_search"]["value"] == 0.0
    assert metrics["multiterm_expand_ms"]["value"] > 0
    assert 0 < metrics["multiterm_pad_share"]["value"] < 100
    assert metrics["multiterm_mb_per_search"]["value"] > 0
    assert metrics["multiterm_terms_per_search"]["value"] > 0
    compared = result["compared"]
    assert compared["rose.search_serving.launch.mask_put_bytes"] == [0, 0]
    assert compared["rose.search_serving.host"] == [0, 0]
    assert compared["window.order_ids_off"] == [0, 0]
    # the traced line holds every metric the cell is listed under and no other
    # (on the CPU no device trace: the three that read one are silent)
    bench = registry.benchmark()
    assert set(metrics) == {
        m["name"] for m in bench["per_layer"]
        if "wiki.multiterm" in m.get("workloads", ())
        and m["source"] != "device_trace"}
