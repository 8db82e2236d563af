"""The query families of the two cells over sorted and unscored answers: `log_ops` (the
Rally `http_logs` operations of `logs.dashboard`) against a brute-force pass over a
2,000-event log corpus, read back from the `_source` lines the generator renders and
not from its columns; `sorted_terms` (`wiki.datesort`); each fault on the number that
names it; the control's float32 sort key, which fails on the sort numbers; and the end of
a run: a rehearsal of `logs.dashboard` stopped by SIGTERM in its pool pass leaves no
child, as a sound run leaves none."""

import copy
import datetime
import json
import math
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark.harness import registry
from benchmark.harness.cell import Compared, Pool
from benchmark.harness.loadgen import _digest, as_response
from benchmark.harness.reference import Reference

K1, B = 1.2, 0.75
BASE = dict(registry.settings()["limits"], rel_dev=1e-5)


def _cell(name: str, docs: int, pool: int, seed: int = 31, **params):
    bench = registry.benchmark()
    cell = registry.cell(bench, name)
    config = registry.config(bench, cell["config"])
    corpus = registry.module("corpora", config["corpus"]["generator"]).generate(
        {**config["corpus"]["params"], **params}, seed, docs)
    ref = Reference(corpus, K1, B)
    mix = dict(registry.mix(cell["traffic"]), pool=pool)
    return ref, Pool(mix, ref, "/bench/_search", BASE), config


@pytest.fixture(scope="module")
def logs():
    return _cell("logs.dashboard", 2000, 140)


@pytest.fixture(scope="module")
def events(logs):
    """The corpus as a client would read it back: one parsed `_source` an event."""
    ref, _pool, _config = logs
    return [json.loads(s) for s in ref.corpus.sources(0, ref.n_docs)]


def _numbers(pool, ref, i, resp):
    got = Compared(pool.limits)
    numbers = pool.compare(ref, i, resp, 1e-5)
    got.add(numbers)
    return numbers, got.passed


def _of(pool, op, **more):
    return [i for i, q in enumerate(pool.queries) if q["op"] == op
            and all(q.get(k) == v for k, v in more.items())]


def test_the_mix_is_the_tracks_seven_operations_in_equal_parts(logs):
    ref, pool, config = logs
    ops = [(q["op"], q.get("descending"), tuple((q.get("status") or [None])[1:]))
           for q in pool.queries]
    assert set(ops) == {
        ("term", None, ()), ("range", None, ()), ("status", None, (200, 300)),
        ("status", None, (400, 500)), ("histogram", None, ()),
        ("sorted", True, ()), ("sorted", False, ())}
    assert all(ops.count(o) == 20 for o in set(ops))
    assert set(pool.limits) == set(BASE) | {
        "order_ids_off", "sort_keys_off", "sort_ids_off", "sort_ties_off",
        "agg_buckets_off", "agg_counts_off"}
    assert config["guarantees"]["score_rel_tol"] == 1e-5
    for q in pool.queries:
        body = q["body"]
        if q["op"] == "term":
            assert list(body["query"]) == ["term"]
            continue
        lo, hi = q["window"]
        assert lo < hi and hi % 60_000 == 0 and lo % 60_000 == 0
        if q["op"] == "range":
            assert body["query"] == {"range": {"@timestamp": {"gte": lo, "lt": hi}}}
            continue
        f = body["query"]["filtered"]
        # as Kibana and the track send it: no caching option of the benchmark's own
        assert f["filter"] == {"range": {"@timestamp": {"gte": lo, "lt": hi}}}
        assert body["size"] == (0 if q["op"] == "histogram" else 10)
    # the request cache is switched off for the whole configuration, in the URL
    assert config["search"]["params"] == {"request_cache": "false"}
    assert pool.path == "/bench/_search"  # (this test's own path)


def test_the_generator_keeps_the_tracks_shapes(logs, events):
    ref, _pool, config = logs
    assert set(events[0]) == set(config["index"]["mappings"]["doc"]["properties"])
    stamps = np.array([e["@timestamp"] for e in events])
    assert (stamps % 1000 == 0).all()  # seconds resolution, epoch milliseconds
    first = datetime.datetime(1998, 6, 8, tzinfo=datetime.timezone.utc).timestamp()
    assert stamps.min() >= first * 1000 and stamps.max() < (first + 7 * 86_400) * 1000
    # document order is close to time order and not equal to it (at the cell's
    # own density a third of the neighbours are out of order; here a few)
    assert 0 < (np.diff(stamps) < 0).mean() < 0.5
    assert ref.corpus.lengths.tolist() == [1] * ref.n_docs
    assert all(e["request"] == ref.corpus.request_line(int(t))
               for e, t in zip(events, ref.corpus.tokens))
    assert all(e["size"] == 0 for e in events if e["status"] == 304)
    again = registry.module("corpora", "web_logs").generate(
        config["corpus"]["params"], 31, 2000)
    assert again.sources(0, 50) == ref.corpus.sources(0, 50)


def _brute(events, q, n_docs):
    """The operation's answer by a loop over the events, and nothing of numpy."""
    if q["op"] == "term":
        line = q["body"]["query"]["term"]["request.raw"]["value"]
        hit = [i for i, e in enumerate(events) if e["request"] == line]
        idf = math.log(1.0 + (n_docs - len(hit) + 0.5) / (len(hit) + 0.5))
        return hit, idf * (K1 + 1.0) / (1.0 + K1)  # one term a field: tf 1, dl = avgdl
    lo, hi = q["window"]
    hit = [i for i, e in enumerate(events) if lo <= e["@timestamp"] < hi]
    if q["op"] == "status":
        _f, gte, lt = q["status"]
        hit = [i for i in hit if gte <= events[i]["status"] < lt]
    return hit, 1.0


def test_every_operation_against_a_brute_force_pass(logs, events):
    ref, pool, _config = logs
    for i, q in enumerate(pool.queries):
        hit, score = _brute(events, q, ref.n_docs)
        resp = pool.answer(ref, i)
        assert resp["hits"]["total"] == len(hit), q["body"]
        ids = [int(h["_id"]) for h in resp["hits"]["hits"]]
        if q["op"] == "sorted":
            order = sorted(hit, key=lambda d: (
                -events[d]["@timestamp"] if q["descending"] else events[d]["@timestamp"],
                d))
            assert ids == order[:10]
            assert [h["sort"] for h in resp["hits"]["hits"]] == \
                [[events[d]["@timestamp"]] for d in ids]
        elif q["op"] == "histogram":
            assert ids == []
            want = {}
            for d in hit:
                hour = events[d]["@timestamp"] // 3_600_000 * 3_600_000
                want[hour] = want.get(hour, 0) + 1
            got = resp["aggregations"]["by_hour"]["buckets"]
            assert [(b["key"], b["doc_count"]) for b in got] == sorted(want.items())
            assert all(b["key_as_string"].endswith(":00:00.000Z") for b in got)
        else:
            assert ids == hit[:10]  # equal scores: document order
            assert all(h["_score"] == pytest.approx(score, rel=1e-6)
                       for h in resp["hits"]["hits"])
        numbers, passed = _numbers(pool, ref, i, resp)
        assert passed and not any(numbers.values()), (q["op"], numbers)


def _first_with(pool, ref, op, want, **more):
    for i in _of(pool, op, **more):
        resp = pool.answer(ref, i)
        if want(resp):
            return i, resp
    raise AssertionError(f"no {op} search of the pool suits the test")


def test_constant_score_hits_out_of_document_order_fail_on_order_ids_off(logs):
    ref, pool, _config = logs
    i, resp = _first_with(pool, ref, "range", lambda r: len(r["hits"]["hits"]) >= 3)
    hits = resp["hits"]["hits"]
    hits[0], hits[1] = hits[1], hits[0]
    numbers, passed = _numbers(pool, ref, i, resp)
    # check_hits sees two hits of one score change places: a tie, no fault of its own
    assert not passed and numbers.pop("order_ids_off") == 2 and not any(numbers.values())
    # a hit past the first ten in the place of the tenth: still a match, still a tie
    i, resp = _first_with(pool, ref, "status",
                          lambda r: r["hits"]["total"] > 10, status=(
                              "status", 200, 300))
    stranger = int(np.flatnonzero(pool.family[i].expected(ref, pool.queries[i])[1])[10])
    resp["hits"]["hits"][-1]["_id"] = str(stranger)
    numbers, passed = _numbers(pool, ref, i, resp)
    assert not passed and numbers.pop("order_ids_off") == 1 and not any(numbers.values())


def test_sorted_faults_fail_on_the_sort_numbers(logs):
    ref, pool, _config = logs
    stamps = ref.corpus.columns["@timestamp"]

    def clear_pair(r):
        k = [h["sort"][0] for h in r["hits"]["hits"]]
        return len(k) == 10 and len({k[3], k[4], k[5], k[6]}) == 4
    i, resp = _first_with(pool, ref, "sorted", clear_pair)
    swapped = copy.deepcopy(resp)
    h = swapped["hits"]["hits"]
    h[4], h[5] = h[5], h[4]
    numbers, passed = _numbers(pool, ref, i, swapped)
    assert not passed and numbers["sort_ids_off"] == 2 and numbers["sort_keys_off"] == 2
    assert numbers["sort_ties_off"] == 0
    rounded = copy.deepcopy(resp)
    rounded["hits"]["hits"][2]["sort"] = [float(np.float32(h[2]["sort"][0])) + 1.0]
    numbers, passed = _numbers(pool, ref, i, rounded)
    assert not passed and numbers["sort_keys_off"] == 1 and numbers["sort_ids_off"] == 0

    def tied_pair(r):
        k = [h["sort"][0] for h in r["hits"]["hits"]]
        return any(a == b for a, b in zip(k, k[1:]))
    # events as dense as the cell's own: 20,000 in one day share seconds
    ref, pool, _config = _cell("logs.dashboard", 20_000, 70, seed=33, days=1,
                               bursts=[])
    stamps = ref.corpus.columns["@timestamp"]
    i, resp = _first_with(pool, ref, "sorted", tied_pair)
    h = resp["hits"]["hits"]
    j = next(j for j in range(len(h) - 1) if h[j]["sort"] == h[j + 1]["sort"])
    h[j], h[j + 1] = h[j + 1], h[j]
    numbers, passed = _numbers(pool, ref, i, resp)
    assert not passed and numbers["sort_ties_off"] == 2
    assert numbers["sort_ids_off"] == 0 and numbers["sort_keys_off"] == 0
    assert stamps[int(h[j]["_id"])] == stamps[int(h[j + 1]["_id"])]


@pytest.mark.parametrize("change, number", [
    (lambda b: b[0].update(doc_count=b[0]["doc_count"] + 1), "agg_counts_off"),
    (lambda b: b.pop(0), "agg_buckets_off"),
    (lambda b: b[0].update(key=b[0]["key"] + 1800_000), "agg_buckets_off"),
    (lambda b: b.reverse(), "agg_buckets_off"),
], ids=["count_off_by_one", "bucket_missing", "bucket_off_the_hour", "out_of_order"])
def test_histogram_faults_fail_on_the_number_that_names_them(logs, change, number):
    ref, pool, _config = logs
    i, resp = _first_with(
        pool, ref, "histogram",
        lambda r: len(r["aggregations"]["by_hour"]["buckets"]) >= 2)
    change(resp["aggregations"]["by_hour"]["buckets"])
    numbers, passed = _numbers(pool, ref, i, resp)
    assert not passed and numbers[number] > 0
    assert not any(numbers[k] for k in BASE)


def test_the_window_keeps_sort_values_and_buckets(logs):
    ref, pool, _config = logs
    assert pool.keeps == [{"response": ["aggregations"], "hit": ["sort"]}] * 140
    for op in ("sorted", "histogram", "status"):
        i = _of(pool, op)[0]
        sound = pool.answer(ref, i)
        for h in sound["hits"]["hits"]:
            h.update(_type="doc", _source={"request": "GET / HTTP/1.0"})
        _whole, answer, _spans = _digest(200, json.dumps(sound).encode(), pool.keeps[i])
        numbers, passed = _numbers(pool, ref, i, as_response(answer))
        assert passed and not any(numbers.values())


def test_a_float32_sort_key_fails_the_control_on_ids_and_ties():
    """The control's system holds float32 keys: 65,536 ms of 1998 are one key."""
    ref, pool, config = _cell("logs.dashboard", 20_000, 70, seed=32)
    low = Reference(ref.corpus, K1, B, precision="bfloat16")
    got = Compared(pool.limits)
    for i in _of(pool, "sorted"):
        got.add(pool.compare(ref, i, pool.answer(low, i), 1e-5))
    assert not got.passed
    assert got.numbers["sort_ids_off"] + got.numbers["sort_ties_off"] > 0
    assert got.numbers["sort_keys_off"] > 0 and got.numbers["total_off"] == 0
    # the unscored operations compute nothing in floating point: the control moves
    # none of them
    for op in ("range", "status", "histogram"):
        for i in _of(pool, op):
            numbers, passed = _numbers(pool, ref, i, pool.answer(low, i))
            assert passed and not any(numbers.values())


def test_sorted_terms_sends_term_queries_sorted_by_date_both_ways():
    ref, pool, _config = _cell("wiki.datesort", 3000, 64)
    assert pool.limits == {**BASE, "sort_keys_off": 0, "sort_ids_off": 0,
                           "sort_ties_off": 0}
    assert pool.keeps == [{"hit": ["sort"]}] * 64
    orders = set()
    for i, q in enumerate(pool.queries):
        body = q["body"]
        (order,) = body["sort"][0].values()
        orders.add(order)
        assert list(body["query"]) == ["match"] and len(q["terms"]) == 1
        assert "filtered" not in body["query"] and "aggs" not in body
        resp = pool.answer(ref, i)
        keys = [h["sort"][0] for h in resp["hits"]["hits"]]
        assert keys == sorted(keys, reverse=order == "desc")
        assert all(k % 86_400_000 == 0 for k in keys)  # a date's first millisecond
        numbers, passed = _numbers(pool, ref, i, resp)
        assert passed and not any(numbers.values())
        if len(keys) > 3 and len(set(keys[:4])) == 4:
            h = resp["hits"]["hits"]
            h[1], h[2] = h[2], h[1]
            numbers, passed = _numbers(pool, ref, i, resp)
            assert not passed and numbers["sort_ids_off"] == 2
    assert orders == {"asc", "desc"}
    # the control's float32 keys keep the order of whole days and lose their values
    low = Reference(ref.corpus, K1, B, precision="bfloat16")
    got = Compared(pool.limits)
    for i in range(len(pool.queries)):
        got.add(pool.compare(ref, i, pool.answer(low, i), 1e-5))
    assert not got.passed and got.numbers["sort_keys_off"] > 0
    assert got.numbers["sort_ids_off"] == 0 and got.numbers["sort_ties_off"] == 0


def _children(pid: int) -> list:
    """The live child processes of `pid`, from /proc."""
    out = []
    for task in os.listdir(f"/proc/{pid}/task"):
        with open(f"/proc/{pid}/task/{task}/children") as f:
            out.extend(int(c) for c in f.read().split())
    return out


def _gone(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except FileNotFoundError:
        return True


@pytest.mark.parametrize("stopped", [True, False])
def test_a_run_leaves_no_child_behind(tmp_path, stopped):
    """`run.py` as the driver starts it, a CPU rehearsal of `logs.dashboard`: stopped
    by SIGTERM in its pool pass (exit 143 through `finally`) or left to its end (exit
    2), the server it started is gone when it returns."""
    out = tmp_path / "out"
    with open(out, "wb") as fo:
        run = subprocess.Popen(
            [sys.executable, os.path.join(registry.CHECKOUT, "benchmark", "run.py"),
             "--workload", "logs.dashboard", "--seed", str(2**31 + 19), "--seconds",
             "3", "--trace", "0", "--docs", "2000"],
            cwd=registry.CHECKOUT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=fo, stderr=subprocess.DEVNULL)
    servers: list = []
    t_end = time.monotonic() + 600
    while run.poll() is None and time.monotonic() < t_end:
        servers = servers or _children(run.pid)
        if stopped and b'"phase": "first_answers"' in out.read_bytes():
            time.sleep(1.0)  # the pool pass has begun
            run.send_signal(signal.SIGTERM)
            break
        time.sleep(0.2)
    rc = run.wait(timeout=120)
    assert rc == (143 if stopped else 2), out.read_text()[-2000:]
    phases = [json.loads(line).get("phase") for line in out.read_text().splitlines()]
    assert ("warm_up_pool" in phases) is not stopped
    assert len(servers) == 1 and _gone(servers[0])
