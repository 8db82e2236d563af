"""luceneutil's facet tasks over a text query (`MedTermDayTaxoFacets`,
`OrHighMedDayTaxoFacets`, `AndHighMedDayTaxoFacets`, `AndHighHighDayTaxoFacets`): a
one- or two-term `match` with no filter, its top hits, and in the same request one
`date_histogram` per level of the date dimension that the mix lists. The answer is the
hits and, for each histogram, the buckets that hold a matching document: both are
compared.

Parameters: `field`, `size`, `date_field`, `classes` {name: [low share, high share]},
`tasks` [{`task`, `classes`, `operator`, `weight`}], `histograms` [{`name`,
`interval`: "year" or "month"}], `min_doc_count` (written into every request, so that
no default decides the answer). The plan (task, position inside each class) comes from
the mix's own generator; the corpus, and so which word sits at a position, from
`--seed`. A bucket's key is its first millisecond, UTC.
"""

from __future__ import annotations

import datetime

import numpy as np

from benchmark.harness.reference import bucket_counts, check_hits, hits_answer, word

# the numbers `compare` adds to those of `check_hits`, each with its limit (exact)
LIMITS = {"agg_buckets_off": 0, "agg_counts_off": 0}
# what the window keeps of a response beyond total, ids and scores
KEEP = {"response": ["aggregations"]}

_EPOCH = datetime.date(1970, 1, 1).toordinal()


def plan(params: dict, rng, n: int) -> list:
    weights = np.array([t["weight"] for t in params["tasks"]], np.float64)
    out = []
    for _ in range(n):
        task = int(rng.choice(len(weights), p=weights / weights.sum()))
        out.append((task, [float(rng.random())
                           for _ in params["tasks"][task]["classes"]]))
    return out


def _calendar(params: dict, corpus) -> list:
    """For each histogram (name, bucket edges in the column's days, keys in
    milliseconds): the calendar years or months from the corpus's first day to its
    last, the first day read from the corpus's own rendering of day 0."""
    first = datetime.date.fromisoformat(
        corpus.render[params["date_field"]](0).strip('"'))
    last = first.toordinal() + int(corpus.columns[params["date_field"]].max())
    out = []
    for h in params["histograms"]:
        step = {"year": 12, "month": 1}[h["interval"]]
        month = first.year * 12 + (first.month - 1 if step == 1 else 0)
        starts = []
        while not starts or starts[-1] <= last:
            starts.append(datetime.date(month // 12, month % 12 + 1, 1).toordinal())
            month += step
        starts = np.array(starts, np.int64)
        out.append((h["name"], starts - first.toordinal(),
                    (starts[:-1] - _EPOCH) * 86_400_000))
    return out


def build(params: dict, ref, plans: list) -> list:
    present = ref.by_df[:ref.n_present]
    share = ref.df[present] / ref.n_docs
    pools = {name: present[(share > lo) & (share <= hi)]
             for name, (lo, hi) in params["classes"].items()}
    calendar = _calendar(params, ref.corpus)
    aggs = {h["name"]: {"date_histogram": {
        "field": params["date_field"], "interval": h["interval"],
        "min_doc_count": params["min_doc_count"]}} for h in params["histograms"]}
    return [_build_one(params, pools, calendar, aggs, picks) for picks in plans]


def _build_one(params: dict, pools: dict, calendar: list, aggs: dict, picks) -> dict:
    task_i, spots = picks
    task = params["tasks"][task_i]
    terms = []
    for name, u in zip(task["classes"], spots):
        pool = pools[name]
        if not len(pool):
            raise ValueError(f"no term of class {name} in this corpus")
        i = int(u * len(pool))
        while int(pool[i]) in terms:
            i = (i + 1) % len(pool)
        terms.append(int(pool[i]))
    must_all = task.get("operator", "or") == "and"
    text = " ".join(word(t) for t in terms)
    match = {"query": text, "operator": "and"} if must_all else text
    return {"terms": terms, "must_all": must_all, "size": params["size"],
            "allowed": None, "date_field": params["date_field"],
            "min_doc_count": params["min_doc_count"], "calendar": calendar,
            "body": {"query": {"match": {params["field"]: match}},
                     "size": params["size"], "aggs": aggs}}


def expected(ref, q: dict):
    return ref.score_all(q["terms"], q["must_all"])


def _buckets(ref, q: dict, matched) -> dict:
    """The reference's side of each histogram: {key in ms: count of matched docs}."""
    column = ref.corpus.columns[q["date_field"]]
    out = {}
    for name, edges, keys in q["calendar"]:
        counts = bucket_counts(matched, column, edges)
        out[name] = {int(k): int(c) for k, c in zip(keys, counts)
                     if c >= q["min_doc_count"]}
    return out


def compare(ref, q: dict, resp: dict, tol: float) -> dict:
    """The hits as `check_hits` compares them; and over the histograms together
    `agg_buckets_off` (buckets missing, extra, twice, out of order or under another
    key) and `agg_counts_off` (sum of absolute differences of `doc_count`)."""
    scores, matched = expected(ref, q)
    out = check_hits(ref, scores, matched, q["size"], resp, tol)
    out.update(agg_buckets_off=0, agg_counts_off=0)
    if out["not_whole"]:
        return out
    served = resp.get("aggregations") or {}
    for name, want in _buckets(ref, q, matched).items():
        buckets = (served.get(name) or {}).get("buckets") or []
        # a bucket with no number for a key stands under no key the reference has
        keys = [b["key"] if isinstance(b.get("key"), (int, float)) else -1.0 - i
                for i, b in enumerate(buckets)]
        got = {k: b.get("doc_count", 0) for k, b in zip(keys, buckets)}
        out["agg_buckets_off"] += len(want.keys() ^ got.keys()) \
            + (len(keys) - len(got)) \
            + sum(1 for a, b in zip(keys, keys[1:]) if not a < b)
        out["agg_counts_off"] += sum(abs(got.get(k, 0) - want.get(k, 0))
                                     for k in want.keys() | got.keys())
    return out


def answer(ref, q: dict) -> dict:
    """What `ref` itself would serve: its own top hits and its own buckets."""
    scores, matched = expected(ref, q)
    resp = hits_answer(ref, scores, matched, q["size"])
    resp["aggregations"] = {
        name: {"buckets": [
            {"key": float(k), "doc_count": c, "key_as_string":
             datetime.datetime.fromtimestamp(k / 1000.0, datetime.timezone.utc)
             .strftime("%Y-%m-%dT%H:%M:%S.000Z")} for k, c in sorted(want.items())]}
        for name, want in _buckets(ref, q, matched).items()}
    return resp
