"""The search operations of the Rally track `http_logs` (challenge
`append-no-conflicts`), as a dashboard over a web-server log index sends them: every
operation but `term` under a time window of its own on `@timestamp`, in the ES 1.x
idiom `filtered{match_all | the operation's query, range filter}`.

Parameters: `op`, one of
- `term`: a `term` query on `field` (`request.raw`), the value drawn Zipf(`zipf_a`)
  over the `top` most frequent request lines; scored hits (BM25), compared as
  `check_hits` compares them;
- `range`: the track's `range` query on `time_field`, its bounds the window;
- `status`: a `range` query on `status_field` over [`status_gte`, `status_lt`) under the
  window (`status-200s-in-range`, `status-400s-in-range`);
- `histogram`: `hourly_agg`: `size: 0` and a `date_histogram` of `interval: hour` on
  `time_field` over everything in the window (the configuration's `search.params`
  switch the request cache off: a dashboard's bounds never recur; the pool's do);
- `sorted`: `desc_sort_timestamp` / `asc_sort_timestamp` (`order`): everything in the
  window sorted on `time_field`;
and `size`, `time_field`, `widths_h` (the windows' widths in hours, drawn in equal
parts; one as long as the index is the whole index, the track's own case). A window's
end is uniform over the index's span and rounded to the minute; its bounds are absolute
epoch milliseconds. The filter carries no caching option, as Kibana's and the track's
carry none: what the filter caches keep of it is the program's to decide.

Every answer is exact: totals, ids in order (`order_ids_off`: constant-score hits come
in document order, where `check_hits` sees only ties), `sort` values and their order,
every bucket's key and count. The plan (window, order, rank) comes from the mix's own
generator; the corpus from `--seed`.
"""

from __future__ import annotations

import datetime

import numpy as np

from benchmark.harness.reference import (
    bucket_counts, check_hits, check_sorted_hits, hits_answer, rank_by_column)

# the numbers `compare` adds to the shared ones, each with its limit (exact)
LIMITS = {"order_ids_off": 0, "sort_keys_off": 0, "sort_ids_off": 0,
          "sort_ties_off": 0, "agg_buckets_off": 0, "agg_counts_off": 0}
# what the window keeps of a response beyond total, ids and scores
KEEP = {"response": ["aggregations"], "hit": ["sort"]}

_HOUR_MS = 3_600_000


def plan(params: dict, rng, n: int) -> list:
    return [(float(rng.random()), int(rng.integers(0, len(params["widths_h"]))),
             float(rng.random())) for _ in range(n)]


def _window(params: dict, stamps: np.ndarray, u: float, width_i: int):
    """[lo, hi) in epoch milliseconds: the end uniform over the index's span and
    rounded to the minute, or the whole index where the width is its length."""
    first = int(stamps.min()) // 60_000 * 60_000
    last = (int(stamps.max()) // 60_000 + 1) * 60_000
    width = int(params["widths_h"][width_i]) * _HOUR_MS
    if width >= last - first:
        return first, last
    hi = first + max(60_000, int(u * (last - first)) // 60_000 * 60_000)
    return hi - width, hi


def build(params: dict, ref, plans: list) -> list:
    return [_build_one(params, ref, picks) for picks in plans]


def _build_one(params: dict, ref, picks) -> dict:
    u, width_i, v = picks
    op, size = params["op"], params["size"]
    q = {"op": op, "terms": [], "must_all": False, "size": size, "allowed": None}
    if op == "term":
        # Zipf over the `top` most frequent request lines
        ranks = np.arange(1, params["top"] + 1, dtype=np.float64) ** -params["zipf_a"]
        rank = int(np.searchsorted(np.cumsum(ranks / ranks.sum()), v))
        term = int(ref.by_df[min(rank, ref.n_present - 1)])
        q["terms"] = [term]
        q["body"] = {"query": {"term": {params["field"]: {
            "value": ref.corpus.request_line(term)}}}, "size": size}
        return q
    field = params["time_field"]
    lo, hi = _window(params, ref.corpus.columns[field], u, width_i)
    q.update(time_field=field, window=(lo, hi))
    bounds = {"gte": lo, "lt": hi}
    window = {"range": {field: bounds}}
    if op == "range":
        q["body"] = {"query": {"range": {field: bounds}}, "size": size}
        return q
    inner = {"match_all": {}}
    if op == "status":
        q["status"] = (params["status_field"], params["status_gte"], params["status_lt"])
        inner = {"range": {params["status_field"]: {
            "gte": params["status_gte"], "lt": params["status_lt"]}}}
    q["body"] = {"query": {"filtered": {"query": inner, "filter": window}},
                 "size": size}
    if op == "histogram":
        q["size"] = 0
        q["body"].update(size=0, aggs={"by_hour": {"date_histogram": {
            "field": field, "interval": "hour", "min_doc_count": 1}}})
    elif op == "sorted":
        q["descending"] = params["order"] == "desc"
        q["body"]["sort"] = [{field: params["order"]}]
    return q


def expected(ref, q: dict):
    """(scores, matched) over the whole corpus: BM25 for `term`; for every other
    operation integer compares on the int64 columns and the constant score 1."""
    if q["op"] == "term":
        return ref.score_all(q["terms"], False)
    lo, hi = q["window"]
    stamps = ref.corpus.columns[q["time_field"]]
    matched = (stamps >= lo) & (stamps < hi)
    if q["op"] == "status":
        field, gte, lt = q["status"]
        column = ref.corpus.columns[field]
        matched &= (column >= gte) & (column < lt)
    return matched.astype(np.float32), matched


def _hour_buckets(ref, q: dict, matched) -> dict:
    """{an hour's first millisecond, UTC: count of matched events in it}."""
    stamps = ref.corpus.columns[q["time_field"]]
    if not matched.any():
        return {}
    first = int(stamps[matched].min()) // _HOUR_MS * _HOUR_MS
    last = (int(stamps[matched].max()) // _HOUR_MS + 1) * _HOUR_MS
    edges = np.arange(first, last + 1, _HOUR_MS, dtype=np.int64)
    counts = bucket_counts(matched, stamps, edges)
    return {int(k): int(c) for k, c in zip(edges[:-1], counts) if c}


def _served_keys(ref, q: dict) -> np.ndarray:
    """The sort keys a system of `ref`'s precision holds: exact int64 for the
    reference; for a control below it float32, in which 65,536 ms of 1998 are one
    key."""
    stamps = ref.corpus.columns[q["time_field"]]
    if ref.precision == "float32":
        return stamps
    return stamps.astype(np.float32).astype(np.float64)


def compare(ref, q: dict, resp: dict, tol: float) -> dict:
    scores, matched = expected(ref, q)
    if q["op"] == "sorted":
        # a sorted response states no score (`_score` is null): none deviates
        return {"rel_dev": 0.0, **check_sorted_hits(
            ref.corpus.columns[q["time_field"]], matched, q["size"], resp,
            q["descending"])}
    out = check_hits(ref, scores, matched, q["size"], resp, tol)
    if out["not_whole"] or q["op"] == "term":
        return out
    if q["op"] == "histogram":
        want = _hour_buckets(ref, q, matched)
        buckets = ((resp.get("aggregations") or {}).get("by_hour") or {}).get(
            "buckets") or []
        # a bucket with no number for a key stands under no key the reference has
        keys = [b["key"] if isinstance(b.get("key"), (int, float)) else -1.0 - i
                for i, b in enumerate(buckets)]
        got = {k: b.get("doc_count", 0) for k, b in zip(keys, buckets)}
        out["agg_buckets_off"] = len(want.keys() ^ got.keys()) \
            + (len(keys) - len(got)) \
            + sum(1 for a, b in zip(keys, keys[1:]) if not a < b)
        out["agg_counts_off"] = sum(abs(got.get(k, 0) - want.get(k, 0))
                                    for k in want.keys() | got.keys())
        return out
    # constant scores: the hits are the first matches in document order
    want_ids = np.flatnonzero(matched)[:q["size"]]
    got_ids = [int(h["_id"]) if str(h["_id"]).isdigit() else -1
               for h in resp["hits"]["hits"]]
    out["order_ids_off"] = abs(len(got_ids) - len(want_ids)) + sum(
        g != w for g, w in zip(got_ids, want_ids.tolist()))
    return out


def answer(ref, q: dict) -> dict:
    """What `ref` itself would serve."""
    scores, matched = expected(ref, q)
    if q["op"] == "sorted":
        keys = _served_keys(ref, q)
        total, ranked = rank_by_column(matched, keys, q["descending"])
        return {"_shards": {"total": 1, "successful": 1, "failed": 0},
                "timed_out": False, "hits": {"total": total, "hits": [
                    {"_id": str(int(d)), "_score": None, "sort": [float(keys[d])]}
                    for d in ranked[:q["size"]]]}}
    resp = hits_answer(ref, scores, matched, q["size"])
    if q["op"] == "histogram":
        resp["aggregations"] = {"by_hour": {"buckets": [
            {"key": float(k), "doc_count": c, "key_as_string":
             datetime.datetime.fromtimestamp(k / 1000.0, datetime.timezone.utc)
             .strftime("%Y-%m-%dT%H:%M:%S.000Z")}
            for k, c in sorted(_hour_buckets(ref, q, matched).items())]}}
    return resp
