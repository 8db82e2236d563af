"""luceneutil's `TermDTSort`: a one-term `match` query whose hits come sorted by the
date field, newest or oldest first, with no filter and no aggregation.

Parameters: `field`, `size`, `date_field`, `classes` {name: [low share, high share]},
`tasks` [{`task`, `classes`, `order`, `weight`}]. The plan (task, position inside the
class) comes from the mix's own generator; the corpus, and so which word sits at a
position, from `--seed`. A hit's `sort` value is its date's first millisecond, UTC;
hits of one date come in document order.
"""

from __future__ import annotations

import datetime

import numpy as np

from benchmark.harness.reference import check_sorted_hits, rank_by_column, word

# the numbers `compare` returns beyond the shared ones, each with its limit (exact)
LIMITS = {"sort_keys_off": 0, "sort_ids_off": 0, "sort_ties_off": 0}
# what the window keeps of a response beyond total, ids and scores
KEEP = {"hit": ["sort"]}

_EPOCH = datetime.date(1970, 1, 1).toordinal()


def plan(params: dict, rng, n: int) -> list:
    weights = np.array([t["weight"] for t in params["tasks"]], np.float64)
    return [(int(rng.choice(len(weights), p=weights / weights.sum())),
             float(rng.random())) for _ in range(n)]


def build(params: dict, ref, plans: list) -> list:
    present = ref.by_df[:ref.n_present]
    share = ref.df[present] / ref.n_docs
    pools = {name: present[(share > lo) & (share <= hi)]
             for name, (lo, hi) in params["classes"].items()}
    corpus = ref.corpus
    first = datetime.date.fromisoformat(
        corpus.render[params["date_field"]](0).strip('"')).toordinal()
    # each document's sort value as a response states it: epoch milliseconds
    keys = (first - _EPOCH + corpus.columns[params["date_field"]]) * 86_400_000
    out = []
    for task_i, u in plans:
        task = params["tasks"][task_i]
        (name,) = task["classes"]
        if not len(pools[name]):
            raise ValueError(f"no term of class {name} in this corpus")
        term = int(pools[name][int(u * len(pools[name]))])
        out.append({
            "terms": [term], "must_all": False, "size": params["size"],
            "allowed": None, "keys": keys, "descending": task["order"] == "desc",
            "body": {"query": {"match": {params["field"]: word(term)}},
                     "sort": [{params["date_field"]: task["order"]}],
                     "size": params["size"]}})
    return out


def expected(ref, q: dict):
    return ref.score_all(q["terms"], q["must_all"])


def compare(ref, q: dict, resp: dict, tol: float) -> dict:
    _scores, matched = expected(ref, q)
    # a sorted response states no score (`_score` is null): none deviates
    return {"rel_dev": 0.0, **check_sorted_hits(
        q["keys"], matched, q["size"], resp, q["descending"])}


def answer(ref, q: dict) -> dict:
    """What `ref` itself would serve: its matches ranked by the keys a system of its
    precision holds, exact for the reference; for a control below it float32. This
    corpus's dates are whole days, 86,400,000 ms apart, and float32 keeps their order:
    the control misstates the `sort` values (`sort_keys_off`) and moves no id, so this
    cell cannot tell an exact key row from a float32 one. `logs.dashboard`, whose
    events lie seconds apart, is the cell that can (`sort_ids_off`, `sort_ties_off`)."""
    _scores, matched = expected(ref, q)
    keys = q["keys"] if ref.precision == "float32" else \
        q["keys"].astype(np.float32).astype(np.float64)
    total, ranked = rank_by_column(matched, keys, q["descending"])
    return {"_shards": {"total": 1, "successful": 1, "failed": 0}, "timed_out": False,
            "hits": {"total": total, "hits": [
                {"_id": str(int(d)), "_score": None, "sort": [float(keys[d])]}
                for d in ranked[:q["size"]]]}}
