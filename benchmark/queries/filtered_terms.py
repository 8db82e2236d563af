"""The ES 1.x `filtered` idiom: a one- or two-term text query under a `range` filter
on a date field. Terms are classed by document frequency as luceneutil classes them
(High / Med / Low), tasks pair the classes (`Term`, `OrHighMed`, `AndHighLow`, ...),
and the filter is one of a few fixed windows.

Parameters: `field`, `size`, `date_field`, `classes` {name: [low share, high share]},
`tasks` [{`task`, `classes`, `operator`, `weight`}], `windows` [[first day, days]].
The plan (task, position inside each class, window) comes from the mix's own
generator; the corpus, and so which word sits at a position, from `--seed`.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.reference import word


def plan(params: dict, rng, n: int) -> list:
    weights = np.array([t["weight"] for t in params["tasks"]], np.float64)
    out = []
    for _ in range(n):
        task = int(rng.choice(len(weights), p=weights / weights.sum()))
        spots = [float(rng.random()) for _ in params["tasks"][task]["classes"]]
        out.append((task, spots, int(rng.integers(0, len(params["windows"])))))
    return out


def build(params: dict, ref, plans: list) -> list:
    present = ref.by_df[:ref.n_present]
    share = ref.df[present] / ref.n_docs
    pools = {name: present[(share > lo) & (share <= hi)]
             for name, (lo, hi) in params["classes"].items()}
    return [_build_one(params, ref, pools, picks) for picks in plans]


def _build_one(params: dict, ref, pools: dict, picks) -> dict:
    task_i, spots, window_i = picks
    task = params["tasks"][task_i]
    day_text = ref.corpus.render[params["date_field"]]
    terms = []
    for name, u in zip(task["classes"], spots):
        pool = pools[name]
        if not len(pool):
            raise ValueError(f"no term of class {name} in this corpus")
        i = int(u * len(pool))
        while int(pool[i]) in terms:
            i = (i + 1) % len(pool)
        terms.append(int(pool[i]))
    first, days = params["windows"][window_i]
    must_all = task.get("operator", "or") == "and"
    text = " ".join(word(t) for t in terms)
    match = {"query": text, "operator": "and"} if must_all else text
    rng_filter = {"range": {params["date_field"]: {
        "gte": day_text(first).strip('"'),
        "lt": day_text(first + days).strip('"')}}}
    return {"terms": terms, "must_all": must_all, "size": params["size"],
            "allowed": (params["date_field"], first, first + days),
            "body": {"query": {"filtered": {
                "query": {"match": {params["field"]: match}},
                "filter": rng_filter}}, "size": params["size"]}}


def expected(ref, q: dict):
    field, lo, hi = q["allowed"]
    col = ref.corpus.columns[field]
    return ref.score_all(q["terms"], q["must_all"], (col >= lo) & (col < hi))
