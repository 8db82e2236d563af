"""A prefix or a wildcard on one field: luceneutil's `Prefix3` (a `PrefixQuery` of a
word's first characters) and `Wildcard` (a `WildcardQuery` with a literal head, a `*`
and a literal tail), as Elasticsearch 1.x sends them: `{"prefix": {field: {"value":
..., "boost": ...}}}` and `{"wildcard": ...}` under the default rewrite
(`constant_score_auto`). Every live document that holds at least one term the pattern
names matches; its score is the query's boost (BM25: queryNorm is 1); hits of equal
score come in document order; `hits.total` is exact.

The harness spells term id n as `word(n)` = `w<n>`, so the dictionary is a decimal
trie and patterns are digits. A task draws its pattern from the plan alone, so every
seed sends the same patterns (what they match differs with the corpus):
- `{"task": ..., "kind": "prefix", "digits": [lo, hi]}`: `w` + a number of lo..hi
  (three digits: some hundreds of terms; two: some thousands);
- `{"task": ..., "kind": "wildcard", "digits": [lo, hi]}`: `w` + a number of lo..hi
  + `*` + one digit.
`boosts` are the boosts drawn in equal parts for the `boost_share` of the searches
that carry one: none of them is a bfloat16 number, so a control that scores below
float32 moves `rel_dev`.

The reference's side is here and imports nothing of the program: the terms a pattern
names are those whose SPELLING passes plain string tests (`startswith`, `endswith`,
a length) over `word(n)` of every term the corpus holds, with no sorted dictionary and
no bisection, and the match set is the union of their postings.
`benchmark/tests/test_multiterm_families.py` holds that union to a scan of the
documents' own tokens.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.reference import check_hits, round_to, word

# the number `compare` adds to the shared ones: constant scores tie everywhere, and
# `check_hits` cannot see the order of equal scores
LIMITS = {"order_ids_off": 0}
# nothing beyond total, ids in their order and scores, which the window keeps of
# every response; stated because the harness holds that a family which brings a
# number of its own also says what the window keeps for it
KEEP = {"hit": []}


def plan(params: dict, rng, n: int) -> list:
    weights = np.array([t["weight"] for t in params["tasks"]], np.float64)
    out = []
    for _ in range(n):
        task = int(rng.choice(len(weights), p=weights / weights.sum()))
        out.append((task, float(rng.random()), int(rng.integers(0, 10)),
                    float(rng.random()), int(rng.integers(0, len(params["boosts"])))))
    return out


def build(params: dict, ref, plans: list) -> list:
    return [_build_one(params, ref, picks) for picks in plans]


def _build_one(params: dict, ref, picks) -> dict:
    task_i, u, last_digit, v, boost_i = picks
    task = params["tasks"][task_i]
    lo, hi = task["digits"]
    head = word(lo + int(u * (hi - lo + 1)))
    spec = {}
    q = {"task": task["task"], "head": head, "tail": "", "boost": 1.0,
         "size": params["size"], "must_all": False, "allowed": None}
    if task["kind"] == "prefix":
        name, spec["value"] = "prefix", head
    else:
        q["tail"] = str(last_digit)
        name, spec["value"] = "wildcard", f"{head}*{q['tail']}"
    if v < params["boost_share"]:
        q["boost"] = spec["boost"] = params["boosts"][boost_i]
    # the window's sample always holds the search of the most terms (cell.py)
    q["terms"] = [int(t) for t in matching_terms(ref, q)]
    q["body"] = {"query": {name: {params["field"]: spec}}, "size": params["size"]}
    return q


def _spellings(ref):
    """(the spelling of every term a document holds, those terms' ids)."""
    kept = getattr(ref, "_multiterm_spellings", None)
    if kept is None:
        present = np.flatnonzero(ref.df > 0)
        kept = ref._multiterm_spellings = (
            np.array([word(int(t)) for t in present]), present)
    return kept


def matching_terms(ref, q: dict) -> np.ndarray:
    """The term ids whose spelling starts with `head`, and ends with `tail` behind
    it where the pattern has one (`head*tail`: the two do not overlap)."""
    words, present = _spellings(ref)
    hit = np.char.startswith(words, q["head"])
    if q["tail"]:
        hit &= np.char.endswith(words, q["tail"]) \
            & (np.char.str_len(words) >= len(q["head"]) + len(q["tail"]))
    return present[hit]


def expected(ref, q: dict):
    """The reference's side: (scores, matched) over the whole corpus."""
    matched = np.zeros(ref.n_docs, bool)
    for t in matching_terms(ref, q):
        matched[ref.postings(int(t))[0]] = True
    score = round_to(np.float32(q["boost"]), ref.precision)
    return np.where(matched, score, np.float32(0)).astype(np.float32), matched


def compare(ref, q: dict, resp: dict, tol: float) -> dict:
    scores, matched = expected(ref, q)
    out = check_hits(ref, scores, matched, q["size"], resp, tol)
    out["order_ids_off"] = 0
    if out["not_whole"]:
        return out
    # constant scores: the hits are the first matches in document order
    want_ids = np.flatnonzero(matched)[:q["size"]]
    got_ids = [int(h["_id"]) if str(h["_id"]).isdigit() else -1
               for h in resp["hits"]["hits"]]
    out["order_ids_off"] = abs(len(got_ids) - len(want_ids)) + sum(
        g != w for g, w in zip(got_ids, want_ids.tolist()))
    return out
