"""A `match` query of several terms on one field, OR by default.

The plan (a query's length, and each term's rank on the document-frequency curve)
comes from the mix's own generator, so every seed sends the same shapes; the corpus,
and so which word sits at a rank, comes from `--seed`.

Parameters: `field`, `size`, `min_terms`, `poisson_mean`, `max_terms`,
`head_probability`, `head_ranks`, and optionally `operator`.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness.reference import word


def plan(params: dict, rng, n: int) -> list:
    """`n` plans: a tuple of distinct positions in [0, 1) or head ranks. A position u
    stands for rank floor(exp(u * ln(present))): log-uniform over the whole curve."""
    out = []
    for _ in range(n):
        length = int(np.clip(params["min_terms"] + rng.poisson(params["poisson_mean"]),
                             params["min_terms"], params["max_terms"]))
        picks = []
        for _ in range(length):
            if rng.random() < params["head_probability"]:
                picks.append(("head", int(rng.integers(0, params["head_ranks"]))))
            else:
                picks.append(("curve", float(rng.random())))
        out.append(picks)
    return out


def build(params: dict, ref, plans: list) -> list:
    return [_build_one(params, ref, picks) for picks in plans]


def _build_one(params: dict, ref, picks) -> dict:
    ranks = []
    for kind, v in picks:
        r = v if kind == "head" else \
            int(math.floor(math.exp(v * math.log(ref.n_present)))) - 1
        r = min(max(r, 0), ref.n_present - 1)
        while r in ranks:  # distinct terms: the next rank down the curve
            r = (r + 1) % ref.n_present
        ranks.append(r)
    terms = [int(ref.by_df[r]) for r in ranks]
    must_all = params.get("operator", "or") == "and"
    text = " ".join(word(t) for t in terms)
    match = {"query": text, "operator": "and"} if must_all else text
    return {"terms": terms, "must_all": must_all, "size": params["size"],
            "allowed": None,
            "body": {"query": {"match": {params["field"]: match}},
                     "size": params["size"]}}


def expected(ref, q: dict):
    """The reference's side: (scores, matched) over the whole corpus."""
    return ref.score_all(q["terms"], q["must_all"])
