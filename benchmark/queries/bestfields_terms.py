"""One text over two fields, the best field leading: BEIR's Elasticsearch BM25
baseline (`beir/retrieval/search/lexical/elastic_search.py` `lexical_multisearch`),
`{"query": {"multi_match": {"query": <text>, "type": "best_fields", "fields": [<text
field>, <title field>], "tie_breaker": <tie>}}, "_source": false, "size": <size>}`.

The plain reference is here, numpy and float32, and imports nothing of the program:
per field Lucene's BM25 sum over the query's terms (`Reference.score_all`, the text's
on `ref`, the titles' on a second `Reference` over `ref.corpus.titles()`, kept on
`ref` and computed in `ref.precision`, so that `benchmark/control.py` lowers both),
then `DisjunctionMaxQuery`'s score, `max + tie * (sum - max)` over the fields, for
every document that matches in either field.

The plan (a query's length, and for each term whether it is a head term of the text,
a place on the text's document-frequency curve, or a word of some page's title) comes
from the mix's own generator, so every seed sends the same shapes; the corpus, and so
which word that is, comes from `--seed`.

Parameters: `fields` ([text field, title field], as sent), `tie_breaker`, `size`, `b`
(the configuration's BM25 b, for the titles' reference: a `Reference` keeps k1 alone),
`min_terms`, `poisson_mean`, `max_terms` (a query has Poisson(`poisson_mean`) terms,
clipped), `title_probability` (a term is a word of a random page's title),
`head_probability`, `head_ranks` (of the others: a term from the text's `head_ranks`
most frequent, else log-uniform over the curve, as `match_terms` draws them).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness.reference import Reference, round_to, word

BLOCK = 128  # postings a block row of the device's planes holds


def plan(params: dict, rng, n: int) -> list:
    out = []
    for _ in range(n):
        length = int(np.clip(rng.poisson(params["poisson_mean"]),
                             params["min_terms"], params["max_terms"]))
        picks = []
        for _ in range(length):
            if rng.random() < params["title_probability"]:
                picks.append(("title", float(rng.random()), float(rng.random())))
            elif rng.random() < params["head_probability"]:
                picks.append(("head", int(rng.integers(0, params["head_ranks"]))))
            else:
                picks.append(("curve", float(rng.random())))
        out.append(picks)
    return out


def title_reference(ref, b: float) -> Reference:
    """BM25 over the titles of `ref`'s documents, in `ref`'s precision (kept on it;
    `b` is the configuration's, which a `Reference` does not keep)."""
    titles = getattr(ref, "_bestfields_titles", None)
    if titles is None:
        titles = ref._bestfields_titles = Reference(
            ref.corpus.titles(), ref.k1, b, precision=ref.precision)
    return titles


def build(params: dict, ref, plans: list) -> list:
    title_starts = np.zeros(ref.corpus.n_docs + 1, np.int64)
    np.cumsum(ref.corpus.title_lengths, out=title_starts[1:])
    rank_of = np.argsort(ref.by_df)  # a term's rank on the text's curve
    return [_build_one(params, ref, title_starts, rank_of, picks)
            for picks in plans]


def _build_one(params: dict, ref, title_starts, rank_of, picks) -> dict:
    corpus = ref.corpus
    terms = []
    for kind, *v in picks:
        if kind == "title":
            doc = int(corpus.page_first[int(v[0] * len(corpus.page_first))])
            at = title_starts[doc] + int(v[1] * corpus.title_lengths[doc])
            t = int(corpus.title_tokens[at])
        else:
            r = v[0] if kind == "head" else \
                int(math.floor(math.exp(v[0] * math.log(ref.n_present)))) - 1
            t = int(ref.by_df[min(max(r, 0), ref.n_present - 1)])
        while t in terms:  # distinct terms: the next rank down the text's curve
            t = int(ref.by_df[(rank_of[t] + 1) % ref.n_present])
        terms.append(t)
    text = " ".join(word(t) for t in terms)
    return {"terms": terms, "must_all": False, "size": params["size"],
            "allowed": None, "tie_breaker": params["tie_breaker"], "b": params["b"],
            "body": {"query": {"multi_match": {
                "query": text, "type": "best_fields",
                "fields": list(params["fields"]),
                "tie_breaker": params["tie_breaker"]}},
                "_source": False, "size": params["size"]}}


def expected(ref, q: dict, tie_breaker: float | None = None):
    """The reference's side: (scores, matched) over the whole corpus. `tie_breaker`
    in the query's place is a control's: a program that dropped the combine."""
    tie = np.float32(q["tie_breaker"] if tie_breaker is None else tie_breaker)
    parts = [r.score_all(q["terms"], False)
             for r in (ref, title_reference(ref, q["b"]))]
    best = np.zeros(ref.n_docs, np.float32)
    total = np.zeros(ref.n_docs, np.float32)
    matched = np.zeros(ref.n_docs, bool)
    for scores, m in parts:
        best = np.maximum(best, scores)
        total = round_to(total + scores, ref.precision)
        matched |= m
    scores = round_to(best + round_to(tie * round_to(total - best, ref.precision),
                                      ref.precision), ref.precision)
    return np.where(matched, scores, np.float32(0)).astype(np.float32), matched


def dismax_launch_bytes(triples: int, rows: int, head_trips: int, doc_pad: int,
                        queries: int, head_row_itemsize: int) -> int:
    """The HBM bytes one launch of `jit_estpu_scoring_dismax` reads, as the program
    reckons them (`search_serving.launch.dismax_bytes`; PERF.md divides the traced
    window's by the program's device seconds and the chip's 819 GB/s for its share of
    the roofline). `rows` accumulators (queries x disjuncts, padding included) of
    `doc_pad` documents: a launched (row, block) triple gathers 128 slots of document
    id, frequency and table value, 4 B each; a trip of the head loop gathers a
    [rows, doc_pad] plane of head rows (`head_row_itemsize` B a document) and one of
    table values (4 B); the combine reads the `rows` float32 accumulators and top-k the
    `queries` combined planes."""
    return (triples * BLOCK * (4 + 4 + 4)
            + rows * head_trips * doc_pad * (head_row_itemsize + 4)
            + rows * doc_pad * 4 + queries * doc_pad * 4)
