"""An exact phrase in double quotes (`match_phrase`, slop 0) of 2 to 4 terms on one
field: luceneutil's HighPhrase / MedPhrase / LowPhrase, classed by the PHRASE's own
document frequency as the reference counts it. The phrases are the corpus' own
collocations (`corpus.collocations`, the generator's table) and the ordered pairs of
its `head_ranks` most frequent terms, which sit side by side by chance.

The reference's side is here, plain numpy over `Corpus.tokens` and `Corpus.lengths`,
and imports nothing of the program: the phrase's frequency in a document is the
number of places where its terms follow one another inside the document (shifted
equality over the stream; a place whose last token lies in another document does not
count), and its score Lucene's BM25 with that frequency as tf and the SUM of the
terms' idfs as idf (`PhraseWeight` over `TermStatistics[]`), float32.

Parameters: `field`, `size`, `classes` {name: [low share, high share]} (of the
documents: low < df / n <= high), `tasks` [{`task`, `class`, `weight`}], `lengths`
{terms: share}, `head_ranks`. The plan (task, number of terms, place inside the class)
comes from the mix's own generator; the corpus, and so which phrase sits at a place,
from `--seed`. A class's phrases of one length stand in the order of their longest
term's occurrences, so a place is a quantile of list lengths and every seed sends the
same shapes: a search's cost is set by that list, and a pool drawn without the order
held 28 to 38 phrases of head terms from seed to seed. A class that holds no phrase of the planned length gives one of
another length, and a class that holds none at all one of the nearest class that does
(another configuration's corpus, which no collocation was written over, has no Low
phrase; `benchmark/tests/test_phrase_families.py` holds that on this configuration's
corpus no class is empty); a corpus with no phrase of any class stops the run.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.reference import round_to, word


def plan(params: dict, rng, n: int) -> list:
    weights = np.array([t["weight"] for t in params["tasks"]], np.float64)
    sizes = sorted(int(s) for s in params["lengths"])
    shares = np.array([params["lengths"][str(s)] for s in sizes], np.float64)
    out = []
    for _ in range(n):
        task = int(rng.choice(len(weights), p=weights / weights.sum()))
        n_terms = int(rng.choice(sizes, p=shares / shares.sum()))
        out.append((task, n_terms, float(rng.random())))
    return out


def _doc_of(ref) -> np.ndarray:
    """The document of every token of the stream (kept on the reference)."""
    doc_of = getattr(ref, "_phrase_doc_of", None)
    if doc_of is None:
        doc_of = ref._phrase_doc_of = np.repeat(
            np.arange(ref.n_docs, dtype=np.int64), ref.corpus.lengths)
    return doc_of


def phrase_starts(ref, terms) -> np.ndarray:
    """Where in the stream the phrase starts: every term in its place, and the last
    token in the document of the first."""
    tokens, n = ref.corpus.tokens, len(terms)
    doc_of = _doc_of(ref)
    last = len(tokens) - n + 1
    if last <= 0:
        return np.zeros(0, np.int64)
    hit = doc_of[:last] == doc_of[n - 1:]
    for j, t in enumerate(terms):
        hit &= tokens[j: last + j] == t
    return np.flatnonzero(hit)


def phrase_freq(ref, terms) -> np.ndarray:
    """The phrase's frequency in every document, as exact integers."""
    return np.bincount(_doc_of(ref)[phrase_starts(ref, terms)],
                       minlength=ref.n_docs).astype(np.int64)


class _Occurrences:
    """The stream's places by term, to class many phrases fast: a phrase is looked
    for at the places of its rarest term alone. `phrase_freq` above, the plain test,
    is what a response is compared with; benchmark/tests hold the two together."""

    def __init__(self, ref):
        self.ref = ref
        tokens = ref.corpus.tokens
        self.order = np.argsort(tokens, kind="stable")
        self.off = np.zeros(ref.corpus.n_vocab + 1, np.int64)
        np.cumsum(np.bincount(tokens, minlength=ref.corpus.n_vocab), out=self.off[1:])

    def count(self, term: int) -> int:
        return int(self.off[term + 1] - self.off[term])

    def doc_freq(self, terms) -> int:
        tokens, doc_of, n = self.ref.corpus.tokens, _doc_of(self.ref), len(terms)
        lead = int(np.argmin([self.count(t) for t in terms]))
        p = self.order[self.off[terms[lead]]: self.off[terms[lead] + 1]] - lead
        p = p[(p >= 0) & (p + n <= len(tokens))]
        p = p[doc_of[p] == doc_of[p + n - 1]]
        for j, t in enumerate(terms):
            if j != lead:
                p = p[tokens[p + j] == t]
        return len(np.unique(doc_of[p]))


def candidates(params: dict, ref) -> dict:
    """class name -> {number of terms: [term tuples]}, each list ordered by its
    phrases' longest term list."""
    head = [int(t) for t in ref.by_df[:min(params["head_ranks"], ref.n_present)]]
    phrases = list(getattr(ref.corpus, "collocations", ())) + [
        (a, b) for a in head for b in head]
    occ = _Occurrences(ref)
    out = {name: {} for name in params["classes"]}
    for terms in dict.fromkeys(phrases):
        share = occ.doc_freq(terms) / ref.n_docs
        for name, (lo, hi) in params["classes"].items():
            if lo < share <= hi:
                out[name].setdefault(len(terms), []).append(terms)
    # by the phrase's longest list, so that a place of the plan is the same
    # quantile of list lengths on every seed: what a search costs is set by
    # its longest term's occurrences, and the plan's places are the mix's
    for by_length in out.values():
        for same in by_length.values():
            same.sort(key=lambda terms: (max(occ.count(t) for t in terms), terms))
    return out


def build(params: dict, ref, plans: list) -> list:
    pools = candidates(params, ref)
    return [_build_one(params, pools, picks) for picks in plans]


def _build_one(params: dict, pools: dict, picks) -> dict:
    task_i, n_terms, u = picks
    task = params["tasks"][task_i]
    names = list(pools)
    at = names.index(task["class"])
    # the planned class, else the nearest class that holds a phrase (a corpus
    # without the collocations has no Low phrase at all)
    held = [name for name in sorted(names, key=lambda m: abs(names.index(m) - at))
            if pools[name]]
    if not held:
        raise ValueError("no phrase of any class in this corpus")
    by_length = pools[held[0]]
    # the planned length, else the nearest length the class holds
    n = min(by_length, key=lambda m: (abs(m - n_terms), m))
    terms = list(by_length[n][int(u * len(by_length[n]))])
    text = " ".join(word(t) for t in terms)
    return {"terms": terms, "must_all": True, "size": params["size"],
            "allowed": None, "task": task["task"],
            "body": {"query": {"match_phrase": {params["field"]: text}},
                     "size": params["size"]}}


def expected(ref, q: dict):
    """The reference's side: (scores, matched) over the whole corpus."""
    freq = phrase_freq(ref, q["terms"]).astype(np.float32)
    matched = freq > 0
    idf_sum = np.float32(sum(float(ref.idf[t]) for t in q["terms"]))
    w = np.float32(idf_sum * np.float32(ref.k1 + 1.0))
    with np.errstate(invalid="ignore"):
        scores = round_to(w * (freq / (freq + ref.denom)), ref.precision)
    return np.where(matched, scores, np.float32(0)).astype(np.float32), matched
