"""`zipf_text`'s stream with collocations written over it: an independent Zipf stream
has no phrase of middling frequency at all (two words sit side by side by chance
alone, so a bigram is either of two head terms and in nearly every document, or in
almost none), and real text is not independent: names, titles and idioms recur.

The stream, the lengths and the dates are `zipf_text`'s own draw from `--seed`, so the
corpus before the writes is the one `wikimedium-1shard` indexes. Then a seeded table
of `collocations.count` term tuples of 2, 3 and 4 terms (`collocations.lengths`:
length -> share), each term drawn from the stream's own distribution (head terms take
part), each tuple given a target document frequency log-uniform over
`collocations.df_share` (shares of the documents), is written over the stream: a
tuple goes into as many documents drawn at random, at a random place inside the
document, never across a boundary. A later write may land on an earlier one, and
chance adjacency adds occurrences, so a tuple's document frequency is what the
reference counts, not its target. The corpus carries the table as `collocations` (a
list of term tuples), for the query family that sends them.

Parameters: `zipf_text`'s, and `collocations`: {`count`, `lengths`, `df_share`}.
"""

from __future__ import annotations

import numpy as np

from benchmark.corpora import zipf_text
from benchmark.harness.reference import Corpus


def _table(params: dict, rng, n_docs: int):
    """(term tuples, target document frequencies) of the collocations."""
    c = params["collocations"]
    sizes = sorted(int(n) for n in c["lengths"])
    shares = np.array([c["lengths"][str(n)] for n in sizes], np.float64)
    lengths = rng.choice(sizes, c["count"], p=shares / shares.sum())
    lo, hi = c["df_share"]
    target = np.exp(rng.uniform(np.log(lo), np.log(hi), c["count"])) * n_docs
    terms = [tuple(int(t) for t in
                   (rng.zipf(params["zipf_a"], int(n)).astype(np.int64) - 1)
                   % params["vocabulary"]) for n in lengths]
    return terms, np.maximum(1, np.rint(target)).astype(np.int64)


def generate(params: dict, seed: int, n_docs: int) -> Corpus:
    lengths, tokens, columns = zipf_text._draw(
        params, np.random.default_rng(seed), n_docs)
    rng = np.random.default_rng([seed, 1])  # the stream above stays zipf_text's
    starts = np.zeros(n_docs + 1, np.int64)
    np.cumsum(lengths, out=starts[1:])
    table, target = _table(params, rng, n_docs)
    for terms, df in zip(table, target):
        docs = rng.integers(0, n_docs, int(df))
        # a place inside the document: the tuple ends at or before its last token
        at = starts[docs] + rng.integers(0, lengths[docs] - len(terms) + 1)
        for j, t in enumerate(terms):
            tokens[at + j] = t
    corpus = Corpus(lengths, tokens, params["vocabulary"], params["text_field"],
                    columns, zipf_text._renderers(params))
    corpus.collocations = list(dict.fromkeys(table))
    return corpus


late_documents = zipf_text.late_documents
