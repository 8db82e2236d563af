"""Seeded passages with their page's title: `zipf_text`'s stream as the passages' text,
cut into pages of a geometric number of passages that share ONE title, a second, short
analysed field. A title's words come half from the rarer half of its page's own text
(a page's title names what the page is about, so its terms recur in the passages under
it: the case a `best_fields` tie-breaker exists for) and half from the stream.

The corpus object has `Corpus`'s attributes over the TEXT field (`text_field`, `lengths`,
`tokens`: the harness builds its `Reference` and its late writes over them) and beside
them the titles: `title_field`, `title_lengths`, `title_tokens`, and `page_first` (the
first document of every page, for the query family that draws a page's title).
`titles()` is the same documents as a `Corpus` over the title field, for a second
`Reference`.

Parameters: `zipf_text`'s (`vocabulary`, `mean_length`, `min_length`, `max_length`,
`zipf_a`, `text_field`), `title_field`, `pages`: {`mean_passages`, `max_passages`} and
`title`: {`poisson_mean`, `max_words`} (a title has 1 + Poisson(`poisson_mean`) words,
at most `max_words`; its first ceil(half) come from the page).
"""

from __future__ import annotations

import numpy as np

from benchmark.corpora import zipf_text
from benchmark.harness.reference import Corpus, word


class TitledCorpus(Corpus):
    """`Corpus` over the text field, and the titles beside it."""

    def __init__(self, lengths, tokens, n_vocab: int, text_field: str,
                 title_field: str, title_lengths, title_tokens, page_first):
        super().__init__(lengths, tokens, n_vocab, text_field)
        self.title_field = title_field
        self.title_lengths = np.asarray(title_lengths, np.int64)
        self.title_tokens = np.asarray(title_tokens, np.int64)
        self.page_first = np.asarray(page_first, np.int64)

    def titles(self) -> Corpus:
        """The same documents as a corpus over their titles."""
        return Corpus(self.title_lengths, self.title_tokens, self.n_vocab,
                      self.title_field)

    def extended(self, extra_docs: list, extra_columns: dict) -> "TitledCorpus":
        """A copy with `extra_docs` appended, their titles under
        `extra_columns[title_field]` (lists of term ids)."""
        grown = super().extended(extra_docs, {})
        titles = extra_columns[self.title_field]
        return TitledCorpus(
            grown.lengths, grown.tokens, grown.n_vocab, self.text_field,
            self.title_field,
            np.concatenate([self.title_lengths, [len(t) for t in titles]]),
            np.concatenate([self.title_tokens,
                            np.array([t for d in titles for t in d], np.int64)]),
            self.page_first)

    def sources(self, lo: int, hi: int) -> list:
        """The `_source` of documents lo..hi-1, as JSON text: both fields."""
        starts = self.starts()
        t_starts = np.zeros(self.n_docs + 1, np.int64)
        np.cumsum(self.title_lengths, out=t_starts[1:])
        if self._words is None:
            self._words = np.array([word(t) for t in range(self.n_vocab)], dtype=object)
        text = self._words[self.tokens[starts[lo]: starts[hi]]]
        title = self._words[self.title_tokens[t_starts[lo]: t_starts[hi]]]
        base, t_base = starts[lo], t_starts[lo]
        return ['{"%s":"%s","%s":"%s"}' % (
            self.title_field,
            " ".join(title[t_starts[i] - t_base: t_starts[i + 1] - t_base]),
            self.text_field, " ".join(text[starts[i] - base: starts[i + 1] - base]))
            for i in range(lo, hi)]


def _pages(params: dict, rng, n_docs: int) -> np.ndarray:
    """The first document of every page: page sizes geometric around
    `mean_passages`, at most `max_passages`, until the documents are used up
    (the last page takes what is left)."""
    p = params["pages"]
    sizes = np.zeros(0, np.int64)
    while sizes.sum() < n_docs:
        sizes = np.concatenate([sizes, np.minimum(
            rng.geometric(1.0 / p["mean_passages"], 4096), p["max_passages"])])
    first = np.cumsum(sizes) - sizes
    return first[first < n_docs]


def _title(params: dict, rng, page_tokens: np.ndarray) -> list:
    t = params["title"]
    n = int(min(1 + rng.poisson(t["poisson_mean"]), t["max_words"]))
    own = (n + 1) // 2
    # a term's id is its rank on the stream's Zipf curve, so the page's rarer
    # half is the upper half of its distinct ids
    distinct = np.unique(page_tokens)
    rarer = distinct[len(distinct) // 2:]
    words = [int(w) for w in rng.choice(rarer, own)]
    stream = (rng.zipf(params["zipf_a"], n - own).astype(np.int64) - 1) \
        % params["vocabulary"]
    return words + [int(w) for w in stream]


def generate(params: dict, seed: int, n_docs: int) -> TitledCorpus:
    lengths, tokens, _columns = zipf_text._draw(
        params, np.random.default_rng(seed), n_docs)
    rng = np.random.default_rng([seed, 2])  # the stream above stays zipf_text's
    first = _pages(params, rng, n_docs)
    starts = np.zeros(n_docs + 1, np.int64)
    np.cumsum(lengths, out=starts[1:])
    bounds = np.append(first, n_docs)
    title_lengths = np.zeros(n_docs, np.int64)
    title_tokens = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        title = _title(params, rng, tokens[starts[lo]: starts[hi]])
        title_lengths[lo:hi] = len(title)
        title_tokens.extend(title * int(hi - lo))
    return TitledCorpus(lengths, tokens, params["vocabulary"], params["text_field"],
                        params["title_field"], title_lengths, title_tokens, first)


def late_documents(params: dict, corpus: TitledCorpus, seed: int, n: int):
    """`zipf_text`'s late documents (20 drawn terms and one term no other document
    has, in the text), each under a title of two drawn words."""
    docs, _columns = zipf_text.late_documents(params, corpus, seed, n)
    rng = np.random.default_rng([seed, 2])
    titles = [[int(t) for t in (rng.zipf(params["zipf_a"], 2).astype(np.int64) - 1)
               % params["vocabulary"]] for _ in range(n)]
    return docs, {params["title_field"]: titles}
