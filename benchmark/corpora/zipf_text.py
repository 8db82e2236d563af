"""Seeded text corpus: Poisson document lengths, Zipf term ids folded into a fixed
vocabulary (the generator `chip_smoke.py` proved on the chip), and optionally one date
per document, uniform over a range of days.

Parameters (from the configuration's file): `vocabulary`, `mean_length`, `min_length`,
`max_length`, `zipf_a`, `text_field`, and optionally `date`: {`field`, `first_day`
(ISO), `days`}.
"""

from __future__ import annotations

import datetime

import numpy as np

from benchmark.harness.reference import Corpus


def _renderers(params: dict) -> dict:
    date = params.get("date")
    if not date:
        return {}
    first = datetime.date.fromisoformat(date["first_day"]).toordinal()
    # one day past the range, so that a filter can write its open upper bound
    table = ['"%s"' % datetime.date.fromordinal(first + d).isoformat()
             for d in range(date["days"] + 1)]
    return {date["field"]: lambda d: table[int(d)]}


def _draw(params: dict, rng, n_docs: int):
    lengths = np.clip(rng.poisson(params["mean_length"], n_docs),
                      params["min_length"], params["max_length"]).astype(np.int64)
    raw = rng.zipf(params["zipf_a"], int(lengths.sum())).astype(np.int64)
    columns = {}
    if params.get("date"):
        columns[params["date"]["field"]] = rng.integers(
            0, params["date"]["days"], n_docs).astype(np.int64)
    return lengths, (raw - 1) % params["vocabulary"], columns


def generate(params: dict, seed: int, n_docs: int) -> Corpus:
    lengths, tokens, columns = _draw(params, np.random.default_rng(seed), n_docs)
    return Corpus(lengths, tokens, params["vocabulary"], params["text_field"],
                  columns, _renderers(params))


def late_documents(params: dict, corpus: Corpus, seed: int, n: int):
    """`n` new documents of 20 drawn terms, each with one term no other document has
    (vocabulary + j). Returns (docs as lists of term ids, their columns)."""
    rng = np.random.default_rng(seed)
    docs = []
    for j in range(n):
        body = (rng.zipf(params["zipf_a"], 20).astype(np.int64) - 1) \
            % params["vocabulary"]
        docs.append([int(t) for t in body] + [corpus.n_vocab + j])
    columns = {}
    if params.get("date"):
        columns[params["date"]["field"]] = rng.integers(
            0, params["date"]["days"], n).astype(np.int64)
    return docs, columns
