"""The plain reference: a corpus as term ids, Lucene 4.x BM25 over it in numpy, and the
comparison of one response's hits with it. A copy of `chip_smoke.py`'s `Corpus`,
`Reference` and `check_hits`; it imports nothing of the program. Beside them, for the
query families whose answers are not scored hits: exact bucket counts of a matched set
over a numeric column, and the ranking of a matched set by a column with its check.
"""

from __future__ import annotations

import numpy as np


def word(term_id: int) -> str:
    return f"w{term_id}"


class Corpus:
    """Documents as term ids: doc i is `lengths[i]` ids from `tokens`, plus one
    numeric column per extra field. `text_field` is the analysed field."""

    def __init__(self, lengths, tokens, n_vocab: int, text_field: str,
                 columns: dict | None = None, render: dict | None = None):
        self.lengths = np.asarray(lengths, np.int64)
        self.tokens = np.asarray(tokens, np.int64)
        self.n_vocab = int(n_vocab)
        self.text_field = text_field
        self.columns = dict(columns or {})
        # how a column's number is written into a document: field -> f(number) -> JSON
        self.render = dict(render or {})
        self._words = None

    @property
    def n_docs(self) -> int:
        return len(self.lengths)

    def starts(self) -> np.ndarray:
        s = np.zeros(self.n_docs + 1, np.int64)
        np.cumsum(self.lengths, out=s[1:])
        return s

    def extended(self, extra_docs: list, extra_columns: dict) -> "Corpus":
        """A copy with `extra_docs` (lists of term ids, possibly >= n_vocab) appended."""
        flat = np.array([t for d in extra_docs for t in d], np.int64)
        cols = {k: np.concatenate([v, np.asarray(extra_columns[k], v.dtype)])
                for k, v in self.columns.items()}
        return Corpus(
            np.concatenate([self.lengths, [len(d) for d in extra_docs]]),
            np.concatenate([self.tokens, flat]),
            max(self.n_vocab, int(flat.max()) + 1), self.text_field, cols, self.render)

    def sources(self, lo: int, hi: int) -> list:
        """The `_source` of documents lo..hi-1, as JSON text, built in bulk."""
        starts = self.starts()
        if self._words is None:
            self._words = np.array([word(t) for t in range(self.n_vocab)], dtype=object)
        toks = self._words[self.tokens[starts[lo]: starts[hi]]]
        base = starts[lo]
        out = []
        for i in range(lo, hi):
            body = " ".join(toks[starts[i] - base: starts[i + 1] - base])
            extra = "".join(',"%s":%s' % (f, self.render[f](self.columns[f][i]))
                            for f in self.columns)
            out.append('{"%s":"%s"%s}' % (self.text_field, body, extra))
        return out


def float_to_byte315(f: np.ndarray) -> np.ndarray:
    """Lucene SmallFloat.floatToByte315: 3 mantissa bits, 5 exponent bits, zero
    exponent 15, from the IEEE-754 definition."""
    bits = np.asarray(f, np.float32).view(np.int32)
    small = bits >> 21
    floor = (63 - 15) << 3
    out = np.clip(small - floor, 0, 255)
    out = np.where(small <= floor, np.where(bits <= 0, 0, 1), out)
    return out.astype(np.uint8)


def byte315_to_float(b: np.ndarray) -> np.ndarray:
    b = np.asarray(b, np.uint8)
    bits = (b.astype(np.int32) << 21) + ((63 - 15) << 24)
    return np.where(b == 0, np.float32(0), bits.view(np.float32))


def round_to(x: np.ndarray, precision: str) -> np.ndarray:
    """`x` as float32 holding values of a lower precision: what a control computes in.
    bfloat16 keeps float32's exponent and 8 bits of mantissa, rounded to nearest even."""
    x = np.asarray(x, np.float32)
    if precision == "float32":
        return x
    if precision == "bfloat16":
        bits = x.view(np.uint32)
        rounded = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
        return (rounded & np.uint32(0xFFFF0000)).view(np.float32)
    raise ValueError(f"no such precision: {precision!r}")


class Reference:
    """Lucene 4.x BM25 (one-byte norms) over a whole corpus: idf = ln(1 + (N - df +
    .5)/(df + .5)); score = sum idf*(k1+1)*f/(f + k1*(1 - b + b*dl/avgdl)) with dl
    decoded from the norm byte. float32 like Lucene; `precision` below float32 is a
    control, never the yardstick."""

    def __init__(self, corpus: Corpus, k1: float, b: float, precision: str = "float32"):
        n = corpus.n_docs
        self.corpus, self.n_docs, self.k1, self.precision = corpus, n, k1, precision
        doc_of_tok = np.repeat(np.arange(n, dtype=np.int64), corpus.lengths)
        uniq, counts = np.unique(corpus.tokens * n + doc_of_tok, return_counts=True)
        terms = uniq // n
        self.post_docs = (uniq % n).astype(np.int64)
        self.post_freqs = counts.astype(np.float32)
        self.df = np.bincount(terms, minlength=corpus.n_vocab).astype(np.int64)
        self.offsets = np.zeros(corpus.n_vocab + 1, np.int64)
        np.cumsum(self.df, out=self.offsets[1:])
        with np.errstate(divide="ignore"):
            norm = float_to_byte315(
                (1.0 / np.sqrt(corpus.lengths.astype(np.float64))).astype(np.float32))
        f = byte315_to_float(np.arange(256, dtype=np.uint8)).astype(np.float64)
        with np.errstate(divide="ignore"):
            dl = np.where(f > 0, 1.0 / (f * f), 0.0).astype(np.float32)
        avgdl = np.float32(corpus.lengths.sum() / n)
        table = (k1 * (1.0 - b + b * dl / avgdl)).astype(np.float32)
        self.denom = table[norm]  # [n_docs]
        self.idf = np.log(
            1.0 + (n - self.df + 0.5) / (self.df + 0.5)).astype(np.float32)
        self.by_df = np.argsort(-self.df, kind="stable")  # term ids, most frequent first
        self.n_present = int((self.df > 0).sum())

    def postings(self, t: int):
        s, e = self.offsets[t], self.offsets[t + 1]
        return self.post_docs[s:e], self.post_freqs[s:e]

    def score_all(self, terms, must_all: bool, allowed: np.ndarray | None = None):
        """(scores[n_docs] f32, matched[n_docs] bool) for an OR (or AND) of terms,
        under an optional filter mask."""
        scores = np.zeros(self.n_docs, np.float32)
        seen = np.zeros(self.n_docs, np.int32)
        for t in terms:
            d, f = self.postings(t)
            w = np.float32(self.idf[t] * np.float32(self.k1 + 1.0))
            part = round_to(w * (f / (f + self.denom[d])), self.precision)
            scores[d] = round_to(scores[d] + part, self.precision)
            seen[d] += 1
        matched = seen == len(terms) if must_all else seen > 0
        if allowed is not None:
            matched &= allowed
        return scores, matched

    def top(self, scores: np.ndarray, matched: np.ndarray, k: int):
        """(total, ranked doc ids): score descending, then doc id."""
        cand = np.flatnonzero(matched)
        return len(cand), cand[np.lexsort((cand, -scores[cand]))]


def _not_whole(resp: dict) -> bool:
    sh = resp.get("_shards", {})
    return bool(resp.get("timed_out") or sh.get("failed")
                or sh.get("successful") != sh.get("total"))


def _hit_ids(hits: list, matched: np.ndarray):
    """(the hits' doc ids, -1 where an `_id` is no number; how many do not match)."""
    got_ids = np.array([int(h["_id"]) if str(h["_id"]).isdigit() else -1
                        for h in hits], np.int64)
    ok = (got_ids >= 0) & (got_ids < len(matched))
    ok[ok] = matched[got_ids[ok]]
    return got_ids, int((~ok).sum())


def check_hits(ref: Reference, scores: np.ndarray, matched: np.ndarray, size: int,
               resp: dict, tol_rel: float) -> dict:
    """One response against the reference. Returns the numbers compared, each of which
    has its own limit: whether it answered whole, its total, the number of hits,
    returned docs that do not match, `rel_dev` (the largest relative deviation of a
    returned score from the reference's score of that doc or of that rank), and ids
    that differ at ranks whose gap to both neighbours is clear of `tol_rel`."""
    out = {"not_whole": 0, "total_off": 0, "hits_off": 0, "not_matching": 0,
           "rel_dev": 0.0, "ids_off": 0}
    if _not_whole(resp):
        out["not_whole"] = 1
        return out
    total, ranked = ref.top(scores, matched, size)
    out["total_off"] = abs(int(resp["hits"]["total"]) - total)
    hits = resp["hits"]["hits"]
    k = min(size, total)
    out["hits_off"] = abs(len(hits) - k)
    if out["hits_off"] or k == 0:
        return out
    order = ranked[:k]
    ref_scores = scores[order]
    got_ids, out["not_matching"] = _hit_ids(hits, matched)
    got_scores = np.array([h["_score"] for h in hits], np.float32)
    if out["not_matching"]:
        return out
    own = scores[got_ids]
    dev_own = np.abs(got_scores - own) / np.maximum(np.abs(own), 1e-9)
    dev_rank = np.abs(got_scores - ref_scores) / np.maximum(np.abs(ref_scores), 1e-9)
    out["rel_dev"] = float(max(dev_own.max(), dev_rank.max()))
    tol = tol_rel * np.maximum(np.abs(ref_scores), 1e-9)
    gap = np.abs(np.diff(ref_scores)) > tol[:-1]
    # the hit just past k closes the last gap
    last_clear = len(ranked) == k or abs(ref_scores[-1] - scores[ranked[k]]) > tol[-1]
    clear = np.concatenate([[True], gap]) & np.concatenate([gap, [last_clear]])
    out["ids_off"] = int((got_ids[clear] != order[clear]).sum())
    return out


def hits_answer(ref: Reference, scores: np.ndarray, matched: np.ndarray,
                size: int) -> dict:
    """What a system that computed `scores` and `matched` would serve: its own top
    `size` by score, as a response. A control's answer, and a test's sound one."""
    total, ranked = ref.top(scores, matched, size)
    return {"_shards": {"total": 1, "successful": 1, "failed": 0}, "timed_out": False,
            "hits": {"total": total,
                     "hits": [{"_id": str(int(d)), "_score": float(scores[d])}
                              for d in ranked[:size]]}}


def bucket_counts(matched: np.ndarray, column: np.ndarray,
                  edges: np.ndarray) -> np.ndarray:
    """Exact counts of the matched documents in each bucket of a numeric column:
    bucket b holds `edges[b] <= value < edges[b + 1]`, `edges` ascending. A matched
    value outside the edges is an error of the caller's edges, not a dropped count."""
    values = np.asarray(column)[matched]
    edges = np.asarray(edges)
    b = np.searchsorted(edges, values, side="right") - 1
    if len(b) and (b.min() < 0 or b.max() >= len(edges) - 1):
        raise ValueError("a matched value lies outside the bucket edges")
    return np.bincount(b, minlength=len(edges) - 1).astype(np.int64)


def rank_by_column(matched: np.ndarray, keys: np.ndarray, descending: bool):
    """(total, ranked doc ids) of a matched set sorted by a column's value, ties by
    doc id ascending whichever way the sort runs, as Lucene's `TopFieldCollector`
    breaks them."""
    cand = np.flatnonzero(matched)
    k = np.asarray(keys)[cand]
    return len(cand), cand[np.lexsort((cand, -k if descending else k))]


def check_sorted_hits(keys: np.ndarray, matched: np.ndarray, size: int, resp: dict,
                      descending: bool) -> dict:
    """One response of a search sorted by a column against the reference. `keys` holds
    each document's sort value as a response states it (`sort[0]` of a hit). Numbers,
    each with a limit of its own: whether it answered whole, its total, the number of
    hits, returned docs that do not match, `sort_keys_off` (hits whose sort value is
    not the reference's at that rank: any misordering shows here, ties or not),
    `sort_ids_off` (ids that differ at ranks whose key differs from both neighbours')
    and `sort_ties_off` (ids that differ at the other ranks: a tie broken otherwise
    than by doc id, which holds on one shard and is no guarantee across several)."""
    out = {"not_whole": 0, "total_off": 0, "hits_off": 0, "not_matching": 0,
           "sort_keys_off": 0, "sort_ids_off": 0, "sort_ties_off": 0}
    if _not_whole(resp):
        out["not_whole"] = 1
        return out
    total, ranked = rank_by_column(matched, keys, descending)
    out["total_off"] = abs(int(resp["hits"]["total"]) - total)
    hits = resp["hits"]["hits"]
    k = min(size, total)
    out["hits_off"] = abs(len(hits) - k)
    if out["hits_off"] or k == 0:
        return out
    order = ranked[:k]
    got_ids, out["not_matching"] = _hit_ids(hits, matched)
    if out["not_matching"]:
        return out
    want = np.asarray(keys)[order]
    got = [(h.get("sort") or [None])[0] for h in hits]
    out["sort_keys_off"] = int(sum(g is None or g != w for g, w in zip(got, want)))
    gap = want[1:] != want[:-1]
    # the hit just past k closes the last gap
    last_clear = len(ranked) == k or keys[ranked[k]] != want[-1]
    clear = np.concatenate([[True], gap]) & np.concatenate([gap, [last_clear]])
    off = got_ids != order
    out["sort_ids_off"] = int((off & clear).sum())
    out["sort_ties_off"] = int((off & ~clear).sum())
    return out
