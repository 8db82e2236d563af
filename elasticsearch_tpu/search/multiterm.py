"""Multi-term expansion: which terms of a segment's dictionary a prefix, a
wildcard or a regexp names (Lucene's MultiTermQuery under its constant-score
rewrite: every document that holds at least one matching term matches).

ONE function, `expand`, for every caller: the filters (PrefixFilter,
WildcardFilter, RegexpFilter: filters.MultiTermFilter), the host scorer
(`HostScorer._multi_term_mask`, the span and phrase-prefix rewrites) and the
device path (execute._filter_mask_matrix, which turns the matching terms into
block rows of the postings plane: filters.MultiTermFilter.block_rows).
The host scorer and the device path therefore cannot disagree on which terms
match; tests/test_device_multiterm.py holds both to a union over the raw
documents' tokens that reads no dictionary.

Host arithmetic over `FrozenSegment.sorted_terms`: term ids ascend with the
terms, so a prefix is a range of term ids found by two bisections, and a
wildcard or regexp tests only the terms inside its literal head's range (a
single `*` between a literal head and tail is that range and a suffix test;
anything else `re` over the range). A pattern with no literal head tests the
whole field: exact and slow, and `Expansion.whole_field` says so."""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass

import numpy as np

from ..ops.device_index import BLOCK

PREFIX, WILDCARD, REGEXP = "prefix", "wildcard", "regexp"

_RE_META = frozenset(".^$*+?{}[]\\|()")


@dataclass(frozen=True)
class Expansion:
    """The terms a pattern names in one segment: `tids` ascending (int64),
    the block rows those terms take in a pack of the segment (every term's
    postings in rows of BLOCK: what a launch gathers, and what the ladder's
    last rung bounds), and whether the pattern had no literal head, so that
    a predicate ran over the whole field's dictionary."""

    tids: np.ndarray
    rows: int
    whole_field: bool = False


def wildcard_to_regex(pattern: str) -> str:
    out = []
    for ch in pattern:
        if ch == "*":
            out.append(".*")
        elif ch == "?":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "".join(out)


def literal_head(kind: str, pattern: str) -> str:
    """The literal every matching term starts with (possibly empty). A
    regexp's is cut conservatively: at the first metacharacter, a character
    sooner where a quantifier makes the last literal optional, and to
    nothing where the pattern holds an alternation or may not be anchored
    the plain way."""
    if kind == PREFIX:
        return pattern
    if kind == WILDCARD:
        for i, ch in enumerate(pattern):
            if ch in "*?":
                return pattern[:i]
        return pattern
    if "|" in pattern:
        return ""
    i = 0
    while i < len(pattern) and pattern[i] not in _RE_META:
        i += 1
    if i < len(pattern) and pattern[i] in "*?{":
        i -= 1
    return pattern[:max(i, 0)]


def head_range(terms: list, head: str) -> tuple[int, int]:
    """[lo, hi) of the sorted `terms` that start with `head`, by bisection."""
    if not head:
        return 0, len(terms)
    lo = bisect.bisect_left(terms, head)
    n = len(head)
    return lo, bisect.bisect_right(terms, head, lo, key=lambda t: t[:n])


def _predicate(kind: str, pattern: str, head: str):
    """The test a term inside the head's range still has to pass, or None
    where the range is the answer."""
    if kind == PREFIX:
        return None
    if kind == WILDCARD:
        if "?" not in pattern and pattern.count("*") == 1:
            tail = pattern[len(head) + 1:]
            if not tail:
                return None
            least = len(head) + len(tail)
            return lambda t: len(t) >= least and t.endswith(tail)
        if "?" not in pattern and "*" not in pattern:
            return lambda t: t == pattern
        rex = re.compile(wildcard_to_regex(pattern), re.DOTALL)
    else:
        rex = re.compile(pattern)
    return lambda t: rex.fullmatch(t) is not None


def expand(seg, field: str, kind: str, pattern: str) -> Expansion:
    """The term ids of `field` in `seg` that the `kind` pattern names."""
    terms, first = seg.sorted_terms(field)
    head = literal_head(kind, pattern)
    lo, hi = head_range(terms, head)
    whole = not head and kind != PREFIX
    pred = _predicate(kind, pattern, head)
    if pred is None:
        tids = np.arange(first + lo, first + hi, dtype=np.int64)
    else:
        tids = np.array([i for i, t in enumerate(terms[lo:hi], first + lo)
                         if pred(t)], dtype=np.int64)
    postings = seg.post_offsets[tids + 1] - seg.post_offsets[tids]
    return Expansion(tids, int(((postings + BLOCK - 1) // BLOCK).sum()), whole)


def ranges_of(offsets: np.ndarray, tids: np.ndarray) -> tuple[np.ndarray, int]:
    """(the places `offsets[t] .. offsets[t + 1]` of every term of `tids`,
    ascending, in one int64 array; how many separate runs they form). With
    the CSR `post_offsets` the places are the terms' postings, with a packed
    segment's `term_blk_start` their block rows: terms next to each other in
    the dictionary are next to each other in both."""
    starts, ends = offsets[tids], offsets[tids + 1]
    held = ends > starts
    starts, ends = starts[held], ends[held]
    if not len(starts):
        return np.zeros(0, np.int64), 0
    counts = ends - starts
    before = np.cumsum(counts) - counts
    places = np.repeat(starts - before, counts) + np.arange(int(counts.sum()))
    return places, 1 + int(np.count_nonzero(starts[1:] != ends[:-1]))


def docs_mask(seg, tids: np.ndarray) -> np.ndarray:
    """bool [doc_count]: the documents of `seg` (dead and nested ones too:
    the caller gates) that hold one of the terms `tids`, from the CSR
    postings."""
    mask = np.zeros(seg.doc_count, dtype=bool)
    places, _runs = ranges_of(seg.post_offsets, tids)
    mask[seg.post_docs[places]] = True
    return mask


def host_mask(seg, field: str, kind: str, pattern: str) -> np.ndarray:
    """The documents of `seg` that hold a term the pattern names."""
    return docs_mask(seg, expand(seg, field, kind, pattern).tids)
