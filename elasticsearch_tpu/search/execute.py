"""Per-shard query planning + execution.

The analogue of the reference's QueryPhase + Lucene Weight/Scorer machinery
(search/query/QueryPhase.java:95-137, SURVEY.md §3.3 "north-star path"). Two paths:

- **Device path** (the common case: match / term / terms / flat bool over terms —
  exactly the queries in BASELINE.md configs): the query lowers to a flat clause list;
  clauses from a whole QUERY BATCH are fused into one TermBatch per segment and executed
  by ops/scoring.py in a single device program (gather → FMA → scatter → top_k). An
  exact phrase (`match_phrase`, slop 0, 2-4 terms) lowers to a plan that carries the
  phrase in the clauses' place and runs the phrase program over the segment's resident
  positions plane (launch_flat_phrase).

- **Host path** (everything else: sloppy and prefix phrases, phrases inside other
  queries, spans, multi-term expansion, joins,
  function_score internals, scripts): recursive numpy evaluation per segment producing
  dense (scores float32[D], match bool[D]) with the SAME similarity math, so device and
  host paths rank identically on queries both can run.

Weight normalization mirrors Lucene: a pre-pass collects the sum of squared term weights
(createWeight), queryNorm = 1/sqrt(ssw) if the index default similarity is TF-IDF
(BM25Similarity.queryNorm ≡ 1), coord applied per matched-clause count.
Term statistics (df, sumTotalTermFreq, maxDoc) are SHARD-level — summed over segments
before weighting, like IndexSearcher's top-level stats; in multi-shard search the DFS
phase swaps in cluster-level stats (parallel/dfs.py), the analogue of
SearchPhaseController.aggregateDfs.
"""

from __future__ import annotations

import contextlib
import math
import re
import time
from dataclasses import dataclass, field as dc_field, replace as dc_replace
from functools import partial
from typing import NamedTuple

import numpy as np

from ..common import profile as _profile
from ..common.errors import QueryParsingError
from ..index.engine import Searcher
from ..index.segment import FrozenSegment
from .filters import (
    BoolFilter,
    Filter,
    MULTI_TERM_FILTERS,
    MatchAllFilter,
    QueryWrapperFilter,
    RangeFilter,
    TermFilter,
    segment_mask,
)
from . import multiterm
from .queries import (
    BoolQuery,
    BoostingQuery,
    CommonTermsQuery,
    ConstantScoreQuery,
    DisMaxQuery,
    FilteredQuery,
    FunctionScoreQuery,
    FuzzyLikeThisQuery,
    FuzzyQuery,
    HasChildQuery,
    HasParentQuery,
    IdsQuery,
    IndicesQuery,
    MatchAllQuery,
    MatchQuery,
    MoreLikeThisQuery,
    MultiMatchQuery,
    NestedQuery,
    PhraseQuery,
    PrefixQuery,
    Query,
    QueryStringQuery,
    SimpleQueryStringQuery,
    RangeQuery,
    RegexpQuery,
    FieldMaskingSpanQuery,
    SpanFirstQuery,
    SpanMultiTermQuery,
    SpanNearQuery,
    SpanNotQuery,
    SpanOrQuery,
    SpanTermQuery,
    TermQuery,
    WildcardQuery,
)
from ..common import tracing
from ..common.breaker import reserve
from ..common.devicehealth import tag_domain as _tag_domain
from ..common.jaxenv import compile_tag
from ..ops.device_index import HOST_ONLY_FIELDS
from ..transport.faults import DEVICE_FAULTS as _DEVICE_FAULTS
from ..transport.faults import DEVICE_PULL as _DEVICE_PULL
from .similarity import (
    BM25Similarity,
    FreqNormSimilarity,
    SimilarityService,
    TFIDFSimilarity,
)

GROUP_SHOULD, GROUP_MUST, GROUP_MUST_NOT = 0, 1, 2
MODE_BM25, MODE_TFIDF, MODE_CONST = 0, 1, 2


class ShardContext:
    """Shard-level stats + mapping access shared by planner and scorers."""

    def __init__(self, searcher: Searcher, mapper_service, similarity_service=None,
                 global_stats: dict | None = None, index_name: str | None = None,
                 breakers=None, batcher=None, filter_cache=None):
        self.searcher = searcher
        self.mapper_service = mapper_service
        self.similarity_service = similarity_service or SimilarityService(
            mapper_service=mapper_service
        )
        # DFS-phase override: {"df": {(field, term): df}, "max_doc": N,
        #                      "field_stats": {field: FieldStats}}
        self.global_stats = global_stats or {}
        # which index this shard belongs to (indices query/filter targeting);
        # None = unknown → indices-targeted constructs assume a match
        self.index_name = index_name
        # the node's CircuitBreakerService (None in unwired contexts — unit
        # tests, standalone shard work): allocation hot spots reserve through
        # breaker(name) and every charge site tolerates the None no-op
        self.breakers = breakers
        # the node's cross-request DeviceBatcher (search/batcher.py), or None
        # in unwired contexts — single-plan device launches coalesce with
        # concurrent searches when present (service._execute_flat_single)
        self.batcher = batcher
        # the node's device-resident filter/bitset cache
        # (ops/device_index.DeviceFilterCache), or None in unwired contexts —
        # hot filters' packed doc masks stay in HBM so cached filtered plans
        # skip mask construction + transfer (_filter_mask_matrix)
        self.filter_cache = filter_cache

    def breaker(self, name: str):
        """The named circuit breaker, or None when no service is wired."""
        return None if self.breakers is None else self.breakers.breaker(name)

    @property
    def max_doc(self) -> int:
        return self.global_stats.get("max_doc", self.searcher.max_doc)

    def doc_freq(self, field: str, term: str) -> int:
        dfs = self.global_stats.get("df")
        if dfs is not None and (field, term) in dfs:
            return dfs[(field, term)]
        return self.searcher.doc_freq(field, term)

    def field_stats(self, field: str):
        fs = self.global_stats.get("field_stats")
        if fs is not None and field in fs:
            return fs[field]
        return self.searcher.field_stats(field)

    def field_type(self, field: str):
        return self.mapper_service.field_type(field)

    def analyze(self, field: str, text: str) -> list[str]:
        return self.mapper_service.search_analyzer_for(field).terms(text)

    def analyze_tokens(self, field: str, text: str):
        return self.mapper_service.search_analyzer_for(field).analyze(text)

    def similarity_for(self, field: str):
        return self.similarity_service.for_field(field)

    @property
    def default_similarity(self):
        return self.similarity_service.default

    def all_terms(self, field: str) -> list[str]:
        terms: set[str] = set()
        for seg in self.searcher.segments:
            terms.update(seg.term_dict.get(field, ()))
        return sorted(terms)


@dataclass
class TopDocs:
    total: int
    hits: list  # [(score, global_doc)]
    max_score: float
    # the shard's time budget ran out mid-collection: hits/total cover only the
    # segments scored before expiry (ref: TimeLimitingCollector partial results)
    timed_out: bool = False


@dataclass
class Clause:
    field: str
    term: str
    boost: float
    group: int  # GROUP_*
    disjunct: int = 0  # of a dis_max plan: which disjunct's sum it adds to


@dataclass(frozen=True)
class PhraseClause:
    """An exact phrase on one field: the analyzed terms and each one's place
    (the analyzer's positions, so a removed stop word leaves its gap)."""

    field: str
    terms: tuple
    rel_pos: tuple


@dataclass
class FlatPlan:
    """A query lowered to one flat weighted-term batch (device-executable)."""

    clauses: list  # list[Clause]
    msm: int
    n_must: int
    coord_enabled: bool
    boost: float
    query_norm: float = 1.0
    # function_score plans: the wrapping FunctionScoreQuery (kernel applies the
    # function tail), the original query (host rerun on script-badness fallback),
    # and the outer boost — which participates in the TF-IDF queryNorm pre-pass
    # (execute._weight_prepass walks through FunctionScoreQuery with the outer
    # boost folded in) but NOT in the sub-query clause weights
    fs: object = None  # FunctionScoreQuery | None (also the host-fallback query)
    fs_kind: str | None = None  # "rows" | "script" (classified at lower time)
    norm_boost: float = 1.0
    # FilteredQuery: the filter gates MATCHING only (host: match &= mask, scores
    # untouched for matched docs — HostScorer FilteredQuery branch); evaluated
    # host-side per segment via the filter cache and shipped as a mask row
    filt: object = None  # Filter | None
    # a plan with NO scoring clause (match_all, constant_score, a range or
    # numeric term query, a bare filter or must_not bool — `_unscored`): no
    # clause at all; every live document `filt` admits (all, where it is
    # None) matches and scores `const` x queryNorm (unscored_score), the
    # product of boosts the host scorer gives its constant. `norm_boost`
    # then holds what the host's queryNorm pre-pass squares for the query
    # (the same product, or 0 where the pre-pass counts nothing)
    const: float | None = None
    # an exact phrase (PhraseQuery, slop 0, 2 to PHRASE_SLOTS terms) in the
    # clauses' place: `clauses` is empty, `boost` the query's, and the plan
    # runs the phrase program (launch_flat_phrase). It carries no tail: a
    # phrase under a filter, a function_score, a sort or aggregations is the
    # host's. Only lower_flat(phrases=True) gives such a plan out
    phrase: PhraseClause | None = None
    # a dis_max of `n_disjuncts` (2 to DISMAX_SLOTS) one-field OR queries:
    # each clause adds to its own disjunct's sum (Clause.disjunct), a document
    # matches where any clause does and scores best + tie_breaker * (total -
    # best) over the disjuncts' sums. `boost` is 1 and every boost is folded
    # into its clause's, in the host's order. Like a phrase it carries no
    # tail and only lower_flat(phrases=True) gives it out; 0: a plan with ONE
    # accumulator a document (every other plan)
    tie_breaker: float = 0.0
    n_disjuncts: int = 0


# ---------------------------------------------------------------------------
# minimum_should_match parsing (ref: common/lucene/search/Queries.calculateMinShouldMatch)
# ---------------------------------------------------------------------------


def calculate_msm(spec, clause_count: int) -> int:
    if spec is None:
        return 0
    if isinstance(spec, int):
        result = spec
    else:
        s = str(spec).strip()
        if "<" in s:
            # "3<90%" — conditional combos separated by spaces
            result = clause_count
            for combo in s.split():
                cond, _, value = combo.partition("<")
                if clause_count > int(cond):
                    result = _msm_value(value, clause_count)
                    break
            else:
                result = clause_count
        else:
            result = _msm_value(s, clause_count)
    # no upper clamp: msm > clause_count matches nothing (Lucene semantics)
    return max(0, result)


def _msm_value(s: str, clause_count: int) -> int:
    s = s.strip()
    if s.endswith("%"):
        pct = float(s[:-1])
        if pct < 0:
            return clause_count + int(clause_count * pct / 100.0)
        return int(clause_count * pct / 100.0)
    v = int(s)
    return clause_count + v if v < 0 else v


# ---------------------------------------------------------------------------
# flat lowering (device path)
# ---------------------------------------------------------------------------


def lower_flat(query: Query, ctx: ShardContext,
               phrases: bool = False) -> FlatPlan | None:
    """Lower a query to a flat clause list, or None if it needs the host path.
    Fields scored by a freq/norm-generic similarity (DFR/IB/LM*) always take the host
    path — the device kernel's fused modes are BM25/TF-IDF only. `phrases`: the
    caller hands its plans to execute_flat_batch with no tail, the one consumer
    that can run a phrase plan (its `clauses` are empty) or a dis_max plan (an
    accumulator a disjunct); every other caller is declined both here and
    answers them from the host."""
    plan = _lower_top(query, ctx)
    if plan is not None and not phrases and (
            plan.phrase is not None or plan.n_disjuncts > 1):
        return None
    if plan is not None:
        for field in _plan_fields(plan):
            if field in HOST_ONLY_FIELDS:
                return None  # its postings are not in the device planes
            if not isinstance(ctx.similarity_for(field),
                              (BM25Similarity, TFIDFSimilarity)):
                return None
    return plan


def _lower_top(query: Query, ctx: ShardContext) -> FlatPlan | None:
    """_lower_flat_inner, and at the top of the query alone an exact phrase:
    nothing that wraps a sub plan (function_score, filtered) ever meets one."""
    if isinstance(query, PhraseQuery):
        return _lower_phrase(query, ctx)
    return _lower_flat_inner(query, ctx)


def _plan_fields(plan: FlatPlan):
    """The fields whose postings or positions a plan scores by."""
    if plan.phrase is not None:
        return (plan.phrase.field,)
    return [c.field for c in plan.clauses]


_MULTI_TERM_QUERIES = (PrefixQuery, WildcardQuery, RegexpQuery)


def _multiterm_lowering(query: Query, ctx: ShardContext):
    """(None, the filter whose mask is a prefix, wildcard or regexp QUERY's
    match set) or (why the host scorer answers it, None). The filter is not
    cacheable: the reference caches filters, never queries, so every such
    search builds its row (on the chip: _filter_mask_matrix). Declined: a
    scoring rewrite (the device holds no per-term scores of an expansion), a
    field whose postings are not in the device planes, and an expansion of
    more block rows on some segment than the ladder's last rung holds. The
    pattern is expanded here, once a segment, to count those rows, and the
    filter carries what was expanded to the launch (it lives as long as the
    plan: one request)."""
    from ..ops.scoring import MULTITERM_RUNGS

    rewrite = (query.rewrite or "constant_score_auto").lower()
    if not rewrite.startswith("constant_score"):
        return "scoring_rewrite", None
    kind, pattern = _multiterm_pattern(query)
    filt = MULTI_TERM_FILTERS[kind](query.field, pattern, cached=False)
    reason = filt.host_reason(ctx)
    if reason:
        return reason, None
    expanded = []
    for seg in ctx.searcher.segments:
        exp = filt.expansion(seg)
        if exp.rows > MULTITERM_RUNGS[-1]:
            return "multiterm_expansion", None
        expanded.append((seg, exp))
    filt.expanded = tuple(expanded)
    return None, filt


def _unscored(query: Query, ctx: ShardContext, boost: float):
    """(const, filter | None, norm_boost) of a query with no scoring clause,
    or None: the filter whose mask is the query's whole match set (None: every
    document), the constant HostScorer scores a match before queryNorm
    (boosts multiplied downward in its order, so the product is bitwise its
    own) and what _weight_prepass squares for it."""
    b = boost * getattr(query, "boost", 1.0)
    if isinstance(query, MatchAllQuery):
        return b, None, b
    if isinstance(query, RangeQuery):
        return b, RangeFilter(
            query.field, query.gte, query.gt, query.lte, query.lt), b
    if isinstance(query, TermQuery):
        ft = ctx.field_type(query.field)
        if ft is None or not ft.is_numeric:
            return None
        return b, TermFilter(query.field, query.value), 0.0
    if isinstance(query, _MULTI_TERM_QUERIES):
        # every document with a matching term scores the boost, as
        # ConstantScoreQuery over the expansion does (the default rewrite)
        reason, filt = _multiterm_lowering(query, ctx)
        return None if reason else (b, filt, b)
    if isinstance(query, ConstantScoreQuery):
        return b, (query.filter if query.filter is not None
                   else QueryWrapperFilter(query.query)), b
    if isinstance(query, BoolQuery) and not query.must and not query.should:
        # filter/must_not-only bool: all remaining documents (HostScorer.
        # _eval_bool scores them `b * q.boost`, and the pre-pass sums nothing)
        filt = None
        if query.filter or query.must_not:
            filt = BoolFilter(
                must=list(query.filter),
                must_not=[QueryWrapperFilter(q) for q in query.must_not])
        return b * query.boost, filt, 0.0
    if isinstance(query, FilteredQuery):
        sub = _unscored(query.query, ctx, b)
        if sub is None:
            return None
        const, filt, norm_boost = sub
        return const, (query.filter if filt is None
                       else BoolFilter(must=[filt, query.filter])), norm_boost
    return None


def unscored_score(plan: FlatPlan, ctx: ShardContext) -> np.float32:
    """The score of every match of a plan with no scoring clause, bitwise
    HostScorer._const's: the boost product times queryNorm as float32."""
    qn = 1.0
    if isinstance(ctx.default_similarity, TFIDFSimilarity) and plan.norm_boost:
        qn = float(TFIDFSimilarity.query_norm(
            float(plan.norm_boost * plan.norm_boost)))
    return np.float32(plan.const * np.float32(qn))


def _lower_flat_inner(query: Query, ctx: ShardContext) -> FlatPlan | None:
    unscored = _unscored(query, ctx, 1.0)
    if unscored is not None:
        const, filt, norm_boost = unscored
        return FlatPlan([], msm=0, n_must=0, coord_enabled=False, boost=1.0,
                        norm_boost=norm_boost, filt=filt, const=const)
    if isinstance(query, TermQuery):
        ft = ctx.field_type(query.field)
        if ft is not None and ft.is_numeric:
            return None  # numeric term → columnar filter, host path
        return FlatPlan([Clause(query.field, str(query.value), query.boost, GROUP_SHOULD)],
                        msm=1, n_must=0, coord_enabled=False, boost=1.0)
    if isinstance(query, MatchQuery):
        if query.fuzziness is not None:
            return None
        terms = ctx.analyze(query.field, query.text)
        if not terms:
            return FlatPlan([], msm=0, n_must=0, coord_enabled=False, boost=query.boost)
        group = GROUP_MUST if query.operator == "and" else GROUP_SHOULD
        clauses = [Clause(query.field, t, 1.0, group) for t in terms]
        n_must = len(clauses) if group == GROUP_MUST else 0
        msm = calculate_msm(query.minimum_should_match, len(clauses)) if group == GROUP_SHOULD else 0
        if group == GROUP_SHOULD and msm == 0:
            msm = 1
        coord = len(clauses) > 1
        return FlatPlan(clauses, msm=msm, n_must=n_must, coord_enabled=coord,
                        boost=query.boost)
    if isinstance(query, BoolQuery):
        if query.filter:
            return None
        clauses: list[Clause] = []
        n_scoring = 0
        n_should = 0
        for sub, group in (
            [(q, GROUP_MUST) for q in query.must]
            + [(q, GROUP_SHOULD) for q in query.should]
            + [(q, GROUP_MUST_NOT) for q in query.must_not]
        ):
            term = _single_term(sub, ctx)
            if term is None:
                return None
            field, t, boost = term
            clauses.append(Clause(field, t, boost * (1.0 if group == GROUP_MUST_NOT else 1.0), group))
            if group != GROUP_MUST_NOT:
                n_scoring += 1
            if group == GROUP_SHOULD:
                n_should += 1
        n_must = sum(1 for c in clauses if c.group == GROUP_MUST)
        msm = calculate_msm(query.minimum_should_match, n_should)
        if msm == 0 and n_should > 0 and n_must == 0:
            msm = 1
        coord = not query.disable_coord and n_scoring > 1
        return FlatPlan(clauses, msm=msm, n_must=n_must, coord_enabled=coord,
                        boost=query.boost)
    if isinstance(query, FunctionScoreQuery):
        # device function_score: sub query must lower flat, and the functions must
        # classify as "rows" or "script" (see _classify_fs); the function tail is
        # fused into the dense kernel (ops/scoring._fs_rows_impl/_fs_script_impl,
        # ref: common/lucene/search/function/FunctionScoreQuery.java). A sub
        # query with no scoring clause keeps its constant (`_score` is that
        # constant) and a filtered one its filter: the tail gates the match by
        # the mask it takes, behind either launch ABI
        if query.query is None:
            return None
        sub = _lower_flat_inner(query.query, ctx)
        if sub is None or sub.fs is not None or sub.n_disjuncts > 1:
            return None
        kind = _classify_fs(query)
        if kind is None:
            return None
        if sub.const is not None:
            # the host evaluates the sub query under boost 1 (its constant is
            # `sub.const`), and its queryNorm pre-pass walks through the
            # wrapper with the wrapper's boost folded in
            return dc_replace(sub, fs=query, fs_kind=kind,
                              norm_boost=sub.norm_boost * query.boost)
        return FlatPlan(sub.clauses, msm=sub.msm, n_must=sub.n_must,
                        coord_enabled=sub.coord_enabled, boost=sub.boost,
                        fs=query, fs_kind=kind, norm_boost=query.boost,
                        filt=sub.filt)
    if isinstance(query, FilteredQuery):
        # the reference's canonical query+filter idiom (ES 1.x `filtered`):
        # boost folds into the sub clauses (host: eval(q.query, b)), the filter
        # becomes a match-gating mask row in the dense kernel
        sub = _lower_flat_inner(query.query, ctx)
        if sub is None or sub.fs is not None or sub.filt is not None \
                or sub.n_disjuncts > 1:
            return None
        return FlatPlan(sub.clauses, msm=sub.msm, n_must=sub.n_must,
                        coord_enabled=sub.coord_enabled,
                        boost=sub.boost * query.boost, filt=query.filter)
    if isinstance(query, (MultiMatchQuery, DisMaxQuery)):
        return _multi_field_lowering(query, ctx)[1]
    return None


def multi_match_subqueries(q: MultiMatchQuery) -> list:
    """One `match` a field of a multi_match, a field's `^boost` as its
    match's boost: what HostScorer evaluates and _multi_field_lowering
    lowers."""
    subs = []
    for fspec in q.fields:
        fname, _, fboost = fspec.partition("^")
        subs.append(MatchQuery(fname, q.text, operator=q.operator,
                               minimum_should_match=q.minimum_should_match,
                               boost=float(fboost) if fboost else 1.0))
    return subs


def _multi_field_lowering(query: Query, ctx: ShardContext):
    """(None, the plan of a `multi_match` or an explicit `dis_max`) or (why
    the host scorer answers it, None).

    A dis_max whose every sub-query is a one-field OR (`match`, operator or,
    no minimum_should_match above 1; or `term`) under BM25 lowers to ONE plan
    of SHOULD clauses in the host's order, each with its disjunct's index and
    its boosts folded as HostScorer.eval folds them on its way down (outer
    boost x the sub-query's, as Python floats; the plan's own boost is 1).
    `multi_match` of type best_fields is that dis_max with a `match` a field;
    of type most_fields it is the flat sum of every field's clauses (the host
    evaluates a bool of should matches with coord off), a plain plan. A
    sub-query that analyzes to nothing is no disjunct; one disjunct left is
    the plain plan it is (best + tie * (total - best) of one sum is the sum),
    none the empty plan of an empty match.

    Declined: a multi_match of another type (phrase and phrase_prefix score
    positions, cross_fields blends statistics); a sub-query that is not a
    one-field OR; a TF-IDF default similarity (there every disjunct's bool
    takes a coord and the query a queryNorm: HostScorer._eval_bool) or a field
    whose similarity is not BM25; more disjuncts than the program's slots."""
    from ..ops.scoring import DISMAX_SLOTS

    if isinstance(query, MultiMatchQuery):
        if query.type not in ("best_fields", "most_fields"):
            return "multi_match_type", None
        subs = multi_match_subqueries(query)
        tie = query.tie_breaker if query.type == "best_fields" else None
    else:
        subs, tie = query.queries, query.tie_breaker
    if not all(isinstance(sub, TermQuery) or (
            isinstance(sub, MatchQuery) and sub.fuzziness is None
            and sub.operator == "or") for sub in subs):
        return "dismax_subquery", None
    if isinstance(ctx.default_similarity, TFIDFSimilarity) or not all(
            isinstance(ctx.similarity_for(sub.field), BM25Similarity)
            for sub in subs):
        return "dismax_similarity", None
    disjuncts = []  # (field, terms, boost) of the disjuncts that hold a term
    for sub in subs:
        ft = ctx.field_type(sub.field)
        boost = query.boost * sub.boost
        if (ft is not None and ft.is_numeric) or boost <= 0:
            # a numeric term is a filter's constant; under a boost that is
            # not positive a disjunct's sum no longer says whether it matched
            return "dismax_subquery", None
        if isinstance(sub, TermQuery):
            terms = [str(sub.value)]
        else:
            terms = ctx.analyze(sub.field, sub.text)
            if calculate_msm(sub.minimum_should_match, len(terms)) > 1:
                return "dismax_subquery", None
        if terms:
            disjuncts.append((sub.field, terms, boost))
    if not disjuncts:
        return None, FlatPlan([], msm=0, n_must=0, coord_enabled=False,
                              boost=1.0)
    flat = tie is None or len(disjuncts) == 1
    if not flat and len(disjuncts) > DISMAX_SLOTS:
        return "dismax_disjuncts", None
    clauses = [Clause(field, t, boost, GROUP_SHOULD, 0 if flat else d)
               for d, (field, terms, boost) in enumerate(disjuncts)
               for t in terms]
    plan = FlatPlan(clauses, msm=1, n_must=0, coord_enabled=False, boost=1.0)
    if not flat:
        plan.tie_breaker, plan.n_disjuncts = float(tie), len(disjuncts)
    return None, plan


def _lower_phrase(query: PhraseQuery, ctx: ShardContext) -> FlatPlan | None:
    """A phrase the device answers: exact (slop 0), no prefix, the analyzed
    terms as HostScorer._eval_phrase reads them. One analyzed term is the
    term query the host scores it as, none an empty plan; more terms than
    the phrase program's line holds (PHRASE_SLOTS) stay on the host."""
    from ..ops.scoring import PHRASE_SLOTS

    if query.prefix or hasattr(query, "_pre_analyzed"):
        return None
    toks = ctx.analyze_tokens(query.field, query.text)
    if not toks:
        return FlatPlan([], msm=0, n_must=0, coord_enabled=False,
                        boost=query.boost)
    if len(toks) == 1:
        return FlatPlan([Clause(query.field, toks[0].term, query.boost,
                                GROUP_SHOULD)],
                        msm=1, n_must=0, coord_enabled=False, boost=1.0)
    if query.slop != 0 or len(toks) > PHRASE_SLOTS:
        return None
    return FlatPlan([], msm=0, n_must=0, coord_enabled=False,
                    boost=query.boost, phrase=PhraseClause(
                        query.field, tuple(t.term for t in toks),
                        tuple(int(t.position) for t in toks)))


def _classify_fs(q: FunctionScoreQuery):
    """Device eligibility for a function_score spec:
      "rows"   — no function reads _score: values fold to host-combined f32 rows
      "script" — exactly one function, a _score-reading script_score inside the
                 vectorizable AST subset: traced into the kernel
      None     — host path."""
    from ..common.errors import ScriptError
    from ..script import compile_script, script_uses_score, script_vectorizable

    score_readers = 0
    for sf in q.functions:
        if sf.kind == "script_score":
            try:
                cs = compile_script(sf.script, sf.params)
            except ScriptError:
                return None
            if script_uses_score(cs):
                score_readers += 1
    if score_readers == 0:
        return "rows"
    if score_readers == 1 and len(q.functions) == 1 and script_vectorizable(
            compile_script(q.functions[0].script, q.functions[0].params)):
        return "script"
    return None


def _single_term(query: Query, ctx: ShardContext):
    """A sub-query usable as one flat clause: a term query or single-token match."""
    if isinstance(query, TermQuery):
        ft = ctx.field_type(query.field)
        if ft is not None and ft.is_numeric:
            return None
        return (query.field, str(query.value), query.boost)
    if isinstance(query, MatchQuery) and query.fuzziness is None:
        terms = ctx.analyze(query.field, query.text)
        if len(terms) == 1:
            return (query.field, terms[0], query.boost)
    return None


# ---------------------------------------------------------------------------
# profile API support: plan shape + fallback-reason classification
# ---------------------------------------------------------------------------

_GROUP_NAMES = {GROUP_SHOULD: "should", GROUP_MUST: "must",
                GROUP_MUST_NOT: "must_not"}


def plan_profile(plan: FlatPlan, query: Query) -> dict:
    """The resolved plan shape a profiled request reports: per-clause
    (field, term, boost, group), bool semantics, and the fused tail kind.
    Plain scalars only — this dict crosses the wire through the binary codec
    and renders as JSON unchanged."""
    return {
        "query_type": type(query).__name__,
        "clauses": [{"field": c.field, "term": c.term,
                     "boost": float(c.boost), "group": _GROUP_NAMES[c.group],
                     "disjunct": int(c.disjunct)}
                    for c in plan.clauses],
        "msm": int(plan.msm),
        "n_must": int(plan.n_must),
        "coord": bool(plan.coord_enabled),
        "boost": float(plan.boost),
        "function_score": plan.fs_kind,  # None | "rows" | "script"
        "filtered": plan.filt is not None,
        "unscored": plan.const is not None,  # no scoring clause: mask & const
        # an exact phrase in the clauses' place: field, terms, places
        "phrase": None if plan.phrase is None else {
            "field": plan.phrase.field, "terms": list(plan.phrase.terms),
            "rel_pos": list(plan.phrase.rel_pos)},
        # a dis_max plan: an accumulator a disjunct, combined by the tie-breaker
        "dis_max": None if plan.n_disjuncts < 2 else {
            "disjuncts": int(plan.n_disjuncts),
            "tie_breaker": float(plan.tie_breaker)},
    }


def lower_fallback_reason(query: Query, ctx: ShardContext) -> str:
    """Why lower_flat declined this query — the profile API's fallback-reason
    vocabulary (common/profile.py docstring, ARCHITECTURE.md "Profile API").
    Profiled-request only: it re-walks the query, which the hot path never
    pays. The classification mirrors _lower_flat_inner's decline points; when
    the inner lowering actually SUCCEEDS, the decline was lower_flat's
    similarity gate (DFR/IB/LM fields score host-side)."""
    plan = _lower_top(query, ctx)
    if plan is not None:
        if any(f in HOST_ONLY_FIELDS for f in _plan_fields(plan)):
            return "host_only_field"
        return "similarity_not_fused"
    if isinstance(query, PhraseQuery):
        # the phrases that stay on the host: a prefix on the last term, a
        # slop (the host's sloppy frequency is an approximation of Lucene's:
        # no exact semantics to hold a device program to), and a phrase of
        # more terms than the phrase program's line holds
        if query.prefix:
            return "phrase_prefix"
        if query.slop != 0:
            return "sloppy_phrase"
        return "long_phrase"
    if isinstance(query, MatchQuery):
        # the only non-lowering match query: fuzzy (empty analysis still
        # lowers — to an empty flat plan that scores nothing on-device)
        return "fuzzy_match"
    if isinstance(query, _MULTI_TERM_QUERIES):
        # a prefix, wildcard or regexp lowers (an unscored plan whose mask
        # row the chip builds) but for these
        return _multiterm_lowering(query, ctx)[0]
    if isinstance(query, FuzzyQuery):
        # the host's expansion (the first max_expansions terms in dictionary
        # order, constant score) is not Lucene's (the top terms by edit
        # distance, each scored): no exact semantics to hold a program to
        return "fuzzy_query"
    if isinstance(query, SpanMultiTermQuery):
        return "span_multi"
    if isinstance(query, BoolQuery):
        if query.filter:
            return "bool_filter_clause"
        subs = query.must + query.should + query.must_not
        # (a bool with no must or should lowers as an unscored plan; one with
        # them takes term subclauses alone, an unscored one among them not)
        return "non_term_subclause"
    if isinstance(query, (MultiMatchQuery, DisMaxQuery)):
        # a dis_max of one-field OR queries lowers but for these
        return _multi_field_lowering(query, ctx)[0]
    if isinstance(query, (FunctionScoreQuery, FilteredQuery)):
        if query.query is None:
            return "function_score_no_query"
        sub = _lower_flat_inner(query.query, ctx)
        if sub is not None and sub.n_disjuncts > 1:
            return "dismax_tail"  # a dis_max plan carries no tail
        if isinstance(query, FilteredQuery) or sub is None \
                or sub.fs is not None:
            return "non_flat_subquery"
        return "function_score_ineligible"
    return f"unsupported_query:{type(query).__name__}"


def finalize_flat(plan: FlatPlan, ctx: ShardContext):
    """Resolve clause weights against shard/global stats; returns per-clause arrays +
    per-field norm caches, exactly the kernel's inputs."""
    max_doc = ctx.max_doc
    fields: list[str] = []
    caches: list[np.ndarray] = []
    field_idx: dict[str, int] = {}
    resolved = []  # (field, term, weight, fidx, group, mode)
    ssw = 0.0
    for c in plan.clauses:
        sim = ctx.similarity_for(c.field)
        df = ctx.doc_freq(c.field, c.term)
        if c.field not in field_idx:
            field_idx[c.field] = len(fields)
            fields.append(c.field)
            caches.append(sim.norm_cache(ctx.field_stats(c.field), max_doc))
        fi = field_idx[c.field]
        if df <= 0:
            resolved.append((c.field, c.term, 0.0, fi, c.group, MODE_BM25, 0))
            continue
        if isinstance(sim, BM25Similarity):
            idf = sim.idf(df, max_doc)
            w = np.float32(idf * c.boost * plan.boost * (sim.k1 + 1.0))
            mode = MODE_BM25
        else:
            idf = TFIDFSimilarity.idf(df, max_doc)
            w = np.float32(idf * idf * c.boost * plan.boost)  # queryNorm folded later
            mode = MODE_TFIDF
        if c.group != GROUP_MUST_NOT:
            ssw += float((idf * c.boost * plan.boost * plan.norm_boost) ** 2)
        resolved.append((c.field, c.term, float(w), fi, c.group, mode, df))
    qn = 1.0
    if isinstance(ctx.default_similarity, TFIDFSimilarity) and ssw > 0:
        qn = float(TFIDFSimilarity.query_norm(ssw))
    out = []
    for (f, t, w, fi, g, mode, df) in resolved:
        out.append((f, t, w * qn if mode == MODE_TFIDF else w, fi, g, mode, df))
    n_scoring = sum(1 for c in plan.clauses if c.group != GROUP_MUST_NOT)
    coord = np.ones(max(n_scoring, 1) + 1, dtype=np.float32)
    if plan.coord_enabled and isinstance(ctx.default_similarity, TFIDFSimilarity) and n_scoring > 0:
        coord = np.arange(n_scoring + 1, dtype=np.float32) / np.float32(n_scoring)
    return out, fields, np.stack(caches) if caches else None, coord


# ---------------------------------------------------------------------------
# batched device execution
# ---------------------------------------------------------------------------


def finalize_phrase(plan: FlatPlan, ctx: ShardContext):
    """A phrase plan's (weight float32, TFN_* mode, norm cache float32 [256])
    against shard/global stats, each in HostScorer._eval_phrase's own
    arithmetic: ONE weight from the sum of the terms' idfs (a term repeated
    in the phrase counts twice, a term no document holds not at all), boost
    and (k1 + 1), or for TF-IDF the sum squared and the queryNorm of
    _weight_prepass."""
    from ..ops.device_index import TFN_BM25, TFN_TFIDF

    ph = plan.phrase
    sim = ctx.similarity_for(ph.field)
    max_doc = ctx.max_doc
    cache = sim.norm_cache(ctx.field_stats(ph.field), max_doc)
    dfs = [ctx.doc_freq(ph.field, t) for t in ph.terms]
    idfs = sum(float(sim.idf(df, max_doc)) for df in dfs if df > 0)
    idf_sum = np.float32(idfs)
    if isinstance(sim, BM25Similarity):
        return (np.float32(idf_sum * plan.boost * (sim.k1 + 1.0)), TFN_BM25,
                cache)
    qn = 1.0
    ssw = float((idfs * plan.boost) ** 2)
    if isinstance(ctx.default_similarity, TFIDFSimilarity) and ssw > 0:
        qn = float(TFIDFSimilarity.query_norm(ssw))
    return (np.float32(idf_sum * idf_sum * plan.boost) * np.float32(qn),
            TFN_TFIDF, cache)


@dataclass(frozen=True)
class FlatTail:
    """What an aggregated or a sorted search asks of its dense launch besides
    the top documents, riding beside its plan through the batcher and
    execute_flat_batch (a plan without one is answered with TopDocs).

    `kind` is "aggs" (`fields`: the sorted metric fields; `bucket_aggs`:
    (Agg, sub-field order | None) pairs) or "sorted" (`spec`: the one field
    sort). Plans whose tails have one `kind` and one `key`, and that are all
    scored or all unscored, launch as one group: the key is everything the
    launch's resident operands and compiled shape depend on, read off the
    request alone — for aggregations the metric fields and each bucket
    aggregation's `bucket_cache_key` with its sub-field order (what
    ensure_agg_rows and the bucket columns' cache key on), for a sort what
    _sort_key_row keys its row on. The group's leader lends its Agg objects
    or its spec to the launch; what differs between members (a terms
    aggregation's size or order) is applied on the request thread, to each
    member's own slice."""

    kind: str
    key: tuple
    fields: list = ()
    bucket_aggs: list = ()
    spec: object = None


def aggs_tail(fields: list[str], bucket_aggs: list) -> FlatTail:
    from .aggregations import bucket_cache_key

    return FlatTail(
        "aggs", (tuple(fields), tuple(
            (bucket_cache_key(agg), tuple(sub_order or ()))
            for agg, sub_order in bucket_aggs)),
        fields=list(fields), bucket_aggs=list(bucket_aggs))


def sort_tail(spec) -> FlatTail:
    return FlatTail("sorted", _sort_row_key(spec), spec=spec)


def plan_kind(plan: FlatPlan, tail: FlatTail | None = None) -> str:
    """The kind of group a plan launches in, a key of GROUP_KINDS: its tail's
    (aggs, sorted) where it has one, else function_score, filtered (a filter,
    or no scoring clause at all), phrase, dis_max or plain."""
    if tail is not None:
        return tail.kind
    if plan.fs is not None:
        return "function_score"
    if plan.filt is not None or plan.const is not None:
        return "filtered"
    if plan.phrase is not None:
        return "phrase"
    if plan.n_disjuncts > 1:
        return "dis_max"
    return "plain"


# the group of the plans the sparse candidate path serves: scoring clauses
# and neither a tail nor a filter
_PLAIN_GROUP = ("plain", None, False)


def _flat_groups(plans: list[FlatPlan], tails=None) -> dict:
    """group -> positions in `plans`, in order of first sighting: the plans
    one launch a segment answers together. A group is (kind, key, unscored):
    its kind (plan_kind; `search.batcher.kinds` in /_nodes/stats), what the
    launch's operands and compiled shape depend on beside it (a
    function_score's spec, a tail's key) and whether its plans have no
    scoring clause (scored and unscored plans launch apart, as
    _segment_batches does not mix them)."""
    groups: dict = {}
    for i, p in enumerate(plans):
        tail = tails[i] if tails else None
        key = tail.key if tail is not None else \
            _fs_group_key(p.fs) if p.fs is not None else None
        groups.setdefault((plan_kind(p, tail), key, p.const is not None),
                          []).append(i)
    return groups


def execute_flat_batch(plans: list[FlatPlan], ctx: ShardContext, k: int,
                       tails: list | None = None) -> list:
    """Run a batch of flat plans through the device kernels on the calling
    thread, a launch a segment for each group of _flat_groups: the dispatch
    half and its merge, one after the other (_dispatch_flat says which
    protocol carries which group). A result a plan: TopDocs, or for a plan
    with a FlatTail (`tails`: one or None a plan) what its kind's launcher
    hands back (launch_flat_aggs, launch_flat_sorted; None where the host
    serves)."""
    return _dispatch_flat(plans, ctx, k, tails).merge()


def _run_flat_groups(plans: list[FlatPlan], ctx: ShardContext, k: int,
                     tails, groups: dict) -> list:
    """A result a plan, group by group, by the two protocols a launch has.

    The plain group runs first and whole (_execute_flat_plain). It is the one
    launch whose pull can happen off the dispatch half (_PendingFlat: the
    overlap a batch of plain plans alone runs on), and its merge carries the
    scratch pool's release and the DEVICE_PULL / DEVICE_FAULTS seams. First,
    because the device runs its programs in order: a group that pulls at
    once must not wait behind one that does not.

    Every other group launches by its row of GROUP_KINDS with NO pull, and
    all of them are pulled TOGETHER, in one device_get for the batch: a
    group's program runs while the next group is staged, and the drainer
    gives the GIL up once a batch and not once a group (a mix of operations
    holds two or three groups a batch)."""
    from ..ops.scoring import _pull

    out: list = [None] * len(plans)
    idxs = groups.get(_PLAIN_GROUP)
    if idxs:
        for i, r in zip(idxs, _execute_flat_plain([plans[i] for i in idxs],
                                                  ctx, k)):
            out[i] = r
    launched = []  # (positions, device outputs, finish) a launch
    for group, idxs in groups.items():
        kind = GROUP_KINDS[group[0]]
        if kind.launch is None:
            continue
        tail = tails[idxs[0]] if tails else None
        for start in range(0, len(idxs), kind.width):
            chunk = idxs[start: start + kind.width]
            handle = kind.launch([plans[i] for i in chunk], ctx, k, tail)
            if handle is not None:  # None: the host serves every member
                launched.append((chunk, *handle))
    if launched:
        pulled = _pull([refs for _chunk, refs, _finish in launched])
        for (chunk, _refs, finish), host in zip(launched, pulled):
            for i, r in zip(chunk, finish(host)):
                out[i] = r
    return out


def _fs_group_key(fsq) -> tuple:
    """Queries whose function_score spec is VALUE-identical share kernel launches
    (the spec's scalars are baked per launch). Dataclass reprs are content reprs."""
    return (repr(fsq.functions), fsq.score_mode, fsq.boost_mode, fsq.max_boost,
            fsq.min_score, fsq.boost)


def _assemble_batch(plans: list[FlatPlan], finals: list):
    """Field/cache tables + per-query bool-semantics arrays for a batch of
    finalized plans — single construction site for both the plain and the
    function_score batch paths (the coord padding rule is kernel ABI)."""
    Q = len(plans)
    all_fields: list[str] = []
    field_idx: dict[str, int] = {}
    cache_rows: list[np.ndarray] = []
    for (_resolved, fields, caches, _coord) in finals:
        for i, f in enumerate(fields):
            if f not in field_idx:
                field_idx[f] = len(all_fields)
                all_fields.append(f)
                cache_rows.append(caches[i])
    caches_stack = np.stack(cache_rows) if cache_rows else np.ones((1, 256), np.float32)
    max_clauses = max(1, max(
        (sum(1 for c in p.clauses if c.group != GROUP_MUST_NOT) for p in plans),
        default=1))
    coord_tbl = np.ones((Q, max_clauses + 1), dtype=np.float32)
    n_must = np.zeros(Q, np.int32)
    msm = np.zeros(Q, np.int32)
    for qi, (plan, (_resolved, _fields, _caches, coord)) in enumerate(zip(plans, finals)):
        coord_tbl[qi, : len(coord)] = coord
        if len(coord) <= max_clauses:
            coord_tbl[qi, len(coord):] = coord[-1]
        n_must[qi] = plan.n_must
        msm[qi] = plan.msm
    return all_fields, field_idx, cache_rows, caches_stack, coord_tbl, n_must, msm


class _PendingFlat:
    """Device work in flight for one plain-plan batch: every segment's sparse
    bucket launches (+ dense-overflow launches) with NO host pull yet.
    merge() performs the batch's ONE explicit jax.device_get and the host
    top-k merge — the dispatch/merge split the cross-request batcher overlaps
    (search/batcher.py: batch N+1 dispatches while batch N merges)."""

    __slots__ = ("Q", "k", "breaker", "seg_work", "releases",
                 "pull_t0", "pull_t1", "index", "clock")

    def __init__(self, Q: int, k: int, breaker, seg_work: list, releases: list,
                 index: str | None = None):
        self.Q = Q
        self.k = k
        self.breaker = breaker
        # owning index (ShardContext.index_name) — stall-injection matching
        # and capacity-ledger attribution; None in unwired contexts
        self.index = index
        # per segment: (seg, base, doc_pad, launches, dense)
        self.seg_work = seg_work
        # scratch-pool release callbacks — invoked by merge() AFTER the pull
        # (staging arrays must stay untouched while transfers are in flight)
        self.releases = releases
        # host-monotonic endpoints of the batch's single device_get, stamped
        # by merge(): the tracing layer's device span rides THIS existing
        # pull instead of adding any sync of its own (common/tracing.py)
        self.pull_t0: float | None = None
        self.pull_t1: float | None = None
        # the dispatch's stage/launch intervals (tracing.DispatchClock), set
        # by dispatch_flat_batch for the batcher; None on direct launches
        self.clock = None

    def merge(self) -> list[TopDocs]:
        return _merge_flat_plain(self)

    @property
    def kinds(self) -> tuple:
        return (("plain", self.Q),)

    def sync(self):
        """Block until every dispatched launch completes — the profile API's
        per-request sync ONLY (_execute_flat_plain); the serving path never
        calls this, its one sync is the batched pull in merge()."""
        import jax

        for (_seg, _base, _doc_pad, launches, dense) in self.seg_work:
            for (_sb, r) in launches:
                jax.block_until_ready(r)
            if dense is not None:
                jax.block_until_ready(dense[1])


class _PendingDone:
    """Already-merged results behind the pending interface: a batch that is
    not plain plans alone runs whole inside the dispatch half
    (_run_flat_groups: its one-pull groups are pulled there, in one
    device_get). `clock` holds that dispatch's stage / launch / device_pull
    intervals (tracing.DispatchClock): the pull happened INSIDE the dispatch,
    so the batcher records it there, not under its merge. `kinds` is what the
    batch launched, (kind, members) a group of _flat_groups, for the
    batcher's per-kind counters."""

    __slots__ = ("results", "clock", "kinds")

    def __init__(self, results: list, kinds: tuple = ()):
        self.results = results
        self.clock = None
        self.kinds = kinds

    def merge(self) -> list:
        return self.results


def _dispatch_flat(plans: list[FlatPlan], ctx: ShardContext, k: int,
                   tails: list | None = None):
    """A batch's device work behind a pending handle whose merge() yields a
    result a plan: the ONE place that chooses the protocol. Plain plans alone
    enqueue their launches without syncing (_PendingFlat: the pull is the
    merge's); any other batch runs whole here, group by group
    (_run_flat_groups), and so does a PROFILED plain one, whose per-request
    sync sits between its dispatch and its merge (_execute_flat_plain)."""
    groups = _flat_groups(plans, tails)
    if groups.keys() == {_PLAIN_GROUP} and _profile.current() is None:
        return _dispatch_flat_plain(plans, ctx, k)
    return _PendingDone(
        _run_flat_groups(plans, ctx, k, tails, groups),
        tuple((group[0], len(idxs)) for group, idxs in groups.items()))


def dispatch_flat_batch(plans: list[FlatPlan], ctx: ShardContext, k: int,
                        tails: list | None = None):
    """Dispatch half of execute_flat_batch for the cross-request batcher: the
    pending handle of _dispatch_flat with the dispatch's clock on it (its
    stage / launch intervals, and the device_pull of a batch that ran
    whole)."""
    with tracing.timing_dispatch() as clock:
        pending = _dispatch_flat(plans, ctx, k, tails)
    pending.clock = clock
    return pending


@contextlib.contextmanager
def traced_dispatch():
    """A launch that bypasses the batcher and pulls on the request thread —
    the mesh's feature launches, and an aggregated or a sorted search that
    is profiled, carries DFS statistics or runs on a node without a batcher
    (service._execute_flat_single; a served one is the drainer's): a SAMPLED
    request records its stage / launch / device_pull intervals under its own
    active span; an unsampled one pays the thread-local read."""
    span = tracing.current_span()
    if not span:
        yield
        return
    with tracing.timing_dispatch() as clock:
        yield
    clock.record_under(span)


def _dispatch_flat_plain(plans: list[FlatPlan], ctx: ShardContext,
                         k: int) -> _PendingFlat:
    """Plan + launch a batch of plain flat plans across every segment WITHOUT
    any host pull (the merge half does the batch's single device_get).

    The common case rides the sparse candidate-centric kernel (ops/scoring.py
    launch_flat_sparse — work scales with postings touched, not corpus size);
    queries whose terms cover too many postings blocks (tb_max) fall back to
    the dense scatter kernel, which is O(Q·doc_pad) but block-count-insensitive.
    Sparse staging buffers are pooled per segment and accounted per batch on
    the request breaker (see launch_flat_sparse)."""
    from ..ops.device_index import (
        TFN_BM25, TFN_TFIDF, ensure_sim_tables, packed_for)
    from ..ops.scoring import launch_flat_sparse

    Q = len(plans)
    finals = [finalize_flat(p, ctx) for p in plans]
    (all_fields, field_idx, cache_rows, caches_stack,
     coord_tbl, n_must, msm) = _assemble_batch(plans, finals)
    sim_tables = {
        f: (TFN_BM25 if isinstance(ctx.similarity_for(f), BM25Similarity)
            else TFN_TFIDF, cache_rows[field_idx[f]])
        for f in all_fields
    }
    # zero-df clauses (w=0, no postings anywhere) can't affect results — don't let
    # them demote the batch off the simple fast path
    simple = bool(
        np.all(n_must == 0) and np.all(msm <= 1) and np.all(coord_tbl == 1.0)
        and all(g == GROUP_SHOULD and mode == MODE_BM25 and w > 0
                for (resolved, _f, _c, _coord) in finals
                for (_f2, _t, w, _fi, g, mode, df) in resolved if df > 0))

    prof = _profile.current()
    seg_work = []  # (seg, base, doc_pad, launches, dense)
    releases = []
    for seg, base in zip(ctx.searcher.segments, ctx.searcher.bases):
        t_seg = time.monotonic() if prof is not None else 0.0
        packed = packed_for(seg, breaker=ctx.breaker("fielddata"),
                            owner=ctx.index_name)
        # cheap LUT swap (1 KB/field), not a postings re-bake: the quantized
        # scan decodes tf→tfn in-kernel against these stacked cache rows
        sim = ensure_sim_tables(packed, sim_tables)
        # every clause resolved ONCE per segment: the sparse planner's block
        # ranges and the dense fallback's are the same records
        entries = _dense_entries(finals, seg, packed, field_idx)
        fid_of = [sim.fid[f] for f in all_fields]
        clause_lists = [[] for _ in range(Q)]
        for (qi, b0, b1, w, fi, g, mode, _row) in entries:
            clause_lists[qi].append((b0, b1, w, g, mode == MODE_CONST, fid_of[fi]))
        # compile_tag: backend compiles triggered by these launches land in
        # the capacity ledger's per-family attribution (common/jaxenv).
        # Launch failures are tagged with their compile-family fault domain
        # (and the seeded DEVICE_FAULTS seam injects here) so the circuit
        # tracker attributes the trip to the right domain.
        try:
            if _DEVICE_FAULTS.active:
                _DEVICE_FAULTS.check("compile:sparse")
            with compile_tag("sparse"):
                launches, overflow, release = launch_flat_sparse(
                    packed, clause_lists, n_must, msm, coord_tbl, k,
                    simple=simple, breaker=ctx.breaker("request"), sim=sim)
        except Exception as e:  # noqa: BLE001 — re-raised tagged
            raise _tag_domain(e, "compile:sparse")
        releases.append(release)
        dense = None
        if overflow:
            try:
                if _DEVICE_FAULTS.active:
                    _DEVICE_FAULTS.check("compile:dense")
                with compile_tag("dense"):
                    dense = _launch_dense_fallback(
                        overflow, entries, all_fields, caches_stack,
                        n_must, msm, coord_tbl, packed, k,
                        breaker=ctx.breaker("fielddata"))
            except Exception as e:  # noqa: BLE001 — re-raised tagged
                raise _tag_domain(e, "compile:dense")
        seg_work.append((seg, base, packed.doc_pad, launches, dense))
        if prof is not None:
            from ..ops.scoring import SparseScratchPool

            prof.segment(
                seg.gen, docs=int(seg.doc_count), path="sparse_composed",
                tf_layout=packed.tf_layout,
                # the launch counters' own sum (ops/scoring.LAUNCHES
                # `blocks_real`): the blocks every launch above named
                blocks_scanned=sum(sb.blocks_real for (sb, _r) in launches)
                + (dense[2] if dense is not None else 0),
                postings_scanned=_postings_scanned(finals, seg),
                staged_bytes=sum(
                    SparseScratchPool.staging_bytes(*sb.qblk.shape)
                    for (sb, _r) in launches),
                buckets=len(launches),
                dense_overflow=len(overflow),
                ms=(time.monotonic() - t_seg) * 1000.0)
    return _PendingFlat(Q=Q, k=k, breaker=ctx.breaker("request"),
                        seg_work=seg_work, releases=releases,
                        index=ctx.index_name)


def _merge_flat_plain(pending: _PendingFlat) -> list[TopDocs]:
    """Merge half: ONE explicit device_get drains every launch of the batch
    (sparse buckets + dense overflow across all segments), then the pure-host
    cross-segment top-k merge. This is the only pull on the plain serving
    path — per-bucket np.asarray pulls would be a transfer per array, which
    the transfer_guard("disallow") sanitizer rejects."""
    import jax

    from ..ops.scoring import collect_flat_sparse, finalize_score_result

    Q, k = pending.Q, pending.k
    refs = []
    for (_seg, _base, _doc_pad, launches, dense) in pending.seg_work:
        refs.extend(r for (_sb, r) in launches)
        if dense is not None:
            refs.append(dense[1])
    # chaos hook (transport/faults.DEVICE_PULL): one plain attribute read
    # when disarmed; armed, the stall-watchdog tests wedge THIS pull the way
    # a hung runtime would (the sleep happens before the guard-legal pull)
    if _DEVICE_PULL.active:
        stall = _DEVICE_PULL.delay_for(pending.index)
        if stall > 0.0:
            time.sleep(stall)
    try:
        # seeded device-error seam (transport/faults.DEVICE_FAULTS): same
        # one-attr-read gate; armed, the batch pull raises the injected
        # XlaRuntimeError exactly where a real transfer failure would
        if _DEVICE_FAULTS.active:
            _DEVICE_FAULTS.check(f"pull:{pending.index}")
        # stamp the pull window for tracing (host clocks around the pull the
        # serving path performs anyway — the device span's end rides this)
        # and name it on the profiler's own clock, inside the drainer's
        # estpu.batch.merge annotation (a flag check while no session is on)
        pending.pull_t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("estpu.batch.pull"):
            pulled = iter(jax.device_get(refs) if refs else [])
        pending.pull_t1 = time.monotonic()
    except Exception as e:  # noqa: BLE001 — abandoning the batch
        # drain whatever the device will still write into the staging
        # buffers, then hand them back: a poisoned pull (this failure path is
        # cold — syncing here is legal) must not leak the scratch pool while
        # the batcher replays members individually
        for r in refs:
            try:
                jax.block_until_ready(r)
            except Exception:  # noqa: BLE001 — the launch itself may be poisoned
                pass
        for release in pending.releases:
            try:
                release()
            except Exception:  # noqa: BLE001 — best-effort cleanup
                pass
        raise _tag_domain(e, f"pull:{pending.index}")
    # results are on the host — the borrowed staging arrays are reusable now
    for release in pending.releases:
        release()
    totals = np.zeros(Q, dtype=np.int64)
    seg_hits = []  # (scores [Q,k] f32, global_docs [Q,k] int64) per segment
    for (seg, base, doc_pad, launches, dense) in pending.seg_work:
        sparse_pulled = [next(pulled) for _ in launches]
        scores, docs, tq = collect_flat_sparse(launches, sparse_pulled, Q, k,
                                               doc_pad)
        if dense is not None:
            sub = dense[0]
            # already host arrays — the batch's single device_get pulled them
            ts, td, tt = next(pulled)
            res = finalize_score_result(ts, td, tt, doc_pad)
            kk = res.scores.shape[1]
            scores[sub, :kk] = res.scores
            docs[sub, :kk] = res.docs
            scores[sub, kk:] = -np.inf
            docs[sub, kk:] = doc_pad
            tq[sub] = res.total_hits
        totals += tq
        seg_hits.append(_segment_hits(scores, docs,
                                      min(doc_pad, seg.doc_count), base))
    return _merge_seg_hits(seg_hits, totals, Q, k, breaker=pending.breaker)


def _execute_flat_plain(plans: list[FlatPlan], ctx: ShardContext, k: int) -> list[TopDocs]:
    """Run a batch of flat plans through the device kernels: dispatch every
    segment's launches, then merge per-segment top-k host-side (score desc,
    global doc asc — Lucene order). Synchronous composition of the
    dispatch/merge halves the batcher overlaps.

    A PROFILED request (common/profile.py — it bypassed the batcher, so this
    runs on the request thread) additionally syncs on the dispatched launches
    between dispatch and merge: that per-request sync is the opt-in that buys
    precise dispatch/device/pull/merge phase attribution; the unprofiled path
    takes the early return and adds zero syncs."""
    prof = _profile.current()
    if prof is None:
        return _dispatch_flat_plain(plans, ctx, k).merge()
    t0 = time.monotonic()
    pending = _dispatch_flat_plain(plans, ctx, k)
    t1 = time.monotonic()
    # the profiled request's explicit sync: device phase = dispatch end →
    # every launch complete (legal ONLY here — the request opted in)
    pending.sync()
    t2 = time.monotonic()
    out = pending.merge()
    t3 = time.monotonic()
    prof.phase_s("dispatch", t1 - t0)
    prof.phase_s("device", t2 - t1)
    pull_s = (pending.pull_t1 - pending.pull_t0) \
        if pending.pull_t0 is not None else 0.0
    prof.phase_s("pull", pull_s)
    prof.phase_s("merge", max(t3 - t2 - pull_s, 0.0))
    return out


def _segment_hits(scores, docs, n_docs: int, base: int):
    """One segment's pulled top documents (scores float32 [Q, k], local docs
    [Q, k]) as _merge_seg_hits takes them: a slot counts where its document
    is one of the segment's `n_docs` and its score is finite, and holds the
    global doc id; every other slot -inf and an id past every document."""
    valid = (docs < n_docs) & np.isfinite(scores)
    return (np.where(valid, scores, -np.inf),
            np.where(valid, docs.astype(np.int64, copy=False) + base,
                     np.int64(2**62)))


def _merge_pulled(ctx: ShardContext, n_docs: list, pulled: list, Q: int,
                  k: int) -> list[TopDocs]:
    """TopDocs a plan from a dense group's pulled outputs, a segment each:
    (scores, docs, totals, ...), whose rows past `Q` are the launch's padding
    up its ladder; `n_docs` a segment, as _segment_hits takes it."""
    totals = np.zeros(Q, dtype=np.int64)
    seg_hits = []
    for base, n, out in zip(ctx.searcher.bases, n_docs, pulled):
        totals += out[2][:Q]
        seg_hits.append(_segment_hits(out[0][:Q], out[1][:Q], n, base))
    return _merge_seg_hits(seg_hits, totals, Q, k,
                           breaker=ctx.breaker("request"))


def _merge_launches(ctx: ShardContext, n_docs: list, members: list,
                    pulled: list, Q: int, k: int) -> list[TopDocs]:
    """TopDocs a plan from the pulled outputs of a group whose plans launch
    apart on a segment (the phrase and dis_max groups: by rung): `members`
    holds (segment index, plan indexes) a launch, `pulled` its (scores, docs,
    totals), whose rows past the launch's members are its ladder's padding. A
    plan no launch of a segment names matches nothing there; one that several
    name (a long phrase's tiles, each over its own documents) has their
    totals added and their hits merged like those of as many segments."""
    totals = np.zeros(Q, dtype=np.int64)
    kk = max(k, 1)
    bases = ctx.searcher.bases
    hits = []
    for (si, qis), (s, d, tq) in zip(members, pulled):
        n = len(qis)
        scores = np.full((Q, kk), -np.inf, np.float32)
        docs = np.zeros((Q, kk), np.int64)
        scores[qis, : s.shape[1]] = s[:n]
        docs[qis, : s.shape[1]] = d[:n]
        totals[qis] += tq[:n]
        hits.append(_segment_hits(scores, docs, n_docs[si], bases[si]))
    return _merge_seg_hits(hits, totals, Q, k, breaker=ctx.breaker("request"))


def _merge_seg_hits(seg_hits, totals, Q: int, k: int,
                    breaker=None) -> list[TopDocs]:
    """Cross-segment top-k merge: score desc, global doc asc — the Lucene
    tie-break order (single site; shared by the plain and function_score paths).

    The host-side merge buffers (concatenated score/doc canvases plus the
    per-query negated-score copy for lexsort) are reserved on the request
    breaker BEFORE np.concatenate allocates them — a wide batch over many
    segments is exactly the allocation the reference's request breaker guards."""
    if not seg_hits:
        return [TopDocs(total=0, hits=[], max_score=float("nan")) for _ in range(Q)]
    width = sum(s.shape[1] for (s, _d) in seg_hits)
    # f32 scores + i64 docs concatenated, + one negated f32 row per lexsort
    est = Q * width * (4 + 8) + width * 4
    with reserve(breaker, est, "<merge_seg_hits>"):
        all_scores = np.concatenate([s for (s, _d) in seg_hits], axis=1)
        all_docs = np.concatenate([d for (_s, d) in seg_hits], axis=1)
        out = []
        totals_h = totals.tolist()
        for qi in range(Q):
            order = np.lexsort((all_docs[qi], -all_scores[qi]))[:k]
            order = order[np.isfinite(all_scores[qi, order])]
            # one batched pull per query, not 2k scalar conversions (tpulint TPU001)
            hits = list(zip(all_scores[qi, order].tolist(),
                            all_docs[qi, order].tolist()))
            out.append(TopDocs(
                total=totals_h[qi],
                hits=hits,
                max_score=hits[0][0] if hits else float("nan"),
            ))
    return out


def _ensure_norm_rows(packed, all_fields, breaker=None):
    """Dense-launch prologue (every dense path funnels through here): fault in
    the lazy f32 freqs plane and the head-term rows under the fielddata
    `breaker` (the blk_freqs-drop rule — sparse-only segments never allocated
    them), and zero-fill norms_stack rows for queried fields this segment
    never indexed."""
    import jax.numpy as jnp

    from ..ops.device_index import ensure_blk_freqs, ensure_head_rows

    ensure_blk_freqs(packed, breaker=breaker)
    ensure_head_rows(packed, breaker=breaker)
    for f in all_fields:
        if f not in packed.norm_bytes:
            packed.norm_bytes[f] = jnp.zeros(packed.doc_pad, dtype=jnp.uint8)


def _dense_entries(finals, seg, packed, field_idx, slots=None) -> list:
    """One (qidx, b0, b1, weight, fidx, group, mode, row) record per clause
    whose term this segment holds — [b0, b1) its block rows in the packed
    planes, `row` its row of the segment's head_rows (-1: the term has none),
    qidx = position in `finals`, or where `slots` is given (a list a final, an
    accumulator row a clause: launch_flat_dismax) the clause's entry there.
    scoring.build_term_batch gives a dense launch the row where there is one
    and expands the range where there is not; the sparse planner reads the
    ranges alone."""
    entries = []
    row_of = packed.head_row_of
    for qi, (resolved, _f, _c, _coord) in enumerate(finals):
        for ci, (f, t, w, _fi, g, mode, df) in enumerate(resolved):
            tid = seg.term_id(f, t)
            if tid is None:
                continue
            b0, b1 = packed.blocks_for_term(tid)
            entries.append((qi if slots is None else slots[qi][ci], b0, b1, w,
                            field_idx[f], g, mode, row_of.get(tid, -1)))
    return entries


def _term_batch(entries, Q, n_must, msm, coord_tbl, all_fields, caches_stack,
                packed, floor: int = 0):
    """scoring.build_term_batch over `packed`'s planes (after
    _ensure_norm_rows): padding triples point at its all-sentinel block row,
    padding head slots at its head plane's last row."""
    from ..ops.scoring import build_term_batch

    return build_term_batch(entries, Q, n_must, msm, coord_tbl, all_fields,
                            caches_stack,
                            nb_pad_row=packed.blk_docs.shape[0] - 1,
                            head_pad_row=packed.head_rows.shape[0] - 1,
                            floor=floor)


def _postings_scanned(finals, seg) -> int:
    """Postings under every clause's term in this segment (profile API only)."""
    tids = [seg.term_id(f, t) for (resolved, _f, _c, _coord) in finals
            for (f, t, *_rest) in resolved]
    return sum(int(seg.post_offsets[tid + 1] - seg.post_offsets[tid])
               for tid in tids if tid is not None)


def _launch_dense_fallback(overflow, entries, all_fields, caches_stack,
                           n_must, msm, coord_tbl, packed, k, breaker=None):
    """Launch overflow queries (block count past the sparse planner's tb_max)
    on the dense scatter kernel WITHOUT syncing; `entries` are the batch's
    _dense_entries records. Returns (sub indices, device result triple,
    blocks the launch named) for the merge half, or None when no entries
    resolved."""
    from ..ops.scoring import score_term_batch_async

    _ensure_norm_rows(packed, all_fields, breaker=breaker)
    row = {qi: i for i, qi in enumerate(overflow)}
    entries = [(row[e[0]], *e[1:]) for e in entries if e[0] in row]
    if not entries:
        return None
    sub = np.asarray(overflow, dtype=np.int64)
    batch = _term_batch(entries, len(overflow), n_must[sub], msm[sub],
                        coord_tbl[sub], list(all_fields), caches_stack, packed)
    return sub, score_term_batch_async(packed, batch, k), batch.blocks_real


def _prof_dense_segment(prof, seg, packed, batch, path: str, t_seg: float,
                        launched=None):
    """Per-segment profile record for the dense kernel families (fs /
    filtered / sorted / aggs) — the batch holds one (query, block) triple per
    scanned block, and `blocks_real` is the count the launch counters took.
    `launched`: the segment's device outputs where the family has not pulled
    them yet (sorted / aggs); the profile's per-request sync waits for them,
    so `ms` holds the program as it did when the launch pulled."""
    if prof is None:
        return
    if launched is not None:
        import jax

        jax.block_until_ready(launched)
    prof.segment(seg.gen, docs=int(seg.doc_count), path=path,
                 tf_layout=packed.tf_layout, blocks_scanned=batch.blocks_real,
                 launches=1, ms=(time.monotonic() - t_seg) * 1000.0)


def _segment_batches(plans: list[FlatPlan], ctx: ShardContext):
    """`batch_for(seg, packed)`: the operands of a dense launch of `plans`
    on one segment. Scored plans are finalized and assembled once and staged a
    segment at a time (a scoring.TermBatch); plans with no scoring clause
    (all of `plans` or none: callers do not mix) stage their constant scores
    alone, the same scoring.ConstBatch for every segment."""
    if plans[0].const is not None:
        from ..ops.scoring import LAUNCHES, ConstBatch

        LAUNCHES.bump(unscored_plans=len(plans))
        batch = ConstBatch(np.array([unscored_score(p, ctx) for p in plans],
                                    np.float32))
        return lambda seg, packed: batch
    Q = len(plans)
    finals = [finalize_flat(p, ctx) for p in plans]
    (all_fields, field_idx, _cache_rows, caches_stack,
     coord_tbl, n_must, msm) = _assemble_batch(plans, finals)

    def batch_for(seg, packed):
        _ensure_norm_rows(packed, all_fields,
                          breaker=ctx.breaker("fielddata"))
        entries = _dense_entries(finals, seg, packed, field_idx)
        return _term_batch(entries, Q, n_must, msm, coord_tbl,
                           list(all_fields), caches_stack, packed)

    return batch_for


def _fs_function_rows(fsq, seg, ctx: ShardContext, doc_pad: int):
    """The host's side of a "rows" launch on one segment: the spec's doc-only
    function values, score_mode-combined (functions.combined_doc_rows —
    float32, bit-identical to the host tail), and the documents a function
    applies to, each padded to `doc_pad`."""
    from .functions import combined_doc_rows

    D = seg.doc_count
    g_row = np.ones(doc_pad, np.float32)
    applies_row = np.zeros(doc_pad, bool)
    if fsq.functions:
        g_row[:D], applies_row[:D] = combined_doc_rows(
            fsq, np.zeros(D, np.float32), seg, ctx)
    return g_row, applies_row


def _fs_script_rows(sf, used_fields, seg, ctx: ShardContext, doc_pad: int):
    """The host's side of a "script" launch on one segment: one float32 row a
    column the script reads (NaN: no value), the script function's filter
    mask, the parent documents that lack a column (they rerun on the host)
    and the parent mask, each padded to `doc_pad`."""
    from .filters import segment_mask
    from .functions import _column_first_value

    D = seg.doc_count
    col_rows = []
    colmiss = np.zeros(D, bool)
    for f in used_fields:
        col = _column_first_value(seg, f)
        colmiss |= np.isnan(col)
        row = np.full(doc_pad, np.nan, np.float32)
        row[:D] = col.astype(np.float32)
        col_rows.append(row)
    parent_row = np.zeros(doc_pad, bool)
    parent_row[:D] = seg.parent_mask
    bad_row = np.zeros(doc_pad, bool)
    bad_row[:D] = seg.parent_mask & colmiss
    fmask_row = np.zeros(doc_pad, bool)
    if sf.filter is not None:
        fmask_row[:D] = segment_mask(seg, sf.filter, ctx)
    return tuple(col_rows), fmask_row, bad_row, parent_row


def _fs_rows_key(fsq, kind: str, used_fields, ctx: ShardContext):
    """What a function_score group's rows of a segment are computed from
    (the key of the device row store's FUNCTION_ROWS space), or None where
    the rows are not a pure function of (segment, key) and must be evaluated
    for every launch.

    "rows": the functions and the score_mode, the part of _fs_group_key that
    combined_doc_rows reads; functions.doc_rows_are_the_segments says whether
    they are the segment's own (no decay that reads the clock, no filter
    whose mask spans more than the segment). "script": the columns the
    script reads and its function's filter (the script's source and params
    are statics of the program, not of the rows); the columns are the
    segment's, so only the filter is asked (Filter.cacheable()).
    `max_boost`, `boost`, `min_score` and `boost_mode` are scalars of the
    launch and no part of either key."""
    if kind == "rows":
        from .functions import doc_rows_are_the_segments

        if not doc_rows_are_the_segments(fsq, ctx):
            return None
        return ("rows", repr(fsq.functions), fsq.score_mode)
    filt = fsq.functions[0].filter
    if filt is None:
        return ("script", tuple(used_fields), None)
    return ("script", tuple(used_fields), filt.key()) if filt.cacheable() \
        else None


def _fs_segment_rows(key, evaluate, seg, ctx: ShardContext, doc_pad: int):
    """The rows a function_score launch reads of `seg`, looked up before
    they are evaluated: the resident device rows where the node's row store
    (ops/device_index.DeviceFilterCache, its FUNCTION_ROWS space) holds
    (segment, key), else `evaluate(seg, ctx, doc_pad)` on the host
    (_fs_function_rows or _fs_script_rows) with the filter masks' own
    sighting-based promotion (maybe_store: the second sighting among the
    segment's last 256 misses puts the rows once and publishes them). The
    values are the host's either way, bit for bit. `key` None (rows that are
    not the segment's own: _fs_rows_key) always evaluates.

    Counted a launch group a segment as `fs_rows_resident` or
    `fs_rows_evaluated`; `fs_row_put_bytes` counts the evaluated rows' bytes
    (they go down once, into the store or with the launch; a hit adds 0)."""
    import jax

    from ..ops.device_index import FUNCTION_ROWS
    from ..ops.scoring import LAUNCHES

    fc = ctx.filter_cache
    kept = key is not None and fc is not None and fc.enabled
    if kept:
        rows = fc.lookup(seg, key, FUNCTION_ROWS)
        if rows is not None:
            LAUNCHES.bump(fs_rows_resident=1)
            return rows
    host = evaluate(seg, ctx, doc_pad)
    LAUNCHES.bump(fs_rows_evaluated=1, fs_row_put_bytes=sum(
        row.nbytes for row in jax.tree_util.tree_leaves(host)))
    rows = fc.maybe_store(seg, key, host, FUNCTION_ROWS) if kept else None
    return host if rows is None else rows


def launch_flat_fs(plans: list[FlatPlan], ctx: ShardContext, k: int,
                    tail=None):
    """function_score plans (at most _GROUP_WIDTH) sharing ONE spec (see
    _fs_group_key; all scored or all unscored: _flat_groups) through the
    dense kernel with the function tail fused in, behind the launch ABI of
    their kind (a scoring.TermBatch, or a ConstBatch for sub queries with no
    scoring clause, whose `_score` is their constant). One launch a segment,
    its query count up the ladder the aggregated and sorted groups ride
    (_group_operands: a spec has two programs, not one a count), and NO
    pull: returns (device outputs a segment, finish); `finish(pulled)` takes
    the outputs on the host (the batch's one device_get: _run_flat_groups)
    and returns TopDocs a plan.

    "rows": the spec's doc-only function values are host-combined once per
    segment (_fs_function_rows) and shipped as a row. "script": the single
    _score-reading script is traced into the kernel; queries flagged bad
    (missing columns / non-finite values on parent docs) rerun on the host
    in `finish`, so error semantics are preserved. A segment's rows are
    looked up in the device row store before they are evaluated
    (_fs_segment_rows): a spec that recurs finds them resident from its
    second sighting on, and the launch takes the resident arrays (same
    shapes and dtypes: the same compiled program). The lookup-or-evaluate is
    noted on the dispatch clock as `shard.fs_rows` (inside its
    `dispatch.stage`); what the host evaluated is counted as
    `search_serving.launch.fs_row_put_bytes`.

    A ScriptError raised while a segment's rows are evaluated sends the
    whole group to the host scorer, which is authoritative for error
    semantics: the handle then has no device outputs and its `finish` runs
    every plan there."""
    from ..common.errors import ScriptError
    from ..ops.device_index import packed_for
    from ..ops.scoring import (score_fs_rows_batch_async,
                               score_fs_script_batch_async)
    from ..script import compile_script, script_vector_info

    fsq = plans[0].fs
    kind = plans[0].fs_kind  # classified once at lower time
    Q = len(plans)

    script = used_fields = sf = None
    if kind == "script":
        sf = fsq.functions[0]
        script = compile_script(sf.script, sf.params)
        used_fields = script_vector_info(script)[1]
    evaluate = partial(_fs_function_rows, fsq) if kind == "rows" \
        else partial(_fs_script_rows, sf, used_fields)
    rows_key = _fs_rows_key(fsq, kind, used_fields, ctx)

    def on_host(_pulled=None) -> list[TopDocs]:
        return [_host_search(ctx, p.fs, k) for p in plans]

    launched = []
    n_docs = []
    prof = _profile.current()
    try:
        operands = _group_operands(plans, ctx)
        for seg in ctx.searcher.segments:
            t_seg = time.monotonic() if prof is not None else 0.0
            packed = packed_for(seg, breaker=ctx.breaker("fielddata"),
                                owner=ctx.index_name)
            t_rows = time.monotonic()
            rows = _fs_segment_rows(rows_key, evaluate, seg, ctx,
                                    packed.doc_pad)
            tracing.note("shard.fs_rows", t_rows)
            batch, fmask = operands(seg, packed)
            with compile_tag("function_score"):
                if kind == "rows":
                    launched.append(score_fs_rows_batch_async(
                        packed, batch, k, fmask, *rows, fsq.max_boost,
                        fsq.boost, fsq.min_score, fsq.boost_mode,
                        no_functions=not fsq.functions))
                else:
                    launched.append(score_fs_script_batch_async(
                        packed, batch, k, fmask, script, used_fields,
                        *rows, sf.weight, fsq.max_boost, fsq.boost,
                        fsq.min_score, fsq.boost_mode,
                        has_filter=sf.filter is not None))
            n_docs.append(min(packed.doc_pad, seg.doc_count))
            _prof_dense_segment(prof, seg, packed, batch,
                                "dense_function_score", t_seg, launched[-1])
    except ScriptError:
        return (), on_host
    if not launched:
        return (), on_host  # a view with no segment

    def finish(pulled: list) -> list[TopDocs]:
        merged = _merge_pulled(ctx, n_docs, pulled, Q, k)
        if kind == "script":  # the members a segment's program flagged bad
            for qi in sorted({int(qi) for out in pulled
                              for qi in np.nonzero(out[3][:Q])[0]}):
                merged[qi] = _host_search(ctx, plans[qi].fs, k)
        return merged

    return launched, finish


def _filter_mask_matrix(filters: list, seg, packed, ctx: ShardContext,
                        n_rows: int | None = None):
    """The [Q, Dpad] FilteredQuery mask the dense kernels consume — the ONE
    assembly site for the filtered/sorted/aggregated paths. A query with no
    filter (None: an unscored plan that matches every document) takes
    MatchAllFilter's row; where no query has one the result is None and the
    launch takes scoring's resident [1, 1] no-op.

    Per query, a row has one of three sources. A resident device row from
    the node's filter cache when the (segment, filter-key) mask is already in
    HBM (zero host evaluation, zero transfer). Else, for a prefix, wildcard
    or regexp (filters.MultiTermFilter: a filter, or the wrapper of such a
    QUERY, which is not cacheable and never resident), a row BUILT ON THE
    CHIP: the host expands the pattern over the segment's sorted dictionary
    into block rows of the postings plane (search/multiterm.py) and
    scoring.build_multiterm_rows gathers and ORs them, with no pull and no
    put of a row between. Else host evaluation via the per-segment host
    filter cache (`segment_mask`) with sighting-based promotion to device
    residency (DeviceFilterCache.maybe_store: build outside locks,
    device_put once, publish under the leaf lock).

    A host row holds every document with a matching value, dead and nested
    ones too: the launch's live gate is the view's own, so one cached row
    serves every view of the segment (with_deletes views share the cache's
    holder). A built row comes from `blk_docs`, in which THIS view's dead
    documents are masked already: it answers this view exactly as a host
    row does, but an older view (a scroll, a search in flight) would miss
    the documents it still holds. So a cacheable filter's built row is
    admitted by the sighting rule, without the put, only where the view has
    no tombstone; on a view with one the row is built again each search.

    Returns the mask operand of the launch, which the tail turns into its
    [Q, Dpad] matrix inside the program (scoring._mask_matrix); no row is
    put for the launch and no program is dispatched here but a build launch
    (a row the sighting rule promotes is put once, into the cache). A host bool
    [Q, Dpad] when every row stayed host-side (one leaf of the launch's one
    put: scoring._dense_args); the device matrix one build launch returned
    where it built every row of the batch in place (a batch of multi-term
    searches of the first rung); else a TUPLE of Q [Dpad] rows, resident
    ones as they are and host stragglers as numpy rows that ride the
    launch's put: the program stacks them, so the drainer dispatches no
    eager stack (it was an expand_dims a row and a concatenate, each a
    Python-dispatched device call, for every batch). Where a row was
    evaluated on the host, the whole assembly is noted on the dispatch clock
    as `shard.filter_mask` (inside its `dispatch.stage`), and every host
    row's bytes are counted as `search_serving.launch.mask_put_bytes`; where
    one was built on the chip, the expansion and the operands' put are noted
    as `shard.multiterm_expand` and counted under
    `search_serving.launch.multiterm_*`. `n_rows` pads the mask with
    rows that match nothing (a coalesced batch of unscored plans rides the
    pow-2 ladder of query counts, whether its rows are resident or not)."""
    if all(f is None for f in filters):
        return None
    fc = ctx.filter_cache
    rows = []
    any_dev = False
    host_bytes = 0
    builds = []  # (place in `rows`, cache key | None, block rows to gather)
    tally = {"multiterm_terms": 0, "multiterm_runs": 0,
             "multiterm_field_scans": 0}
    t0 = time.monotonic()
    for f in filters:
        if f is None:
            f = MatchAllFilter()
        row = None
        key = None
        if fc is not None and fc.enabled and f.cacheable():
            key = f.key()
            row = fc.lookup(seg, key)
        named = f.block_rows(seg, packed, ctx) if row is None else None
        if named is not None:
            blk, terms, runs, whole_field = named
            builds.append((len(rows), key, blk))
            tally["multiterm_terms"] += terms
            tally["multiterm_runs"] += runs
            tally["multiterm_field_scans"] += whole_field
            rows.append(None)
            continue
        if row is None:
            m = np.zeros(packed.doc_pad, dtype=bool)
            m[: seg.doc_count] = segment_mask(seg, f, ctx)
            if key is not None:
                row = fc.maybe_store(seg, key, m)
            if row is None:
                row = m
            host_bytes += m.nbytes
        if not isinstance(row, np.ndarray):
            any_dev = True
        rows.append(row)
    want = max(n_rows or 0, len(rows))
    whole = None
    if builds:
        from ..ops.scoring import LAUNCHES, _false_row, build_multiterm_rows

        any_dev = True
        admit = bool(seg.live.all())
        for at, _key, _blk in builds:
            rows[at] = _false_row(packed.doc_pad)  # a list with no row
        launched = build_multiterm_rows(
            packed, [blk for _at, _key, blk in builds], t0)
        for places, _matrix, built in launched:
            for place, row in zip(places, built):
                at, key, _blk = builds[place]
                if key is not None and admit:
                    # the sighting rule's admission, with nothing to transfer
                    kept = fc.maybe_store(seg, key, row)
                    row = row if kept is None else kept
                rows[at] = row
        if len(launched) == 1 and launched[0][0] == list(range(len(rows))) \
                and launched[0][1].shape[0] == want:
            whole = launched[0][1]  # one launch built every row, in place
        # a plan is counted once, on the view's first segment
        LAUNCHES.bump(multiterm_searches=len(builds)
                      if seg is ctx.searcher.segments[0] else 0, **tally)
    if whole is not None:
        out = whole
    elif not any_dev:
        out = np.stack(rows + [np.zeros(packed.doc_pad, dtype=bool)]
                       * (want - len(rows)))
    else:
        from ..ops.scoring import _false_row

        rows.extend([_false_row(packed.doc_pad)] * (want - len(rows)))
        out = tuple(rows)
    if host_bytes:
        from ..ops.scoring import LAUNCHES

        LAUNCHES.bump(mask_put_bytes=host_bytes)
        tracing.note("shard.filter_mask", t0)
    return out


def launch_flat_filtered(plans: list[FlatPlan], ctx: ShardContext, k: int,
                         tail=None):
    """Filtered plans: per-query filter masks (host-evaluated via the per-segment
    filter cache — the same masks the host scorer uses) gate matching inside the
    dense kernel. Scores/weights are untouched, so sub-query scoring parity is
    inherited from the plain path. Plans with no scoring clause come here too
    (all of `plans` or none): their mask is their whole match set, their
    score a constant, and hits of equal score merge in document order. One
    launch a segment and NO pull: returns (device outputs a segment, finish);
    `finish(pulled)` takes the outputs on the host (the batch's one
    device_get: _run_flat_groups) and returns TopDocs a plan."""
    from ..ops.device_index import _pow2_bucket, packed_for
    from ..ops.scoring import score_filtered_batch_async

    Q = len(plans)
    batch_for = _segment_batches(plans, ctx)
    launched = []
    n_docs = []
    prof = _profile.current()
    for seg in ctx.searcher.segments:
        t_seg = time.monotonic() if prof is not None else 0.0
        packed = packed_for(seg, breaker=ctx.breaker("fielddata"),
                            owner=ctx.index_name)
        batch = batch_for(seg, packed)
        fmask = _filter_mask_matrix(
            [plan.filt for plan in plans], seg, packed, ctx,
            n_rows=_pow2_bucket(Q, 1) if plans[0].const is not None else Q)
        with compile_tag("filtered"):
            launched.append(score_filtered_batch_async(packed, batch, k, fmask))
        n_docs.append(min(packed.doc_pad, seg.doc_count))
        _prof_dense_segment(prof, seg, packed, batch, "dense_filtered",
                            t_seg, launched[-1])

    return launched, partial(_merge_pulled, ctx, n_docs, Q=Q, k=k)


def _phrase_launches(by_rung: dict):
    """((field, rung), plans) a launch of one segment: the plans of the first
    rung together (_group_width: 1 or 4 a launch), those of a longer rung,
    a long phrase's tiles among them, one a launch. A merge costs the device
    what its lines hold, so four long lines in one launch save a dispatch and
    nothing else, and cost a program of their own that a warm-up seldom meets
    and four times the temporaries (0.86 GB a plan at the second rung)."""
    from ..ops.scoring import PHRASE_RUNGS

    for key, group in by_rung.items():
        if key[1] == PHRASE_RUNGS[0]:
            yield key, group
        else:
            for member in group:
                yield key, [member]


def _phrase_tiles(plane, named: dict, below: np.ndarray, n_docs: int,
                  tile_rows: int):
    """A plan whose longest kept list passes `tile_rows` block rows, cut by
    document ranges: [(lo, hi, {term id: block rows})], every term's rows of
    a tile within `tile_rows`, or None where no cut of a few tiles does it
    (the host serves). A phrase is matched inside one document, so the
    documents [lo, hi) with the rows that hold their candidates
    (PositionsPlane.rows_holding over the candidates of the range alone) are
    a whole problem: the tiles' totals add up and their hits merge like
    segments'. A row at a tile's edge may hold keys of the next tile's
    documents, so the program counts a match inside [lo, hi) alone
    (scoring._phrase_impl, `mine`). The cuts fall on the first document of
    every (rows / tiles)-th row of the longest list; more tiles are tried
    where a shorter list crowds one range."""
    longest = max(named.values(), key=len)
    least = -(-len(longest) // tile_rows)
    for n_tiles in range(least, 2 * least + 1):
        firsts = longest[np.arange(1, n_tiles) * len(longest) // n_tiles]
        cuts = [0, *plane.blk_first[firsts].tolist(), n_docs]
        tiles = []
        for lo, hi in zip(cuts, cuts[1:]):
            if below[hi] == below[lo]:
                continue  # no candidate in the range
            inside = np.clip(below, below[lo], below[hi])
            tiles.append((lo, hi, {tid: plane.rows_holding(tid, inside)
                                   for tid in named}))
        if all(len(rows) <= tile_rows for _lo, _hi, by_term in tiles
               for rows in by_term.values()):
            return tiles
    return None


def launch_flat_phrase(plans: list[FlatPlan], ctx: ShardContext, k: int,
                       tail=None):
    """Phrase plans (at most _GROUP_WIDTH) over every segment's positions
    plane, faulted in by the first phrase a segment's field meets
    (device_index.ensure_positions). A phrase can only occur in the documents
    of its rarest term in the segment, the lead: of every term's block rows a
    launch names those whose document range holds one of the lead's documents
    (docs_below, PositionsPlane.rows_holding; the lead's postings, deleted
    and non-parent documents among them, is a superset, which is enough), and
    the program gathers that list (scoring, "exact phrases", argues why the
    answer is the whole lists'). On a segment the plans launch by the rung of
    the most rows a term keeps (scoring.phrase_rung): those of the first rung
    together at the group's width (_group_width: 1 or 4 plans a launch, rows
    past them matching nothing), those of a longer rung one a launch
    (_phrase_launches), so a rare phrase does not ride a head term's line,
    and a head term beside a rare one rides the first rung. A launch's line
    is as long as its members have terms (scoring.phrase_slots reads the
    entries it is handed, nothing else): two slots of the rung where every
    member is a pair, one merge over half the places, else the four a plan
    may hold; a long rung launches one plan, so a pair there is a pair's
    program, and on the first rung a pair beside a longer phrase rides the
    longer one's line (to launch them apart would add a launch to a batch for
    a rung that holds a fourteenth of the device's time). A plan whose
    longest kept list passes the second rung is cut by document ranges into
    tiles of that rung, a launch each (_phrase_tiles; scoring, PHRASE_RUNGS:
    the last rung is no program), every tile told the documents it answers
    for. NO pull: returns (device outputs a launch, finish), or None where a
    segment's positions do not fit the plane's keys, the rows a term keeps
    outgrow the last rung or no cut fits its tiles (the host serves every
    plan). `finish(pulled)` takes the outputs on the
    host (the batch's one device_get: _run_flat_groups) and returns TopDocs a
    plan. The host's share, the rows each term keeps and the operands' one
    device_put, is the span `shard.phrase_plan` inside `dispatch.stage`."""
    from ..ops.device_index import (docs_below, ensure_positions,
                                    ensure_sim_tables, packed_for)
    from ..ops.scoring import (LAUNCHES, PHRASE_ALL_DOCS, PHRASE_RUNGS,
                               phrase_rung, phrase_tile_rows,
                               score_phrase_batch_async)

    Q = len(plans)
    finals = [finalize_phrase(p, ctx) for p in plans]
    sim_tables = {p.phrase.field: (mode, cache)
                  for p, (_w, mode, cache) in zip(plans, finals)}
    shifts = [[max(p.phrase.rel_pos) - r for r in p.phrase.rel_pos]
              for p in plans]
    breaker = ctx.breaker("fielddata")
    fields = sorted({p.phrase.field for p in plans})
    staged = []  # a segment: (packed, sim, planes, {(field, rung): [(plan index, entry)]}, t0)
    for seg in ctx.searcher.segments:
        packed = packed_for(seg, breaker=breaker, owner=ctx.index_name)
        sim = ensure_sim_tables(packed, sim_tables)
        planes = {f: ensure_positions(seg, packed, f, breaker=breaker)
                  for f in fields}
        t0 = time.monotonic()
        by_rung: dict = {}
        for qi, (plan, (w, _mode, _cache)) in enumerate(zip(plans, finals)):
            ph = plan.phrase
            plane = planes[ph.field]
            if not plane.room_for(max(shifts[qi])):
                return None
            tids = [seg.term_id(ph.field, t) for t in ph.terms]
            if any(tid is None for tid in tids):
                continue  # a term the segment lacks: no match here
            lead = min(ph.terms, key=lambda t: seg.doc_freq(ph.field, t))
            below = docs_below(seg.postings(ph.field, lead)[0], seg.doc_count)
            named = {tid: plane.rows_holding(tid, below) for tid in set(tids)}
            rung = phrase_rung(max(len(rows) for rows in named.values()))
            if rung is None:
                return None
            tiles = [(*PHRASE_ALL_DOCS, named)]
            if rung == PHRASE_RUNGS[-1] and phrase_tile_rows():
                rung = phrase_tile_rows()
                tiles = _phrase_tiles(plane, named, below, seg.doc_count, rung)
                if tiles is None:
                    return None
            for lo, hi, rows in tiles:
                by_rung.setdefault((ph.field, rung), []).append((qi, (
                    w, sim.fid[ph.field],
                    [(rows[tid], plane.rows_between(tid, lo, hi), shift)
                     for tid, shift in zip(tids, shifts[qi])], (lo, hi))))
        staged.append((packed, sim, planes, by_rung, t0))
    launched = []
    members = []  # a launch: (segment index, plan indexes)
    for si, (packed, sim, planes, by_rung, t0) in enumerate(staged):
        for (field, rung), group in _phrase_launches(by_rung):
            try:
                if _DEVICE_FAULTS.active:
                    _DEVICE_FAULTS.check("compile:phrase")
                with compile_tag("phrase"):
                    launched.append(score_phrase_batch_async(
                        planes[field], sim, [entry for _qi, entry in group],
                        _group_width(len(group)), rung, max(k, 1),
                        note_t0=t0))
            except Exception as e:  # noqa: BLE001 — re-raised tagged
                raise _tag_domain(e, "compile:phrase")
            members.append((si, [qi for qi, _entry in group]))
            t0 = time.monotonic()
    LAUNCHES.bump(phrase_searches=Q)
    n_docs = [min(entry[0].doc_pad, seg.doc_count)
              for seg, entry in zip(ctx.searcher.segments, staged)]

    return launched, partial(_merge_launches, ctx, n_docs, members, Q=Q, k=k)


def launch_flat_dismax(plans: list[FlatPlan], ctx: ShardContext, k: int,
                       tail=None):
    """dis_max plans (at most _GROUP_WIDTH) on the dense core with an
    accumulator a (plan, disjunct): a launch of Qp plans holds a TermBatch of
    Qp * D rows, D the most disjuncts a member of the group has (static in
    the program's key: 2 to DISMAX_SLOTS), and the clause of its q-th plan's
    disjunct d is staged under row q * D + d, so the dense gather, scatter
    and head rows run as they are and the program's combine folds a plan's D
    planes into one before the top-k cut (scoring, "dis_max"). Rows past a
    plan's disjuncts and plans past the members hold no clause: they add
    zero to a max and a sum, and match nothing.

    A launch's shape is its query count and its triples' rung M, and every
    program a window launches has to have been met before it (a first
    sighting compiles for seconds on the one drainer), so neither follows
    what a batch happens to hold. On a segment the plans whose tail blocks
    fit TAIL_FLOOR launch together at the group's width (_group_width: 1 or
    4) with M fixed at a TAIL_FLOOR a plan, whatever they sum to: two
    programs, which a warm-up's first answers and its pool pass meet. A plan
    of more blocks launches alone at its own rung, which the pool pass met
    when it sent that plan (the sum of four plans' blocks rode three rungs,
    the rarest once in a hundred launches: one window in six compiled).

    NO pull: returns (device outputs a launch, finish); `finish(pulled)`
    takes the outputs on the host (the batch's one device_get:
    _run_flat_groups) and returns TopDocs a plan. The host's share of a
    launch, the clauses' block rows under their rows and the operand plane's
    one device_put, is the span `shard.dismax_plan` inside `dispatch.stage`."""
    from ..ops.device_index import packed_for
    from ..ops.scoring import (LAUNCHES, TAIL_FLOOR,
                               score_dismax_batch_async)

    t0 = time.monotonic()
    Q = len(plans)
    D = max(p.n_disjuncts for p in plans)
    finals = [finalize_flat(p, ctx) for p in plans]
    all_fields, field_idx, _rows, caches_stack, *_bool_semantics = \
        _assemble_batch(plans, finals)
    slots = [[(qi, c.disjunct) for c in p.clauses]
             for qi, p in enumerate(plans)]
    launched = []
    members = []  # a launch: (segment index, plan indexes)
    n_docs = []
    prof = _profile.current()
    for si, seg in enumerate(ctx.searcher.segments):
        packed = packed_for(seg, breaker=ctx.breaker("fielddata"),
                            owner=ctx.index_name)
        _ensure_norm_rows(packed, all_fields,
                          breaker=ctx.breaker("fielddata"))
        n_docs.append(min(packed.doc_pad, seg.doc_count))
        by_plan = [[] for _ in plans]
        tail_blocks = [0] * Q
        for ((qi, d), b0, b1, *rest) in _dense_entries(
                finals, seg, packed, field_idx, slots):
            by_plan[qi].append((d, b0, b1, *rest))
            if rest[-1] < 0:  # no head row: its blocks are scattered
                tail_blocks[qi] += b1 - b0
        together = [qi for qi in range(Q) if tail_blocks[qi] <= TAIL_FLOOR]
        alone = [[qi] for qi in range(Q) if tail_blocks[qi] > TAIL_FLOOR]
        for qis in ([together] if together else []) + alone:
            Qp = _group_width(len(qis))
            # every row is SHOULD clauses alone: no must, no msm, no coord
            none = np.zeros(Qp * D, np.int32)
            batch = _term_batch(
                [(pos * D + d, *rest) for pos, qi in enumerate(qis)
                 for (d, *rest) in by_plan[qi]],
                Qp * D, none, none, np.ones((Qp * D, 2), np.float32),
                list(all_fields), caches_stack, packed,
                floor=0 if qis in alone else Qp * TAIL_FLOOR)
            ties = tuple(plans[qi].tie_breaker for qi in qis) \
                + (0.0,) * (Qp - len(qis))
            with compile_tag("dis_max"):
                launched.append(score_dismax_batch_async(
                    packed, batch, max(k, 1), ties, D, note_t0=t0))
            members.append((si, qis))
            _prof_dense_segment(prof, seg, packed, batch, "dense_dismax", t0,
                                launched[-1])
            t0 = time.monotonic()
    LAUNCHES.bump(dismax_searches=Q,
                  dismax_disjuncts=sum(p.n_disjuncts for p in plans))
    return launched, partial(_merge_launches, ctx, n_docs, members, Q=Q, k=k)


def _sort_row_key(spec) -> tuple:
    """What a field sort's device key row depends on: the key of the row on
    the packed segment (_sort_key_row) and the group key of the sorted
    searches that can share a launch (sort_tail)."""
    return (spec.field, spec.mode, spec.order, repr(spec.missing))


def _sort_key_row(spec, seg, packed, breaker=None):
    """The device-resident f32 [Dpad] key row of a field sort on one segment,
    or None where the host has to sort (sorting.device_sort_key_row /
    device_sort_rank_row say why). Kept on the packed segment per (field,
    mode, order, missing), FIFO-bounded like the agg stacks: a warmed sorted
    launch evaluates no column and puts no row."""
    from ..ops.scoring import _put_operands
    from .sorting import device_sort_key_row, device_sort_rank_row

    key = _sort_row_key(spec)
    row = packed.sort_rows.get(key)
    if row is None:
        host = device_sort_key_row(spec, seg, packed.doc_pad)
        if host is None:
            host = device_sort_rank_row(spec, seg, packed.doc_pad)
        if host is None:
            return None
        with reserve(breaker, host.nbytes * 2, f"<sort_row>{spec.field}"):
            (row,) = _put_operands(host)
        while len(packed.sort_rows) >= 8:
            packed.sort_rows.pop(next(iter(packed.sort_rows)), None)
        packed.sort_rows[key] = row
    return row


# what pads a group of scored plans up the ladder of query counts: no clause
# and msm 1, so it matches nothing (the mesh family pads with the same plan)
_NO_MATCH_PLAN = FlatPlan([], msm=1, n_must=0, coord_enabled=False, boost=1.0)


# the most function_score, phrase, aggregated or sorted plans one launch
# takes (_run_flat_groups launches a larger group four at a time)
_GROUP_WIDTH = 4


def _group_width(n: int) -> int:
    """The query count `n` <= _GROUP_WIDTH aggregated or sorted plans launch
    at: 1 alone, else 4, so a group key has two programs and not one a
    count. Every program a served window launches must have been met before
    it (a first sighting compiles for seconds on the one drainer), and a
    count that a mix of operations reaches a few times a minute is met by no
    warm-up: with a rung 2 and with a rung 8, one window in six of
    `logs.dashboard` compiled. The padding is free where it matters: the
    bucket scatter costs the same at four queries as at one, and a query with
    no scoring clause costs the device 20-30 us (PERF.md section 6, PR 33)."""
    return 1 if n == 1 else _GROUP_WIDTH


def _group_operands(plans: list[FlatPlan], ctx: ShardContext):
    """`operands(seg, packed)` -> (batch, fmask): what a dense launch of a
    group of aggregated or sorted plans takes on one segment, its query count
    up the group's ladder (_group_width). Scored plans are padded with plans
    that match nothing before they are staged; plans with no scoring clause
    (all of `plans` or none) are staged as they are and their ConstBatch and
    mask padded after (scoring.ladder_const_batch), as the filtered family's
    are; where none of them has a filter the rung's resident mask stands in
    (scoring.ladder_mask), so a rung launches one program whether it is full
    or padded. The callers slice the padding off every result."""
    from ..ops.scoring import ladder_const_batch, ladder_mask

    Qp = _group_width(len(plans))
    unscored = plans[0].const is not None
    filters = [p.filt for p in plans]
    batch_for = _segment_batches(
        plans if unscored else plans + [_NO_MATCH_PLAN] * (Qp - len(plans)),
        ctx)

    def operands(seg, packed):
        batch = batch_for(seg, packed)
        fmask = _filter_mask_matrix(filters, seg, packed, ctx, n_rows=Qp)
        if unscored:
            if fmask is None and Qp > 1:
                fmask = ladder_mask(len(plans), Qp, packed.doc_pad)
            batch, fmask = ladder_const_batch(batch, fmask, packed.doc_pad,
                                              Qp)
        return batch, fmask

    return operands


def launch_flat_sorted(plans: list[FlatPlan], ctx: ShardContext, k: int,
                       tail: FlatTail):
    """Field-sorted dense launches of a group of plans under ONE sort
    (`tail.spec`, the leader's; all scored or all unscored: sort_tail's key
    and _flat_groups), one launch a
    segment and NO pull: returns (device outputs a segment, finish), or None
    when any segment's column refuses device keys (_sort_key_row: the host
    sorts every plan). `finish(pulled)` takes the outputs on the host (one
    device_get for all such groups of a batch: _run_flat_groups) and returns
    a result a plan: (total, max_score, per segment (segment index, local
    docs, scores) of the segment's best min(total, k) by its device keys),
    which sorted_entries merges on the request thread."""
    from ..ops.device_index import packed_for
    from ..ops.scoring import score_sorted_batch_async

    Q = len(plans)
    spec = tail.spec
    # validate EVERY segment's eligibility before the first launch — a
    # late-segment refusal must not waste completed kernel work
    packeds = [packed_for(seg, breaker=ctx.breaker("fielddata"),
                          owner=ctx.index_name)
               for seg in ctx.searcher.segments]
    key_rows = [_sort_key_row(spec, seg, p, breaker=ctx.breaker("fielddata"))
                for seg, p in zip(ctx.searcher.segments, packeds)]
    if any(r is None for r in key_rows):
        return None
    operands = _group_operands(plans, ctx)
    launched = []
    prof = _profile.current()
    for seg, packed, key_row in zip(ctx.searcher.segments, packeds, key_rows):
        t_seg = time.monotonic() if prof is not None else 0.0
        batch, fmask = operands(seg, packed)
        with compile_tag("sorted"):
            launched.append(score_sorted_batch_async(
                packed, batch, max(k, 1), key_row, spec.reverse, fmask=fmask))
        _prof_dense_segment(prof, seg, packed, batch, "dense_sorted", t_seg,
                            launched[-1])

    def finish(pulled: list) -> list:
        totals = [0] * Q
        max_scores = [float("nan")] * Q
        parts: list[list] = [[] for _ in range(Q)]
        for si, (_keys, docs, scores, qmax, tq) in enumerate(pulled):
            # batched host pulls: one .tolist() per row instead of a
            # float()/int() scalar conversion per hit (tpulint TPU001)
            for qi, (seg_total, m) in enumerate(zip(tq[:Q].tolist(),
                                                    qmax[:Q].tolist())):
                if not seg_total:
                    continue
                totals[qi] += seg_total
                max_scores[qi] = m if max_scores[qi] != max_scores[qi] \
                    else max(max_scores[qi], m)
                n = min(seg_total, docs.shape[1])
                parts[qi].append((si, docs[qi, :n], scores[qi, :n]))
        return list(zip(totals, max_scores, parts))

    return launched, finish


def sorted_entries(result, ctx: ShardContext, k: int, spec):
    """The request thread's half of a sorted search: one plan's result of
    launch_flat_sorted as (total, max_score, ordered entries [(key, gdoc,
    seg_idx, local, score)]), the best `k`. Ordering: (key asc/desc, global
    doc asc) — the host lexsort order; `key` is the document's exact float64
    value (sorting.exact_sort_keys), because a segment's device keys may be
    ranks that another segment's do not compare with."""
    from .sorting import exact_sort_keys

    total, max_score, parts = result
    cand = []  # (key, gdoc, seg_idx, local, score)
    for si, locals_, scores in parts:
        seg, base = ctx.searcher.segments[si], ctx.searcher.bases[si]
        cand.extend(
            (ki, base + di, si, di, sc)
            for ki, di, sc in zip(exact_sort_keys(spec, seg, locals_).tolist(),
                                  locals_.tolist(), scores.tolist()))
    cand.sort(key=lambda e: (-e[0] if spec.reverse else e[0], e[1]))
    return total, max_score, cand[: max(k, 0)]


def launch_flat_aggs(plans: list[FlatPlan], ctx: ShardContext, k: int,
                     tail: FlatTail):
    """Dense launches of a group of plans with ONE set of aggregations fused
    into the kernel (`tail.fields` and `tail.bucket_aggs`, the leader's; all
    scored or all unscored: aggs_tail's key and _flat_groups), one launch a
    segment and NO pull: returns (device outputs
    a segment, finish), or None when a segment holds more documents than the
    integer limbs allow (→ host collectors for every plan). `finish(pulled)`
    takes the outputs on the host (one device_get for all such groups of a
    batch: _run_flat_groups) and returns a result a plan: (TopDocs,
    per-segment (counts int [F], stats float32 [F, 4], sums [F], bucket list
    of (keys, counts, sub_cnt|None, sub_stats|None, sub_sums|None))) with
    F = len(fields), stats = (sum, min, max, sumsq) over the plan's matched
    docs and sums the exact integer sum of each whole-number column (a Python
    integer; None for a fractional column, whose sum is stats' float32 one)
    — its own slices of the launch's outputs, which the request thread turns
    into partials (service._try_device_aggs). bucket_aggs: (Agg,
    sub_field_order|None) pairs whose (doc, bucket) pairs ride the kernel's
    scatter (aggregations.bucket_cols_for); metric sub-agg folds scatter
    along the same pairs. Serving uses this when every aggregation is
    device-eligible (service.execute_query_phase →
    aggregations.device_agg_fields / device_bucket_eligible)."""
    import jax
    import jax.numpy as jnp

    from ..ops.device_index import (_pow2_bucket, ensure_agg_rows,
                                    limb_totals, packed_for)
    from ..ops.scoring import score_agg_batch_async
    from .aggregations import bucket_cache_key, bucket_cols_for

    Q = len(plans)
    fields, bucket_aggs = tail.fields, tail.bucket_aggs
    operands = _group_operands(plans, ctx)
    launched = []
    keys_by_seg = []
    n_docs = []
    prof = _profile.current()
    for seg in ctx.searcher.segments:
        t_seg = time.monotonic() if prof is not None else 0.0
        packed = packed_for(seg, breaker=ctx.breaker("fielddata"),
                            owner=ctx.index_name)
        stack = ensure_agg_rows(seg, packed, fields,
                                breaker=ctx.breaker("fielddata"))
        if stack is None:
            return None  # the limbs do not hold a column → host collectors
        pair_args = []
        seg_keys = []
        for agg, sub_order in bucket_aggs:
            pdoc, pbucket, keys = bucket_cols_for(agg, seg, ctx)
            ck = bucket_cache_key(agg)  # same constructor as the host cache
            dev = packed.bucket_cols.get(ck)
            if dev is None:
                from .aggregations import _bucket_cache_put

                # explicit device_put: eager jnp.zeros builds its fill scalar
                # through an implicit host→device transfer, which the
                # transfer_guard("disallow") sanitizer rejects. The NB dim
                # rides the pow-2 ladder — it shapes the scatter outputs
                # inside the jit, so a raw len(keys) would compile one
                # executable per distinct bucket-key count; every consumer
                # zips counts against `keys` and ignores the padding.
                dev = _bucket_cache_put(
                    packed.bucket_cols, ck,
                    (jnp.asarray(pdoc), jnp.asarray(pbucket),
                     jax.device_put(np.zeros(_pow2_bucket(len(keys), 1),
                                             np.int32))))
            sub_stack = None
            if sub_order:
                sub_stack = ensure_agg_rows(seg, packed, sub_order,
                                            breaker=ctx.breaker("fielddata"))
            pair_args.append((dev[0], dev[1], dev[2], sub_stack))
            seg_keys.append((keys, None if sub_stack is None
                             else sub_stack.limbed))
        batch, fmask = operands(seg, packed)
        with compile_tag("aggs"):
            launched.append(score_agg_batch_async(
                packed, batch, k, stack, tuple(pair_args), fmask=fmask))
        keys_by_seg.append((seg_keys, stack.limbed))
        n_docs.append(min(packed.doc_pad, seg.doc_count))
        _prof_dense_segment(prof, seg, packed, batch, "dense_aggs", t_seg,
                            launched[-1])

    def exact(limb_sums, limbed):
        """One plan's limb totals [F, L, ...] as Python integers, a row a
        field: None for a field whose sum is its float32 one."""
        if not any(limbed):
            return [None] * len(limbed)
        whole = limb_totals(limb_sums, 1)
        return [whole[i] if is_limbed else None
                for i, is_limbed in enumerate(limbed)]

    def finish(pulled: list) -> list:
        seg_stats: list[list] = [[] for _ in range(Q)]
        for (seg_keys, limbed), out in zip(keys_by_seg, pulled):
            counts, stats, limb_sums, bcounts = out[3:]
            for qi in range(Q):
                seg_stats[qi].append((
                    counts[qi], stats[qi], exact(limb_sums[qi], limbed), [
                        (keys, bc[qi],
                         None if sc is None else sc[qi],
                         None if ss is None else ss[qi],
                         None if sl is None else exact(sl[qi], sub_limbed))
                        for (keys, sub_limbed), (bc, sc, ss, sl)
                        in zip(seg_keys, bcounts)]))
        return list(zip(_merge_pulled(ctx, n_docs, pulled, Q, k), seg_stats))

    return launched, finish


class GroupKind(NamedTuple):
    """How one kind of group (plan_kind) reaches the device.

    `launch(members, ctx, k, tail)` makes the members' launches, a segment
    each, with NO pull, and returns (device outputs, finish), or None where
    the host serves every member; `finish(pulled)` takes the outputs on the
    host and returns a result a member; `tail` is the leader's FlatTail
    (None for a kind without one). The plain group has no launcher: it keeps
    its own protocol (_PendingFlat; _run_flat_groups says why). `width`: the
    most members one launch takes (the dense accumulator is O(Q * doc_pad),
    and a kind whose query count rides a ladder has a program a rung).
    `families`: the compile families a launch may reach, which are the fault
    domains a search of this kind asks about first (service). `served`: the
    SERVING_COUNTERS outcome of a search this kind answers."""

    launch: object
    width: int
    families: tuple
    served: str


# The ONE place that knows the kinds' names on the launch side.
# batcher._KINDS and jaxenv.COMPILE_FAMILIES, the stats and lint
# vocabularies, are held to it by tests/test_launch_seam.py
_FILTERED_WIDTH = 256  # it pads up no ladder: the accumulator's bound alone
GROUP_KINDS = {
    "plain": GroupKind(None, 0, ("sparse", "dense"), "device_sparse"),
    "function_score": GroupKind(launch_flat_fs, _GROUP_WIDTH,
                                ("function_score",), "device_function_score"),
    "filtered": GroupKind(launch_flat_filtered, _FILTERED_WIDTH,
                          ("filtered",), "device_filtered"),
    "phrase": GroupKind(launch_flat_phrase, _GROUP_WIDTH, ("phrase",),
                        "device_sparse"),
    "dis_max": GroupKind(launch_flat_dismax, _GROUP_WIDTH, ("dis_max",),
                         "device_sparse"),
    "aggs": GroupKind(launch_flat_aggs, _GROUP_WIDTH, ("aggs",),
                      "device_aggs"),
    "sorted": GroupKind(launch_flat_sorted, _GROUP_WIDTH, ("sorted",),
                        "device_sort"),
}


# ---------------------------------------------------------------------------
# host scorer (general path)
# ---------------------------------------------------------------------------


def _weight_prepass(query: Query, ctx: ShardContext) -> float:
    """Sum of squared leaf weights (Lucene getValueForNormalization pre-pass)."""

    def walk(q: Query, boost: float) -> float:
        b = boost * getattr(q, "boost", 1.0)
        if isinstance(q, TermQuery):
            ft = ctx.field_type(q.field)
            if ft is not None and ft.is_numeric:
                return 0.0
            df = ctx.doc_freq(q.field, str(q.value))
            if df <= 0:
                return 0.0
            sim = ctx.similarity_for(q.field)
            idf = sim.idf(df, ctx.max_doc)
            return float((idf * b) ** 2)
        if isinstance(q, MatchQuery):
            total = 0.0
            for t in ctx.analyze(q.field, q.text):
                df = ctx.doc_freq(q.field, t)
                if df > 0:
                    sim = ctx.similarity_for(q.field)
                    total += float((sim.idf(df, ctx.max_doc) * b) ** 2)
            return total
        if isinstance(q, PhraseQuery):
            terms = [t.term for t in ctx.analyze_tokens(q.field, q.text)]
            sim = ctx.similarity_for(q.field)
            idf_sum = sum(
                float(sim.idf(max(ctx.doc_freq(q.field, t), 0), ctx.max_doc))
                for t in terms if ctx.doc_freq(q.field, t) > 0
            )
            return float((idf_sum * b) ** 2)
        if isinstance(q, BoolQuery):
            return sum(walk(s, b) for s in q.must + q.should)
        if isinstance(q, DisMaxQuery):
            return sum(walk(s, b) for s in q.queries)
        if isinstance(q, FilteredQuery):
            return walk(q.query, b)
        if isinstance(q, (ConstantScoreQuery, MatchAllQuery, RangeQuery, PrefixQuery,
                          WildcardQuery, RegexpQuery, FuzzyQuery, IdsQuery)):
            return float(b * b)
        if isinstance(q, FunctionScoreQuery) and q.query is not None:
            return walk(q.query, b)
        if isinstance(q, NestedQuery):
            return walk(q.query, b)
        return float(b * b)

    return walk(query, 1.0)


def query_norm_for(query: Query, ctx: ShardContext) -> float:
    if not isinstance(ctx.default_similarity, TFIDFSimilarity):
        return 1.0
    ssw = _weight_prepass(query, ctx)
    return float(TFIDFSimilarity.query_norm(ssw)) if ssw > 0 else 1.0


class HostScorer:
    """Recursive dense evaluation of one query against one segment.
    Produces (scores float32[D], match bool[D]); live/parent masking happens in the
    caller so nested/child evaluation can see non-parent docs."""

    def __init__(self, ctx: ShardContext, seg: FrozenSegment, query_norm: float = 1.0):
        self.ctx = ctx
        self.seg = seg
        self.qn = np.float32(query_norm)
        self.D = seg.doc_count

    # -- leaf helpers --------------------------------------------------------
    def _term_scores(self, field: str, term: str, boost: float) -> tuple[np.ndarray, np.ndarray]:
        seg, ctx = self.seg, self.ctx
        scores = np.zeros(self.D, dtype=np.float32)
        match = np.zeros(self.D, dtype=bool)
        df = ctx.doc_freq(field, term)
        docs, freqs = seg.postings(field, term)
        if df <= 0 or len(docs) == 0:
            return scores, match
        sim = ctx.similarity_for(field)
        norms = seg.norms.get(field)
        nb = norms[docs] if norms is not None else np.zeros(len(docs), np.uint8)
        cache = sim.norm_cache(ctx.field_stats(field), ctx.max_doc)
        if isinstance(sim, BM25Similarity):
            w = np.float32(sim.idf(df, ctx.max_doc) * boost * (sim.k1 + 1.0))
            # tf factor first, then weight — bit-parity with the device kernels'
            # in-scan tfn (ops/scoring.sparse_candidates)
            vals = w * (freqs / (freqs + cache[nb]))
        elif isinstance(sim, FreqNormSimilarity):
            # generic freq/doc-len similarities (DFR, IB, LM*) — host-only path
            from ..common.smallfloat import decode_norm_doclen

            dl = decode_norm_doclen(nb)
            ttf = sum(int(s.postings(field, term)[1].sum())
                      for s in ctx.searcher.segments
                      if s.doc_freq(field, term) > 0)
            vals = sim.score_freqs(freqs, dl, df, ttf, ctx.field_stats(field),
                                   ctx.max_doc, boost)
        else:
            idf = TFIDFSimilarity.idf(df, ctx.max_doc)
            w = np.float32(idf * idf * boost) * self.qn
            vals = w * (np.sqrt(freqs, dtype=np.float32) * cache[nb])
        scores[docs] = vals.astype(np.float32)
        match[docs] = True
        return scores, match

    def _const(self, mask: np.ndarray, boost: float) -> tuple[np.ndarray, np.ndarray]:
        scores = np.where(mask, np.float32(boost * self.qn), np.float32(0.0)).astype(np.float32)
        return scores, mask.copy()

    def _mask(self, f: Filter) -> np.ndarray:
        return segment_mask(self.seg, f, self.ctx)

    # -- main dispatch -------------------------------------------------------
    def eval(self, q: Query, boost: float = 1.0) -> tuple[np.ndarray, np.ndarray]:
        b = boost * getattr(q, "boost", 1.0)
        seg, ctx = self.seg, self.ctx

        if isinstance(q, MatchAllQuery):
            return self._const(np.ones(self.D, dtype=bool), b)

        if isinstance(q, TermQuery):
            ft = ctx.field_type(q.field)
            if ft is not None and ft.is_numeric:
                from .filters import TermFilter

                return self._const(self._mask(TermFilter(q.field, q.value)), b)
            return self._term_scores(q.field, str(q.value), b)

        if isinstance(q, MatchQuery):
            if q.fuzziness is not None:
                terms = ctx.analyze(q.field, q.text)
                subs = [FuzzyQuery(q.field, t, q.fuzziness, 0, q.max_expansions) for t in terms]
                return self.eval(BoolQuery(should=subs, minimum_should_match=1), b)
            terms = ctx.analyze(q.field, q.text)
            if not terms:
                return np.zeros(self.D, np.float32), np.zeros(self.D, bool)
            sub = (BoolQuery(must=[TermQuery(q.field, t) for t in terms])
                   if q.operator == "and"
                   else BoolQuery(should=[TermQuery(q.field, t) for t in terms],
                                  minimum_should_match=q.minimum_should_match or 1))
            return self.eval(sub, b)

        if isinstance(q, MultiMatchQuery):
            subs = multi_match_subqueries(q)
            if q.type in ("best_fields", "phrase", "phrase_prefix"):
                return self.eval(DisMaxQuery(queries=subs, tie_breaker=q.tie_breaker), b)
            return self.eval(BoolQuery(should=subs, minimum_should_match=1,
                                       disable_coord=True), b)

        if isinstance(q, BoolQuery):
            return self._eval_bool(q, b)

        if isinstance(q, FilteredQuery):
            scores, match = self.eval(q.query, b)
            fmask = self._mask(q.filter)
            return np.where(fmask, scores, 0).astype(np.float32), match & fmask

        if isinstance(q, ConstantScoreQuery):
            if q.filter is not None:
                return self._const(self._mask(q.filter), b)
            _, match = self.eval(q.query, 1.0)
            return self._const(match, b)

        if isinstance(q, DisMaxQuery):
            scores = np.zeros(self.D, np.float32)
            best = np.zeros(self.D, np.float32)
            total = np.zeros(self.D, np.float32)
            match = np.zeros(self.D, bool)
            for sub in q.queries:
                s, m = self.eval(sub, b)
                s = np.where(m, s, 0).astype(np.float32)
                best = np.maximum(best, s)
                total += s
                match |= m
            tie = np.float32(q.tie_breaker)
            scores = best + tie * (total - best)
            return np.where(match, scores, 0).astype(np.float32), match

        if isinstance(q, RangeQuery):
            from .filters import RangeFilter

            return self._const(self._mask(RangeFilter(q.field, q.gte, q.gt, q.lte, q.lt)), b)

        if isinstance(q, (PrefixQuery, WildcardQuery, RegexpQuery)):
            return self._const(self._multi_term_mask(q), b)

        if isinstance(q, FuzzyQuery):
            terms = self._fuzzy_terms(q)
            mask = np.zeros(self.D, bool)
            for t in terms:
                docs, _ = seg.postings(q.field, t)
                mask[docs] = True
            return self._const(mask, b)

        if isinstance(q, IdsQuery):
            from .filters import IdsFilter

            return self._const(self._mask(IdsFilter(q.ids, q.types)), b)

        if isinstance(q, PhraseQuery):
            return self._eval_phrase(q, b)

        if isinstance(q, QueryStringQuery):
            return self.eval(parse_query_string(q, self.ctx), b)

        if isinstance(q, CommonTermsQuery):
            return self.eval(self._rewrite_common(q), b)

        if isinstance(q, FunctionScoreQuery):
            return self._eval_function_score(q, b)

        if isinstance(q, NestedQuery):
            mask, scores = child_match_to_parents(
                seg, ctx, q.path, q.query, score_mode=q.score_mode, query_norm=float(self.qn)
            )
            return (scores * np.float32(b)).astype(np.float32), mask

        if isinstance(q, (HasChildQuery, HasParentQuery)):
            # resolved at shard level (cross-segment join) — executor special-cases;
            # segment-local fallback: no match
            return np.zeros(self.D, np.float32), np.zeros(self.D, bool)

        if isinstance(q, BoostingQuery):
            scores, match = self.eval(q.positive, b)
            _, neg = self.eval(q.negative, 1.0)
            scores = np.where(neg, scores * np.float32(q.negative_boost), scores)
            return scores.astype(np.float32), match

        if isinstance(q, MoreLikeThisQuery):
            return self.eval(self._rewrite_mlt(q), b)

        if isinstance(q, SpanTermQuery):
            return self._term_scores(q.field, q.value, b)

        if isinstance(q, (SpanNearQuery, SpanOrQuery, SpanFirstQuery, SpanNotQuery,
                          SpanMultiTermQuery, FieldMaskingSpanQuery)):
            return self._eval_spans(q, b)

        if isinstance(q, IndicesQuery):
            # ref: IndicesQueryParser — the query applies on the named indices,
            # no_match_query (default all, "none" = nothing) elsewhere
            import fnmatch

            name = getattr(self.ctx, "index_name", None)
            if name is None or any(fnmatch.fnmatch(name, p)
                                   for p in (q.indices or [])):
                return self.eval(q.query, b * q.boost)
            if q.no_match_none:
                return (np.zeros(self.D, np.float32), np.zeros(self.D, bool))
            if q.no_match_query is None:
                return self.eval(MatchAllQuery(), b * q.boost)
            return self.eval(q.no_match_query, b * q.boost)

        if isinstance(q, SimpleQueryStringQuery):
            return self.eval(parse_simple_query_string(q), b)

        if isinstance(q, FuzzyLikeThisQuery):
            return self.eval(self._rewrite_flt(q), b)

        raise QueryParsingError(f"unsupported query type {type(q).__name__}")

    # -- bool ---------------------------------------------------------------
    def _eval_bool(self, q: BoolQuery, boost: float):
        D = self.D
        scores = np.zeros(D, np.float32)
        matched_count = np.zeros(D, np.int32)
        must_ok = np.ones(D, bool)
        excluded = np.zeros(D, bool)
        should_count = np.zeros(D, np.int32)
        n_scoring = 0
        for sub in q.must:
            s, m = self.eval(sub, boost)
            scores += np.where(m, s, 0).astype(np.float32)
            must_ok &= m
            matched_count += m
            n_scoring += 1
        for sub in q.should:
            s, m = self.eval(sub, boost)
            scores += np.where(m, s, 0).astype(np.float32)
            should_count += m
            matched_count += m
            n_scoring += 1
        for sub in q.must_not:
            _, m = self.eval(sub, 1.0)
            excluded |= m
        fmask = np.ones(D, bool)
        for f in q.filter:
            fmask &= self._mask(f)
        msm = calculate_msm(q.minimum_should_match, len(q.should))
        if msm == 0 and q.should and not q.must:
            msm = 1
        match = must_ok & ~excluded & fmask & (should_count >= msm)
        if not q.must and not q.should:
            match = fmask & ~excluded  # filter/must_not-only bool matches all remaining
            scores = np.where(match, np.float32(boost * q.boost * self.qn), 0).astype(np.float32)
            return scores, match
        match &= matched_count > 0
        if (not q.disable_coord and n_scoring > 1
                and isinstance(self.ctx.default_similarity, TFIDFSimilarity)):
            coord = matched_count.astype(np.float32) / np.float32(n_scoring)
            scores = scores * coord
        return np.where(match, scores, 0).astype(np.float32), match

    # -- spans ---------------------------------------------------------------
    # The span family enumerates (start, end) position windows per doc, composed
    # recursively — the host-plane equivalent of Lucene's Spans enumerations
    # (ref: SpanOrQueryParser.java:1, SpanFirstQueryParser.java:1,
    # SpanNotQueryParser.java:1, SpanMultiTermQueryParser.java:1,
    # FieldMaskingSpanQueryParser.java:1). Scoring mirrors this framework's phrase
    # convention: freq = number of matching spans (exact for adjacent matches;
    # documented approximation of Lucene's sloppyFreq weighting otherwise).

    def _span_tree(self, q):
        """Returns (field, {local_doc: sorted [(start, end)]}, contributing terms)."""
        seg = self.seg
        if isinstance(q, SpanTermQuery):
            docs, _ = seg.postings(q.field, q.value)
            pos_lists = seg.term_positions(q.field, q.value)
            spans = {int(d): [(int(p), int(p) + 1) for p in np.sort(pl)]
                     for d, pl in zip(docs, pos_lists) if len(pl)}
            return q.field, spans, {(q.field, q.value)}
        if isinstance(q, SpanMultiTermQuery):
            inner = q.match
            if isinstance(inner, _MULTI_TERM_QUERIES):
                field = inner.field
                sorted_terms, first = seg.sorted_terms(field)
                terms = [sorted_terms[t - first] for t in multiterm.expand(
                    seg, field, *_multiterm_pattern(inner)).tids.tolist()]
            elif isinstance(inner, FuzzyQuery):
                terms = self._fuzzy_terms(inner)
                field = inner.field
            else:
                raise QueryParsingError(
                    f"span_multi does not support [{type(inner).__name__}]")
            spans: dict = {}
            termset = set()
            for t in terms:
                _f, s2, t2 = self._span_tree(SpanTermQuery(field, t))
                termset |= t2
                for d, sp in s2.items():
                    spans.setdefault(d, []).extend(sp)
            return field, {d: sorted(set(sp)) for d, sp in spans.items()}, termset
        if isinstance(q, FieldMaskingSpanQuery):
            _f, spans, terms = self._span_tree(q.query)
            return q.field, spans, terms
        if isinstance(q, SpanOrQuery):
            field, spans, termset = None, {}, set()
            for c in q.clauses:
                f2, s2, t2 = self._span_tree(c)
                field = field or f2
                if f2 != field:
                    raise QueryParsingError("span_or clauses must share a field")
                termset |= t2
                for d, sp in s2.items():
                    spans.setdefault(d, []).extend(sp)
            return field, {d: sorted(set(sp)) for d, sp in spans.items()}, termset
        if isinstance(q, SpanFirstQuery):
            field, spans, terms = self._span_tree(q.match)
            out = {d: [s for s in sp if s[1] <= q.end] for d, sp in spans.items()}
            return field, {d: sp for d, sp in out.items() if sp}, terms
        if isinstance(q, SpanNotQuery):
            field, inc, terms = self._span_tree(q.include)
            f2, exc, _t2 = self._span_tree(q.exclude)
            if f2 != field:
                raise QueryParsingError("span_not include/exclude must share a field")
            out = {}
            for d, sp in inc.items():
                ex = exc.get(d)
                keep = sp if not ex else [
                    s for s in sp
                    if not any(e[0] < s[1] and s[0] < e[1] for e in ex)]
                if keep:
                    out[d] = keep
            # Lucene SpanNotQuery extracts only include terms into the weight
            return field, out, terms
        if isinstance(q, SpanNearQuery):
            field, children, termset = None, [], set()
            for c in q.clauses:
                f2, s2, t2 = self._span_tree(c)
                field = field or f2
                if f2 != field:
                    raise QueryParsingError("span_near clauses must share a field")
                children.append(s2)
                termset |= t2
            if not children:
                return field, {}, termset
            docs = set(children[0])
            for s2 in children[1:]:
                docs &= set(s2)
            spans = {}
            for d in docs:
                found = _near_spans([s2[d] for s2 in children], q.slop, q.in_order)
                if found:
                    spans[d] = found
            return field, spans, termset
        raise QueryParsingError(f"not a span query: {type(q).__name__}")

    def _eval_spans(self, q, boost: float):
        seg, ctx = self.seg, self.ctx
        scores = np.zeros(self.D, np.float32)
        match = np.zeros(self.D, bool)
        field, spans, termset = self._span_tree(q)
        if not spans or field is None:
            return scores, match
        sim = ctx.similarity_for(field)
        cache = sim.norm_cache(ctx.field_stats(field), ctx.max_doc)
        norms = seg.norms.get(field)
        idf_sum = np.float32(sum(
            float(sim.idf(ctx.doc_freq(f, t), ctx.max_doc))
            for (f, t) in sorted(termset) if ctx.doc_freq(f, t) > 0))
        for d, sp in spans.items():
            freq = len(sp)
            nb = norms[d] if norms is not None else 0
            if isinstance(sim, BM25Similarity):
                w = np.float32(idf_sum * boost * (sim.k1 + 1.0))
                scores[d] = w * (np.float32(freq) / (np.float32(freq) + cache[nb]))
            else:
                w = np.float32(idf_sum * idf_sum * boost) * self.qn
                scores[d] = w * (np.sqrt(np.float32(freq)) * cache[nb])
            match[d] = True
        return scores, match

    # -- multi-term ----------------------------------------------------------
    def _multi_term_mask(self, q) -> np.ndarray:
        """The documents that hold a term the prefix, wildcard or regexp
        names: multiterm.expand's terms, the ones the device path gathers."""
        return multiterm.host_mask(self.seg, q.field, *_multiterm_pattern(q))

    def _fuzzy_terms(self, q: FuzzyQuery) -> list[str]:
        max_edits = _fuzzy_max_edits(q.fuzziness, q.value)
        out = []
        for term in self.seg.terms_for_field(q.field):
            if q.prefix_length and not term.startswith(q.value[: q.prefix_length]):
                continue
            if _within_edits(q.value, term, max_edits):
                out.append(term)
                if len(out) >= q.max_expansions:
                    break
        return out

    # -- phrase --------------------------------------------------------------
    def _eval_phrase(self, q: PhraseQuery, boost: float, in_order: bool = True):
        seg, ctx = self.seg, self.ctx
        scores = np.zeros(self.D, np.float32)
        match = np.zeros(self.D, bool)
        if hasattr(q, "_pre_analyzed"):
            terms = list(q._pre_analyzed)  # type: ignore[attr-defined]
            rel_pos = list(range(len(terms)))
        else:
            toks = ctx.analyze_tokens(q.field, q.text)
            terms = [t.term for t in toks]
            rel_pos = [t.position for t in toks]
        if not terms:
            return scores, match
        if len(terms) == 1 and not q.prefix:
            return self._term_scores(q.field, terms[0], boost)
        last_terms = [terms[-1]]
        if q.prefix:
            sorted_terms = seg.terms_for_field(q.field)
            lo, hi = multiterm.head_range(sorted_terms, terms[-1])
            last_terms = sorted_terms[lo: min(hi, lo + q.max_expansions)]
            if not last_terms:
                return scores, match
        # candidate docs: intersection of postings
        doc_sets = []
        for t in terms[:-1]:
            docs, _ = seg.postings(q.field, t)
            doc_sets.append(set(docs.tolist()))
        last_docs: set = set()
        for lt in last_terms:
            docs, _ = seg.postings(q.field, lt)
            last_docs.update(docs.tolist())
        doc_sets.append(last_docs)
        candidates = sorted(set.intersection(*doc_sets)) if doc_sets else []
        if not candidates:
            return scores, match
        # positions check
        pos_maps = []
        for t in terms[:-1]:
            pos_maps.append(_positions_by_doc(seg, q.field, t))
        last_pos: dict[int, set] = {}
        for lt in last_terms:
            for d, ps in _positions_by_doc(seg, q.field, lt).items():
                last_pos.setdefault(d, set()).update(ps)
        sim = ctx.similarity_for(q.field)
        norms = seg.norms.get(q.field)
        cache = sim.norm_cache(ctx.field_stats(q.field), ctx.max_doc)
        idf_sum = np.float32(sum(
            float(sim.idf(ctx.doc_freq(q.field, t), ctx.max_doc))
            for t in terms if ctx.doc_freq(q.field, t) > 0
        ))
        for d in candidates:
            freq = _phrase_freq(
                [pm.get(d, set()) for pm in pos_maps] + [last_pos.get(d, set())],
                rel_pos, q.slop, in_order,
            )
            if freq <= 0:
                continue
            nb = norms[d] if norms is not None else 0
            if isinstance(sim, BM25Similarity):
                w = np.float32(idf_sum * boost * (sim.k1 + 1.0))
                scores[d] = w * (np.float32(freq) / (np.float32(freq) + cache[nb]))
            else:
                w = np.float32(idf_sum * idf_sum * boost) * self.qn
                scores[d] = w * (np.sqrt(np.float32(freq)) * cache[nb])
            match[d] = True
        return scores, match

    # -- rewrites ------------------------------------------------------------
    def _rewrite_common(self, q: CommonTermsQuery) -> Query:
        ctx = self.ctx
        terms = ctx.analyze(q.field, q.text)
        max_doc = max(ctx.max_doc, 1)
        low, high = [], []
        for t in terms:
            df = ctx.doc_freq(q.field, t)
            cutoff = q.cutoff_frequency
            threshold = cutoff * max_doc if cutoff < 1.0 else cutoff
            (high if df > threshold else low).append(TermQuery(q.field, t))
        if not low:
            op_group = q.high_freq_operator
            return BoolQuery(must=high if op_group == "and" else [],
                             should=high if op_group != "and" else [],
                             minimum_should_match=q.minimum_should_match)
        low_bool = BoolQuery(must=low if q.low_freq_operator == "and" else [],
                             should=low if q.low_freq_operator != "and" else [],
                             minimum_should_match=q.minimum_should_match)
        if not high:
            return low_bool
        return BoolQuery(must=[low_bool], should=high, disable_coord=True)

    def _rewrite_mlt(self, q: MoreLikeThisQuery) -> Query:
        from collections import Counter

        ctx = self.ctx
        shoulds = []
        for field in q.fields:
            counts = Counter(ctx.analyze(field, q.like_text))
            scored = []
            for t, tf in counts.items():
                if tf < q.min_term_freq:
                    continue
                df = ctx.doc_freq(field, t)
                if df < q.min_doc_freq or df <= 0:
                    continue
                idf = TFIDFSimilarity.idf(df, ctx.max_doc)
                scored.append((float(tf * idf), t))
            scored.sort(reverse=True)
            for _, t in scored[: q.max_query_terms]:
                shoulds.append(TermQuery(field, t))
        return BoolQuery(should=shoulds, minimum_should_match=q.minimum_should_match)

    def _rewrite_flt(self, q: FuzzyLikeThisQuery) -> Query:
        """ref: FuzzyLikeThisQueryParser.java:1 — like_text analyzed per field,
        each term OR-expanded to its fuzzy neighborhood. Legacy float
        fuzziness < 1 is a min-similarity: edits = min(2, ⌊(1-sim)·len⌋) — the
        classic Lucene FuzzyQuery conversion."""
        ctx = self.ctx
        fields = q.fields or ["_all"]
        shoulds: list = []
        budget = max(int(q.max_query_terms), 1)
        for field in fields:
            terms = list(dict.fromkeys(ctx.analyze(field, q.like_text)))[:budget]
            for t in terms:
                fz = q.fuzziness
                try:
                    f_val = float(fz)
                    if 0 < f_val < 1:
                        fz = min(2, int((1.0 - f_val) * len(t)))
                except (TypeError, ValueError):
                    pass
                shoulds.append(FuzzyQuery(field, t, fz, q.prefix_length))
        return BoolQuery(should=shoulds, minimum_should_match=1, boost=q.boost)

    # -- function score ------------------------------------------------------
    def _eval_function_score(self, q: FunctionScoreQuery, boost: float):
        from .functions import apply_functions

        if q.query is not None:
            sub_scores, match = self.eval(q.query, 1.0)
        elif q.filter is not None:
            sub_scores, match = self._const(self._mask(q.filter), 1.0)
        else:
            sub_scores, match = self._const(np.ones(self.D, bool), 1.0)
        scores = apply_functions(q, sub_scores, match, self.seg, self.ctx)
        scores = (scores * np.float32(boost)).astype(np.float32)
        if q.min_score is not None:
            match = match & (scores >= np.float32(q.min_score))
        return scores, match


def _positions_by_doc(seg: FrozenSegment, field: str, term: str) -> dict[int, set]:
    tid = seg.term_id(field, term)
    if tid is None:
        return {}
    s, e = int(seg.post_offsets[tid]), int(seg.post_offsets[tid + 1])
    out = {}
    docs = seg.post_docs[s:e].tolist()  # one batched pull, not int() per doc
    for i, d in zip(range(s, e), docs):
        out[d] = set(seg.positions[seg.pos_offsets[i]: seg.pos_offsets[i + 1]].tolist())
    return out


def _near_spans(lists: list[list[tuple[int, int]]], slop: int,
                in_order: bool) -> list[tuple[int, int]]:
    """Compose child span lists into near-spans with total gap <= slop.

    Ordered: one span per clause, each starting at or after the previous clause's
    end (Lucene NearSpansOrdered's non-overlap rule), gap = sum of inter-span
    distances. Unordered: any one span per clause, gap = covering width minus total
    child length (overlaps clamp to 0). Enumeration is bounded (the per-doc span
    count is small); combos past the cap are dropped rather than searched."""
    out: set[tuple[int, int]] = set()
    if in_order:
        budget = [20000]  # recursion guard for pathological position lists

        def rec(i: int, start: int, prev_end: int, gap: int):
            if budget[0] <= 0:
                return
            if i == len(lists):
                out.add((start, prev_end))
                return
            for (s, e) in lists[i]:
                if i > 0 and s < prev_end:
                    continue
                g = gap + (s - prev_end if i > 0 else 0)
                if g > slop:
                    continue
                budget[0] -= 1
                rec(i + 1, start if i > 0 else s, e, g)

        rec(0, 0, 0, 0)
    else:
        import itertools

        for combo in itertools.islice(itertools.product(*lists), 20000):
            mn = min(s for s, _e in combo)
            mx = max(e for _s, e in combo)
            gap = max((mx - mn) - sum(e - s for s, e in combo), 0)
            if gap <= slop:
                out.add((mn, mx))
    return sorted(out)


def _phrase_freq(pos_sets: list[set], rel_pos: list[int], slop: int, in_order: bool) -> int:
    """Count phrase occurrences. slop=0: exact relative positions. slop>0: alignments
    whose total displacement ≤ slop (greedy per anchor — matches Lucene for common
    cases; documented approximation for pathological overlaps)."""
    if not pos_sets or any(not s for s in pos_sets):
        return 0
    first = pos_sets[0]
    count = 0
    for p0 in sorted(first):
        if slop == 0:
            if all((p0 + rel_pos[i] - rel_pos[0]) in pos_sets[i] for i in range(1, len(pos_sets))):
                count += 1
        else:
            total_disp = 0
            ok = True
            prev = p0
            for i in range(1, len(pos_sets)):
                expected = p0 + rel_pos[i] - rel_pos[0]
                cands = pos_sets[i]
                if in_order:
                    cands = {c for c in cands if c > prev}
                if not cands:
                    ok = False
                    break
                nearest = min(cands, key=lambda c: abs(c - expected))
                total_disp += abs(nearest - expected)
                prev = nearest
            if ok and total_disp <= slop:
                count += 1
    return count


def _multiterm_pattern(q) -> tuple[str, str]:
    """(kind, pattern) of a prefix, wildcard or regexp query, as
    multiterm.expand takes them."""
    if isinstance(q, PrefixQuery):
        return multiterm.PREFIX, q.prefix
    return (multiterm.WILDCARD if isinstance(q, WildcardQuery)
            else multiterm.REGEXP), q.pattern


def _fuzzy_max_edits(fuzziness, value: str) -> int:
    if fuzziness in ("AUTO", "auto", None):
        n = len(value)
        return 0 if n <= 2 else (1 if n <= 5 else 2)
    try:
        return int(float(fuzziness))
    except (TypeError, ValueError):
        return 1


def _within_edits(a: str, b: str, max_edits: int) -> bool:
    if abs(len(a) - len(b)) > max_edits:
        return False
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i] + [0] * len(b)
        row_min = i
        for j, cb in enumerate(b, 1):
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + (ca != cb))
            row_min = min(row_min, cur[j])
        if row_min > max_edits:
            return False
        prev = cur
    return prev[-1] <= max_edits


# ---------------------------------------------------------------------------
# query_string mini-parser (subset of Lucene syntax)
# ---------------------------------------------------------------------------

_QS_TOKEN = re.compile(
    r"\s*(?:(\()|(\))|(AND\b|&&)|(OR\b|\|\|)|(NOT\b|!)|([+-])?"
    r"(?:(\w[\w.]*):)?(?:\"([^\"]*)\"|([^\s()]+)))"
)


_SQS_TOKEN = re.compile(
    r'\s*(?:(\|)|(\+)|(-)|"([^"]*)"(?:~(\d+))?|([^\s|+\-][^\s|+]*))'
)


def parse_simple_query_string(q: "SimpleQueryStringQuery") -> Query:
    """The degraded-gracefully syntax (ref: SimpleQueryStringParser.java:1 /
    Lucene SimpleQueryParser): whitespace-separated terms joined by the default
    operator, `+` forces AND, `|` forces OR, leading `-` negates, `"..."` is a
    phrase (optional ~slop), a trailing `*` is a prefix. Invalid syntax never
    errors — stray operators degrade to plain text handling."""
    fields = q.fields or ["_all"]

    def node_for(phrase, slop, word):
        subs: list = []
        for f in fields:
            fname, _, fboost = f.partition("^")
            boost = float(fboost) if fboost else 1.0
            if phrase is not None:
                subs.append(PhraseQuery(fname, phrase, slop=int(slop or 0),
                                        boost=boost))
            elif word.endswith("*") and len(word) > 1:
                subs.append(PrefixQuery(fname, word[:-1].lower(), boost))
            else:
                subs.append(MatchQuery(fname, word, boost=boost))
        if len(subs) == 1:
            return subs[0]
        return BoolQuery(should=subs, minimum_should_match=1,
                         disable_coord=True)

    must, should, must_not = [], [], []
    pending = None  # explicit connective seen since the last term
    negate = False
    for m in _SQS_TOKEN.finditer(q.query):
        bar, plus, minus, phrase, slop, word = m.groups()
        if bar:
            # "a | b": explicit OR releases its LEFT operand from must (the
            # default_operator=and case) — Lucene's SimpleQueryParser OR wins
            if must:
                should.append(must.pop())
            pending = "or"
            continue
        if plus:
            pending = "and"
            continue
        if minus:
            negate = True
            continue
        node = node_for(phrase, slop, word)
        if negate:
            must_not.append(node)
        elif pending == "and" or (pending is None
                                  and q.default_operator == "and"):
            if pending == "and" and should:
                must.append(should.pop())  # "a + b": AND binds its left operand
            must.append(node)
        else:
            should.append(node)
        pending = None
        negate = False
    if not must and not should and not must_not:
        return MatchAllQuery()
    if len(should) == 1 and not must and not must_not:
        out = should[0]
        out.boost = out.boost * q.boost
        return out
    return BoolQuery(must=must, should=should, must_not=must_not, boost=q.boost)


def parse_query_string(q: QueryStringQuery, ctx: ShardContext) -> Query:
    """field:term, AND/OR/NOT, +/-, "phrases", wild*cards, (grouping — flattened)."""
    default_fields = q.fields or [q.default_field]
    must, should, must_not = [], [], []
    pending_op = None
    for m in _QS_TOKEN.finditer(q.query):
        lparen, rparen, and_, or_, not_, sign, fname, phrase, word = m.groups()
        if lparen or rparen:
            continue
        if and_:
            # "a AND b": the left operand becomes required too
            if should:
                must.append(should.pop())
            pending_op = "and"
            continue
        if or_:
            pending_op = "or"
            continue
        if not_:
            pending_op = "not"
            continue
        target_fields = [fname] if fname else default_fields
        subs: list[Query] = []
        for f in target_fields:
            if phrase is not None:
                subs.append(PhraseQuery(f, phrase))
            elif word == "*":
                subs.append(MatchAllQuery())
            elif word and ("*" in word or "?" in word):
                subs.append(WildcardQuery(f, word))
            elif word and "~" in word:
                base, _, fuzz = word.partition("~")
                subs.append(FuzzyQuery(f, base, fuzz or "AUTO"))
            elif word:
                subs.append(MatchQuery(f, word))
            else:
                continue
        node = subs[0] if len(subs) == 1 else DisMaxQuery(queries=subs)
        if sign == "+" or pending_op == "and" or (pending_op is None and q.default_operator == "and"):
            must.append(node)
        elif sign == "-" or pending_op == "not":
            must_not.append(node)
        else:
            should.append(node)
        pending_op = None
    if not must and not should and not must_not:
        return MatchAllQuery()
    if len(should) == 1 and not must and not must_not:
        out = should[0]
        out.boost = out.boost * q.boost
        return out
    return BoolQuery(must=must, should=should, must_not=must_not, boost=q.boost)


# ---------------------------------------------------------------------------
# nested / parent-child joins
# ---------------------------------------------------------------------------


def _parent_of_map(seg: FrozenSegment) -> np.ndarray:
    cache = seg._device_cache
    pm = cache.get("parent_of")
    if pm is None:
        pm = np.zeros(seg.doc_count, dtype=np.int64)
        parent = -1
        for local in range(seg.doc_count - 1, -1, -1):
            if seg.parent_mask[local]:
                parent = local
            pm[local] = parent
        cache["parent_of"] = pm
    return pm


def child_match_to_parents(seg: FrozenSegment, ctx: ShardContext, path: str, inner,
                           score_mode: str = "none", query_norm: float = 1.0):
    """Block-join: evaluate `inner` over nested child docs of `path`, aggregate to
    parents (ref: index/search/nested/ block-join queries)."""
    child_sel = np.asarray(
        [p == path for p in seg.nested_paths], dtype=bool
    )
    if isinstance(inner, Filter):
        cmask = segment_mask(seg, inner, ctx)
        cscores = cmask.astype(np.float32)
    else:
        scorer = HostScorer(ctx, seg, query_norm)
        cscores, cmask = scorer.eval(inner)
    cmask = cmask & child_sel
    parents = _parent_of_map(seg)
    pmask = np.zeros(seg.doc_count, dtype=bool)
    pscores = np.zeros(seg.doc_count, dtype=np.float32)
    pcounts = np.zeros(seg.doc_count, dtype=np.int32)
    idx = np.nonzero(cmask)[0]
    if len(idx):
        pidx = parents[idx]
        valid = pidx >= 0
        idx, pidx = idx[valid], pidx[valid]
        pmask[pidx] = True
        if score_mode in ("sum", "avg", "total"):
            np.add.at(pscores, pidx, cscores[idx])
            np.add.at(pcounts, pidx, 1)
            if score_mode == "avg":
                nz = pcounts > 0
                pscores[nz] = pscores[nz] / pcounts[nz]
        elif score_mode == "max":
            np.maximum.at(pscores, pidx, cscores[idx])
        else:
            pscores[pidx] = 1.0
    return pmask, pscores


def host_match_mask(query: Query, seg: FrozenSegment, ctx: ShardContext) -> np.ndarray:
    _, match = HostScorer(ctx, seg).eval(query)
    return match


# ---------------------------------------------------------------------------
# shard-level entry points
# ---------------------------------------------------------------------------


def search_shard(ctx: ShardContext, query: Query, k: int, use_device: bool = True,
                 extra_filter: Filter | None = None, deadline=None) -> TopDocs:
    return search_shard_batch(ctx, [query], k, use_device=use_device,
                              extra_filter=extra_filter, deadline=deadline)[0]


def search_shard_batch(ctx: ShardContext, queries: list[Query], k: int,
                       use_device: bool = True,
                       extra_filter: Filter | None = None,
                       deadline=None) -> list[TopDocs]:
    """Execute a batch: flat-lowerable queries fused onto the device, the rest host.

    `deadline` (common.deadline.Deadline) clamps HOST execution at segment
    granularity; device launches are never interrupted (a deadline check cannot
    cross into traced code), so the flat path runs whole once started."""
    results: list[TopDocs | None] = [None] * len(queries)
    flat_idx: list[int] = []
    flat_plans: list[FlatPlan] = []
    if extra_filter is None:
        for i, q in enumerate(queries):
            plan = lower_flat(q, ctx, phrases=True) if use_device else None
            if plan is not None:
                flat_idx.append(i)
                flat_plans.append(plan)
    if flat_plans:
        for i, td in zip(flat_idx, execute_flat_batch(flat_plans, ctx, k)):
            results[i] = td
    for i, q in enumerate(queries):
        if results[i] is None:
            results[i] = _host_search(ctx, q, k, extra_filter, deadline)
    return results  # type: ignore[return-value]


def _shard_join(ctx: ShardContext, q: Query):
    """Cross-segment parent/child join: returns per-segment (scores, match) overrides
    for has_child / has_parent queries, else None."""
    if not isinstance(q, (HasChildQuery, HasParentQuery)):
        return None
    from .filters import TermFilter

    out = []
    if isinstance(q, HasChildQuery):
        # collect matching children's _parent ids across segments
        parent_ids: dict[str, float] = {}
        for seg in ctx.searcher.segments:
            scorer = HostScorer(ctx, seg, 1.0)
            s, m = scorer.eval(q.query)
            m = m & np.asarray([t == q.child_type for t in seg.types], dtype=bool)
            locs = np.nonzero(m)[0]
            # batch the matched scores in one pull; float(s[local]) per child
            # was a scalar extraction per matching doc
            for local, sval in zip(locs.tolist(), s[locs].tolist()):
                pid = (seg.str_values("_parent", local) or [None])[0]
                if pid is None:
                    continue
                prev = parent_ids.get(pid, 0.0)
                parent_ids[pid] = max(prev, sval) if q.score_mode == "max" \
                    else prev + sval
        for seg in ctx.searcher.segments:
            match = np.zeros(seg.doc_count, bool)
            scores = np.zeros(seg.doc_count, np.float32)
            for local in range(seg.doc_count):
                if seg.parent_mask[local] and seg.ids[local] in parent_ids:
                    match[local] = True
                    scores[local] = parent_ids[seg.ids[local]] if q.score_mode != "none" else 1.0
            out.append((scores * np.float32(q.boost), match))
        return out
    # has_parent: find matching parents, then select children pointing at them
    matched_parents: dict[str, float] = {}
    for seg in ctx.searcher.segments:
        scorer = HostScorer(ctx, seg, 1.0)
        s, m = scorer.eval(q.query)
        m = m & np.asarray([t == q.parent_type for t in seg.types], dtype=bool)
        locs = np.nonzero(m)[0]
        for local, sval in zip(locs.tolist(), s[locs].tolist()):
            matched_parents[str(seg.ids[local])] = sval
    for seg in ctx.searcher.segments:
        match = np.zeros(seg.doc_count, bool)
        scores = np.zeros(seg.doc_count, np.float32)
        for local in range(seg.doc_count):
            pid = (seg.str_values("_parent", local) or [None])[0]
            if pid is not None and pid in matched_parents:
                match[local] = True
                scores[local] = matched_parents[pid] if q.score_mode != "none" else 1.0

        out.append((scores * np.float32(q.boost), match))
    return out


def _host_search(ctx: ShardContext, query: Query, k: int,
                 extra_filter: Filter | None = None, deadline=None) -> TopDocs:
    qn = query_norm_for(query, ctx)
    all_scores: list[np.ndarray] = []
    all_docs: list[np.ndarray] = []
    total = 0
    timed_out = False
    join = _shard_join(ctx, query)
    prof = _profile.current()
    for si, (seg, base) in enumerate(zip(ctx.searcher.segments, ctx.searcher.bases)):
        # host-side segment boundary: the one legal clamp point (never inside
        # a traced region) — expiry keeps the segments already scored
        if deadline is not None and deadline.expired():
            timed_out = True
            break
        t_seg = time.monotonic() if prof is not None else 0.0
        if join is not None:
            scores, match = join[si]
        else:
            scorer = HostScorer(ctx, seg, qn)
            scores, match = scorer.eval(query)
        if prof is not None:
            prof.segment(seg.gen, docs=int(seg.doc_count), path="host",
                         ms=(time.monotonic() - t_seg) * 1000.0)
        match = match & seg.live & seg.parent_mask
        if extra_filter is not None:
            match = match & segment_mask(seg, extra_filter, ctx)
        idx = np.nonzero(match)[0]
        total += len(idx)
        if len(idx):
            all_scores.append(scores[idx])
            all_docs.append(idx + base)
    if not all_scores:
        return TopDocs(0, [], float("nan"), timed_out=timed_out)
    scores = np.concatenate(all_scores)
    docs = np.concatenate(all_docs)
    order = np.lexsort((docs, -scores))[:k]
    hits = list(zip(scores[order].tolist(), docs[order].tolist()))
    return TopDocs(total, hits, float(scores.max()), timed_out=timed_out)


def count_shard(ctx: ShardContext, query: Query, extra_filter: Filter | None = None) -> int:
    total = 0
    for seg in ctx.searcher.segments:
        match = host_match_mask(query, seg, ctx) & seg.live & seg.parent_mask
        if extra_filter is not None:
            match &= segment_mask(seg, extra_filter, ctx)
        total += int(match.sum())
    return total


def iter_match_masks(ctx: ShardContext, query: Query,
                     extra_filter: Filter | None = None):
    """Lazily yield per-segment (scores, match): deadline-aware callers
    (execute_query_phase's general path) stop consuming at segment granularity
    and keep the segments already scored as a partial result."""
    qn = query_norm_for(query, ctx)
    join = _shard_join(ctx, query)
    for si, seg in enumerate(ctx.searcher.segments):
        if join is not None:
            scores, match = join[si]
        else:
            scorer = HostScorer(ctx, seg, qn)
            scores, match = scorer.eval(query)
        match = match & seg.live & seg.parent_mask
        if extra_filter is not None:
            match = match & segment_mask(seg, extra_filter, ctx)
        yield (scores, match)


def match_masks(ctx: ShardContext, query: Query, extra_filter: Filter | None = None):
    """Per-segment (scores, match) for aggregation/fetch sub-phases."""
    return list(iter_match_masks(ctx, query, extra_filter))
