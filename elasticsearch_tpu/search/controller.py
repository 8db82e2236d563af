"""SearchPhaseController: the coordinating-node reduce.

Analogue of search/controller/SearchPhaseController.java (SURVEY.md §2.5):
- sortDocs: merge per-shard top-k into the global top-k (score order or field-sort
  order, ties broken by shard index then doc — SearchPhaseController.java:137-214)
- aggregateDfs: sum per-shard term/field statistics for exact global IDF
  (SearchPhaseController.java:83-135) — the mesh executor sums the same way, on the
  host, over its shards' packed statistics (parallel/mesh_search.py)
- merge: reduce aggregations/facets/suggest partials and assemble the final response

Pure functions over shard results — unit-testable exactly like the reference's
controller, and identical whether results came from local shards, remote nodes, or the
device mesh.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field

import numpy as np

from ..index.segment import FieldStats
from .aggregations import facet_response, reduce_aggs
from .service import ParsedSearchRequest, ShardQueryResult
from .sorting import compare_sort_values


@dataclass
class DfsResult:
    """Per-shard statistics collected by the DFS phase (ref: search/dfs/DfsPhase.java:
    term stats + collection stats per queried field)."""

    shard_id: int
    max_doc: int
    term_df: dict  # (field, term) -> df
    field_stats: dict  # field -> FieldStats


def aggregate_dfs(results: list[DfsResult]) -> dict:
    """Sum per-shard stats into the global view handed back to every shard's query
    phase (ShardContext.global_stats) — ref: SearchPhaseController.aggregateDfs."""
    df: dict = {}
    fstats: dict[str, FieldStats] = {}
    max_doc = 0
    for r in results:
        max_doc += r.max_doc
        for key, v in r.term_df.items():
            df[key] = df.get(key, 0) + v
        for f, s in r.field_stats.items():
            cur = fstats.get(f)
            fstats[f] = s if cur is None else cur.merged(s)
    return {"df": df, "max_doc": max_doc, "field_stats": fstats}


def collect_dfs(ctx, query, shard_id: int = 0) -> DfsResult:
    """DFS phase on one shard: df for every term the query will score + field stats."""
    from .execute import FlatPlan, lower_flat

    term_df = {}
    fields = set()
    plan = lower_flat(query, ctx)
    if plan is not None:
        for c in plan.clauses:
            term_df[(c.field, c.term)] = ctx.searcher.doc_freq(c.field, c.term)
            fields.add(c.field)
    else:
        _walk_terms(query, ctx, term_df, fields)
    return DfsResult(
        shard_id=shard_id,
        max_doc=ctx.searcher.max_doc,
        term_df=term_df,
        field_stats={f: ctx.searcher.field_stats(f) for f in fields},
    )


def _walk_terms(query, ctx, term_df: dict, fields: set):
    from .queries import (
        BoolQuery, DisMaxQuery, FilteredQuery, FunctionScoreQuery, MatchQuery,
        MultiMatchQuery, NestedQuery, PhraseQuery, TermQuery,
    )

    if isinstance(query, TermQuery):
        term_df[(query.field, str(query.value))] = ctx.searcher.doc_freq(
            query.field, str(query.value))
        fields.add(query.field)
    elif isinstance(query, (MatchQuery, PhraseQuery)):
        for t in ctx.analyze(query.field, query.text):
            term_df[(query.field, t)] = ctx.searcher.doc_freq(query.field, t)
        fields.add(query.field)
    elif isinstance(query, MultiMatchQuery):
        for fspec in query.fields:
            f = fspec.split("^")[0]
            for t in ctx.analyze(f, query.text):
                term_df[(f, t)] = ctx.searcher.doc_freq(f, t)
            fields.add(f)
    elif isinstance(query, BoolQuery):
        for sub in query.must + query.should + query.must_not:
            _walk_terms(sub, ctx, term_df, fields)
    elif isinstance(query, DisMaxQuery):
        for sub in query.queries:
            _walk_terms(sub, ctx, term_df, fields)
    elif isinstance(query, (FilteredQuery, FunctionScoreQuery, NestedQuery)):
        inner = getattr(query, "query", None)
        if inner is not None and not callable(getattr(inner, "evaluate", None)):
            _walk_terms(inner, ctx, term_df, fields)


@dataclass
class MergedTopDocs:
    total: int
    max_score: float
    # [(score, shard_id, global_doc, sort_values)]
    hits: list
    timed_out: bool = False


def sort_docs(req: ParsedSearchRequest, shard_results: list[ShardQueryResult]) -> MergedTopDocs:
    """Global top-(from+size) merge across shards. Score order: (score desc, shard asc,
    doc asc). Field order: sort-value tuples via the shared comparator. A single
    shard-level partial (deadline expired mid-collection) marks the whole merged
    result timed_out — totals and aggregations cover only the scored segments."""
    total = sum(r.total for r in shard_results)
    max_score = float("nan")
    for r in shard_results:
        if r.max_score == r.max_score:
            max_score = r.max_score if max_score != max_score else max(max_score, r.max_score)
    entries = []
    for r in shard_results:
        for (score, doc, sort_values) in r.docs:
            entries.append((score, r.shard_id, doc, sort_values))
    _order(req, entries)
    k = req.from_ + req.size
    return MergedTopDocs(total=total, max_score=max_score, hits=entries[:k],
                         timed_out=any(r.timed_out for r in shard_results))


def _order(req: ParsedSearchRequest, entries: list) -> None:
    """[(score, shard, doc, sort_values)] into the response's order, in place."""
    if req.sort:
        import functools

        entries.sort(key=functools.cmp_to_key(
            lambda a, b: (compare_sort_values(a[3], b[3], req.sort)
                          or (a[1] - b[1]) or (a[2] - b[2]))
        ))
    else:
        entries.sort(key=lambda e: (-e[0] if e[0] == e[0] else float("inf"), e[1], e[2]))


def shard_page(req: ParsedSearchRequest, docs: list, from_: int, size: int) -> list:
    """The page of a search that met ONE shard, chosen on that shard from its
    own docs [(score, doc, sort_values)]: the order sort_docs gives them and
    the coordinator's `from`/`size` cut, so the hits the shard hydrates are
    the page the coordinator would have asked for (ref:
    SearchService.shortcutDocIdsToLoad)."""
    entries = [(score, 0, doc, sort_values) for score, doc, sort_values in docs]
    _order(req, entries)
    return [(score, doc, sort_values) for score, _shard, doc, sort_values
            in entries[from_: from_ + size]]


def merge_responses(req: ParsedSearchRequest, merged: MergedTopDocs,
                    shard_results: list[ShardQueryResult],
                    fetched_hits: list[dict], took_ms: int,
                    total_shards: int, successful: int, failures: list | None = None) -> dict:
    """Final response assembly (ref: SearchPhaseController.merge:308-380)."""
    resp: dict = {
        "took": took_ms,
        "timed_out": merged.timed_out,
        "_shards": {
            "total": total_shards,
            "successful": successful,
            # shards that answered (counted successful — same bitwise hits)
            # but via the host path because a device fault domain was open
            # (common/devicehealth): the response stays honest about serving
            # health without failing anything, like the reference's
            # timed_out-but-partial contract
            "degraded": sum(1 for r in shard_results
                            if getattr(r, "degraded", False)),
            "failed": total_shards - successful,
        },
        "hits": {
            "total": merged.total,
            "max_score": None if merged.max_score != merged.max_score else merged.max_score,
            "hits": fetched_hits,
        },
    }
    if failures:
        resp["_shards"]["failures"] = failures
    if req.profile:
        # per-shard white-box execution profiles merged next to _shards —
        # the reference's `profile` section shape: one entry per shard copy
        # that answered, ordered by shard id (common/profile.py; shards that
        # failed contribute no profile, exactly like their hits)
        shard_profiles = [r.profile for r in shard_results
                          if r.profile is not None]
        shard_profiles.sort(key=lambda p: (str(p.get("index", "")),
                                           int(p.get("shard", 0))))
        resp["profile"] = {"shards": shard_profiles}
    if req.aggs:
        partials = [p for r in shard_results for p in r.agg_partials]
        resp["aggregations"] = reduce_aggs(req.aggs, partials)
    if req.facets:
        facets = {}
        for name, (agg, kind) in req.facets.items():
            partials = [p[name] for r in shard_results for p in r.facet_partials]
            facets[name] = facet_response(agg, kind, agg.finalize(agg.merge(partials)))
        resp["facets"] = facets
    suggest_merged = _merge_suggest(shard_results)
    if suggest_merged is not None:
        resp["suggest"] = suggest_merged
    return resp


def _merge_suggest(shard_results: list[ShardQueryResult]):
    """Merge per-shard suggester entries: options unioned, re-ranked, deduped."""
    any_suggest = [r.suggest for r in shard_results if r.suggest is not None]
    if not any_suggest:
        return None
    out: dict = {}
    for s in any_suggest:
        for name, entries in s.items():
            if name not in out:
                out[name] = [dict(e, options=list(e["options"])) for e in entries]
            else:
                for mine, theirs in zip(out[name], entries):
                    mine["options"].extend(theirs["options"])
    for entries in out.values():
        for e in entries:
            seen = {}
            for o in e["options"]:
                key = o["text"]
                if key not in seen or o.get("score", 0) > seen[key].get("score", 0):
                    seen[key] = o
            e["options"] = sorted(
                seen.values(),
                key=lambda o: (-o.get("score", 0), -o.get("freq", 0), o["text"]),
            )[:5]
    return out
