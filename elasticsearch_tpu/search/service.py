"""Per-shard search service: full request bodies → query phase / fetch phase, scroll
contexts, rescore — the analogue of search/SearchService.java + DefaultSearchContext
(SURVEY.md §2.5): parse once, execute query phase (top docs + agg partials + suggest),
keep the context alive for fetch/scroll, reap on keep-alive expiry.

The query/fetch split exists for the same reason as the reference's: in multi-shard
search only the GLOBAL top-k winners get hydrated (fetch), so the query phase returns
doc ids + sort tuples only (TransportSearchQueryThenFetchAction — SURVEY.md §3.3)."""

from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field as dc_field
from functools import partial

import numpy as np

from ..common import insights as _insights
from ..common import profile as _profile
from ..common import tracing
from ..common.breaker import reserve as breaker_reserve
from ..common.deadline import NO_DEADLINE, Deadline, parse_timevalue
from ..common.devicehealth import DEVICE_HEALTH
from ..common.errors import (
    CircuitBreakingError,
    QueryParsingError,
    RejectedExecutionError,
    SearchContextMissingError,
    SearchEngineError,
)
from .aggregations import facet_response, parse_aggs, parse_facets, reduce_aggs
from .execute import (
    GROUP_KINDS,
    HostScorer,
    ShardContext,
    TopDocs,
    lower_flat,
    execute_flat_batch,
    iter_match_masks,
    match_masks,
    plan_kind,
    query_norm_for,
    search_shard,
)
from .fetch import build_hit
from .filters import Filter, segment_mask
from .queries import MatchAllQuery, Query, parse_filter, parse_query
from .sorting import (
    SortSpec,
    apply_missing,
    compare_sort_values,
    parse_sort,
    sort_key_column,
    sort_values_for_docs,
)
from .suggest import run_suggest


@dataclass
class ParsedSearchRequest:
    query: Query
    post_filter: Filter | None
    from_: int
    size: int
    sort: list  # list[SortSpec]
    aggs: dict
    facets: dict
    suggest: dict | None
    rescore: list
    min_score: float | None
    body: dict
    track_scores: bool = False
    explain: bool = False
    timeout_s: float | None = None
    # `"profile": true` / `?profile=true`: arm the white-box execution
    # profiler for this request (common/profile.py — per-shard collectors,
    # merged into a top-level `profile` response section by the coordinator)
    profile: bool = False


def parse_search_body(body: dict | None) -> ParsedSearchRequest:
    body = body or {}
    try:
        timeout_s = parse_timevalue(body.get("timeout"))
    except ValueError as e:
        raise QueryParsingError(str(e)) from None  # malformed timeout is a 400
    query = parse_query(body.get("query")) if body.get("query") else MatchAllQuery()
    # top-level "filter" is the POST filter (applied to hits, not aggs/facets) —
    # ref: DefaultSearchContext.parsedPostFilter
    post_filter = parse_filter(body["filter"]) if body.get("filter") else \
        parse_filter(body["post_filter"]) if body.get("post_filter") else None
    rescore = body.get("rescore") or []
    if isinstance(rescore, dict):
        rescore = [rescore]
    return ParsedSearchRequest(
        query=query,
        post_filter=post_filter,
        from_=int(body.get("from", 0)),
        size=int(body.get("size", 10)),
        sort=parse_sort(body.get("sort")),
        aggs=parse_aggs(body.get("aggs") or body.get("aggregations") or {}),
        facets=parse_facets(body.get("facets") or {}),
        suggest=body.get("suggest"),
        rescore=rescore,
        min_score=body.get("min_score"),
        body=body,
        track_scores=bool(body.get("track_scores", False)),
        explain=bool(body.get("explain", False)),
        # ref: the request-body `timeout` TimeValue ("50ms"/"2s"; bare ms) that
        # bounds the query phase — enforced at segment granularity on the host
        timeout_s=timeout_s,
        profile=bool(body.get("profile", False)),
    )


@dataclass
class ShardQueryResult:
    """Query-phase output for ONE shard (what travels back to the coordinating node
    before the reduce — ref: QuerySearchResult)."""

    total: int
    # [(score, global_doc, sort_values|None)] — length ≤ from+size
    docs: list
    max_score: float
    agg_partials: list = dc_field(default_factory=list)  # one partial dict per segment
    facet_partials: list = dc_field(default_factory=list)
    suggest: dict | None = None
    context_id: int | None = None
    shard_id: int = 0
    # deadline expired mid-collection: docs/total/partials cover the segments
    # scored before expiry (the coordinator surfaces this as `timed_out: true`)
    timed_out: bool = False
    # white-box execution profile of this shard's query phase (plain scalars —
    # rides the wire like the span list does; None when unprofiled)
    profile: dict | None = None
    # served by the host fallback because the device path failed or its fault
    # domain is open (common/devicehealth) — bitwise-identical hits, but the
    # coordinator's `_shards` rollup must not count this copy as fully healthy
    degraded: bool = False
    # the page's hits, where the shard's query phase hydrated them itself (a
    # search that met one shard: actions._s_query_phase); None where a fetch
    # phase follows
    hits: list | None = None


# process-wide serving-path counters (which executor served the query phase —
# surfaced via nodes stats "search_serving"; in-process test clusters share the
# process, so treat these as process rollups, like the script registry)
SERVING_COUNTERS = {
    "device_sparse": 0,  # flat top-k via the sparse candidate kernel, or the phrase program
    "device_filtered": 0,  # filtered dense kernel
    "device_function_score": 0,  # fs rows/script kernels
    "device_aggs": 0,  # fused agg launch (metric/bucket)
    "device_sort": 0,  # field-sort kernel (incl. sort+aggs composition)
    "device_percolate": 0,  # batched percolation launches
    "device_percolate_fallbacks": 0,  # batch failed → host loop
    "device_errors": 0,  # device launch failed → host fallback (see _device_failed)
    "degraded": 0,  # served host-side on device failure OR an open fault domain
    "host": 0,  # host scorer / mask path
}

_device_error_logged: set = set()


def _count(path: str):
    SERVING_COUNTERS[path] += 1
    prof = _profile.current()
    if prof is not None:
        prof.outcome(path)  # the resolved execution path, recorded once
    obs = _insights.current()
    if obs is not None and obs.outcome is None:
        obs.outcome = path  # always-on query-shape outcome mix (one
        # thread-local read + attribute write — the insights hook contract)


def _device_failed(e: BaseException, ctx: "ShardContext | None" = None):
    """A device launch failed (broken backend, OOM, failed init): the search
    must still answer — count it, log each distinct error once, serve host.
    Mirrors mesh_serving's any-mesh-failure-must-not-fail-the-search rule.

    Classified jax/XLA errors also advance the owning fault domain's circuit
    (common/devicehealth): the raiser tags the exception with its narrowest
    domain (`_estpu_device_domain`, stamped at the pack/launch/pull seams);
    untagged device errors attribute to the index's batch-pull domain."""
    from ..common.logging import get_logger

    SERVING_COUNTERS["device_errors"] += 1
    SERVING_COUNTERS["degraded"] += 1
    domain = getattr(e, "_estpu_device_domain", None)
    if domain is None and ctx is not None:
        domain = f"pull:{ctx.index_name}"
    if domain is not None:
        DEVICE_HEALTH.record_failure(domain, e)
    prof = _profile.current()
    if prof is not None:
        prof.event("device_error", error=type(e).__name__)
        prof.fallback(f"device_error:{type(e).__name__}")
    key = type(e).__name__
    if key not in _device_error_logged:
        _device_error_logged.add(key)
        get_logger("search.device").warning(
            f"device serving failed ({key}: {e}); falling back to the host "
            f"scorer (logged once per error type)")


def _domains_for(ctx: "ShardContext", families: tuple) -> tuple:
    """The fault domains one device attempt on this shard exercises: the
    index's pack + batch-pull domains plus each compile family it may launch
    (the devicehealth domain taxonomy)."""
    idx = str(ctx.index_name)
    return (f"pack:{idx}",) + tuple(f"compile:{f}" for f in families) \
        + (f"pull:{idx}",)


def _blocked_domain(ctx: "ShardContext", families: tuple) -> str | None:
    """The open fault domain that routes this query host-side before any
    launch, or None (all closed, or this caller was admitted as the probe).
    One plain attr read when every domain is closed — the standing hot-path
    contract."""
    if not DEVICE_HEALTH.any_open:
        return None
    return DEVICE_HEALTH.blocked(_domains_for(ctx, families))


def _device_degraded(domain: str):
    """An open fault domain skipped the device path: count + profile the
    degrade (the result is still bitwise-identical host-scored hits)."""
    SERVING_COUNTERS["degraded"] += 1
    prof = _profile.current()
    if prof is not None:
        prof.event("device_degraded", domain=domain)
        prof.fallback(f"device_degraded:{domain}")


def _note_device_ok(ctx: "ShardContext", families: tuple):
    """Clean device outcome: close a half-open domain this query just probed
    (one attr read when no device failure was ever recorded)."""
    if DEVICE_HEALTH.dirty:
        DEVICE_HEALTH.note_success(_domains_for(ctx, families))


def _execute_flat_single(ctx: ShardContext, plan, k: int,
                         deadline: Deadline, tail=None):
    """One plan's device execution — through the node's cross-request
    DeviceBatcher when one is wired (coalescing with concurrent searches into
    one bucketed launch; search/batcher.py), else a direct single-plan launch.
    This is the ONE served launch route: a plain, function_score or filtered
    plan is answered with TopDocs, an aggregated or sorted one (`tail`, an
    execute.FlatTail) with its slice of its group's launch
    (launch_flat_aggs / launch_flat_sorted; None where the executor sends
    the group to the host).
    DFS-stats requests always launch directly: their per-request global stats
    change clause weights, which a shared batch cannot express.

    PROFILED requests bypass the batcher explicitly (recorded as
    `batcher: {bypassed, reason: "profile"}`): a coalesced batch's device
    phases belong to the batch, not to one member, and the per-request sync
    the profiler performs must never serialize innocent neighbors' launches.
    The bypass also keeps the collector single-writer — execution never
    leaves this thread. A direct launch with a tail pulls on this thread, and
    a sampled request records it (execute.traced_dispatch)."""
    if ctx.batcher is not None and not ctx.global_stats:
        prof = _profile.current()
        if prof is None:
            span = tracing.current_span()
            if span:
                # sampled: what the shard did before handing the plan to the
                # batcher (request parse, lower_flat), from the shard span's
                # own start to the enqueue
                span.record("shard.lower", span.t0, time.monotonic())
            return ctx.batcher.execute(plan, ctx, k, deadline=deadline,
                                       tail=tail)
        # recorded ONLY when the batcher would actually have served this
        # request — a DFS search or batcher-less node launches directly
        # either way, and must not claim (or count) a profile bypass
        prof.batcher_bypass("profile")
        ctx.batcher.note_profile_bypass()
    if tail is None:
        return execute_flat_batch([plan], ctx, k)[0]
    from .execute import traced_dispatch

    with traced_dispatch():
        return execute_flat_batch([plan], ctx, k, [tail])[0]


def _prof_record_plan(prof, plan, req: ParsedSearchRequest, ctx: ShardContext,
                      use_device: bool):
    """Record the resolved plan shape (or the host-fallback reason when the
    query would not lower flat) — profiled requests only."""
    from .execute import lower_fallback_reason, plan_profile

    if plan is not None:
        prof.set_plan(plan_profile(plan, req.query))
    else:
        prof.set_plan({"query_type": type(req.query).__name__})
        prof.fallback("device_disabled" if not use_device
                      else lower_fallback_reason(req.query, ctx))


def _mask_consumers(req: ParsedSearchRequest) -> tuple:
    """What of the request reads the per-segment match masks of the general
    host path, computed once: each device route says which sets it serves
    (_MASK_ROUTES), and the profile names them when none does."""
    return tuple(name for name, present in (
        ("aggs", req.aggs), ("facets", req.facets), ("sort", req.sort),
        ("post_filter", req.post_filter is not None),
        ("rescore", req.rescore), ("min_score", req.min_score is not None),
        ("explain", req.explain),
    ) if present)


def _device_attempt(ctx: ShardContext, families: tuple, route):
    """The ONE envelope around a device attempt of the query phase:
    (`route()`'s result, degraded).

    An open fault domain among `families` (devicehealth) launches nothing:
    (None, True). A `fielddata` CircuitBreakingError (out of device-pack
    budget) and any exception that is not the search's own answer count as a
    device failure (_device_failed): (None, True), the host serves, marked
    degraded. Any other breaker trip (request / parent: load-shed, the 429)
    and a SearchEngineError (scripts, parsing: the answer itself) re-raise.
    A clean result closes a half-open domain the attempt probed
    (_note_device_ok); a clean None is a route that declined before or at
    its launch: the host serves, NOT degraded, and a probe is left to the
    next window."""
    dom = _blocked_domain(ctx, families)
    if dom is not None:
        _device_degraded(dom)  # open fault domain: host serves, no launch
        return None, True
    try:
        result = route()
    except CircuitBreakingError as e:
        if getattr(e, "breaker", None) != "fielddata":
            raise
        _device_failed(e, ctx)
        return None, True
    except SearchEngineError:
        raise
    except Exception as e:  # noqa: BLE001 — device trouble must not fail
        _device_failed(e, ctx)  # the search; the host scorer answers
        return None, True
    if result is not None:
        _note_device_ok(ctx, families)
    return result, False


def _top_docs_result(td: TopDocs, n: int | None, suggest_out,
                     shard_id: int) -> ShardQueryResult:
    """A device launch's TopDocs as the shard's answer, its first `n` hits
    (None: all the launch returned)."""
    return ShardQueryResult(
        total=td.total, docs=[(s, d, None) for s, d in td.hits[:n]],
        max_score=td.max_score, suggest=suggest_out, shard_id=shard_id)


def execute_query_phase(ctx: ShardContext, req: ParsedSearchRequest,
                        use_device: bool = True, shard_id: int = 0,
                        deadline: Deadline | None = None) -> ShardQueryResult:
    # the shard's time budget: coordinator-supplied remaining budget when the
    # request came over transport, else the request's own `timeout`. Enforced
    # ONLY at host-side segment boundaries — a device launch, once started,
    # always completes whole (deadline checks never cross into traced code).
    if deadline is None:
        deadline = Deadline.after(req.timeout_s) if req.timeout_s is not None \
            else NO_DEADLINE
    k = req.from_ + req.size
    consumers = _mask_consumers(req)
    suggest_out = run_suggest(ctx, req.suggest) if req.suggest else None
    if deadline.expired():
        # budget gone before any segment was scored: legal partial = nothing
        return ShardQueryResult(total=0, docs=[], max_score=float("nan"),
                                suggest=suggest_out, shard_id=shard_id,
                                timed_out=True)

    # profile hooks (one thread-local read when unprofiled): lowering wall
    # time + the resolved plan shape, with the fallback reason whenever the
    # fused path is declined (execute.lower_fallback_reason vocabulary)
    prof = _profile.current()

    # no mask consumer (`explain` alone is none: the fetch phase explains)
    if not consumers or consumers == ("explain",):
        t_low = time.monotonic() if prof is not None else 0.0
        plan = lower_flat(req.query, ctx, phrases=True) if use_device else None
        if plan is not None and plan.const is not None and k == 0:
            # a bare count (`_count` is a search of size 0): summing a mask on
            # the host launches nothing, packs nothing and compiles nothing.
            # On the device it packed every unmerged segment of a fresh index
            # and made the force-merge's pack a 25-100 s device concat that
            # the first real search then waited out (PERF.md section 6, PR 31)
            plan = None
        if prof is not None:
            prof.phase_s("lower", time.monotonic() - t_low)
            _prof_record_plan(prof, plan, req, ctx, use_device)
        degraded = False
        if plan is not None:
            # the plan's own kind says which families it may launch and
            # which outcome it books (a plan with no scoring clause launches
            # the filtered family's tail, whether or not it carries a filter)
            kind = GROUP_KINDS[plan_kind(plan)]
            td, degraded = _device_attempt(
                ctx, kind.families,
                lambda: _execute_flat_single(ctx, plan, max(k, 1), deadline))
            if td is not None:
                _count(kind.served)
                return _top_docs_result(td, None, suggest_out, shard_id)
            if not degraded:
                # here a clean None is the LAUNCH's, made after the pack and
                # the positions plane were reached (a phrase the plane cannot
                # hold: execute.launch_flat_phrase): the attempt still counts
                # as clean, and the host scorer answers, not degraded
                _note_device_ok(ctx, kind.families)
        _count("host")
        td = _host_topk(ctx, req, k, deadline)
        return ShardQueryResult(total=td.total, docs=[(s, d, None) for s, d in td.hits],
                                max_score=td.max_score, suggest=suggest_out,
                                shard_id=shard_id, timed_out=td.timed_out,
                                degraded=degraded)

    if prof is not None:
        # profiled-only pre-lowering: the mask-needing branches below lower
        # again internally; this records the plan shape (or the lowering
        # fallback reason) once, before any branch runs
        t_low = time.monotonic()
        _prof_record_plan(prof, lower_flat(req.query, ctx, phrases=True)
                          if use_device else None, req, ctx, use_device)
        prof.phase_s("lower", time.monotonic() - t_low)

    # the device route that serves this set of mask consumers, if one does
    # (_MASK_ROUTES), attempted under the envelope. An open domain or a
    # device failure degrades to the general host path below, which marks
    # its ShardQueryResult so `_shards` stays honest
    degraded = False
    for serves, launch_for, families, served in _MASK_ROUTES:
        launch = launch_for(ctx, req, k, suggest_out, shard_id, deadline) \
            if use_device and consumers in serves else None
        if launch is not None:
            device, degraded = _device_attempt(ctx, families, launch)
            if device is not None:
                _count(served)
                return device

    # general path: the whole host materialization (per-segment score/match
    # arrays, agg/facet bucket state, the sort-entry list) is reserved on the
    # request breaker UP FRONT — this is the node's "wide aggregation"
    # overload face; the reservation holds until the partials are built and
    # releases on exit (estimate-before-allocate; all host-side, never traced)
    _mask_est = ctx.searcher.max_doc * (
        5 + 16 * (len(req.aggs) + len(req.facets)))
    with breaker_reserve(ctx.breaker("request"), _mask_est, "<query_phase_host>"):
        # general path: dense per-segment masks drive sort/aggs/rescore. Masks are
        # consumed lazily so the deadline clamps BETWEEN segments: expiry keeps the
        # segments already scored as an honest partial (timed_out below)
        _count("host")
        if prof is not None:
            # which mask-needing features sent the request here (set-if-unset:
            # a lowering-level reason already recorded wins)
            prof.fallback("features:" + ",".join(consumers))
        timed_out = False
        seg_results = []
        masks_iter = iter_match_masks(ctx, req.query)
        seg_masks_for_aggs = []
        all_entries = []  # (sortkeys..., score, global_doc, seg_idx, local)
        total = 0
        max_score = float("nan")
        for si, (seg, base) in enumerate(
            zip(ctx.searcher.segments, ctx.searcher.bases)
        ):
            if si > 0 and deadline.expired():
                timed_out = True
                break
            t_seg = time.monotonic() if prof is not None else 0.0
            scores, match = next(masks_iter)
            if prof is not None:
                prof.segment(seg.gen, docs=int(seg.doc_count), path="host",
                             ms=(time.monotonic() - t_seg) * 1000.0)
            seg_results.append((scores, match))
            if req.min_score is not None:
                match = match & (scores >= np.float32(req.min_score))
            seg_masks_for_aggs.append((seg, match, scores))
            hit_mask = match
            if req.post_filter is not None:
                hit_mask = match & segment_mask(seg, req.post_filter, ctx)
            idx = np.nonzero(hit_mask)[0]
            total += len(idx)
            if not len(idx):
                continue
            seg_scores = scores[idx]
            if len(seg_scores):
                m = float(seg_scores.max())
                max_score = m if max_score != max_score else max(max_score, m)
            if req.sort:
                keycols = []
                for spec in req.sort:
                    col = apply_missing(sort_key_column(spec, seg, ctx, scores), spec)
                    keycols.append(col[idx] * (-1.0 if spec.reverse else 1.0))
                for j, local in enumerate(idx):
                    all_entries.append(
                        (tuple(kc[j] for kc in keycols), float(seg_scores[j]),
                         base + int(local), si, int(local))
                    )
            else:
                for j, local in enumerate(idx):
                    all_entries.append(
                        ((-float(seg_scores[j]),), float(seg_scores[j]),
                         base + int(local), si, int(local))
                    )
        all_entries.sort(key=lambda e: (e[0], e[2]))
        top = all_entries[: max(k, 0)]

        # rescore: re-rank the top window with the rescore queries
        if req.rescore and top:
            top = _apply_rescore(ctx, req, top)

        docs = []
        # per-segment grouped sort-value extraction for response "sort" arrays
        if req.sort:
            sort_vals_by_rank = _sort_values_by_rank(
                req.sort, ctx, [(si, local) for (_, _s, _g, si, local) in top],
                scores_by_seg={si: r[0] for si, r in enumerate(seg_results)})
            for rank, (_, s, g, si, local) in enumerate(top):
                score = s if req.track_scores or _score_in_sort(req.sort) else float("nan")
                docs.append((score, g, sort_vals_by_rank[rank]))
        else:
            docs = [(s, g, None) for (_, s, g, _si, _l) in top]

        agg_partials = []
        facet_partials = []
        if req.aggs:
            agg_partials = [
                {n: a.collect(seg, ctx, mask, scores) for n, a in req.aggs.items()}
                for seg, mask, scores in seg_masks_for_aggs
            ]
        if req.facets:
            facet_partials = [
                {n: agg.collect(seg, ctx, mask, scores)
                 for n, (agg, _kind) in req.facets.items()}
                for seg, mask, scores in seg_masks_for_aggs
            ]
        return ShardQueryResult(
            total=total, docs=docs, max_score=max_score, agg_partials=agg_partials,
            facet_partials=facet_partials, suggest=suggest_out, shard_id=shard_id,
            timed_out=timed_out, degraded=degraded,
        )


def _try_device_aggs(ctx: ShardContext, req: ParsedSearchRequest, k: int,
                     suggest_out, shard_id: int,
                     deadline: Deadline = NO_DEADLINE
                     ) -> "ShardQueryResult | None":
    """Serve query + aggregations in one fused device program per segment; None
    when any agg (or the query) needs the host path. Metric aggs reduce to
    masked stats, bucket aggs (terms/histogram/date_histogram) to exact
    scatter-add doc counts over host-computed keys.

    The plan goes the one served launch route (_execute_flat_single) with an
    execute.aggs_tail beside it: the batcher's drainer launches it with the
    aggregated searches in flight that share its key and hands this thread
    the search's own slices (counts, stats, bucket counts), which become
    partials here."""
    from ..ops.device_index import agg_device_exact
    from .aggregations import (device_agg_field, device_agg_needs_values,
                               device_bucket_eligible, device_bucket_partial,
                               device_bucket_subs, device_partial)
    from .execute import aggs_tail

    metric_fields = {}
    bucket_names = []
    bucket_subs: dict[str, dict] = {}
    for name, agg in req.aggs.items():
        f = device_agg_field(agg, ctx)
        if f is not None:
            metric_fields[name] = f
        elif device_bucket_eligible(agg):
            subs = device_bucket_subs(agg, ctx) if agg.subs else {}
            if subs is None:
                return None  # a sub-agg can't ride the kernel
            bucket_names.append(name)
            # the ONE field-order used for both the kernel stack layout and
            # partial-assembly row lookup
            bucket_subs[name] = (subs, sorted(set(subs.values())))
        else:
            return None
    # a whole-number column the device cannot answer exactly stays with the
    # host collectors: a min, a max or a stats of values float32 cannot hold,
    # a sum its integer limbs cannot (device_index.agg_device_exact)
    valued = [(agg, metric_fields[name]) for name, agg in req.aggs.items()
              if name in metric_fields] + [
        (sub, bucket_subs[name][0][sub_name]) for name in bucket_names
        for sub_name, sub in req.aggs[name].subs.items()]
    if not all(agg_device_exact(ctx.searcher.segments, field,
                                device_agg_needs_values(agg))
               for agg, field in valued):
        return None
    plan = lower_flat(req.query, ctx)
    if plan is None or plan.fs is not None:
        return None
    fields = sorted(set(metric_fields.values()))
    fpos = {f: i for i, f in enumerate(fields)}
    bucket_aggs = [
        (req.aggs[n], bucket_subs[n][1] or None) for n in bucket_names
    ]
    # kernel k is at least 1 so max_score stays observable; hits trim to the
    # requested size below (size=0 agg-only requests return no docs, like the
    # host mask path)
    res = _execute_flat_single(ctx, plan, max(k, 1), deadline,
                               aggs_tail(fields, bucket_aggs))
    if res is None:
        return None  # the launch refused a column — host path
    td, seg_stats = res
    bpos = {n: i for i, n in enumerate(bucket_names)}

    def bucket_partial(name, agg, buckets, seg):
        keys, bcounts, sub_cnt, sub_stats, sub_sums = buckets[bpos[name]]
        sub_data = None
        field_of, order = bucket_subs[name]
        if field_of:
            sub_data = (agg.subs, field_of, order, sub_cnt, sub_stats,
                        sub_sums)
        return device_bucket_partial(agg, keys, bcounts, seg=seg,
                                     sub_data=sub_data)

    agg_partials = [
        {name: (device_partial(agg, counts[fpos[metric_fields[name]]],
                               stats[fpos[metric_fields[name]]],
                               sums[fpos[metric_fields[name]]])
                if name in metric_fields
                else bucket_partial(name, agg, buckets, seg))
         for name, agg in req.aggs.items()}
        for (counts, stats, sums, buckets), seg in zip(seg_stats,
                                                       ctx.searcher.segments)
    ]
    return ShardQueryResult(
        total=td.total, docs=[(s, d, None) for s, d in td.hits[:max(k, 0)]],
        max_score=td.max_score, agg_partials=agg_partials, suggest=suggest_out,
        shard_id=shard_id,
    )


def _try_device_post_filter(ctx: ShardContext, req: ParsedSearchRequest, k: int,
                            suggest_out, shard_id: int,
                            deadline: Deadline = NO_DEADLINE
                            ) -> "ShardQueryResult | None":
    """post_filter requests: the hit launch gates on (query filter AND post
    filter); the agg launch (when aggs exist and are device-eligible) sees only
    the query's own match set — exactly the host mask path's split."""
    import dataclasses

    from .execute import lower_flat
    from .filters import BoolFilter

    plan = lower_flat(req.query, ctx)
    if plan is None or plan.fs is not None:
        return None
    agg_result = None
    if req.aggs:
        agg_result = _try_device_aggs(ctx, req, 0, None, shard_id, deadline)
        if agg_result is None:
            return None
    hit_filter = req.post_filter if plan.filt is None else \
        BoolFilter(must=[plan.filt, req.post_filter])
    hit_plan = dataclasses.replace(plan, filt=hit_filter)
    td = execute_flat_batch([hit_plan], ctx, max(k, 1))[0]
    return ShardQueryResult(
        total=td.total, docs=[(s, d, None) for s, d in td.hits[: max(k, 0)]],
        max_score=td.max_score,
        agg_partials=agg_result.agg_partials if agg_result is not None else [],
        suggest=suggest_out, shard_id=shard_id,
    )


def _try_device_sort(ctx: ShardContext, req: ParsedSearchRequest, k: int,
                     suggest_out, shard_id: int,
                     deadline: Deadline = NO_DEADLINE
                     ) -> "ShardQueryResult | None":
    """Field-sorted top-k in the fused kernel; None when the spec/columns/query
    need the host path. Sort VALUES in the response come from the host extractor
    (exact f64 / None-for-missing), only the ORDERING rides the device. Requests
    that ALSO carry device-eligible aggs make a second submission for the
    partials (same match set — both kernels share the dense core).

    The plan goes the one served launch route (_execute_flat_single) with an
    execute.sort_tail beside it: the drainer launches it with the searches in
    flight under the same sort and hands this thread each segment's best
    documents by the device keys; the merge by exact keys
    (execute.sorted_entries) and the response's sort values are this
    thread's."""
    from .execute import lower_flat, sort_tail, sorted_entries

    spec = req.sort[0]
    if spec.kind != "field":
        return None
    agg_result = None
    if req.aggs:
        agg_result = _try_device_aggs(ctx, req, 0, None, shard_id, deadline)
        if agg_result is None:
            return None  # any host-only agg sends the whole request host-side
    plan = lower_flat(req.query, ctx)
    if plan is None or plan.fs is not None:
        return None
    res = _execute_flat_single(ctx, plan, max(k, 1), deadline, sort_tail(spec))
    if res is None:
        return None
    total, max_score, entries = sorted_entries(res, ctx, max(k, 1), spec)
    values_by_rank = _sort_values_by_rank(
        req.sort, ctx, [(si, local) for (_key, _g, si, local, _s) in entries])
    docs = [
        (s if req.track_scores else float("nan"), g, values_by_rank[rank])
        for rank, (_key, g, _si, _local, s) in enumerate(entries)
    ][: max(k, 0)]
    return ShardQueryResult(
        total=total, docs=docs, max_score=max_score,
        agg_partials=agg_result.agg_partials if agg_result is not None else [],
        suggest=suggest_out, shard_id=shard_id,
    )


def _min_score_launch(ctx: ShardContext, req: ParsedSearchRequest, k: int,
                      suggest_out, shard_id: int, deadline: Deadline):
    """The function_score rows kernel with no functions IS a score threshold
    gate: an empty function_score wrapper around the query carries the
    request's `min_score`. A query that does not lower flat stays with the
    host, and asks no domain."""
    from .queries import FunctionScoreQuery

    plan = lower_flat(FunctionScoreQuery(query=req.query,
                                         min_score=req.min_score), ctx)
    if plan is None:
        return None
    return lambda: _top_docs_result(
        _execute_flat_single(ctx, plan, max(k, 1), deadline), max(k, 0),
        suggest_out, shard_id)


def _sort_launch(ctx: ShardContext, req: ParsedSearchRequest, *rest):
    """The field-sort kernel takes one sort key; several stay with the host,
    and ask no domain."""
    return partial(_try_device_sort, ctx, req, *rest) \
        if len(req.sort) == 1 else None


def _whole(attempt):
    """A route with nothing to decide before its domains are asked."""
    return lambda *request: partial(attempt, *request)


# The device routes of a request with mask consumers, in the order of trial:
# (the sets of _mask_consumers it serves; launch_for(ctx, req, k, suggest_out,
# shard_id, deadline) -> the attempt _device_attempt runs, or None where the
# route does not apply; the compile families it may launch, its fault
# domains; the SERVING_COUNTERS outcome it books). No two serve one set, and
# a set none of them names (a facet, a rescore, an `explain` beside anything,
# two of post_filter / sort / min_score) is the general host path's.
_MASK_ROUTES = (
    # aggregations alone fuse into the scoring kernel
    # (execute.launch_flat_aggs) instead of materializing host masks
    ((("aggs",),), _whole(_try_device_aggs), ("aggs",), "device_aggs"),
    # booked as device_filtered though the function_score family launches
    # it: to the user a min_score is a gate on the hits
    ((("min_score",),), _min_score_launch, ("function_score",),
     "device_filtered"),
    # aggs (if any) reduce over the FULL match set while hits gate on the
    # post filter: two composed launches sharing the dense core (the
    # reference's faceting idiom: post_filter never affects aggregations)
    ((("post_filter",), ("aggs", "post_filter")),
     _whole(_try_device_post_filter), ("filtered", "aggs"), "device_filtered"),
    # one numeric field sort, top-k over pre-folded key rows inside the
    # kernel (execute.launch_flat_sorted); device-eligible aggs ride beside
    # it (the agg launch supplies partials, the sort launch the ordering)
    ((("sort",), ("aggs", "sort")), _sort_launch, ("sorted", "aggs"),
     "device_sort"),
)


def _sort_values_by_rank(specs: list, ctx: ShardContext, seg_locals: list,
                         scores_by_seg: dict | None = None) -> dict:
    """rank -> sort-value list, extracted per segment so column reads vectorize
    — the ONE site for response "sort" arrays (host mask path AND device sort
    path). seg_locals: (seg_idx, local) per rank; scores_by_seg supplies dense
    score arrays for _score-kind specs (host path only)."""
    by_seg: dict[int, list[int]] = {}
    for rank, (si, _local) in enumerate(seg_locals):
        by_seg.setdefault(si, []).append(rank)
    out: dict[int, list] = {}
    for si, ranks in by_seg.items():
        seg = ctx.searcher.segments[si]
        locals_ = np.asarray([seg_locals[r][1] for r in ranks])
        scores = scores_by_seg.get(si) if scores_by_seg else None
        vals = sort_values_for_docs(specs, seg, ctx, locals_, scores)
        for r, v in zip(ranks, vals):
            out[r] = v
    return out


def _score_in_sort(sort: list) -> bool:
    return any(s.kind == "score" for s in sort)


def _host_topk(ctx: ShardContext, req: ParsedSearchRequest, k: int,
               deadline: Deadline = NO_DEADLINE) -> TopDocs:
    return search_shard(ctx, req.query, max(k, 1), use_device=False,
                        deadline=deadline)


def _apply_rescore(ctx: ShardContext, req: ParsedSearchRequest, top: list) -> list:
    """ref: search/rescore/QueryRescorer — window top-N re-scored, combined by
    score_mode with query/rescore weights, then re-sorted within the window."""
    for rspec in req.rescore:
        window = int(rspec.get("window_size", 10))
        qspec = rspec.get("query", {})
        rq = parse_query(qspec.get("rescore_query"))
        qw = float(qspec.get("query_weight", 1.0))
        rw = float(qspec.get("rescore_query_weight", 1.0))
        mode = qspec.get("score_mode", "total")
        qn = query_norm_for(rq, ctx)
        window_entries = top[:window]
        rest = top[window:]
        by_seg: dict[int, list[int]] = {}
        for i, (_, _s, _g, si, local) in enumerate(window_entries):
            by_seg.setdefault(si, []).append(i)
        new_entries = list(window_entries)
        for si, idxs in by_seg.items():
            seg = ctx.searcher.segments[si]
            scorer = HostScorer(ctx, seg, qn)
            rscores, rmatch = scorer.eval(rq)
            for i in idxs:
                key0, s, g, si2, local = window_entries[i]
                if rmatch[local]:
                    rs = float(rscores[local])
                    if mode == "total":
                        ns = s * qw + rs * rw
                    elif mode == "multiply":
                        ns = s * qw * rs * rw
                    elif mode == "avg":
                        ns = (s * qw + rs * rw) / 2.0
                    elif mode == "max":
                        ns = max(s * qw, rs * rw)
                    elif mode == "min":
                        ns = min(s * qw, rs * rw)
                    else:
                        raise QueryParsingError(f"unknown rescore score_mode [{mode}]")
                else:
                    ns = s * qw
                new_entries[i] = ((-ns,), ns, g, si2, local)
        new_entries.sort(key=lambda e: (e[0], e[2]))
        top = new_entries + rest
    return top


def execute_fetch_phase(ctx: ShardContext, req: ParsedSearchRequest,
                        docs: list, index_name: str = "index",
                        shard_id: int | None = None) -> list[dict]:
    """docs: [(score, global_doc, sort_values|None)] — the winners to hydrate."""
    hits = []
    for score, g, sort_values in docs:
        seg, local = ctx.searcher.resolve(g)
        hits.append(build_hit(seg, local, score, req.body, req.query, ctx,
                              index_name=index_name, sort_values=sort_values,
                              shard_id=shard_id))
    return hits


def reduce_and_respond(ctx: ShardContext, req: ParsedSearchRequest,
                       result: ShardQueryResult, took_ms: int = 0,
                       index_name: str = "index") -> dict:
    """Single-shard convenience: query result → full response body."""
    page = result.docs[req.from_: req.from_ + req.size]
    hits = execute_fetch_phase(ctx, req, page, index_name=index_name)
    resp: dict = {
        "took": took_ms,
        "timed_out": False,
        "_shards": {"total": 1, "successful": 1, "failed": 0},
        "hits": {
            "total": result.total,
            "max_score": None if result.max_score != result.max_score else result.max_score,
            "hits": hits,
        },
    }
    if req.aggs:
        resp["aggregations"] = reduce_aggs(req.aggs, result.agg_partials)
    if req.facets:
        resp["facets"] = {
            name: facet_response(agg, kind, agg.finalize(agg.merge(
                [p[name] for p in result.facet_partials])))
            for name, (agg, kind) in req.facets.items()
        }
    if result.suggest is not None:
        resp["suggest"] = result.suggest
    return resp


# ---------------------------------------------------------------------------
# search contexts (scroll / two-phase) — ref: SearchService's active contexts map
# ---------------------------------------------------------------------------


@dataclass
class SearchContextEntry:
    ctx: ShardContext
    req: ParsedSearchRequest
    ordered_docs: list  # full sorted [(score, global_doc, sort_values)]
    position: int
    keep_alive_s: float
    last_access: float
    index_name: str = "index"


class SearchService:
    """Holds long-lived shard search contexts keyed by id (scroll); reaps expired ones
    (ref: SearchService keep-alive reaper)."""

    def __init__(self):
        self._contexts: dict[int, SearchContextEntry] = {}
        self._ids = itertools.count(1)
        self._lock = threading.Lock()

    def create_scroll(self, ctx: ShardContext, req: ParsedSearchRequest,
                      keep_alive_s: float = 300.0, use_device: bool = True,
                      index_name: str = "index") -> tuple[int, ShardQueryResult]:
        # materialize the FULL ordering once; scroll pages through it
        big = ParsedSearchRequest(**{**req.__dict__, "from_": 0,
                                     "size": max(ctx.searcher.max_doc, 1)})
        result = execute_query_phase(ctx, big, use_device=use_device)
        cid = next(self._ids)
        with self._lock:
            self._contexts[cid] = SearchContextEntry(
                ctx=ctx, req=req, ordered_docs=result.docs, position=0,
                keep_alive_s=keep_alive_s, last_access=time.monotonic(),
                index_name=index_name,
            )
        first = ShardQueryResult(
            total=result.total, docs=result.docs[: req.size],
            max_score=result.max_score, agg_partials=result.agg_partials,
            facet_partials=result.facet_partials, suggest=result.suggest,
            context_id=cid,
        )
        with self._lock:
            self._contexts[cid].position = req.size
        return cid, first

    def scroll(self, cid: int) -> tuple[ShardQueryResult, bool]:
        with self._lock:
            entry = self._contexts.get(cid)
            if entry is None:
                raise SearchContextMissingError(cid)
            entry.last_access = time.monotonic()
            page = entry.ordered_docs[entry.position: entry.position + entry.req.size]
            entry.position += entry.req.size
            done = entry.position >= len(entry.ordered_docs)
        return ShardQueryResult(
            total=len(entry.ordered_docs), docs=page, max_score=float("nan"),
            context_id=cid,
        ), done

    def entry(self, cid: int) -> SearchContextEntry:
        with self._lock:
            e = self._contexts.get(cid)
            if e is None:
                raise SearchContextMissingError(cid)
            return e

    def free(self, cid: int) -> bool:
        with self._lock:
            return self._contexts.pop(cid, None) is not None

    def reap_expired(self):
        now = time.monotonic()
        with self._lock:
            for cid, e in list(self._contexts.items()):
                if now - e.last_access > e.keep_alive_s:
                    del self._contexts[cid]

    def active_contexts(self) -> int:
        return len(self._contexts)


# ---------------------------------------------------------------------------
# deadline-aware admission control (coordinator side)
# ---------------------------------------------------------------------------


class SearchAdmissionController:
    """Reject unservable searches BEFORE the fan-out.

    A request whose remaining Deadline budget is smaller than the node's
    recent shard-phase latency cannot finish in time — executing it anyway
    burns a search worker, transport slots, and breaker headroom to produce
    an answer the client has already given up on. The coordinator tracks
    observed shard-phase latency in a MeanMetric (common/metrics.py) and
    turns those requests into an immediate 429 with a Retry-After hint.

    Unbounded requests (no `timeout`) are always admitted, and nothing is
    rejected before `min_samples` observations — a cold node (whose first
    searches include multi-second XLA compiles) must not poison admission
    for everyone.

    The admit() signal is an EWMA over the MeanMetric's samples, not the
    lifetime mean: one slow failover chain must stop poisoning admission
    within ~1/alpha further observations, while a lifetime mean would shed
    servable load for hundreds of requests after a single 5s outlier.
    """

    EWMA_ALPHA = 0.2  # ~5-sample memory

    def __init__(self, min_samples: int = 10):
        from ..common.metrics import CounterMetric, HistogramMetric, MeanMetric

        self.min_samples = min_samples
        self.latency = MeanMetric()  # lifetime rollup (stats/observability)
        # tail view of the same signal: the EWMA decides admission, the
        # histogram answers "what does p99 shard-phase latency look like"
        # (p50/p95/p99 in /_nodes/stats + the Prometheus exposition)
        self.histogram = HistogramMetric()
        self.rejected = CounterMetric()
        self._ewma = 0.0  # the decaying signal admit() compares against
        self._ewma_lock = threading.Lock()

    def observe(self, seconds: float):
        s = max(0.0, float(seconds))
        self.latency.inc(s)
        self.histogram.observe(s)
        with self._ewma_lock:
            self._ewma = s if self.latency.count <= 1 else \
                self.EWMA_ALPHA * s + (1.0 - self.EWMA_ALPHA) * self._ewma

    def admit(self, deadline: Deadline):
        """Raise RejectedExecutionError (429) when the remaining budget cannot
        cover one expected shard phase; no-op while unbounded or cold."""
        remaining = deadline.remaining()
        if remaining is None or self.latency.count < self.min_samples:
            return
        expected = self._ewma
        if remaining < expected:
            self.rejected.inc()
            err = RejectedExecutionError(
                f"rejected before fan-out: remaining budget "
                f"[{remaining * 1000:.0f}ms] < expected shard phase "
                f"[{expected * 1000:.0f}ms]")
            # hint when the request WOULD be servable: one expected phase
            err.retry_after_s = max(expected, 0.001)
            raise err

    def stats(self) -> dict:
        return {
            "observed": self.latency.count,
            "mean_shard_phase_ms": round(self.latency.mean * 1000.0, 3),
            "ewma_shard_phase_ms": round(self._ewma * 1000.0, 3),
            "rejected": self.rejected.count,
            # tail percentiles of the same observations (HistogramMetric)
            "shard_phase": self.histogram.stats(),
        }
