"""Mesh serving: route co-located multi-shard searches through the SPMD program.

In the reference, scatter-gather IS the production search path — the coordinator
fans query-phase requests to every shard copy and reduces
(action/search/type/TransportSearchTypeAction.java:117,135-216; the merge at
search/controller/SearchPhaseController.java:137). Here, when an index's shards all
live on THIS node and a device mesh can hold one shard per device, the whole
scatter/score/reduce collapses into ONE jitted SPMD program (mesh_search.py): DFS
stats are summed on the host that assembles the batch, the reduce rides all_gather +
top_k — collectives over ICI instead of RPC over DCN. Anything the program can't express (aggregations, sort, rescore,
filters, non-flat queries, remote shards) falls back to the transport scatter-gather
unchanged — same results either way, checked by tests/test_mesh_serving.py.

The executor is cached per index and rebuilt when any shard's segment generation or
live version moves (NRT refresh / merges / deletes)."""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future

import numpy as np

from ..common import profile as _profile
from ..common import tracing
from ..common.breaker import reserve as breaker_reserve
from ..common.devicehealth import (DEVICE_HEALTH, classify_device_error,
                                   tag_domain)
from ..common.errors import CircuitBreakingError
from ..common.logging import get_logger
from ..search.execute import lower_flat, traced_dispatch
from ..search.filters import segment_mask
from ..search.queries import FilteredQuery
from ..search.service import ParsedSearchRequest, ShardQueryResult
from ..search.similarity import BM25Similarity, TFIDFSimilarity
from ..transport.faults import DEVICE_FAULTS
from .mesh_search import MeshSearchExecutor, build_sharded_index


def _plan_to_dict(plan) -> dict:
    """JSON form of a mesh-eligible FlatPlan (never carries fs/filt — the
    eligibility gate in _search_mesh declined those) for the compile-warm
    manifest: a restarted node replays these to pre-trace the SPMD program."""
    return {
        "clauses": [[c.field, c.term, float(c.boost), int(c.group)]
                    for c in plan.clauses],
        "msm": int(plan.msm), "n_must": int(plan.n_must),
        "coord": bool(plan.coord_enabled), "boost": float(plan.boost),
        "query_norm": float(plan.query_norm),
    }


def _plan_from_dict(d: dict):
    from ..search.execute import Clause, FlatPlan

    return FlatPlan(
        [Clause(str(f), str(t), float(b), int(g))
         for (f, t, b, g) in d.get("clauses", ())],
        msm=int(d.get("msm", 0)), n_must=int(d.get("n_must", 0)),
        coord_enabled=bool(d.get("coord", False)),
        boost=float(d.get("boost", 1.0)),
        query_norm=float(d.get("query_norm", 1.0)))


class MeshServingService:
    """Decides per search whether the SPMD mesh program can serve it, and does."""

    MIN_SHARDS = 2  # a 1-shard search gains nothing from the mesh

    def __init__(self, indices_service, settings, node_name: str = "node"):
        self.indices = indices_service
        self.enabled = bool(settings.get_bool("search.mesh.enabled", True))
        self.node_name = node_name  # profile attribution ("[node][index][shard]")
        self.logger = get_logger("search.mesh", node=node_name)
        # the node's cross-request DeviceBatcher (set by ActionModule): plain
        # mesh searches coalesce into one SPMD launch through the same queue
        # the transport path uses (search/batcher.py _MeshFamily)
        self.batcher = None
        self.mesh_queries = 0  # served via the SPMD program (stats/test hook)
        self.mesh_fallbacks = 0  # eligible-looking but fell back mid-flight
        self.mesh_rebuilds = 0  # executors rebuilt after a device launch fault
        self._lock = threading.Lock()
        self._meshes: dict[int, object] = {}
        self._executors: dict = {}  # index -> (freshness_key, executor dict)
        # index -> (freshness_key, svc, Future) for a repack in flight: racers
        # park on the future with NO lock held instead of serializing every
        # search on the node behind a multi-second device_put (tpulint TPU004)
        self._building: dict = {}

    # ------------------------------------------------------------------
    def _mesh_for(self, n_shards: int):
        import jax

        with self._lock:
            mesh = self._meshes.get(n_shards)
        if mesh is not None:
            return mesh
        devices = jax.devices()
        if len(devices) < n_shards:
            return None
        from jax.sharding import Mesh

        mesh = Mesh(np.array(devices[:n_shards]), ("shards",))
        with self._lock:
            return self._meshes.setdefault(n_shards, mesh)

    def _eligible(self, state, local_node_id, indices, alias_filters, shards,
                  req: ParsedSearchRequest):
        """Cheap host-side checks, in rough rejection-frequency order.

        Round-5 widening: sort (single field spec), post_filter, min_score and
        bucket aggs all ride the program now (per-agg/per-column eligibility is
        checked in _search_mesh where the shard context exists), and a
        routing/preference-selected shard SUBSET is served via an active-shard
        mask as long as the whole index is locally present."""
        if not self.enabled or len(indices) != 1:
            return None
        index = indices[0]
        if alias_filters.get(index):
            return None
        if req.facets or req.suggest or req.rescore or req.explain:
            return None
        if req.sort and (len(req.sort) != 1 or req.sort[0].kind != "field"):
            return None
        if len(shards) < self.MIN_SHARDS:
            return None
        if any(c.node_id != local_node_id for c in shards):
            return None
        meta = state.metadata.index(index)
        if meta is None:
            return None
        n_total = meta.number_of_shards
        sids = sorted(c.shard_id for c in shards)
        if len(set(sids)) != len(sids) or sids[-1] >= n_total:
            return None
        return index, n_total

    def try_search(self, state, local_node_id: str, indices, alias_filters,
                   shards, req: ParsedSearchRequest, use_global_stats: bool,
                   deadline=None):
        """Returns per-ordinal ShardQueryResults (ordinal = position in `shards`)
        when the mesh program served the query phase, else None (transport path).
        `deadline` rides into the batcher's deadline-aware flush for plain
        (coalescable) searches — a launched SPMD program still runs whole."""
        eligible = self._eligible(state, local_node_id, indices, alias_filters,
                                  shards, req)
        if eligible is None:
            return None
        index, n_total = eligible
        # device fault-domain gate (common/devicehealth): an OPEN mesh:<index>
        # domain — launch failures that survived the one-rebuild heal — routes
        # this search to the transport scatter-gather (same results, host/
        # single-shard kernels) instead of re-poking a broken mesh; blocked()
        # admits one probe per backoff window, which IS this search
        if DEVICE_HEALTH.any_open and \
                DEVICE_HEALTH.blocked((f"mesh:{index}",)) is not None:
            self.mesh_fallbacks += 1
            return None
        self._prune(state)
        # the mesh path runs ON the coordinator (no shard-side _s_query_phase
        # to arm a collector), so a profiled request roots its collector here:
        # one collector for the single SPMD launch, fanned out per ordinal
        prof = None
        if req.profile:
            prof = _profile.ProfileCollector(node=self.node_name, index=index)
        try:
            if prof is None:
                results = self._search_mesh(index, n_total, shards, req,
                                            use_global_stats,
                                            deadline=deadline)
            else:
                with _profile.activate(prof):
                    results = self._search_mesh(index, n_total, shards, req,
                                                use_global_stats,
                                                deadline=deadline, prof=prof)
        except CircuitBreakingError:
            # a tripped breaker means the NODE is out of budget — falling back
            # to the transport path would re-materialize the same request-sized
            # buffers it just rejected; shed the load instead (429 upstream)
            raise
        except Exception as e:  # noqa: BLE001 — any mesh failure must not fail the search
            results = None
            self.logger.warning(f"mesh path failed, falling back to transport: {e}")
        if results is None:
            self.mesh_fallbacks += 1  # eligible-looking but fell back mid-flight
        elif DEVICE_HEALTH.dirty:
            # mesh program served: clean device outcome (closes a half-open
            # mesh domain when this search was the admitted probe)
            DEVICE_HEALTH.note_success((f"mesh:{index}",))
        return results

    def _breakers(self):
        """The owning node's CircuitBreakerService (None when the indices
        service is not node-attached — standalone unit tests)."""
        node = getattr(self.indices, "node", None)
        return getattr(node, "breakers", None)

    def _breaker(self, name: str):
        svc = self._breakers()
        return None if svc is None else svc.breaker(name)

    def _prune(self, state):
        """Drop executors (and their device-resident index arrays) for indices that no
        longer exist — a deleted-then-recreated index must never hit the old cache."""
        with self._lock:
            if not self._executors:
                return
            live = {n for n, _m in state.metadata.indices}
            for name in [n for n in self._executors if n not in live]:
                del self._executors[name]

    # ------------------------------------------------------------------
    def _search_mesh(self, index: str, n_total: int, shards,
                     req: ParsedSearchRequest, use_global_stats: bool,
                     deadline=None, prof=None):
        from ..common.errors import IndexShardMissingError

        svc = self.indices.index_service(index)
        S = n_total
        try:
            searchers = [svc.shard(sid).engine.acquire_searcher()
                         for sid in range(S)]
        except IndexShardMissingError:
            return None  # subset selected but index not fully local

        from ..search.execute import ShardContext

        ctxs = [ShardContext(s, svc.mapper_service, svc.similarity_service,
                             index_name=index, breakers=self._breakers())
                for s in searchers]
        ctx0 = ctxs[0]
        query = req.query
        filt = None
        if isinstance(query, FilteredQuery):
            # the filter gates matching only — evaluate host-side per shard (reusing
            # the per-segment filter cache) and ship masks onto the mesh
            if getattr(query, "boost", 1.0) != 1.0:
                return None
            filt = query.filter
            query = query.query
        plan = lower_flat(query, ctx0)
        if (plan is None or plan.fs is not None or plan.filt is not None
                or plan.const is not None):
            # function_score / nested-filtered / unscored plans carry a device
            # tail the mesh program doesn't express — transport path (which itself serves them
            # on-device via execute_flat_batch's fs/filtered kernels); an exact
            # phrase is declined by lower_flat itself and goes the same way
            return None

        # ---- aggregation eligibility: metric aggs fuse as masked stats, bucket
        # aggs as per-shard scatter counts (+ metric sub-agg folds); anything
        # else declines to the transport path ----
        metric_fields: dict = {}
        bucket_names: list = []
        bucket_subs: dict = {}
        if req.aggs:
            from ..search.aggregations import (SignificantTermsAgg,
                                               device_agg_field,
                                               device_bucket_eligible,
                                               device_bucket_subs)

            for name, agg in req.aggs.items():
                f = device_agg_field(agg, ctx0)
                if f is not None:
                    metric_fields[name] = f
                    continue
                if isinstance(agg, SignificantTermsAgg):
                    # per-SEGMENT background counts don't survive the mesh's
                    # shard-level partial merge — transport path serves these
                    return None
                if device_bucket_eligible(agg):
                    subs = device_bucket_subs(agg, ctx0) if agg.subs else {}
                    if subs is None:
                        return None
                    bucket_names.append(name)
                    bucket_subs[name] = (subs, sorted(set(subs.values())))
                else:
                    return None
        # one similarity family per program: every queried field must score with the
        # index default (per-field DFR/IB/etc lowered out already by lower_flat)
        default_sim = svc.similarity_service.default
        kind = "BM25" if isinstance(default_sim, BM25Similarity) else "default"
        for c in plan.clauses:
            sim = svc.similarity_service.for_field(c.field)
            if type(sim) is not type(default_sim):
                return None
            if isinstance(sim, BM25Similarity) and (
                    sim.k1 != default_sim.k1 or sim.b != default_sim.b):
                return None
        k = max(req.from_ + req.size, 1)

        executor = self._executor_for(index, svc, searchers, kind, default_sim,
                                      use_global_stats)
        if executor is None:
            return None
        if prof is not None:
            from ..search.execute import plan_profile

            prof.outcome("mesh_spmd")
            # report the REQUEST's query shape: `query` was rebound to the
            # inner query for FilteredQuery (the mesh applies the filter via
            # mask rows, so plan.filt is always None here) — the profile must
            # match what the transport path reports for the same body
            shape = plan_profile(plan, req.query)
            shape["filtered"] = filt is not None
            prof.set_plan(shape)
            prof.mesh_info(
                shards=int(S), tf_layout=executor.index.tf_layout,
                resident_postings_bytes=int(
                    executor.index.resident_postings_bytes()),
                global_stats=bool(use_global_stats))
        doc_pad = executor.index.doc_pad
        if k > doc_pad:
            return None
        # queried fields must exist in the packed norm stack (a field with no norms
        # anywhere would silently score with another field's norms)
        for c in plan.clauses:
            if c.field not in executor.index.fields:
                return None

        # mesh result assembly — per-shard mask canvases, sort-key rows,
        # bucket pair canvases and the gathered program output — reserved on
        # the request breaker for the duration of the program + assembly
        # (host-side code around the SPMD launch; the launch itself is traced
        # and carries no breaker calls — tpulint TPU010)
        n_mask_kinds = (1 if filt is not None else 0) + \
            (1 if req.post_filter is not None else 0)
        assembly_est = S * doc_pad * (n_mask_kinds + 4 + 8) + S * doc_pad
        with breaker_reserve(self._breaker("request"), assembly_est,
                             f"<mesh_assembly>[{index}]"):
            def shard_masks(f):
                masks = np.zeros((S, 1, doc_pad), bool)
                for si, searcher in enumerate(searchers):
                    for seg, base in zip(searcher.segments, searcher.bases):
                        masks[si, 0, base: base + seg.doc_count] = \
                            segment_mask(seg, f, ctxs[si])
                return masks

            filter_masks = shard_masks(filt) if filt is not None else None
            post_masks = (shard_masks(req.post_filter)
                          if req.post_filter is not None else None)

            # ---- single-field sort: per-shard key rows (host-exact fold, f32-exact
            # gate per segment — sorting.device_sort_key_row) ----
            sort_spec = req.sort[0] if req.sort else None
            sort_keys = None
            if sort_spec is not None:
                from ..search.sorting import device_sort_key_row

                fill = np.finfo(np.float32).max * (-1.0 if sort_spec.reverse else 1.0)
                sort_keys = np.full((S, doc_pad), fill, np.float32)
                for si, searcher in enumerate(searchers):
                    for seg, base in zip(searcher.segments, searcher.bases):
                        row = device_sort_key_row(sort_spec, seg, seg.doc_count)
                        if row is None:
                            return None  # column/spec needs the host path
                        sort_keys[si, base: base + seg.doc_count] = row

            # ---- ONE per-doc fold stack for metric aggs and bucket sub-aggs ----
            all_stack_fields = tuple(sorted(
                set(metric_fields.values())
                | {f for (_subs, order) in bucket_subs.values() for f in order}))
            agg_rows = None
            if all_stack_fields:
                from .mesh_search import ensure_mesh_agg_stack

                agg_rows = ensure_mesh_agg_stack(executor.index, all_stack_fields)
                if agg_rows is None:
                    return None  # column not f32-exact → transport/host path
            fpos = {f: i for i, f in enumerate(all_stack_fields)}

            bucket_pairs, bucket_keys_per = self._bucket_pairs(
                req, bucket_names, bucket_subs, fpos, searchers, ctxs, S)
            if bucket_names and bucket_pairs is None:
                return None

            active = None
            selected = sorted(c.shard_id for c in shards)
            if selected != list(range(S)):
                active = np.zeros(S, bool)
                active[selected] = True

            plain = (filter_masks is None and agg_rows is None
                     and post_masks is None and req.min_score is None
                     and sort_keys is None and active is None
                     and not bucket_pairs)
            if plain and self.batcher is not None and prof is not None:
                # mirror of service._execute_flat_single: the coalescing
                # queue WOULD have served this plain search — record and
                # count the explicit profile bypass before launching directly
                prof.batcher_bypass("profile")
                self.batcher.note_profile_bypass()
            if plain and self.batcher is not None and prof is None:
                # plain searches carry no per-request program arguments, so
                # concurrent ones coalesce into ONE SPMD launch through the
                # node's cross-request queue (search/batcher.py _MeshFamily —
                # same flush policy as the single-shard transport path); the
                # fan-out hands back this query's host rows directly
                out = None
                (shard_row, score_row, doc_row, totals_col,
                 qmax_col) = self._launch_contained(
                     index, svc, searchers, kind, default_sim,
                     use_global_stats, executor,
                     lambda ex: self.batcher.execute_mesh(
                         plan, ex, k, deadline=deadline))
            else:
                # the SPMD launch + its program-output pull, timed as one
                # mesh span on the request's trace (no extra sync: the span
                # end rides the pull executor.search performs anyway); the
                # batcher path above records its own queue/dispatch/merge
                # spans per coalesced member instead
                cur = tracing.current_span()
                mesh_span = cur.child("mesh.launch").tag(
                    index=index, shards=S) if cur is not None else None
                t_launch = time.monotonic() if prof is not None else 0.0
                try:
                    # a sampled request keeps the launch's stage / launch /
                    # device_pull intervals beside its mesh span
                    with traced_dispatch():
                        out = self._launch_contained(
                            index, svc, searchers, kind, default_sim,
                            use_global_stats, executor,
                            lambda ex: ex.search(
                                [plan], k, filter_masks=filter_masks,
                                agg_rows=agg_rows,
                                use_metric_aggs=bool(metric_fields),
                                post_masks=post_masks,
                                min_score=(float(req.min_score)
                                           if req.min_score is not None
                                           else None),
                                sort_keys=sort_keys,
                                sort_desc=bool(sort_spec.reverse)
                                if sort_spec is not None else False,
                                active=active,
                                bucket_pairs=bucket_pairs or None))
                finally:
                    if mesh_span is not None:
                        mesh_span.end()
                if prof is not None:
                    # launch + the executor's own program-output pull, one
                    # phase (the pull IS the sync — nothing extra added)
                    prof.phase_s("mesh_launch", time.monotonic() - t_launch)
            self.mesh_queries += 1
            # remember this served plan batch (dict work, ring-deduped): the
            # compile warmer replays it against a REBUILT executor (refresh /
            # restart) so the SPMD re-trace happens on the warmer pool, not
            # under the first post-rebuild query
            from ..common.compilecache import REGISTRY as _warm_registry

            _warm_registry.record_mesh(index, [plan], k, [_plan_to_dict(plan)])

            track = bool(req.track_scores) if req.sort else True
            if out is not None:
                # batch every host read ONCE: the executor already device_get
                # the whole program output, so these are pure-host .tolist()
                # conversions — the per-element float()/int() pulls this
                # replaces were a scalar extraction per hit per shard (the
                # grandfathered TPU001 block)
                shard_row = out.shard[0].tolist()
                score_row = out.scores[0].tolist()
                doc_row = out.doc[0].tolist()
                totals_col = out.shard_totals[:, 0].tolist()
                qmax_col = out.qmax[:, 0].tolist()
            # one collector covers the single SPMD launch; each ordinal's
            # entry re-brands the shared attribution with its own shard id
            # (the reference's per-shard `profile` entries, mesh-served)
            mesh_prof = prof.to_dict() if prof is not None else None
            results = []
            for ordinal, copy in enumerate(shards):
                sid = copy.shard_id
                sel = [j for j, sh in enumerate(shard_row) if sh == sid]
                if req.sort:
                    locals_ = [doc_row[j] for j in sel]
                    sort_vals = self._sort_values(req.sort, ctxs[sid],
                                                  searchers[sid], locals_)
                    rows = [(score_row[j] if track else float("nan"),
                             doc_row[j], sort_vals[i])
                            for i, j in enumerate(sel)]
                else:
                    rows = [(score_row[j], doc_row[j], None) for j in sel]
                qm = qmax_col[sid]
                agg_partials = self._shard_agg_partials(
                    req, metric_fields, bucket_names, bucket_subs, fpos,
                    bucket_keys_per, out, sid, searchers[sid])
                result = ShardQueryResult(
                    total=totals_col[sid],
                    docs=rows,
                    max_score=qm if np.isfinite(qm) else float("nan"),
                    agg_partials=agg_partials,
                    shard_id=ordinal,
                )
                if mesh_prof is not None:
                    result.profile = {
                        **mesh_prof, "shard": int(sid),
                        "id": f"[{self.node_name}][{index}][{sid}]"}
                # pin the query-time searcher for the fetch phase (a merge between
                # phases must not move local doc ids under the fetch)
                pin = getattr(self, "pin_context", None)
                if pin is not None:
                    result.context_id = pin(copy.index, sid, ctxs[sid])
                results.append(result)
            return results

    # ------------------------------------------------------------------
    _POSITIONAL_BUCKETS = None  # class-level lazy import cache

    @classmethod
    def _positional(cls, agg) -> bool:
        """Positionally-keyed bucket aggs: the key LIST comes from the spec and
        is identical in every segment (ranges/filters/missing/geo_distance), so
        bucket ordinals align across segments without a key union."""
        if cls._POSITIONAL_BUCKETS is None:
            from ..search.aggregations import (FilterAgg, FiltersAgg,
                                               GeoDistanceAgg, MissingAgg,
                                               RangeAgg)

            cls._POSITIONAL_BUCKETS = (RangeAgg, FilterAgg, FiltersAgg,
                                       MissingAgg, GeoDistanceAgg)
        return isinstance(agg, cls._POSITIONAL_BUCKETS)

    def _bucket_pairs(self, req, bucket_names, bucket_subs, fpos, searchers,
                      ctxs, S):
        """Per bucket agg: shard-level (doc, bucket) pair arrays padded to
        common shapes, plus each shard's key list. Segments concatenate into
        the shard's doc space (bases rebase pair docs); value-keyed aggs union
        their segment key lists per shard, positional aggs share the spec's.
        Returns (bucket_pairs, keys_per_name) or (None, None) on any shape the
        partial assembly can't express."""
        if not bucket_names:
            return [], {}
        from ..search.aggregations import bucket_cols_for

        bucket_pairs = []
        bucket_keys_per: dict = {}
        for name in bucket_names:
            agg = req.aggs[name]
            positional = self._positional(agg)
            per_shard = []
            shard_keys = []
            for si in range(S):
                seg_cols = [
                    (bucket_cols_for(agg, seg, ctxs[si]), base)
                    for seg, base in zip(searchers[si].segments,
                                         searchers[si].bases)
                ]
                pd_parts, pb_parts = [], []
                if positional:
                    keys = next((c[2] for c, _b in seg_cols if c[2]), [])
                    for (pd, pb, seg_keys), base in seg_cols:
                        if seg_keys and len(seg_keys) != len(keys):
                            return None, None  # spec-derived keys must align
                        pd_parts.append(pd.astype(np.int64) + base)
                        pb_parts.append(pb)
                else:
                    union = sorted({k2 for c, _b in seg_cols for k2 in c[2]})
                    pos = {k2: i for i, k2 in enumerate(union)}
                    keys = list(union)
                    for (pd, pb, seg_keys), base in seg_cols:
                        if not len(pd):
                            continue
                        remap = np.asarray([pos[k2] for k2 in seg_keys],
                                           dtype=np.int32)
                        pd_parts.append(pd.astype(np.int64) + base)
                        pb_parts.append(remap[pb])
                pd_all = (np.concatenate(pd_parts).astype(np.int32)
                          if pd_parts else np.zeros(0, np.int32))
                pb_all = (np.concatenate(pb_parts).astype(np.int32)
                          if pb_parts else np.zeros(0, np.int32))
                per_shard.append((pd_all, pb_all))
                shard_keys.append(keys)
            NB = max((len(ks) for ks in shard_keys), default=0) or 1
            P = max((len(pd) for pd, _ in per_shard), default=0) or 1
            # pad pairs with (doc 0, bucket NB): the OOB bucket scatter drops
            # under jit, so padding contributes nothing
            pdoc = np.zeros((S, P), np.int32)
            pbucket = np.full((S, P), NB, np.int32)
            for si, (pd, pb) in enumerate(per_shard):
                pdoc[si, : len(pd)] = pd
                pbucket[si, : len(pb)] = pb
            sub_order = bucket_subs[name][1]
            sub_idx = (tuple(fpos[f] for f in sub_order)
                       if sub_order else None)
            bucket_pairs.append((pdoc, pbucket, NB, sub_idx))
            bucket_keys_per[name] = shard_keys
        return bucket_pairs, bucket_keys_per

    def _shard_agg_partials(self, req, metric_fields, bucket_names, bucket_subs,
                            fpos, bucket_keys_per, out, sid, searcher):
        """One shard-level partial dict (the transport path emits one per
        SEGMENT; merge is associative so one-per-shard reduces identically).
        Shards with no segments emit none — mirroring the transport path's
        empty per-segment list."""
        if not (metric_fields or bucket_names) or not searcher.segments:
            return []
        from ..search.aggregations import device_bucket_partial, device_partial

        partial = {}
        for name, agg in req.aggs.items():
            if name in metric_fields:
                fi = fpos[metric_fields[name]]
                partial[name] = device_partial(
                    agg, out.agg_counts[sid, 0][fi], out.agg_stats[sid, 0][fi])
            else:
                bi = bucket_names.index(name)
                cnts, scnt, sstats = out.bucket_results[bi]
                keys = bucket_keys_per[name][sid]
                sub_aggs_map, order = bucket_subs[name]
                sub_data = None
                if sub_aggs_map:
                    # (no exact sums: this program takes a whole-number
                    # column only where its float32 sums are exact)
                    sub_data = (agg.subs, sub_aggs_map, order,
                                scnt[sid, 0], sstats[sid, 0], None)
                partial[name] = device_bucket_partial(
                    agg, keys, cnts[sid, 0][: len(keys)], seg=None,
                    sub_data=sub_data)
        return [partial]

    def _sort_values(self, specs, ctx, searcher, locals_):
        """Host-exact sort VALUES for the response "sort" arrays, extracted per
        segment (the one extraction idiom — service._sort_values_by_rank)."""
        from ..search.sorting import sort_values_for_docs

        bases = np.asarray(searcher.bases)
        out: list = [None] * len(locals_)
        by_seg: dict = {}
        # one vectorized searchsorted for ALL docs (the per-doc int() pair was
        # a scalar extraction per hit), then pure-list bucketing
        seg_of = (np.searchsorted(bases, np.asarray(locals_, dtype=np.int64),
                                  side="right") - 1).tolist()
        base_list = bases.tolist()
        for i, (g, si) in enumerate(zip(locals_, seg_of)):
            by_seg.setdefault(si, []).append((i, g - base_list[si]))
        for si, items in by_seg.items():
            seg = searcher.segments[si]
            vals = sort_values_for_docs(
                specs, seg, ctx, np.asarray([l for _i, l in items]), None)
            for (i, _l), v in zip(items, vals):
                out[i] = v
        return out

    def _launch_contained(self, index: str, svc, searchers, kind, default_sim,
                          use_global_stats: bool, executor, launch):
        """One SPMD launch with device fault containment.

        The seeded chaos seam (transport/faults.DEVICE_FAULTS, domain
        ``mesh:<index>``) fires before the launch. A device-classified launch
        failure invalidates the cached executor and rebuilds it ONCE — a
        poisoned executable heals with a rebuild, not a retry against the same
        program — then retries the launch on the fresh executor. A second
        failure records the ``mesh:<index>`` fault domain and re-raises;
        try_search's blanket handler degrades this search to the transport
        scatter-gather, and the now-open circuit keeps later searches off the
        mesh until a probe succeeds. Host-side exceptions (classify → None)
        pass straight through: no rebuild, no circuit movement."""
        try:
            if DEVICE_FAULTS.active:
                DEVICE_FAULTS.check(f"mesh:{index}")
            return launch(executor)
        except Exception as e:  # noqa: BLE001
            if classify_device_error(e) is None:
                raise
            with self._lock:
                cached = self._executors.get(index)
                if cached is not None and cached[2] is not None \
                        and executor in cached[2].values():
                    del self._executors[index]
            self.mesh_rebuilds += 1
            self.logger.warning(
                f"mesh launch failed for [{index}] ({type(e).__name__}: {e});"
                f" rebuilding executor once")
            rebuilt = self._executor_for(index, svc, searchers, kind,
                                         default_sim, use_global_stats)
            if rebuilt is None:
                DEVICE_HEALTH.record_failure(
                    f"mesh:{index}", tag_domain(e, f"mesh:{index}"))
                raise
            try:
                if DEVICE_FAULTS.active:
                    DEVICE_FAULTS.check(f"mesh:{index}")
                return launch(rebuilt)
            except Exception as e2:  # noqa: BLE001
                DEVICE_HEALTH.record_failure(
                    f"mesh:{index}", tag_domain(e2, f"mesh:{index}"))
                raise

    def _executor_for(self, index: str, svc, searchers, kind, default_sim,
                      use_global_stats: bool):
        """Build-or-reuse the ShardedIndex + executor; rebuilt when any shard's
        segments or tombstones moved.

        The multi-second device repack runs with NO lock held (tpulint TPU004:
        device dispatch under `self._lock` would serialize every search on the
        node — not just this index — behind the pack). Racing searches dedup
        on an in-flight build future: exactly one thread packs, the rest park
        on the future lock-free (tpulint TPU011)."""
        freshness = tuple(
            (tuple(seg.gen for seg in s.segments),
             tuple(seg.live_gen for seg in s.segments),
             s.max_doc)
            for s in searchers
        )
        prof = _profile.current()
        with self._lock:
            cached = self._executors.get(index)
            if cached is not None and cached[0] == freshness and cached[1] is svc:
                execs = cached[2]
                if execs is None:
                    return None  # negative cache: this generation failed to build
                if prof is not None:
                    # pure list append — event() takes no locks, blocks on
                    # nothing, dispatches nothing (profile.py design rules)
                    prof.event("mesh_executor", cache="hit")
                return execs[use_global_stats]
            inflight = self._building.get(index)
            if inflight is not None and inflight[0] == freshness \
                    and inflight[1] is svc:
                fut = inflight[2]
                builder = False
            else:
                fut = Future()
                self._building[index] = (freshness, svc, fut)
                builder = True
        if not builder:
            try:
                execs = fut.result(timeout=120.0)
            except Exception as e:  # noqa: BLE001 — builder wedged/timed out
                # loud, unlike an ineligible search: every deduped waiter is
                # degrading to the transport path because the BUILDER is stuck
                self.logger.warning(
                    f"mesh executor build wait failed for [{index}] "
                    f"({type(e).__name__}: {e}); serving via transport path")
                return None
            return None if execs is None else execs[use_global_stats]
        execs = None
        t_build = time.monotonic() if prof is not None else 0.0
        try:
            execs = self._build_executors(searchers, kind, default_sim)
            if prof is not None and execs is not None:
                prof.event("mesh_executor", cache="build",
                           ms=round((time.monotonic() - t_build) * 1000.0, 4))
        except Exception as e:  # noqa: BLE001 — e.g. device OOM on pack
            # negative-cache the failure so every search doesn't re-pay a
            # doomed multi-second repack
            self.logger.warning(f"mesh index build failed for [{index}]: {e}")
        finally:
            # publish cache + clear the in-flight entry ONLY if this build is
            # still the current one: a refresh mid-pack lets a NEWER freshness
            # register its own build, and a stale finally must not clobber its
            # cache entry or pop its in-flight dedup record
            with self._lock:
                inflight = self._building.get(index)
                if inflight is not None and inflight[2] is fut:
                    self._executors[index] = (freshness, svc, execs)
                    self._building.pop(index, None)
            # but ALWAYS resolve: this generation's waiters park on this
            # future whether or not it is still the freshest
            fut.set_result(execs)
        if execs is not None:
            # a fresh executor pack means every program for this index must
            # re-trace — replay the recently-served plan batches on the warmer
            # pool so the re-compiles happen off the query path
            self._schedule_mesh_warm(index, execs)
        return None if execs is None else execs[use_global_stats]

    def _schedule_mesh_warm(self, index: str, execs) -> None:
        """Leaf: queue a mesh warm replay for a just-built executor pair."""
        from ..common.compilecache import REGISTRY

        node = getattr(self.indices, "node", None)
        tp = getattr(node, "threadpool", None)
        warmer = getattr(node, "warmer", None)
        if (tp is None or not REGISTRY.enabled
                or (warmer is not None and not warmer.enabled)):
            return
        live, manifest = REGISTRY.mesh_entries(index)
        if not live and not manifest:
            return
        try:
            tp.submit("warmer", self._run_mesh_warm, index, execs, live,
                      manifest)
        except Exception:  # noqa: BLE001 — rejected/shut-down pool
            pass

    def _run_mesh_warm(self, index: str, execs, live, manifest) -> None:
        """Warmer-pool worker: replay recorded mesh plan batches against both
        stats-mode executors (each holds its own compiled-program cache).
        Live FlatPlan payloads serve same-process rebuilds; after a restart
        only the manifest's JSON plans exist — same shapes either way (the
        executable key depends on clause counts/k, not term values)."""
        from ..common.compilecache import REGISTRY

        # entry k values are plain ints (record_mesh / JSON manifest)
        batches = [(e["plans"], e["k"]) for e in live]
        if not batches:
            batches = [([_plan_from_dict(d) for d in e.get("plans", ())],
                        e.get("k", 10)) for e in manifest]
        domain = "compile:mesh"
        for plans, k in batches:
            if not plans or DEVICE_HEALTH.blocked((domain,)):
                continue
            for ex in execs.values():
                try:
                    # executor.search wraps its launch in compile_tag("mesh")
                    # and pulls the program output itself
                    ex.search(plans, min(k, ex.index.doc_pad))
                except Exception as e:  # noqa: BLE001 — warm failure: off-path
                    REGISTRY.note_mesh_warm(False)
                    DEVICE_HEALTH.record_failure(domain, e)
                    return
                REGISTRY.note_mesh_warm(True)
            DEVICE_HEALTH.note_success((domain,))

    def _build_executors(self, searchers, kind, default_sim):
        """The device-side pack: ShardedIndex + one executor per stats mode.
        Called with no lock held; returns None when the mesh can't serve."""
        mesh = self._mesh_for(len(searchers))
        if mesh is None:
            return None
        fields = sorted({f for s in searchers for seg in s.segments
                         for f in seg.norms})
        if not fields:
            return None
        sharded = build_sharded_index(searchers, fields, mesh=mesh)
        # capacity-planning breadcrumb: the quantized tf plane halves-or-better
        # the mesh-resident postings footprint vs the old f32 layout
        self.logger.debug(
            f"mesh repack: {sharded.n_shards} shards, tf layout "
            f"[{sharded.tf_layout}], resident postings "
            f"~{sharded.resident_postings_bytes() // 1024} KiB")
        execs = {}
        compiled: dict = {}  # one executable serves both search types
        for gs in (False, True):
            execs[gs] = MeshSearchExecutor(
                sharded, mesh, similarity=kind,
                k1=getattr(default_sim, "k1", 1.2),
                b=getattr(default_sim, "b", 0.75),
                use_global_stats=gs, compiled=compiled)
        return execs
