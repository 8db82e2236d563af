"""Action layer: the API kernel over transport.

Analogue of action/ (69k LoC — SURVEY.md §2.6). Each API is a transport action
implementing one of the reference's interaction patterns (action/support/):

- master-node  (TransportMasterNodeOperationAction): forwarded to the elected master,
  which mutates cluster state through the single-threaded executor → publish.
  [create/delete/open/close index, mappings, settings, aliases, templates, reroute]
- replication  (TransportShardReplicationOperationAction): route to primary by djb2,
  write-consistency precheck, primary op, fan to assigned replicas, ack.
  [index, delete, bulk per-shard groups, update (get-modify-reindex on primary)]
- single-shard (TransportSingleShardOperationAction): one active copy, realtime.
  [get, multi_get, explain, termvector-lite]
- scatter-gather (TransportSearchTypeAction): one copy per shard group, per-shard
  query phase (+ optional DFS pre-phase), controller reduce, fetch winners, per-shard
  failover to the next copy on failure.
  [search (query_then_fetch / dfs_query_then_fetch / count / scan), msearch, count,
   suggest, delete_by_query (broadcast), refresh/flush/optimize (broadcast)]
"""

from __future__ import annotations

import base64
import contextlib
import threading
import time
import uuid
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeoutError

from .common import insights as _insights
from .common import profile as profiling
from .common import tracing
from .common.units import parse_time
from .common.deadline import NO_DEADLINE, Deadline
from .common.metrics import HistogramMetric
from .common.retry import RetryPolicy
from .common.errors import (
    ActionNotFoundError,
    CircuitBreakingError,
    DocumentMissingError,
    IllegalArgumentError,
    IndexAlreadyExistsError,
    IndexMissingError,
    MasterNotDiscoveredError,
    NoShardAvailableError,
    ReceiveTimeoutError,
    RejectedExecutionError,
    IndexWarmerMissingError,
    SearchEngineError,
    TransportError,
    TypeMissingError,
    UnavailableShardsError,
    VersionConflictError,
)
from .common.logging import get_logger
from .common.settings import Settings, validate_index_name
from .cluster.allocation import new_index_routing
from .cluster.service import HIGH, URGENT
from .cluster.state import (
    BLOCK_INDEX_CLOSED,
    ClusterState,
    IndexMetaData,
    IndexTemplateMetaData,
    ShardRouting,
)
from .index.translog import CREATE, DELETE, INDEX, TranslogOp
from .indices_service import ACTION_SHARD_FAILED, ACTION_SHARD_STARTED
from .search.queries import resolve_terms_lookups
from .search.request_cache import cache_policy, request_fingerprint
from .search.controller import (
    aggregate_dfs,
    collect_dfs,
    DfsResult,
    merge_responses,
    shard_page,
    sort_docs,
)
from .search.execute import ShardContext
from .transport import fut_result
from .transport.service import complete_fut
from .search.queries import parse_query
from .search.service import (
    ParsedSearchRequest,
    ShardQueryResult,
    execute_fetch_phase,
    execute_query_phase,
    parse_search_body,
)

A_CREATE_INDEX = "indices:admin/create"
A_DELETE_INDEX = "indices:admin/delete"
A_OPEN_INDEX = "indices:admin/open"
A_CLOSE_INDEX = "indices:admin/close"
A_PUT_MAPPING = "indices:admin/mapping/put"
A_DELETE_MAPPING = "indices:admin/mapping/delete"
A_UPDATE_SETTINGS = "indices:admin/settings/update"
A_ALIASES = "indices:admin/aliases"
A_PUT_TEMPLATE = "indices:admin/template/put"
A_DELETE_TEMPLATE = "indices:admin/template/delete"
A_CLUSTER_SETTINGS = "cluster:admin/settings/update"
A_REROUTE = "cluster:admin/reroute"
A_SHUTDOWN_NODE = "cluster:admin/nodes/shutdown"
A_MAPPING_UPDATED = "internal:cluster/mapping_updated"

A_INDEX_PRIMARY = "indices:data/write/index[p]"
A_INDEX_REPLICA = "indices:data/write/index[r]"
A_DELETE_PRIMARY = "indices:data/write/delete[p]"
A_DELETE_REPLICA = "indices:data/write/delete[r]"
A_BULK_SHARD = "indices:data/write/bulk[s]"
A_GET = "indices:data/read/get[s]"
A_TERMVECTOR = "indices:data/read/termvector[s]"
A_QUERY_PHASE = "indices:data/read/search[phase/query]"
A_QUERY_PROGRESS = "indices:data/read/search[phase/query/progress]"
A_FETCH_PHASE = "indices:data/read/search[phase/fetch]"
A_FREE_CONTEXT = "indices:data/read/search[free-context]"
A_DFS_PHASE = "indices:data/read/search[phase/dfs]"
A_SHARD_BROADCAST = "indices:admin/broadcast[s]"
# stall-watchdog event gossip (common/events.py): a node's warn events are
# pushed best-effort to every peer's journal so any coordinator's /_events
# shows the cluster-wide causal record
A_EVENTS_PUBLISH = "internal:cluster/events/publish"

# the search types whose query phase waits for a DFS round over every shard
DFS_SEARCH_TYPES = ("dfs_query_then_fetch", "dfs_query_and_fetch")


def _normalize_alias_specs(aliases: dict) -> dict:
    """Alias metadata stores index_routing/search_routing; a bare `routing` key sets
    both (ref: cluster/metadata/AliasMetaData + AliasAction semantics)."""
    out = {}
    for name, spec in aliases.items():
        spec = dict(spec or {})
        spec = {k: v for k, v in spec.items()
                if k in ("filter", "index_routing", "search_routing", "routing")}
        if "routing" in spec:
            r = spec.pop("routing")
            spec.setdefault("index_routing", r)
            spec.setdefault("search_routing", r)
        out[name] = spec
    return out


def _normalize_warmer(body) -> dict:
    """Warmer metadata is {types, source} (ref: search/warmer/IndexWarmersMetaData);
    a bare search body becomes the source."""
    body = dict(body or {})
    if "source" in body:
        return {"types": body.get("types") or [], "source": body["source"]}
    types = body.pop("types", []) or []
    return {"types": types, "source": body}


class ActionModule:
    """Registers every handler on one node + provides coordinator entry points."""

    def __init__(self, node):
        self.node = node
        self.transport = node.transport
        self.cluster_service = node.cluster_service
        self.indices = node.indices
        self.routing = node.operation_routing
        self.allocation = node.allocation
        self.logger = get_logger("action", node=node.name)
        # SPMD mesh serving for co-located shards (ICI data plane as the search path;
        # ref: the scatter-gather in TransportSearchTypeAction.java:117 this bypasses)
        from .parallel.mesh_serving import MeshServingService

        self.mesh_serving = MeshServingService(node.indices, node.settings,
                                               node_name=node.name)
        self.mesh_serving.pin_context = self._pin_context
        # plain mesh searches coalesce through the same cross-request queue as
        # the transport path's single-shard launches (search/batcher.py)
        self.mesh_serving.batcher = getattr(node, "search_batcher", None)
        # point-in-time contexts pinned between the query and fetch phases (the
        # reference's SearchService active-contexts map: a merge/refresh between
        # phases must not move local doc ids under the fetch — SearchContext
        # holds the query-time searcher; ref SearchService.java:177,315)
        self._pinned: dict[int, tuple] = {}  # cid -> (expiry, index, shard, ctx)
        self._pinned_lock = threading.Lock()
        self._pinned_next = [1]
        # write-path retry schedule (replica fan-out, shard-failed reports):
        # transient transport failures back off with decorrelated jitter, then
        # exhaustion is REPORTED to the master — never swallowed (tests swap in
        # a faster policy)
        self.retry_policy = RetryPolicy(max_attempts=3, base_s=0.05, cap_s=1.0)
        # deadline-aware admission control: searches whose remaining budget
        # cannot cover one observed shard phase are 429'd BEFORE the fan-out
        from .search.service import SearchAdmissionController

        self.admission = SearchAdmissionController()
        # parsed cluster-level slowlog thresholds, cached against the
        # metadata version that produced them: the unset-thresholds default
        # must not rebuild the flattened settings dict per query phase
        # (plain attr, single value — a benign race rebuilds once)
        self._slowlog_cluster: tuple | None = None
        # end-to-end coordinator search latency (accept -> response assembled):
        # the histogram behind /_nodes/stats search.latency percentiles and
        # the Prometheus estpu_search_latency_seconds series
        self.search_latency = HistogramMetric()
        # searches answered, by the trips they made to their shards: one where
        # the query phase of the one shard they met hydrated the page, two
        # where a fetch phase followed the reduce (plain ints booked in
        # _finish_search; /_nodes/stats `search.phases`); `inline_query`: those
        # of them whose query phase ran on the asking thread
        # (_query_shard_inline books it)
        self.search_phases = {"one_trip": 0, "two_trip": 0, "inline_query": 0}
        t = self.transport
        # master-node actions
        for action, fn in [
            (A_CREATE_INDEX, self._m_create_index),
            (A_DELETE_INDEX, self._m_delete_index),
            (A_OPEN_INDEX, self._m_open_index),
            (A_CLOSE_INDEX, self._m_close_index),
            (A_PUT_MAPPING, self._m_put_mapping),
            (A_DELETE_MAPPING, self._m_delete_mapping),
            (A_UPDATE_SETTINGS, self._m_update_settings),
            (A_ALIASES, self._m_aliases),
            (A_PUT_TEMPLATE, self._m_put_template),
            (A_DELETE_TEMPLATE, self._m_delete_template),
            (A_CLUSTER_SETTINGS, self._m_cluster_settings),
            ("indices:admin/warmers/put", self._m_put_warmer),
            ("indices:admin/warmers/delete", self._m_delete_warmer),
            (A_REROUTE, self._m_reroute),
            (A_MAPPING_UPDATED, self._m_mapping_updated),
            (ACTION_SHARD_STARTED, self._m_shard_started),
            (ACTION_SHARD_FAILED, self._m_shard_failed),
        ]:
            t.register_handler(action, self._master_wrap(action, fn))
        # data-path actions, each on its named pool (ref: every TransportAction names
        # its ThreadPool executor — search ops on SEARCH, writes on INDEX/BULK, …).
        # The dispatch trampoline ("generic") then never blocks on handler work, so
        # concurrent fan-outs can't starve it into a deadlock.
        t.register_handler(A_INDEX_PRIMARY, self._p_index, executor="index")
        t.register_handler(A_INDEX_REPLICA, self._r_index, executor="replica")
        t.register_handler(A_DELETE_PRIMARY, self._p_delete, executor="index")
        t.register_handler(A_DELETE_REPLICA, self._r_delete, executor="replica")
        t.register_handler(A_BULK_SHARD, self._p_bulk_shard, executor="bulk")
        t.register_handler(A_GET, self._s_get, executor="get")
        t.register_handler(A_TERMVECTOR, self._s_termvector, executor="get")
        t.register_handler(A_QUERY_PHASE, self._s_query_phase, executor="search")
        # not on the search pool: it is asked about a node whose searches are late
        t.register_handler(A_QUERY_PROGRESS, self._s_query_progress,
                           executor="management")
        t.register_handler(A_FETCH_PHASE, self._s_fetch_phase, executor="search")
        t.register_handler(A_FREE_CONTEXT, self._s_free_context, executor="search")
        t.register_handler(A_DFS_PHASE, self._s_dfs_phase, executor="search")
        t.register_handler(A_SHARD_BROADCAST, self._s_broadcast, executor="management")
        # sniffing TransportClient surface (ref: TransportClientNodesService — the
        # sampler asks for the node list; every API call arrives as a typed proxy)
        from .client import A_CLIENT_EXEC, A_CLIENT_NODES

        t.register_handler(A_CLIENT_NODES, self._s_client_nodes, executor="management")
        t.register_handler(A_CLIENT_EXEC, self._s_client_exec, executor="generic")
        t.register_handler(A_SHUTDOWN_NODE, self._s_shutdown_node,
                           executor="management")
        t.register_handler(A_EVENTS_PUBLISH, self._s_event_publish,
                           executor="management")

    def _s_event_publish(self, request, channel):
        """Gossip ingestion: a peer's watchdog event lands in this node's
        journal, dedup'd by origin seq (common/events.EventJournal.ingest)."""
        journal = getattr(self.node, "events", None)
        stored = journal.ingest(request.get("event") or {}) \
            if journal is not None else False
        return {"stored": stored}

    # ================= node shutdown =================
    def nodes_shutdown(self, node_ids=None, delay_s: float = 0.2) -> dict:
        """ref: TransportNodesShutdownAction — fan a shutdown order to the
        resolved nodes; each closes itself after `delay` (so the ack can make
        it back out first). node_ids: None/_all, _local, _master, or ids/names."""
        state = self.cluster_service.state
        targets = []
        spec = node_ids
        if spec in (None, "", "_all"):
            targets = list(state.nodes.nodes)
        else:
            wanted = [s.strip() for s in str(spec).split(",") if s.strip()]
            for w in wanted:
                if w == "_local":
                    targets.append(state.nodes.get(self.node.local_node.id))
                elif w == "_master":
                    targets.append(state.nodes.master)
                else:
                    targets.extend(n for n in state.nodes.nodes
                                   if n.id == w or n.name == w)
        targets = [t2 for t2 in targets if t2 is not None]
        acked = {}
        for n in targets:
            try:
                self.transport.submit_request(
                    n, A_SHUTDOWN_NODE, {"delay_s": delay_s}, timeout=10.0)
                acked[n.id] = {"name": n.name}
            except SearchEngineError:
                pass  # already gone — shutdown is best-effort, like the reference
        return {"cluster_name": state.cluster_name, "nodes": acked}

    def _s_shutdown_node(self, request, channel):
        delay = float(request.get("delay_s", 0.2))

        def _close():
            time.sleep(delay)
            try:
                self.node.close()
            except Exception:  # noqa: BLE001 — shutdown must not raise upward
                pass

        threading.Thread(target=_close, daemon=True,
                         name=f"estpu-shutdown[{self.node.name}]").start()
        return {"ok": True}

    # ================= transport-client proxy =================
    def _s_client_nodes(self, request, channel):
        state = self.cluster_service.state
        return {"nodes": [[n.id, n.name, n.transport_address]
                          for n in state.nodes.nodes]}

    def _s_client_exec(self, request, channel):
        from .client import CLIENT_PROXY_METHODS

        method = str(request.get("method"))
        if method not in CLIENT_PROXY_METHODS:
            raise ActionNotFoundError(f"client method [{method}] is not proxied")
        fn = getattr(self.node.client(), method)
        return {"r": fn(**(request.get("kwargs") or {}))}

    # ================= master-node pattern =================
    def _master_wrap(self, action, fn):
        def handler(request, channel):
            state = self.cluster_service.state
            if state.nodes.master_id is None:
                raise MasterNotDiscoveredError("no master")
            if state.nodes.master_id != self.node.node_id:
                # forward to master (ref: TransportMasterNodeOperationAction)
                master = state.nodes.master
                return self.transport.submit_request(master.transport_address, action,
                                                     request, timeout=30.0)
            return fn(request, channel)

        return handler

    def _submit(self, source, fn, priority=HIGH, timeout=30.0) -> ClusterState:
        return self.cluster_service.submit_state_update_task(source, fn, priority) \
            .result(timeout)

    def _m_create_index(self, request, channel):
        index = request["index"]
        validate_index_name(index)
        body = request.get("body") or {}

        def update(state: ClusterState) -> ClusterState:
            if state.metadata.has_index(index):
                raise IndexAlreadyExistsError(index)
            settings = {(k if k.startswith("index.") else f"index.{k}"): v
                        for k, v in Settings.from_flat(
                            body.get("settings") or {}).as_dict().items()}
            mappings = dict(body.get("mappings") or {})
            aliases = dict(body.get("aliases") or {})
            # apply matching templates lowest order first (ref: IndexTemplateMetaData)
            for tpl in state.metadata.templates_for(index):
                merged = dict(tpl.settings_map)
                merged.update(Settings.from_flat(settings).as_dict())
                settings = merged
                import json as _json

                for ttype, m in tpl.mappings:
                    mappings.setdefault(ttype, _json.loads(m) if isinstance(m, str) else m)
                for a, spec in tpl.aliases:
                    aliases.setdefault(a, spec)
            flat = {(k if k.startswith("index.") else f"index.{k}"): v
                    for k, v in Settings.from_flat(settings).as_dict().items()}
            flat.setdefault("index.number_of_shards", 5)
            flat.setdefault("index.number_of_replicas", 1)
            flat["index.number_of_shards"] = int(flat["index.number_of_shards"])
            flat["index.number_of_replicas"] = int(flat["index.number_of_replicas"])
            meta = IndexMetaData(
                name=index, settings_map=tuple(sorted(flat.items())),
            )
            for t, m in mappings.items():
                meta = meta.with_mapping(t, m)
            if aliases:
                meta = meta.with_aliases(_normalize_alias_specs(aliases))
            for wname, wbody in (body.get("warmers") or {}).items():
                meta = meta.with_warmer(wname, _normalize_warmer(wbody))
            new = state.next_version(
                metadata=state.metadata.with_index(meta),
                routing_table=state.routing_table.with_index(
                    new_index_routing(index, meta.number_of_shards,
                                      meta.number_of_replicas)),
            )
            return self.allocation.reroute(new)

        self._submit(f"create-index[{index}]", update, priority=URGENT)
        ok = self._wait_for_active_primaries(index, timeout=10.0)
        return {"acknowledged": True, "index": index, "primaries_active": ok}

    def _m_delete_index(self, request, channel):
        indices = self.cluster_service.state.metadata.resolve_indices(request["index"])

        def update(state: ClusterState) -> ClusterState:
            md, rt, blocks = state.metadata, state.routing_table, state.blocks
            for index in indices:
                md = md.without_index(index)
                rt = rt.without_index(index)
                blocks = blocks.without_index(index)
            return state.next_version(metadata=md, routing_table=rt, blocks=blocks)

        self._submit(f"delete-index{indices}", update, priority=URGENT)
        return {"acknowledged": True}

    def _m_open_index(self, request, channel):
        return self._set_index_state(request["index"], "open")

    def _m_close_index(self, request, channel):
        return self._set_index_state(request["index"], "close")

    def _set_index_state(self, index_expr, target):
        indices = self.cluster_service.state.metadata.resolve_indices(index_expr)

        def update(state: ClusterState) -> ClusterState:
            md, rt, blocks = state.metadata, state.routing_table, state.blocks
            from dataclasses import replace as _replace

            for index in indices:
                meta = md.require_index(index)
                md = md.with_index(_replace(meta, state=target, version=meta.version + 1))
                if target == "close":
                    rt = rt.without_index(index)
                    blocks = blocks.with_index_block(index, BLOCK_INDEX_CLOSED)
                else:
                    rt = rt.with_index(new_index_routing(
                        index, meta.number_of_shards, meta.number_of_replicas))
                    blocks = blocks.without_index(index)
            new = state.next_version(metadata=md, routing_table=rt, blocks=blocks)
            return self.allocation.reroute(new)

        self._submit(f"{target}-index{indices}", update, priority=URGENT)
        return {"acknowledged": True}

    def _m_put_mapping(self, request, channel):
        indices = self.cluster_service.state.metadata.resolve_indices(request["index"])
        type_name = request["type"]
        mapping = request["body"].get(type_name, request["body"])

        def update(state: ClusterState) -> ClusterState:
            md = state.metadata
            for index in indices:
                meta = md.require_index(index)
                existing = meta.mapping(type_name) or {}
                # validate merge via a throwaway mapper (conflicts raise)
                from .mapper import MapperService as MS

                svc = MS(meta.settings)
                if existing:
                    svc.put_mapping(type_name, existing)
                svc.put_mapping(type_name, mapping)
                merged_out = svc.mappings_dict()[type_name]
                md = md.with_index(meta.with_mapping(type_name, merged_out))
            return state.next_version(metadata=md)

        self._submit(f"put-mapping[{indices}/{type_name}]", update)
        return {"acknowledged": True}

    def _m_mapping_updated(self, request, channel):
        """Dynamic-mapping propagation from data nodes (ref: MappingUpdatedAction)."""
        return self._m_put_mapping(
            {"index": request["index"], "type": request["type"],
             "body": request["mapping"]}, channel)

    def _m_update_settings(self, request, channel):
        indices = self.cluster_service.state.metadata.resolve_indices(request["index"])
        flat = Settings.from_flat(request["body"].get("settings", request["body"])).as_dict()
        normalized = {}
        for k, v in flat.items():
            normalized[k if k.startswith("index.") else f"index.{k}"] = v

        # index.blocks.* settings install/remove the matching cluster blocks
        # (ref: IndexMetaData block settings → ClusterBlocks)
        block_keys = {"index.blocks.read_only": ("index_read_only", "write"),
                      "index.blocks.read": ("index_read", "read"),
                      "index.blocks.write": ("index_write", "write"),
                      "index.blocks.metadata": ("index_metadata", "metadata")}

        def update(state: ClusterState) -> ClusterState:
            md = state.metadata
            rt = state.routing_table
            blocks = state.blocks
            for index in indices:
                meta = md.require_index(index)
                old_replicas = meta.number_of_replicas
                meta = meta.with_settings(normalized)
                md = md.with_index(meta)
                if meta.number_of_replicas != old_replicas:
                    rt = self._resize_replicas(rt, index, meta.number_of_replicas)
                for key, block in block_keys.items():
                    if key in normalized:
                        on = str(normalized[key]).lower() in ("true", "1")
                        if on:
                            blocks = blocks.with_index_block(index, block)
                        else:
                            blocks = blocks.without_index_block(index, block)
            new = state.next_version(metadata=md, routing_table=rt, blocks=blocks)
            return self.allocation.reroute(new)

        self._submit(f"update-settings{indices}", update)
        return {"acknowledged": True}

    @staticmethod
    def _resize_replicas(rt, index, target):
        from dataclasses import replace as _replace

        from .cluster.state import IndexRoutingTable, IndexShardRoutingTable

        table = rt.index(index)
        groups = []
        for grp in table.shards:
            primary = [s for s in grp.shards if s.primary]
            replicas = [s for s in grp.shards if not s.primary]
            while len(replicas) > target:
                replicas.pop()
            while len(replicas) < target:
                replicas.append(ShardRouting(index, grp.shards[0].shard_id, None, False))
            groups.append(IndexShardRoutingTable(tuple(primary + replicas)))
        return rt.with_index(IndexRoutingTable(index, tuple(groups)))

    def _m_aliases(self, request, channel):
        actions = request["body"].get("actions", [])
        # resolve index expressions up-front so missing indices fail before mutation
        state0 = self.cluster_service.state
        resolved = []
        for entry in actions:
            (op, spec), = entry.items()
            indices = state0.metadata.resolve_indices(
                spec.get("index") or spec.get("indices") or "_all")
            aliases = spec.get("alias") or spec.get("aliases") or []
            if not isinstance(aliases, list):
                aliases = [a.strip() for a in str(aliases).split(",")]
            resolved.append((op, spec, indices, aliases))

        from .common.errors import AliasesMissingError
        from .common.names import name_matches

        # `remove` with wildcards must match something (ref: AliasesMissingException)
        for op, spec, indices, alias_exprs in resolved:
            if op != "remove":
                continue
            found = any(
                name_matches(a, expr)
                for index in indices
                for a, _ in state0.metadata.require_index(index).aliases
                for expr in alias_exprs)
            if not found:
                raise AliasesMissingError(alias_exprs)

        def update(state: ClusterState) -> ClusterState:
            md = state.metadata
            for op, spec, indices, alias_exprs in resolved:
                for index in indices:
                    meta = md.require_index(index)
                    aliases = dict(meta.aliases)
                    if op == "add":
                        for alias in alias_exprs:
                            aliases.update(_normalize_alias_specs({alias: spec}))
                    elif op == "remove":
                        for expr in alias_exprs:
                            for a in [a for a in aliases
                                      if name_matches(a, expr)]:
                                aliases.pop(a)
                    md = md.with_index(meta.with_aliases(aliases))
            return state.next_version(metadata=md)

        self._submit("aliases", update)
        return {"acknowledged": True}

    def _m_delete_mapping(self, request, channel):
        """ref: action/admin/indices/mapping/delete — drop the type's mapping and its
        documents from every resolved index."""
        state0 = self.cluster_service.state
        indices = state0.metadata.resolve_indices(request["index"])
        type_expr = request["type"]
        from .common.names import name_matches

        matched = {
            index: [t for t, _ in state0.metadata.require_index(index).mappings
                    if name_matches(t, type_expr)]
            for index in indices}
        if not any(matched.values()):
            raise TypeMissingError(f"type[[{type_expr}]] missing")

        def update(state: ClusterState) -> ClusterState:
            md = state.metadata
            for index, types in matched.items():
                meta = md.require_index(index)
                for t in types:
                    meta = meta.without_mapping(t)
                md = md.with_index(meta)
            return state.next_version(metadata=md)

        self._submit(f"delete-mapping[{indices}/{type_expr}]", update)
        # purge documents of the removed types locally (primary-owned shards)
        for index, types in matched.items():
            for t in types:
                try:
                    self.delete_by_query(index, {"query": {
                        "filtered": {"query": {"match_all": {}},
                                     "filter": {"type": {"value": t}}}}})
                except SearchEngineError as e:
                    self.logger.warning(
                        "delete-mapping [%s/%s]: mapping removed but doc purge "
                        "failed: %s", index, t, e)
        return {"acknowledged": True}

    def _m_put_template(self, request, channel):
        name = request["name"]
        body = request["body"]

        # template settings are stored flat with the index. prefix, like index settings
        flat_settings = {
            (k if k.startswith("index.") else f"index.{k}"): v
            for k, v in Settings.from_flat(body.get("settings", {})).as_dict().items()}

        def update(state: ClusterState) -> ClusterState:
            tpl = IndexTemplateMetaData(
                name=name, template=body.get("template", "*"),
                order=int(body.get("order", 0)),
                settings_map=tuple(sorted(flat_settings.items())),
                mappings=tuple((t, __import__("json").dumps(m))
                               for t, m in (body.get("mappings") or {}).items()),
                aliases=tuple(sorted((body.get("aliases") or {}).items())),
            )
            return state.next_version(metadata=state.metadata.with_template(tpl))

        self._submit(f"put-template[{name}]", update)
        return {"acknowledged": True}

    def _m_delete_template(self, request, channel):
        name = request["name"]

        def update(state: ClusterState) -> ClusterState:
            return state.next_version(metadata=state.metadata.without_template(name))

        self._submit(f"delete-template[{name}]", update)
        return {"acknowledged": True}

    def _m_put_warmer(self, request, channel):
        """ref: search/warmer/IndexWarmersMetaData + indices/warmer — registered
        searches run against new searchers on refresh before exposure."""
        indices = self.cluster_service.state.metadata.resolve_indices(request["index"])
        name, body = request["name"], _normalize_warmer(request.get("body"))

        def update(state: ClusterState) -> ClusterState:
            md = state.metadata
            for index in indices:
                md = md.with_index(md.require_index(index).with_warmer(name, body))
            return state.next_version(metadata=md)

        self._submit(f"put-warmer[{name}]", update)
        return {"acknowledged": True}

    def _m_delete_warmer(self, request, channel):
        state0 = self.cluster_service.state
        indices = state0.metadata.resolve_indices(request["index"])
        name_expr = request["name"] or "_all"
        from .common.names import name_matches

        matched = {
            index: [w for w, _ in state0.metadata.require_index(index).warmers
                    if name_matches(w, name_expr)]
            for index in indices}
        if not any(matched.values()):
            raise IndexWarmerMissingError(name_expr)

        def update(state: ClusterState) -> ClusterState:
            md = state.metadata
            for index, names in matched.items():
                meta = md.require_index(index)
                for w in names:
                    meta = meta.with_warmer(w, None)
                md = md.with_index(meta)
            return state.next_version(metadata=md)

        self._submit(f"delete-warmer[{name_expr}]", update)
        return {"acknowledged": True}

    def _run_warmers(self, index: str, shard_id: int):
        """After refresh, run registered warm-up searches against the new searcher
        (populates filter caches + device packing before user traffic)."""
        meta = self.cluster_service.state.metadata.index(index)
        if meta is None or not meta.warmers:
            return
        for name, body in meta.warmers_dict().items():
            try:
                ctx = self._shard_ctx(index, shard_id)
                execute_query_phase(ctx, parse_search_body(body.get("source", body)),
                                    shard_id=shard_id)
            except SearchEngineError as e:
                self.logger.debug("warmer [%s] failed on [%s][%d]: %s",
                                  name, index, shard_id, e)

    def _m_cluster_settings(self, request, channel):
        body = request["body"]

        def update(state: ClusterState) -> ClusterState:
            md = state.metadata
            from dataclasses import replace as _replace

            transient = dict(md.transient_settings)
            transient.update(Settings.from_flat(body.get("transient", {})).as_dict())
            persistent = dict(md.persistent_settings)
            persistent.update(Settings.from_flat(body.get("persistent", {})).as_dict())
            md = _replace(md, transient_settings=tuple(sorted(transient.items())),
                          persistent_settings=tuple(sorted(persistent.items())),
                          version=md.version + 1)
            return state.next_version(metadata=md)

        self._submit("cluster-settings", update)
        return {"acknowledged": True,
                "transient": body.get("transient", {}),
                "persistent": body.get("persistent", {})}

    def _m_reroute(self, request, channel):
        commands = (request.get("body") or {}).get("commands", [])

        def update(state: ClusterState) -> ClusterState:
            from dataclasses import replace as _replace

            for entry in commands:
                (cmd, spec), = entry.items()
                index, shard = spec["index"], int(spec["shard"])
                table = state.routing_table.index(index)
                group = table.shard(shard)
                shards = list(group.shards)
                if cmd in ("move",):
                    for i, s in enumerate(shards):
                        if s.node_id == spec["from_node"] and s.active:
                            shards[i] = _replace(s, node_id=spec["to_node"],
                                                 state="INITIALIZING")
                elif cmd in ("cancel",):
                    for i, s in enumerate(shards):
                        if s.node_id == spec.get("node") and not s.primary:
                            shards[i] = _replace(s, node_id=None, state="UNASSIGNED")
                elif cmd in ("allocate", "allocate_replica"):
                    for i, s in enumerate(shards):
                        if not s.assigned and not s.primary:
                            shards[i] = _replace(s, node_id=spec["node"],
                                                 state="INITIALIZING")
                            break
                from .cluster.state import IndexRoutingTable, IndexShardRoutingTable

                groups = list(table.shards)
                groups[shard] = IndexShardRoutingTable(tuple(shards))
                state = state.next_version(routing_table=state.routing_table.with_index(
                    IndexRoutingTable(index, tuple(groups))))
            return self.allocation.reroute(state)

        new_state = self._submit("reroute", update, priority=URGENT)
        return {"acknowledged": True, "state_version": new_state.version}

    def _m_shard_started(self, request, channel):
        shard = ShardRouting.from_dict(request["shard"])

        def update(state: ClusterState) -> ClusterState:
            return self.allocation.apply_started_shards(state, [shard])

        self._submit(f"shard-started[{shard.index}][{shard.shard_id}]", update,
                     priority=URGENT)
        return {"ok": True}

    def _m_shard_failed(self, request, channel):
        shard = ShardRouting.from_dict(request["shard"])

        def update(state: ClusterState) -> ClusterState:
            return self.allocation.apply_failed_shard(state, shard)

        self._submit(f"shard-failed[{shard.index}][{shard.shard_id}]", update,
                     priority=URGENT)
        return {"ok": True}

    def _wait_for_active_primaries(self, index: str, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            table = self.cluster_service.state.routing_table.index(index)
            if table is not None and table.primaries_active():
                return True
            time.sleep(0.02)
        return False

    # ================= replication pattern =================
    def _resolve_index_write(self, index: str) -> str:
        state = self.cluster_service.state
        if not state.metadata.has_index(index):
            # write to an alias targeting exactly one index
            resolved = state.metadata.resolve_indices(index)
            if len(resolved) == 1:
                return resolved[0]
            raise IndexMissingError(index)
        return index

    def _required_routing_check(self, index: str, type_name: str, doc_id: str,
                                routing) -> None:
        """ref: MetaData.resolveIndexRouting — `_routing.required` (and `_parent`
        mappings, whose parent value routes the doc) reject ops without routing."""
        if routing is not None:
            return
        meta = self.cluster_service.state.metadata.index(index)
        if meta is None:
            return
        mapping = meta.mapping(type_name) if type_name and type_name != "_all" else None
        if mapping and (mapping.get("_routing", {}).get("required")
                        or "_parent" in mapping):
            from .common.errors import RoutingMissingError

            raise RoutingMissingError(index, type_name, doc_id)

    def index_doc(self, index: str, type_name: str, doc_id: str | None, source: dict,
                  routing=None, version=None, version_type="internal",
                  op_type="index", refresh=False, consistency="quorum",
                  auto_create=True, parent=None, timestamp=None, ttl=None) -> dict:
        state = self.cluster_service.state
        if not state.metadata.has_index(index) and auto_create:
            try:
                resolved = state.metadata.resolve_indices(index)
                index = resolved[0] if len(resolved) == 1 else index
            except IndexMissingError:
                try:
                    self.transport.submit_request(
                        self.node.local_node, A_CREATE_INDEX,
                        {"index": index, "body": {}}, timeout=30.0)
                except IndexAlreadyExistsError:
                    pass
                state = self.cluster_service.state
        index = self._resolve_index_write(index)
        if doc_id is None:
            doc_id = uuid.uuid4().hex[:20]
        effective_routing = routing if routing is not None else parent
        self._required_routing_check(index, type_name, doc_id, effective_routing)
        req = {"index": index, "type": type_name, "id": doc_id, "source": source,
               "routing": routing, "parent": parent, "timestamp": timestamp,
               "ttl": ttl, "version": version, "version_type": version_type,
               "op_type": op_type, "refresh": refresh, "consistency": consistency}
        return self._route_to_primary(index, doc_id, effective_routing,
                                      A_INDEX_PRIMARY, req)

    def delete_doc(self, index: str, type_name: str, doc_id: str, routing=None,
                   version=None, version_type="internal", refresh=False,
                   parent=None) -> dict:
        index = self._resolve_index_write(index)
        effective_routing = routing if routing is not None else parent
        self._required_routing_check(index, type_name, doc_id, effective_routing)
        req = {"index": index, "type": type_name, "id": doc_id, "routing": routing,
               "version": version, "version_type": version_type, "refresh": refresh}
        return self._route_to_primary(index, doc_id, effective_routing,
                                      A_DELETE_PRIMARY, req)

    def update_doc(self, index: str, type_name: str, doc_id: str, body: dict,
                   routing=None, retry_on_conflict: int = 0, parent=None,
                   refresh=False, fields=None, ttl=None, timestamp=None,
                   version=None, version_type="internal") -> dict:
        """Get-modify-reindex on the coordinator with CAS retry
        (ref: TransportUpdateAction.java:212-270; auto-creates the index like the
        index action does)."""
        if not self.cluster_service.state.metadata.has_index(index):
            try:
                self.cluster_service.state.metadata.resolve_indices(index)
            except IndexMissingError:
                try:
                    self.transport.submit_request(
                        self.node.local_node, A_CREATE_INDEX,
                        {"index": index, "body": {}}, timeout=30.0)
                except IndexAlreadyExistsError:
                    pass
        index = self._resolve_index_write(index)
        effective_routing = routing if routing is not None else parent
        self._required_routing_check(index, type_name, doc_id, effective_routing)
        if isinstance(fields, str):
            fields = [f.strip() for f in fields.split(",")]
        attempts = retry_on_conflict + 1
        last_error = None
        for _ in range(attempts):
            try:
                current = self.get_doc(index, type_name, doc_id,
                                       routing=effective_routing)
                noop = False
                if not current["found"]:
                    # internal CAS against a missing doc is a conflict, not a 404
                    # (ref: update/30_internal_version.yaml)
                    if version is not None and version_type == "internal":
                        raise VersionConflictError(
                            f"{type_name}#{doc_id}", 0, version)
                    if "upsert" in body:
                        source = body["upsert"]
                    elif body.get("doc_as_upsert") and "doc" in body:
                        source = body["doc"]
                    else:
                        raise DocumentMissingError(
                            f"[{index}][{type_name}][{doc_id}] missing")
                    r = self.index_doc(index, type_name, doc_id, source,
                                       routing=routing, parent=parent,
                                       version=version, version_type=version_type,
                                       op_type="create" if version is None else "index",
                                       refresh=refresh, ttl=ttl, timestamp=timestamp)
                else:
                    source = dict(current["_source"])
                    op = "index"
                    if "script" in body:
                        from .script import compile_update_script

                        us = compile_update_script(body["script"],
                                                   body.get("params", {}),
                                                   lang=body.get("lang"))
                        ctx = {"_source": source, "op": "index",
                               "_index": index, "_type": type_name, "_id": doc_id,
                               "_version": current.get("_version"),
                               "_routing": current.get("_routing"),
                               "_parent": current.get("_parent"),
                               "_ttl": ttl, "_timestamp": timestamp}
                        us.run(ctx)
                        source = ctx.get("_source", source)
                        op = ctx.get("op", "index")
                        if ctx.get("_ttl") is not None:
                            ttl = ctx["_ttl"]
                        if ctx.get("_timestamp") is not None:
                            timestamp = ctx["_timestamp"]
                    elif "doc" in body:
                        _deep_merge(source, body["doc"])
                    if op == "delete":
                        r = self.delete_doc(index, type_name, doc_id, routing=routing,
                                            parent=parent, refresh=refresh)
                        r.pop("found", None)
                    elif op == "none":
                        noop = True
                        r = {"_index": index, "_type": type_name, "_id": doc_id,
                             "_version": current["_version"]}
                    else:
                        r = self.index_doc(index, type_name, doc_id, source,
                                           routing=routing, parent=parent,
                                           version=version if version is not None
                                           else current["_version"],
                                           version_type=version_type,
                                           refresh=refresh, ttl=ttl,
                                           timestamp=timestamp)
                out = {"_index": index, "_type": type_name, "_id": doc_id,
                       "_version": r.get("_version", current.get("_version", 1))}
                if fields:
                    # build the get section from the state in hand — no extra
                    # round-trip, and consistent with the _version we report
                    pseudo = {"found": True, "_source": source,
                              "_version": out["_version"]}
                    if effective_routing is not None:
                        pseudo["_routing"] = str(effective_routing)
                    if parent is not None:
                        pseudo["_parent"] = str(parent)
                    fdict, src = _extract_fields(pseudo, fields)
                    get_section = {"found": True}
                    if src is not None:
                        get_section["_source"] = src
                    if fdict:
                        get_section["fields"] = fdict
                    out["get"] = get_section
                if noop:
                    out["noop"] = True
                return out
            except VersionConflictError as e:
                last_error = e
        raise last_error

    def _route_to_primary(self, index: str, doc_id: str, routing, action, req) -> dict:
        state = self.cluster_service.state
        state.blocks.check("write", index)
        deadline = time.monotonic() + 10.0
        while True:
            group = self.routing.index_shard(state, index, doc_id, routing)
            primary = group.primary
            if primary is not None and primary.active:
                node = state.nodes.get(primary.node_id)
                req["shard"] = primary.shard_id
                try:
                    return self.transport.submit_request(node, action, req, timeout=30.0)
                except (NoShardAvailableError, SearchEngineError) as e:
                    if isinstance(e, VersionConflictError) or time.monotonic() > deadline:
                        raise
            if time.monotonic() > deadline:
                raise UnavailableShardsError(
                    f"primary not active for [{index}] doc [{doc_id}]")
            # wait for the next cluster state (ref: retry on cluster state change)
            time.sleep(0.05)
            state = self.cluster_service.state

    def _check_consistency(self, index: str, shard_id: int, consistency: str):
        """ref: write consistency precheck :393-408 — quorum/one/all of the group."""
        state = self.cluster_service.state
        group = state.routing_table.index(index).shard(shard_id)
        size = group.size()
        active = len(group.active_shards())
        if consistency == "one":
            required = 1
        elif consistency == "all":
            required = size
        else:
            required = size // 2 + 1 if size > 2 else 1
        if active < required:
            raise UnavailableShardsError(
                f"not enough active copies for [{index}][{shard_id}]: "
                f"{active} < required {required}")

    def _register_percolator(self, index: str, request: dict, delete: bool = False):
        if request.get("type") != ".percolator":
            return
        svc = getattr(self.node, "percolator", None)
        if svc is None:
            return
        if delete:
            svc.unregister_query(index, request["id"])
        else:
            svc.register_query(index, request["id"], request.get("source") or {})

    def _p_index(self, request, channel):
        index, shard_id = request["index"], request["shard"]
        self._check_consistency(index, shard_id, request.get("consistency", "quorum"))
        self._register_percolator(index, request)
        shard = self.indices.index_service(index).shard(shard_id)
        mapper = shard.engine.mapper_service.mapper_for(request["type"])
        known_before = set(mapper.fields)
        version, created = shard.engine.index(
            request["type"], request["id"], request["source"],
            routing=request.get("routing"), version=request.get("version"),
            version_type=request.get("version_type", "internal"),
            op_type=request.get("op_type", "index"),
            parent=request.get("parent"), timestamp=request.get("timestamp"),
            ttl=request.get("ttl"),
        )
        if set(mapper.fields) - known_before:
            # dynamic mapping grew: propagate to master → cluster state
            # (ref: MappingUpdatedAction via TransportIndexAction.java:278-290)
            try:
                self.transport.submit_request(
                    self.node.local_node, A_MAPPING_UPDATED,
                    {"index": index, "type": request["type"],
                     "mapping": mapper.to_mapping()}, timeout=10.0)
            except SearchEngineError as e:
                self.logger.warning("mapping update propagation failed: %s", e)
        self._replicate(index, shard_id, A_INDEX_REPLICA,
                        {**request, "version": version, "version_type": "external"})
        if request.get("refresh"):
            shard.engine.refresh()
        shard.engine.maybe_flush()
        return {"_index": index, "_type": request["type"], "_id": request["id"],
                "_version": version, "created": created}

    def _r_index(self, request, channel):
        self._register_percolator(request["index"], request)
        shard = self.indices.index_service(request["index"]).shard(request["shard"])
        try:
            shard.engine.index(
                request["type"], request["id"], request["source"],
                routing=request.get("routing"), version=request.get("version"),
                version_type="external",
            )
        except VersionConflictError:
            pass  # replica already has a newer copy
        if request.get("refresh"):
            shard.engine.refresh()
        return {"ok": True}

    def _p_delete(self, request, channel):
        index, shard_id = request["index"], request["shard"]
        self._register_percolator(index, request, delete=True)
        shard = self.indices.index_service(index).shard(shard_id)
        version, found = shard.engine.delete(
            request["type"], request["id"], version=request.get("version"),
            version_type=request.get("version_type", "internal"))
        self._replicate(index, shard_id, A_DELETE_REPLICA, dict(request))
        if request.get("refresh"):
            shard.engine.refresh()
        return {"_index": index, "_type": request["type"], "_id": request["id"],
                "_version": version, "found": found}

    def _r_delete(self, request, channel):
        shard = self.indices.index_service(request["index"]).shard(request["shard"])
        try:
            shard.engine.delete(request["type"], request["id"])
        except (VersionConflictError, SearchEngineError):
            pass
        return {"ok": True}

    REPLICA_OP_TIMEOUT = 30.0

    def _replicate(self, index: str, shard_id: int, action: str, request: dict):
        """Fan the op to every assigned replica concurrently, wait for all acks
        (sync replication default). Transient failures retry through the write
        retry policy (backoff + jitter); on exhaustion the copy is reported
        shard-failed to the master so it gets routed out and resynced — a
        swallowed replica failure is silent divergence until the next recovery
        (ref: :245 fan-out + ShardStateAction on replica error)."""
        state = self.cluster_service.state
        group = state.routing_table.index(index).shard(shard_id)
        futs = []
        for replica in group.replicas():
            if not replica.assigned:
                continue
            node = state.nodes.get(replica.node_id)
            if node is None:
                continue
            futs.append((replica, node,
                         self.transport.send_request(node, action, request)))
        for replica, node, fut in futs:
            try:
                self._await_replica_op(node, action, request, fut)
            except SearchEngineError as e:
                self._report_replica_failed(index, shard_id, replica, e)

    def _await_replica_op(self, node, action: str, request: dict, first_fut=None):
        """Wait for one replica's ack (first attempt may already be in flight
        for fan-out concurrency; retries re-send sequentially with backoff).
        The WHOLE retry sequence shares one REPLICA_OP_TIMEOUT deadline — a
        downed replica costs a synchronous write the same worst-case wait as
        the pre-retry single attempt did, not attempts x timeout."""
        deadline = Deadline.after(self.REPLICA_OP_TIMEOUT)
        pending = [first_fut] if first_fut is not None else []

        def one_attempt():
            # blocking wait — fut_result bounds it, no per-request timer
            budget = deadline.clamp(self.REPLICA_OP_TIMEOUT)
            fut = pending.pop() if pending else \
                self.transport.send_request(node, action, request)
            return fut_result(fut, budget)

        return self.retry_policy.call(one_attempt, deadline=deadline,
                                      describe=f"replica op [{action}]")

    def _report_replica_failed(self, index: str, shard_id: int, replica, error):
        """Mark a replica copy failed on the master (ref: ShardStateAction).
        The report itself retries; if even that exhausts, log at ERROR — the
        one thing this path must never do is stay silent."""
        self.logger.warning("replica [%s][%d] on %s failed: %s — reporting "
                            "shard-failed", index, shard_id, replica.node_id, error)
        try:
            self.retry_policy.call(
                lambda: self.transport.submit_request(
                    self.node.local_node, ACTION_SHARD_FAILED,
                    {"shard": replica.to_dict(), "reason": str(error)},
                    timeout=10.0),
                deadline=Deadline.after(20.0),
                describe="shard-failed report")
        except SearchEngineError as e:
            self.logger.error(
                "could not report shard-failed for [%s][%d] on %s (%s); the "
                "copy may serve stale reads until the next cluster-state "
                "change or recovery", index, shard_id, replica.node_id, e)

    def bulk(self, operations: list[dict], refresh=False) -> dict:
        """Coordinator: group ops per (index, shard) → one A_BULK_SHARD per group
        (ref: TransportShardBulkAction per-shard sub-batches)."""
        t0 = time.monotonic()
        # auto-create any missing target indices first so EVERY op takes the per-shard
        # path (a mixed path would miss the shard-level refresh for some docs)
        state = self.cluster_service.state
        for op in operations:
            (_op_name, meta) = next(iter(op["action"].items()))
            index = meta.get("_index")
            if index and not state.metadata.has_index(index):
                try:
                    self.transport.submit_request(self.node.local_node, A_CREATE_INDEX,
                                                  {"index": index, "body": {}},
                                                  timeout=30.0)
                except IndexAlreadyExistsError:
                    pass
                state = self.cluster_service.state
        prepared = []
        for i, op in enumerate(operations):
            (op_name, meta) = next(iter(op["action"].items()))
            index = meta.get("_index")
            type_name = meta.get("_type", "_default_")
            doc_id = meta.get("_id") or uuid.uuid4().hex[:20]
            routing = meta.get("_routing") or meta.get("routing")
            shard_id = self.routing.shard_id(state, index, doc_id, routing)
            prepared.append((i, (index, shard_id),
                             {"op": op_name, "index": index, "type": type_name,
                              "id": doc_id, "routing": routing,
                              "source": op.get("source"),
                              "version": meta.get("_version"),
                              "body": op.get("source")}))
        by_shard: dict = {}
        for i, key, item in prepared:
            by_shard.setdefault(key, []).append((i, item))
        results: dict[int, dict] = {}
        # all shard sub-batches in flight at once (ref: TransportBulkAction fans
        # TransportShardBulkAction per shard asynchronously)
        bulk_futs = []

        def primary_node(st, index, shard_id):
            group = st.routing_table.index(index).shard(shard_id)
            primary = group.primary
            return st.nodes.get(primary.node_id) \
                if primary and primary.assigned else None

        def dispatch_group(node, index, shard_id, items):
            bulk_futs.append((items, self.transport.send_request(
                node, A_BULK_SHARD,
                {"index": index, "shard": shard_id, "refresh": refresh,
                 "items": [item for _, item in items]})))

        unrouted = []
        for (index, shard_id), items in by_shard.items():
            node = primary_node(state, index, shard_id)
            if node is None:
                unrouted.append(((index, shard_id), items))
                continue
            dispatch_group(node, index, shard_id, items)
        if unrouted:
            # one retry against a FRESH cluster state: an unassigned primary is
            # usually mid-failover, and the next published state names its new
            # home (ref: TransportBulkAction retrying unavailable primaries on
            # cluster-state change)
            time.sleep(0.1)
            state = self.cluster_service.state
            for (index, shard_id), items in unrouted:
                node = primary_node(state, index, shard_id)
                if node is None:
                    for i, item in items:
                        results[i] = {"error": "primary unavailable",
                                      "status": 503, **item}
                else:
                    dispatch_group(node, index, shard_id, items)
        for items, fut in bulk_futs:
            try:
                resp = fut_result(fut, 60.0)
                for (i, _item), r in zip(items, resp["items"]):
                    results[i] = r
            except SearchEngineError as e:
                for i, item in items:
                    results[i] = {"error": str(e), "status": 503}
        items_out = [results[i] for i in range(len(operations))]
        errors = any("error" in r for r in items_out)
        return {"took": int((time.monotonic() - t0) * 1000), "errors": errors,
                "items": [{r.pop("op", "index"): r} for r in items_out]}

    def _p_bulk_shard(self, request, channel):
        index, shard_id = request["index"], request["shard"]
        shard = self.indices.index_service(index).shard(shard_id)
        out = []
        for item in request["items"]:
            op = item.get("op", "index")
            try:
                if op in ("index", "create"):
                    version, created = shard.engine.index(
                        item["type"], item["id"], item.get("source") or {},
                        routing=item.get("routing"), version=item.get("version"),
                        op_type="create" if op == "create" else "index")
                    out.append({"_index": index, "_type": item["type"], "_id": item["id"],
                                "_version": version,
                                "status": 201 if created else 200, "op": op})
                elif op == "delete":
                    version, found = shard.engine.delete(item["type"], item["id"])
                    out.append({"_index": index, "_type": item["type"], "_id": item["id"],
                                "_version": version, "found": found,
                                "status": 200 if found else 404, "op": op})
                elif op == "update":
                    body = item.get("source") or {}
                    r = self.update_doc(index, item["type"], item["id"], body,
                                        routing=item.get("routing"))
                    out.append({**r, "status": 200, "op": op})
                else:
                    out.append({"error": f"unknown bulk op [{op}]", "status": 400, "op": op})
            except SearchEngineError as e:
                out.append({"_index": index, "_type": item.get("type"),
                            "_id": item.get("id"), "error": e.to_dict(),
                            "status": e.status, "op": op})
        # replicas get individual replicated ops (simple + idempotent via
        # versions). Transient failures retry with backoff; when a replica
        # exhausts its retries it is reported shard-failed and the REST of the
        # stream to that copy stops — recovery resyncs the whole copy, and
        # silently skipping ops would leave it diverged from the primary
        state = self.cluster_service.state
        group = state.routing_table.index(index).shard(shard_id)
        for replica in group.replicas():
            if not replica.assigned:
                continue
            node = state.nodes.get(replica.node_id)
            if node is None:
                continue
            for item, r in zip(request["items"], out):
                if "error" in r:
                    continue
                if item.get("op") in ("index", "create", "update"):
                    rep_action, rep_req = A_INDEX_REPLICA, {
                        "index": index, "shard": shard_id, "type": item["type"],
                        "id": item["id"], "source": item.get("source") or {},
                        "routing": item.get("routing"),
                        "version": r.get("_version"), "version_type": "external",
                    }
                elif item.get("op") == "delete":
                    rep_action, rep_req = A_DELETE_REPLICA, {
                        "index": index, "shard": shard_id, "type": item["type"],
                        "id": item["id"],
                    }
                else:
                    continue
                try:
                    self._await_replica_op(node, rep_action, rep_req)
                except SearchEngineError as e:
                    self._report_replica_failed(index, shard_id, replica, e)
                    break
        if request.get("refresh"):
            shard.engine.refresh()
        shard.engine.maybe_flush()
        return {"items": out}

    # ================= single-shard reads =================
    def get_doc(self, index: str, type_name: str, doc_id: str, routing=None,
                realtime=True, refresh=False, preference=None, parent=None) -> dict:
        state = self.cluster_service.state
        state.blocks.check("read", index)
        index = state.metadata.resolve_indices(index)[0]
        effective_routing = routing if routing is not None else parent
        copy = self.routing.get_shard_copy(state, index, doc_id, effective_routing,
                                           preference)
        node = state.nodes.get(copy.node_id)
        return self.transport.submit_request(node, A_GET, {
            "index": index, "shard": copy.shard_id, "type": type_name, "id": doc_id,
            "realtime": realtime, "refresh": refresh}, timeout=10.0)

    def _s_get(self, request, channel):
        shard = self.indices.index_service(request["index"]).shard(request["shard"])
        if request.get("refresh"):
            shard.engine.refresh()
        type_name = request["type"] or "_all"
        if type_name == "_all":
            # resolve the uid across types (ref: _all type get)
            r = None
            for t in list(shard.engine.mapper_service.types()) or []:
                r = shard.engine.get(t, request["id"],
                                     realtime=request.get("realtime", True))
                if r.found:
                    type_name = t
                    break
            if r is None or not r.found:
                return {"_index": request["index"], "_type": request["type"],
                        "_id": request["id"], "found": False}
        else:
            r = shard.engine.get(type_name, request["id"],
                                 realtime=request.get("realtime", True))
        out = {"_index": request["index"], "_type": type_name,
               "_id": request["id"], "found": r.found}
        if r.found:
            out["_version"] = r.version
            out["_source"] = r.source
            if r.routing is not None:
                out["_routing"] = str(r.routing)
            if r.parent is not None:
                out["_parent"] = str(r.parent)
            if r.timestamp is not None:
                out["_timestamp"] = int(r.timestamp)
            if r.ttl is not None:
                out["_ttl"] = int(r.ttl)
        return out

    def term_vector(self, index: str, type_name: str, doc_id: str, routing=None,
                    fields=None, positions=True, offsets=True,
                    term_statistics=False, field_statistics=True,
                    preference=None) -> dict:
        """Term-vectors API (ref: action/termvector/TransportTermVectorAction —
        single-shard read pattern). Vectors are re-derived by re-analyzing the stored
        _source, which is exact for this framework's write-once segments."""
        state = self.cluster_service.state
        state.blocks.check("read", index)
        index = state.metadata.resolve_indices(index)[0]
        copy = self.routing.get_shard_copy(state, index, doc_id, routing, preference)
        node = state.nodes.get(copy.node_id)
        return self.transport.submit_request(node, A_TERMVECTOR, {
            "index": index, "shard": copy.shard_id, "type": type_name, "id": doc_id,
            "fields": list(fields) if fields else None,
            "positions": positions, "offsets": offsets,
            "term_statistics": term_statistics, "field_statistics": field_statistics,
        }, timeout=10.0)

    def multi_termvector(self, docs: list[dict]) -> dict:
        out = []
        for d in docs:
            try:
                out.append(self.term_vector(
                    d["_index"], d.get("_type", "_all"), d["_id"],
                    routing=d.get("routing"), fields=d.get("fields"),
                    positions=d.get("positions", True),
                    offsets=d.get("offsets", True),
                    term_statistics=d.get("term_statistics", False),
                    field_statistics=d.get("field_statistics", True)))
            except SearchEngineError as e:
                out.append({"_index": d.get("_index"), "_id": d.get("_id"),
                            "error": e.to_dict()})
        return {"docs": out}

    def _s_termvector(self, request, channel):
        index, shard_id = request["index"], request["shard"]
        shard = self.indices.index_service(index).shard(shard_id)
        r = shard.engine.get(request["type"], request["id"], realtime=True)
        out = {"_index": index, "_type": request["type"], "_id": request["id"],
               "found": r.found}
        if not r.found:
            return out
        out["_version"] = r.version
        ctx = self._shard_ctx(index, shard_id)
        flat = _flatten_text_fields(r.source)
        wanted = request.get("fields")
        tv = {}
        for field, texts in sorted(flat.items()):
            if wanted is not None and field not in wanted:
                continue
            ft = ctx.field_type(field)
            if ft is not None and getattr(ft, "index", "analyzed") == "no":
                continue
            terms: dict[str, dict] = {}
            for text in texts:
                for tok in ctx.analyze_tokens(field, str(text)):
                    e = terms.setdefault(tok.term, {"term_freq": 0, "tokens": []})
                    e["term_freq"] += 1
                    t = {}
                    if request.get("positions", True):
                        t["position"] = tok.position
                    if request.get("offsets", True):
                        t["start_offset"] = tok.start
                        t["end_offset"] = tok.end
                    if t:
                        e["tokens"].append(t)
            if not terms:
                continue
            if request.get("term_statistics"):
                for term, e in terms.items():
                    e["doc_freq"] = ctx.doc_freq(field, term)
                    e["ttf"] = sum(
                        int(seg.postings(field, term)[1].sum())
                        for seg in ctx.searcher.segments)
            entry = {"terms": terms}
            if request.get("field_statistics", True):
                fs = ctx.field_stats(field)
                entry["field_statistics"] = {
                    "doc_count": fs.doc_count, "sum_ttf": fs.sum_ttf,
                    "sum_doc_freq": fs.sum_dfs}
            tv[field] = entry
        out["term_vectors"] = tv
        return out

    def more_like_this(self, index: str, type_name: str, doc_id: str,
                       mlt_fields=None, search_body=None, routing=None,
                       **mlt_params) -> dict:
        """MLT API (ref: action/mlt/TransportMoreLikeThisAction): GET the doc, build a
        more_like_this query from its field text, exclude the doc itself, search."""
        doc = self.get_doc(index, type_name, doc_id, routing=routing)
        if not doc.get("found"):
            raise DocumentMissingError(f"[{index}][{type_name}][{doc_id}] missing")
        flat = _flatten_text_fields(doc.get("_source") or {})
        if mlt_fields:
            flat = {f: v for f, v in flat.items() if f in set(mlt_fields)}
        like_text = " ".join(str(t) for texts in flat.values() for t in texts)
        mlt = {"fields": sorted(flat) or ["_all"], "like_text": like_text}
        for k in ("min_term_freq", "min_doc_freq", "max_query_terms",
                  "minimum_should_match", "percent_terms_to_match", "boost_terms"):
            if mlt_params.get(k) is not None:
                mlt[k] = mlt_params[k]
        body = dict(search_body or {})
        body["query"] = {"bool": {
            "must": [{"more_like_this": mlt}],
            "must_not": [{"ids": {"type": type_name, "values": [doc_id]}}],
        }}
        return self.search(index, body)

    def multi_get(self, docs: list[dict]) -> dict:
        """ref: TransportMultiGetAction — request-level validation, then per-doc
        gets; a missing index yields found:false for that doc, not an error."""
        from .common.errors import ActionRequestValidationError

        if not docs:
            raise ActionRequestValidationError("Validation Failed: no documents to get")
        for i, d in enumerate(docs):
            if not d.get("_id"):
                raise ActionRequestValidationError(
                    f"Validation Failed: {i + 1}: id is missing")
            if not d.get("_index"):
                raise ActionRequestValidationError(
                    f"Validation Failed: {i + 1}: index is missing")
        out = []
        for d in docs:
            type_name = d.get("_type") or "_all"
            try:
                r = self.get_doc(d["_index"], type_name, str(d["_id"]),
                                 routing=d.get("routing") or d.get("_routing"),
                                 parent=d.get("parent") or d.get("_parent"),
                                 realtime=d.get("realtime", True),
                                 refresh=d.get("refresh", False))
                if d.get("_type") and r.get("_type") != d["_type"]:
                    # requested type doesn't hold this id
                    r = {"_index": d["_index"], "_type": d["_type"],
                         "_id": str(d["_id"]), "found": False}
                fields = d.get("fields") or d.get("_fields")
                src_spec = d.get("_source")
                if r.get("found") and (fields or src_spec is not None):
                    shaped = {k: v for k, v in r.items() if k != "_source"}
                    src = r.get("_source")
                    keep_source = True
                    if fields:
                        fdict, fsrc = _extract_fields(r, fields)
                        if fdict:
                            shaped["fields"] = fdict
                        keep_source = fsrc is not None
                    if src_spec is not None:
                        if src_spec is False or src_spec == "false":
                            keep_source = False
                        elif src_spec is True or src_spec == "true":
                            keep_source = True
                        elif isinstance(src_spec, (str, list)):
                            src = filter_source(src, src_spec, None)
                            keep_source = True
                        elif isinstance(src_spec, dict):
                            src = filter_source(
                                src, src_spec.get("include") or
                                src_spec.get("includes"),
                                src_spec.get("exclude") or src_spec.get("excludes"))
                            keep_source = True
                    if keep_source and src is not None:
                        shaped["_source"] = src
                    r = shaped
                out.append(r)
            except IndexMissingError:
                out.append({"_index": d["_index"], "_type": d.get("_type"),
                            "_id": str(d["_id"]), "found": False})
            except SearchEngineError as e:
                out.append({"_index": d.get("_index"), "_type": d.get("_type"),
                            "_id": str(d.get("_id")), "error": e.to_dict()})
        return {"docs": out}

    # ================= scatter-gather search =================
    def search(self, index_expr, body: dict | None = None, search_type="query_then_fetch",
               routing=None, preference=None, deadline: Deadline | None = None) -> dict:
        """Tracing + latency-histogram wrapper around the scatter-gather body.

        When the calling thread already carries a sampled span (REST ingress
        started the trace), the coordinator span nests under it; a direct
        client call roots a new trace here (subject to the sampling rate).
        Unsampled requests pay one thread-local read + one clock pair."""
        t0 = time.monotonic()
        parent = tracing.current_span()
        tracer = getattr(self.node, "tracer", None)
        if parent is not None:
            span = parent.child("coordinator")
        elif tracer is not None:
            span = tracer.start_trace("coordinator").root
        else:
            span = tracing.NOOP_SPAN
        try:
            with tracing.activate(span):
                return self._search_inner(index_expr, body, search_type,
                                          routing, preference, deadline)
        finally:
            span.end()
            self.search_latency.observe(time.monotonic() - t0)

    def _search_inner(self, index_expr, body: dict | None = None,
                      search_type="query_then_fetch", routing=None,
                      preference=None, deadline: Deadline | None = None) -> dict:
        t0 = time.monotonic()
        state = self.cluster_service.state
        indices = state.metadata.resolve_indices(index_expr)
        for i in indices:
            state.blocks.check("read", i)
        # filtered aliases compose into the query (ref: filtered alias handling)
        alias_filters = {i: state.metadata.alias_filter(i, index_expr) for i in indices}
        # terms LOOKUPS resolve here, once, against the get path — every shard
        # then sees identical literal values (ref: TermsFilterParser lookup)
        body = resolve_terms_lookups(body, self._lookup_get)
        req = parse_search_body(body)
        # ONE deadline for the whole request (REST `?timeout=` / body `timeout`):
        # every per-attempt transport timeout, failover-chain cap, and per-shard
        # segment clamp below derives from its REMAINING budget — k slow hops
        # run down one clock instead of stacking k fresh timeouts
        if deadline is None:
            deadline = Deadline.after(req.timeout_s) if req.timeout_s is not None \
                else NO_DEADLINE
        # admission control: a budget that cannot cover one expected shard
        # phase is rejected up front (429 + Retry-After) — running it would
        # only burn workers on an answer the client already abandoned
        self.admission.admit(deadline)
        # cache-affinity routing: cache-ELIGIBLE requests (the same policy
        # the shard consults — request_cache.cache_policy) carry their
        # fingerprint into copy selection as a soft affinity, so the same
        # hot query rendezvous-lands on the same healthy copy and N replica
        # caches become N× effective capacity instead of N× redundancy.
        # Health still dominates (affinity picks within the spread set);
        # ineligible requests route exactly as before (affinity=None).
        affinity = None
        _rc = getattr(self.node, "request_cache", None)
        if _rc is not None and _rc.enabled and cache_policy(body):
            affinity = request_fingerprint(body)
        shards = self.routing.search_shards(state, indices, routing,
                                            preference, affinity=affinity)
        # a sampled search divides its coordinator span into phases (an
        # unsampled one reads no clock and allocates nothing for them): plan
        # ends here, query runs until the last shard has answered, and the
        # transport round-trips of a phase nest under it
        span = tracing.current_span()
        if span:
            span.record("coordinator.plan", t0, time.monotonic())
        # a search that meets ONE shard makes one trip: that shard's query
        # phase hydrates the page it chose and no fetch phase follows (ref:
        # TransportSearchAction.doExecute turns shardCount == 1 into
        # QUERY_AND_FETCH, "no need to do THEN since we hit one shard"). With
        # more shards the winners are not known until the reduce
        page = [req.from_, req.size] if len(shards) == 1 else None

        with tracing.child_scope(span, "coordinator.query", shards=len(shards)):
            # co-located shards + flat query → one SPMD program over the device mesh
            # (host-summed DFS stats + all_gather top-k on ICI) instead of per-shard RPC scatter-gather;
            # None = ineligible or failed, fall through to the transport path unchanged
            mesh_results = self.mesh_serving.try_search(
                state, self.node.local_node.id, indices, alias_filters, shards, req,
                use_global_stats=search_type in DFS_SEARCH_TYPES,
                deadline=deadline)
            if mesh_results is None:
                results, failures, chain_terminals, shard_meta = \
                    self._query_phase(state, shards, body, alias_filters,
                                      search_type, preference, deadline, page)
        if mesh_results is not None:
            # mesh-served searches never reach _s_query_phase, so the
            # query-shape classification happens HERE instead (one record per
            # search, outcome mesh_spmd, latency from the t0 this method
            # already read) — "classify every search" includes the SPMD path
            insights_reg = getattr(self.node, "insights", None)
            if insights_reg is not None and insights_reg.enabled:
                sid, shape = insights_reg.fingerprint(body)
                obs = _insights.Observation()
                obs.outcome = "mesh_spmd"
                insights_reg.record(sid, shape, time.monotonic() - t0, obs)
            node_local = state.nodes.get(self.node.local_node.id)
            shard_meta = {o: (copy.index, copy.shard_id, node_local,
                              mesh_results[o].context_id)
                          for o, copy in enumerate(shards)}
            return self._finish_search(req, body, mesh_results, [], shards,
                                       shard_meta, t0)
        overload = [e for e in chain_terminals
                    if isinstance(e, (CircuitBreakingError,
                                      RejectedExecutionError))]
        if not results and chain_terminals \
                and len(overload) == len(chain_terminals):
            # EVERY shard's failover chain died on overload protection — this
            # is a load-shed, not a data failure: surface the 429 (with its
            # Retry-After hint) so clients back off instead of retrying hot.
            # Any chain that died on something ELSE keeps the normal partial
            # response with its _shards.failures entries — a permanent data
            # failure must not masquerade as "retry later"
            raise overload[-1]
        # shard-side partials mark timed_out in the reduce (sort_docs); chain
        # exhaustion by deadline must surface it too, even with no results back
        return self._finish_search(req, body, results, failures, shards,
                                   shard_meta, t0, timed_out=deadline.expired())

    def _query_phase(self, state, shards, body, alias_filters, search_type,
                     preference, deadline, page=None):
        """The transport query phase of one search (the DFS fan-out first,
        where the search type asks for it): every shard's chain dispatched at
        once, then collected. `page` ([from, size], one-shard searches only)
        asks the shard for the page's hits beside its partial. A search that
        met one shard whose only copy is on this node builds no chain: its
        query phase runs here, on the calling thread (_inline_node,
        _query_shard_inline). Returns (results, failures, chain_terminals,
        shard_meta); the caller decides between a 429 and a partial answer."""
        dfs_stats = None
        dfs_failed: set[int] = set()  # ordinals excluded from the query phase
        if search_type in DFS_SEARCH_TYPES:
            # concurrent DFS fan-out — the distributed-IDF all-reduce's gather leg
            # (ref: TransportSearchDfsQueryThenFetchAction async per-shard phase).
            # Each shard fails over across its copies like the query phase; a
            # shard with no serving copy becomes a recorded shard FAILURE and is
            # excluded from the query phase — querying it against stats that
            # omit it would silently skew every shard's IDF
            dfs_futs = [(copy, self.transport.send_request(
                state.nodes.get(copy.node_id), A_DFS_PHASE, {
                    "index": copy.index, "shard": copy.shard_id, "body": body or {},
                })) for copy in shards]
            dfs_results = []
            for ordinal, (copy, fut) in enumerate(dfs_futs):
                r = self._dfs_shard_result(state, copy, body, fut, deadline)
                if r is None:
                    dfs_failed.add(ordinal)
                    continue
                dfs_results.append(DfsResult(
                    shard_id=copy.shard_id, max_doc=r["max_doc"],
                    term_df={(f, t): v for f, t, v in r["term_df"]},
                    field_stats={f: _fs_from(l) for f, l in r["field_stats"].items()},
                ))
            agg = aggregate_dfs(dfs_results)
            dfs_stats = {
                "max_doc": agg["max_doc"],
                "term_df": [[f, t, v] for (f, t), v in agg["df"].items()],
                "field_stats": {f: [s.doc_count, s.sum_ttf, s.sum_dfs]
                                for f, s in agg["field_stats"].items()},
            }
        results: list[ShardQueryResult] = []
        failures = []
        # terminal error of each FAILED chain (None = not overload-shaped,
        # e.g. a DFS phase dead on every copy) — decides 429 vs 200-partial
        chain_terminals: list = []
        # merge identity is a coordinator-assigned ordinal — (index, shard) pairs from
        # different indices may share a shard id (ref: the per-request shard index in
        # TransportSearchTypeAction), so results carry the ordinal as shard_id
        shard_meta: dict[int, tuple] = {}  # ordinal -> (index, real_shard_id, node, ctx_id)
        # concurrent query-phase fan-out: every shard's first phase is dispatched at
        # once and failover chains advance via future callbacks, so N-shard latency is
        # max(shard) not sum(shard) and no coordinator thread parks per shard
        # (ref: TransportSearchTypeAction.java:135-216 async performFirstPhase)
        t_fanout = time.monotonic()
        # hard copy pins disable HEDGING (a speculative answer from a node
        # the caller explicitly pinned away from violates the preference's
        # contract even on success); failover-on-failure keeps its
        # pre-existing cross-copy semantics. Soft preferences (_prefer_node,
        # _local, session keys) keep hedging — they name a starting point,
        # not an exclusivity constraint. The pin comes from the SAME parser
        # search_shards uses ("_shards:N;<pref>" carries the copy preference
        # after the ";" — testing the raw string would miss a compound
        # "_shards:0;_only_node:x" pin entirely).
        _, pin = self.routing.split_preference(preference)
        pin = pin or ""
        allow_hedge = not pin.startswith("_only_node:") and pin != "_primary"
        inline_node = self._inline_node(state, shards, search_type, page)
        if inline_node is not None:
            query_futs = [self._query_shard_inline(
                shards[0], inline_node, body, alias_filters, deadline, page)]
        else:
            query_futs = [
                None if ordinal in dfs_failed else
                self._query_shard_async(state, copy, body, alias_filters,
                                        dfs_stats, deadline,
                                        allow_hedge=allow_hedge, page=page)
                for ordinal, copy in enumerate(shards)]
        # shared backstop: chains resolve themselves (every attempt is
        # timer-bounded), so this only catches a wedged chain — scaled to the
        # longest possible failover chain, and clamped by the request deadline
        # (plus grace for in-flight partials to land) when one is set.
        max_chain = max((getattr(f, "max_attempts", 1) for f in query_futs
                         if f is not None), default=1)
        backstop = deadline.clamp(
            (self.QUERY_ATTEMPT_TIMEOUT + self.QUERY_PROGRESS_TIMEOUT)
            * (max(1, max_chain) + self.QUERY_ATTEMPT_EXTENSIONS))
        collect_by = time.monotonic() + backstop + 5.0
        span = tracing.current_span()  # sampled: the wake-up after the answers
        answered = None  # where the last round-trip waited for ended
        for ordinal, (copy, fut) in enumerate(zip(shards, query_futs)):
            if fut is None:
                failures.append({"index": copy.index, "shard": copy.shard_id,
                                 "reason": "dfs phase failed on every copy"})
                chain_terminals.append(None)  # a data failure, never overload
                continue
            try:
                r, used, err = fut.result(
                    timeout=max(0.0, collect_by - time.monotonic()))
            except (TimeoutError, FutureTimeoutError):
                r, used, err = None, None, TransportError("query phase timed out")
                cancel = getattr(fut, "cancel_chain", None)
                if cancel is not None:
                    cancel()  # abandoned chain must not keep scheduling attempts
            if span:
                answered = tracing.later(answered,
                                         getattr(fut, "answered_at", None))
            if r is not None:
                shard_meta[ordinal] = (copy.index, r.shard_id, used, r.context_id)
                r.shard_id = ordinal
                results.append(r)
                # feed admission control: coordinator-observed shard-phase
                # latency, fan-out → future RESOLUTION (stamped by the chain;
                # falls back to now inside the callback race window) — the
                # decaying signal the next request's budget is compared against
                self.admission.observe(
                    getattr(fut, "completed_at", time.monotonic()) - t_fanout)
            else:
                # one failure entry per attempted copy (ref: ShardSearchFailure
                # carries the shard target) — chains record each downed copy.
                # The terminal error is appended too unless it IS the last
                # recorded attempt error: a backstop/budget cutoff with an
                # attempt still in flight must not vanish from the response
                per_copy = list(getattr(fut, "attempt_errors", None) or [])
                if err is not None and \
                        (not per_copy or per_copy[-1][1] is not err):
                    per_copy.append((copy.node_id, err))
                for node_id, copy_err in per_copy:
                    failures.append({"index": copy.index, "shard": copy.shard_id,
                                     "node": node_id, "reason": str(copy_err)})
                terminal = err if err is not None \
                    else per_copy[-1][1] if per_copy else None
                chain_terminals.append(terminal)
                # failed chains feed admission too — a degrading node whose
                # phases all time out must RAISE the latency signal, not
                # starve it (successes-only would freeze it at the healthy
                # value). Overload rejections are excluded: they resolve
                # near-instantly and would drag the signal DOWN mid-overload
                if not isinstance(terminal, (CircuitBreakingError,
                                             RejectedExecutionError)):
                    self.admission.observe(
                        getattr(fut, "completed_at", time.monotonic())
                        - t_fanout)
        tracing.record_wake(span, answered, "transport")
        return results, failures, chain_terminals, shard_meta

    def _inline_node(self, state, shards, search_type, page):
        """The local node, where this search's query phase can run on the
        calling thread with nothing lost: the search met ONE shard (`page`,
        PR 38's predicate), no DFS round goes before the query, the chosen
        copy is on this node, no other copy on a live node could take a
        failover or a hedge, and no fault rule could match the message the
        call stands for. Else None, and the chain runs. All of it is read off
        this search's own input (ref: TransportSearchAction's shardCount == 1
        and TransportService's localNode short-circuit); there is no setting."""
        if page is None or search_type in DFS_SEARCH_TYPES:
            return None
        copy = shards[0]
        node = state.nodes.get(copy.node_id)
        if node is None or \
                not self.transport.runs_locally(node, A_QUERY_PHASE):
            return None
        group = state.routing_table.index(copy.index).shard(copy.shard_id)
        if any(state.nodes.get(other.node_id) is not None
               for other in self.routing.other_copies(group, copy)):
            return None
        return node

    def _query_shard_inline(self, copy: ShardRouting, node, body,
                            alias_filters, deadline: Deadline, page) -> Future:
        """The query phase of a search whose one shard has its only copy on
        this node (_inline_node), run on the calling thread inside one of the
        `search` pool's slots (TransportService.call_local): no chain, no
        timer, no message to oneself, no thread to hand over to and wake up
        after. Resolves, before it returns, what _query_shard_async's future
        resolves to, with the same `attempt_errors` and `completed_at`, so
        the collection loop reads either alike; the selector, the hedge
        budget and admission control are fed as the chain's one attempt would
        feed them. An exception is the chain's terminal error: a `failures`
        entry of a partial answer, or the 429 where it is the pool's
        rejection or a breaker's.

        There is no attempt timer here to fail a late copy over: there is no
        other copy. What bounds the wait is what bounds the `search` pool's
        thread under the chain: the shard's deadline, and the batcher's own
        guard (the remaining budget plus 30 s, search/batcher.py `_submit`)."""
        done: Future = Future()
        attempt_errors: list = []
        done.attempt_errors = attempt_errors  # type: ignore[attr-defined]
        payload = self._query_payload(copy, body, alias_filters, None,
                                      deadline, page)
        span = tracing.current_span()
        if span:
            # the shard continues the trace from the wire context, as one on
            # another node does: its `shard` span is a child of this one
            payload[tracing.TRACE_WIRE_KEY] = tracing.wire_context(span)
        selector = self.routing.selector
        if selector is not None:
            selector.begin_attempt(copy)
            selector.hedges.note_request()  # accrue hedge budget
        t_sent = time.monotonic()
        try:
            r = self.transport.call_local(A_QUERY_PHASE, payload)
            if selector is not None:
                selector.observe(copy, time.monotonic() - t_sent,
                                 load=r.get("load"))
            if span:
                span.trace.add_remote(r.get("spans"))
            outcome = (self._shard_result(r, copy, hedge=False), node, None)
        except Exception as e:  # noqa: BLE001 — the one attempt's failure,
            # whatever it is, is the shard's failure (_query_shard_async's
            # on_done: any error fails the attempt, the last one the chain)
            if selector is not None:
                selector.failure(copy)
            attempt_errors.append((copy.node_id, e))
            outcome = (None, None, e)
        finally:
            if selector is not None:
                selector.end_attempt(copy)
        if not isinstance(outcome[2], (CircuitBreakingError,
                                       RejectedExecutionError)):
            # a search shed with a 429 is booked under no trip either
            # (_finish_search never sees it). No lock, as there
            self.search_phases["inline_query"] += 1
        done.completed_at = time.monotonic()  # type: ignore[attr-defined]
        done.set_result(outcome)
        return done

    def _finish_search(self, req, body, results, failures, shards, shard_meta, t0,
                       timed_out: bool = False):
        """Reduce + fetch + response assembly, shared by the transport scatter-gather
        and the mesh SPMD query phase (both deliver per-ordinal ShardQueryResults).
        A search that met one shard got its page's hits with the partial (one
        trip: _s_query_phase) and no fetch phase follows.
        The fetch phase deliberately ignores the request deadline: winners are
        already chosen, and hydrating them is what makes a timed-out response a
        PARTIAL answer instead of an empty one (ref: the reference's fetch runs
        after TimeLimitingCollector fires too). `timed_out` ORs in coordinator-
        level budget expiry; shard-level partials are folded in by sort_docs."""
        span = tracing.current_span()  # sampled: reduce / fetch / render
        t_reduce = time.monotonic() if span else 0.0
        merged = sort_docs(req, results)
        merged.timed_out = merged.timed_out or timed_out
        # a search that met one shard brings its page with it (the shard chose
        # it as sort_docs does: controller.shard_page); a chain that failed
        # brings nothing and there is nothing to fetch either
        one_trip = len(shards) == 1 and \
            (not results or results[0].hits is not None)
        # booked without a lock, as SERVING_COUNTERS are: exact enough for a share
        self.search_phases["one_trip" if one_trip else "two_trip"] += 1
        by_shard: dict = {}
        fetch_failed = 0
        if not one_trip:
            # fetch phase: winners only, grouped per shard, all shards in flight
            # at once (ref: TransportSearchQueryThenFetchAction.java:93-147)
            page = merged.hits[req.from_: req.from_ + req.size]
            for rank, (score, ordinal, doc, sort_values) in enumerate(page):
                by_shard.setdefault(ordinal, []).append(
                    (rank, score, doc, sort_values))
        with tracing.child_scope(span, "coordinator.fetch",
                                 shards=len(by_shard)) as fetch_span:
            if one_trip:
                hits = results[0].hits if results else []
            else:
                fetched, fetch_failed = self._fetch_phase(
                    body, by_shard, shard_meta, failures)
                hits = [fetched[r] for r in sorted(fetched)]
        response = merge_responses(req, merged, results, hits,
                                   took_ms=int((time.monotonic() - t0) * 1000),
                                   total_shards=len(shards),
                                   successful=len(results) - fetch_failed,
                                   failures=failures)
        if span:
            span.record("coordinator.reduce", t_reduce, fetch_span.t0)
            span.record("coordinator.render", fetch_span.t1, time.monotonic())
        return response

    def _fetch_phase(self, body, by_shard, shard_meta, failures):
        """Hydrate the page's winners: one fetch per contributing shard, all in
        flight at once; a shard lost between the phases drops ITS hits and is
        appended to `failures`. Returns ({rank: hit}, shards that failed)."""
        fetched: dict[int, dict] = {}
        fetch_failed = 0
        fetch_futs = []
        span = tracing.current_span()  # sampled: coordinator.fetch
        answered = None  # where the last round-trip waited for ended
        for ordinal, entries in by_shard.items():
            index_name, real_shard, node, ctx_id = shard_meta[ordinal]
            fetch_futs.append(((ordinal, entries), self.transport.send_request(
                node, A_FETCH_PHASE, {
                    "index": index_name, "shard": real_shard, "body": body or {},
                    "ctx": ctx_id,
                    "docs": [[score, doc, sort_values]
                             for (_rank, score, doc, sort_values) in entries],
                })))
        for (ordinal, entries), fut in fetch_futs:
            try:
                r = fut_result(fut, 30.0)
            except Exception as e:  # noqa: BLE001 — ANY per-shard fetch failure
                # (remote errors arrive typed over TCP but raw over the local
                # transport): a shard lost between phases drops ITS hits and
                # records a failure; the rest of the page still returns (ref:
                # fetch-phase onFailure collects ShardFetchFailures)
                index_name, real_shard, _node, _cid = shard_meta[ordinal]
                failures.append({"index": index_name, "shard": real_shard,
                                 "reason": f"fetch phase failed: {e}"})
                fetch_failed += 1
                continue
            if span:
                # the shard's `shard.fetch` rides its response, as the query
                # phase's span list does
                span.trace.add_remote(r.get("spans"))
                answered = tracing.later(answered, tracing.round_trip_end(fut))
            for (rank, *_), hit in zip(entries, r["hits"]):
                fetched[rank] = hit
        tracing.record_wake(span, answered, "transport")
        # release pinned contexts of shards that contributed no fetched hits
        # (fire-and-forget, like the reference's free-context after the merge)
        for ordinal, meta in shard_meta.items():
            index_name, real_shard, node, ctx_id = meta
            if ctx_id is not None and ordinal not in by_shard:
                with contextlib.suppress(Exception):
                    self.transport.send_request(node, A_FREE_CONTEXT, {
                        "index": index_name, "shard": real_shard, "ctx": ctx_id})
        return fetched, fetch_failed

    @staticmethod
    def _shard_index(shards, shard_id):
        for s in shards:
            if s.shard_id == shard_id:
                return s.index
        return None

    # The failover timer for a WEDGED copy (a merely slow one is the hedge's
    # job — adaptive routing). A cold device is neither: it compiles every
    # (segment size, bucket) program a request meets ON THE QUERY PATH, up to
    # ~18 s each on a v5e, and the first search over an 11-segment index took
    # 88.5 s there (PR 22) — this timer alone failed that healthy shard at 60 s.
    # So when it runs out the coordinator asks the copy's node
    # (A_QUERY_PROGRESS) and waits another window while the node's last
    # compile, or its last segment pack (a merged segment's device concat runs
    # for a minute and more at a quarter of a million documents, and the first
    # search waits for it), is younger than one: at most QUERY_ATTEMPT_EXTENSIONS more per
    # chain, 600 s in all, the timeout a client should give a cold server. A
    # copy whose node compiles nothing, or does not answer within
    # QUERY_PROGRESS_TIMEOUT, fails over after one window as before.
    QUERY_ATTEMPT_TIMEOUT = 60.0
    QUERY_ATTEMPT_EXTENSIONS = 9
    QUERY_PROGRESS_TIMEOUT = 5.0

    def _dfs_shard_result(self, state, copy: ShardRouting, body, first_fut,
                          deadline: Deadline = NO_DEADLINE):
        """DFS phase for one shard group with failover across its copies (the
        first attempt is already in flight for fan-out concurrency; failover
        attempts are sequential — rare). Returns the stats dict, or None when no
        copy on a live node serves it. Per-attempt waits and the failover chain
        are bounded by the request deadline's remaining budget."""
        group = state.routing_table.index(copy.index).shard(copy.shard_id)
        candidates = [copy] + [s for s in group.active_shards()
                               if s.node_id != copy.node_id]
        fut = first_fut
        for cand in candidates:
            if fut is None:
                if deadline.expired():
                    return None  # no budget left for another copy
                node = state.nodes.get(cand.node_id)
                if node is None:
                    continue
                fut = self.transport.send_request(node, A_DFS_PHASE, {
                    "index": cand.index, "shard": cand.shard_id,
                    "body": body or {}})
            try:
                return fut_result(fut, deadline.clamp(30.0))
            except SearchEngineError:  # TransportError subclasses it
                fut = None  # next copy
        return None

    def _query_shard_async(self, state, copy: ShardRouting, body, alias_filters,
                           dfs_stats, deadline: Deadline = NO_DEADLINE,
                           allow_hedge: bool = True, page=None) -> Future:
        """Per-shard query phase with rank-ordered failover and hedged
        attempts, driven entirely by future callbacks — the coordinator parks
        no thread per shard (ref: performFirstPhase + onFirstPhaseResult
        failover, TransportSearchTypeAction.java:135-216,292).

        Failover: candidates are `routing.ranked_copies` — the chosen copy
        first, then the remaining active copies best-first by the adaptive
        health rank (cluster/stats.py), so the first fallback is the best
        REMAINING copy. Each attempt's timeout is the flat attempt budget
        clamped to the request deadline's REMAINING budget, and the chain
        gives up once the deadline expires.

        Hedging (The Tail at Scale): when a primary attempt outlives its
        copy's own p99 (hedge_delay_s — warm copies only, clamped by the
        remaining budget) and the token-bucket HedgeBudget grants a token,
        the next-ranked unattempted copy is dispatched speculatively; the
        FIRST successful response resolves the chain (complete-once via
        complete_fut) and the loser's response is discarded by the existing
        late-response path. `allow_hedge=False` (hard copy pins:
        _only_node/_primary) suppresses hedging entirely — a speculative
        answer from an un-pinned copy would violate the preference even on
        success. Hedges ride the normal transport send — the
        in-flight breaker charges them and the remote search pool's bounded
        queue can 429 them, so overload protection governs hedges exactly
        like primaries. Every attempt feeds the health tracker: latency +
        piggybacked load on success (even when it lost the race), a decayed
        failure count on error/timeout.

        Resolves to (ShardQueryResult | None, node | None, error | None);
        every failed attempt is recorded on the returned future's
        `attempt_errors` as (node_id, error).

        Not built at all where there is nothing for it to do: a search that
        met one shard whose only copy is on the coordinator's own node, with
        no DFS round and no fault rule in the way (_inline_node), runs its
        query phase on the calling thread (_query_shard_inline) and resolves
        the same triple."""
        done: Future = Future()
        # stamp resolution time for admission-control latency: the collection
        # loop drains futures in ordinal order, so "time until collected" of a
        # fast shard parked behind a slow chain would overstate its phase by
        # the whole wait (first callback → runs at resolution)
        done.add_done_callback(
            lambda f: setattr(f, "completed_at", time.monotonic()))
        # sampled trace of the calling coordinator (None when untraced): shard
        # responses carry their span lists back inline; stitching them here —
        # not in the collection loop — keeps the spans even for chains the
        # backstop later abandons
        cur_span = tracing.current_span()
        trace_ref = cur_span.trace if cur_span else None
        group = state.routing_table.index(copy.index).shard(copy.shard_id)
        # ONE wiring point: the same selector that ranks the failover chain
        # receives the observations and issues the hedge budget — reading it
        # from a second place (a node attribute) could leave an embedding
        # half-wired with no error
        selector = self.routing.selector
        candidates = self.routing.ranked_copies(group, copy)
        # the coordinator's backstop may abandon this chain; once it does, stop
        # scheduling further attempts (they'd leak requests + timers)
        cancelled = threading.Event()
        done.cancel_chain = cancelled.set  # type: ignore[attr-defined]
        done.max_attempts = len(candidates)  # type: ignore[attr-defined]
        attempt_errors: list = []
        done.attempt_errors = attempt_errors  # type: ignore[attr-defined]
        # chain state: which candidate indices have been attempted (hedges
        # included — a failover never double-sends to a copy a hedge already
        # covers) and how many attempts are in flight. The chain fails only
        # when every candidate is consumed AND nothing is in flight.
        chain_lock = threading.Lock()
        launched: set[int] = set()
        in_flight = [0]
        extensions_left = [self.QUERY_ATTEMPT_EXTENSIONS]  # the chain's, not an attempt's

        def resolve(result, node, err) -> bool:
            return complete_fut(done, (result, node, err))

        def attempt_failed(candidate, err, hedge: bool):
            with chain_lock:
                in_flight[0] -= 1
                alive = in_flight[0]
                attempt_errors.append((candidate.node_id, err))
                # attempts actually SENT (launched also counts dead-node
                # candidates the claim loop consumed without a send)
                attempts = len(attempt_errors)
            if cancelled.is_set() or done.done():
                return
            if deadline.expired():
                # budget exhausted mid-chain: trying another copy could only
                # answer after the caller stopped caring. But an attempt
                # STILL in flight keeps the chain open — its timer is
                # deadline-clamped, and a late success is exactly the partial
                # the coordinator's collection grace window exists to accept
                if alive == 0:
                    resolve(None, None, ReceiveTimeoutError(
                        f"search budget exhausted after {attempts} "
                        f"attempt(s) on [{copy.index}][{copy.shard_id}]: "
                        f"{err}"))
                return
            if hedge and alive > 0:
                return  # a dead hedge never advances the chain while the
                # primary attempt it shadowed is still in flight
            try_next(err)

        def try_next(last_err, hedge: bool = False) -> bool:
            """Claim + launch the best not-yet-attempted copy on a live node.
            False = no candidate left (the chain resolves its terminal error
            iff nothing is in flight either)."""
            with chain_lock:
                j = None
                for i in range(len(candidates)):
                    if i in launched:
                        continue
                    if state.nodes.get(candidates[i].node_id) is None:
                        launched.add(i)  # dead node: consumed, never retried
                        continue
                    j = i
                    launched.add(j)
                    in_flight[0] += 1
                    break
                alive = in_flight[0]
            if j is None:
                if alive == 0:
                    resolve(None, None, last_err or NoShardAvailableError(
                        f"no active copy of [{copy.index}][{copy.shard_id}] "
                        f"on a live node"))
                return False
            launch(j, hedge)
            return True

        def launch(j: int, hedge: bool):
            candidate = candidates[j]
            # liveness was checked by try_next's claim loop against the SAME
            # immutable ClusterState snapshot — node cannot be None here
            node = state.nodes.get(candidate.node_id)
            payload = self._query_payload(candidate, body, alias_filters,
                                          dfs_stats, deadline, page)
            if hedge:
                # the shard tags its span hedge:true from this (sibling shard
                # spans in ?trace=true); the winner annotation on the profile
                # happens coordinator-side (_shard_result)
                payload["hedge"] = True
            # re-activate the coordinator's span around the send: retry
            # attempts run on timer / transport-callback threads whose
            # thread-local is empty, and an un-activated send would strip the
            # trace context from exactly the failover attempts most worth
            # tracing (the transport injects context from current_span())
            with tracing.activate(cur_span):
                fut = self.transport.send_request(node, A_QUERY_PHASE, payload)
            t_sent = time.monotonic()
            if selector is not None:
                selector.begin_attempt(candidate)
                if hedge:
                    selector.hedges.record_issued()
                else:
                    selector.hedges.note_request()  # accrue hedge budget
            # exactly one of {response callback, attempt timer} consumes the
            # attempt for CHAIN purposes; `settled` separately guarantees the
            # selector's outstanding count drops exactly once
            consumed_lock = threading.Lock()
            consumed = [False]
            settled = [False]

            def consume() -> bool:
                with consumed_lock:
                    if consumed[0]:
                        return False
                    consumed[0] = True
                    return True

            def settle() -> bool:
                with consumed_lock:
                    if settled[0]:
                        return False
                    settled[0] = True
                    return True

            def fail_over():
                if selector is not None and settle():
                    selector.end_attempt(candidate)
                    selector.failure(candidate)
                if consume():
                    err = ReceiveTimeoutError(
                        f"query phase attempt to [{candidate.node_id}] timed out")
                    attempt_failed(candidate, err, hedge)

            def on_timeout():
                # a node that is compiling is busy, not wedged: ask it before
                # failing its copy over (QUERY_ATTEMPT_TIMEOUT's comment)
                with chain_lock:
                    ask = extensions_left[0] > 0 and not deadline.expired() \
                        and not cancelled.is_set() and not done.done()
                    if ask:
                        extensions_left[0] -= 1
                if not ask:
                    fail_over()
                    return
                self.transport.send_request(
                    node, A_QUERY_PROGRESS, {},
                    timeout=self.QUERY_PROGRESS_TIMEOUT,
                ).add_done_callback(on_progress)

            def on_progress(f):
                busy = {} if f.exception() is not None else (f.result() or {})
                idles = [v for v in (busy.get("compile_idle_s"),
                                     busy.get("pack_idle_s")) if v is not None]
                idle = min(idles) if idles else None
                if idle is None or idle >= self.QUERY_ATTEMPT_TIMEOUT \
                        or not arm_timer():
                    fail_over()
                    return
                self.logger.info(
                    "query phase attempt to [%s] for [%s][%d] is late and its "
                    "node compiled or packed %.1f s ago: waiting another window",
                    candidate.node_id, candidate.index, candidate.shard_id, idle)

            timer = [None]

            def arm_timer() -> bool:
                """False once the response consumed the attempt: no timer
                outlives it (on_done cancels the one armed last)."""
                with consumed_lock:
                    if consumed[0]:
                        return False
                    timer[0] = self.node.threadpool.schedule(
                        deadline.clamp(self.QUERY_ATTEMPT_TIMEOUT), "generic",
                        on_timeout)
                    return True

            arm_timer()

            if allow_hedge and not hedge and selector is not None:
                with chain_lock:
                    alts = [candidates[i] for i in range(len(candidates))
                            if i not in launched]
                hd = selector.hedge_delay_s(candidate, deadline.remaining(),
                                            others=alts)
                if hd is not None:
                    def on_hedge():
                        if cancelled.is_set() or done.done():
                            return
                        with consumed_lock:
                            if consumed[0]:
                                return  # attempt already failed over: the
                                # chain is advancing anyway, no hedge needed
                        with chain_lock:
                            has_next = any(
                                i not in launched and
                                state.nodes.get(candidates[i].node_id)
                                is not None
                                for i in range(len(candidates)))
                        if not has_next:
                            return  # nothing to hedge to
                        if not selector.hedges.try_acquire():
                            return  # budget exhausted (counted) — brown-out
                            # protection: never amplify load on a sick group
                        if not try_next(None, hedge=True):
                            # lost the claim race (concurrent failover took
                            # the last candidate / its node left): the token
                            # bought nothing — put it back
                            selector.hedges.refund()

                    hedge_timer = self.node.threadpool.schedule(
                        hd, "generic", on_hedge)
                    done.add_done_callback(lambda _f: hedge_timer.cancel())

            def on_done(f):
                err0 = f.exception()
                lat = time.monotonic() - t_sent
                if selector is not None and settle():
                    selector.end_attempt(candidate)
                if not consume():
                    # the timer already failed this attempt over; a late
                    # response still teaches the health tracker — the copy's
                    # TRUE latency is exactly what routing must learn
                    if selector is not None and err0 is None:
                        r0 = f.result()
                        selector.observe(candidate, lat,
                                         load=r0.get("load")
                                         if isinstance(r0, dict) else None)
                    return
                timer[0].cancel()
                if err0 is not None:
                    # ANY per-attempt failure fails over to the next copy —
                    # including transport errors to a node that died after
                    # this state was read (ref: onFirstPhaseResult treats
                    # every shard exception as failover, :292); terminal
                    # only when the chain runs out of candidates
                    if selector is not None:
                        selector.failure(candidate)
                    attempt_failed(candidate, err0, hedge)
                    return
                try:
                    r = f.result()
                    if selector is not None:
                        selector.observe(candidate, lat,
                                         load=r.get("load")
                                         if isinstance(r, dict) else None)
                    if trace_ref is not None and isinstance(r, dict):
                        trace_ref.add_remote(r.get("spans"))
                        # where this round-trip ended, for the coordinator's
                        # wake-up (written before `done` resolves)
                        done.answered_at = tracing.round_trip_end(f)  # type: ignore[attr-defined]
                    result = self._shard_result(r, candidate, hedge)
                except Exception as e:  # noqa: BLE001 — a malformed/corrupt
                    # response is an attempt failure like any other: fail
                    # over instead of terminally resolving (which would
                    # discard a concurrently in-flight sibling's good answer)
                    attempt_failed(candidate, e, hedge)
                    return
                # resolve BEFORE dropping the in-flight count: decrement-
                # first opens a window where the last OTHER attempt's
                # concurrent failure reads alive==0 and resolves the chain
                # with its terminal error, discarding this good response
                won = resolve(result, node, None)
                with chain_lock:
                    in_flight[0] -= 1
                if won and hedge and selector is not None:
                    selector.hedges.record_won()

            fut.add_done_callback(on_done)

        try_next(None)
        return done

    @staticmethod
    def _query_payload(copy: ShardRouting, body, alias_filters, dfs_stats,
                       deadline: Deadline, page) -> dict:
        """What one attempt of a shard's query phase asks of `copy`: the ONE
        construction site, for every attempt of a chain and for the call that
        builds none (_query_shard_inline)."""
        payload = {
            "index": copy.index, "shard": copy.shard_id,
            "body": body or {},
            "alias_filter": alias_filters.get(copy.index),
            "dfs": dfs_stats,
            # remaining budget as a DURATION (monotonic clocks don't
            # cross processes); the shard restarts its own clock from it
            "deadline_s": deadline.remaining(),
        }
        if page is not None:
            # one trip: every attempt of the chain, failover and hedge
            # alike, asks its copy for the page's hits too
            payload["fetch"] = page
        return payload

    @staticmethod
    def _shard_result(r: dict, copy: ShardRouting,
                      hedge: bool) -> ShardQueryResult:
        """The shard's answer (_s_query_phase's dict, over the wire or as the
        handler returned it) as the reduce reads it: the ONE construction
        site. Raises on a malformed answer."""
        prof = r.get("profile")
        if isinstance(prof, dict):
            # ?profile=true: record whether this shard's profile
            # came from the winning primary attempt or a hedge
            prof = {**prof, "winner": "hedge" if hedge else "primary"}
        result = ShardQueryResult(
            total=r["total"],
            docs=[tuple(d) for d in r["docs"]],
            max_score=r["max_score"] if r["max_score"] is not None else float("nan"),
            agg_partials=_decode_partials(r.get("agg_partials")),
            facet_partials=_decode_partials(r.get("facet_partials")),
            suggest=r.get("suggest"),
            context_id=r.get("ctx_id"),
            shard_id=copy.shard_id,
            timed_out=bool(r.get("timed_out")),
            degraded=bool(r.get("degraded")),
            profile=prof,
            hits=r.get("hits"),
        )
        result.index_name = copy.index  # type: ignore[attr-defined]
        return result

    _PIN_KEEP_S = 60.0

    def _pin_context(self, index: str, shard_id: int, ctx: ShardContext) -> int:
        """Pin a query-phase ShardContext for the fetch phase; reaped lazily."""
        now = time.monotonic()
        with self._pinned_lock:
            for k in [k for k, v in self._pinned.items() if v[0] < now]:
                del self._pinned[k]
            cid = self._pinned_next[0]
            self._pinned_next[0] += 1
            self._pinned[cid] = (now + self._PIN_KEEP_S, index, shard_id, ctx)
        return cid

    def _take_pinned(self, cid, index: str, shard_id: int) -> ShardContext | None:
        now = time.monotonic()
        with self._pinned_lock:
            for k in [k for k, v in self._pinned.items() if v[0] < now]:
                del self._pinned[k]
            v = self._pinned.pop(cid, None) if cid is not None else None
        if v is not None and v[1] == index and v[2] == shard_id:
            return v[3]
        return None

    def _s_query_progress(self, request, channel):
        """What a coordinator's attempt timer asks before it fails a late copy
        over: is this node compiling, or packing a segment
        (QUERY_ATTEMPT_TIMEOUT's comment)."""
        from .common.jaxenv import seconds_since_compile
        from .ops.device_index import PACK_LEDGER

        return {"compile_idle_s": seconds_since_compile(),
                "pack_idle_s": PACK_LEDGER.idle_s()}

    def _s_free_context(self, request, channel):
        """ES's free-context: the coordinator releases pinned searchers of shards
        that contributed no fetched hits (the fetch itself pops the winners)."""
        self._take_pinned(request.get("ctx"), request["index"], request["shard"])
        return {}

    def _shard_ctx(self, index: str, shard_id: int, dfs: dict | None = None) -> ShardContext:
        svc = self.indices.index_service(index)
        shard = svc.shard(shard_id)
        # opens the warmer's pack-scheduling gate (warmer.py): refreshes of a
        # shard that has never served a search stay device-free; after the
        # first search, every new view's packs/remasks move off the query
        # path onto the warmer/merge pools. Plain attr write, idempotent
        shard.engine.search_active = True
        global_stats = None
        if dfs:
            global_stats = {
                "max_doc": dfs["max_doc"],
                "df": {(f, t): v for f, t, v in dfs["term_df"]},
                "field_stats": {f: _fs_from(l) for f, l in dfs["field_stats"].items()},
            }
        return ShardContext(shard.engine.acquire_searcher(), svc.mapper_service,
                            svc.similarity_service, global_stats,
                            index_name=index, breakers=self.node.breakers,
                            batcher=getattr(self.node, "search_batcher", None),
                            filter_cache=getattr(self.node, "filter_cache",
                                                 None))

    def _continue_trace(self, request, name: str):
        """The sender's trace continued on this shard's node from the wire
        context, rooted at `name`; NOOP_TRACE where the request carries none
        (the sender injects one for sampled traces only)."""
        tracer = getattr(self.node, "tracer", None)
        if tracer is None:
            return tracing.NOOP_TRACE
        return tracer.continue_trace(request.get(tracing.TRACE_WIRE_KEY), name)

    def _s_query_phase(self, request, channel):
        index, shard_id = request["index"], request["shard"]
        body = dict(request.get("body") or {})
        alias_filter = request.get("alias_filter")
        if alias_filter:
            query = body.get("query") or {"match_all": {}}
            body["query"] = {"filtered": {"query": query, "filter": alias_filter}}
        # `"profile": true` (peeked BEFORE parsing, so the unprofiled path
        # pays no clock read — profile.py design rule): arm the white-box
        # execution profiler for THIS shard's query phase. The collector is
        # created ahead of parse_search_body so its t0 — and therefore
        # phases_ms.total — covers the parse phase it times; it is activated
        # thread-locally around the phase (profiled requests bypass the
        # batcher, so execution never leaves this thread), and its result
        # rides the response next to the span list.
        prof = None
        if isinstance(body, dict) and bool(body.get("profile")):
            prof = profiling.ProfileCollector(node=self.node.name,
                                              index=index, shard=shard_id)
        # continue the coordinator's trace from the wire context (the sender
        # only injects one for sampled traces) BEFORE the parse, so that the
        # shard span covers it (`shard.lower` is cut from its start); the
        # shard span is the parent every batcher span of this request
        # attaches to
        trace = self._continue_trace(request, "shard")
        shard_span = trace.root.tag(index=index, shard=shard_id)
        try:
            req = parse_search_body(body)
            if prof is not None:
                prof.phase_s("parse", time.monotonic() - prof.t0)
            ctx = self._shard_ctx(index, shard_id, request.get("dfs"))
        except BaseException:
            shard_span.end()  # a body that does not parse still ends its span
            raise
        # shard-side budget: the tighter of the coordinator's remaining budget
        # (shipped as a duration in `deadline_s`) and the body's own `timeout`
        budget = request.get("deadline_s")
        if req.timeout_s is not None:
            budget = req.timeout_s if budget is None else min(budget, req.timeout_s)
        deadline = Deadline.after(budget) if budget is not None else NO_DEADLINE
        if request.get("hedge"):
            # speculative (hedged) attempt: its shard span shows as a sibling
            # of the primary attempt's in the stitched ?trace=true tree
            shard_span.tag(hedge=True)
        # one trip (a search that met this shard alone): hydrate the page here,
        # from the searcher that chose the doc ids, and pin no context for a
        # fetch phase that will not come
        page = request.get("fetch")
        # ---- shard request cache (search/request_cache.py) ----------------
        # key = (index, shard, point-in-time view version, fingerprint of the
        # normalized body). A hit returns the stored partial BEFORE
        # execute_query_phase — zero device launches, zero device syncs. DFS
        # requests never cache (per-request global stats change clause
        # weights); profiled requests always execute (profiling is an
        # explicit opt-in to re-execution) but record hit/miss/store
        # attribution events. The uncached path pays one fingerprint
        # serialization and nothing else.
        rcache = getattr(self.node, "request_cache", None)
        cache_key = None
        peek_hit = False
        if (rcache is not None and rcache.enabled
                and request.get("dfs") is None and cache_policy(body)):
            cache_key = (index, shard_id, ctx.searcher.version,
                         request_fingerprint(body))
        # ---- always-on query-shape insights (common/insights.py) ----------
        # EVERY search classifies into a bounded registry of normalized plan
        # shapes — one canonicalization + hash per request (the same cost
        # class as the request-cache fingerprint above), zero added clocks
        # (latency reuses the slowlog's t_q pair below; the cache-hit path
        # records count + hit attribution only, reading no clock at all)
        insights_reg = getattr(self.node, "insights", None)
        shape_id = shape = None
        if insights_reg is not None and insights_reg.enabled:
            shape_id, shape = insights_reg.fingerprint(body)
        if cache_key is not None:
            if prof is None:
                data = rcache.get(cache_key)
                if data is not None:
                    try:
                        shard_span.tag(request_cache="hit")
                        out = _decode_cached_partial(data)
                        if page is not None:
                            # the key holds the searcher's version: the cached
                            # doc ids are this view's
                            out["hits"] = self._page_hits(
                                ctx, request, req, out["docs"], shard_span)
                    finally:
                        shard_span.end()
                    if shape_id is not None:
                        insights_reg.record(shape_id, shape, cache="hit")
                    out["ctx_id"] = None if page is not None else \
                        self._pin_context(index, shard_id, ctx)
                    out["load"] = self._load_signal()
                    if trace:
                        out["spans"] = trace.span_dicts()
                    return out
            else:
                peek_hit = rcache.peek(cache_key)
                prof.event("request_cache",
                           cache="hit" if peek_hit else "miss")
        t_q = time.monotonic()
        obs = _insights.Observation() if shape_id is not None else None
        hits = None
        try:
            try:
                with tracing.activate(shard_span):
                    if obs is not None:
                        with _insights.activate(obs):
                            result = self._execute_qp(ctx, req, shard_id,
                                                      deadline, prof)
                    else:
                        result = self._execute_qp(ctx, req, shard_id,
                                                  deadline, prof)
            except Exception:
                # a failing shape still classifies (outcome "error"): a query
                # shape storming a breaker/deadline must show in
                # /_insights/queries precisely when the operator needs it
                if shape_id is not None:
                    obs.outcome = "error"
                    insights_reg.record(
                        shape_id, shape, time.monotonic() - t_q, obs,
                        cache="miss" if cache_key is not None else None)
                raise
            # the slow log and the insights record keep the query phase's
            # own edges; the shard span ends behind the page's fetch
            took_s = time.monotonic() - t_q
            if page is not None:
                hits = self._page_hits(ctx, request, req, result.docs,
                                       shard_span)
        finally:
            shard_span.end()
        partial = _shard_partial_dict(result)
        if shape_id is not None:
            # profiled runs that found the entry present (peek) attribute a
            # hit even though profiling re-executed — same rule as the
            # profile event above
            insights_reg.record(
                shape_id, shape, took_s, obs,
                cache=("hit" if peek_hit else "miss")
                if cache_key is not None else None)
        self._maybe_slowlog(index, shard_id, body, took_s,
                            trace=trace, shape_id=shape_id)
        # store the partial for the next sighting of this (body, view) —
        # never a timed-out partial (an honest partial is not THE answer),
        # and never re-store what a profiled run already found present
        if cache_key is not None and not result.timed_out and not peek_hit:
            # the stored bytes drop the degraded flag: it describes HOW this
            # execution was served (host path while a device domain was open),
            # not the data — the partial itself is bitwise-identical, and a
            # later cache hit is served from memory, degraded by nothing
            data = _encode_cached_partial({**partial, "degraded": False})
            # `body` registers the fingerprint in the shard's hot-key memory
            # (hit counts drive the warmer's post-refresh top-N replay)
            if data is not None and rcache.put(cache_key, data, body=body) \
                    and prof is not None:
                prof.event("request_cache", cache="store")
        out = {
            **partial,
            # a fetch phase must read the SAME point-in-time searcher these doc
            # ids come from (a merge between phases moves local ids); none
            # follows a query phase that built the page's hits itself
            "ctx_id": None if page is not None
            else self._pin_context(index, shard_id, ctx),
            # response-piggybacked load signals for the coordinator's adaptive
            # replica selection (cluster/stats.py): this node's search-pool
            # queue depth + request-breaker headroom. Plain attribute reads —
            # the serving path gains no locks, clocks, or device traffic
            "load": self._load_signal(),
        }
        if hits is not None:
            out["hits"] = hits
        if trace:
            # the shard's span list rides the response so the coordinator can
            # stitch the cross-node tree inline (the `?trace=true` contract);
            # the shard node ALSO keeps its own copy in its /_traces ring
            out["spans"] = trace.span_dicts()
        if prof is not None:
            # the shard profile crosses the wire the same way the span list
            # does — plain scalars through the binary codec, stitched by the
            # coordinator into the top-level `profile` section
            out["profile"] = prof.to_dict()
        return out

    @staticmethod
    def _page_hits(ctx, request, req, docs, shard_span) -> list:
        """One trip: the hits of the page a search that met this shard alone
        asked for (`request["fetch"]`: [from, size]), built on the searcher
        that chose `docs`; of a sampled search a `shard.fetch` span inside its
        `shard` span. An error here fails the attempt as one in the query
        does, so the coordinator's chain tries the next copy."""
        with tracing.child_scope(shard_span, "shard.fetch"):
            winners = shard_page(req, docs, *request["fetch"])
            if request.get("alias_filter"):
                # the fetch phase reads the body as the client sent it:
                # highlight and explain see its query, not the alias's filter
                req = parse_search_body(request.get("body") or {})
            return execute_fetch_phase(ctx, req, winners,
                                       index_name=request["index"],
                                       shard_id=request["shard"])

    @staticmethod
    def _execute_qp(ctx, req, shard_id: int, deadline, prof):
        """One shard query phase, with the profiler activated only when the
        request opted in (profile.py rule: activate(None) is never entered)."""
        if prof is None:
            return execute_query_phase(ctx, req, shard_id=shard_id,
                                       deadline=deadline)
        with profiling.activate(prof):
            return execute_query_phase(ctx, req, shard_id=shard_id,
                                       deadline=deadline)

    def warm_shard_queries(self, index: str, shard_id: int,
                           bodies: list[dict],
                           budget_s: float = 5.0) -> tuple[int, int]:
        """Warmer re-prime (warmer.py, on the `warmer` pool): execute the
        shard's hottest cached bodies against its CURRENT view and store the
        partials, so the first post-refresh sighting of a hot query is a
        request-cache hit. Mirrors _s_query_phase's execute→encode→store
        path minus the spans/insights/slowlog (a warm execution is not a
        request); already-warmed keys are skipped via peek (no hit/miss
        accounting perturbed). Returns (warmed, failed)."""
        rcache = getattr(self.node, "request_cache", None)
        if rcache is None or not rcache.enabled:
            return 0, 0
        warmed = failed = 0
        for body in bodies:
            try:
                ctx = self._shard_ctx(index, shard_id)
                key = (index, shard_id, ctx.searcher.version,
                       request_fingerprint(body))
                if rcache.peek(key):
                    continue  # a live request (or earlier warm) beat us
                req = parse_search_body(dict(body))
                result = execute_query_phase(
                    ctx, req, shard_id=shard_id,
                    deadline=Deadline.after(budget_s))
                if result.timed_out:
                    continue  # honest partials are never cached
                data = _encode_cached_partial(_shard_partial_dict(result))
                # body=None: the warm store must not touch the hot-key
                # ranking the live traffic builds
                if data is not None and rcache.put(key, data):
                    warmed += 1
            except SearchEngineError:
                failed += 1  # shard gone / parse drift: skip this body
            except Exception:  # noqa: BLE001 — warming must never throw into
                # the warmer pool; a single bad body just doesn't warm
                failed += 1
        return warmed, failed

    def _load_signal(self) -> dict:
        """The query-phase response's piggybacked load sample: search-pool
        queue depth + request-breaker headroom fraction, read as plain
        attributes (unlocked int/float reads are exact enough for a decayed
        routing signal and keep the hot path free of new locks and clocks)."""
        queue = self.node.threadpool.queue_depth("search")
        br = self.node.breakers.breaker("request")
        headroom = 1.0 if br.limit <= 0 else \
            max(0.0, 1.0 - br.used / br.limit)
        out = {"queue": queue, "headroom": round(headroom, 4)}
        # per-copy request-cache hit rate piggybacks alongside (also plain
        # int reads): the adaptive selector records it per copy so operators
        # can see WHERE the affinity routing is landing hits (reported in
        # /_nodes/stats adaptive_routing; never a rank input — health ranks)
        rc = getattr(self.node, "request_cache", None)
        if rc is not None:
            lookups = rc.hits + rc.misses
            out["rc_hit_rate"] = round(rc.hits / lookups, 4) if lookups \
                else 0.0
        return out

    def _cluster_slowlog_levels(self, md) -> dict:
        """Parsed cluster-level slowlog thresholds {level: seconds|None},
        rebuilt only when the metadata version moves — the shipped default
        (no thresholds anywhere) costs one attr read + version compare per
        query phase, never a settings-dict flatten."""
        cached = self._slowlog_cluster
        if cached is not None and cached[0] == md.version:
            return cached[1]
        flat = dict(md.persistent_settings)
        flat.update(dict(md.transient_settings))
        levels: dict = {}
        for level in ("warn", "info", "debug"):
            raw = flat.get(f"index.search.slowlog.threshold.query.{level}")
            value = None
            if raw is not None:
                try:
                    value = parse_time(raw)
                except IllegalArgumentError:
                    value = None
            levels[level] = value
        self._slowlog_cluster = (md.version, levels)
        return levels

    def _maybe_slowlog(self, index: str, shard_id: int, body: dict, took_s: float,
                       trace=None, shape_id: str | None = None):
        """Per-shard query slowlog (ref: index/search/slowlog/
        ShardSlowLogSearchService.java:41,60-63 — warn/info/debug/trace thresholds from
        dynamic index settings). Each line carries the trace id, the
        query-shape fingerprint (joinable to `GET /_insights/queries` exactly
        the way the trace id joins `/_traces`), and the queue/device/merge
        phase breakdown (zeros + trace[-] when the request was unsampled).

        Thresholds resolve index settings first, then the CLUSTER transient/
        persistent settings — so `PUT /_cluster/settings` arms the slowlog
        fleet-wide at runtime, no node restart (transient wins over
        persistent, per-index settings win over both)."""
        md = self.cluster_service.state.metadata
        meta = md.index(index)
        if meta is None:
            return
        settings = meta.settings
        cluster_levels = self._cluster_slowlog_levels(md)
        for level, log in (("warn", self.logger.warning), ("info", self.logger.info),
                           ("debug", self.logger.debug)):
            key = f"index.search.slowlog.threshold.query.{level}"
            threshold = settings.get_time(key, None)
            if threshold is None:
                threshold = cluster_levels.get(level)
            if threshold is not None and threshold >= 0 and took_s >= threshold:
                # breakdown only on a threshold hit: phase_breakdown copies
                # the span list under the trace lock — with thresholds unset
                # (the default) a sampled query must not pay that per call
                phases = tracing.phase_breakdown(trace)
                trace_id = trace.trace_id if trace else "-"
                log("slowlog [%s][%d] took[%.1fms] trace[%s] shape[%s] "
                    "queue[%.1fms] device[%.1fms] merge[%.1fms] source[%s]",
                    index, shard_id, took_s * 1000, trace_id, shape_id or "-",
                    phases["queue_ms"], phases["device_ms"],
                    phases["merge_ms"], str(body)[:500])
                return

    def _s_fetch_phase(self, request, channel):
        # a sampled search's fetch continues its trace from the wire context,
        # as the query phase does: `shard.fetch` runs from the handler's
        # entry until the hits are built, and its span list rides the
        # response (an unsampled request carries no context: NOOP_TRACE)
        trace = self._continue_trace(request, "shard.fetch")
        try:
            # the pinned query-time context when available (expired/restarted
            # nodes fall back to a fresh searcher — best effort, like a lost
            # scroll)
            ctx = self._take_pinned(request.get("ctx"), request["index"],
                                    request["shard"]) \
                or self._shard_ctx(request["index"], request["shard"])
            req = parse_search_body(request.get("body") or {})
            docs = [(s, d, sv) for s, d, sv in request["docs"]]
            hits = execute_fetch_phase(ctx, req, docs,
                                       index_name=request["index"],
                                       shard_id=request["shard"])
        finally:
            trace.root.end()
        out = {"hits": hits}
        if trace:
            out["spans"] = trace.span_dicts()
        return out

    def _s_dfs_phase(self, request, channel):
        ctx = self._shard_ctx(request["index"], request["shard"])
        body = request.get("body") or {}
        query = parse_query(body.get("query")) if body.get("query") else None
        from .search.queries import MatchAllQuery

        dfs = collect_dfs(ctx, query or MatchAllQuery(), shard_id=request["shard"])
        return {
            "max_doc": dfs.max_doc,
            "term_df": [[f, t, v] for (f, t), v in dfs.term_df.items()],
            "field_stats": {f: [s.doc_count, s.sum_ttf, s.sum_dfs]
                            for f, s in dfs.field_stats.items()},
        }

    def count(self, index_expr, body=None) -> dict:
        r = self.search(index_expr, {**(body or {}), "size": 0})
        return {"count": r["hits"]["total"], "_shards": r["_shards"]}

    def _lookup_get(self, index, type_name, doc_id, routing=None):
        # a missing lookup DOCUMENT resolves to no terms (reference behavior);
        # a missing lookup INDEX (typo) must fail the request, not silently
        # return zero hits — get_doc's IndexMissingError propagates
        return self.get_doc(index, type_name or "_all", doc_id, routing=routing)

    def delete_by_query(self, index_expr, body) -> dict:
        """Broadcast: resolve matching uids per shard, tombstone (ref: delete_by_query
        replication action — here resolved per shard then replicated)."""
        body = resolve_terms_lookups(body, self._lookup_get)
        state = self.cluster_service.state
        indices = state.metadata.resolve_indices(index_expr)
        futs = []
        for index in indices:
            table = state.routing_table.index(index)
            for group in table.shards:
                for copy in group.active_shards():
                    node = state.nodes.get(copy.node_id)
                    futs.append((index, copy, self.transport.send_request(
                        node, A_SHARD_BROADCAST, {
                            "index": index, "shard": copy.shard_id,
                            "op": "delete_by_query", "body": body})))
        deleted = {i: 0 for i in indices}
        for index, copy, fut in futs:
            r = fut_result(fut, 30.0)
            if copy.primary:
                deleted[index] += r.get("deleted", 0)
        return {"_indices": {i: {"deleted": n} for i, n in deleted.items()}}

    def broadcast(self, index_expr, op: str, extra: dict | None = None) -> dict:
        """refresh / flush / optimize / clear_cache across all shard copies.
        `extra` rides the per-shard payload (e.g. the _cache/clear
        request/filter tier selectors)."""
        state = self.cluster_service.state
        indices = state.metadata.resolve_indices(index_expr) if index_expr else \
            state.metadata.index_names()
        futs = []
        for index in indices:
            table = state.routing_table.index(index)
            if table is None:
                continue
            for group in table.shards:
                for copy in group.active_shards():
                    node = state.nodes.get(copy.node_id)
                    futs.append(self.transport.send_request(node, A_SHARD_BROADCAST, {
                        "index": index, "shard": copy.shard_id, "op": op,
                        **(extra or {}),
                    }))
        # a force-merge runs as long as the merge takes (the reference's
        # _optimize waits for it too): a timeout here would report a shard
        # `failed` while its merge goes on and completes
        wait = None if op == "optimize" else 30.0
        ok = 0
        for fut in futs:
            try:
                fut_result(fut, wait)
                ok += 1
            except SearchEngineError:
                pass
        total = len(futs)
        return {"_shards": {"total": total, "successful": ok, "failed": total - ok}}

    def _s_broadcast(self, request, channel):
        shard = self.indices.index_service(request["index"]).shard(request["shard"])
        op = request["op"]
        if op == "refresh":
            if shard.engine.refresh():
                self._run_warmers(request["index"], request["shard"])
            return {"ok": True}
        if op == "flush":
            shard.engine.flush()
            return {"ok": True}
        if op == "optimize":
            shard.engine.optimize()
            return {"ok": True}
        if op == "clear_cache":
            # tier selectors (the `?request=&filter=` params of
            # POST /_cache/clear): both default true, reference parity
            clear_request = request.get("request", True) is not False
            clear_filter = request.get("filter", True) is not False
            cleared = {"request": 0, "filter": 0}
            if clear_filter:
                fcache = getattr(self.node, "filter_cache", None)
                for seg in shard.engine.acquire_searcher().segments:
                    seg._device_cache.pop("filters", None)  # host mask cache
                    if fcache is not None:  # device-resident masks + breaker
                        cleared["filter"] += fcache.clear_segment(seg)
            if clear_request:
                rcache = getattr(self.node, "request_cache", None)
                if rcache is not None:
                    cleared["request"] = rcache.invalidate_shard(
                        request["index"], request["shard"], None)
            return {"ok": True, "cleared": cleared}
        if op == "delete_by_query":
            ctx = self._shard_ctx(request["index"], request["shard"])
            from .search.execute import host_match_mask
            from .search.queries import parse_query as pq

            query = pq((request.get("body") or {}).get("query"))
            uids = []
            for seg in ctx.searcher.segments:
                mask = host_match_mask(query, seg, ctx) & seg.live & seg.parent_mask
                import numpy as np

                for local in np.nonzero(mask)[0]:
                    uids.append(f"{seg.types[local]}#{seg.ids[local]}")
            shard.engine.delete_by_uids(uids, query=(request.get("body") or {}).get("query"))
            shard.engine.refresh()
            return {"ok": True, "deleted": len(uids)}
        raise SearchEngineError(f"unknown broadcast op [{op}]")


class _SourceDoc:
    """doc[...] access over a plain source dict (for update scripts)."""

    def __init__(self, source: dict):
        self._source = source

    def __getitem__(self, field):
        from .search.filters import FieldVal

        v = self._source.get(field)
        if v is None:
            return FieldVal([])
        return FieldVal(v if isinstance(v, list) else [v])


def _flatten_text_fields(source: dict, prefix: str = "") -> dict[str, list]:
    """Flatten a _source dict to dotted-path -> list of string values (termvector/mlt
    operate on text fields only)."""
    out: dict[str, list] = {}
    for key, value in (source or {}).items():
        path = f"{prefix}{key}"
        if isinstance(value, dict):
            for k, v in _flatten_text_fields(value, path + ".").items():
                out.setdefault(k, []).extend(v)
        elif isinstance(value, list):
            for item in value:
                if isinstance(item, dict):
                    for k, v in _flatten_text_fields(item, path + ".").items():
                        out.setdefault(k, []).extend(v)
                elif isinstance(item, str):
                    out.setdefault(path, []).append(item)
        elif isinstance(value, str):
            out.setdefault(path, []).append(value)
    return out


def _extract_fields(get_response: dict, fields) -> tuple[dict, dict | None]:
    """Build the `fields` section of a get/update response: meta fields as scalars,
    source leaves as single-element lists (ref: GetResult field rendering)."""
    if isinstance(fields, str):
        fields = [f.strip() for f in fields.split(",")]
    out: dict = {}
    source_out = None
    src = get_response.get("_source") or {}
    for f in fields or []:
        if f == "_source":
            source_out = src
        elif f in ("_routing", "_parent"):
            v = get_response.get(f)
            if v is not None:
                out[f] = str(v)
        elif f in ("_timestamp", "_ttl"):
            v = get_response.get(f)
            if v is not None:
                out[f] = int(v)
        else:
            vals = _source_leaf(src, f)
            if vals:
                out[f] = vals
    return out, source_out


def _source_leaf(src: dict, path: str) -> list:
    cur = src
    for part in path.split("."):
        if isinstance(cur, dict) and part in cur:
            cur = cur[part]
        else:
            return []
    return cur if isinstance(cur, list) else [cur]


def filter_source(src: dict, includes, excludes) -> dict:
    """_source filtering with wildcard paths (ref: common/xcontent XContentMapValues
    .filter — include/exclude globs over the source tree). An include naming an
    object node keeps its whole subtree; an include naming a deeper path descends."""
    import fnmatch

    def norm(spec):
        if spec is None:
            return []
        if isinstance(spec, str):
            return [s.strip() for s in spec.split(",") if s.strip()]
        return [str(s) for s in spec]

    includes, excludes = norm(includes), norm(excludes)

    def matches(path, pattern):
        return fnmatch.fnmatch(path, pattern)

    def is_ancestor(path, pattern):
        """`path` is a strict ancestor of a path the pattern could match."""
        pa, pp = path.split("."), pattern.split(".")
        if len(pa) >= len(pp):
            return False
        return all(fnmatch.fnmatch(a, b) for a, b in zip(pa, pp))

    def walk(obj, prefix, included):
        out = {}
        for k, v in obj.items():
            path = f"{prefix}{k}"
            if excludes and any(matches(path, p) for p in excludes):
                continue
            hit = included or not includes or any(matches(path, p)
                                                 for p in includes)
            if isinstance(v, dict):
                if hit:
                    sub = walk(v, path + ".", included=True)
                    out[k] = sub
                elif any(is_ancestor(path, p) for p in includes):
                    sub = walk(v, path + ".", included=False)
                    if sub:
                        out[k] = sub
            elif hit:
                out[k] = v
        return out

    return walk(src, "", included=False)


def _deep_merge(dst: dict, src: dict):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _deep_merge(dst[k], v)
        else:
            dst[k] = v


def _fs_from(lst):
    from .index.segment import FieldStats

    return FieldStats(*lst)


def _shard_partial_dict(result) -> dict:
    """The wire/cache shape of one shard's query-phase partial — the ONE
    construction site shared by the live query phase (_s_query_phase) and
    the warmer's re-prime (warm_shard_queries): warm-stored and live-stored
    request-cache entries must decode identically or a post-refresh hit on
    a warmed entry fails where the live entry worked."""
    return {
        "total": result.total,
        "docs": [[s, d, sv] for (s, d, sv) in result.docs],
        "max_score": None if result.max_score != result.max_score
        else result.max_score,
        "agg_partials": _encode_partials(result.agg_partials),
        "facet_partials": _encode_partials(result.facet_partials),
        "suggest": result.suggest,
        "timed_out": result.timed_out,
        "degraded": result.degraded,
    }


def _encode_cached_partial(partial: dict) -> bytes | None:
    """Serialize a cacheable shard partial through the binary wire codec
    (common/stream.py) — the SAME bytes that cross the transport, so breaker
    accounting is honest and a cache hit hands back an isolated copy. A
    value the codec refuses (an exotic plugin payload) skips caching rather
    than failing the search."""
    from .common.stream import StreamOutput

    try:
        out = StreamOutput()
        out.write_map(partial)
        return out.bytes()
    except SearchEngineError:
        return None


def _decode_cached_partial(data: bytes) -> dict:
    from .common.stream import StreamInput

    return StreamInput(data).read_map()


def _encode_partials(partials):
    """Agg partials cross the wire pickled+b64 (they contain numpy arrays/sets;
    a typed codec replaces this when the TCP transport hardens)."""
    import pickle

    return base64.b64encode(pickle.dumps(partials)).decode("ascii") if partials else None


def _decode_partials(blob):
    import pickle

    if not blob:
        return []
    return pickle.loads(base64.b64decode(blob))
