"""Node assembly + client.

Analogue of node/internal/InternalNode.java (SURVEY.md §2.12): builds every service in
dependency order (threadpool → transport → cluster service → allocation → indices →
actions → discovery → gateway), starts discovery, and exposes a Client facade (the
NodeClient shape: one method per action, routed through the local transport).

An in-process multi-node cluster (nodes sharing a LocalTransportRegistry) is the direct
analogue of the reference's TestCluster (SURVEY.md §4.2) — and also the single-host
production topology: one node process per host, shards on the TPU mesh.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
import uuid

from .actions import ActionModule
from .cluster.allocation import AllocationService
from .cluster.routing import OperationRouting
from .cluster.service import ClusterService
from .cluster.state import BLOCK_STATE_NOT_RECOVERED, DiscoveryNode
from .common.errors import SearchEngineError
from .common.logging import get_logger
from .common.names import is_pattern as _is_pattern
from .common.names import name_matches as _name_matches
from .common.settings import Settings, prepare_settings
from .discovery.zen import ZenDiscovery
from .gateway import LocalGateway
from .indices_service import IndicesService
from .threadpool import ThreadPool
from .transport.local import DEFAULT_REGISTRY, LocalTransport
from .transport.service import TransportService


class Node:
    def __init__(self, name: str | None = None, settings=None, registry=None,
                 data_path: str | None = None, tribe_registries=None):
        self.settings = prepare_settings(settings)
        self.name = name or self.settings.get_str("node.name") or f"node_{uuid.uuid4().hex[:6]}"
        self.node_id = self.settings.get_str("node.id") or self.name
        self.data_path = data_path or self.settings.get_str("path.data") or \
            tempfile.mkdtemp(prefix=f"estpu_{self.name}_")
        self.logger = get_logger("node", node=self.name)
        self.registry = registry or DEFAULT_REGISTRY
        # plugin discovery before service assembly (ref: InternalNode.java:150 —
        # PluginsService first, so plugins can contribute settings defaults)
        from .plugins import PluginsService

        self.plugins = PluginsService(self.settings, self.data_path or ".")
        extra = self.plugins.additional_settings()
        if extra:
            merged = dict(extra)
            merged.update(self.settings.as_dict())  # node settings win
            from .common.settings import Settings as _S

            self.settings = _S.from_flat(merged)
        # transport.type: "local" (in-process, the test default — LocalTransport.java's
        # role) or "tcp" (DCN sockets between host processes — NettyTransport's role).
        if self.settings.get_str("transport.type", "local") == "tcp":
            from .transport.tcp import TcpTransport

            backend = TcpTransport(
                host=self.settings.get_str("transport.tcp.host", "127.0.0.1"),
                port=self.settings.get_int("transport.tcp.port", 0),
                compress=self.settings.get_bool("transport.tcp.compress", False),
            )
            address = backend.address
        else:
            backend = None
            address = f"local://{self.node_id}"
        attrs = tuple(sorted(
            (k[len("node.attr."):], str(v)) for k, v in self.settings.as_dict().items()
            if k.startswith("node.attr.")
        ))
        self.local_node = DiscoveryNode(
            id=self.node_id, name=self.name, transport_address=address, attrs=attrs,
            master_eligible=self.settings.get_bool("node.master", True),
            data=self.settings.get_bool("node.data", True),
        )
        self.threadpool = ThreadPool(self.settings)
        # overload protection: the node's breaker hierarchy (parent budget over
        # request / fielddata / in_flight_requests children) — consulted by the
        # search hot spots via ShardContext and by the transport send path
        from .common.breaker import CircuitBreakerService

        self.breakers = CircuitBreakerService(self.settings)
        # multi-tier caching (ISSUE 11): the shard request cache (normalized
        # request + point-in-time view → serialized partial, accounted on the
        # request breaker) and the device-resident filter/bitset cache (hot
        # filters' packed doc masks stay in HBM, accounted on the fielddata
        # breaker next to the packed postings) — invalidation rides the
        # engines' view listeners (indices_service._wire_cache_listeners)
        from .ops.device_index import DeviceFilterCache
        from .search.request_cache import ShardRequestCache

        self.request_cache = ShardRequestCache(
            self.settings, breaker=self.breakers.breaker("request"),
            total_budget=self.breakers.total_budget)
        self.filter_cache = DeviceFilterCache(
            self.settings, breaker=self.breakers.breaker("fielddata"))
        # request-scoped tracing: sampling knobs ESTPU_TRACE /
        # search.trace.sample_rate, bounded ring of finished traces
        # (GET /_traces), in-flight registry (GET /_tasks) — the span
        # substrate the REST/coordinator/shard/batcher path records into
        from .common.tracing import Tracer

        self.tracer = Tracer(self.settings, node_name=self.name)
        # always-on fleet telemetry (ISSUE 13): every search classifies into
        # a bounded registry of normalized plan shapes (count/latency/queue/
        # device histograms, outcome mix, cache hit rates — common/insights),
        # and a bounded journal of typed stall/pressure events fed by the
        # management-pool watchdog (common/events; started below, after the
        # services it reads exist)
        from .common.events import EventJournal
        from .common.insights import QueryShapeInsights

        self.insights = QueryShapeInsights(self.settings)
        self.events = EventJournal(self.settings, node_name=self.name,
                                   node_id=self.node_id)
        # device fault-domain circuit tracker (common/devicehealth singleton):
        # register this node's journal so trip/recover transitions
        # (device_degraded / device_recovered) land next to watchdog events
        from .common.devicehealth import DEVICE_HEALTH

        DEVICE_HEALTH.register_publisher(self.node_id, self.events.publish)
        # install the process compile listener NOW so the capacity ledger's
        # per-family attribution covers this node's first searches (counts
        # start at install — jaxenv._CompileCounter)
        from .common.jaxenv import compile_events_total

        compile_events_total()
        # cross-request device micro-batching: concurrent query phases on one
        # shard coalesce into one bucketed launch (search/batcher.py; wired
        # into ShardContext by ActionModule._shard_ctx and into mesh serving)
        from .search.batcher import DeviceBatcher

        self.search_batcher = DeviceBatcher(self.settings,
                                            threadpool=self.threadpool,
                                            node_name=self.name)
        if backend is None:
            backend = LocalTransport(address, self.registry)
        self.transport = TransportService(backend, self.local_node, self.threadpool)
        self.transport.in_flight_breaker = self.breakers.breaker("in_flight_requests")
        self.cluster_service = ClusterService(self.name)
        self.allocation = AllocationService(self.settings)
        # adaptive replica selection + hedging (cluster/stats.py): per-copy
        # health records fed by the coordinator's query-phase attempts, the
        # rank behind preference-free copy choice, failover-chain order, and
        # the hedge delay/budget ("The Tail at Scale" / C3)
        from .cluster.stats import AdaptiveReplicaSelector

        self.adaptive_routing = AdaptiveReplicaSelector(self.settings)
        self.operation_routing = OperationRouting(
            selector=self.adaptive_routing)
        self.indices = IndicesService(self.node_id, self.name, self.data_path,
                                      self.transport, self.cluster_service)
        self.gateway = LocalGateway(self.data_path, self.cluster_service,
                                    self.settings, node_name=self.name)
        self.actions = ActionModule(self)
        from .monitor import MonitorService
        from .percolator import PercolatorService
        from .snapshots import SnapshotsService

        self.snapshots = SnapshotsService(self)
        self.percolator = PercolatorService(self)
        # index warmer (ISSUE 14): every searcher install schedules the new
        # view's device packs/remasks on the warmer/merge pools (so the
        # query path stops paying them) and replays the shard's hottest
        # request-cache bodies against the new view
        # (`indices.warmer.enabled` gates the re-prime half)
        from .warmer import IndexWarmerService

        self.warmer = IndexWarmerService(self)
        # compile warming (ROADMAP item 5): configure the process registry
        # with this node's knobs/path.data — loads the shape manifest a prior
        # process persisted, arms the persistent XLA compilation cache under
        # path.data, and registers the per-pool compile-event observer. The
        # startup warm cycle below replays every manifest spec on the warmer
        # pool so the first serving sighting of yesterday's query mix is a
        # dispatch-cache hit, not an on-path compile
        from .common.compilecache import REGISTRY as _compile_registry

        _compile_registry.configure(self.settings, self.data_path)
        self.compile_warming = _compile_registry
        self.warmer.schedule_compile_warm("startup")
        self.indices.node = self
        self.monitor = MonitorService(self)
        # stall watchdog: management-pool periodic comparing live in-flight
        # state (dispatched-unmerged batch age, per-pool queue-wait p99,
        # breaker near-trip dwell, locktrace long-held counters) against
        # adaptive thresholds; typed events land in self.events and gossip
        # to the other nodes (common/events.StallWatchdog)
        from .common.events import StallWatchdog

        self.watchdog = StallWatchdog(self, self.settings).start()
        # IndicesTTLService analogue: periodic purge of _ttl-expired docs
        self._ttl_task = self.threadpool.schedule_with_fixed_delay(
            self.settings.get_time("indices.ttl.interval", 60.0), self._purge_expired,
            name="generic")
        # scheduled NRT refresh + merge-policy driver (per-shard interval honored
        # inside periodic_refresh; this is just the tick)
        self._refresh_task = self.threadpool.schedule_with_fixed_delay(
            0.5, self.indices.periodic_refresh, name="refresh")
        # IndexingMemoryController: shared indexing-buffer budget across shards
        # (ref default 10% of heap → here: % of system RAM, or explicit bytes)
        self._imc_budget = self._resolve_index_buffer_size()
        self._imc_task = self.threadpool.schedule_with_fixed_delay(
            5.0, lambda: self.indices.check_indexing_memory(self._imc_budget),
            name="management")
        self.discovery = ZenDiscovery(self.local_node, self.transport,
                                      self.cluster_service, self.allocation,
                                      self.settings)
        self.discovery.on_joined = None
        # ResourceWatcherService: hot-reloadable config files; the script
        # directory (config/scripts) is the flagship consumer
        # (ref: watcher/ResourceWatcherService.java + ScriptService wiring)
        from .script import ScriptService
        from .watcher import FileWatcher, ResourceWatcherService, ScriptDirectoryListener

        self.script_service = ScriptService(self.settings)
        self.resource_watcher = ResourceWatcherService(self.settings, self.threadpool)
        scripts_dir = self.settings.get("path.scripts") or (
            os.path.join(self.data_path, "config", "scripts") if self.data_path else None)
        if scripts_dir:
            self.scripts_dir = scripts_dir
            self.resource_watcher.add(FileWatcher(
                scripts_dir, ScriptDirectoryListener(self.script_service)))
        self.resource_watcher.start()
        # Bulk-over-UDP ingestion (ref: bulk/udp/BulkUdpService.java; off by default)
        from .bulk_udp import BulkUdpService

        self.bulk_udp = BulkUdpService(self, self.settings)
        # rivers: _river-index-driven ingestion singletons
        # (ref: river/RiversService.java; `dummy` in-tree, plugins add types)
        from .rivers import RiversService

        self.rivers = RiversService(
            self, interval=self.settings.get_time("rivers.check_interval", 1.0))
        # tribe node: inner member nodes + merged client view
        # (ref: tribe/TribeService.java; enabled by tribe.<name>.* settings)
        from .tribe import TribeService

        self.tribe = TribeService(self)
        self._tribe_registries = tribe_registries or {}
        self.http = None
        self._started = False
        self._closed = False

    # ------------------------------------------------------------------ lifecycle
    def start(self, seeds: list[str] | None = None) -> "Node":
        """ref: InternalNode.start:210-235 — services then discovery then gateway."""
        if seeds is not None:
            addresses = seeds
        else:
            # TCP nodes seed from unicast hosts (zen/ping/unicast/UnicastZenPing.java);
            # local nodes see everything on the shared in-process registry.
            unicast = self.settings.get_list("discovery.zen.ping.unicast.hosts", [])
            if unicast:
                addresses = list(unicast)
            elif isinstance(self.local_node.transport_address, str) and \
                    self.local_node.transport_address.startswith("local://"):
                addresses = self.registry.addresses()
            else:
                addresses = []
        self.plugins.on_node_created(self)
        self.discovery.start(addresses)
        self.gateway.maybe_recover()
        self.bulk_udp.start()
        if self.tribe.enabled:
            self.tribe.start(self._tribe_registries)
        self._started = True
        self.plugins.on_node_started(self)
        if self.settings.get_bool("http.enabled", False):
            self.start_http(self.settings.get_int("http.port", 9200))
        self.logger.info("started (master=%s)",
                         self.cluster_service.state.nodes.master_id)
        return self

    def start_http(self, port: int = 0):
        """Bind the REST surface (port 0 = ephemeral)."""
        from .http.server import HttpServer
        from .rest.controller import build_rest_controller

        self.http = HttpServer(build_rest_controller(self), port=port).start()
        return self.http

    def close(self):
        if self._closed:
            return
        self._closed = True
        self.plugins.on_node_closed(self)
        from .common.devicehealth import DEVICE_HEALTH

        DEVICE_HEALTH.unregister_publisher(self.node_id)
        self.watchdog.stop()
        self.rivers.stop()
        self.tribe.stop()
        self.bulk_udp.stop()
        self.resource_watcher.stop()
        if self.http is not None:
            self.http.stop()
        self.discovery.leave()
        self.discovery.stop()
        self.gateway.persist_now()
        # persist the compile-shape manifest next to the gateway state: the
        # restarted process warms exactly the executables this one served
        if self.data_path and self.compile_warming.persist:
            from .common.compilecache import MANIFEST_NAME

            self.compile_warming.save_manifest(
                os.path.join(self.data_path, MANIFEST_NAME))
        self.indices.close()
        self.cluster_service.close()
        self.transport.close()
        # stop the batcher drainer BEFORE its pool closes so queued searches
        # fail typed (RejectedExecutionError) instead of hanging on futures
        self.search_batcher.shutdown()
        self.threadpool.shutdown()

    def _resolve_index_buffer_size(self) -> int:
        """indices.memory.index_buffer_size: "10%" (of system RAM) or bytes value
        (ref: IndexingMemoryController.java:52 — default 10% of heap)."""
        raw = self.settings.get("indices.memory.index_buffer_size", "10%")
        if isinstance(raw, str) and raw.strip().endswith("%"):
            try:
                frac = float(raw.strip()[:-1]) / 100.0
                total = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
                return max(int(total * frac), 16 * 1024 ** 2)
            except (ValueError, OSError):
                return 64 * 1024 ** 2
        v = self.settings.get_bytes("indices.memory.index_buffer_size", None)
        return v if v else 64 * 1024 ** 2

    def _purge_expired(self):
        """ref: indices/ttl/IndicesTTLService — delete docs whose _ttl expired."""
        import time as _time

        now = _time.time() * 1000
        for index, svc in list(self.indices.indices.items()):
            for sid, shard in list(svc.shards.items()):
                if not shard.primary:
                    continue
                try:
                    searcher = shard.engine.acquire_searcher()
                    uids = []
                    for seg in searcher.segments:
                        col = seg.dv_num.get("_expiry")
                        if col is None:
                            continue
                        import numpy as _np

                        off, vals = col
                        counts = _np.diff(off)
                        doc_of_val = _np.repeat(_np.arange(seg.doc_count), counts)
                        expired = doc_of_val[vals < now]
                        for local in expired:
                            if seg.live[local] and seg.parent_mask[local]:
                                uids.append(f"{seg.types[local]}#{seg.ids[local]}")
                    if uids:
                        shard.engine.delete_by_uids(uids, query={"expired": True})
                        shard.engine.refresh()
                        self.logger.info("ttl purged %d docs from [%s][%d]",
                                         len(uids), index, sid)
                except SearchEngineError:
                    continue

    def is_master(self) -> bool:
        s = self.cluster_service.state
        return s.nodes.master_id == self.node_id

    def client(self) -> "Client":
        if self.tribe.enabled:
            from .tribe import TribeClient

            return TribeClient(self.tribe)
        return Client(self)

    # test/ops helper
    def wait_for_master(self, timeout: float = 5.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.cluster_service.state.nodes.master_id is not None:
                return True
            time.sleep(0.02)
        return False


class Client:
    """One method per action (ref: client/Client.java + admin facades)."""

    def __init__(self, node: Node):
        self.node = node
        self.actions = node.actions

    # --- document APIs ------------------------------------------------------
    def index(self, index, doc_type, body, id=None, routing=None, version=None,
              version_type="internal", op_type="index", refresh=False,
              parent=None, timestamp=None, ttl=None):
        return self.actions.index_doc(index, doc_type, id, body, routing=routing,
                                      version=version, version_type=version_type,
                                      op_type=op_type, refresh=refresh,
                                      parent=parent, timestamp=timestamp, ttl=ttl)

    def create(self, index, doc_type, body, id=None, **kw):
        return self.index(index, doc_type, body, id=id, op_type="create", **kw)

    def get(self, index, doc_type, id, routing=None, realtime=True, refresh=False,
            preference=None, parent=None):
        return self.actions.get_doc(index, doc_type, id, routing=routing,
                                    realtime=realtime, refresh=refresh,
                                    preference=preference, parent=parent)

    def mget(self, docs):
        return self.actions.multi_get(docs)

    def delete(self, index, doc_type, id, routing=None, version=None,
               version_type="internal", refresh=False, parent=None):
        return self.actions.delete_doc(index, doc_type, id, routing=routing,
                                       version=version, version_type=version_type,
                                       refresh=refresh, parent=parent)

    def update(self, index, doc_type, id, body, routing=None, retry_on_conflict=0,
               parent=None, refresh=False, fields=None, ttl=None, timestamp=None,
               version=None, version_type="internal"):
        return self.actions.update_doc(index, doc_type, id, body, routing=routing,
                                       retry_on_conflict=retry_on_conflict,
                                       parent=parent, refresh=refresh, fields=fields,
                                       ttl=ttl, timestamp=timestamp, version=version,
                                       version_type=version_type)

    def bulk(self, operations, refresh=False):
        return self.actions.bulk(operations, refresh=refresh)

    def delete_by_query(self, index, body):
        return self.actions.delete_by_query(index, body)

    # --- search APIs --------------------------------------------------------
    def search(self, index=None, body=None, search_type="query_then_fetch",
               routing=None, preference=None):
        return self.actions.search(index or "_all", body, search_type=search_type,
                                   routing=routing, preference=preference)

    def msearch(self, requests):
        responses = []
        for header, body in requests:
            try:
                responses.append(self.search(header.get("index", "_all"), body))
            except SearchEngineError as e:
                responses.append({"error": e.es1_string(), "status": e.status})
        return {"responses": responses}

    def count(self, index=None, body=None):
        return self.actions.count(index or "_all", body)

    def suggest(self, index, body):
        r = self.search(index, {"size": 0, "suggest": body})
        return r.get("suggest", {})

    def termvector(self, index, doc_type, id, routing=None, fields=None,
                   positions=True, offsets=True, term_statistics=False,
                   field_statistics=True):
        return self.actions.term_vector(index, doc_type, id, routing=routing,
                                        fields=fields, positions=positions,
                                        offsets=offsets,
                                        term_statistics=term_statistics,
                                        field_statistics=field_statistics)

    def mtermvectors(self, docs):
        return self.actions.multi_termvector(docs)

    def mlt(self, index, doc_type, id, mlt_fields=None, search_body=None,
            routing=None, **mlt_params):
        return self.actions.more_like_this(index, doc_type, id,
                                           mlt_fields=mlt_fields,
                                           search_body=search_body,
                                           routing=routing, **mlt_params)

    def explain(self, index, doc_type, id, body):
        r = self.search(index, {"query": {"bool": {
            "must": [body.get("query", {"match_all": {}})],
            "filter": [{"ids": {"values": [id]}}]}}, "size": 1})
        matched = r["hits"]["total"] > 0
        out = {"_index": index, "_type": doc_type, "_id": id, "matched": matched}
        if matched:
            out["explanation"] = {"value": r["hits"]["hits"][0]["_score"],
                                  "description": "score of matching document"}
        return out

    # --- indices admin ------------------------------------------------------
    def create_index(self, index, body=None):
        return self._local(A("indices:admin/create"), {"index": index, "body": body or {}})

    def delete_index(self, index):
        return self._local(A("indices:admin/delete"), {"index": index})

    def open_index(self, index):
        return self._local(A("indices:admin/open"), {"index": index})

    def close_index(self, index):
        return self._local(A("indices:admin/close"), {"index": index})

    def put_mapping(self, index, doc_type, body):
        return self._local(A("indices:admin/mapping/put"),
                           {"index": index or "_all", "type": doc_type, "body": body})

    def delete_mapping(self, index, doc_type):
        return self._local(A("indices:admin/mapping/delete"),
                           {"index": index or "_all", "type": doc_type})

    def get_mapping(self, index=None, doc_type=None):
        state = self.node.cluster_service.state
        out = {}
        for name in state.metadata.resolve_indices(index or "_all"):
            meta = state.metadata.index(name)
            mappings = meta.mappings_dict()
            if doc_type:
                mappings = {t: m for t, m in mappings.items()
                            if _name_matches(t, doc_type)}
                if not mappings:
                    continue
            # an index with no mappings is omitted when listing across indices
            # (ref: get-mapping omits empty indices)
            if not mappings and (index is None or _is_pattern(index)):
                continue
            out[name] = {"mappings": mappings}
        # missing type → empty 200 response (ref: indices.get_mapping/20_missing_type)
        return out

    def get_field_mapping(self, index=None, doc_type=None, field=None,
                          include_defaults=False):
        """ref: action/admin/indices/mapping/get/TransportGetFieldMappingsAction —
        per-index, per-type, per-field slice of the mapping. Fields resolve by full
        path first, then by index name (`index_name` attribute, or the leaf name when
        an enclosing object has `path: just_name`); the response key is the name the
        field matched by."""
        state = self.node.cluster_service.state
        from .common.errors import TypeMissingError

        out = {}
        type_seen = False
        for name in state.metadata.resolve_indices(index or "_all"):
            meta = state.metadata.index(name)
            for t, mapping in meta.mappings_dict().items():
                if doc_type and not _name_matches(t, doc_type):
                    continue
                type_seen = True
                props = _flatten_properties(mapping.get("properties") or {})
                # full path → def, plus the alternate "index name" each leaf answers to
                index_names: dict[str, str] = {}  # alternate name → full path
                for fname, fdef in props.items():
                    alts = set()
                    if isinstance(fdef, dict):
                        if fdef.get("index_name"):
                            alts.add(fdef["index_name"])
                        if fdef.get("_just_name"):
                            alts.add(fname.rsplit(".", 1)[-1])
                    for alt in alts:
                        if alt != fname:
                            index_names.setdefault(alt, fname)
                wanted: dict[str, str] = {}  # response key → full path
                exprs = ([field] if not isinstance(field, list) else field) if field \
                    else ["*"]
                exprs = [e for expr in exprs for e in str(expr).split(",")]
                for expr in exprs:
                    for fname in props:
                        if _name_matches(fname, expr):
                            wanted[fname] = fname
                    # index names match only where no full name claimed the key and
                    # the field itself wasn't already matched by full name
                    for alt, fname in index_names.items():
                        if alt not in wanted and fname not in wanted.values() \
                                and _name_matches(alt, expr):
                            wanted[alt] = fname
                for key, fname in sorted(wanted.items()):
                    fdef = {k: v for k, v in props[fname].items()
                            if k != "_just_name"}
                    leaf = fname.rsplit(".", 1)[-1]
                    if include_defaults:
                        fdef.setdefault("type", "string")
                        fdef.setdefault("index", "analyzed")
                        fdef.setdefault("analyzer", "default")
                    out.setdefault(name, {"mappings": {}})["mappings"] \
                        .setdefault(t, {})[key] = {
                        "full_name": fname, "mapping": {leaf: fdef}}
        if doc_type and not type_seen:
            raise TypeMissingError(f"type[[{doc_type}]] missing")
        return out

    def exists_type(self, index, doc_type) -> bool:
        """True only if every resolved index has the type (ref: TransportTypesExistsAction)."""
        state = self.node.cluster_service.state
        try:
            names = state.metadata.resolve_indices(index or "_all")
        except SearchEngineError:
            return False
        if not names:
            return False
        return all(
            any(_name_matches(t, doc_type)
                for t in state.metadata.index(n).mappings_dict())
            for n in names)

    def update_settings(self, index, body):
        return self._local(A("indices:admin/settings/update"),
                           {"index": index or "_all", "body": body})

    def get_settings(self, index=None, name=None):
        state = self.node.cluster_service.state
        out = {}
        for idx in state.metadata.resolve_indices(index or "_all"):
            flat = {k: _settings_str(v)
                    for k, v in state.metadata.index(idx).settings.as_dict().items()}
            if name:
                flat = {k: v for k, v in flat.items() if _name_matches(k, name)}
            if flat:
                out[idx] = {"settings": _nest_keys(flat)}
        return out

    def update_aliases(self, body):
        return self._local(A("indices:admin/aliases"), {"body": body})

    def get_aliases(self, index=None, name=None):
        """Plural form (/_aliases): explicitly-addressed indices appear even with no
        matching aliases (ref: RestGetAliasesAction)."""
        state = self.node.cluster_service.state
        explicit = set()
        if index and not _is_pattern(index) and index not in ("_all", "*"):
            explicit = {p.strip() for p in str(index).split(",")}
        out = {}
        for idx in state.metadata.resolve_indices(index or "_all"):
            aliases = state.metadata.index(idx).aliases_dict()
            if name is not None:
                aliases = {a: s for a, s in aliases.items() if _name_matches(a, name)}
                if not aliases and idx not in explicit:
                    continue
            out[idx] = {"aliases": aliases}
        return out

    def get_alias(self, index=None, name=None):
        """Singular form (/_alias): 404 when nothing matches
        (ref: TransportGetAliasesAction + RestGetAliasesAction.notFound)."""
        state = self.node.cluster_service.state
        out = {}
        for idx in state.metadata.resolve_indices(index or "_all"):
            aliases = state.metadata.index(idx).aliases_dict()
            if name is not None:
                aliases = {a: s for a, s in aliases.items() if _name_matches(a, name)}
            if aliases:
                out[idx] = {"aliases": aliases}
        # explicitly-addressed indices make an empty result a 200 {} (ref:
        # indices.delete_alias/10_basic); only an all-indices miss is a 404
        if not out and name is not None and index is None:
            from .common.errors import AliasesMissingError

            raise AliasesMissingError([name])
        return out

    def exists_alias(self, index=None, name=None) -> bool:
        try:
            return bool(self.get_alias(index, name))
        except SearchEngineError:
            return False

    def put_template(self, name, body):
        return self._local(A("indices:admin/template/put"), {"name": name, "body": body})

    def delete_template(self, name):
        return self._local(A("indices:admin/template/delete"), {"name": name})

    def get_template(self, name=None):
        state = self.node.cluster_service.state
        out = {}
        for n, t in state.metadata.templates:
            if name is None or _name_matches(n, name):
                out[n] = t.to_dict()
        if name is not None and not out and not _is_pattern(name):
            from .common.errors import IndexTemplateMissingError

            raise IndexTemplateMissingError(name)
        return out

    def refresh(self, index=None):
        return self.actions.broadcast(index, "refresh")

    def flush(self, index=None):
        return self.actions.broadcast(index, "flush")

    def optimize(self, index=None):
        return self.actions.broadcast(index, "optimize")

    def clear_cache(self, index=None, request=None, filter=None):  # noqa: A002
        extra = {}
        if request is not None:
            extra["request"] = bool(request)
        if filter is not None:
            extra["filter"] = bool(filter)
        return self.actions.broadcast(index, "clear_cache", extra=extra)

    def exists_index(self, index) -> bool:
        try:
            return bool(self.node.cluster_service.state.metadata.resolve_indices(index))
        except SearchEngineError:
            return False

    def stats(self, index=None):
        """Index stats; `/{index}/_stats` REALLY filters to the resolved
        indices now and carries each index's device capacity stanza (HBM
        residency by tier + pack timings — ops/device_index.capacity_report)."""
        out = self.node.indices.stats()
        if index is not None:
            names = set(self.node.cluster_service.state.metadata
                        .resolve_indices(index))
            out = {n: v for n, v in out.items() if n in names}
        from .ops.device_index import capacity_report

        # scope the segment walk to the indices this call returns — an
        # index-scoped stats request must not walk the whole node
        device = capacity_report(self.node.indices,
                                 index=set(out))["indices"]
        for name, entry in out.items():
            if name in device:
                entry["device"] = device[name]
        return out

    def segments(self, index=None):
        """Real per-shard segment introspection (ref: indices.segments spec /
        TransportIndicesSegmentsAction — no longer an alias of `_stats`):
        per-segment doc/postings counts plus the device packed-layout report —
        tf layout rung, bytes/posting, resident vs lazily-faulted dense plane,
        SimTables state (ops/device_index quantized layout). Pure host reads
        over already-known shapes — no device sync, no packing side effects."""
        from .ops.device_index import bytes_per_posting, packed_resident_bytes

        state = self.node.cluster_service.state
        names = state.metadata.resolve_indices(index or "_all")
        total = ok = failed = 0
        indices_out = {}
        for name in names:
            # total counts EVERY assigned copy cluster-wide (the
            # indices_status idiom): the body below is node-local, so
            # total > successful+failed makes shards hosted on OTHER nodes
            # visible as unreported instead of silently complete-looking
            table = state.routing_table.index(name)
            if table is not None:
                total += sum(1 for grp in table.shards
                             for s in grp.shards if s.active)
            svc = self.node.indices.indices.get(name)
            if svc is None:
                continue
            shards_out = {}
            for sid, shard in sorted(svc.shards.items()):
                try:
                    searcher = shard.engine.acquire_searcher()
                except SearchEngineError:
                    # closed/recovering engine: counted as failed — a
                    # clean-looking response must not hide a missing report
                    failed += 1
                    continue
                ok += 1
                segs = {}
                for seg in searcher.segments:
                    # Lucene segment semantics: num_docs counts every live
                    # slot (nested children included) so num_docs +
                    # deleted_docs == doc_count always holds
                    live = int(seg.live.sum())
                    entry = {
                        "generation": int(seg.gen),
                        "num_docs": live,
                        "deleted_docs": int(seg.doc_count) - live,
                        "doc_count": int(seg.doc_count),
                        "postings": int(len(seg.post_docs)),
                        "fields": len(seg.term_dict),
                        "search": True,
                        "committed": True,
                    }
                    packed = seg._device_cache.get("packed")
                    if packed is None:
                        # never served a device query phase — nothing resident
                        entry["device"] = {"packed": False}
                    else:
                        dense = packed.blk_freqs is not None
                        sim = packed.sim
                        entry["device"] = {
                            "packed": True,
                            "tf_layout": packed.tf_layout,
                            "bytes_per_posting": bytes_per_posting(
                                packed.tf_layout, dense_resident=dense),
                            "resident_bytes": int(
                                packed_resident_bytes(packed)),
                            "doc_pad": int(packed.doc_pad),
                            # the blk_freqs-drop rule: the dense f32 plane is
                            # faulted in lazily — report which state it is in
                            "dense_plane": "resident" if dense else "lazy",
                            "sim_tables": ({"fields": list(sim.fields)}
                                           if sim is not None else None),
                        }
                    segs[f"_{seg.gen}"] = entry
                shards_out[str(sid)] = [{
                    "routing": {"state": "STARTED",
                                "primary": bool(shard.primary),
                                "node": self.node.node_id},
                    "num_search_segments": len(searcher.segments),
                    "segments": segs,
                }]
            if shards_out:
                indices_out[name] = {"shards": shards_out}
        return {"_shards": {"total": total, "successful": ok,
                            "failed": failed},
                "indices": indices_out}

    def indices_status(self, index=None):
        """Legacy _status API (ref: action/admin/indices/status) — per-shard view."""
        state = self.node.cluster_service.state
        names = state.metadata.resolve_indices(index or "_all")
        stats = self.node.indices.stats()
        total = ok = 0
        indices = {}
        for name in names:
            table = state.routing_table.index(name)
            shards = {}
            if table is not None:
                for grp in table.shards:
                    total += len(grp.shards)
                    ok += sum(1 for s in grp.shards if s.active)
            st = stats.get(name)
            indices[name] = {"index": {"primary_size_in_bytes": 0},
                             "shards": (st or {}).get("shards", shards)}
        return {"_shards": {"total": total, "successful": ok, "failed": 0},
                "indices": indices}

    def gateway_snapshot(self, index=None):
        """Legacy _gateway/snapshot (ref: indices.snapshot_index spec) — force-persist
        local gateway state + flush, the durability checkpoint."""
        self.flush(index)
        self.node.gateway.persist_now()
        return {"_shards": {"total": 0, "successful": 0, "failed": 0}}

    # --- cluster admin ------------------------------------------------------
    def cluster_health(self, index=None, wait_for_status=None, wait_for_nodes=None,
                       timeout=10.0):
        deadline = time.monotonic() + timeout
        while True:
            h = self._health(index)
            status_ok = wait_for_status is None or _status_at_least(
                h["status"], wait_for_status)
            nodes_ok = wait_for_nodes is None or \
                h["number_of_nodes"] >= int(wait_for_nodes)
            if (status_ok and nodes_ok) or time.monotonic() > deadline:
                h["timed_out"] = not (status_ok and nodes_ok)
                return h
            time.sleep(0.05)

    def _health(self, index=None):
        state = self.node.cluster_service.state
        all_shards = [s for s in state.routing_table.all_shards()
                      if index is None or s.index == index]
        # relocation TARGETS are surplus copies of an already-active shard:
        # they must not drag status to yellow (the reference stays green while
        # relocating — the group's required copies are all active)
        shards = [s for s in all_shards
                  if not (s.state == "INITIALIZING"
                          and s.relocating_node is not None)]
        total = len(shards)
        active = sum(1 for s in shards if s.active)
        primaries = [s for s in shards if s.primary]
        active_primaries = sum(1 for s in primaries if s.active)
        relocating = sum(1 for s in shards if s.state == "RELOCATING")
        initializing = sum(1 for s in shards if s.state == "INITIALIZING")
        unassigned = sum(1 for s in shards if s.state == "UNASSIGNED")
        if active_primaries < len(primaries):
            status = "red"
        elif active < total:
            status = "yellow"
        else:
            status = "green"
        return {
            "cluster_name": state.cluster_name,
            "status": status,
            "number_of_nodes": state.nodes.size,
            "number_of_data_nodes": len(state.nodes.data_nodes()),
            "active_primary_shards": active_primaries,
            "active_shards": active,
            "relocating_shards": relocating,
            "initializing_shards": initializing,
            "unassigned_shards": unassigned,
        }

    def cluster_state(self, metric=None, index=None, index_templates=None):
        """ref: cluster.state spec — optional metric list filters the response parts.
        `routing_table` metric also carries routing_nodes + allocations, as the
        reference's ClusterState.toXContent does."""
        state = self.node.cluster_service.state
        full = state.to_dict()
        full["master_node"] = state.nodes.master_id
        full["cluster_name"] = state.cluster_name
        # REST view of blocks: only non-empty sections (the YAML suite length-checks it)
        blocks = {}
        if state.blocks.global_blocks:
            blocks["global"] = {b[0]: {"description": b[0], "levels": [b[1]]}
                                for b in state.blocks.global_blocks}
        idx_blocks = {}
        for i, b in state.blocks.index_blocks:
            idx_blocks.setdefault(i, {})[b[0]] = {"description": b[0], "levels": [b[1]]}
        if idx_blocks:
            blocks["indices"] = idx_blocks
        full["blocks"] = blocks
        # REST view of routing: indices-keyed table + node-centric view
        names = set(state.metadata.resolve_indices(index)) if index else None
        rt_indices, routing_nodes = {}, {"unassigned": [], "nodes": {}}
        for tname, t in state.routing_table.indices:
            if names is not None and tname not in names:
                continue
            shards = {}
            for gid, grp in enumerate(t.shards):
                shards[str(gid)] = [s.to_dict() for s in grp.shards]
                for s in grp.shards:
                    if s.node_id is None:
                        routing_nodes["unassigned"].append(s.to_dict())
                    else:
                        routing_nodes["nodes"].setdefault(s.node_id, []).append(s.to_dict())
            rt_indices[tname] = {"shards": shards}
        full["routing_table"] = {"indices": rt_indices}
        full["routing_nodes"] = routing_nodes
        full["allocations"] = []
        metrics = None
        if metric and metric not in ("_all",):
            metrics = set(str(metric).split(","))
        if metrics is not None and "routing_table" in metrics:
            metrics |= {"routing_nodes", "allocations"}
        out = full
        if metrics is not None:
            out = {"cluster_name": state.cluster_name}
            for m in metrics:
                if m == "master_node":
                    out["master_node"] = full["master_node"]
                elif m == "version":
                    out["version"] = full["version"]
                elif m in full:
                    out[m] = full[m]
        if "metadata" in out:
            md = dict(out["metadata"])
            if names is not None:
                md["indices"] = {n: v for n, v in md.get("indices", {}).items()
                                 if n in names}
            if index_templates:
                wanted = [t.strip() for t in str(index_templates).split(",") if t.strip()]
                md["templates"] = {n: v for n, v in md.get("templates", {}).items()
                                   if n in wanted}
            out["metadata"] = md
        return out

    def cluster_reroute(self, body=None):
        return self._local(A("cluster:admin/reroute"), {"body": body or {}})

    def cluster_update_settings(self, body, flat=False):
        self._local(A("cluster:admin/settings/update"), {"body": body})
        r = self.cluster_get_settings(flat=flat)
        r["acknowledged"] = True
        return r

    def cluster_get_settings(self, flat=False):
        md = self.node.cluster_service.state.metadata
        out = {}
        for section, stored in (("persistent", md.persistent_settings),
                                ("transient", md.transient_settings)):
            flat_map = {k: _settings_str(v) for k, v in stored}
            out[section] = flat_map if flat else _nest_keys(flat_map)
        return out

    def pending_tasks(self):
        return {"tasks": self.node.cluster_service.pending_tasks()}

    def node_events(self, size=None):
        """THIS node's event journal (common/events.py), newest first —
        the per-node leg `cluster_events` fans out through the proxy."""
        return {"node": self.node.node_id, "name": self.node.name,
                "events": self.node.events.events(size),
                "stats": self.node.events.stats()}

    def cluster_events(self, size=None, local=False):
        """GET /_events: the cluster-wide causal event record. Each node's
        journal already holds gossiped copies of remote warn events, but the
        default view pulls every journal through the client-exec proxy
        (dropping nodes skipped) and merges newest-first with origin-seq
        dedup — lossless even when gossip was. `local=true` reads only this
        node's ring."""
        state = self.node.cluster_service.state
        if local:
            mine = self.node_events(size)
            return {"cluster_name": state.cluster_name,
                    "total": len(mine["events"]),
                    "events": mine["events"],
                    "nodes": {self.node.node_id: mine["stats"]}}
        from .client import A_CLIENT_EXEC
        from .transport import fut_result

        merged = []
        node_stats = {}
        # concurrent fan-out with ONE shared deadline: /_events is read
        # during cluster distress, so k unreachable nodes must cost one
        # timeout, not k sequential ones (a dropping node is skipped)
        futs = []
        for n in state.nodes.nodes:
            if n.id == self.node.node_id:
                continue
            try:
                futs.append((n, self.node.transport.send_request(
                    n, A_CLIENT_EXEC,
                    {"method": "node_events", "kwargs": {"size": size}})))
            except SearchEngineError:
                continue
        mine = self.node_events(size)
        node_stats[self.node.node_id] = mine["stats"]
        merged.extend(mine["events"])
        collect_by = time.monotonic() + 5.0
        for n, fut in futs:
            try:
                r = fut_result(fut, timeout=max(
                    0.0, collect_by - time.monotonic()))["r"]
            except SearchEngineError:
                continue
            node_stats[n.id] = r["stats"]
            merged.extend(r["events"])
        seen = set()
        events = []
        for e in sorted(merged, key=lambda ev: -float(ev.get("ts", 0.0))):
            k = (e.get("node"), e.get("seq"))
            if k in seen:
                continue  # a gossiped copy of an event we pulled directly
            seen.add(k)
            events.append(e)
        if size is not None:
            events = events[: max(int(size), 0)]
        return {"cluster_name": state.cluster_name, "total": len(events),
                "events": events, "nodes": node_stats}

    def nodes_info(self):
        state = self.node.cluster_service.state
        nodes = {}
        for n in state.nodes.nodes:
            d = n.to_dict()
            if n.id == self.node.node_id:
                d["plugins"] = self.node.plugins.info()
            nodes[n.id] = d
        return {"cluster_name": state.cluster_name, "nodes": nodes}

    def nodes_stats(self, metric=None):
        """Per-node stats; `metric` (comma list of section names, the
        `/_nodes/stats/{metric}` path param) filters the response to those
        sections — an unknown metric is a 400, not a silent full dump."""
        from .search.service import SERVING_COUNTERS

        def serving_stats():
            # which executor served each query phase (device kernel variants
            # vs host scorer; process-wide rollup)
            ms = getattr(self.node.actions, "mesh_serving", None)
            serving = dict(SERVING_COUNTERS)
            # what the scoring launches touched (ops/scoring.LaunchCounters)
            from .ops.scoring import LAUNCHES

            serving["launch"] = LAUNCHES.snapshot()
            # a cached answer launches nothing: the shard request cache's
            # hits beside the outcomes they stand in for
            serving["request_cache_hits"] = \
                self.node.request_cache.stats()["hits"]
            if ms is not None:
                serving["mesh_spmd"] = ms.mesh_queries
                serving["mesh_fallbacks"] = ms.mesh_fallbacks
                serving["mesh_rebuilds"] = ms.mesh_rebuilds
            return serving

        # section -> thunk: a narrow `/_nodes/stats/{metric}` request only
        # pays for the sections it asked for (the monitor sections alone are
        # several procfs reads — a scraper polling one cheap section every
        # few seconds must not do the full-dump work each time)
        def indices_stats():
            # per-index shard stats + the node's cache tiers (the reference
            # nests request_cache/filter_cache under nodes.<id>.indices too);
            # index names never collide with the tier keys (validate_index_name
            # rejects leading underscores — tier keys are plain but reserved)
            out = self.node.indices.stats()
            out["request_cache"] = self.node.request_cache.stats()
            out["filter_cache"] = self.node.filter_cache.stats()
            return out

        sections = {
            "indices": indices_stats,
            "transport": lambda: self.node.transport.stats,
            "thread_pool": lambda: self.node.threadpool.stats(),
            # overload protection: breaker hierarchy + admission control —
            # the operator's view of how close the node is to shedding load
            "breakers": lambda: self.node.breakers.stats(),
            "admission_control": lambda: self.node.actions.admission.stats(),
            # cross-request device micro-batching + end-to-end coordinator
            # latency percentiles (HistogramMetric — means hide the tail) +
            # the always-on query-shape insights registry (search.shapes:
            # occupancy, demotions, top shapes by cost — full entries at
            # GET /_insights/queries); `phases`: the searches this node
            # coordinated, by the trips they made to their shards (one where
            # the one shard's query phase hydrated the page, else two)
            "search": lambda: {
                "batcher": self.node.search_batcher.stats(),
                "latency": self.node.actions.search_latency.stats(),
                "phases": dict(self.node.actions.search_phases),
                "shapes": self.node.insights.stats()},
            # device capacity ledger: per-index/per-segment HBM residency by
            # tier + pack/repack timings + compile events by plan family
            "device": self._device_section,
            # index warmer: off-query-path pack scheduling + post-refresh
            # cache re-prime counters (warmer.py)
            "warmer": lambda: self.node.warmer.stats(),
            # stall watchdog + event journal occupancy
            "events": lambda: {
                "journal": self.node.events.stats(),
                "watchdog": self.node.watchdog.stats()},
            "search_serving": serving_stats,
            # response encode + socket write, which no span can hold
            "http": lambda: self.node.http.stats() if self.node.http else {},
            # request-scoped tracing: sample rate, ring occupancy, in-flight
            "tracing": lambda: self.node.tracer.stats(),
            # adaptive replica selection: per-copy rank inputs (latency EWMA/
            # p99, piggybacked queue + headroom, outstanding, decayed
            # failures), selection/probe counters, hedge budget
            "adaptive_routing": lambda: self.node.adaptive_routing.stats(),
            **self.node.monitor.sections(),
        }
        if metric and metric not in ("_all",):
            wanted = [m.strip() for m in str(metric).split(",") if m.strip()]
            unknown = [m for m in wanted if m not in sections and m != "_all"]
            if unknown:
                from .common.errors import IllegalArgumentError

                raise IllegalArgumentError(
                    f"unknown metric {unknown} for [/_nodes/stats]; known "
                    f"metrics are {sorted(sections)}")
            if "_all" not in wanted:
                sections = {k: sections[k] for k in sections if k in wanted}
        return {"cluster_name": self.node.cluster_service.state.cluster_name,
                "nodes": {self.node.node_id:
                          {k: build() for k, build in sections.items()}}}

    def _device_section(self):
        """The `/_nodes/stats` `device` section: the capacity ledger walk
        over this node's live shard searchers + the process compile rollup."""
        from .common.devicehealth import DEVICE_HEALTH
        from .common.jaxenv import (compile_events_by_family,
                                    compile_events_by_pool,
                                    compile_events_total, compile_seconds)
        from .ops.device_index import capacity_report

        out = capacity_report(self.node.indices)
        out["compile"] = {"total": compile_events_total(),
                          "by_family": compile_events_by_family(),
                          # what the events cost, and the persistent
                          # cache's hits and misses among them
                          **compile_seconds(),
                          # pool attribution: a warmed node's serving pools
                          # (search/flat/mesh) should read 0 here — every
                          # compile lands on warmer/startup threads
                          "by_pool": compile_events_by_pool()}
        out["compile_warming"] = self.node.compile_warming.stats()
        # per-fault-domain circuit states (common/devicehealth): the
        # operator's answer to "is any serving path degraded to host scoring"
        out["health"] = DEVICE_HEALTH.stats()
        return out

    def _resolve_node_ids(self, node_id):
        """Resolve a comma list of node ids/names (`_all`/None = every node)
        against cluster state; an unknown id is a 404 (NodeMissingError)."""
        from .common.errors import NodeMissingError

        state = self.node.cluster_service.state
        if node_id in (None, "", "_all"):
            return list(state.nodes.nodes)
        out = []
        for w in [s.strip() for s in str(node_id).split(",") if s.strip()]:
            if w == "_local":
                n = state.nodes.get(self.node.node_id)
                matched = [n] if n is not None else []
            elif w == "_master":
                matched = [state.nodes.master] if state.nodes.master else []
            else:
                matched = [n for n in state.nodes.nodes
                           if n.id == w or n.name == w]
            if not matched:
                raise NodeMissingError(w)
            out.extend(matched)
        # stable dedup (an id and its name may both appear in the list)
        seen = set()
        return [n for n in out if n.id not in seen and not seen.add(n.id)]

    def cluster_stats(self, node_id=None):
        """ref: action/admin/cluster/stats/TransportClusterStatsAction — the
        cluster-wide rollup: index/shard/doc counts aggregated by fanning the
        per-node stats through the client-exec proxy, node counts from state.

        `node_id` (the `/_cluster/stats/nodes/{node_id}` path param — comma
        list of ids or names, `_all` for everything) restricts the rollup to
        the named nodes; an unknown id is a 404, never a silent full dump."""
        from .client import A_CLIENT_EXEC

        state = self.node.cluster_service.state
        wanted = self._resolve_node_ids(node_id)
        wanted_ids = {n.id for n in wanted}
        # unassigned shards (node_id None) belong to every "whole cluster"
        # spelling — /_cluster/stats and /_cluster/stats/nodes/_all must
        # agree; only a NAMED-nodes view narrows to those nodes' shards
        all_nodes = node_id in (None, "", "_all")
        shards = [s for s in state.routing_table.all_shards()
                  if all_nodes or s.node_id in wanted_ids]
        doc_count = deleted = segments = 0
        per_node = {}
        for n in wanted:
            try:
                if n.id == self.node.node_id:
                    per_node[n.id] = self.nodes_stats()["nodes"][n.id]
                else:
                    r = self.node.transport.submit_request(
                        n, A_CLIENT_EXEC, {"method": "nodes_stats"},
                        timeout=10.0)
                    per_node[n.id] = r["r"]["nodes"][n.id]
            except SearchEngineError:
                continue  # a dropping node must not fail the rollup
        for stats in per_node.values():
            for idx in stats.get("indices", {}).values():
                for shard in idx.get("shards", {}).values():
                    if not shard.get("primary"):
                        continue  # docs count primaries only (reference)
                    doc_count += shard.get("docs", {}).get("count", 0)
                    deleted += shard.get("docs", {}).get("deleted", 0)
                    segments += shard.get("segments", 0)
        nodes = wanted
        count = {
            "total": len(nodes),
            "master_only": sum(1 for n in nodes if n.master_eligible and not n.data),
            "data_only": sum(1 for n in nodes if n.data and not n.master_eligible),
            "master_data": sum(1 for n in nodes if n.master_eligible and n.data),
            "client": sum(1 for n in nodes if not n.master_eligible and not n.data),
        }
        return {
            "timestamp": int(time.time() * 1000),
            "cluster_name": state.cluster_name,
            "status": self._health()["status"],
            "indices": {
                "count": len(state.metadata.index_names()),
                "shards": {
                    "total": len(shards),
                    "primaries": sum(1 for s in shards if s.primary),
                    "replication": (
                        (len(shards) - sum(1 for s in shards if s.primary))
                        / max(sum(1 for s in shards if s.primary), 1)),
                },
                "docs": {"count": doc_count, "deleted": deleted},
                "segments": {"count": segments},
            },
            "nodes": {
                "count": count,
                "versions": sorted({str(n.version_id) for n in nodes}),
            },
        }

    def nodes_shutdown(self, node_ids=None, delay_s: float = 0.2):
        return self.node.actions.nodes_shutdown(node_ids, delay_s=delay_s)

    # --- percolate ----------------------------------------------------------
    def percolate(self, index, body):
        return self.node.percolator.percolate(index, body)

    def count_percolate(self, index, body):
        return self.node.percolator.count_percolate(index, body)

    def mpercolate(self, requests):
        return self.node.percolator.multi_percolate(requests)

    # --- warmers ------------------------------------------------------------
    def put_warmer(self, index, name, body, doc_type=None):
        if doc_type:
            body = dict(body or {})
            body["types"] = [t for t in str(doc_type).split(",") if t]
        return self._local("indices:admin/warmers/put",
                           {"index": index or "_all", "name": name, "body": body})

    def delete_warmer(self, index, name):
        return self._local("indices:admin/warmers/delete",
                           {"index": index or "_all", "name": name})

    def get_warmer(self, index=None, name=None):
        state = self.node.cluster_service.state
        out = {}
        for idx in state.metadata.resolve_indices(index or "_all"):
            warmers = state.metadata.index(idx).warmers_dict()
            if name is not None:
                warmers = {w: s for w, s in warmers.items() if _name_matches(w, name)}
                if not warmers:
                    continue
            if not warmers and (index is None or _is_pattern(index)):
                continue
            out[idx] = {"warmers": warmers}
        return out

    # --- snapshots ----------------------------------------------------------
    def put_repository(self, name, body):
        return self.node.snapshots.put_repository(name, body)

    def get_repository(self, name=None):
        return self.node.snapshots.get_repository(name)

    def delete_repository(self, name):
        return self.node.snapshots.delete_repository(name)

    def verify_repository(self, name):
        return self.node.snapshots.verify_repository(name)

    def create_snapshot(self, repo, snapshot, body=None):
        return self.node.snapshots.create_snapshot(repo, snapshot, body)

    def get_snapshots(self, repo, snapshot=None):
        return self.node.snapshots.get_snapshots(repo, snapshot)

    def snapshot_status(self, repo, snapshot):
        return self.node.snapshots.snapshot_status(repo, snapshot)

    def delete_snapshot(self, repo, snapshot):
        return self.node.snapshots.delete_snapshot(repo, snapshot)

    def restore_snapshot(self, repo, snapshot, body=None):
        return self.node.snapshots.restore_snapshot(repo, snapshot, body)

    # --- plumbing -----------------------------------------------------------
    def _local(self, action, body):
        return self.node.transport.submit_request(self.node.local_node, action, body,
                                                  timeout=30.0)


def A(name: str) -> str:
    return name




def _settings_str(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    return str(v)


def _nest_keys(flat: dict) -> dict:
    """{"index.number_of_shards": "5"} → {"index": {"number_of_shards": "5"}}."""
    out: dict = {}
    for k, v in flat.items():
        parts = k.split(".")
        cur = out
        for p in parts[:-1]:
            nxt = cur.get(p)
            if not isinstance(nxt, dict):
                nxt = {}
                cur[p] = nxt
            cur = nxt
        cur[parts[-1]] = v
    return out


def _flatten_properties(props: dict, prefix: str = "", just_name: bool = False) -> dict:
    """Mapping properties tree → {"a.b": leaf_def} (multi-fields included). Leaves under
    an object with `path: just_name` are tagged so they also answer to their bare name
    (ref: object mapper path semantics used by get-field-mapping)."""
    out = {}
    for name, fdef in (props or {}).items():
        full = f"{prefix}{name}"
        if isinstance(fdef, dict) and isinstance(fdef.get("properties"), dict) and \
                fdef.get("type", "object") in ("object", "nested"):
            sub_just = just_name or fdef.get("path") == "just_name"
            out.update(_flatten_properties(fdef["properties"], full + ".", sub_just))
        else:
            leaf = dict(fdef) if isinstance(fdef, dict) else {}
            if just_name:
                leaf["_just_name"] = True
            out[full] = leaf
            if isinstance(fdef, dict) and isinstance(fdef.get("fields"), dict):
                for sub, sdef in fdef["fields"].items():
                    out[f"{full}.{sub}"] = dict(sdef) if isinstance(sdef, dict) else {}
    return out


def _status_at_least(status: str, wanted: str) -> bool:
    order = {"red": 0, "yellow": 1, "green": 2}
    return order.get(status, 0) >= order.get(wanted, 0)
