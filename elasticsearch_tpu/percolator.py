"""Percolator: reverse search — match a DOCUMENT against registered queries.

Analogue of percolator/PercolatorService.java + index/percolator/ (SURVEY.md §2.9):
queries are registered as documents under the special `.percolator` type of an index;
`percolate(doc)` parses the document into an in-memory single-doc segment and evaluates
every registered query against it, returning the ids of matching queries.

TPU note: percolation evaluates MANY queries against ONE doc — the transpose of the
scoring kernel's many-docs-one-query layout. The host scorer over a 1-doc segment is the
right tool; a device batch variant (queries × 1-doc) is a later-round optimization for
large registries.
"""

from __future__ import annotations

import threading

from .common.errors import PercolateError
from .mapper import MapperService
from .index.segment import SegmentBuilder
from .search.execute import HostScorer, ShardContext
from .search.queries import Query, parse_query

PERCOLATOR_TYPE = ".percolator"


class PercolatorRegistry:
    """Per-index registry of parsed percolator queries (ref: index/percolator/
    PercolatorQueriesRegistry — kept in sync with .percolator-type docs)."""

    DEVICE_BATCH_MIN = 64  # below this the host loop beats device dispatch

    def __init__(self):
        self._queries: dict[str, tuple[dict, Query]] = {}
        self._lock = threading.Lock()

    def register(self, query_id: str, body: dict):
        if "query" not in body:
            raise PercolateError("percolator document requires a [query]")
        q = parse_query(body["query"])
        with self._lock:
            self._queries[query_id] = (body, q)

    def unregister(self, query_id: str):
        with self._lock:
            self._queries.pop(query_id, None)

    def count(self) -> int:
        return len(self._queries)

    def percolate(self, doc: dict, mapper_service: MapperService,
                  type_name: str = "doc", filter_ids=None) -> list[str]:
        """Build a 1-doc in-memory segment from `doc`, run every registered query."""
        mapper = mapper_service.mapper_for(type_name)
        parsed = mapper.parse(doc, doc_id="_percolate")
        builder = SegmentBuilder(gen=0)
        builder.add(parsed)
        seg = builder.freeze()

        class _OneDocSearcher:
            segments = [seg]
            bases = [0]
            max_doc = seg.doc_count

            def doc_freq(self, field, term):
                return seg.doc_freq(field, term)

            def field_stats(self, field):
                from .index.segment import FieldStats as FS

                return seg.field_stats.get(field) or FS()

            def live_doc_count(self):
                return seg.live_count()

            def resolve(self, g):
                return seg, g

        # late import loop guard
        from .index.segment import FieldStats  # noqa: F401

        ctx = ShardContext(_OneDocSearcher(), mapper_service)
        matches = []
        with self._lock:
            items = list(self._queries.items())
        if filter_ids is not None:
            items = [(qid, v) for qid, v in items if qid in filter_ids]

        # reverse search as ONE batched kernel launch: registered queries that
        # lower flat score against the 1-doc segment together — the percolation
        # cost the reference pays per query (PercolatorService's per-query
        # memory-index search) amortizes into a single device program. Small
        # registries stay on the host loop (dispatch would dominate).
        host_items = items
        if len(items) >= self.DEVICE_BATCH_MIN:
            from .search.execute import execute_flat_batch, lower_flat
            from .search.service import SERVING_COUNTERS

            flat_plans, flat_qids, rest = [], [], []
            for qid, (_body, query) in items:
                try:
                    plan = lower_flat(query, ctx)
                except Exception:  # noqa: BLE001 — lowering trouble → host path
                    plan = None
                if plan is not None and plan.const is None:
                    flat_plans.append(plan)
                    flat_qids.append(qid)
                else:
                    rest.append((qid, (_body, query)))
            # the gate's rationale is batch size: only launch when the FLAT
            # count amortizes dispatch (a mostly-non-flat registry stays host)
            if len(flat_plans) >= self.DEVICE_BATCH_MIN:
                try:
                    from .common.jaxenv import compile_tag

                    # capacity-ledger attribution: compiles triggered by the
                    # batched percolation launch land under "percolate", not
                    # the inner kernels' own families
                    with compile_tag("percolate"):
                        tds = execute_flat_batch(flat_plans, ctx, 1)
                    matches.extend(qid for qid, td in zip(flat_qids, tds)
                                   if td.total > 0)
                    host_items = rest
                    SERVING_COUNTERS["device_percolate"] += 1
                except Exception:  # noqa: BLE001 — any batch failure falls back
                    matches = []
                    host_items = items
                    SERVING_COUNTERS["device_percolate_fallbacks"] += 1

        for qid, (_body, query) in host_items:
            scorer = HostScorer(ctx, seg)
            try:
                _, match = scorer.eval(query)
            except Exception:  # noqa: BLE001 — a bad query must not break the rest
                continue
            if bool((match & seg.parent_mask).any()):
                matches.append(qid)
        return sorted(matches)


class PercolatorService:
    """Node-level: registries per index, fed by the engine write path and exposed via
    the REST /_percolate APIs."""

    def __init__(self, node):
        self.node = node
        self.registries: dict[str, PercolatorRegistry] = {}

    def registry(self, index: str) -> PercolatorRegistry:
        r = self.registries.get(index)
        if r is None:
            r = PercolatorRegistry()
            self.registries[index] = r
        return r

    def register_query(self, index: str, query_id: str, body: dict):
        self.registry(index).register(query_id, body)

    def unregister_query(self, index: str, query_id: str):
        self.registry(index).unregister(query_id)

    def percolate(self, index: str, body: dict | None, doc_type: str = "doc",
                  doc_id=None, version=None, percolate_index=None,
                  percolate_type=None) -> dict:
        """Percolate an inline doc, or an EXISTING doc by id (optionally against a
        different percolator index — ref: PercolatorService existing-doc path)."""
        body = body or {}
        if doc_id is not None:
            from .common.errors import DocumentMissingError, VersionConflictError

            g = self.node.actions.get_doc(index, doc_type or "_all", str(doc_id))
            if not g.get("found"):
                raise DocumentMissingError(
                    f"[{index}][{doc_type}][{doc_id}] missing")
            if version is not None and int(version) != int(g.get("_version", -1)):
                raise VersionConflictError(f"{doc_type}#{doc_id}",
                                           g.get("_version", -1), int(version))
            doc = g.get("_source") or {}
            target = percolate_index or index
            target_type = percolate_type or doc_type
        else:
            doc = body.get("doc")
            if doc is None:
                raise PercolateError("percolate request requires [doc]")
            target = index
            target_type = doc_type
        svc = self.node.indices.index_service(target)
        reg = self.registry(target)
        matches = reg.percolate(doc, svc.mapper_service, type_name=target_type or "doc")
        return {
            "total": len(matches),
            "_shards": {"total": 1, "successful": 1, "failed": 0},
            "matches": [{"_index": target, "_id": qid} for qid in matches],
        }

    def count_percolate(self, index: str, body: dict | None, doc_type: str = "doc",
                        doc_id=None) -> dict:
        r = self.percolate(index, body, doc_type=doc_type, doc_id=doc_id)
        return {"total": r["total"], "_shards": r["_shards"]}

    def multi_percolate(self, requests: list[tuple[dict, dict]],
                        default_index=None, default_type=None) -> dict:
        """ndjson multi-percolate (ref: TransportMultiPercolateAction): header lines
        {"percolate": {...}} / {"count": {...}} paired with doc bodies."""
        responses = []
        for header, body in requests:
            (op, params), = header.items() if header else (("percolate", {}),)
            try:
                kwargs = dict(
                    index=params.get("index", default_index),
                    body=body,
                    doc_type=params.get("type", default_type) or "doc",
                    doc_id=params.get("id"),
                    percolate_index=params.get("percolate_index"),
                    percolate_type=params.get("percolate_type"),
                )
                if op == "count":
                    kwargs.pop("percolate_index")
                    kwargs.pop("percolate_type")
                    responses.append(self.count_percolate(
                        kwargs["index"], body, doc_type=kwargs["doc_type"],
                        doc_id=kwargs["doc_id"]))
                else:
                    responses.append(self.percolate(**kwargs))
            except Exception as e:  # noqa: BLE001
                from .common.errors import SearchEngineError

                if isinstance(e, SearchEngineError):
                    responses.append({"error": e.es1_string(), "status": e.status})
                else:
                    responses.append({"error": str(e)})
        return {"responses": responses}
