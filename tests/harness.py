"""Test harness: the in-process multi-node cluster + fault injection utilities.

ref: test/TestCluster.java:88 (N real nodes in one JVM, kill/restart APIs),
test/store/MockFSDirectoryService.java:35 (random IOExceptions on store reads),
test/engine/MockInternalEngine.java:58 (suite fails on leaked searchers — here:
an acquire-tracking engine wrapper usable as an assertion context).

Usage:
    with TestCluster(n_nodes=3, data_root=tmp_path, seed=7) as cluster:
        cluster.client().create_index("idx", {"settings": {
            "number_of_shards": 4, "number_of_replicas": 1}})
        cluster.ensure_green("idx")
        cluster.kill_node(cluster.master_name())   # failover
"""

from __future__ import annotations

import contextlib
import random

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.transport.faults import FaultPolicy
from elasticsearch_tpu.transport.local import LocalTransportRegistry


class TestCluster:
    """N real nodes on one in-process transport registry (the reference boots N
    InternalNodes in one JVM — same trick, same failover surface)."""

    __test__ = False  # utility class, not a pytest collection target

    def __init__(self, n_nodes: int = 3, data_root=None, settings=None,
                 name: str = "tc", seed: int | None = None):
        self.registry = LocalTransportRegistry()
        self.n_nodes = n_nodes
        self.data_root = str(data_root) if data_root else None
        self.settings = dict(settings or {})
        self.name = name
        self.rng = random.Random(seed)
        self.nodes: dict[str, Node] = {}
        self._counter = 0

    # -- lifecycle ---------------------------------------------------------
    def start(self, n_nodes: int | None = None):
        for _ in range(n_nodes if n_nodes is not None else self.n_nodes):
            self.add_node()
        self.nodes[next(iter(self.nodes))].wait_for_master()
        return self

    def add_node(self) -> Node:
        self._counter += 1
        nname = f"{self.name}{self._counter}"
        node = Node(name=nname, registry=self.registry,
                    settings=dict(self.settings),
                    data_path=(f"{self.data_root}/{nname}" if self.data_root
                               else None))
        node.start([node.local_node.transport_address] if not self.nodes else None)
        # block until the join's state publish lands: a client bound to this
        # node before then sees an EMPTY metadata (version 0) and raises
        # IndexMissing on perfectly healthy indices (observed as a chaos-suite
        # flake when client() picked a just-added node)
        node.wait_for_master(timeout=15.0)
        self.nodes[nname] = node
        return node

    def kill_node(self, name: str):
        """Hard-stop a node (the reference's TestCluster.stopRandomNode)."""
        node = self.nodes.pop(name)
        node.close()

    def kill_random_node(self, exclude_master: bool = False) -> str:
        names = list(self.nodes)
        if exclude_master:
            m = self.master_name()
            names = [n for n in names if n != m] or names
        victim = self.rng.choice(names)
        self.kill_node(victim)
        return victim

    def master_name(self) -> str | None:
        for name, node in self.nodes.items():
            state = node.cluster_service.state
            if state.nodes.master_id == node.local_node.id:
                return name
        return None

    def client(self):
        """A client on a random live node (the reference randomizes too)."""
        return self.nodes[self.rng.choice(list(self.nodes))].client()

    # -- fault injection (transport/faults.py) -----------------------------
    def fault_policy(self, node_name: str, seed: int | None = None) -> FaultPolicy:
        """Install (or return the already-installed) FaultPolicy on one live
        node's TransportService — the MockTransportService hook. An EXPLICIT
        seed always installs a fresh policy (replayability demands a pristine
        RNG, not one another test already advanced); without a seed, an
        existing policy is reused and a new one draws from the cluster RNG."""
        service = self.nodes[node_name].transport
        if seed is not None:
            FaultPolicy(seed).install(service)
        elif service.fault_policy is None:
            FaultPolicy(self.rng.randrange(2 ** 31)).install(service)
        return service.fault_policy

    def clear_faults(self):
        """Drop every installed fault rule on every live node."""
        for node in self.nodes.values():
            if node.transport.fault_policy is not None:
                node.transport.fault_policy.clear()

    def address(self, node_name: str) -> str:
        """A node's transport address — the `node=` pattern FaultRules match."""
        return self.nodes[node_name].local_node.transport_address

    def ensure_green(self, index=None, timeout: float = 30.0):
        h = self.client().cluster_health(index, wait_for_status="green",
                                         timeout=timeout)
        assert h["status"] == "green", h
        return h

    def close(self):
        for node in list(self.nodes.values()):
            with contextlib.suppress(Exception):
                node.close()
        self.nodes.clear()

    def __enter__(self):
        if not self.nodes:
            self.start()
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------


class FaultyStore:
    """Wraps a shard's Store so reads fail with IOError at a given rate —
    MockFSDirectoryService's random-IOException wrapper, shrunk to the read path
    that peer recovery and gateway restore exercise."""

    def __init__(self, inner, fail_rate: float = 0.3, seed: int = 0):
        self._inner = inner
        self._rng = random.Random(seed)
        self.fail_rate = fail_rate
        self.reads = 0
        self.failures = 0

    def read_segment(self, gen, verify=None):
        self.reads += 1
        if self._rng.random() < self.fail_rate:
            self.failures += 1
            raise IOError(f"injected read failure (segment {gen})")
        return self._inner.read_segment(gen, verify)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class SearcherLeakTracker:
    """Counts engine searcher acquisitions inside a scope — MockInternalEngine's
    INFLIGHT_ENGINE_SEARCHERS check. Searchers here are snapshot objects released
    by GC, so the assertable invariant is acquisition-count sanity (no unbounded
    growth per request), not explicit release."""

    def __init__(self, engine):
        self.engine = engine
        self.acquired = 0
        self._orig = None

    def __enter__(self):
        orig = self.engine.acquire_searcher
        self._orig = orig

        def tracked():
            self.acquired += 1
            return orig()

        self.engine.acquire_searcher = tracked
        return self

    def __exit__(self, *exc):
        self.engine.acquire_searcher = self._orig


def run_query_phases(batcher, ctx, bodies, **ctx_kw):
    """Every body's query phase from its own thread on a copy of `ctx` wired
    to `batcher`; returns the ShardQueryResults (or the exception raised)."""
    import threading

    from elasticsearch_tpu.search import ShardContext
    from elasticsearch_tpu.search.service import (execute_query_phase,
                                                  parse_search_body)

    bctx = ShardContext(ctx.searcher, ctx.mapper_service, ctx.similarity_service,
                        batcher=batcher, **ctx_kw)
    reqs = [parse_search_body(b) for b in bodies]
    out = [None] * len(reqs)

    def worker(i):
        try:
            out[i] = execute_query_phase(bctx, reqs[i], use_device=True)
        except Exception as e:  # noqa: BLE001 — surfaced by the caller's asserts
            out[i] = e

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    return out


def run_as_one_batch(ctx, bodies):
    """run_query_phases through ONE DeviceBatcher that flushes when all the
    bodies are queued; returns (results, the batcher's stats)."""
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.search.batcher import DeviceBatcher

    batcher = DeviceBatcher(Settings.from_flat({
        "search.batch.max_batch": str(len(bodies)),
        "search.batch.linger_ms": "5000"}))
    try:
        return run_query_phases(batcher, ctx, bodies), batcher.stats()
    finally:
        batcher.shutdown()
