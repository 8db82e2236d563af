"""Concurrent scatter-gather: the query phase must fan to all shards at once.

The reference dispatches every shard's first phase asynchronously and reduces on
completion (TransportSearchTypeAction.java:135-216) — N-shard latency is max(shard),
not sum(shard). These tests inject a per-shard delay and assert wall-clock stays far
under the sequential sum, and that per-shard failover still works when dispatch is
concurrent.
"""

import time

import pytest

from elasticsearch_tpu.actions import A_QUERY_PHASE, A_QUERY_PROGRESS
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.transport.local import LocalTransportRegistry

pytestmark = pytest.mark.mesh

SHARDS = 6
DELAY = 0.25


@pytest.fixture()
def node(tmp_path):
    registry = LocalTransportRegistry()
    n = Node(name="par0", registry=registry, data_path=str(tmp_path),
             settings={"index.number_of_shards": SHARDS,
                       "index.number_of_replicas": 0})
    n.start([n.local_node.transport_address])
    n.wait_for_master()
    yield n
    n.close()


def _slow_query_phase(node, delay=DELAY):
    """Re-register the query-phase handler with an injected per-shard delay.
    This test targets the TRANSPORT scatter-gather specifically — disable the mesh
    serving path, which would otherwise bypass A_QUERY_PHASE entirely (and put its
    first XLA compile inside the timed region)."""
    node.actions.mesh_serving.enabled = False
    original = node.transport.handlers[A_QUERY_PHASE].fn

    def slow(request, channel):
        time.sleep(delay)
        return original(request, channel)

    node.transport.register_handler(A_QUERY_PHASE, slow, executor="search")


def test_query_phase_is_concurrent(node):
    client = node.client()
    client.create_index("t", {"settings": {"index.number_of_shards": SHARDS,
                                           "index.number_of_replicas": 0}})
    for i in range(SHARDS * 3):
        client.index("t", "doc", {"body": f"term{i} common"}, id=str(i))
    client.refresh("t")

    _slow_query_phase(node)
    # warm the exact query once OUTSIDE the timed region: whether the device
    # program is already compiled depends on which tests ran earlier in the
    # process, and a cold first compile (~0.7s) dwarfs the concurrency margin
    client.search(["t"], {"query": {"match": {"body": "common"}}})
    t0 = time.monotonic()
    r = client.search(["t"], {"query": {"match": {"body": "common"}}})
    took = time.monotonic() - t0
    assert r["_shards"]["successful"] == SHARDS
    assert r["hits"]["total"] == SHARDS * 3
    # sequential would be >= SHARDS * DELAY (1.5 s); concurrent ≈ DELAY + overhead
    assert took < SHARDS * DELAY * 0.6, f"search took {took:.2f}s — looks sequential"


def test_failover_still_works_under_concurrent_dispatch(tmp_path):
    registry = LocalTransportRegistry()
    n1 = Node(name="fo1", registry=registry, data_path=str(tmp_path / "n1"),
              settings={"index.number_of_shards": 2,
                        "index.number_of_replicas": 1})
    n1.start([n1.local_node.transport_address])
    n1.wait_for_master()
    n2 = Node(name="fo2", registry=registry, data_path=str(tmp_path / "n2"))
    n2.start([n1.local_node.transport_address])
    n2.wait_for_master()
    client = n1.client()
    client.create_index("t", {"settings": {"index.number_of_shards": 2,
                                           "index.number_of_replicas": 1}})
    for i in range(8):
        client.index("t", "doc", {"body": "common"}, id=str(i))
    node_for = {n1.node_id: n1, n2.node_id: n2}

    # wait for replicas to go green so both copies hold data
    h = client.cluster_health("t", wait_for_status="green")
    assert h["status"] == "green"
    client.refresh("t")

    # make every query attempt against n2 fail: the coordinator must fail over to
    # the other copy concurrently and still return full results
    from elasticsearch_tpu.common.errors import SearchEngineError

    def broken(request, channel):
        raise SearchEngineError("injected shard failure")

    n2.transport.register_handler(A_QUERY_PHASE, broken, executor="search")
    for _ in range(6):  # preference rotation may or may not pick n2 first; try a few
        r = client.search(["t"], {"query": {"match": {"body": "common"}}})
        assert r["hits"]["total"] == 8
        assert r["_shards"]["successful"] == 2

    # a HUNG copy (accepts the request, never responds) must also fail over — the
    # per-attempt timer, not the error path, advances the chain. Its node compiles
    # nothing, so the failover costs ONE window plus the question put to the node
    def hung(request, channel):
        time.sleep(30)

    asked = []

    def not_compiling(request, channel):
        asked.append(1)
        return {"compile_idle_s": None}

    n2.transport.register_handler(A_QUERY_PHASE, hung, executor="search")
    n2.transport.register_handler(A_QUERY_PROGRESS, not_compiling,
                                  executor="management")
    old_timeout = type(n1.actions).QUERY_ATTEMPT_TIMEOUT
    type(n1.actions).QUERY_ATTEMPT_TIMEOUT = 0.3
    try:
        # start at n2: after the failures above the selector ranks it last
        t0 = time.monotonic()
        r = client.search(["t"], {"query": {"match": {"body": "common"}}},
                          preference=f"_prefer_node:{n2.node_id}")
        took = time.monotonic() - t0
        assert r["hits"]["total"] == 8
        assert r["_shards"]["successful"] == 2
        assert took < 5.0
        assert asked  # the timer asked n2 before it gave its copies up
    finally:
        type(n1.actions).QUERY_ATTEMPT_TIMEOUT = old_timeout
        n2.close()
        n1.close()


@pytest.mark.parametrize("busy", ["compile_idle_s", "pack_idle_s"])
def test_a_compiling_or_packing_copy_is_late_not_wedged(tmp_path, busy):
    """A cold device compiles on the query path (88.5 s for a first search on a
    v5e, PR 22), and a merged segment's device concat keeps the first search
    after a force-merge waiting for 84-120 s at 250,000 documents (PR 31): the
    attempt timer asks the copy's node and waits on while it reports a recent
    compile or a pack in flight, so the one copy answers instead of failing at
    the first window. A node that reports neither is given up after one window."""
    registry = LocalTransportRegistry()
    n1 = Node(name="cc1", registry=registry, data_path=str(tmp_path / "n1"),
              settings={"index.number_of_shards": 1,
                        "index.number_of_replicas": 0})
    n1.start([n1.local_node.transport_address])
    n1.wait_for_master()
    client = n1.client()
    # one shard: co-located shards would be served by the mesh, with no attempt
    client.create_index("t", {"settings": {"index.number_of_shards": 1,
                                           "index.number_of_replicas": 0}})
    for i in range(8):
        client.index("t", "doc", {"body": "common"}, id=str(i))
    client.refresh("t")
    body = {"query": {"match": {"body": "common"}}}
    assert client.search(["t"], body)["hits"]["total"] == 8  # warm: no real compile

    # what the node really reports: seconds since its last compile, or None
    real = n1.actions._s_query_progress({}, None)
    assert set(real) == {"compile_idle_s", "pack_idle_s"}
    assert all(v is None or v >= 0.0 for v in real.values())

    serve = n1.actions._s_query_phase
    windows = 4  # the copy answers in the fourth window
    delay = [0.3 * (windows - 0.5)]

    def late(request, channel):
        time.sleep(delay[0])
        return serve(request, channel)

    idle = {"compile_idle_s": None, "pack_idle_s": None}
    reports = {**idle, busy: 0.01}
    asked = []

    def progress(request, channel):
        asked.append(1)
        return dict(reports)

    n1.transport.register_handler(A_QUERY_PHASE, late, executor="search")
    n1.transport.register_handler(A_QUERY_PROGRESS, progress, executor="management")
    # the attempt timer is the failover chain's, and a search whose one shard has
    # its only copy on the asking node builds no chain (it runs its query phase on
    # the asking thread: actions._inline_node): take that path away, so that this
    # one copy is asked as a copy with a replica elsewhere is
    n1.actions._inline_node = lambda *a, **kw: None
    cls = type(n1.actions)
    old_timeout = cls.QUERY_ATTEMPT_TIMEOUT
    cls.QUERY_ATTEMPT_TIMEOUT = 0.3
    try:
        r = client.search(["t"], body)
        assert (r["_shards"]["successful"], r["_shards"]["failed"]) == (1, 0)
        assert r["hits"]["total"] == 8
        assert 1 <= len(asked) <= windows - 1  # as each window ran out
        # the same late copy on a node that does neither: one window, then failed
        reports[busy] = None
        t0 = time.monotonic()
        r = client.search(["t"], body)
        assert r["_shards"]["failed"] == 1 and r["_shards"]["successful"] == 0
        assert time.monotonic() - t0 < delay[0]
        # ... and the extensions are bounded: a copy later than all of them fails
        reports[busy] = 0.01
        cls.QUERY_ATTEMPT_TIMEOUT = 0.05
        delay[0] = 3.0
        del asked[:]
        r = client.search(["t"], body)
        assert r["_shards"]["failed"] == 1
        assert len(asked) == cls.QUERY_ATTEMPT_EXTENSIONS
    finally:
        cls.QUERY_ATTEMPT_TIMEOUT = old_timeout
        n1.close()
