"""Tribe node: inner member per cluster, merged read view, first-wins conflicts,
write/metadata blocks. ref: tribe/TribeService.java."""

import time

import pytest

from elasticsearch_tpu.common.errors import ClusterBlockError, IndexMissingError
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.transport.local import LocalTransportRegistry


@pytest.fixture()
def two_clusters(tmp_path):
    reg_a, reg_b = LocalTransportRegistry(), LocalTransportRegistry()
    a = Node(name="ca1", registry=reg_a, data_path=str(tmp_path / "a"))
    a.start([a.local_node.transport_address])
    a.wait_for_master()
    b = Node(name="cb1", registry=reg_b, data_path=str(tmp_path / "b"))
    b.start([b.local_node.transport_address])
    b.wait_for_master()
    ca, cb = a.client(), b.client()
    for c, idx, word in ((ca, "books", "novel"), (cb, "films", "cinema")):
        c.create_index(idx, {"settings": {"number_of_shards": 1,
                                          "number_of_replicas": 0}})
        c.cluster_health(wait_for_status="green")
        c.index(idx, "doc", {"t": f"{word} common"}, id="1")
        c.index(idx, "doc", {"t": f"{word} extra"}, id="2")
        c.refresh(idx)
    # same-named index in BOTH clusters: tribe must keep the FIRST (t1 = cluster a)
    for c, val in ((ca, "alpha"), (cb, "beta")):
        c.create_index("shared", {"settings": {"number_of_shards": 1,
                                               "number_of_replicas": 0}})
        c.cluster_health(wait_for_status="green")
        c.index("shared", "doc", {"t": val}, id="1")
        c.refresh("shared")
    yield (a, reg_a), (b, reg_b), tmp_path
    a.close()
    b.close()


def make_tribe(tmp_path, reg_a, reg_b, extra=None):
    settings = {"tribe.t1.cluster.group": "a", "tribe.t2.cluster.group": "b"}
    settings.update(extra or {})
    t = Node(name="tr1", settings=settings, data_path=str(tmp_path / "tr"),
             registry=LocalTransportRegistry(),
             tribe_registries={"t1": reg_a, "t2": reg_b})
    t.start([t.local_node.transport_address])
    return t


class TestTribe:
    def test_reads_route_and_merge(self, two_clusters):
        (a, reg_a), (b, reg_b), tmp = two_clusters
        t = make_tribe(tmp, reg_a, reg_b)
        try:
            c = t.client()
            # single-index reads route to the owning cluster
            r = c.search("books", {"query": {"term": {"t": "novel"}}})
            assert r["hits"]["total"] == 2
            g = c.get("films", "doc", "1")
            assert g["_source"]["t"] == "cinema common"
            # cross-tribe search merges both clusters
            r = c.search("_all", {"query": {"term": {"t": "common"}}, "size": 10})
            assert r["hits"]["total"] == 2
            found = {h["_index"] for h in r["hits"]["hits"]}
            assert found == {"books", "films"}
            assert c.count("_all")["count"] >= 5
        finally:
            t.close()

    def test_conflicting_index_first_wins(self, two_clusters):
        (a, reg_a), (b, reg_b), tmp = two_clusters
        t = make_tribe(tmp, reg_a, reg_b)
        try:
            g = t.client().get("shared", "doc", "1")
            assert g["_source"]["t"] == "alpha"  # t1 configured first
        finally:
            t.close()

    def test_writes_route_unless_blocked(self, two_clusters):
        (a, reg_a), (b, reg_b), tmp = two_clusters
        t = make_tribe(tmp, reg_a, reg_b)
        try:
            c = t.client()
            c.index("books", "doc", {"t": "novel added"}, id="3")
            c.refresh("books")
            assert a.client().get("books", "doc", "3")["found"]
            with pytest.raises(ClusterBlockError):
                c.create_index("newidx", {})  # metadata ops: no master on a tribe
            with pytest.raises(IndexMissingError):
                c.get("nowhere", "doc", "1")
        finally:
            t.close()

    def test_write_block_setting(self, two_clusters):
        (a, reg_a), (b, reg_b), tmp = two_clusters
        t = make_tribe(tmp, reg_a, reg_b, {"tribe.blocks.write": True})
        try:
            with pytest.raises(ClusterBlockError):
                t.client().index("books", "doc", {"t": "x"}, id="9")
        finally:
            t.close()

    def test_cross_tribe_sorted_search(self, two_clusters):
        (a, reg_a), (b, reg_b), tmp = two_clusters
        ca, cb = a.client(), b.client()
        for c, idx, vals in ((ca, "books", (30, 10)), (cb, "films", (20, 40))):
            for i, v in enumerate(vals):
                c.index(idx, "doc", {"t": "sortme", "rank": v}, id=f"s{i}")
            c.refresh(idx)
        t = make_tribe(tmp, reg_a, reg_b)
        try:
            r = t.client().search("_all", {
                "query": {"term": {"t": "sortme"}},
                "sort": [{"rank": "asc"}], "size": 10})
            ranks = [h["sort"][0] for h in r["hits"]["hits"]]
            assert ranks == [10, 20, 30, 40]  # interleaved across tribes, asc
            r = t.client().search("_all", {
                "query": {"term": {"t": "sortme"}},
                "sort": [{"rank": {"order": "desc"}}], "size": 2, "from": 1})
            assert [h["sort"][0] for h in r["hits"]["hits"]] == [30, 20]
        finally:
            t.close()

    def test_merged_health(self, two_clusters):
        (a, reg_a), (b, reg_b), tmp = two_clusters
        t = make_tribe(tmp, reg_a, reg_b)
        try:
            # an inner member joins its cluster asynchronously (it can start with
            # master=None): give its view a moment to reach the merged health
            deadline = time.monotonic() + 10.0
            while True:
                h = t.client().cluster_health()
                if h["number_of_nodes"] >= 4 or time.monotonic() > deadline:
                    break
                time.sleep(0.05)
            assert h["status"] in ("green", "yellow")
            assert h["number_of_nodes"] >= 4  # 2 cluster nodes + 2 inner members
        finally:
            t.close()
