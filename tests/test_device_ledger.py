"""Device capacity ledger (ops/device_index + common/jaxenv) — ISSUE 13
tentpole (b).

Covers: the per-segment tier-bytes breakdown (consistent with
packed_resident_bytes), the pack/repack timing ledger (bounds, per-index
attribution, forget-on-delete), compile-event attribution by plan family
(jaxenv.compile_tag), the capacity report walk, the /_nodes/stats `device`
section + /{index}/_stats device stanza, and the per-index Prometheus
families' cardinality bound under index create/delete churn.
"""

from __future__ import annotations

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.ops.device_index import (
    PACK_LEDGER, PackLedger, capacity_report, ensure_blk_freqs,
    packed_for, packed_resident_bytes, packed_tier_bytes, segment_capacity)

from .harness import TestCluster

_SEG_SEQ = [0]


def _segment(tmp_path, n_docs=40):
    """One frozen segment via a throwaway engine (the _mk_engine idiom)."""
    _SEG_SEQ[0] += 1
    svc = MapperService(Settings.from_flat({}))
    eng = Engine(str(tmp_path / f"seg{_SEG_SEQ[0]}"), svc)
    for i in range(n_docs):
        eng.index("doc", str(i), {"body": f"alpha{i % 5} beta{i % 3}"})
    eng.refresh()
    seg = eng.acquire_searcher().segments[0]
    eng.close()
    return seg


class TestTierBytes:
    def test_tiers_sum_to_resident_postings_planes(self, tmp_path):
        seg = _segment(tmp_path)
        packed = packed_for(seg)
        tiers = packed_tier_bytes(packed)
        # postings tier == the resident planes packed_resident_bytes counts
        # (dense plane not faulted yet)
        assert tiers["postings"] == packed_resident_bytes(packed)
        assert tiers["dense_plane"] == 0
        ensure_blk_freqs(packed)
        tiers = packed_tier_bytes(packed)
        assert tiers["dense_plane"] > 0
        assert tiers["postings"] + tiers["dense_plane"] == \
            packed_resident_bytes(packed)
        # norms: live mask + per-field norm columns are accounted
        assert tiers["norms"] > 0

    def test_segment_capacity_row(self, tmp_path):
        seg = _segment(tmp_path)
        assert segment_capacity(_segment(tmp_path)) is None  # never packed
        packed = packed_for(seg)
        row = segment_capacity(seg)
        assert row is not None
        assert row["generation"] == seg.gen
        assert row["tf_layout"] == packed.tf_layout
        assert row["tiers"]["filter_masks"] == 0
        assert row["total_bytes"] == sum(row["tiers"].values())


class TestPackLedger:
    def test_record_and_stats(self):
        led = PackLedger()
        led.record("idx", 3, 1.5, 1024, "u8")
        led.record("idx", 4, 0.5, 2048, "u8", kind="remask")
        st = led.stats("idx")
        assert st["packs"] == 1 and st["remasks"] == 1
        assert st["pack_ms_total"] == 2.0
        assert [e["kind"] for e in st["recent"]] == ["pack", "remask"]
        assert led.stats("other") == {}

    def test_bounds(self):
        led = PackLedger()
        for i in range(PackLedger.MAX_INDICES + 10):
            led.record(f"i{i}", 1, 0.1, 10, "u8")
        assert len(led.stats()) == PackLedger.MAX_INDICES
        assert "i0" not in led.stats()  # LRU-evicted
        for _ in range(PackLedger.RING + 5):
            led.record("ring", 1, 0.1, 10, "u8")
        assert len(led.stats("ring")["recent"]) == PackLedger.RING

    def test_forget(self):
        led = PackLedger()
        led.record("gone", 1, 0.1, 10, "u8")
        led.forget("gone")
        assert led.stats("gone") == {}

    def test_packed_for_attributes_owner(self, tmp_path):
        seg = _segment(tmp_path)
        PACK_LEDGER.forget("owner-test")
        packed_for(seg, owner="owner-test")
        st = PACK_LEDGER.stats("owner-test")
        assert st["packs"] == 1
        assert st["recent"][0]["bytes"] > 0
        assert st["recent"][0]["tf_layout"] == "u8"
        PACK_LEDGER.forget("owner-test")


class TestCompileAttribution:
    def test_compile_tag_buckets_events(self):
        import jax
        import jax.numpy as jnp

        from elasticsearch_tpu.common.jaxenv import (
            compile_events_by_family, compile_tag)

        before = compile_events_by_family().get("aggs", 0)
        # a fresh jit with a process-unique shape guarantees one real compile
        n = 577  # odd prime-ish size no other test uses

        @jax.jit
        def f(x):
            return (x * 2.0).sum()

        with compile_tag("aggs"):
            f(jnp.zeros((n,), jnp.float32)).block_until_ready()
        after = compile_events_by_family().get("aggs", 0)
        assert after >= before + 1

    def test_unknown_tag_folds_to_untagged_and_outermost_wins(self):
        from elasticsearch_tpu.common import jaxenv

        with jaxenv.compile_tag("not-a-family"):
            assert jaxenv._tag_local.tag == "untagged"
        assert jaxenv._tag_local.tag is None
        # outermost scope wins: a percolation's inner sparse launch must
        # stay attributed to the workload that triggered it
        with jaxenv.compile_tag("percolate"):
            assert jaxenv._tag_local.tag == "percolate"
            with jaxenv.compile_tag("sparse"):
                assert jaxenv._tag_local.tag == "percolate"
            assert jaxenv._tag_local.tag == "percolate"
        assert jaxenv._tag_local.tag is None


# ---------------------------------------------------------------------------
# live cluster
# ---------------------------------------------------------------------------


def _boot(tmp_path, settings=None, indices=("led",)):
    cluster = TestCluster(n_nodes=1, data_root=tmp_path, seed=9,
                          settings=settings or {})
    cluster.start()
    c = cluster.client()
    for name in indices:
        c.create_index(name, {"settings": {"number_of_shards": 1,
                                           "number_of_replicas": 0}})
        cluster.ensure_green(name)
        for i in range(25):
            c.index(name, "doc", {"body": f"alpha{i % 4}", "n": i},
                    id=str(i))
        c.refresh(name)
    return cluster, c


@pytest.mark.insights
class TestLiveLedger:
    def test_capacity_report_and_stats_surfaces(self, tmp_path):
        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            c.search("led", {"query": {"match": {"body": "alpha1"}},
                             "size": 3})
            report = capacity_report(node.indices)
            assert "led" in report["indices"]
            led = report["indices"]["led"]
            assert led["totals"]["postings"] > 0
            assert led["totals"]["sim_tables"] > 0
            assert led["pack"]["packs"] >= 1
            assert led["pack"]["recent"][0]["ms"] >= 0
            assert report["total_bytes"] >= led["total_bytes"]
            # per-segment rows carry the tier taxonomy
            (shard_rows,) = led["shards"].values()
            for row in shard_rows:
                assert set(row["tiers"]) == {
                    "postings", "dense_plane", "positions_plane",
                    "sim_tables", "agg_rows", "agg_limbs", "sort_keys",
                    "norms", "filter_masks", "function_rows"}

            # /_nodes/stats device section (+ compile family rollup)
            st = c.nodes_stats(metric="device")
            (sections,) = st["nodes"].values()
            dev = sections["device"]
            assert dev["indices"]["led"]["totals"]["postings"] > 0
            assert "by_family" in dev["compile"]
            assert dev["compile"]["by_family"].get("sparse", 0) >= 1

            # /{index}/_stats device stanza (through the filtering Client)
            idx_stats = c.stats("led")
            assert set(idx_stats) == {"led"}
            assert idx_stats["led"]["device"]["totals"]["postings"] > 0
        finally:
            cluster.close()

    def test_filter_masks_tier_counts_resident_masks(self, tmp_path):
        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            filt = {"query": {"filtered": {
                "query": {"match": {"body": "alpha1"}},
                "filter": {"term": {"n": 3}}}}, "size": 3}
            for _ in range(3):  # 2nd sighting promotes to device residency
                c.search("led", filt)
            assert node.filter_cache.stats()["masks"] >= 1
            report = capacity_report(node.indices)
            assert report["indices"]["led"]["totals"]["filter_masks"] > 0
        finally:
            cluster.close()

    def test_function_rows_tier_counts_resident_rows(self, tmp_path):
        from elasticsearch_tpu.rest.controller import _prometheus_text

        cluster, c = _boot(tmp_path)
        node = next(iter(cluster.nodes.values()))
        try:
            body = {"query": {"function_score": {
                "query": {"match_all": {}},
                "functions": [{"field_value_factor": {
                    "field": "n", "factor": 2, "modifier": "log1p"}}]}},
                "size": 3}
            for _ in range(3):  # 2nd sighting promotes to device residency
                c.search("led", body)
            held = node.filter_cache.stats()["function_rows"]
            assert held["entries"] >= 1
            totals = capacity_report(node.indices)["indices"]["led"]["totals"]
            assert totals["function_rows"] == held["memory_size_in_bytes"] > 0
            assert totals["filter_masks"] == 0
            assert ('estpu_device_index_bytes{index="led",tier="function_rows"} '
                    f'{totals["function_rows"]}') in _prometheus_text(node)
        finally:
            cluster.close()

    def test_prometheus_cardinality_bounded_under_index_churn(self, tmp_path):
        """The satellite bound: create/delete of many indices keeps the
        per-index device-ledger families at their documented caps — labels
        exist only for LIVE indices, and the emission caps at
        telemetry.device.max_label_indices (overflow counted)."""
        from elasticsearch_tpu.rest.controller import _prometheus_text
        from tools.obs_smoke import _parse_prometheus

        names = tuple(f"churn{i}" for i in range(6))
        cluster, c = _boot(
            tmp_path, settings={"telemetry.device.max_label_indices": 3},
            indices=names)
        node = next(iter(cluster.nodes.values()))
        try:
            for name in names:
                c.search(name, {"query": {"match": {"body": "alpha1"}},
                                "size": 2})
            text = _prometheus_text(node)
            _parse_prometheus(text)

            def labels(fam):
                return {ln.split('index="', 1)[1].split('"', 1)[0]
                        for ln in text.splitlines()
                        if ln.startswith(fam + "{")}

            assert len(labels("estpu_device_index_bytes")) == 3
            assert len(labels("estpu_device_pack_total")) == 3
            assert "estpu_device_ledger_omitted_indices 3" in text

            # delete most indices: labels track the LIVE set, and the pack
            # ledger forgets the deleted ones
            for name in names[1:]:
                c.delete_index(name)
            text = _prometheus_text(node)
            _parse_prometheus(text)
            assert labels("estpu_device_index_bytes") == {names[0]}
            assert PACK_LEDGER.stats(names[1]) == {}
            assert "estpu_device_ledger_omitted_indices 0" in text
        finally:
            cluster.close()

    def test_remask_recorded_on_tombstone_refresh(self, tmp_path):
        cluster, c = _boot(tmp_path)
        try:
            c.search("led", {"query": {"match": {"body": "alpha1"}},
                             "size": 3})
            packs0 = PACK_LEDGER.stats("led").get("packs", 0)
            c.delete("led", "doc", "3")
            c.refresh("led")
            c.search("led", {"query": {"match": {"body": "alpha1"}},
                             "size": 3})
            st = PACK_LEDGER.stats("led")
            # the tombstone refresh either remasked the packed segment or a
            # new view repacked — either way the ledger saw the work
            assert st.get("remasks", 0) >= 1 or st.get("packs", 0) > packs0
        finally:
            cluster.close()


class TestTierMathProperties:
    def test_plane_bytes_agree_with_numpy(self, tmp_path):
        seg = _segment(tmp_path, 10)
        packed = packed_for(seg)
        tiers = packed_tier_bytes(packed)
        expect = sum(int(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
                     for p in (packed.blk_docs, packed.blk_tf, packed.blk_nb))
        assert tiers["postings"] == expect
