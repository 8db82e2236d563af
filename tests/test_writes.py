"""Heavy-write serving (ISSUE 14): incremental device-index packing — delta
packs, off-query-path packing, and background device compaction.

Unit half: pack-ledger kind/pool vocabulary (delta_pack/compact + pool
attribution), the in-flight pack Future coordination (a racing search WAITS
instead of duplicating the pack; a cancelled warm unblocks; copy-on-write
views drop stale futures), compaction concat parity (bitwise-identical
planes vs pack_segment(merged), tf-rung widening, the exact breaker
estimate, every ineligibility fallback), the off-lock merge (acquire_searcher
never blocks on merge compute; a concurrent tombstone ABORTS the publish
instead of resurrecting the delete), the incremental _uid_index update, and
request-cache hot-key tracking.

Chaos half (live cluster): a warmed continuous-indexing loop serves with
ZERO query-path packs (ledger pool attribution + 0 recompiles under hard
transfer_guard("disallow")), base+delta scores are bitwise-identical to a
cold monolithic repack, a fielddata breaker trip during a delta pack
degrades to the host scorer (correct results, no 5xx), a compaction
publishing mid-search serves the old view while searches complete un-blocked,
recovery replays onto delta-aware packs, the warmer re-primes the request
cache so the first post-refresh sighting of a hot body is a HIT, and the
`/_nodes/stats` warmer section + `/{index}/_stats` device stanza report the
new rows.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.segment import SegmentBuilder, merge_segments
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.ops.device_index import (
    BLOCK,
    PACK_LEDGER,
    PackLedger,
    begin_warm,
    cancel_warm,
    concat_estimate_bytes,
    device_counts,
    concat_source_packs,
    pack_segment,
    pack_segment_concat,
    pack_shape_math,
    packed_for,
    run_warm,
    tf_plane_itemsize,
)
from elasticsearch_tpu.search.request_cache import (ShardRequestCache,
                                                    request_fingerprint)

from .harness import TestCluster

pytestmark = pytest.mark.writes


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _mapper():
    return MapperService(Settings.EMPTY)


def _segment(svc, gen: int, n: int, off: int = 0, seed: int = 0,
             frac_tf: bool = False, big_tf: bool = False):
    mapper = svc.mapper_for("doc")
    rng = np.random.default_rng(seed + gen)
    b = SegmentBuilder(gen)
    for i in range(n):
        words = " ".join(
            f"w{int(rng.integers(0, 25))}"
            for _ in range(int(rng.integers(2, 10))))
        if big_tf:
            words += " w0" * 300  # tf > 255 → i16 rung
        doc = mapper.parse({"body": words, "tag": f"t{(i + off) % 3}",
                            "n": i + off}, str(i + off))
        b.add(doc, version=1)
    seg = b.freeze()
    if frac_tf:
        seg.post_freqs = seg.post_freqs + np.float32(0.5)  # non-integral f32
    return seg


def _pack_live(seg):
    seg._device_cache["packed"] = pack_segment(seg)
    seg._device_cache["live"] = True
    return seg._device_cache["packed"]


def _pull(*planes):
    import jax

    return jax.device_get(list(planes))


# ---------------------------------------------------------------------------
# pack ledger: kind + pool vocabulary
# ---------------------------------------------------------------------------


class TestPackLedgerKinds:
    def test_kind_counters_and_pool_rows(self):
        led = PackLedger()
        led.record("i", 1, 1.0, 10, "u8", kind="pack", pool="search")
        led.record("i", 2, 1.0, 10, "u8", kind="delta_pack", pool="warmer")
        led.record("i", 3, 1.0, 10, "u8", kind="remask", pool="warmer")
        led.record("i", 4, 1.0, 30, "u8", kind="compact", pool="merge",
                   method="concat")
        st = led.stats("i")
        assert st["packs"] == 1 and st["delta_packs"] == 1
        assert st["remasks"] == 1 and st["compacts"] == 1
        assert st["pools"] == {"search": 1, "warmer": 2, "merge": 1}
        kinds = [e["kind"] for e in st["recent"]]
        assert kinds == ["pack", "delta_pack", "remask", "compact"]
        assert st["recent"][-1]["method"] == "concat"

    def test_pool_defaults_to_thread_name(self):
        led = PackLedger()
        led.record("i", 1, 1.0, 10, "u8")  # test main thread
        assert led.stats("i")["pools"] == {"other": 1}
        out = {}

        def work():
            led.record("i", 2, 1.0, 10, "u8", kind="delta_pack")
            out["pools"] = led.stats("i")["pools"]

        t = threading.Thread(target=work, name="estpu[warmer]_0")
        t.start()
        t.join(5)
        assert out["pools"] == {"other": 1, "warmer": 1}


# ---------------------------------------------------------------------------
# in-flight pack coordination
# ---------------------------------------------------------------------------


class TestPackCoordination:
    def test_racing_search_waits_for_actively_running_pack(self, monkeypatch):
        """A search hitting a segment whose pack is actively RUNNING on
        another thread parks on the in-flight future and gets THE same
        object — exactly one pack runs."""
        from elasticsearch_tpu.ops import device_index as di

        svc = _mapper()
        seg = _segment(svc, 1, 20)
        gate = threading.Event()
        started = threading.Event()
        real_pack = di.pack_segment

        def gated_pack(s, *a, **k):
            started.set()
            gate.wait(5)
            return real_pack(s, *a, **k)

        monkeypatch.setattr(di, "pack_segment", gated_pack)
        results = []
        owner = threading.Thread(
            target=lambda: results.append(packed_for(seg)))
        owner.start()
        assert started.wait(5)  # owner claimed and is packing
        waiter = threading.Thread(
            target=lambda: results.append(packed_for(seg)))
        waiter.start()
        time.sleep(0.05)
        assert waiter.is_alive()  # parked on the future, not duplicating
        gate.set()
        owner.join(10)
        waiter.join(10)
        assert len(results) == 2 and results[0] is results[1]

    def test_search_steals_unstarted_warm_pack(self):
        """The deadlock-proofing half of the claimable-future protocol: a
        pack SCHEDULED but not yet started is claimed by the first arriving
        search, which packs inline and resolves the shared future; the warm
        task then returns without waiting (no pool slot is ever parked on
        work queued behind it)."""
        svc = _mapper()
        seg = _segment(svc, 1, 20)
        fut = begin_warm(seg)
        assert fut is not None
        assert begin_warm(seg) is None  # deduped while in flight
        packed = packed_for(seg)  # steals the claim, packs inline
        assert fut.done() and fut.result() is packed
        assert run_warm(seg, fut) is None  # late worker: nothing to do

    def test_cancel_warm_unblocks_query_path(self):
        svc = _mapper()
        seg = _segment(svc, 1, 10)
        fut = begin_warm(seg)
        cancel_warm(seg, fut)  # pool rejected the task
        packed = packed_for(seg)  # packs inline, no deadlock
        assert packed is seg._device_cache["packed"]

    def test_with_deletes_view_drops_stale_future(self):
        svc = _mapper()
        seg = _segment(svc, 1, 10)
        fut = begin_warm(seg)
        view = seg.with_deletes([0])
        assert view._device_cache.get("pack_future") is None
        run_warm(seg, fut)  # old view's pack completes normally
        assert seg._device_cache.get("pack_future") is None

    def test_warm_failure_propagates_then_retries_inline(self, monkeypatch):
        from elasticsearch_tpu.ops import device_index as di

        svc = _mapper()
        seg = _segment(svc, 1, 10)
        fut = begin_warm(seg)
        monkeypatch.setattr(di, "pack_segment",
                            lambda *a, **k: (_ for _ in ()).throw(
                                RuntimeError("boom")))
        with pytest.raises(RuntimeError):
            run_warm(seg, fut)
        monkeypatch.undo()
        packed = packed_for(seg)  # marker was cleared: inline retry works
        assert packed.doc_count == seg.doc_count


# ---------------------------------------------------------------------------
# compaction concat pack
# ---------------------------------------------------------------------------


class TestConcatPack:
    def _parity(self, sources, gen=99):
        for s in sources:
            _pack_live(s)
        merged = merge_segments(sources, gen)
        ref = pack_segment(merged)
        got = pack_segment_concat(merged, sources)
        assert got is not None, "concat unexpectedly ineligible"
        a = _pull(ref.blk_docs, ref.blk_tf, ref.blk_nb,
                  got.blk_docs, got.blk_tf, got.blk_nb)
        assert np.array_equal(a[0], a[3])
        assert np.array_equal(a[1], a[4]) and a[1].dtype == a[4].dtype
        assert np.array_equal(a[2], a[5])
        assert np.array_equal(ref.term_blk_start, got.term_blk_start)
        assert np.array_equal(ref.host_docs, got.host_docs)
        assert np.array_equal(ref.host_freqs, got.host_freqs)
        assert np.array_equal(ref.blk_field, got.blk_field)
        lp = _pull(ref.live_parent, got.live_parent)
        assert np.array_equal(lp[0], lp[1])
        assert ref.tf_layout == got.tf_layout
        return got

    def test_bitwise_parity_three_sources(self):
        svc = _mapper()
        self._parity([_segment(svc, 1, 37), _segment(svc, 2, 21, off=100),
                      _segment(svc, 3, 5, off=200)])

    def test_tf_rung_widening_u8_to_i16(self):
        svc = _mapper()
        got = self._parity([_segment(svc, 1, 10), _segment(svc, 2, 6,
                                                           off=50,
                                                           big_tf=True)])
        assert got.tf_layout == "i16"

    def test_estimate_exact_for_concat_layout(self):
        svc = _mapper()
        sources = [_segment(svc, 1, 30), _segment(svc, 2, 12, off=100)]
        for s in sources:
            _pack_live(s)
        merged = merge_segments(sources, 9)
        NBpad, Dpad, layout = pack_shape_math(merged)
        tf_b = tf_plane_itemsize(layout)
        # the tables hold a column a term the planes hold: not _id's, not _uid's
        W, T = len(sources), int(np.count_nonzero(device_counts(merged)))
        assert T == len(merged.post_offsets) - 1 - 2 * merged.doc_count
        expect = (NBpad * BLOCK * ((4 + 4) + (4 + tf_b + 1) + 8)
                  + NBpad * 4 * 2 + (2 * W + 1) * T * 4 * 2 + Dpad * 2
                  + Dpad * len(merged.norms) + Dpad * 8 * len(merged.dv_num))
        assert concat_estimate_bytes(merged, sources) == expect

    def test_ineligible_tombstoned_source(self):
        svc = _mapper()
        a, b = _segment(svc, 1, 20), _segment(svc, 2, 10, off=50)
        _pack_live(a)
        _pack_live(b)
        a2 = a.with_deletes([3])
        a2._device_cache["live"] = True  # even remasked: still ineligible
        assert concat_source_packs([a2, b]) is None
        merged = merge_segments([a2, b], 9)
        assert pack_segment_concat(merged, [a2, b]) is None

    def test_ineligible_fractional_f32(self):
        svc = _mapper()
        a = _segment(svc, 1, 12, frac_tf=True)
        b = _segment(svc, 2, 8, off=50)
        _pack_live(a)
        _pack_live(b)
        assert a._device_cache["packed"].tf_layout == "f32"
        assert not a._device_cache["packed"].tf_integral
        assert concat_source_packs([a, b]) is None

    def test_ineligible_unpacked_source(self):
        svc = _mapper()
        a, b = _segment(svc, 1, 10), _segment(svc, 2, 10, off=50)
        _pack_live(a)  # b never packed
        assert concat_source_packs([a, b]) is None

    def test_warm_compact_uses_concat_and_ledger_records_it(self, tmp_path):
        """Engine merge publish plants the compact hint; the warm pack takes
        the concat path and the ledger shows kind=compact method=concat."""
        from tests.test_merge_policy import build_engine

        e, svc = build_engine(tmp_path, {
            "index.merge.policy.segments_per_tier": 2})
        for i in range(12):
            e.index("doc", str(i), {"body": f"alpha w{i % 4} common"})
            if i % 3 == 2:
                e.refresh()
        for seg in e.acquire_searcher().segments:
            _pack_live(seg)
        e.maybe_merge(max_merges=1)
        searcher = e.acquire_searcher()
        merged = next(s for s in searcher.segments
                      if s._device_cache.get("pack_hint", {}).get("kind")
                      == "compact")
        fut = begin_warm(merged)
        PACK_LEDGER.forget("cc-test")
        run_warm(merged, fut, owner="cc-test")
        st = PACK_LEDGER.stats("cc-test")
        assert st["compacts"] == 1
        assert st["recent"][-1]["method"] == "concat"
        assert merged._device_cache.get("pack_hint") is None  # refs dropped
        PACK_LEDGER.forget("cc-test")


# ---------------------------------------------------------------------------
# off-lock merge + incremental uid index
# ---------------------------------------------------------------------------


class TestMergeOffLock:
    def test_search_not_blocked_by_merge_compute(self, tmp_path,
                                                 monkeypatch):
        """The acceptance pin: a search issued during a large merge completes
        without waiting for it — acquire_searcher's timed lock acquisition
        succeeds while merge_segments is still running."""
        from elasticsearch_tpu.index import engine as engine_mod

        from tests.test_merge_policy import build_engine

        e, svc = build_engine(tmp_path, {
            "index.merge.policy.segments_per_tier": 2})
        for i in range(10):
            e.index("doc", str(i), {"n": i, "body": f"alpha w{i % 3}"})
            e.refresh()
        real_merge = engine_mod.merge_segments
        in_merge = threading.Event()

        def slow_merge(segments, gen):
            in_merge.set()
            time.sleep(0.8)
            return real_merge(segments, gen)

        monkeypatch.setattr(engine_mod, "merge_segments", slow_merge)
        t = threading.Thread(target=lambda: e.maybe_merge(max_merges=1))
        t.start()
        assert in_merge.wait(5)
        t0 = time.monotonic()
        got = e._lock.acquire(timeout=0.3)
        waited = time.monotonic() - t0
        assert got, "engine lock held across merge compute"
        e._lock.release()
        assert waited < 0.3
        searcher = e.acquire_searcher()  # serves the OLD view mid-merge
        assert searcher.live_doc_count() == 10
        t.join(10)
        assert e.acquire_searcher().live_doc_count() == 10

    def test_concurrent_tombstone_aborts_publish(self, tmp_path,
                                                 monkeypatch):
        """A delete landing in a source segment mid-merge must NOT be
        resurrected by the merge publish: identity validation aborts it."""
        from elasticsearch_tpu.index import engine as engine_mod

        from tests.test_merge_policy import build_engine

        e, svc = build_engine(tmp_path, {
            "index.merge.policy.segments_per_tier": 2})
        for i in range(8):
            e.index("doc", str(i), {"n": i, "body": "alpha"})
            e.refresh()
        real_merge = engine_mod.merge_segments
        in_merge = threading.Event()
        release = threading.Event()

        def gated_merge(segments, gen):
            in_merge.set()
            release.wait(5)
            return real_merge(segments, gen)

        monkeypatch.setattr(engine_mod, "merge_segments", gated_merge)
        merges0 = e.stats["merge_total"]
        t = threading.Thread(target=lambda: e.maybe_merge(max_merges=1))
        t.start()
        assert in_merge.wait(5)
        e.delete("doc", "0")  # tombstones a doc inside the merge window
        e.refresh()
        release.set()
        t.join(10)
        # the publish aborted (no merge landed) — and the delete held
        assert e.stats["merge_total"] == merges0
        assert not e.get("doc", "0").found
        assert e.acquire_searcher().live_doc_count() == 7
        monkeypatch.undo()
        e.maybe_merge(max_merges=10)  # re-plan merges fine afterwards
        assert not e.get("doc", "0").found
        assert e.acquire_searcher().live_doc_count() == 7

    def test_uid_index_incremental_matches_full_rebuild(self, tmp_path):
        from tests.test_merge_policy import build_engine

        e, svc = build_engine(tmp_path, {
            "index.merge.policy.segments_per_tier": 2})
        for i in range(20):
            e.index("doc", str(i), {"n": i})
            e.refresh()
        e.index("doc", "5", {"n": 500})  # update: old copy dies in-window
        e.delete("doc", "7")
        e.refresh()
        e.maybe_merge(max_merges=20)
        rebuilt = {}
        for seg in e._segments:
            for local in range(seg.doc_count):
                if seg.parent_mask[local] and seg.live[local]:
                    rebuilt[f"{seg.types[local]}#{seg.ids[local]}"] = (
                        seg.gen, local)
        assert e._uid_index == rebuilt
        assert e.get("doc", "5").source["n"] == 500
        assert not e.get("doc", "7").found


# ---------------------------------------------------------------------------
# request-cache hot keys (warmer input)
# ---------------------------------------------------------------------------


class TestHotKeys:
    def _rc(self):
        return ShardRequestCache(Settings.EMPTY)

    def test_hits_rank_hot_bodies(self):
        rc = self._rc()
        bodies = [{"query": {"match": {"f": f"t{i}"}}, "size": 0}
                  for i in range(3)]
        keys = [("i", 0, 1, request_fingerprint(b)) for b in bodies]
        for k, b in zip(keys, bodies):
            rc.put(k, b"x", body=b)
        assert rc.hot_bodies("i", 0) == []  # stored but never hit
        assert not rc.has_hot("i", 0)
        for _ in range(3):
            rc.get(keys[1])
        rc.get(keys[2])
        assert rc.has_hot("i", 0)
        hot = rc.hot_bodies("i", 0, n=2)
        assert hot == [bodies[1], bodies[2]]
        # replayed bodies fingerprint identically to the live ones
        assert request_fingerprint(hot[0]) == keys[1][3]

    def test_hot_survives_view_invalidation_not_shard_drop(self):
        rc = self._rc()
        body = {"query": {"match_all": {}}, "size": 0}
        k = ("i", 0, 1, request_fingerprint(body))
        rc.put(k, b"x", body=body)
        rc.get(k)
        rc.invalidate_shard("i", 0, 2)  # view advanced
        assert rc.has_hot("i", 0)
        rc.invalidate_shard("i", 0, None)  # shard leaving the node
        assert not rc.has_hot("i", 0)

    def test_hot_bounded_per_shard(self):
        rc = self._rc()
        for i in range(rc.HOT_PER_SHARD + 10):
            b = {"query": {"match": {"f": f"t{i}"}}, "size": 0}
            rc.put(("i", 0, 1, request_fingerprint(b)), b"x", body=b)
        assert len(rc._hot[("i", 0)]) == rc.HOT_PER_SHARD


# ---------------------------------------------------------------------------
# live cluster: the write-to-serve spine
# ---------------------------------------------------------------------------


WRITES_INDEX = "wr"


def _boot(tmp_path, settings=None, index_settings=None, docs=40):
    cluster = TestCluster(n_nodes=1, data_root=tmp_path, seed=14,
                          settings=settings or {})
    cluster.start()
    c = cluster.client()
    c.create_index(WRITES_INDEX, {"settings": {
        "number_of_shards": 1, "number_of_replicas": 0,
        # deterministic view control: tests drive refresh explicitly
        "index.refresh_interval": -1, **(index_settings or {})}})
    cluster.ensure_green(WRITES_INDEX)
    for i in range(docs):
        c.index(WRITES_INDEX, "doc",
                {"body": f"alpha beta{i % 4} w{i % 7}", "n": i,
                 "tag": f"t{i % 3}"}, id=str(i))
    c.refresh(WRITES_INDEX)
    return cluster, c


def _engine(cluster):
    node = next(iter(cluster.nodes.values()))
    return node, node.indices.indices[WRITES_INDEX].shards[0].engine


def _wait(predicate, timeout=8.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


class TestLiveWrites:
    def test_warmed_loop_given_time_zero_query_path_packs_zero_recompiles(
            self, tmp_path):
        """THE acceptance pin, for a warmer that is GIVEN TIME: each search of
        the loop waits until the warmer pool has drained, so what is pinned is
        that a warmed continuous-indexing serving loop under hard
        transfer_guard("disallow") recompiles nothing and that the warmer
        packs every delta off the query path when it gets there first, while
        searches stay correct as the corpus grows. A search that gets there
        first packs inline, by design:
        test_search_that_beats_the_warmer_packs_inline_and_is_counted."""
        import jax

        from elasticsearch_tpu.common.jaxenv import sanitize

        cluster, c = _boot(tmp_path)
        try:
            node, engine = _engine(cluster)
            q = {"query": {"match": {"body": "alpha"}}, "size": 5}
            r = c.search(WRITES_INDEX, q)  # opens the pack-warming gate
            assert r["hits"]["total"] == 40
            # warm every delta shape: a couple of rounds OUTSIDE the armed
            # window compile the (stable, pow-2-bucketed) delta executables
            for rnd in range(2):
                for i in range(6):
                    c.index(WRITES_INDEX, "doc",
                            {"body": f"alpha beta{i % 4} w{i % 7}", "n": i},
                            id=f"warm{rnd}-{i}")
                c.refresh(WRITES_INDEX)
                c.search(WRITES_INDEX, q)
            def drained():
                st = node.warmer.stats()
                return st["packs_done"] >= st["packs_scheduled"]

            assert _wait(drained)
            PACK_LEDGER.forget(WRITES_INDEX)  # armed window sees only new
            total0 = c.search(WRITES_INDEX, q)["hits"]["total"]
            stolen0 = node.warmer.stats()["packs_stolen"]
            jax.config.update("jax_transfer_guard", "disallow")
            try:
                with sanitize(max_compiles=0, transfers="disallow") as rep:
                    for rnd in range(3):
                        for i in range(6):
                            c.index(WRITES_INDEX, "doc",
                                    {"body": f"alpha beta{i % 4} w{i % 7}",
                                     "n": i}, id=f"live{rnd}-{i}")
                        c.refresh(WRITES_INDEX)
                        # the refresh hands the delta pack to the warmer pool
                        # and returns; a search that beats the pool thread to
                        # it CLAIMS the pack and runs it inline (by design —
                        # device_index's claimable future). Under a loaded
                        # machine (the driver's six xdist workers) that race
                        # goes either way, so let the warmer finish: the
                        # window then pins the warmed loop, not the scheduler
                        assert _wait(drained)
                        r = c.search(WRITES_INDEX, q)
                        assert r["hits"]["total"] == total0 + 6 * (rnd + 1)
            finally:
                jax.config.update("jax_transfer_guard", "allow")
            assert rep.compiles == 0, rep.compile_events
            st = PACK_LEDGER.stats(WRITES_INDEX)
            assert st.get("delta_packs", 0) >= 3, st
            # pool attribution: ALL pack work off the query path
            assert node.warmer.stats()["packs_stolen"] == stolen0
            assert set(st["pools"]) <= {"warmer", "merge"}, st["pools"]
            for e in st["recent"]:
                assert e["pool"] in ("warmer", "merge"), e
            # delta packs are delta-sized: far below the base segment's pack
            base_bytes = max(e["bytes"] for e in st["recent"])
            delta_bytes = [e["bytes"] for e in st["recent"]
                           if e["kind"] == "delta_pack"]
            assert delta_bytes and min(delta_bytes) <= base_bytes
        finally:
            cluster.close()

    def test_search_that_beats_the_warmer_packs_inline_and_is_counted(
            self, tmp_path, monkeypatch):
        """The other side of the warmed loop: a refresh hands the delta pack to
        the warmer pool and returns, and a search that reaches the segment
        before a pool thread does CLAIMS the pack and runs it on the query
        path (device_index's claimable future). That is allowed, and it is
        visible: the warmer counts the pack as stolen and the ledger books it
        to the pool of the search, not to warmer/merge."""
        cluster, c = _boot(tmp_path)
        try:
            node, engine = _engine(cluster)
            q = {"query": {"match": {"body": "alpha"}}, "size": 5}
            total0 = c.search(WRITES_INDEX, q)["hits"]["total"]  # opens the gate

            def drained():
                st = node.warmer.stats()
                return st["packs_done"] >= st["packs_scheduled"]

            assert _wait(drained)
            PACK_LEDGER.forget(WRITES_INDEX)
            before = node.warmer.stats()
            release = threading.Event()
            run_pack = node.warmer._run_pack

            def held_run_pack(*a, **kw):  # the pool thread arrives late
                assert release.wait(30)
                return run_pack(*a, **kw)

            monkeypatch.setattr(node.warmer, "_run_pack", held_run_pack)
            for i in range(6):
                c.index(WRITES_INDEX, "doc", {"body": f"alpha claim{i}", "n": i},
                        id=f"claim{i}")
            c.refresh(WRITES_INDEX)
            assert node.warmer.stats()["packs_scheduled"] > before["packs_scheduled"]
            r = c.search(WRITES_INDEX, q)  # beats the held warmer to the delta
            assert r["hits"]["total"] == total0 + 6
            release.set()
            assert _wait(drained)
            after = node.warmer.stats()
            assert after["packs_stolen"] == before["packs_stolen"] + (
                after["packs_scheduled"] - before["packs_scheduled"])
            st = PACK_LEDGER.stats(WRITES_INDEX)
            assert st.get("delta_packs", 0) >= 1, st
            # booked to the query path, not to the pools that were held
            assert st["pools"] and not set(st["pools"]) & {"warmer", "merge"}, st
            assert set(st["pools"]) <= {"search", "search_batcher"}, st["pools"]
        finally:
            cluster.close()

    def test_optimize_waits_for_a_merge_that_outlasts_the_broadcast_limit(
            self, tmp_path, monkeypatch):
        """A force-merge longer than the broadcast's per-copy limit used to be
        reported `failed` while it went on and completed. The limit is scaled
        from 30 s to 0.2 s here and the merge held for 1 s: the optimize must
        wait it out and report the shard successful, merged."""
        import elasticsearch_tpu.actions as actions_mod
        import elasticsearch_tpu.index.engine as engine_mod

        cluster, c = _boot(tmp_path)
        try:
            node, engine = _engine(cluster)
            for i in range(6):
                c.index(WRITES_INDEX, "doc", {"body": f"alpha late{i}"},
                        id=f"late{i}")
                c.refresh(WRITES_INDEX)
            assert engine.segment_count() >= 2
            real_merge = engine_mod.merge_segments
            real_wait = actions_mod.fut_result

            def held_merge(*a, **kw):
                time.sleep(1.0)
                return real_merge(*a, **kw)

            def scaled_wait(fut, timeout=30.0):
                return real_wait(fut, None if timeout is None else timeout / 150.0)

            monkeypatch.setattr(engine_mod, "merge_segments", held_merge)
            monkeypatch.setattr(actions_mod, "fut_result", scaled_wait)
            r = c.optimize(WRITES_INDEX)
            assert r["_shards"] == {"total": 1, "successful": 1, "failed": 0}
            assert engine.segment_count() == 1
            # the other broadcasts keep their limit and still answer
            assert c.refresh(WRITES_INDEX)["_shards"]["failed"] == 0
        finally:
            cluster.close()

    def test_base_delta_bitwise_identical_to_cold_monolithic_repack(
            self, tmp_path):
        """Scores over base+delta segment views are BITWISE identical to a
        cold monolithic repack of the optimized index (same shard-level
        stats, same f32 op order per doc)."""
        cluster, c = _boot(tmp_path)
        try:
            node, engine = _engine(cluster)
            q = {"query": {"match": {"body": "alpha beta1"}}, "size": 20}
            for rnd in range(2):  # grow base + deltas
                for i in range(7):
                    c.index(WRITES_INDEX, "doc",
                            {"body": f"alpha beta{i % 4} w{i % 7}",
                             "n": 100 + i}, id=f"d{rnd}-{i}")
                c.refresh(WRITES_INDEX)
            assert engine.segment_count() >= 3
            before = [(h["_id"], h["_score"])
                      for h in c.search(WRITES_INDEX, q)["hits"]["hits"]]
            assert before
            c.optimize(WRITES_INDEX)
            searcher = engine.acquire_searcher()
            assert len(searcher.segments) == 1
            # force a COLD host-staged repack (drop hint + resident pack)
            seg = searcher.segments[0]
            seg._device_cache.pop("pack_hint", None)
            seg._device_cache.pop("pack_future", None)
            seg._device_cache.pop("packed", None)
            seg._device_cache.pop("live", None)
            after = [(h["_id"], h["_score"])
                     for h in c.search(WRITES_INDEX, q)["hits"]["hits"]]
            assert before == after  # ids, order, AND bitwise f32 scores
        finally:
            cluster.close()

    def test_breaker_trip_during_delta_pack_degrades_to_host(self, tmp_path):
        """Out of fielddata budget mid-delta-pack: the warm pack fails, the
        search's wait sees the trip, and the HOST scorer answers correctly —
        no 5xx, no wrong counts."""
        from elasticsearch_tpu.search.service import SERVING_COUNTERS

        cluster, c = _boot(tmp_path)
        try:
            node, engine = _engine(cluster)
            q = {"query": {"match": {"body": "alpha"}}, "size": 5}
            assert c.search(WRITES_INDEX, q)["hits"]["total"] == 40
            fd = node.breakers.breaker("fielddata")
            old_limit = fd.limit
            fd.limit = 1  # every pack estimate trips from here on
            try:
                for i in range(5):
                    c.index(WRITES_INDEX, "doc",
                            {"body": "alpha fresh", "n": i}, id=f"t{i}")
                c.refresh(WRITES_INDEX)
                host0 = SERVING_COUNTERS.get("host", 0)
                r = c.search(WRITES_INDEX, q)
                assert r["hits"]["total"] == 45
                assert SERVING_COUNTERS.get("host", 0) > host0
                assert node.warmer.stats()["pack_failures"] >= 1
            finally:
                fd.limit = old_limit
            # budget restored: device packing resumes on the next sighting
            r = c.search(WRITES_INDEX, q)
            assert r["hits"]["total"] == 45
        finally:
            cluster.close()

    def test_compaction_publish_mid_search_serves_old_view(self, tmp_path,
                                                           monkeypatch):
        """A search issued during a large merge completes without waiting
        for it (timed), the pre-publish searcher keeps serving, and the
        compaction pack lands on the merge pool via device concat."""
        from elasticsearch_tpu.index import engine as engine_mod

        cluster, c = _boot(tmp_path, index_settings={
            "index.merge.policy.segments_per_tier": 2})
        try:
            node, engine = _engine(cluster)
            q = {"query": {"match": {"body": "alpha"}}, "size": 5}
            c.search(WRITES_INDEX, q)
            for rnd in range(3):
                for i in range(6):
                    c.index(WRITES_INDEX, "doc",
                            {"body": f"alpha w{i % 3}", "n": i},
                            id=f"m{rnd}-{i}")
                c.refresh(WRITES_INDEX)
                c.search(WRITES_INDEX, q)
            assert _wait(lambda: node.warmer.stats()["packs_done"]
                         >= node.warmer.stats()["packs_scheduled"])
            total = 40 + 18
            real_merge = engine_mod.merge_segments
            in_merge = threading.Event()

            def slow_merge(segments, gen):
                in_merge.set()
                time.sleep(1.0)
                return real_merge(segments, gen)

            monkeypatch.setattr(engine_mod, "merge_segments", slow_merge)
            old_searcher = engine.acquire_searcher()
            t = threading.Thread(target=lambda: engine.maybe_merge(
                max_merges=1))
            t.start()
            assert in_merge.wait(5)
            t0 = time.monotonic()
            r = c.search(WRITES_INDEX, q)
            waited = time.monotonic() - t0
            assert r["hits"]["total"] == total
            assert waited < 0.9, f"search waited {waited}s on merge compute"
            assert old_searcher.live_doc_count() == total  # old view intact
            t.join(15)
            monkeypatch.undo()
            assert c.search(WRITES_INDEX, q)["hits"]["total"] == total
            st = PACK_LEDGER.stats(WRITES_INDEX)
            if _wait(lambda: PACK_LEDGER.stats(WRITES_INDEX)
                     .get("compacts", 0) >= 1, timeout=6.0):
                st = PACK_LEDGER.stats(WRITES_INDEX)
                compact = [e for e in st["recent"]
                           if e["kind"] == "compact"]
                assert compact and compact[-1]["pool"] == "merge"
                assert compact[-1].get("method") == "concat"
        finally:
            cluster.close()

    def test_recovery_replays_onto_delta_aware_packs(self, tmp_path):
        """Store recovery rebuilds segments without pack hints and serves
        correctly — then fresh writes take the delta path again."""
        from elasticsearch_tpu.index.engine import Engine

        from tests.test_merge_policy import build_engine

        e, svc = build_engine(tmp_path, {})
        for rnd in range(3):
            for i in range(5):
                e.index("doc", f"{rnd}-{i}", {"body": f"alpha w{i}",
                                              "n": i})
            e.refresh()
        e.flush()
        e.translog.sync()
        e.close()
        e2 = Engine(str(tmp_path / "s"), svc, settings=Settings.EMPTY)
        e2.recover_from_store()
        e2.refresh()
        from elasticsearch_tpu.search.execute import (ShardContext,
                                                      search_shard)
        from elasticsearch_tpu.search.queries import parse_query
        from elasticsearch_tpu.search.similarity import SimilarityService

        ctx = ShardContext(e2.acquire_searcher(), svc,
                           SimilarityService(Settings.EMPTY,
                                             mapper_service=svc))
        td = search_shard(ctx, parse_query({"match": {"body": "alpha"}}), 30)
        assert td.total == 15
        # a post-recovery refresh increment carries the delta hint
        e2.index("doc", "new", {"body": "alpha", "n": 9})
        e2.refresh()
        segs = e2.acquire_searcher().segments
        assert segs[-1]._device_cache.get("pack_hint", {}).get("kind") \
            == "delta_pack"
        e2.close()

    def test_warmer_reprimes_request_cache_first_sighting_hits(
            self, tmp_path):
        """The warmer satellite: after a refresh, the shard's hot cached
        body is replayed by the warmer pool, so the FIRST post-refresh
        sighting is a request-cache hit (and it sees the new doc)."""
        cluster, c = _boot(tmp_path)
        try:
            node, engine = _engine(cluster)
            hot = {"query": {"match": {"body": "alpha"}}, "size": 0}
            assert c.search(WRITES_INDEX, hot)["hits"]["total"] == 40
            c.search(WRITES_INDEX, hot)  # hit → the body turns hot
            assert node.request_cache.has_hot(WRITES_INDEX, 0)
            c.index(WRITES_INDEX, "doc", {"body": "alpha fresh", "n": 1},
                    id="newdoc")
            c.refresh(WRITES_INDEX)
            fp = request_fingerprint(hot)

            def warmed():
                version = engine.acquire_searcher().version
                return node.request_cache.peek(
                    (WRITES_INDEX, 0, version, fp))

            assert _wait(warmed), node.warmer.stats()
            st0 = node.request_cache.stats()
            r = c.search(WRITES_INDEX, hot)
            assert r["hits"]["total"] == 41  # the warmed entry is CURRENT
            st1 = node.request_cache.stats()
            assert st1["hits"] == st0["hits"] + 1
            assert st1["misses"] == st0["misses"]
            ws = node.warmer.stats()
            assert ws["reprimes"] >= 1 and ws["queries_warmed"] >= 1
        finally:
            cluster.close()

    def test_warmer_kill_switch(self, tmp_path):
        cluster, c = _boot(tmp_path,
                           settings={"indices.warmer.enabled": "false"})
        try:
            node, engine = _engine(cluster)
            hot = {"query": {"match": {"body": "alpha"}}, "size": 0}
            c.search(WRITES_INDEX, hot)
            c.search(WRITES_INDEX, hot)
            c.index(WRITES_INDEX, "doc", {"body": "alpha", "n": 1}, id="x")
            c.refresh(WRITES_INDEX)
            # packs still warm (core serving behavior), re-prime does not
            assert _wait(lambda: node.warmer.stats()["packs_done"] >= 1)
            time.sleep(0.2)
            ws = node.warmer.stats()
            assert ws["enabled"] is False
            assert ws["reprimes"] == 0 and ws["queries_warmed"] == 0
        finally:
            cluster.close()

    def test_stats_surfaces_delta_and_compaction_rows(self, tmp_path):
        """/_nodes/stats gains the warmer section; the device section's and
        /{index}/_stats' pack rollups carry delta_packs/compacts + pools."""
        cluster, c = _boot(tmp_path, index_settings={
            "index.merge.policy.segments_per_tier": 2})
        try:
            node, engine = _engine(cluster)
            q = {"query": {"match": {"body": "alpha"}}, "size": 3}
            c.search(WRITES_INDEX, q)
            for rnd in range(3):
                c.index(WRITES_INDEX, "doc", {"body": "alpha", "n": rnd},
                        id=f"s{rnd}")
                c.refresh(WRITES_INDEX)
                c.search(WRITES_INDEX, q)
            engine.maybe_merge(max_merges=2)
            c.search(WRITES_INDEX, q)
            assert _wait(lambda: node.warmer.stats()["packs_done"]
                         >= node.warmer.stats()["packs_scheduled"])
            ns = node.client().nodes_stats()["nodes"][node.node_id]
            assert "warmer" in ns
            for key in ("packs_scheduled", "packs_done", "reprimes",
                        "queries_warmed", "enabled"):
                assert key in ns["warmer"]
            pack = ns["device"]["indices"][WRITES_INDEX]["pack"]
            for key in ("packs", "delta_packs", "remasks", "compacts",
                        "pools"):
                assert key in pack
            assert pack["delta_packs"] >= 1
            idx_stats = node.client().stats(WRITES_INDEX)
            assert idx_stats[WRITES_INDEX]["device"]["pack"][
                "delta_packs"] >= 1
        finally:
            cluster.close()


# ---------------------------------------------------------------------------
# lint: the write-path modules stay clean
# ---------------------------------------------------------------------------


def test_writes_modules_scan_clean():
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.tpulint import lint_paths

    paths = [os.path.join(repo, "elasticsearch_tpu", p) for p in (
        "ops/device_index.py", "ops/scoring.py", "index/engine.py",
        "index/segment.py", "index/merge_policy.py", "warmer.py",
        "indices_service.py", "search/request_cache.py", "threadpool.py",
    )]
    findings = lint_paths(paths)
    assert not findings, [f.to_dict() for f in findings]
