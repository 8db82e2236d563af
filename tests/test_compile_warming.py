"""Compile warming (ROADMAP item 5): shape-driven AOT executable pre-warming,
autotuned bucket ladders, and the persistent shape manifest.

Unit half: BucketLadder pow-2 cold fallback / DP fit + commit gates
(min_samples, improvement, monotone rungs) / JSON roundtrip; the
encode_args → materialize argspec roundtrip WarmSpec persistence rides on;
registry capture semantics (a serving launch records its spec already-warm, so
steady state never re-executes); request-cache zlib compression (floor,
keep-raw-when-zlib-loses, breaker charged the RESIDENT size, drop-adjusted
gauges).

E2E half (the acceptance pin): boot → serve a query mix → close persists
`<path.data>/compile_manifest.json` → simulated process restart
(jax.clear_caches + registry/ladder reset) → a second node on the SAME
path.data loads the manifest, its startup warm cycle replays every spec on the
warmer pool, and the observed mix then serves under
`sanitize(max_compiles=0)` — zero on-path compiles on a warmed node.
"""

from __future__ import annotations

import json
import os
import time
import zlib

import pytest

from elasticsearch_tpu.common.breaker import CircuitBreakerService
from elasticsearch_tpu.common.compilecache import (LADDERS, MANIFEST_NAME,
                                                   REGISTRY, BucketLadder,
                                                   WarmSpec, encode_args,
                                                   materialize)
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.search.request_cache import ShardRequestCache
from elasticsearch_tpu.transport.local import LocalTransportRegistry

pytestmark = pytest.mark.compile


def wait_until(fn, timeout=20.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


@pytest.fixture()
def registry_guard():
    """REGISTRY/LADDERS are process singletons shared with every other test
    in the session — restore the default knobs and a clean slate afterwards
    (an empty registry is the steady-state no-op: pending 0, no warm work)."""
    yield
    REGISTRY.reset()
    LADDERS.reset()
    REGISTRY.enabled = True
    REGISTRY.persist = True
    REGISTRY.autotune_min_samples = 512
    REGISTRY.autotune_improvement = 0.10


# ---------------------------------------------------------------------------
# bucket ladders
# ---------------------------------------------------------------------------


class TestBucketLadder:
    def test_cold_fallback_is_exact_pow2(self):
        lad = BucketLadder("t")
        # bit-identical to the fixed _pow2_bucket ladder until a fit commits
        assert lad.bucket(5, 4) == 8
        assert lad.bucket(3, 4) == 4
        assert lad.bucket(1, 16) == 16
        assert lad.bucket(17, 16) == 32
        assert lad.bucket(100, 1) == 128

    def test_autotune_commits_fitted_rung(self):
        lad = BucketLadder("t")
        for _ in range(600):
            lad.bucket(17, 1)  # pow-2 pads 17 -> 32 every time
        assert lad.autotune(min_samples=512, improvement=0.10)
        assert lad.stats()["rungs"] == [17]
        assert lad.bucket(17, 1) == 17  # fitted rung adopted
        assert lad.bucket(18, 1) == 32  # past the top rung: pow-2 fallback
        assert lad.bucket(3, 1) == 17  # smallest covering rung

    def test_rungs_monotone_and_bounded(self):
        lad = BucketLadder("t", max_rungs=4)
        for v in (9, 17, 33, 65, 129, 250, 400, 500):
            for _ in range(100):
                lad.bucket(v, 1)
        assert lad.autotune(min_samples=512, improvement=0.10)
        rungs = lad.stats()["rungs"]
        assert rungs == sorted(rungs)
        assert len(rungs) <= 4
        # every observed value has a covering rung at/below its pow-2 pad
        for v in (9, 17, 33, 65, 129, 250, 400, 500):
            assert v <= lad.bucket(v, 1) <= max(rungs)

    def test_no_commit_when_pow2_already_tight(self):
        lad = BucketLadder("t")
        for _ in range(600):
            lad.bucket(64, 1)  # already a pow-2 lane: zero waste to win
        assert not lad.autotune(min_samples=512, improvement=0.10)
        assert lad.stats()["rungs"] is None

    def test_no_commit_below_sample_floor(self):
        lad = BucketLadder("t")
        for _ in range(50):
            lad.bucket(17, 1)
        assert not lad.autotune(min_samples=512, improvement=0.10)
        assert lad.bucket(17, 1) == 32  # still the cold pow-2 ladder

    def test_json_roundtrip_restores_rungs_and_histogram(self):
        lad = BucketLadder("t")
        for _ in range(600):
            lad.bucket(17, 1)
        assert lad.autotune(min_samples=512, improvement=0.10)
        clone = BucketLadder("t")
        clone.load_json(lad.to_json())
        assert clone.bucket(17, 1) == 17  # rungs survive the manifest
        st = clone.stats()
        assert st["observations"] >= 600 and st["rungs"] == [17]


# ---------------------------------------------------------------------------
# argspec encoding
# ---------------------------------------------------------------------------


class TestArgspecRoundtrip:
    def test_encode_materialize_roundtrip(self):
        import numpy as np

        args = [np.zeros((4, 8), np.float32), np.arange(3, dtype=np.int32),
                (np.ones((2,), np.int64), 7), True, "bm25", None, [1.5, 2.5]]
        spec = encode_args(args)
        out = materialize(spec)
        assert out[0].shape == (4, 8) and str(out[0].dtype) == "float32"
        assert out[1].shape == (3,) and str(out[1].dtype) == "int32"
        assert isinstance(out[2], tuple)
        assert out[2][0].shape == (2,) and out[2][1] == 7
        assert out[3] is True and out[4] == "bm25" and out[5] is None
        assert out[6] == [1.5, 2.5]

    def test_warmspec_json_roundtrip_keys_equal(self):
        import json as _json

        import numpy as np

        spec = WarmSpec(site="scoring.dense", family="dense",
                        params=(4, 16, 4096, True),
                        argspec=encode_args([np.zeros((4, 4096), np.float32),
                                             (np.zeros((4,), np.int32), 10)]))
        back = WarmSpec.from_json(_json.loads(_json.dumps(spec.to_json())))
        assert back.key() == spec.key()
        assert back.family == "dense" and back.params == (4, 16, 4096, True)


# ---------------------------------------------------------------------------
# registry capture + warm cycle
# ---------------------------------------------------------------------------


class TestRegistryWarm:
    def test_serving_launch_records_already_warm(self, registry_guard):
        import numpy as np

        REGISTRY.reset()
        REGISTRY.record_launch("test.site", "dense", (2, 16),
                               [np.zeros((2, 64), np.float32)])
        st = REGISTRY.stats()
        # the launch itself populated the dispatch cache: nothing pending, so
        # steady-state warm cycles (and the autotunes they gate) never run
        assert st["specs"] == 1 and st["pending"] == 0

    def test_manifest_restart_warm_cycle_zero_compile_loop(
            self, registry_guard, tmp_path):
        """The invariant in miniature: record a real jitted launch, persist,
        reset (simulated restart), reload, warm — then the SAME-shaped real
        call holds under sanitize(max_compiles=0)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from elasticsearch_tpu.common.jaxenv import compile_tag, sanitize

        REGISTRY.reset()
        LADDERS.reset()

        cache = {}

        def get_fn(scale):
            fn = cache.get(scale)
            if fn is None:
                fn = cache[scale] = jax.jit(lambda x: x * scale + 1.0)
            return fn

        @REGISTRY.builder("test.warm")
        def _build(params):
            return get_fn(params[0])

        x = jax.device_put(np.ones((8, 32), np.float32))
        with compile_tag("dense"):
            get_fn(3.0)(x).block_until_ready()
        REGISTRY.record_launch("test.warm", "dense", (3.0,), [x])
        assert REGISTRY.pending_count() == 0
        REGISTRY._dirty = True
        REGISTRY.save_manifest(str(tmp_path / MANIFEST_NAME))

        # simulated restart: executables and warm state both gone
        cache.clear()
        jax.clear_caches()
        REGISTRY.reset()
        assert REGISTRY.load_manifest(str(tmp_path / MANIFEST_NAME)) == 1
        assert REGISTRY.pending_count() == 1
        REGISTRY._builders["test.warm"] = _build  # reset survivor (module im-
        # port would normally re-register; this test's builder lives here)
        res = REGISTRY.warm_cycle("test")
        assert res["warmed"] == 1 and res["failed"] == 0
        assert REGISTRY.pending_count() == 0
        # the warmed executable serves the real shape with zero compiles
        with sanitize(max_compiles=0) as rep:
            y = get_fn(3.0)(jax.device_put(np.full((8, 32), 2.0, np.float32)))
            jax.block_until_ready(y)
        assert rep.compiles == 0
        assert float(jnp.max(y)) == 7.0  # real math, not a stub

    def test_manifest_of_another_operand_list_replays_nothing(
            self, registry_guard, tmp_path):
        """A manifest written before the launch sites' operand lists changed
        holds argspecs the programs no longer take: its specs are dropped at
        load, its ladders kept."""
        import json as _json

        import numpy as np

        REGISTRY.reset()
        LADDERS.reset()
        REGISTRY.record_launch("test.site", "dense", (2, 16),
                               [np.zeros((2, 64), np.float32)])
        LADDERS.ladder("terms").load_json({"rungs": [24]})
        path = tmp_path / MANIFEST_NAME
        REGISTRY.save_manifest(str(path))
        payload = _json.loads(path.read_text())
        assert payload["version"] == 6 and len(payload["specs"]) == 1
        REGISTRY.reset()
        assert REGISTRY.load_manifest(str(path)) == 1
        payload["version"] = 5  # before a phrase launch's params held its slots
        path.write_text(_json.dumps(payload))
        REGISTRY.reset()
        LADDERS.reset()
        assert REGISTRY.load_manifest(str(path)) == 0
        assert REGISTRY.pending_count() == 0
        assert LADDERS.bucket("terms", 17, 1) == 24

    def test_warm_failure_trips_compile_circuit_off_path(self, registry_guard):
        import numpy as np

        from elasticsearch_tpu.common.devicehealth import DEVICE_HEALTH

        REGISTRY.reset()

        class XlaRuntimeError(RuntimeError):
            """Duck-typed like jaxlib's — a plain Python bug in a builder
            must NOT trip a device circuit (classify returns None for it)."""

        @REGISTRY.builder("test.broken")
        def _build(params):
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory")

        REGISTRY.record_launch("test.broken", "dense", (1,),
                               [np.zeros((2,), np.float32)])
        with REGISTRY._lock:
            REGISTRY._warmed.clear()  # force it pending
        before = DEVICE_HEALTH.stats().get("domains", {}).get(
            "compile:dense", {}).get("failures", 0)
        res = REGISTRY.warm_cycle("test")
        assert res["failed"] == 1 and res["warmed"] == 0
        after = DEVICE_HEALTH.stats().get("domains", {}).get(
            "compile:dense", {}).get("failures", 0)
        assert after == before + 1  # contained in the compile:<family> domain

    def test_disabled_registry_records_nothing(self, registry_guard):
        import numpy as np

        REGISTRY.reset()
        REGISTRY.enabled = False
        REGISTRY.record_launch("test.site", "dense", (1,),
                               [np.zeros((2,), np.float32)])
        assert REGISTRY.stats()["specs"] == 0
        assert REGISTRY.warm_cycle("test") == {
            "warmed": 0, "failed": 0, "skipped": 0}


# ---------------------------------------------------------------------------
# node e2e: restart persistence (the satellite's acceptance test)
# ---------------------------------------------------------------------------


QUERIES = [
    {"query": {"match": {"body": "alpha"}}, "size": 10},
    {"query": {"match": {"body": "alpha beta"}}, "size": 10},
    {"query": {"match": {"body": "gamma"}}, "size": 20},
    {"query": {"match": {"body": "beta"}}, "size": 0},
    # plans with no scoring clause behind each of the three tails: their
    # launches are recorded and replayed like the others (a scored dense
    # launch is not in the mix: its prologue, scoring.doc_table and the eager
    # stack of norm rows, has no warm site and compiles on a restarted node)
    {"query": {"match_all": {}}, "size": 10},
    {"query": {"filtered": {"query": {"match_all": {}},
                            "filter": {"range": {"n": {"gte": 10}}}}},
     "sort": [{"n": "desc"}], "size": 5},
    {"query": {"range": {"n": {"lt": 40}}}, "size": 0, "request_cache": False,
     "aggs": {"n": {"stats": {"field": "n"}}}},
    # exact phrases: the phrase program's launches are recorded and replayed
    # (scoring.phrase), a pair's on its line of two slots and a phrase of
    # three's on the line of four; the restarted node faults the positions
    # plane in on the path, which compiles nothing
    {"query": {"match_phrase": {"body": "alpha beta"}}, "size": 10},
    {"query": {"match_phrase": {"body": "alpha beta beta"}}, "size": 10},
]


def _boot(data_path, extra=None):
    node = Node(name="warm_node", registry=LocalTransportRegistry(),
                data_path=data_path,
                settings=Settings.from_flat(extra or {}))
    node.start([node.local_node.transport_address])
    assert node.wait_for_master(5.0)
    return node


def _seed_and_serve(node):
    c = node.client()
    c.create_index("warm", {"settings": {"number_of_shards": 1,
                                         "number_of_replicas": 0}})
    for i in range(80):
        c.index("warm", "doc",
                {"body": f"alpha beta{'' if i % 3 else ' beta'}"
                         f"{' gamma' if i % 5 == 0 else ''}", "n": i},
                id=str(i))
    c.refresh("warm")
    return c, [c.search("warm", q)["hits"]["total"] for q in QUERIES]


def _warmer_drained(node):
    w = node.threadpool.stats().get("warmer", {})
    return not w.get("active") and not w.get("queue")


class TestRestartPersistence:
    def test_warmed_restart_serves_observed_mix_with_zero_compiles(
            self, registry_guard, tmp_path):
        import jax

        from elasticsearch_tpu.common.jaxenv import sanitize

        REGISTRY.reset()
        LADDERS.reset()
        data = str(tmp_path / "n0")

        node = _boot(data)
        try:
            _, totals_a = _seed_and_serve(node)
            assert REGISTRY.stats()["specs_recorded"] > 0
        finally:
            node.close()  # persists the manifest under path.data
        manifest = os.path.join(data, MANIFEST_NAME)
        assert os.path.exists(manifest)
        # both lines of the phrase program are in it: the params' last word
        # is the launch's slot count, and `blk` is [1, slots, rows]
        with open(manifest) as f:
            phrase = [s for s in json.load(f)["specs"]
                      if s["site"] == "scoring.phrase"]
        assert sorted((s["params"][-1], s["args"][-1]["s"][1])
                      for s in phrase) == [(2, 2), (4, 4)]

        # simulated process restart: every in-process executable and all
        # warm/ladder state is gone; only path.data survives
        jax.clear_caches()
        REGISTRY.reset()
        LADDERS.reset()

        node = _boot(data)
        try:
            assert REGISTRY.stats()["specs_loaded"] > 0
            # the startup warm cycle drains the manifest on the warmer pool
            assert wait_until(lambda: REGISTRY.pending_count() == 0)
            assert wait_until(lambda: _warmer_drained(node))
            st = node.compile_warming.stats()
            assert st["warmed_total"] > 0 and st["warm_failures"] == 0
            ws = node.warmer.stats()
            assert ws["compile_warms_scheduled"] >= 1
            assert ws["compile_warm_cycles"] >= 1
            c = node.client()
            c.refresh("warm")
            assert wait_until(lambda: _warmer_drained(node))
            # the acceptance pin: the observed mix serves on the warmed node
            # with ZERO package compiles — the warm replay, not the serving
            # path, paid every XLA bill
            with sanitize(max_compiles=0) as rep:
                totals_b = [c.search("warm", q)["hits"]["total"]
                            for q in QUERIES]
            assert rep.compiles == 0, rep.compile_events
            assert totals_b == totals_a  # warmed ≠ wrong
        finally:
            node.close()

    @pytest.mark.parametrize("env_dir", [None, "/x/placed-by-the-caller"])
    def test_compile_cache_has_one_placement_rule(self, registry_guard, monkeypatch,
                                                  tmp_path, env_dir):
        """Where JAX_COMPILATION_CACHE_DIR is set no code sets another directory;
        unset, the cache is <checkout>/.jax_cache — never under path.data, whose
        default is a mkdtemp name that moves on every boot and so never hits."""
        import os

        import jax

        from elasticsearch_tpu.common import jaxenv
        from elasticsearch_tpu.common.settings import Settings

        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert jaxenv.compile_cache_dir() == os.path.join(checkout, ".jax_cache")
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            assert jaxenv.compile_cache_dir() == env_dir
        monkeypatch.setattr(jaxenv, "_persistent_cache_armed", False)
        assert jaxenv.armed_compile_cache_dir() is None
        before = jax.config.jax_compilation_cache_dir
        try:
            REGISTRY.configure(Settings.from_flat({}), str(tmp_path))  # node boot
            placed = jax.config.jax_compilation_cache_dir
            # what /_nodes/stats/compile_warming reports is jax's own setting
            assert REGISTRY.stats()["persistent_cache_dir"] == placed
        finally:  # the tests' cache stays where conftest put it
            from jax.experimental.compilation_cache import compilation_cache

            jax.config.update("jax_compilation_cache_dir", before)
            compilation_cache.reset_cache()
        if env_dir is None:
            assert placed == os.path.join(checkout, ".jax_cache")
        else:
            assert placed == before  # jax read the variable itself; code set nothing
        assert not str(placed or "").startswith(str(tmp_path))

    def test_compile_warming_kill_switch(self, registry_guard, tmp_path):
        REGISTRY.reset()
        node = _boot(str(tmp_path / "n1"),
                     {"node.compile_warming.enabled": "false"})
        try:
            _seed_and_serve(node)
            st = node.compile_warming.stats()
            assert not st["enabled"]
            assert st["specs_recorded"] == 0  # capture is off node-wide
            assert not node.warmer.schedule_compile_warm("manual")
        finally:
            node.close()
        # disabled: no manifest written either
        assert not os.path.exists(os.path.join(str(tmp_path / "n1"),
                                               MANIFEST_NAME))

    def test_warmer_kill_switch_blocks_scheduling(self, registry_guard,
                                                  tmp_path):
        import numpy as np

        REGISTRY.reset()
        node = _boot(str(tmp_path / "n2"),
                     {"indices.warmer.enabled": "false"})
        try:
            REGISTRY.record_launch("test.site", "dense", (1,),
                                   [np.zeros((2,), np.float32)])
            with REGISTRY._lock:
                REGISTRY._warmed.clear()
            assert REGISTRY.pending_count() == 1
            # warm work rides the warmer subsystem; its kill switch rules
            assert not node.warmer.schedule_compile_warm("manual")
        finally:
            node.close()


class TestResidentFunctionRows:
    @pytest.mark.parametrize("function", [
        {"field_value_factor": {"field": "n", "factor": 2, "modifier": "log1p"}},
        {"script_score": {"script": "log(doc['n'].value + 2) * _score"}}],
        ids=["rows", "script"])
    def test_a_hit_after_a_miss_reaches_the_same_program(
            self, registry_guard, tmp_path, function):
        """A function_score launch takes its rows as host arrays (a miss), as
        the device arrays the store just put (the second sighting) or as
        resident ones (a hit): one compiled program, no compile event
        between them."""
        from elasticsearch_tpu.common.jaxenv import sanitize
        from elasticsearch_tpu.ops.scoring import LAUNCHES

        node = _boot(str(tmp_path / "n0"))
        try:
            c, _totals = _seed_and_serve(node)
            body = {"query": {"function_score": {
                "query": {"match_all": {}}, "functions": [function]}},
                "size": 5}
            first = c.search("warm", body)  # compiles; the host's rows
            before = LAUNCHES.snapshot()
            with sanitize(max_compiles=0) as rep:
                stored = c.search("warm", body)
                resident = c.search("warm", body)
            assert rep.compiles == 0, rep.compile_events
            after = LAUNCHES.snapshot()
            assert after["fs_rows_evaluated"] > before["fs_rows_evaluated"]
            assert after["fs_rows_resident"] > before["fs_rows_resident"]
            for resp in (stored, resident):
                assert [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]] \
                    == [(h["_id"], h["_score"]) for h in first["hits"]["hits"]]
        finally:
            node.close()


# ---------------------------------------------------------------------------
# request-cache compression (satellite)
# ---------------------------------------------------------------------------


def _breaker():
    svc = CircuitBreakerService(Settings.from_flat(
        {"indices.breaker.total_budget": "1mb"}))
    return svc.breaker("request")


class TestRequestCacheCompression:
    def test_compressed_roundtrip_and_breaker_charges_resident(self):
        br = _breaker()
        rc = ShardRequestCache(Settings.EMPTY, breaker=br,
                               total_budget=1 << 20)
        data = b'{"hits":{"total":12345}}' * 200  # 4.8k, highly compressible
        key = ("i", 0, 1, "fp")
        assert rc.put(key, data)
        st = rc.stats()
        assert st["compressions"] == 1
        assert 0 < st["compressed_bytes"] < len(data)
        assert st["compressed_raw_bytes"] == len(data)
        assert st["compression_ratio"] < 1.0
        # the breaker holds the RESIDENT (compressed) size, not the raw size
        assert br.used == st["compressed_bytes"] + rc.ENTRY_OVERHEAD
        assert rc.get(key) == data  # hit path inflates back to the original

    def test_floor_keeps_small_values_raw(self):
        rc = ShardRequestCache(Settings.EMPTY, total_budget=1 << 20)
        assert rc.put(("i", 0, 1, "fp"), b"x" * 100)  # under the 1k floor
        st = rc.stats()
        assert st["compressions"] == 0 and st["compressed_bytes"] == 0
        assert st["compression_ratio"] == 1.0
        assert rc.get(("i", 0, 1, "fp")) == b"x" * 100

    def test_incompressible_value_stays_raw(self):
        rc = ShardRequestCache(Settings.EMPTY, total_budget=1 << 20)
        data = os.urandom(4096)  # zlib would grow it: keep-raw wins
        assert rc.put(("i", 0, 1, "fp"), data)
        assert rc.stats()["compressions"] == 0
        assert rc.get(("i", 0, 1, "fp")) == data

    def test_negative_floor_disables_compression(self):
        rc = ShardRequestCache(
            Settings.from_flat(
                {"indices.requests.cache.compress_min_bytes": "-1"}),
            total_budget=1 << 20)
        data = b"compress me please " * 400
        assert rc.put(("i", 0, 1, "fp"), data)
        assert rc.stats()["compressions"] == 0
        assert rc.get(("i", 0, 1, "fp")) == data

    def test_gauges_drop_with_entries(self):
        br = _breaker()
        rc = ShardRequestCache(Settings.EMPTY, breaker=br,
                               total_budget=1 << 20)
        data = b'{"aggs":{"m":{"value":59.0}}}' * 100
        rc.put(("i", 0, 1, "a"), data)
        rc.put(("i", 0, 2, "b"), data)
        assert rc.stats()["compressions"] == 2
        # view-advance invalidation drops view-1 entries and their gauges
        rc.invalidate_shard("i", 0, current_view=2)
        st = rc.stats()
        assert st["compressed_raw_bytes"] == len(data)
        rc.clear()
        st = rc.stats()
        assert st["compressed_bytes"] == 0
        assert st["compressed_raw_bytes"] == 0
        assert st["compression_ratio"] == 1.0
        assert br.used == 0  # every resident byte released

    def test_replace_releases_old_compressed_entry(self):
        br = _breaker()
        rc = ShardRequestCache(Settings.EMPTY, breaker=br,
                               total_budget=1 << 20)
        key = ("i", 0, 1, "fp")
        rc.put(key, b"old old old " * 300)
        first = rc.stats()["compressed_bytes"]
        rc.put(key, b"new new new new " * 300)
        st = rc.stats()
        assert st["entries"] == 1 and st["compressions"] == 2
        assert st["compressed_bytes"] != first or first == 0
        assert br.used == st["compressed_bytes"] + rc.ENTRY_OVERHEAD
