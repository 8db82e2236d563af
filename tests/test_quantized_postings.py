"""Quantized device postings: layout ladder, device-side byte315 decode, and
differential hit-ordering parity between the quantized device scorer and the
host scorer (the behavioral reference) — including the int overflow rungs and
the f32 escape hatch.

The resident layout (ops/device_index.py): docs i32 + tf u8/i16/f32 + norm
byte u8, tf→tfn decoded INSIDE the scan against the SimTables 256-entry LUT.
"""

from __future__ import annotations

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.common.smallfloat import (
    NORM_TABLE,
    byte315_to_float,
    float_to_byte315,
)
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.mapper.core import MapperService
from elasticsearch_tpu.ops.device_index import (
    TF_F32,
    TF_I16,
    TF_U8,
    bytes_per_posting,
    choose_tf_layout,
    ensure_blk_freqs,
    pack_estimate_bytes,
    packed_for,
    packed_resident_bytes,
)
from elasticsearch_tpu.search import ShardContext, parse_query, search_shard
from elasticsearch_tpu.search.similarity import SimilarityService


def _mk_engine(tmp_path, docs):
    settings = Settings.from_flat({})
    svc = MapperService(settings)
    eng = Engine(str(tmp_path / "qidx"), svc)
    for i, d in enumerate(docs):
        eng.index("doc", str(i), d)
    eng.refresh()
    ctx = ShardContext(eng.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc))
    return eng, ctx


def _assert_device_host_parity(ctx, queries, k=25):
    """Same totals, same ranking — tolerant only to adjacent swaps among
    near-equal scores (multi-clause sums accumulate in segment-sum tree order
    on device vs sequential clause order on host; the repo-wide differential
    contract, see test_randomized_differential._tie_tolerant_equal)."""
    from tests.test_randomized_differential import _tie_tolerant_equal

    for q in queries:
        dev = search_shard(ctx, parse_query(q), k, use_device=True)
        host = search_shard(ctx, parse_query(q), k, use_device=False)
        assert dev.total == host.total, q
        assert _tie_tolerant_equal(dev, host), (q, dev.hits, host.hits)


class TestByte315DeviceDecode:
    def test_device_table_matches_host(self):
        from elasticsearch_tpu.common.smallfloat import (
            jnp_byte315_to_float, jnp_norm_table)

        all_bytes = np.arange(256, dtype=np.uint8)
        assert np.array_equal(np.asarray(jnp_norm_table()),
                              NORM_TABLE.astype(np.float32))
        assert np.array_equal(np.asarray(jnp_byte315_to_float(all_bytes)),
                              byte315_to_float(all_bytes))

    def test_round_trip_through_encode(self):
        """byte315 decode must round-trip float_to_byte315 EXACTLY — the
        quantized layout stores only the byte, so decode(encode(x)) is the
        value every scorer (host, composed, fused) must agree on."""
        from elasticsearch_tpu.common.smallfloat import jnp_byte315_to_float

        rng = np.random.default_rng(7)
        vals = (rng.random(4096).astype(np.float32) * 4.0) + 1e-4
        enc = float_to_byte315(vals)
        dec_host = byte315_to_float(enc)
        dec_dev = np.asarray(jnp_byte315_to_float(enc))
        assert np.array_equal(dec_host, dec_dev)
        # re-encoding the quantized value is a fixed point
        assert np.array_equal(float_to_byte315(dec_dev), enc)


class TestTfLayoutLadder:
    def test_choose_layout(self):
        assert choose_tf_layout(np.zeros(0, np.float32)) == TF_U8
        assert choose_tf_layout(np.array([1, 3, 255], np.float32)) == TF_U8
        assert choose_tf_layout(np.array([1, 256], np.float32)) == TF_I16
        assert choose_tf_layout(np.array([1, 32767], np.float32)) == TF_I16
        assert choose_tf_layout(np.array([1, 32768], np.float32)) == TF_F32
        assert choose_tf_layout(np.array([1.5], np.float32)) == TF_F32
        assert bytes_per_posting(TF_U8) == 6
        assert bytes_per_posting(TF_I16) == 7
        assert bytes_per_posting(TF_F32) == 9

    def test_u8_default_layout_and_parity(self, tmp_path):
        rng = np.random.default_rng(11)
        words = [f"w{i}" for i in range(40)]
        docs = [{"b": " ".join(rng.choice(words, size=15))} for _ in range(150)]
        eng, ctx = _mk_engine(tmp_path, docs)
        seg = ctx.searcher.segments[0]
        packed = packed_for(seg)
        assert packed.tf_layout == TF_U8
        assert np.asarray(packed.blk_tf).dtype == np.uint8
        _assert_device_host_parity(ctx, [
            {"match": {"b": "w1 w2 w3"}},
            {"bool": {"must": [{"term": {"b": "w4"}}],
                      "should": [{"term": {"b": "w5"}}, {"term": {"b": "w6"}}],
                      "must_not": [{"term": {"b": "w7"}}]}},
        ])
        eng.close()

    def test_i16_overflow_blocks_and_parity(self, tmp_path):
        """A term with tf > 255 pushes the segment to the int16 rung; scoring
        must stay identical to the host scorer (regression for the overflow
        escape: quantization must never clip a frequency)."""
        rng = np.random.default_rng(12)
        words = [f"w{i}" for i in range(20)]
        docs = [{"b": " ".join(rng.choice(words, size=10))} for _ in range(80)]
        docs[3] = {"b": "hot " * 300 + "w1 w2"}  # tf(hot)=300 > 255
        eng, ctx = _mk_engine(tmp_path, docs)
        seg = ctx.searcher.segments[0]
        assert float(seg.post_freqs.max()) > 255
        packed = packed_for(seg)
        assert packed.tf_layout == TF_I16
        assert np.asarray(packed.blk_tf).dtype == np.int16
        # the overflowing frequency survives quantization exactly
        assert int(np.asarray(packed.blk_tf).max()) == int(seg.post_freqs.max())
        _assert_device_host_parity(ctx, [
            {"match": {"b": "hot w1"}},
            {"match": {"b": "w1 w2 w3"}},
        ])
        eng.close()

    def test_f32_escape_hatch_and_parity(self, tmp_path):
        """Non-integral frequencies (synthetic corpora / index-time folding)
        take the f32 escape plane — bit-exact freqs, host parity intact."""
        rng = np.random.default_rng(13)
        words = [f"w{i}" for i in range(20)]
        docs = [{"b": " ".join(rng.choice(words, size=10))} for _ in range(60)]
        eng, ctx = _mk_engine(tmp_path, docs)
        seg = ctx.searcher.segments[0]
        # engineer fractional tf BEFORE the first pack (both scorers read the
        # same CSR, so parity still must hold)
        seg.post_freqs = seg.post_freqs + np.float32(0.5)
        seg._device_cache.clear()
        packed = packed_for(seg)
        assert packed.tf_layout == TF_F32
        assert np.asarray(packed.blk_tf).dtype == np.float32
        _assert_device_host_parity(ctx, [{"match": {"b": "w1 w2"}}])
        eng.close()


class TestLazyDensePlane:
    def test_sparse_only_segment_never_pays_dense_plane(self, tmp_path):
        rng = np.random.default_rng(14)
        words = [f"w{i}" for i in range(30)]
        docs = [{"b": " ".join(rng.choice(words, size=12)), "n": i}
                for i in range(100)]
        eng, ctx = _mk_engine(tmp_path, docs)
        seg = ctx.searcher.segments[0]
        search_shard(ctx, parse_query({"match": {"b": "w1 w2"}}), 10,
                     use_device=True)
        packed = packed_for(seg)
        assert packed.blk_freqs is None  # the blk_freqs-drop rule
        assert packed_resident_bytes(packed) == (
            np.asarray(packed.blk_docs).shape[0] * 128
            * bytes_per_posting(packed.tf_layout))
        # the dense fallback faults the f32 plane in, once
        plane = ensure_blk_freqs(packed)
        assert packed.blk_freqs is plane
        assert ensure_blk_freqs(packed) is plane
        assert np.asarray(plane).dtype == np.float32
        assert packed_resident_bytes(packed) == (
            np.asarray(packed.blk_docs).shape[0] * 128
            * bytes_per_posting(packed.tf_layout, dense_resident=True))
        eng.close()


class TestSimTables:
    def test_table_swap_is_cheap_and_stable(self, tmp_path):
        """avgdl drift re-ensures as a 1 KB LUT swap: fid rows stay stable for
        already-known fields and the postings planes are untouched."""
        from elasticsearch_tpu.ops.device_index import TFN_BM25, ensure_sim_tables

        rng = np.random.default_rng(15)
        docs = [{"b": " ".join(rng.choice([f"w{i}" for i in range(10)], size=8))}
                for _ in range(40)]
        eng, ctx = _mk_engine(tmp_path, docs)
        packed = packed_for(ctx.searcher.segments[0])
        c1 = np.ones(256, np.float32)
        t1 = ensure_sim_tables(packed, {"b": (TFN_BM25, c1)})
        assert ensure_sim_tables(packed, {"b": (TFN_BM25, c1)}) is t1
        tf_plane = packed.blk_tf
        c2 = np.full(256, 2.0, np.float32)  # the "avgdl moved" case
        t2 = ensure_sim_tables(packed, {"b": (TFN_BM25, c2), "other": (TFN_BM25, c1)})
        assert t2 is not t1
        assert t2.fid["b"] == t1.fid["b"]  # stable row for known fields
        assert packed.blk_tf is tf_plane  # no postings re-bake
        eng.close()


class TestRandomizedQuantizedParity:
    def test_fuzz_multi_field_ordering(self, tmp_path):
        """Randomized differential: multi-field bool queries (distinct fid
        rows in one batch) — quantized device ordering == host ordering."""
        rng = np.random.default_rng(17)
        wa = [f"a{i}" for i in range(25)]
        wb = [f"b{i}" for i in range(25)]
        docs = [{"t": " ".join(rng.choice(wa, size=6)),
                 "b": " ".join(rng.choice(wb, size=14))} for _ in range(120)]
        eng, ctx = _mk_engine(tmp_path, docs)
        for _ in range(12):
            clauses = {"should": [
                {"term": {"t": wa[int(rng.integers(len(wa)))]}},
                {"term": {"b": wb[int(rng.integers(len(wb)))]}},
            ]}
            if rng.random() < 0.5:
                clauses["must"] = [{"term": {"b": wb[int(rng.integers(len(wb)))]}}]
            if rng.random() < 0.3:
                clauses["must_not"] = [{"term": {"t": wa[int(rng.integers(len(wa)))]}}]
            _assert_device_host_parity(ctx, [{"bool": clauses}], k=20)
        eng.close()
