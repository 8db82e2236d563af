"""Fused Pallas `sparse_score` kernel vs the composed-jnp quantized scan it
fuses — bitwise parity (interpret mode on CPU; the identical kernel compiles
for TPU). The composed path (`scoring.sparse_candidates` + `sparse_reduce`)
stays the behavioral reference; the kernel's final grid step executes the SAME
`sparse_reduce`, so any divergence here is a decode/accumulator bug."""

import numpy as np
import pytest

from elasticsearch_tpu.ops.device_index import BLOCK, TFN_BM25, TFN_TFIDF
from elasticsearch_tpu.ops.scoring import _sparse_impl

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def data():
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    NB, Qb, TB, F = 64, 8, 16, 3
    doc_pad = 10_240
    return {
        "doc_pad": doc_pad,
        "blk_docs": jnp.asarray(
            rng.integers(0, doc_pad + 1, (NB, BLOCK)).astype(np.int32)),
        "blk_tf": jnp.asarray(rng.integers(0, 200, (NB, BLOCK)).astype(np.uint8)),
        "blk_nb": jnp.asarray(rng.integers(0, 256, (NB, BLOCK)).astype(np.uint8)),
        "caches": jnp.asarray((rng.random((F, 256)) * 2 + 0.1).astype(np.float32)),
        "modes": jnp.asarray(np.array([TFN_BM25, TFN_TFIDF, TFN_BM25], np.int32)),
        "qblk": rng.integers(0, NB, (Qb, TB)).astype(np.int32),
        "qw": (rng.random((Qb, TB)) * 3).astype(np.float32),
        "qconst": rng.random((Qb, TB)) < 0.2,
        "qcnt": np.where(rng.random((Qb, TB)) < 0.7, 1, 1 << 10).astype(np.int32),
        "qfid": rng.integers(0, F, (Qb, TB)).astype(np.int32),
        "n_must": rng.integers(0, 2, Qb).astype(np.int32),
        "msm": np.ones(Qb, np.int32),
        "coord": (rng.random((Qb, 5)) + 0.5).astype(np.float32),
    }


def _run(data, *, use_pallas, simple, use_coord, k=10, passes=3):
    """Launch through jax.jit — exactly how serving launches it
    (_get_sparse_compiled wraps _sparse_impl in one jit; the eager path is not
    a production path and trips the transfer-guard sanitizer on fancy
    indexing)."""
    import jax
    import jax.numpy as jnp

    coord = data["coord"] if use_coord else np.ones_like(data["coord"])
    args = (data["blk_docs"], data["blk_tf"], data["blk_nb"], data["caches"],
            data["modes"], jnp.asarray(data["qblk"]), jnp.asarray(data["qw"]),
            jnp.asarray(data["qconst"]), jnp.asarray(data["qcnt"]),
            jnp.asarray(data["qfid"]), jnp.asarray(data["n_must"]),
            jnp.asarray(data["msm"]), jnp.asarray(coord))

    @jax.jit
    def fn(*a):
        return _sparse_impl(*a, k=k, doc_pad=data["doc_pad"], passes=passes,
                            simple=simple, use_coord=use_coord,
                            use_pallas=use_pallas)

    return fn(*args)


class TestSparseScore:
    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        """The kernel interprets only where ESTPU_PALLAS=interpret says so."""
        monkeypatch.setenv("ESTPU_PALLAS", "interpret")

    @pytest.mark.parametrize("simple,use_coord", [
        (True, False), (False, False), (False, True)])
    def test_bitwise_parity_with_composed(self, data, simple, use_coord):
        """Every variant of the fused kernel must be BIT-identical to the
        composed scan: same scores, same docs, same totals."""
        ref = _run(data, use_pallas=False, simple=simple, use_coord=use_coord)
        out = _run(data, use_pallas=True, simple=simple, use_coord=use_coord)
        for r, o, name in zip(ref, out, ("scores", "docs", "totals")):
            assert np.array_equal(np.asarray(r), np.asarray(o),
                                  equal_nan=True), name

    def test_inside_jit(self, data):
        """The kernel composes under jax.jit (how serving actually launches
        it — _get_sparse_compiled wraps _sparse_impl in one jit)."""
        import jax

        ref = _run(data, use_pallas=False, simple=True, use_coord=False)

        fn = jax.jit(lambda: _run(data, use_pallas=True, simple=True,
                                  use_coord=False))
        out = fn()
        for r, o in zip(ref, out):
            assert np.array_equal(np.asarray(r), np.asarray(o), equal_nan=True)

    def test_i16_and_f32_tf_planes(self, data):
        """The overflow rungs of the tf ladder ride the same kernel: widening
        int16/float32 planes must stay bit-identical to the composed path."""
        import jax.numpy as jnp

        for dt in (np.int16, np.float32):
            d = dict(data)
            d["blk_tf"] = jnp.asarray(np.asarray(data["blk_tf"]).astype(dt))
            ref = _run(d, use_pallas=False, simple=False, use_coord=False)
            out = _run(d, use_pallas=True, simple=False, use_coord=False)
            for r, o in zip(ref, out):
                assert np.array_equal(np.asarray(r), np.asarray(o),
                                      equal_nan=True), dt

    def test_full_sparse_path_parity_with_flag(self, tmp_path, monkeypatch):
        """ESTPU_PALLAS=interpret must produce bit-identical serving results."""
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.engine import Engine
        from elasticsearch_tpu.mapper.core import MapperService
        from elasticsearch_tpu.search import ShardContext, parse_query, search_shard
        from elasticsearch_tpu.search.similarity import SimilarityService

        settings = Settings.from_flat({})
        svc = MapperService(settings)
        eng = Engine(str(tmp_path / "pp"), svc)
        rng = np.random.default_rng(4)
        words = [f"w{i}" for i in range(50)]
        for i in range(200):
            eng.index("doc", str(i),
                      {"b": " ".join(rng.choice(words, size=12))})
        eng.refresh()
        ctx = ShardContext(eng.acquire_searcher(), svc,
                           SimilarityService(settings, mapper_service=svc))
        queries = [{"match": {"b": "w1 w2 w3"}},
                   {"bool": {"must": [{"term": {"b": "w4"}}],
                             "must_not": [{"term": {"b": "w5"}}]}}]
        # the CI pallas-interpret leg exports ESTPU_PALLAS for the whole job —
        # the baseline must be the COMPOSED path, not fused-vs-fused
        monkeypatch.delenv("ESTPU_PALLAS", raising=False)
        base = [search_shard(ctx, parse_query(q), 20, use_device=True)
                for q in queries]
        monkeypatch.setenv("ESTPU_PALLAS", "interpret")
        flagged = [search_shard(ctx, parse_query(q), 20, use_device=True)
                   for q in queries]
        for b, f in zip(base, flagged):
            assert b.total == f.total
            assert b.hits == f.hits
        eng.close()


class TestNeverInterpretsQuietly:
    """ESTPU_PALLAS=1 asks for the compiled kernel: off a TPU it raises, at the
    flag read and at the launch — it never falls to interpret mode."""

    def test_flag_one_raises_off_tpu(self, monkeypatch):
        from elasticsearch_tpu.ops.pallas_kernels import estpu_pallas_enabled

        monkeypatch.setenv("ESTPU_PALLAS", "1")
        with pytest.raises(RuntimeError, match="needs a TPU"):
            estpu_pallas_enabled()
        monkeypatch.setenv("ESTPU_PALLAS", "interpret")
        assert estpu_pallas_enabled() is True
        monkeypatch.setenv("ESTPU_PALLAS", "0")
        assert estpu_pallas_enabled() is False

    @pytest.mark.parametrize("flag", ["1", None])
    def test_launch_raises_off_tpu(self, data, monkeypatch, flag):
        if flag is None:
            monkeypatch.delenv("ESTPU_PALLAS", raising=False)
        else:
            monkeypatch.setenv("ESTPU_PALLAS", flag)
        with pytest.raises(RuntimeError, match="needs a TPU"):
            _run(data, use_pallas=True, simple=True, use_coord=False)
