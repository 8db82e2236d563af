"""The sparse program (`scoring._sparse_impl` = `sparse_candidates` +
`sparse_reduce`, under jax.jit as `_get_sparse_compiled` launches it) against
a plain numpy reference written here: gather the blocks, decode tf and the
norm byte through `caches` by `modes`, weight, sum per document, apply the
bool semantics (`n_must` / `msm` / coord), rank by score then doc id."""

import numpy as np
import pytest

from elasticsearch_tpu.ops.device_index import BLOCK, TFN_BM25, TFN_TFIDF
from elasticsearch_tpu.ops.scoring import _MUST_SHIFT, _NOT_SHIFT, _sparse_impl

REL = 1e-5  # the differential suite's score tolerance (_tie_tolerant_equal)
K, PASSES = 10, 3


def _tf_plane(rng, shape, dtype):
    """Each rung of the tf ladder with values only it can hold."""
    if dtype == np.uint8:
        return rng.integers(0, 200, shape).astype(np.uint8)
    if dtype == np.int16:
        return rng.integers(0, 3000, shape).astype(np.int16)
    return (rng.random(shape) * 50).astype(np.float32)  # fractional: the f32 escape


@pytest.fixture(scope="module", params=[np.uint8, np.int16, np.float32],
                ids=["u8", "i16", "f32"])
def data(request):
    rng = np.random.default_rng(3)
    NB, Qb, TB, F = 64, 8, 16, 3
    doc_pad = 10_240
    group = rng.random((Qb, TB))
    qcnt = np.where(group < 0.6, 1,
                    np.where(group < 0.9, 1 << _MUST_SHIFT, 1 << _NOT_SHIFT))
    qw = (rng.random((Qb, TB)) * 3).astype(np.float32)
    return {
        "doc_pad": doc_pad,
        # doc_pad itself is the padding sentinel: some postings are invalid
        "blk_docs": rng.integers(0, doc_pad + 1, (NB, BLOCK)).astype(np.int32),
        "blk_tf": _tf_plane(rng, (NB, BLOCK), request.param),
        "blk_nb": rng.integers(0, 256, (NB, BLOCK)).astype(np.uint8),
        "caches": (rng.random((F, 256)) * 2 + 0.1).astype(np.float32),
        "modes": np.array([TFN_BM25, TFN_TFIDF, TFN_BM25], np.int32),
        "qblk": rng.integers(0, NB, (Qb, TB)).astype(np.int32),
        # the planner zeroes a must_not clause's weight
        "qw": np.where(qcnt == 1 << _NOT_SHIFT, 0, qw).astype(np.float32),
        "qconst": rng.random((Qb, TB)) < 0.2,
        "qcnt": qcnt.astype(np.int32),
        "qfid": rng.integers(0, F, (Qb, TB)).astype(np.int32),
        "n_must": rng.integers(0, 2, Qb).astype(np.int32),
        "msm": rng.integers(0, 3, Qb).astype(np.int32),
        "coord": (rng.random((Qb, 5)) + 0.5).astype(np.float32),
    }


def _run(data, *, simple, use_coord):
    """Launch through jax.jit — how serving launches it (_get_sparse_compiled
    wraps _sparse_impl in one jit)."""
    import jax
    import jax.numpy as jnp

    names = ("blk_docs", "blk_tf", "blk_nb", "caches", "modes", "qblk", "qw",
             "qconst", "qcnt", "qfid", "n_must", "msm", "coord")
    args = [jnp.asarray(data[n]) for n in names]

    @jax.jit
    def fn(*a):
        return _sparse_impl(*a, k=K, doc_pad=data["doc_pad"], passes=PASSES,
                            simple=simple, use_coord=use_coord)

    return [np.asarray(x) for x in jax.device_get(fn(*args))]


def _reference(data, q, *, simple, use_coord):
    """One query's (scores desc, doc ids, total) over ALL its matches."""
    doc_pad = data["doc_pad"]
    blk = data["qblk"][q]  # [TB]
    docs = data["blk_docs"][blk]  # [TB, B]
    tf = data["blk_tf"][blk].astype(np.float32)
    fid = data["qfid"][q][:, None]
    cv = data["caches"][fid, data["blk_nb"][blk]]
    tfn = np.where(data["modes"][fid] == TFN_BM25, tf / (tf + cv),
                   np.sqrt(tf) * cv).astype(np.float32)
    contrib = data["qw"][q][:, None] * np.where(
        data["qconst"][q][:, None], np.float32(1.0), tfn)
    valid = docs < doc_pad
    score = np.zeros(doc_pad, np.float32)
    np.add.at(score, docs[valid], contrib[valid])
    seen = np.bincount(docs[valid], minlength=doc_pad)
    # the program's segment-sum folds runs of at most 2**passes postings a doc
    assert seen.max() <= 1 << PASSES
    if simple:
        match = score > 0.0
    else:
        cnt = np.zeros(doc_pad, np.int64)
        np.add.at(cnt, docs[valid],
                  np.broadcast_to(data["qcnt"][q][:, None], docs.shape)[valid])
        should = cnt & 0x3FF
        must = (cnt >> _MUST_SHIFT) & 0x3FF
        must_not = cnt >> _NOT_SHIFT
        match = ((must == data["n_must"][q]) & (should >= data["msm"][q])
                 & (must_not == 0) & ((should + must) > 0))
        if use_coord:
            row = data["coord"][q]
            score = score * row[np.minimum(should + must, len(row) - 1)]
    ids = np.flatnonzero(match)
    order = np.lexsort((ids, -score[ids]))  # score desc, then doc id
    return score[ids][order], ids[order], len(ids)


@pytest.mark.parametrize("simple,use_coord", [
    (True, False), (False, False), (False, True)],
    ids=["simple", "bool", "bool_coord"])
def test_sparse_program_matches_numpy_reference(data, simple, use_coord):
    scores, docs, totals = _run(data, simple=simple, use_coord=use_coord)
    matched = 0
    for q in range(len(totals)):
        ref_s, ref_d, ref_total = _reference(
            data, q, simple=simple, use_coord=use_coord)
        assert totals[q] == ref_total, q
        n = min(K, ref_total)
        matched += n
        assert np.all(np.isneginf(scores[q, n:])), q
        np.testing.assert_allclose(scores[q, :n], ref_s[:n], rtol=REL, atol=1e-9)
        # ids exact at every rank whose score is clear of both neighbours
        # (the reference's rank n, where there is one, is the last rank's lower one)
        tol = REL * np.abs(ref_s[:n]) + 1e-9
        step = np.abs(np.diff(ref_s[:n + 1]))
        to_next = np.append(step, np.inf)[:n]
        to_prev = np.insert(step, 0, np.inf)[:n]
        clear = (to_next > tol) & (to_prev > tol)
        assert n == 0 or clear.any(), q
        assert np.array_equal(docs[q, :n][clear], ref_d[:n][clear]), q
    assert matched > 0
