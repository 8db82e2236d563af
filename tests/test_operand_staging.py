"""How a launch's operands are staged (ops/scoring.py, search/execute.py).

Counts and equality only, on the CPU: the range-built TermBatch equals,
array for array, one built by the per-block loops it replaced (kept HERE as
the reference), and a warmed plain batch with a dense overflow puts its
operand planes on the device in one transfer a launch (two for the sparse
launch, three for the dense one) and compiles nothing, under the transfer
guard."""

import functools

import numpy as np
import pytest

from elasticsearch_tpu.common import jaxenv
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index import Engine
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.ops.device_index import _ladder_bucket, packed_for
from elasticsearch_tpu.ops.scoring import (
    GROUP_MUST, GROUP_MUST_NOT, GROUP_SHOULD, MODE_BM25, MODE_CONST,
    MODE_TFIDF, build_term_batch)
from elasticsearch_tpu.search import ShardContext, parse_query
from elasticsearch_tpu.search.execute import (
    _assemble_batch, _dense_entries, execute_flat_batch, finalize_flat,
    lower_flat)
from elasticsearch_tpu.search.similarity import SimilarityService

pytestmark = pytest.mark.serving

N_DOCS = 600  # "common" is in every document: 5 postings blocks of 128


@pytest.fixture(scope="module")
def shard_ctx(tmp_path_factory):
    settings = Settings.from_flat({})
    svc = MapperService(settings)
    e = Engine(str(tmp_path_factory.mktemp("staging") / "shard0"), svc)
    for i in range(N_DOCS):
        words = ["common"]
        if i % 2 == 0:
            words.append("half")  # 300 documents: 3 blocks
        if i % 50 == 0:
            words.append("rare")
        words.append(f"w{i % 7}")
        e.index("doc", str(i), {"body": " ".join(words)})
    e.refresh()
    return ShardContext(e.acquire_searcher(), svc,
                        SimilarityService(settings, mapper_service=svc))


def _plan(ctx, body):
    plan = lower_flat(parse_query(body), ctx)
    assert plan is not None
    return plan


# ---------------------------------------------------------------------------
# the reference: the two per-block loops, as they stood before this change
# ---------------------------------------------------------------------------


def _per_block(entries):
    """execute._dense_entries' inner loop: one 6-tuple per (clause, block)."""
    return [(q, b, w, f, g, m)
            for (q, b0, b1, w, f, g, m, _row) in entries for b in range(b0, b1)]


def _reference_batch(blocks, n_must, msm, coord, nb_pad_row):
    """scoring.build_term_batch storing one scalar at a time."""
    M = _ladder_bucket("terms", max(len(blocks), 1), scoring.TAIL_FLOOR)
    qidx = np.zeros(M, np.int32)
    blk = np.full(M, nb_pad_row, np.int32)
    weight = np.zeros(M, np.float32)
    fidx = np.zeros(M, np.int32)
    group = np.zeros(M, np.int32)
    tfmode = np.zeros(M, np.int32)
    for i, (q, b, w, f, g, m) in enumerate(blocks):
        qidx[i], blk[i], weight[i], fidx[i], group[i], tfmode[i] = q, b, w, f, g, m
    return dict(qidx=qidx, blk=blk, weight=weight, fidx=fidx, group=group,
                tfmode=tfmode, n_must=n_must.astype(np.int32),
                msm=msm.astype(np.int32), coord=_widened(coord),
                blocks_real=len(blocks))


def _widened(coord):
    """The coord table up the pow-2 ladder from 4 columns, each row continued
    with its last value."""
    width = 4
    while width < coord.shape[1]:
        width *= 2
    out = np.repeat(coord[:, -1:].astype(np.float32), width, axis=1)
    out[:, : coord.shape[1]] = coord
    return out


def _assert_same_batch(batch, ref):
    for name, want in ref.items():
        got = getattr(batch, name)
        if name == "blocks_real":
            assert got == want
            continue
        assert got.dtype == want.dtype and got.shape == want.shape, name
        # bitwise: the weights are compared as the bits the device is handed
        assert got.tobytes() == want.tobytes(), name
    # what the launch puts on the device is those same columns, packed
    cols = ("qidx", "blk", "weight", "fidx", "group", "tfmode")
    assert batch.tri.dtype == np.int32 and batch.tri.shape == (6, len(ref["blk"]))
    for row, name in enumerate(cols):
        assert batch.tri[row].tobytes() == ref[name].tobytes(), name
    q = batch.qplane
    assert q.dtype == np.int32
    assert np.array_equal(q[:, 0], ref["n_must"])
    assert np.array_equal(q[:, 1], ref["msm"])
    assert q[:, 2:].tobytes() == ref["coord"].tobytes()


S, MU, NOT = GROUP_SHOULD, GROUP_MUST, GROUP_MUST_NOT
# (qidx, b0, b1, weight, fidx, group, mode) per clause; weights that float32
# cannot hold exactly, as finalize_flat's float64 products are. No clause's
# term has a head row here (tests/test_head_rows.py has those).
_CASES = {
    "one_clause": (1, [(0, 3, 8, 1.7, 0, S, MODE_BM25)]),
    "many_clauses_across_queries": (3, [
        (0, 0, 5, 0.1 * 3.7, 0, S, MODE_BM25),
        (0, 40, 41, 2.2, 1, S, MODE_BM25),
        (0, 7, 19, 1e-3, 0, S, MODE_BM25),
        (1, 100, 103, 5.5, 1, MU, MODE_TFIDF),
        (1, 0, 5, 0.1 * 3.7, 0, MU, MODE_TFIDF),
        (2, 63, 64, 9.25, 0, S, MODE_BM25)]),
    "zero_block_clause": (2, [
        (0, 4, 6, 1.1, 0, S, MODE_BM25),
        (0, 9, 9, 3.3, 0, S, MODE_BM25),
        (1, 9, 9, 3.3, 0, S, MODE_BM25),
        (1, 2, 3, 0.7, 0, S, MODE_BM25)]),
    "must_not_and_const": (2, [
        (0, 0, 4, 1.3, 0, MU, MODE_BM25),
        (0, 10, 12, 0.0, 0, NOT, MODE_BM25),
        (0, 20, 21, 2.0, 1, S, MODE_CONST),
        (1, 30, 33, 0.0, 1, S, MODE_CONST),
        (1, 5, 6, 4.4, 0, NOT, MODE_TFIDF)]),
    # 16 + 240 = 256 blocks: the foot of the ladder, so no pad row
    "exact_ladder_fit": (2, [
        (0, 0, 16, 1.9, 0, S, MODE_BM25),
        (1, 16, 256, 0.3, 0, S, MODE_BM25)]),
    "no_clause_resolved": (1, []),
}
CASES = {name: (Q, [(*e, -1) for e in entries])
         for name, (Q, entries) in _CASES.items()}


@pytest.mark.parametrize("case", list(CASES))
def test_range_built_batch_equals_the_per_block_one(case):
    Q, entries = CASES[case]
    n_must = np.arange(Q, dtype=np.int64) % 2
    msm = np.ones(Q, np.int64)
    coord = np.linspace(0.25, 1.0, Q * 3, dtype=np.float64).reshape(Q, 3)
    caches = np.ones((2, 256), np.float32)
    batch = build_term_batch(entries, Q, n_must, msm, coord, ["a", "b"],
                             caches, nb_pad_row=1023)
    ref = _reference_batch(_per_block(entries), n_must, msm, coord, 1023)
    _assert_same_batch(batch, ref)
    assert batch.head_slots == batch.blocks_as_rows == 0
    assert not batch.head.any()  # every slot the pad row (0 here), weight 0
    if case == "exact_ladder_fit":
        assert batch.blocks_real == len(batch.blk) == 256
    else:
        assert batch.blocks_real < len(batch.blk)
        assert np.all(batch.blk[batch.blocks_real:] == 1023)


def test_a_term_missing_from_the_segment_names_no_block(shard_ctx):
    """Through the real resolution: a clause whose term the segment does not
    hold leaves no record, and the batch is the per-block one."""
    ctx = shard_ctx
    plans = [_plan(ctx, {"match": {"body": "common unicorn rare"}}),
             _plan(ctx, {"bool": {"must": [{"term": {"body": "half"}}],
                                  "must_not": [{"term": {"body": "rare"}}],
                                  "should": [{"term": {"body": "nowhere"}}]}})]
    finals = [finalize_flat(p, ctx) for p in plans]
    (all_fields, field_idx, _rows, caches_stack,
     coord_tbl, n_must, msm) = _assemble_batch(plans, finals)
    (seg,) = ctx.searcher.segments
    packed = packed_for(seg)
    pad_row = packed.blk_docs.shape[0] - 1

    blocks = []  # the old _dense_entries, verbatim
    for qi, (resolved, _f, _c, _coord) in enumerate(finals):
        for (f, t, w, _fi, g, mode, _df) in resolved:
            tid = seg.term_id(f, t)
            if tid is None:
                continue
            b0, b1 = packed.blocks_for_term(tid)
            for b in range(b0, b1):
                blocks.append((qi, b, w, field_idx[f], g, mode))

    entries = _dense_entries(finals, seg, packed, field_idx)
    assert len(entries) == 4  # common, rare | half, rare: two terms resolve nowhere
    assert sum(b1 - b0 for (_q, b0, b1, *_r) in entries) == len(blocks) == 10
    # "common" and "half" match enough of the segment to have a row; the
    # blocks are compared here, so the batch is built as if none had
    assert [e[7] >= 0 for e in entries] == [True, False, True, False]
    entries = [(*e[:7], -1) for e in entries]
    batch = build_term_batch(entries, 2, n_must, msm, coord_tbl,
                             list(all_fields), caches_stack, nb_pad_row=pad_row)
    _assert_same_batch(batch, _reference_batch(blocks, n_must, msm, coord_tbl,
                                               pad_row))


# ---------------------------------------------------------------------------
# one put per launch
# ---------------------------------------------------------------------------


def test_warmed_overflow_batch_puts_its_planes_once_a_launch_and_compiles_nothing(
        shard_ctx, monkeypatch):
    """A plain batch of which one query overflows the sparse planner: the
    sparse launch hands the device its two operand planes and the dense
    launch its three in one explicit put each, and nothing else (the stacked
    tables are kept on the segment), with no compile event once warmed."""
    # steer the overflow in the test: two blocks a query, so the searches of
    # "common" (5 blocks) take the dense program on 600 documents
    monkeypatch.setattr(scoring, "launch_flat_sparse", functools.partial(
        scoring.launch_flat_sparse, tb_max=2))
    ctx = shard_ctx
    plans = [_plan(ctx, {"match": {"body": t}})
             for t in ("common rare", "rare w3", "w1", "half common")]
    warm = execute_flat_batch(plans, ctx, 10)
    before = scoring.LAUNCHES.snapshot()
    compiles = jaxenv.compile_events_total()
    with jaxenv.sanitize(max_compiles=0, transfers="disallow"):
        again = execute_flat_batch(plans, ctx, 10)
    after = scoring.LAUNCHES.snapshot()
    assert jaxenv.compile_events_total() == compiles
    sparse = after["launches_sparse"] - before["launches_sparse"]
    dense = after["launches_dense"] - before["launches_dense"]
    assert sparse >= 1 and dense == 1
    assert after["operand_puts"] - before["operand_puts"] == 2 * sparse + 3 * dense
    for w, a in zip(warm, again):
        assert a.hits == w.hits and a.total == w.total
    # and the overflowed searches answer what the sparse program answers
    monkeypatch.undo()
    for got, a in zip(again, execute_flat_batch(plans, ctx, 10)):
        assert got.total == a.total
        assert [d for (_s, d) in got.hits] == [d for (_s, d) in a.hits]
        np.testing.assert_allclose([s for (s, _d) in got.hits],
                                   [s for (s, _d) in a.hits], rtol=1e-6)
