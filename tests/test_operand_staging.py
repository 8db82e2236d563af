"""How a launch's operands are staged (ops/scoring.py, search/execute.py).

Counts and equality only, on the CPU: the range-built TermBatch equals,
array for array, one built by the per-block loops it replaced (kept HERE as
the reference), and a warmed plain batch with a dense overflow puts its
operands on the device in one transfer a launch (two leaves for the sparse
launch, ONE for the dense one: its packed plane) and compiles nothing, under
the transfer guard. Then the dense launch ABI itself: the packed plane taken
apart inside a program equals the host views word for word, every dense
family answers bit for bit what the three-plane launch it replaced answered
(kept HERE as the reference, over the same scoring core), a warmed launch
puts one leaf and dispatches no program but its own, and the warm registry
replays a launch whose mask is a tuple of rows."""

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
    launch its ONE packed plane in one explicit put each, and nothing else
    (the stacked tables are kept on the segment), with no compile event once
    warmed."""
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
    assert after["operand_puts"] - before["operand_puts"] == 2 * sparse + 1 * dense
    for w, a in zip(warm, again):
        assert a.hits == w.hits and a.total == w.total
    # and the overflowed searches answer what the sparse program answers
    monkeypatch.undo()
    for got, a in zip(again, execute_flat_batch(plans, ctx, 10)):
        assert got.total == a.total
        assert [d for (_s, d) in got.hits] == [d for (_s, d) in a.hits]
        np.testing.assert_allclose([s for (s, _d) in got.hits],
                                   [s for (s, _d) in a.hits], rtol=1e-6)


# ---------------------------------------------------------------------------
# the dense launch ABI: one packed plane, taken apart inside the program
# ---------------------------------------------------------------------------


def _random_batch(M_target: int, Q: int, seed: int):
    """A TermBatch of `Q` queries whose triples fill the rung `M_target` of
    the `terms` ladder, with head slots, weights float32 cannot round-trip
    through a decimal and a coord table off the pow-2 ladder."""
    rng = np.random.default_rng(seed)
    entries = []
    left = M_target - 3  # inside the rung, so its tail is padding
    for q in range(Q):
        take = left // (Q - q)
        left -= take
        entries.append((q, 7, 7 + take, float(rng.uniform(0.1, 9.0)) / 3.0, 0,
                        int(rng.integers(0, 3)), MODE_BM25, -1))
        for slot in range(int(rng.integers(0, 4))):
            entries.append((q, 900, 905, float(rng.uniform(0.1, 9.0)) / 7.0,
                            1, GROUP_SHOULD, MODE_TFIDF, 3 + slot))
    coord = rng.uniform(0.1, 1.0, (Q, 5))
    return build_term_batch(entries, Q, rng.integers(0, 3, Q),
                            rng.integers(0, 2, Q), coord, ["a", "b"],
                            np.ones((2, 256), np.float32), nb_pad_row=4095,
                            head_pad_row=63)


@pytest.mark.parametrize("Q", [1, 2, 4, 8, 64])
@pytest.mark.parametrize("M", [scoring.TAIL_FLOOR, 512, 2048])
def test_the_packed_plane_taken_apart_in_a_program_equals_the_host_views(M, Q):
    """tri | qplane | head, word for word, the f32 weights and the coord rows
    by their bits, and a tail's scalars from the plane's end."""
    import jax

    batch = _random_batch(M, Q, seed=M + Q)
    assert batch.tri.shape == (6, M) and len(batch.blk) == M
    # the three planes ARE the one buffer: nothing is copied to stage them
    for view in (batch.tri, batch.qplane, batch.head):
        assert np.shares_memory(view, batch.plane)
    assert batch.plane.size == batch.tri.size + batch.qplane.size \
        + batch.head.size
    assert batch.qplane.shape == (Q, 2 + 8)  # five columns up the ladder

    scalars = np.array([3.4e38, 1.0 / 3.0, -0.0], np.float32)
    plane = np.concatenate([batch.plane, scalars.view(np.int32)])

    @jax.jit
    def unpack(plane):
        plane, tail = scoring._plane_scalars(plane, 3)
        tri, qplane, head = scoring._plane_views(plane, M, Q)
        weight = jax.lax.bitcast_convert_type(tri[scoring._T_WEIGHT],
                                              np.float32)
        return tri, qplane, head, weight, scoring._unpack_qplane(qplane), tail

    tri, qplane, head, weight, (n_must, msm, coord), tail = jax.device_get(
        unpack(jax.device_put(plane)))
    assert tri.tobytes() == batch.tri.tobytes()
    assert qplane.tobytes() == batch.qplane.tobytes()
    assert head.tobytes() == batch.head.tobytes()
    assert weight.tobytes() == batch.weight.tobytes()
    assert np.array_equal(n_must, batch.n_must) and np.array_equal(msm, batch.msm)
    assert coord.tobytes() == batch.coord.tobytes()
    assert np.stack(tail).tobytes() == scalars.tobytes()


# -- every dense family against the three-plane launch it replaced -----------


FAMILY_MAPPING = {"doc": {"properties": {
    "body": {"type": "string"}, "rank": {"type": "integer"},
    "day": {"type": "integer"}}}}


@pytest.fixture(scope="module")
def family_ctx(tmp_path_factory):
    """One segment with a text field, a whole-number column (its sums ride
    integer limbs) and a bucket key."""
    settings = Settings.from_flat({"index.similarity.default.type": "BM25"})
    svc = MapperService(settings)
    svc.put_mapping("doc", FAMILY_MAPPING)
    e = Engine(str(tmp_path_factory.mktemp("families") / "shard0"), svc)
    for i in range(N_DOCS):
        words = ["common"] * (1 + i % 3)
        if i % 2 == 0:
            words.append("half")
        if i % 50 == 0:
            words.append("rare")
        words.append(f"w{i % 7}")
        e.index("doc", str(i), {"body": " ".join(words), "rank": (i * 37) % 97,
                                "day": i % 30})
    e.refresh()
    yield ShardContext(e.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc))
    e.close()


def _three_plane_abi(tail, *, n_queries, doc_pad, simple=False, scalars=0,
                     **statics):
    """The dense launch ABI as it stood before the packed plane (blk_docs,
    blk_freqs, head_rows, live_parent, doc_table, tri, qplane, head, *extra),
    over the same scoring core (`scalars`: taken and unused, a tail's scalars
    were operands of their own)."""
    def wrapper(blk_docs, blk_freqs, head_rows, live_parent, doc_table,
                tri, qplane, head, *extra):
        scores, counts = scoring._dense_accumulate(
            blk_docs, blk_freqs, head_rows, doc_table, tri, head,
            Q=n_queries, doc_pad=doc_pad, counters=not simple)
        if simple:
            match = (scores > 0.0) & live_parent[None, :]
        else:
            scores, match = scoring._dense_semantics(
                scores, counts, live_parent, *scoring._unpack_qplane(qplane))
        return tail(scores, match, *extra, **statics)

    return wrapper


def _score_row_abi(tail, *, n_queries, doc_pad, scalars=0, **statics):
    """The unscored launch ABI as it stood: (live_parent, score [Q], *extra)."""
    def wrapper(live_parent, score, *extra):
        import jax.numpy as jnp

        match = jnp.broadcast_to(live_parent[None, :], (n_queries, doc_pad))
        scores = jnp.broadcast_to(score[:, None], (n_queries, doc_pad))
        return tail(scores, match, *extra, **statics)

    return wrapper


def _is_mask_rows(x) -> bool:
    return isinstance(x, tuple) and bool(x) and all(
        getattr(r, "dtype", None) == np.bool_ and np.ndim(r) == 1 for r in x)


def _as_it_stood(mp) -> list:
    """The launch sites as they stood before this change, for one run; the
    list gains the host leaves of each launch's put."""
    import jax
    import jax.numpy as jnp

    puts = []

    def three_plane_args(packed, batch, *extra, scalars=()):
        """scoring._dense_args as it stood: three operand planes, every
        scalar a leaf of its own, the whole operand tuple through one
        device_put, mask rows stacked eagerly before it."""
        extra = [jnp.stack([jax.device_put(r) for r in x])
                 if _is_mask_rows(x) else x for x in extra]
        extra += [np.float32(v) for v in scalars]
        if isinstance(batch, scoring.ConstBatch):
            host = (batch.score, *extra)
            resident = (packed.live_parent,)
        else:
            host = (batch.tri, batch.qplane, batch.head, *extra)
            resident = (packed.blk_docs, scoring.ensure_blk_freqs(packed),
                        scoring.ensure_head_rows(packed), packed.live_parent,
                        scoring._doc_table(packed, batch))
        puts.append(sum(isinstance(leaf, (np.ndarray, np.generic))
                        for leaf in jax.tree_util.tree_leaves(host)))
        return (*resident, *jax.device_put(host))

    mp.setattr(scoring, "_dense_abi", _three_plane_abi)
    mp.setattr(scoring, "_unscored_abi", _score_row_abi)
    mp.setattr(scoring, "_dense_args", three_plane_args)
    mp.setattr(scoring, "_DENSE_STATIC_ARGNUMS", ())
    mp.setattr(scoring, "_compiled_cache", {})
    return puts


def _phase(ctx, body):
    from elasticsearch_tpu.search.aggregations import reduce_aggs
    from elasticsearch_tpu.search.service import (
        SERVING_COUNTERS, execute_query_phase, parse_search_body)

    req = parse_search_body(body)
    host = SERVING_COUNTERS["host"]
    res = execute_query_phase(ctx, req, use_device=True)
    assert SERVING_COUNTERS["host"] == host, "the host scorer answered"
    aggs = reduce_aggs(req.aggs, res.agg_partials) if req.aggs else None
    return (res.total, [d for (_s, d, _v) in res.docs],
            [np.float32(s).tobytes() for (s, _d, _v) in res.docs],
            [v for (_s, _d, v) in res.docs],
            np.float32(res.max_score).tobytes(), aggs)


_RANK = {"range": {"rank": {"gte": 10, "lt": 70}}}
_TEXT = {"match": {"body": "common half rare w3"}}
_BOOL = {"bool": {"must": [{"term": {"body": "common"}}],
                  "must_not": [{"term": {"body": "rare"}}],
                  "should": [{"term": {"body": "half"}}, {"term": {"body": "w3"}}]}}
_ALL = {"match_all": {"boost": 1.7}}
_AGGS = {"r": {"stats": {"field": "rank"}},
         "d": {"histogram": {"field": "day", "interval": 7},
               "aggs": {"s": {"sum": {"field": "rank"}}}}}
_FVF = [{"field_value_factor": {"field": "rank"}}]
_SCRIPT = [{"script_score": {"script": "_score * log(2 + doc['rank'].value)"},
            "weight": 1.5}]


def _filtered(q):
    return {"filtered": {"query": q, "filter": _RANK}}


def _fs(q, functions, **more):
    return {"function_score": {"query": q, "functions": functions, **more}}


# name -> (search body, the launch counter that must rise)
FAMILIES = {
    "dense_simple": ({"query": _TEXT}, "launches_dense"),
    "dense_bool": ({"query": _BOOL}, "launches_dense"),
    "filtered": ({"query": _filtered(_TEXT)}, "launches_dense"),
    "aggs_bucket_and_limbed_sum": (
        {"query": _filtered(_BOOL), "aggs": _AGGS}, "launches_dense"),
    "sorted_asc": ({"query": _TEXT, "sort": [{"rank": "asc"}],
                    "track_scores": True}, "launches_dense"),
    "sorted_desc": ({"query": _filtered(_TEXT), "sort": [{"rank": "desc"}],
                     "track_scores": True}, "launches_dense"),
    "fs_rows": ({"query": _fs(_TEXT, _FVF, boost_mode="sum", max_boost=50.0,
                              min_score=0.5, boost=1.3)}, "launches_dense"),
    "fs_script": ({"query": _fs(_filtered(_TEXT), _SCRIPT, max_boost=40.0,
                                boost=0.7)}, "launches_dense"),
    "filtered_unscored": ({"query": _filtered(_ALL)}, "launches_unscored"),
    "aggs_unscored": ({"query": _filtered(_ALL), "aggs": _AGGS},
                      "launches_unscored"),
    "sorted_asc_unscored": ({"query": _ALL, "sort": [{"rank": "asc"}]},
                            "launches_unscored"),
    "sorted_desc_unscored": ({"query": _filtered(_ALL),
                              "sort": [{"rank": "desc"}]},
                             "launches_unscored"),
    "fs_rows_unscored": ({"query": _fs(_ALL, _FVF, boost_mode="replace",
                                       max_boost=60.0, boost=1.1)},
                         "launches_fs_unscored"),
    "fs_script_unscored": ({"query": _fs(_filtered(_ALL), _SCRIPT,
                                         min_score=1.0)},
                           "launches_fs_unscored"),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_every_dense_family_answers_what_its_three_plane_launch_answered(
        family_ctx, family, monkeypatch):
    """Scores, documents, totals, sort values and aggregations, bit for bit,
    on the same segment: the plane is the three planes' own words and the
    in-program stack is the same stack."""
    body, counter = FAMILIES[family]
    body = {"size": 25, **body}
    # every plain search overflows: the dense program, as head terms do at size
    monkeypatch.setattr(scoring, "launch_flat_sparse", functools.partial(
        scoring.launch_flat_sparse, tb_max=0))
    # mask rows and function rows resident, so that a filtered family's mask
    # is a tuple of rows: stacked in the program here, eagerly as it stood
    _resident(monkeypatch, family_ctx, body)
    before = scoring.LAUNCHES.snapshot()
    got = _phase(family_ctx, body)
    after = scoring.LAUNCHES.snapshot()
    assert after[counter] > before[counter], "the family did not launch"
    assert after["operand_puts"] - before["operand_puts"] == 1
    assert got[0] > 0 and got[1]
    with pytest.MonkeyPatch.context() as mp:
        puts = _as_it_stood(mp)
        want = _phase(family_ctx, body)
    assert len(puts) == 1 and puts[0] >= (1 if "unscored" in family else 3)
    assert got == want


# -- one leaf a warmed launch, no program but its own --------------------------


def _resident(mp, ctx, *bodies):
    """`ctx` under a filter and row cache of its own (until `mp` is undone),
    each of `bodies` searched until what it reads is resident (the store
    admits on the second sighting); the cache."""
    from elasticsearch_tpu.ops.device_index import DeviceFilterCache

    cache = DeviceFilterCache()
    mp.setattr(ctx, "filter_cache", cache)
    for body in bodies:
        for _ in range(3):
            _phase(ctx, body)
    return cache


# name -> (body, a filter/row cache?, host leaves a warmed launch puts)
PUTS = {
    "dense_plain": ({"query": _TEXT}, False, 1),
    "filtered_host_mask": ({"query": _filtered(_TEXT)}, False, 2),
    "filtered_resident_rows": ({"query": _filtered(_TEXT)}, True, 1),
    "aggs_resident_rows": ({"query": _filtered(_BOOL), "aggs": _AGGS}, True, 1),
    "sorted_unfiltered": ({"query": _TEXT, "sort": [{"rank": "desc"}]}, False, 1),
    "fs_rows_resident_rows": ({"query": _fs(_filtered(_TEXT), _FVF)}, True, 1),
    "sorted_unscored_resident": ({"query": _filtered(_ALL),
                                  "sort": [{"rank": "asc"}]}, True, 1),
    "fs_rows_unscored_resident": ({"query": _fs(_ALL, _FVF, max_boost=9.0)},
                                  True, 1),
}


@pytest.mark.parametrize("case", list(PUTS))
def test_a_warmed_dense_launch_puts_one_leaf_under_the_transfer_guard(
        family_ctx, case, monkeypatch):
    body, cached, leaves = PUTS[case]
    body = {"size": 10, **body}
    monkeypatch.setattr(scoring, "launch_flat_sparse", functools.partial(
        scoring.launch_flat_sparse, tb_max=0))
    monkeypatch.setattr(family_ctx, "filter_cache", None)
    if cached:
        _resident(monkeypatch, family_ctx, body)
    warm = _phase(family_ctx, body)
    before = scoring.LAUNCHES.snapshot()
    with jaxenv.sanitize(max_compiles=0, transfers="disallow"):
        again = _phase(family_ctx, body)
    d = {k: v - before[k] for k, v in scoring.LAUNCHES.snapshot().items()}
    launches = d["launches_dense"] + d["launches_unscored"]
    assert launches == 1 and d["launches_sparse"] == 0
    assert d["operand_puts"] == leaves
    assert bool(d["mask_put_bytes"]) == (leaves == 2)
    assert again == warm


def test_a_warmed_filtered_batch_of_resident_rows_dispatches_its_program_alone(
        family_ctx, monkeypatch):
    """Between collect and pull the drainer hands the device ONE program: with
    every executable forgotten (jax.clear_caches), each program the batch
    dispatches is a compile event (a persistent-cache hit counts too), and a
    batch of four filtered searches under two resident rows counts one: no
    expand_dims, no concatenate, no stack beside jit_estpu_scoring_aggs_filtered."""
    import jax

    from elasticsearch_tpu.search.execute import dispatch_flat_batch

    ctx = family_ctx
    other = {"range": {"day": {"gte": 3, "lt": 21}}}
    bodies = [{"filtered": {"query": {"match": {"body": t}}, "filter": f}}
              for t, f in (("common rare", _RANK), ("half w3", other),
                           ("rare", _RANK), ("common w5", other))]
    # (the rows live with the segment: one an earlier cache admitted is found
    # there, so this cache may count one of the two alone)
    cache = _resident(monkeypatch, ctx, *({"query": b} for b in bodies[:2]))
    assert cache.stats()["masks"] >= 1
    plans = [_plan(ctx, b) for b in bodies]
    warm = dispatch_flat_batch(plans, ctx, 10).merge()
    jax.clear_caches()
    puts = scoring.LAUNCHES.snapshot()
    compiles = jaxenv.compile_events_total()
    families = dict(jaxenv.compile_events_by_family())
    with jax.transfer_guard("disallow"):
        again = dispatch_flat_batch(plans, ctx, 10).merge()
    d = {k: v - puts[k] for k, v in scoring.LAUNCHES.snapshot().items()}
    assert d["launches_dense"] == 1 and d["mask_put_bytes"] == 0
    assert d["operand_puts"] == 1
    assert jaxenv.compile_events_total() - compiles == 1
    grew = {f: n - families.get(f, 0)
            for f, n in jaxenv.compile_events_by_family().items()
            if n != families.get(f, 0)}
    assert grew == {"filtered": 1}
    for w, a in zip(warm, again):
        assert a.hits == w.hits and a.total == w.total > 0


def test_the_warm_registry_replays_a_launch_whose_mask_is_a_tuple_of_rows(
        family_ctx, monkeypatch, tmp_path):
    """A launch under resident mask rows records the rows as a tuple operand
    (and M as a literal); a restarted warmer (executables forgotten, the
    registry loaded from its manifest) compiles that program off the query
    path, and the search then compiles nothing."""
    import jax

    from elasticsearch_tpu.common.compilecache import (LADDERS, MANIFEST_NAME,
                                                       REGISTRY)

    ctx = family_ctx
    body = {"size": 10, "query": _filtered(_TEXT)}
    try:
        REGISTRY.reset()
        _resident(monkeypatch, ctx, body)
        want = _phase(ctx, body)
        specs = [s for s in REGISTRY._specs.values() if s.site == "scoring.aggs"]
        rows = [a for s in specs for a in s.argspec
                if isinstance(a, dict) and "t" in a and a["t"]
                and all(e == {"s": [1024], "d": "bool"} for e in a["t"])]
        assert rows, "no recorded launch took its mask as a tuple of rows"
        assert any({"v": scoring.TAIL_FLOOR} in s.argspec for s in specs)
        REGISTRY._dirty = True
        REGISTRY.save_manifest(str(tmp_path / MANIFEST_NAME))
        # the restart: executables and warm state both gone
        jax.clear_caches()
        REGISTRY.reset()
        assert REGISTRY.load_manifest(str(tmp_path / MANIFEST_NAME)) >= 1
        assert REGISTRY.pending_count() >= 1
        res = REGISTRY.warm_cycle("test")
        assert res["failed"] == 0 and res["warmed"] >= 1
        assert REGISTRY.pending_count() == 0
        with jaxenv.sanitize(max_compiles=0, transfers="disallow"):
            assert _phase(ctx, body) == want
    finally:
        REGISTRY.reset()
        LADDERS.reset()


# ---------------------------------------------------------------------------
# the per-layer metric that reads the counters: puts_per_launch
# ---------------------------------------------------------------------------


def _node_stats(node) -> dict:
    from elasticsearch_tpu.rest.controller import (RestRequest,
                                                   build_rest_controller)

    resp = build_rest_controller(node).dispatch(RestRequest(
        method="GET", path="/_nodes/stats"))
    assert resp.status == 200, resp.body
    return next(iter(resp.body["nodes"].values()))


def test_puts_per_launch_reads_one_on_a_rehearsal_of_wiki_filtered(tmp_path):
    """The shape of `wiki.filtered` on the CPU: one shard, term queries under
    date filters that recur (their rows resident from the third sighting),
    `/_nodes/stats` taken before and after a handful of searches as the
    harness takes them around its window, read through the benchmark's own
    reader and definition file: one host leaf a launch."""
    from benchmark.harness import readers, registry

    from .harness import TestCluster

    with TestCluster(n_nodes=1, data_root=tmp_path, seed=44, settings={
            "search.mesh.enabled": "false"}) as cluster:
        node = next(iter(cluster.nodes.values()))
        client = node.client()
        client.create_index("wiki", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"doc": {"properties": {
                "body": {"type": "string"}, "date": {"type": "date"}}}}})
        cluster.ensure_green("wiki")
        for i in range(240):
            client.index("wiki", "doc", {
                "body": f"common w{i % 7} w{i % 11}",
                "date": 1_000_000_000_000 + 86_400_000 * (i % 120)}, id=str(i))
        client.refresh("wiki")

        def search(i):
            lo = 1_000_000_000_000 + 86_400_000 * (10 if i % 2 else 40)
            got = client.search("wiki", {"size": 10, "query": {"filtered": {
                "query": {"match": {"body": f"common w{i % 7}"}},
                "filter": {"range": {"date": {
                    "gte": lo, "lt": lo + 50 * 86_400_000}}}}}})
            assert got["hits"]["total"] > 0

        for i in range(8):  # both windows sighted until their rows are kept
            search(i)
        obs = readers.Observations("wiki")
        obs.stats_before = _node_stats(node)
        for i in range(6):
            search(i)
        obs.stats_after = _node_stats(node)
        assert obs.delta("search_serving.launch.launches_dense") >= 6
        assert obs.delta("search_serving.launch.mask_put_bytes") == 0
        definition = registry.layer_metric("puts_per_launch")
        assert readers.read(definition, obs) == 1.0
        # a program without the counters reports nothing, and raises nothing
        for stats in (obs.stats_before, obs.stats_after):
            del stats["search_serving"]["launch"]["operand_puts"]
        assert readers.read(definition, obs) is None
    (entry,) = [m for m in registry.benchmark()["per_layer"]
                if m["name"] == "puts_per_launch"]
    assert entry["layer"] == definition["layer"] == "plan to launch"
    assert entry["moves"] == "search_p50_ms" and entry["better"] == "lower"
    assert "wiki.filtered" in entry["workloads"]
    assert "passage.mesh4.single" not in entry["workloads"]
