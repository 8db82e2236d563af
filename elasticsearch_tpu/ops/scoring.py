"""Batched query scoring on device — the replacement for Lucene's QueryPhase hot loop.

The reference's inner loop (search/query/QueryPhase.java:95-137: per-segment postings
advance + Similarity.score + priority-queue insert) becomes ONE fused device program per
(segment, query-batch):

  1. gather postings blocks for every (query, term) pair            [M, B]
  2. compute per-posting contributions (BM25 tfNorm / TF-IDF)       [M, B] FMA
  3. scatter-add into dense per-query score accumulators            [Q, Dpad+1]
  4. scatter-add packed match counters (should/must/must_not bits)  [Q, Dpad+1]
  5. apply bool-query semantics (must coverage, minimum_should_match,
     must_not exclusion), coord factor, live mask
  6. lax.top_k per query                                            [Q, k]

All shapes are static: M (triple count) is bucketed to powers of two, Dpad/NB come from
the packed segment's buckets, so executables cache across refreshes. No data-dependent
control flow — bool-query logic is mask arithmetic (XLA semantics, SURVEY header).

Match-count packing: one int32 scatter carries three counters —
  bit 0..9   : matched SHOULD clauses
  bit 10..19 : matched MUST clauses
  bit 20..29 : matched MUST_NOT clauses
(queries are capped at 1023 clauses per group, far beyond the reference's default
indices.query.bool.max_clause_count = 1024.)
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field as dc_field

import numpy as np

from ..common import profile as _profile
from ..common import tracing as _tracing
from ..common.breaker import reserve
from ..common.compilecache import REGISTRY as _WARM
from ..common.jaxenv import compile_tag, current_compile_family
from .device_index import (
    BLOCK,
    POS_DEAD_CODE,
    POS_MARK_SHIFT,
    POS_SENTINEL,
    TFN_BM25,
    AggStack,
    PackedSegment,
    PositionsPlane,
    _ladder_bucket,
    _pow2_bucket,
    ensure_blk_freqs,
    ensure_head_rows,
    positions_mark_base,
)

GROUP_SHOULD, GROUP_MUST, GROUP_MUST_NOT = 0, 1, 2
_MUST_SHIFT, _NOT_SHIFT = 10, 20

MODE_BM25 = 0  # contribution = w * freq*(k1+1)/(freq + cache[normbyte])
MODE_TFIDF = 1  # contribution = w * sqrt(freq) * cache[normbyte]
MODE_CONST = 2  # contribution = w per matching term (constant-score / filters)


# rows of TermBatch.tri, the per-triple operand plane of every dense launch
_T_QIDX, _T_BLK, _T_WEIGHT, _T_FIDX, _T_GROUP, _T_TFMODE = range(6)
# rows of TermBatch.head, the per-query head-slot plane of every dense launch
_H_ROW, _H_WEIGHT, _H_FIDX, _H_GROUP, _H_TFMODE = range(5)
# head slots a query: one fixed width, so the count of head clauses is no
# dimension of a program's key; a query's clauses past it keep their blocks
HEAD_SLOTS = 16
# the foot of a dense launch's `terms` rung: with the head terms gone into
# rows a batch's tails are tens to hundreds of blocks, and a rung for each
# power of two between them is a program each (a first sighting stalls the
# drainer); padding blocks cost the device 1.8 us each
TAIL_FLOOR = 256


@dataclass
class TermBatch:
    """Flattened (query, term, block) triples, the head-term slots and the
    per-query bool-semantics arrays. Built host-side by the query planner
    (search/execute.py).

    A launch hands the device ONE flat int32 `plane`, in one put
    (_dense_args), and the program takes it apart by static slices
    (_plane_views): `tri` (int32 [6, M], one row per triple column, the f32
    weights as their bits), then `qplane` (int32 [Q, 2 + C+1]: n_must, msm,
    then the coord row as its bits), then `head` (int32 [5, Q, HEAD_SLOTS]: a
    clause whose term has a row of device_index head_rows names that row here
    and no block in `tri`). `tri`, `qplane` and `head` are host VIEWS of
    `plane` (build_term_batch fills the one buffer), and the per-triple
    columns below are views of `tri`; nothing else of a batch crosses to the
    device."""

    n_queries: int
    plane: np.ndarray  # int32 [6*M + Q*(2 + C+1) + 5*Q*HEAD_SLOTS]: tri | qplane | head
    tri: np.ndarray  # int32 [6, M] — rows _T_*
    qplane: np.ndarray  # int32 [Q, 2 + C+1]
    head: np.ndarray  # int32 [5, Q, HEAD_SLOTS] — rows _H_*; pad: head_rows' last (zero) row
    # per triple (padded to bucket):
    qidx: np.ndarray  # int32 [M]
    blk: np.ndarray  # int32 [M] — block row in the packed segment (pad: NBpad-? safe row)
    weight: np.ndarray  # float32 [M]
    fidx: np.ndarray  # int32 [M] — index into the stacked norm/cache tables
    group: np.ndarray  # int32 [M] — GROUP_*
    tfmode: np.ndarray  # int32 [M] — MODE_* per clause (const-score clauses mix in)
    # per query:
    n_must: np.ndarray  # int32 [Q]
    msm: np.ndarray  # int32 [Q] — minimum should matches
    coord: np.ndarray  # float32 [Q, C+1] — coord factor by matched count (incl queryNorm)
    # stacked per-field tables:
    norm_fields: list = dc_field(default_factory=list)  # field names, order = fidx
    caches: np.ndarray | None = None  # float32 [F, 256]
    simple: bool | None = None  # cached fast-path eligibility (computed on first use)
    blocks_real: int = 0  # triples that name a postings block (the rest of M pads)
    head_slots: int = 0  # slots of `head` that name a row
    head_trips: int = 0  # the fullest query's count of them: the head loop's trips
    blocks_as_rows: int = 0  # postings blocks those rows stand in for


@dataclass
class ScoreResult:
    scores: np.ndarray  # [Q, k] float32
    docs: np.ndarray  # [Q, k] int32 (local doc ids; doc_count → pad/no hit)
    total_hits: np.ndarray  # [Q] int64
    max_score: np.ndarray  # [Q] float32


@dataclass
class ConstBatch:
    """The operands of plans with NO scoring clause (match_all, constant_score,
    a range or numeric term query, a bare filter): no triple, no head slot, no
    coord — one constant score a query (execute.unscored_score). The match set
    is the family's filter mask and the live documents; such a batch launches
    the `scoring_*_unscored` programs (_unscored_abi) behind the same tails as
    a TermBatch. What crosses to the device is `plane`: the scores' bits."""

    score: np.ndarray  # float32 [Q]
    blocks_real: int = 0  # no postings block is read (the profile's record)

    @property
    def n_queries(self) -> int:
        return len(self.score)

    @property
    def plane(self) -> np.ndarray:
        """The launch's one operand plane (int32 [Q]): a view, no copy."""
        return self.score.view(np.int32)


def _top_k_tail(scores, match, *, k: int):
    """The plain dense program's tail. Sentinel substitution + max_score are
    [Q, k]-tiny — done host-side in score_term_batch, not appended here."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("top_k"):
        masked = jnp.where(match, scores, jnp.float32(-jnp.inf))
        top_scores, top_docs = jax.lax.top_k(masked, k)
        total = match.sum(axis=1, dtype=jnp.int32)
    return top_scores, top_docs, total


def _contribution(tf, cache_vals, w, mode):
    """One clause's per-document (or per-posting) score term. Float op ORDER
    matters for bit-parity with the host scorer and the sparse kernel's
    in-scan tfn (sparse_candidates): the tf factor is computed FIRST, then
    multiplied by the weight — Lucene's weight·tfNorm order
    (BM25Similarity.BM25DocScorer / TFIDFSimilarity.ExactSimScorer)."""
    import jax.numpy as jnp

    bm25 = w * (tf / (tf + cache_vals))
    tfidf = w * (jnp.sqrt(tf) * cache_vals)
    return jnp.where(mode == MODE_BM25, bm25,
                     jnp.where(mode == MODE_TFIDF, tfidf, w))


def _group_counter(group):
    import jax.numpy as jnp

    return (jnp.where(group == GROUP_SHOULD, 1, 0)
            + jnp.where(group == GROUP_MUST, 1 << _MUST_SHIFT, 0)
            + jnp.where(group == GROUP_MUST_NOT, 1 << _NOT_SHIFT, 0)
            ).astype(jnp.int32)


def _dense_accumulate(blk_docs, blk_freqs, head_rows, cv, tri, head,
                      *, Q: int, doc_pad: int, counters: bool):
    """Steps 1-4 of the dense kernel into [Q, doc_pad] accumulators: each head
    slot adds its term's row over documents in one elementwise pass (rows in
    clause order), then the blocks that are left are gathered, scored per
    posting and scatter-added. `cv` is the per-document table (_doc_table).
    Returns (scores, counts); `counts` (the packed match counters) is None
    unless `counters`."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("head_rows"):
        h_weight = jax.lax.bitcast_convert_type(head[_H_WEIGHT], jnp.float32)
        h_counter = _group_counter(head[_H_GROUP])
        # padded slots name the plane's last row (always zero): the loop runs
        # to the batch's fullest query, a trip count read from the operand
        used = head[_H_ROW] != head_rows.shape[0] - 1  # [Q, HEAD_SLOTS]
        trips = jnp.max(jnp.sum(used, axis=1, dtype=jnp.int32))

        def add_slot(j, acc):
            row, fid, grp, mode = (
                jax.lax.dynamic_index_in_dim(head[r], j, axis=1, keepdims=False)
                for r in (_H_ROW, _H_FIDX, _H_GROUP, _H_TFMODE))
            w = jax.lax.dynamic_index_in_dim(h_weight, j, axis=1)  # [Q, 1]
            tf = head_rows[row].astype(jnp.float32)  # [Q, doc_pad]
            there = tf > 0.0  # also keeps a normless field's 0/0 out
            contrib = _contribution(tf, cv[fid], w, mode[:, None])
            scoring = there & (grp != GROUP_MUST_NOT)[:, None]
            scores = acc[0] + jnp.where(scoring, contrib, 0.0)
            if not counters:
                return (scores,)
            cnt = jax.lax.dynamic_index_in_dim(h_counter, j, axis=1)
            return scores, acc[1] + jnp.where(there, cnt, 0)

        acc = (jnp.zeros((Q, doc_pad), jnp.float32),)
        if counters:
            acc += (jnp.zeros((Q, doc_pad), jnp.int32),)
        acc = jax.lax.fori_loop(0, trips, add_slot, acc)
        scores, counts = acc[0], (acc[1] if counters else None)

    qidx, blk, fidx = tri[_T_QIDX], tri[_T_BLK], tri[_T_FIDX]
    group = tri[_T_GROUP]
    with jax.named_scope("gather_decode"):
        docs = blk_docs[blk]  # [M, B] int32; padded rows → doc_pad sentinel
        freqs = blk_freqs[blk]  # [M, B]
        valid = docs < doc_pad
        docs_safe = jnp.where(valid, docs, 0)
        cache_vals = cv.reshape(-1)[fidx[:, None] * doc_pad + docs_safe]
        weight = jax.lax.bitcast_convert_type(tri[_T_WEIGHT], jnp.float32)
        contrib = _contribution(freqs, cache_vals, weight[:, None],
                                tri[_T_TFMODE][:, None])
        scoring = (group[:, None] != GROUP_MUST_NOT) & valid
        contrib = jnp.where(scoring, contrib, 0.0)

    with jax.named_scope("scatter_add"):
        # invalid slots index past the end and are dropped
        flat_idx = jnp.where(valid, qidx[:, None] * doc_pad + docs_safe,
                             Q * doc_pad).reshape(-1)
        scores = scores.reshape(-1).at[flat_idx].add(
            contrib.reshape(-1), mode="drop").reshape(Q, doc_pad)
        if counters:
            counter_vals = jnp.where(valid, _group_counter(group)[:, None], 0)
            counts = counts.reshape(-1).at[flat_idx].add(
                counter_vals.reshape(-1), mode="drop").reshape(Q, doc_pad)
    return scores, counts


def _dense_semantics(scores, counts, live_parent, n_must, msm, coord):
    """Bool-query semantics + coord over the dense accumulators: returns the
    coord-scaled scores and the match mask."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("match_coord"):
        m_should = counts & 0x3FF
        m_must = (counts >> _MUST_SHIFT) & 0x3FF
        m_not = counts >> _NOT_SHIFT

        match = (m_must == n_must[:, None]) & (m_should >= msm[:, None]) & (m_not == 0)
        match = match & ((m_should + m_must) > 0) & live_parent[None, :]

        overlap = jnp.minimum(m_should + m_must, coord.shape[1] - 1)
        # per-row lookup into the small [Q, C+1] coord table as a static select-sum —
        # take_along_axis lowers to a serialized per-element gather on TPU (measured
        # ~1.3s for [1024, 128k] vs ~5ms for C+1 fused compare+FMA passes)
        coord_fac = jnp.zeros_like(scores)
        for j in range(coord.shape[1]):
            coord_fac = coord_fac + jnp.where(overlap == j, coord[:, j][:, None], 0.0)
        return scores * coord_fac, match


_compiled_cache: dict = {}


def _named(site: str, fn, variant: str = ""):
    """`fn` renamed after its launch site, for jax.jit: `scoring.sparse`
    compiles as `jit_estpu_scoring_sparse`, so the profiler's `XLA Modules`
    line, HLO dumps and compile logs tell the programs apart (every one was
    `jit_wrapper`). `variant` is for a site that serves two programs through
    a static flag. The name is part of the persistent compile cache's key."""
    fn.__name__ = fn.__qualname__ = "estpu_" + site.replace(".", "_") + (
        "_" + variant if variant else "")
    return fn


class LaunchCounters:
    """Always-on tallies of what the scoring launches touched, from numbers
    the launch site holds anyway (`/_nodes/stats` `search_serving.launch`; a
    process rollup like service.SERVING_COUNTERS).

    `blocks_real` are the postings blocks the queries name, `blocks_launched`
    what the padded launch shape scans (`blocks_padding` their difference).
    `posting_bytes` is the program's own reckoning of the HBM bytes a launch
    reads, no measurement; each formula sits beside its launch
    (score_sparse_batch_async, _dense_bytes). `dense_rows` counts the
    [doc_pad]-wide score rows of the dense launches, `head_slots` the rows
    of device_index head_rows they added and `blocks_as_rows` the postings
    blocks those rows stood in for (`blocks_real` / `blocks_launched` count
    what is still scattered). `operand_puts` counts the host arrays launch
    sites put on the device (_put_operands: each host leaf of a launch's one
    device_put is its own transfer): two for a warmed sparse launch, ONE for
    a warmed dense one of any family (its packed operand plane, _dense_args;
    two where a mask the host evaluated rides along), one for a plain mesh
    search (its operand plane; parallel/mesh_search.py). The launches of
    plans with no scoring clause are tallied apart (`bump`): they read no
    postings."""

    def __init__(self):
        self._lock = threading.Lock()
        self._c = dict.fromkeys(
            ("blocks_real", "blocks_launched", "blocks_padding",
             "posting_bytes", "dense_rows", "head_slots", "blocks_as_rows",
             "launches_sparse", "launches_dense", "operand_puts",
             "unscored_plans", "launches_unscored", "unscored_bytes",
             "mask_put_bytes", "launches_fs_unscored", "fs_row_put_bytes",
             "fs_rows_resident", "fs_rows_evaluated", "exact_sum_rows",
             "phrase", "phrase_pair_launches", "phrase_searches",
             "position_bytes", "position_pad_bytes", "position_list_bytes",
             "position_skip_bytes", "multiterm", "multiterm_searches",
             "multiterm_terms", "multiterm_runs", "multiterm_bytes",
             "multiterm_pad_bytes", "multiterm_field_scans",
             "dismax", "dismax_searches", "dismax_disjuncts", "dismax_bytes",
             "dismax_blocks", "dismax_pad_blocks"), 0)

    def add(self, real: int, launched: int, nbytes: int,
            dense_rows: int = 0, head_slots: int = 0,
            blocks_as_rows: int = 0) -> None:
        with self._lock:
            c = self._c
            c["blocks_real"] += real
            c["blocks_launched"] += launched
            c["blocks_padding"] += launched - real
            c["posting_bytes"] += nbytes
            c["dense_rows"] += dense_rows
            c["head_slots"] += head_slots
            c["blocks_as_rows"] += blocks_as_rows
            c["launches_dense" if dense_rows else "launches_sparse"] += 1

    def puts(self, n: int) -> None:
        with self._lock:
            self._c["operand_puts"] += n

    def bump(self, **counts: int) -> None:
        """The tallies of the launches that read no postings: plans with no
        scoring clause (`unscored_plans`, once a plan whatever its segments),
        their launches and the bytes those read (`launches_unscored`,
        `unscored_bytes`: _count_unscored; `launches_fs_unscored`: those of
        them behind a function_score tail), the bytes of filter-mask rows a
        search evaluated on the host and put (`mask_put_bytes`:
        execute._filter_mask_matrix), how often a function_score launch
        group found a segment's rows resident or had the host evaluate them
        (`fs_rows_resident`, `fs_rows_evaluated`) and the bytes of function
        rows, applies rows and script column rows so evaluated and put
        (`fs_row_put_bytes`: execute._fs_segment_rows), the integer limb
        rows the aggregated launches reduced (`exact_sum_rows`:
        score_agg_batch_async), and the phrase program's launches, the plans
        it served (once a plan whatever its segments) and the bytes of
        position blocks its launches gathered, padding included, and the
        padding's part of them: the slots of the line no term fills and each
        term's rows up to its rung (`phrase`, `phrase_searches`,
        `position_bytes`, `position_pad_bytes`: score_phrase_batch_async,
        execute.launch_flat_phrase), and those of its launches whose line
        held two slots, every plan a pair (`phrase_pair_launches`, a part of
        `phrase`); beside them the bytes of the launched
        plans' WHOLE lists, every block row of every term, and the part of
        those the lead term's documents left out of the launch
        (`position_list_bytes`, `position_skip_bytes`); and the mask rows
        built on the chip for multi-term queries and filters: the program's
        launches (beside the plans, what an operator reads the sharing of a
        launch from: rows built a launch), the plans with such a row (once a
        plan whatever its segments), the terms their patterns matched and the runs of block
        rows those own, the bytes of block rows the launches gathered,
        padding included, and the padding's part (`multiterm`,
        `multiterm_searches`, `multiterm_terms`, `multiterm_runs`,
        `multiterm_bytes`, `multiterm_pad_bytes`: build_multiterm_rows,
        execute._filter_mask_matrix), and the expansions whose pattern had
        no literal head, so that the whole field's dictionary was tested
        (`multiterm_field_scans`); and the dis_max program's launches, the
        plans it served (once a plan whatever its segments) and the
        disjuncts those held, the HBM bytes its launches read by the
        program's own reckoning, and the postings blocks they scanned with
        the ladder's padding among them (`dismax`, `dismax_searches`,
        `dismax_disjuncts`, `dismax_bytes`, `dismax_blocks`,
        `dismax_pad_blocks`: score_dismax_batch_async,
        execute.launch_flat_dismax)."""
        with self._lock:
            for name, n in counts.items():
                self._c[name] += n

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._c)


LAUNCHES = LaunchCounters()


def _put_operands(*operands, shardings=None):
    """A launch's ONE explicit host→device transfer (legal under
    transfer_guard("disallow")): the HOST leaves of `operands` (numpy arrays
    and scalars, at any depth of a tuple operand) go down in a single
    jax.device_put of a flat list, and nothing else is handed to it: a
    resident operand is no leaf of the put's pytree, it is passed on as it
    is. Returns the operands, in order and in their own structure, every leaf
    a device array. `shardings`, one per operand, is the mesh launch's: where
    each operand goes on a mesh of several chips (there the whole tuple is
    put, and a resident operand comes back untouched)."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten(operands)
    at = [i for i, leaf in enumerate(leaves)
          if isinstance(leaf, (np.ndarray, np.generic))]
    LAUNCHES.puts(len(at))
    if shardings is not None:
        return jax.device_put(operands, shardings)
    if at:
        for i, placed in zip(at, jax.device_put([leaves[i] for i in at])):
            leaves[i] = placed
    return jax.tree_util.tree_unflatten(tree, leaves)


def _unpack_qplane(qplane):
    """(n_must, msm, coord) from the per-query operand plane, inside a
    program: the coord bits back to the f32 they are (exact)."""
    import jax
    import jax.numpy as jnp

    return (qplane[:, 0], qplane[:, 1],
            jax.lax.bitcast_convert_type(qplane[:, 2:], jnp.float32))


def _pack_qplane(n_must, msm, coord, out: np.ndarray | None = None) -> np.ndarray:
    """The per-query operand plane (host side of _unpack_qplane), into `out`
    (int32 [Q, 2 + C+1]: a view of a launch's one plane) where given."""
    coord = np.ascontiguousarray(coord, np.float32)
    qplane = out if out is not None else np.empty(
        (coord.shape[0], 2 + coord.shape[1]), np.int32)
    qplane[:, 0] = n_must
    qplane[:, 1] = msm
    qplane[:, 2:] = coord.view(np.int32)
    return qplane


def _plane_views(plane: np.ndarray, m: int, n_queries: int):
    """(tri [6, M], qplane [Q, 2 + C+1], head [5, Q, HEAD_SLOTS]) of a dense
    launch's flat operand plane: host views for build_term_batch to fill,
    and, `plane` being a traced array, the program's own static slices
    (_dense_abi). The coord width is what is left of the plane's length, as C
    is in mesh_search._unpack_plane."""
    o, h = 6 * m, 5 * n_queries * HEAD_SLOTS
    end = plane.shape[0] - h
    return (plane[:o].reshape(6, m),
            plane[o:end].reshape(n_queries, (end - o) // n_queries),
            plane[end:].reshape(5, n_queries, HEAD_SLOTS))


def _plane_scalars(plane, n: int):
    """(the plane without its last `n` words, those words as f32 scalars): the
    few per-launch scalars of a function_score tail ride the launch's plane
    as their bits (_dense_args `scalars`), inside a program."""
    import jax
    import jax.numpy as jnp

    if not n:
        return plane, ()
    cut = plane.shape[0] - n
    words = jax.lax.bitcast_convert_type(plane[cut:], jnp.float32)
    return plane[:cut], tuple(words[i] for i in range(n))


def _launch(fn, args, site: str | None = None, family: str = "", params=()):
    """One call into a compiled program, with this thread's dispatch clock
    (common/tracing.DispatchClock) marked on both sides: what ran since the
    last mark was host staging, the call itself is the launch (a first
    sighting compiles inside it). `site` registers the executable with the
    compile-warm registry (common/compilecache): the first sighting of a
    (site, params, arg shapes) signature stores a JSON-able WarmSpec the
    warmer replays at startup / post-restart, so the NEXT process never pays
    this compile on-path. The active compile_tag family wins attribution (a
    percolation's inner dense launch warms under `compile:percolate`)."""
    _tracing.mark("dispatch.stage")
    out = fn(*args)
    if site is not None:
        _WARM.record_launch(site, current_compile_family() or family, params,
                            args)
    _tracing.mark("dispatch.launch")
    return out


def _pull(out):
    """The one device_get of a family that pulls inside its dispatch (the
    whole result pytree in ONE explicit transfer; None leaves pass through),
    closed on the dispatch clock as `device_pull` and named on the profiler's
    clock inside the drainer's estpu.batch.dispatch annotation."""
    import jax

    with jax.profiler.TraceAnnotation("estpu.batch.pull"):
        host = jax.device_get(out)
    _tracing.mark("device_pull")
    return host


def _dense_bytes(packed: PackedSegment, batch: TermBatch) -> int:
    """A dense launch reads, per launched (query, block) triple, BLOCK slots
    of doc id i32 + freq f32 + one gathered table value f32; per trip of the
    head loop (it runs to the fullest query) a [Q, doc_pad] gather of rows in
    the segment's tf dtype and one of table values f32; and top_k reads the
    [Q, doc_pad] f32 score plane back."""
    q = batch.n_queries
    return (len(batch.blk) * BLOCK * (4 + 4 + 4)
            + q * batch.head_trips * packed.doc_pad
            * (packed.head_rows.dtype.itemsize + 4)
            + q * packed.doc_pad * 4)


def _count_dense(packed: PackedSegment, batch: TermBatch) -> None:
    LAUNCHES.add(batch.blocks_real, len(batch.blk),
                 _dense_bytes(packed, batch), dense_rows=batch.n_queries,
                 head_slots=batch.head_slots,
                 blocks_as_rows=batch.blocks_as_rows)


# where the dense launch ABI carries M, the one size of the plane's layout
# that neither its length nor the program's other statics give (jax.jit's
# static_argnums; a rung of the `terms` ladder, so a handful of values)
_DENSE_STATIC_ARGNUMS = (6,)


def _mask_matrix(fmask):
    """A tail's mask operand as the [Q, Dpad] (or broadcastable [1, 1])
    matrix it gates the match by, inside a program: a tuple of Q [Dpad] rows
    (execute._filter_mask_matrix: resident rows of the filter cache, host
    stragglers the launch's put carried) is stacked HERE, so that no eager
    program runs between the drainer's collect and the launch."""
    import jax.numpy as jnp

    return jnp.stack(fmask) if isinstance(fmask, tuple) else fmask


def _dense_abi(tail, *, n_queries: int, doc_pad: int, simple: bool = False,
               scalars: int = 0, **statics):
    """`tail` behind the dense launch ABI (blk_docs, blk_freqs, head_rows,
    live_parent, doc_table, plane, M, *extra): the ONE scoring core every
    dense family shares. Of these only `plane` was put for the launch
    (_dense_args): the batch's flat int32 operand plane, taken apart INSIDE
    the program by static slices and bit-exact bitcasts (_plane_views:
    tri | qplane | head, then the tail's `scalars` f32 words), accumulated
    (_dense_accumulate) and matched; the family's `tail(scores, match,
    *extra, *scalars, **statics)` ranks and reduces. `M` (the triples' rung)
    is a static argument (_DENSE_STATIC_ARGNUMS): it decided the program as
    `tri`'s shape, it does so as a value. `extra` are the family's resident
    operands and, where the host evaluated one, a mask that rode the put.

    simple=True is a host-detected static fast path: every clause is a SHOULD
    with msm<=1, no coord — match reduces to score>0, so the int counters and
    the per-doc match bookkeeping are skipped entirely (the bulk-query hot
    shape)."""
    def wrapper(blk_docs, blk_freqs, head_rows, live_parent, doc_table,
                plane, m, *extra):
        import jax

        plane, tail_scalars = _plane_scalars(plane, scalars)
        tri, qplane, head = _plane_views(plane, m, n_queries)
        scores, counts = _dense_accumulate(
            blk_docs, blk_freqs, head_rows, doc_table, tri, head,
            Q=n_queries, doc_pad=doc_pad, counters=not simple)
        if simple:
            with jax.named_scope("match_coord"):
                match = (scores > 0.0) & live_parent[None, :]
        else:
            scores, match = _dense_semantics(scores, counts, live_parent,
                                             *_unpack_qplane(qplane))
        return tail(scores, match, *extra, *tail_scalars, **statics)

    return wrapper


def _count_unscored(packed: PackedSegment, batch: ConstBatch,
                    row_bytes: int) -> None:
    """A launch of plans with no scoring clause reads, per query, the filter
    mask's [doc_pad] bool row and the [doc_pad] f32 plane its tail ranks
    (`row_bytes` more where the tail reads a row of its own: the sort key)."""
    LAUNCHES.bump(launches_unscored=1, unscored_bytes=batch.n_queries
                  * packed.doc_pad * (1 + 4 + row_bytes))


def _unscored_abi(tail, *, n_queries: int, doc_pad: int, scalars: int = 0,
                  **statics):
    """`tail` behind the launch ABI of plans with no scoring clause
    (live_parent, plane, *extra): every live document matches and scores its
    query's constant, the first Q words of the launch's one plane (their f32
    bits; then the tail's `scalars` words, as in _dense_abi); the tail gates
    the match by the filter mask it takes (as it does for a scored plan) and
    ranks and reduces. No postings plane, head row, document table or coord
    is an operand."""
    def wrapper(live_parent, plane, *extra):
        import jax
        import jax.numpy as jnp

        plane, tail_scalars = _plane_scalars(plane, scalars)
        with jax.named_scope("match_const"):
            score = jax.lax.bitcast_convert_type(plane, jnp.float32)
            match = jnp.broadcast_to(live_parent[None, :], (n_queries, doc_pad))
            scores = jnp.broadcast_to(score[:, None], (n_queries, doc_pad))
        return tail(scores, match, *extra, *tail_scalars, **statics)

    return wrapper


def _site(site: str, suffix: str) -> str:
    """The warm registry's site of a launch: `scoring.sorted` → with the
    unscored ABI `scoring.sorted_unscored`, a builder of its own below."""
    return site + "_" + suffix if suffix else site


def _abi_for(batch):
    """(the launch ABI, the suffix of its programs' names and warm sites)."""
    return (_unscored_abi, "unscored") if isinstance(batch, ConstBatch) \
        else (_dense_abi, "")


def _static_argnums(abi) -> tuple:
    """jax.jit's static_argnums of a program behind `abi`: the dense ABI's M."""
    return _DENSE_STATIC_ARGNUMS if abi is _dense_abi else ()


def _get_compiled(n_queries: int, k: int, doc_pad: int, simple: bool = False):
    import jax

    key = (n_queries, k, doc_pad, simple)
    fn = _compiled_cache.get(key)
    if fn is None:
        wrapper = _dense_abi(_top_k_tail, n_queries=n_queries, doc_pad=doc_pad,
                             simple=simple, k=k)
        fn = jax.jit(_named("scoring.dense", wrapper, "simple" if simple else "bool"),
                     static_argnums=_DENSE_STATIC_ARGNUMS)
        _compiled_cache[key] = fn
    return fn


# ---------------------------------------------------------------------------
# function_score variants of the dense kernel
# ---------------------------------------------------------------------------
#
# The reference rescores inside the Lucene query (FunctionScoreQuery wraps the sub
# scorer — common/lucene/search/function/FunctionScoreQuery.java); here the
# function value is fused into the same device program that scores the sub query:
#   "rows"   — every function is doc-only (decay/field_value_factor/boost_factor/
#              random/script-without-_score): the score_mode-combined value is one
#              host-computed f32 row per segment (functions.combined_doc_rows),
#              and the kernel applies max_boost/boost_mode/outer-boost/min_score.
#   "script" — a single script_score that READS _score: the sandboxed AST is
#              traced into the kernel (script.jax_vectorizer_cls) with _score
#              bound to the dense sub-score array and doc columns as device rows.
# Tail math is float32 in the same op order as functions.apply_functions, so host
# and device scores are bit-identical for the rows case.


def _bmode_combine(sub, comb, applied, bmode: str):
    """boost_mode combine, float32, op-order-identical to apply_functions.
    applied=None means every doc has a function applied (no filter)."""
    import jax.numpy as jnp

    if bmode == "multiply":
        return sub * comb
    if bmode == "replace":
        return comb if applied is None else jnp.where(applied, comb, sub)
    if bmode == "sum":
        return sub + comb
    if bmode == "avg":
        return (sub + comb) / jnp.float32(2.0)
    if bmode == "max":
        return jnp.maximum(sub, comb)
    if bmode == "min":
        return jnp.minimum(sub, comb)
    raise ValueError(f"unknown boost_mode [{bmode}]")


def _fs_rows_impl(scores, match, fmask, g_row, applies_row, max_boost, fboost,
                  min_score, *, k: int, bmode: str, use_min_score: bool,
                  no_functions: bool):
    import jax.numpy as jnp

    match = match & _mask_matrix(fmask)
    if no_functions:
        out = scores * fboost
    else:
        applied = applies_row[None, :]
        comb = jnp.where(applied, g_row[None, :], jnp.float32(1.0))
        comb = jnp.minimum(comb, max_boost)
        out = _bmode_combine(scores, comb, applied, bmode) * fboost
    if use_min_score:
        match = match & (out >= min_score)
    return _top_k_tail(out, match, k=k)


def _fs_script_impl(scores, match, fmask, col_rows, fmask_row, bad_row,
                    parent_row, weight_s, max_boost, fboost, min_score,
                    *, k: int, script, used_fields: tuple, bmode: str,
                    use_min_score: bool, has_filter: bool, has_weight: bool):
    import jax.numpy as jnp

    from ..script import jax_vectorizer_cls

    match = match & _mask_matrix(fmask)
    cols = dict(zip(used_fields, col_rows))
    vec = jax_vectorizer_cls()(script, lambda f: cols[f], scores)
    val = jnp.broadcast_to(jnp.asarray(vec.vectorize(), jnp.float32), scores.shape)
    if has_weight:
        val = val * weight_s
    applied = fmask_row[None, :] if has_filter else None
    comb = val if applied is None else jnp.where(applied, val, jnp.float32(1.0))
    comb = jnp.minimum(comb, max_boost)
    out = _bmode_combine(scores, comb, applied, bmode) * fboost
    if use_min_score:
        match = match & (out >= min_score)
    # host error semantics (functions.vectorized_script_eval): any parent doc whose
    # used columns are missing or whose script value is non-finite would take the
    # per-doc path (which may raise ScriptError) — flag the query so the caller
    # reruns it on the host
    bad = (bad_row[None, :] | (parent_row[None, :] & ~jnp.isfinite(val))).any(axis=1)
    return (*_top_k_tail(out, match, k=k), bad)


def _get_fs_compiled(kind: str, n_queries: int, k: int, doc_pad: int,
                     abi=_dense_abi, suffix: str = "", **statics):
    import jax

    # the tail's last operands are per-launch f32 scalars (max_boost, boost,
    # min_score; the script's weight before them): they ride the plane
    if kind == "rows":
        key = ("fs_rows", n_queries, k, doc_pad, tuple(sorted(statics.items())))
        impl, scalars = _fs_rows_impl, 3
    else:
        script = statics.pop("script")
        key = ("fs_script", n_queries, k, doc_pad, script.source,
               repr(sorted(script.params.items())),
               tuple(sorted((k2, v) for k2, v in statics.items())))
        impl, scalars = functools.partial(_fs_script_impl, script=script), 4
    key += (suffix,) if suffix else ()
    fn = _compiled_cache.get(key)
    if fn is None:
        wrapper = abi(impl, n_queries=n_queries, doc_pad=doc_pad, k=k,
                      scalars=scalars, **statics)
        fn = jax.jit(_named("scoring.fs_" + kind, wrapper, suffix),
                     static_argnums=_static_argnums(abi))
        _compiled_cache[key] = fn
    return fn


_DENSE_TABLES_MAX = 8  # field tuples kept per segment (FIFO, like agg_stacks)


def _doc_table_impl(norms_stack, caches):
    """[F, doc_pad] f32: each document's norm byte through its field's
    256-entry table, as ONE flat gather (row*256 + byte)."""
    import jax.numpy as jnp

    rows = jnp.arange(norms_stack.shape[0], dtype=jnp.int32)[:, None]
    return caches.reshape(-1)[rows * 256 + norms_stack.astype(jnp.int32)]


@functools.lru_cache(maxsize=None)
def _get_doc_table_compiled():
    import jax

    return jax.jit(_named("scoring.doc_table", _doc_table_impl))


def _doc_table(packed: PackedSegment, batch: TermBatch):
    """Kernel ABI: the per-document table every dense launch takes — for each
    of the batch's fields, the similarity's cache value of every document's
    norm byte, f32 [F, doc_pad] (single construction site — the fallback
    shapes are load-bearing). The TPU runs an element-wise gather serially, so
    the table is made once per document and field, not once per posting, and
    once per table, not once per launch.

    Kept on the packed segment per field tuple beside the stacked norm rows
    it is made from, so a warmed launch runs no other program and puts no
    table: a tuple's norm rows never change once every field has one
    (execute._ensure_norm_rows adds the missing ones BEFORE this runs), and
    the cache rows are compared by value — they move with avgdl, as the
    sparse path's SimTables do."""
    import jax.numpy as jnp

    key = tuple(batch.norm_fields)
    caches = (batch.caches if batch.caches is not None
              else np.ones((1, 256), np.float32))
    held = packed.dense_tables.get(key)
    if held is not None and np.array_equal(held[0], caches):
        return held[2]
    norms_stack = held[1] if held is not None else (
        jnp.stack([packed.norm_bytes[f] for f in key]) if key
        else jnp.zeros((1, packed.doc_pad), jnp.uint8))
    table = _get_doc_table_compiled()(norms_stack, *_put_operands(caches))
    if held is None:
        while len(packed.dense_tables) >= _DENSE_TABLES_MAX:
            packed.dense_tables.pop(next(iter(packed.dense_tables)), None)
    packed.dense_tables[key] = (caches, norms_stack, table)
    return table


def _dense_args(packed: PackedSegment, batch: TermBatch, *extra, scalars=()):
    """The argument list of a dense launch (the _dense_abi order). Between
    the drainer's collect and the compiled call this is the ONE place a dense
    launch touches the device, once: a single put (_put_operands) of the
    batch's flat operand plane (`scalars`, a function_score tail's few f32,
    appended as their bits) and of whatever of the family's `extra` operands
    is a host array: a mask the host evaluated, as a matrix or as stragglers
    among a tuple of resident rows; function or column rows the row store
    does not hold yet. Everything else is resident and passed as it is: the
    postings planes, head rows, live mask and document table, and of `extra`
    the agg stack's rows and limbs, the bucket pairs, a device mask or its
    rows, sort key rows, function rows. A ConstBatch (_unscored_abi) names no
    resident plane but the live mask."""
    plane = batch.plane
    if scalars:
        plane = np.concatenate(
            [plane, np.asarray(scalars, np.float32).view(np.int32)])
    if isinstance(batch, ConstBatch):
        return (packed.live_parent, *_put_operands(plane, *extra))
    doc_table = _doc_table(packed, batch)
    head_rows = ensure_head_rows(packed)
    _count_dense(packed, batch)
    plane, *extra = _put_operands(plane, *extra)
    return (packed.blk_docs, ensure_blk_freqs(packed), head_rows,
            packed.live_parent, doc_table, plane, len(batch.blk), *extra)


def _count_fs_unscored(packed: PackedSegment, batch, row_bytes: int) -> None:
    """A function_score launch of plans with no scoring clause: tallied with
    the unscored launches (_count_unscored; `row_bytes` a query for the
    function row or the script's column rows its tail reads) and apart."""
    if isinstance(batch, ConstBatch):
        _count_unscored(packed, batch, row_bytes)
        LAUNCHES.bump(launches_fs_unscored=1)


def score_fs_rows_batch_async(packed: PackedSegment, batch: TermBatch, k: int,
                              fmask, g_row, applies_row, max_boost: float,
                              fboost: float, min_score, bmode: str,
                              no_functions: bool):
    """Dense launch with host-combined function rows; returns device (scores,
    docs, total) [Q, k]/[Q] without syncing (the caller pulls:
    execute.launch_flat_fs). `fmask`: optional bool [Q, Dpad] match gates of
    filtered or unscored sub queries."""
    params = (batch.n_queries, min(k, packed.doc_pad), packed.doc_pad,
              bmode, min_score is not None, no_functions)
    abi, suffix = _abi_for(batch)
    fn = _get_fs_compiled(
        "rows", params[0], params[1], params[2], abi, suffix,
        bmode=bmode, use_min_score=min_score is not None, no_functions=no_functions)
    _count_fs_unscored(packed, batch, row_bytes=4)
    args = _dense_args(
        packed, batch, _no_mask() if fmask is None else fmask,
        g_row, applies_row,
        scalars=(max_boost, fboost,
                 min_score if min_score is not None else 0.0))
    # the script variant is NOT recorded: its executable closes over a live
    # sandboxed script object that has no JSON form to replay from a manifest
    return _launch(fn, args, _site("scoring.fs_rows", suffix),
                   "function_score", params)


def score_fs_script_batch_async(packed: PackedSegment, batch: TermBatch, k: int,
                                fmask, script, used_fields: tuple, col_rows,
                                fmask_row, bad_row, parent_row, weight,
                                max_boost: float, fboost: float, min_score,
                                bmode: str, has_filter: bool):
    """Dense launch with the script traced into the kernel; returns device
    (scores, docs, total, bad) without syncing."""
    abi, suffix = _abi_for(batch)
    fn = _get_fs_compiled(
        "script", batch.n_queries, min(k, packed.doc_pad), packed.doc_pad,
        abi, suffix,
        script=script, used_fields=used_fields, bmode=bmode,
        use_min_score=min_score is not None, has_filter=has_filter,
        has_weight=weight is not None)
    _count_fs_unscored(packed, batch, row_bytes=4 * len(col_rows))
    return _launch(fn, _dense_args(
        packed, batch, _no_mask() if fmask is None else fmask,
        tuple(col_rows), fmask_row, bad_row, parent_row,
        scalars=(weight if weight is not None else 1.0, max_boost, fboost,
                 min_score if min_score is not None else 0.0)))


# ---------------------------------------------------------------------------
# dense kernel + fused metric-aggregation stats
# ---------------------------------------------------------------------------
#
# The reference collects metric aggs in a second per-doc pass over the matched
# docs (search/aggregations/AggregationPhase + per-agg collectors); here the agg
# reduction fuses into the SAME device program that scored the query: the match
# mask multiplies per-doc (count, sum, sumsq) rows via a [Q, Dpad] @ [Dpad, 3F]
# matmul (MXU work), and min/max ride masked reductions. Rows come from
# device_index.agg_doc_rows — exact for multi-valued fields.


@functools.lru_cache(maxsize=None)
def _no_mask():
    """The unfiltered sorted / aggregated launch's mask: a broadcastable
    [1, 1] & [Q, Dpad] no-op, kept on the device so that such a launch neither
    allocates a full all-true mask nor puts one more operand."""
    return _put_operands(np.ones((1, 1), dtype=bool))[0]


@functools.lru_cache(maxsize=None)
def _false_row(doc_pad: int):
    """A resident [Dpad] row that matches nothing: what pads a mask matrix of
    resident rows to the next query count of the ladder
    (execute._filter_mask_matrix), put once a `doc_pad`."""
    return _put_operands(np.zeros(doc_pad, dtype=bool))[0]


@functools.lru_cache(maxsize=None)
def ladder_mask(q: int, width: int, doc_pad: int):
    """A resident [width, Dpad] mask whose first `q` rows match everything and
    the rest nothing: what a group of `q` unscored plans WITHOUT a filter
    launches under at a rung of `width` > 1 queries (execute._group_operands),
    whether the rung is full or padded. One mask shape a rung means one
    program a rung: with the [1, 1] no-op at a full rung and a put mask at a
    padded one, a rung had two, and the rarer (four searches of one operation
    in one batch) was first met inside a measured window (PERF.md section 6,
    PR 35). Kept for the few (q, width) a group's ladder has."""
    return _put_operands(np.arange(width)[:, None].repeat(doc_pad, 1) < q)[0]


def ladder_const_batch(batch: ConstBatch, fmask, doc_pad: int, width: int):
    """(batch, fmask) of plans with no scoring clause, padded to `width`
    queries (a rung of the caller's ladder of query counts) with queries that
    match nothing: such plans coalesce in any number, so a window meets a few
    programs, not one for each count. A mask matrix comes padded
    (execute._filter_mask_matrix, resident rows or not); where no plan has a
    filter the mask is made here. The caller slices the padding off the
    results."""
    q = batch.n_queries
    pad = width - q
    if pad:
        batch = ConstBatch(np.concatenate([batch.score,
                                           np.zeros(pad, np.float32)]))
        if fmask is None:
            fmask = np.concatenate([np.ones((q, doc_pad), bool),
                                    np.zeros((pad, doc_pad), bool)])
    return batch, fmask


def score_filtered_batch_async(packed: PackedSegment, batch: TermBatch, k: int,
                               fmask):
    """Dense launch with match-gating filter masks (the device form of the
    reference's FilteredQuery — the filter gates matching, never scoring,
    XFilteredQuery). Rides score_agg_batch_async with an empty agg stack
    (F=0): one kernel family to keep in sync. Returns device (scores, docs,
    total) without syncing (the caller pulls: execute.launch_flat_filtered),
    the query count of unscored plans up its ladder: the caller slices the
    padding off."""
    empty = packed.agg_stacks.get(())  # device_index.ensure_agg_rows' key
    if empty is None:
        empty = packed.agg_stacks[()] = AggStack(*_put_operands(
            np.zeros((0, 5, packed.doc_pad), np.float32),
            np.zeros((0, 0, packed.doc_pad), np.int32)), ())
    if isinstance(batch, ConstBatch):
        # unscored plans coalesce in any number: the query count rides the
        # pow-2 ladder, so a window meets four programs
        batch, fmask = ladder_const_batch(batch, fmask, packed.doc_pad,
                                          _pow2_bucket(batch.n_queries, 1))
    scores, docs, total, _counts, _stats, _sums, _buckets = \
        score_agg_batch_async(packed, batch, k, empty, (), fmask=fmask,
                              filtered=True)
    return scores, docs, total


def _dense_sort_impl(scores, match,
                     fmask, key_row,  # f32 [Dpad] ascending-semantics sort keys
                     *, k: int, descending: bool):
    """Dense kernel + field-sort top-k: the device form of the reference's
    sorted TopFieldCollector (QueryPhase sorted search). Keys come pre-folded
    per doc (sorting.device_sort_key_row — mode + missing policy baked in);
    ties break by doc id ascending via top_k's lower-index preference, matching
    the host lexsort."""
    import jax
    import jax.numpy as jnp

    match = match & _mask_matrix(fmask)
    key = jnp.broadcast_to(key_row[None, :], match.shape)
    pad = jnp.float32(-jnp.inf) if descending else jnp.float32(jnp.inf)
    sortable = jnp.where(match, key, pad)
    if descending:
        top_keys, top_docs = jax.lax.top_k(sortable, k)
    else:
        neg, top_docs = jax.lax.top_k(-sortable, k)
        top_keys = -neg
    top_scores = jnp.take_along_axis(scores, top_docs, axis=1)
    # max_score spans ALL matches (the host mask path computes it that way for
    # sorted searches), not just the k winners
    qmax = jnp.max(jnp.where(match, scores, jnp.float32(-jnp.inf)), axis=1)
    return (top_keys, top_docs, top_scores, qmax,
            match.sum(axis=1, dtype=jnp.int32))


def _get_sorted_compiled(n_queries: int, k: int, doc_pad: int,
                         descending: bool, abi=_dense_abi, suffix: str = ""):
    import jax

    key = ("sorted", n_queries, k, doc_pad, descending) + (
        (suffix,) if suffix else ())
    fn = _compiled_cache.get(key)
    if fn is None:
        wrapper = abi(_dense_sort_impl, n_queries=n_queries,
                      doc_pad=doc_pad, k=k, descending=descending)
        fn = jax.jit(_named("scoring.sorted", wrapper, suffix),
                     static_argnums=_static_argnums(abi))
        _compiled_cache[key] = fn
    return fn


def score_sorted_batch_async(packed: PackedSegment, batch: TermBatch, k: int,
                             key_row, descending: bool, fmask=None):
    """Field-sorted dense launch; returns device (keys, docs, scores, qmax,
    total) without syncing (the caller pulls: execute.launch_flat_sorted).
    Matched docs occupy the first min(total, k) slots per query (padding
    ranks strictly after ±FLT_MAX missing keys)."""
    params = (batch.n_queries, min(k, packed.doc_pad), packed.doc_pad,
              descending)
    abi, suffix = _abi_for(batch)
    fn = _get_sorted_compiled(*params, abi, suffix)
    if suffix:
        _count_unscored(packed, batch, row_bytes=4)
    args = _dense_args(packed, batch, _no_mask() if fmask is None else fmask,
                       key_row)
    return _launch(fn, args, _site("scoring.sorted", suffix), "sorted", params)


def agg_stat_reduction(match, agg_rows, agg_limbs=None):
    """Masked metric stats under a match mask — the ONE implementation both trace
    contexts call (single-shard _dense_aggstats_impl and the mesh SPMD program).

    match: bool [Q, Dpad]; agg_rows: f32 [F, 5, Dpad] per-doc folds
    (device_index.agg_doc_rows); agg_limbs: int32 [F, L, Dpad] limb rows of
    the whole-number columns' per-doc sums (device_index.agg_int_limbs) or
    None. Returns (counts int32 [Q, F], stats f32 [Q, F, 4] = (sum, min, max,
    sumsq), limb totals int32 [Q, F, L] | None). Counts and limbs ride exact
    int32 reductions — an f32 accumulator would silently round past 2^24;
    the f32 sums and sumsq (fractional columns) share one [Q, Dpad] @
    [Dpad, 2F] matmul (MXU work)."""
    import jax.numpy as jnp

    F = agg_rows.shape[0]
    mf = match.astype(jnp.float32)
    lin = jnp.concatenate([agg_rows[:, 1], agg_rows[:, 4]], axis=0)  # [2F, Dpad]
    sums2 = mf @ lin.T  # [Q, 2F]
    cnt_rows = agg_rows[:, 0].astype(jnp.int32)  # [F, Dpad]
    counts = jnp.sum(jnp.where(match[:, None, :], cnt_rows[None], 0),
                     axis=2, dtype=jnp.int32)  # [Q, F]
    has = match[:, None, :] & (agg_rows[None, :, 0, :] > 0)  # [Q, F, Dpad]
    mins = jnp.where(has, agg_rows[None, :, 2, :], jnp.inf).min(axis=2)
    maxs = jnp.where(has, agg_rows[None, :, 3, :], -jnp.inf).max(axis=2)
    stats = jnp.stack([sums2[:, :F], mins, maxs, sums2[:, F:]], axis=2)
    limb_sums = None
    if agg_limbs is not None:
        limb_sums = jnp.sum(
            jnp.where(match[:, None, None, :], agg_limbs[None], 0),
            axis=3, dtype=jnp.int32)  # [Q, F, L]
    return counts, stats, limb_sums


def _bucket_scatter(match, pdoc, pbucket, nb: int, sub_stack, sub_limbs=None):
    """One bucket agg's reductions: exact int32 doc counts per bucket, plus —
    when the agg carries metric sub-aggs (sub_stack [Fs, 5, Dpad], sub_limbs
    int32 [Fs, L, Dpad] | None) — per-bucket masked stats of the per-doc
    folds, scattered along the SAME (doc, bucket) pairs so a doc contributes
    once per bucket it belongs to (exactly the host's per-bucket mask
    collection). The limbs of whole-number columns are scattered and added as
    int32, like the counts."""
    import jax.numpy as jnp

    Q = match.shape[0]
    hit = match[:, pdoc]  # [Q, NP] bool
    counts = jnp.zeros((Q, nb), jnp.int32).at[:, pbucket].add(
        hit.astype(jnp.int32))
    if sub_stack is None:
        return counts, None, None, None
    Fs = sub_stack.shape[0]
    m = hit[:, None, :]  # [Q, 1, NP]
    cnt_g = sub_stack[:, 0][:, pdoc].astype(jnp.int32)  # [Fs, NP]
    sub_cnt = jnp.zeros((Q, Fs, nb), jnp.int32).at[:, :, pbucket].add(
        jnp.where(m, cnt_g[None], 0))
    has_vals = m & (cnt_g[None] > 0)  # min/max must ignore value-less docs
    parts = []
    for row, fill, op in ((1, 0.0, "add"), (2, jnp.inf, "min"),
                          (3, -jnp.inf, "max"), (4, 0.0, "add")):
        g = sub_stack[:, row][:, pdoc]  # [Fs, NP]
        gate = m if op == "add" else has_vals
        contrib = jnp.where(gate, g[None], jnp.float32(fill))
        base = jnp.full((Q, Fs, nb), jnp.float32(fill))
        parts.append(getattr(base.at[:, :, pbucket], op)(contrib))
    sub_stats = jnp.stack([parts[0], parts[1], parts[2], parts[3]], axis=3)
    sub_sums = None
    if sub_limbs is not None:
        n_limbs, doc_pad = sub_limbs.shape[1:]
        g = sub_limbs.reshape(Fs * n_limbs, doc_pad)[:, pdoc]  # [Fs*L, NP]
        sub_sums = jnp.zeros((Q, Fs * n_limbs, nb), jnp.int32).at[
            :, :, pbucket].add(jnp.where(m, g[None], 0)).reshape(
                Q, Fs, n_limbs, nb)
    # [Q,Fs,nb], [Q,Fs,nb,4]=(sum,min,max,sumsq), [Q,Fs,L,nb] limb totals
    return counts, sub_cnt, sub_stats, sub_sums


def _dense_aggstats_impl(scores, match,
                         agg_rows,  # [F, 5, Dpad] f32 (F may be 0)
                         agg_limbs,  # [F, L, Dpad] int32 (L may be 0)
                         bucket_pairs,  # tuple of (pair_doc, pair_bucket, nb zeros, (sub rows, sub limbs)|None)
                         fmask,  # bool [Q, Dpad] | tuple of Q [Dpad] rows — FilteredQuery masks (all-true when none)
                         *, k: int):
    match = match & _mask_matrix(fmask)
    counts, stats, limb_sums = agg_stat_reduction(match, agg_rows, agg_limbs)
    bucket_counts = tuple(
        _bucket_scatter(match, pdoc, pbucket, zeros_nb.shape[0],
                        *(sub or (None, None)))
        for (pdoc, pbucket, zeros_nb, sub) in bucket_pairs
    )
    return (*_top_k_tail(scores, match, k=k), counts, stats, limb_sums,
            bucket_counts)


def _get_agg_compiled(n_queries: int, k: int, doc_pad: int, nb_bucket: int,
                      filtered: bool = False, abi=_dense_abi, suffix: str = ""):
    import jax

    # bucket-agg count rides the pow-2 ladder: the wrapper is generic over the
    # pairs pytree (jit retraces per structure under ONE cache entry), so a
    # raw len() here would admit one executable per distinct agg count.
    # `filtered` only names the program: the filtered family rides this site
    # with an empty agg stack, and its launches should read as its own.
    key = ("aggstats", n_queries, k, doc_pad, nb_bucket, filtered) + (
        (suffix,) if suffix else ())
    fn = _compiled_cache.get(key)
    if fn is None:
        wrapper = abi(_dense_aggstats_impl, n_queries=n_queries,
                      doc_pad=doc_pad, k=k)
        fn = jax.jit(_named("scoring.aggs", wrapper, "_".join(filter(
            None, ["filtered" if filtered else "", suffix]))),
                     static_argnums=_static_argnums(abi))
        _compiled_cache[key] = fn
    return fn


def score_agg_batch_async(packed: PackedSegment, batch: TermBatch, k: int,
                          agg_stack, bucket_pairs=(), fmask=None,
                          filtered: bool = False):
    """Dense launch returning device (scores, docs, total, counts [Q, F] int,
    stats [Q, F, 4], limb totals [Q, F, L] int, bucket results) without
    syncing: the caller pulls the whole result pytree in ONE explicit
    device_get (execute._run_flat_groups; per-leaf np.asarray was a transfer
    per output — and an implicit one, which the promoted
    transfer_guard("disallow") sanitizer rejects). `agg_stack`: the
    segment's device_index.AggStack of the metric fields. stats rows: (sum,
    min(+inf if none), max(-inf), sumsq) over matched docs per agg field;
    the sum of a limbed field is its limb totals' (device_index.limb_totals).
    bucket_pairs: per bucket agg, (pair_doc, pair_bucket, zeros[NB], AggStack
    of the sub fields | None) — each bucket result is (doc counts [Q,NB], sub
    value-counts [Q,Fs,NB]|None, sub stats [Q,Fs,NB,4]|None, sub limb totals
    [Q,Fs,L,NB]|None); fmask: optional bool [Q, Dpad] FilteredQuery match
    gates; `filtered` marks the filtered family's launches (no aggregation at
    all), which compile under their own program name."""
    params = (batch.n_queries, min(k, packed.doc_pad), packed.doc_pad,
              _pow2_bucket(len(bucket_pairs), 1) if bucket_pairs else 0,
              filtered)
    abi, suffix = _abi_for(batch)
    fn = _get_agg_compiled(*params, abi, suffix)
    if suffix:
        _count_unscored(packed, batch, row_bytes=0)
    if fmask is None:
        fmask = _no_mask()
    pairs = tuple(
        (pdoc, pbucket, zeros_nb, None if sub is None else (sub.rows, sub.limbs))
        for (pdoc, pbucket, zeros_nb, sub) in bucket_pairs)
    stacks = [agg_stack] + [sub for *_pair, sub in bucket_pairs
                            if sub is not None]
    limb_rows = sum(sum(st.limbed) * st.limbs.shape[1] for st in stacks)
    if limb_rows:
        LAUNCHES.bump(exact_sum_rows=limb_rows)
    # a host mask rides the launch's one put; the resident stacks and pairs
    # are no part of it
    args = _dense_args(packed, batch, agg_stack.rows, agg_stack.limbs, pairs,
                       fmask)
    return _launch(fn, args, _site("scoring.aggs", suffix), "aggs", params)


def _detect_simple(batch: TermBatch) -> bool:
    """Pure-should all-BM25 batches reduce match to score>0 — see
    _dense_abi(simple=). BM25 is the only mode whose contribution is provably
    positive for every posting hit ((w·freq)/(freq+cache) with w>0, cache>0): CONST
    clauses can carry weight 0, and TFIDF clauses score 0 on normless fields (norm
    byte 0 → cache 0 — the meta-field case: term _id/_uid/_type), yet both still
    MATCH — the simple path would drop those hits. Cached on the batch so
    device-resident arrays are not pulled back per call."""
    if batch.simple is None:
        batch.simple = bool(
            np.all(np.asarray(batch.group) == GROUP_SHOULD)
            and np.all(batch.head[_H_GROUP] == GROUP_SHOULD)
            and np.all(np.asarray(batch.msm) <= 1)
            and np.all(np.asarray(batch.n_must) == 0)
            and np.all(np.asarray(batch.tfmode) == MODE_BM25)
            and np.all(batch.head[_H_TFMODE] == MODE_BM25)
            and (batch.coord is None or np.all(np.asarray(batch.coord) == 1.0)))
    return batch.simple


def score_term_batch_async(packed: PackedSegment, batch: TermBatch, k: int):
    """Like score_term_batch but returns device arrays without syncing — callers that
    pipeline many batches block once at the end (the serving throughput path)."""
    params = (batch.n_queries, min(k, packed.doc_pad), packed.doc_pad,
              _detect_simple(batch))
    return _launch(_get_compiled(*params), _dense_args(packed, batch),
                   "scoring.dense", "dense", params)


def score_term_batch(packed: PackedSegment, batch: TermBatch, k: int) -> ScoreResult:
    """Execute a term batch against one packed segment; returns per-query top-k with
    local doc ids (doc_count/doc_pad sentinel = no hit)."""
    top_scores, top_docs, total = _pull(score_term_batch_async(packed, batch, k))
    return finalize_score_result(top_scores, top_docs, total, packed.doc_pad)


def finalize_score_result(scores: np.ndarray, docs: np.ndarray, total: np.ndarray,
                          doc_pad: int) -> ScoreResult:
    """Host-side [Q, k] post-processing: -inf slots → doc_pad sentinel, max_score."""
    finite = np.isfinite(scores)
    docs = np.where(finite, docs, doc_pad).astype(np.int32)
    max_score = np.where(total > 0, scores[:, 0], np.nan).astype(np.float32)
    return ScoreResult(scores=scores, docs=docs, total_hits=total,
                       max_score=max_score)


# ---------------------------------------------------------------------------
# sparse candidate-centric path (the serving hot path)
# ---------------------------------------------------------------------------
#
# The dense kernel above scatter-adds into a [Q, doc_pad] accumulator — measured on the
# v5e: ~112 ms/batch for the scatter alone plus ~49 ms for the full-width top_k, and the
# accumulator is O(Q·doc_count) HBM (24 GB at enwiki scale — impossible). The sparse
# path is candidate-centric, the device analogue of Lucene's doc-at-a-time merge
# (search/query/QueryPhase.java:95-137 walks a merged postings enum; we materialize the
# merged candidate list per query and reduce it in parallel):
#
#   1. row-gather each query's QUANTIZED postings blocks  [Qb, TB, B]   (~5 ms DMA;
#      6 B/posting resident — docs i32 + tf u8 + norm byte u8, see
#      device_index module docstring)
#   2. contribution = weight · tfn, decoded IN the scan: tf widened from the
#      int plane, norm byte through the per-field 256-entry similarity LUT
#      (SimTables — replaces the pack-time baked-tfn f32 plane; the per-doc
#      [M·B] random uint8 gather the bake used to avoid stays avoided because
#      the norm byte is stored per POSTING, a streaming row access)
#   3. sort candidates by doc id per query                [Qb, P] pairs (~6 ms)
#   4. doubling-pass segment-sum merges duplicate docs (run length ≤ clause count)
#   5. bool semantics on the summed match counters at run ends
#   6. top_k over [Qb, P]                                 (~5 ms; P ≪ doc_pad)
#
# Work scales with postings touched, not with corpus size: O(Q·P) HBM per batch,
# corpus-size-independent — the layout that holds 1M+ docs (see ARCHITECTURE.md
# "HBM budget"). Queries are bucketed by their block count (power-of-two TB buckets,
# chunked to a slot budget) so executables cache; pathological block counts
# (TB > tb_max: match-everything terms) fall back to the dense kernel.


class SparseScratchPool:
    """Reusable per-bucket padded staging planes for plan_sparse_buckets.

    The sparse planner re-materialized its [Qb, TB] host staging for every
    bucket of every launch, even when the shapes repeat on every warmed
    batch — pure allocator churn on the serving hot path. The pool hands out
    (and takes back) one slot plane (SparseBatch.slots) keyed by (Qb, TB): a
    warmed repeat batch performs 0 new host allocations (`allocs` stays flat,
    pinned by tests/test_batcher.py). A plane is borrowed from take() until the
    launch's results have been PULLED — device transfers are asynchronous (and
    on CPU possibly zero-copy aliases of the numpy buffer), so giving an array
    back while its launch is still in flight would let the next take() mutate
    data the device is reading. launch_flat_sparse returns a release callback
    its caller invokes after the batch's device_get. Check-out/check-in (not
    shared mutation) keeps concurrent launches on the same segment race-free;
    the free-list is bounded so a concurrency burst can't pin staging memory
    forever."""

    _MAX_FREE = 4  # planes kept per shape

    def __init__(self):
        self._free: dict[tuple, list] = {}
        self._lock = threading.Lock()
        self.allocs = 0  # fresh allocations (a warmed repeat adds none)
        self.reuses = 0

    @staticmethod
    def staging_bytes(Qb: int, tb: int) -> int:
        return len(_S_ROWS) * Qb * tb * 4  # one int32 plane, a row per column

    def take(self, Qb: int, tb: int, sentinel_row: int):
        with self._lock:
            lst = self._free.get((Qb, tb))
            slots = lst.pop() if lst else None
            if slots is None:
                self.allocs += 1
            else:
                self.reuses += 1
        # profile attribution: whether this launch's staging came from the
        # pool or a fresh allocation (recorded OUTSIDE the pool lock — the
        # hook is record-only and must never run under another lock)
        prof = _profile.current()
        if prof is not None:
            prof.event("scratch", cache="alloc" if slots is None else "reuse",
                       shape=[int(Qb), int(tb)])
        return _blank_slots(Qb, tb, sentinel_row, slots)

    def give(self, slots):
        with self._lock:
            lst = self._free.setdefault(slots.shape[1:], [])
            if len(lst) < self._MAX_FREE:
                lst.append(slots)


# rows of SparseBatch.slots, the per-slot operand plane of a sparse launch
_S_ROWS = _S_QBLK, _S_QW, _S_QCONST, _S_QCNT, _S_QFID = range(5)


def _blank_slots(Qb: int, tb: int, sentinel_row: int, slots=None) -> np.ndarray:
    """An all-padding slot plane: every block slot the sentinel row, weight
    bits / const flag / counter / field row zero. Refills `slots` in place
    when given one (the scratch pool's reuse)."""
    if slots is None:
        slots = np.empty((len(_S_ROWS), Qb, tb), np.int32)
    slots[_S_QBLK] = sentinel_row
    slots[_S_QBLK + 1:] = 0
    return slots


@dataclass
class SparseBatch:
    """One bucket of queries sharing a [Qb, TB] block layout.

    A launch hands the device two planes: `slots` (int32 [5, Qb, TB], a row
    per slot column, the f32 weights as their bits, the const flag as 0/1)
    and `qplane` (int32 [Qb, 2 + C+1], as TermBatch's). The [Qb, TB] columns
    below are host VIEWS of `slots`."""

    n_queries: int  # real queries (rows beyond are padding)
    qids: np.ndarray  # int32 [Qb] — caller's query index per row (-1 padding)
    slots: np.ndarray  # int32 [5, Qb, TB] — rows _S_*
    qplane: np.ndarray  # int32 [Qb, 2 + C+1]
    qblk: np.ndarray  # int32 [Qb, TB] — block rows (pad: sentinel all-doc_pad row)
    qw: np.ndarray  # float32 [Qb, TB] — clause weight (0 for must_not/padding)
    qconst: np.ndarray  # int32 0/1 [Qb, TB] — constant-score clause (contribution = w)
    qcnt: np.ndarray  # int32 [Qb, TB] — packed group counter (should/must/must_not bit)
    qfid: np.ndarray  # int32 [Qb, TB] — SimTables cache row of the clause's field
    n_must: np.ndarray  # int32 [Qb]
    msm: np.ndarray  # int32 [Qb]
    coord: np.ndarray  # float32 [Qb, C+1]
    passes: int  # segment-sum doubling passes = ceil(log2(max clauses per query))
    simple: bool  # pure-should all-BM25 msm<=1 no-coord (match ≡ score>0)
    blocks_real: int = 0  # block slots of [Qb, TB] that name a postings block


def sparse_candidates(blk_docs, blk_tf, blk_nb, caches, modes,
                      qblk, qw, qconst, qfid, *, doc_pad: int):
    """The decode half of the quantized sparse scan: row-gather each query's
    postings blocks and compute per-posting contributions IN the scan —
    quantized tf widened to f32, norm byte through the per-field 256-entry
    similarity LUT (device_index.SimTables; the byte315 quantization survives
    all the way into the kernel), tf→tfn in the same f32 op order as the host
    reference (device_index.tfn_values), weight last.

    Returns (docs [Qb, TB, B] i32, contrib [Qb, TB, B] f32 — zeroed on invalid
    slots, valid [Qb, TB, B] bool)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("gather_decode"):
        docs = blk_docs[qblk]  # [Qb, TB, B]
        tf = blk_tf[qblk].astype(jnp.float32)  # u8/i16 widen; f32 escape = no-op
        nb = blk_nb[qblk].astype(jnp.int32)
        # per-field LUT decode as ONE flat gather (row*256 + byte) — XLA lowers a
        # single-index gather better than the 2-axis advanced-indexing form
        cv = caches.reshape(-1)[qfid[:, :, None] * 256 + nb]  # [Qb, TB, B]
        mode = modes[qfid][:, :, None]
        # tf factor first, then weight — Lucene's weight·tfNorm rounding order
        # (shared with the dense kernel and HostScorer)
        tfn = jnp.where(mode == TFN_BM25, tf / (tf + cv), jnp.sqrt(tf) * cv)
        contrib = qw[:, :, None] * jnp.where(qconst[:, :, None], 1.0, tfn)
        valid = docs < doc_pad
        return docs, jnp.where(valid, contrib, 0.0), valid


def sparse_reduce(docs, contrib, cnt, n_must, msm, coord,
                  *, k: int, doc_pad: int, passes: int, simple: bool,
                  use_coord: bool):
    """The reduction half: sort candidates by doc id, segment-sum duplicate
    docs (log2 doubling), bool semantics on the folded counters, top-k.
    [Qb, P] in → ([Qb, k] scores, [Qb, k] docs, [Qb] totals).
    `cnt` may be None when simple."""
    import jax
    import jax.numpy as jnp

    Qb = docs.shape[0]

    def segsum(docs_s, vals_list):
        # duplicate docs form runs of length <= clause count after the sort;
        # log2 doubling leaves the full run sum at the run's LAST element
        for i in range(passes):
            shift = 1 << i
            same = jnp.concatenate(
                [jnp.zeros((Qb, shift), bool),
                 docs_s[:, shift:] == docs_s[:, :-shift]], axis=1)
            out = []
            for v in vals_list:
                shifted = jnp.concatenate(
                    [jnp.zeros((Qb, shift), v.dtype), v[:, :-shift]], axis=1)
                out.append(v + jnp.where(same, shifted, jnp.zeros((), v.dtype)))
            vals_list = out
        return vals_list

    def top(docs_s, c_s, match):
        with jax.named_scope("top_k"):
            masked = jnp.where(match, c_s, -jnp.inf)
            top_scores, idx = jax.lax.top_k(masked, k)
            top_docs = jnp.take_along_axis(docs_s, idx, axis=1)
            return top_scores, top_docs, match.sum(axis=1, dtype=jnp.int32)

    if simple:
        with jax.named_scope("sort_by_doc"):
            docs_s, c_s = jax.lax.sort((docs, contrib), num_keys=1)
        with jax.named_scope("segment_sum"):
            (c_s,) = segsum(docs_s, [c_s])
        with jax.named_scope("match_coord"):
            is_last = jnp.concatenate(
                [docs_s[:, :-1] != docs_s[:, 1:], jnp.ones((Qb, 1), bool)], axis=1)
            match = is_last & (docs_s < doc_pad) & (c_s > 0.0)
        return top(docs_s, c_s, match)

    with jax.named_scope("sort_by_doc"):
        docs_s, c_s, n_s = jax.lax.sort((docs, contrib, cnt), num_keys=1)
    with jax.named_scope("segment_sum"):
        c_s, n_s = segsum(docs_s, [c_s, n_s])
    with jax.named_scope("match_coord"):
        is_last = jnp.concatenate(
            [docs_s[:, :-1] != docs_s[:, 1:], jnp.ones((Qb, 1), bool)], axis=1)
        m_should = n_s & 0x3FF
        m_must = (n_s >> _MUST_SHIFT) & 0x3FF
        m_not = n_s >> _NOT_SHIFT
        match = (
            is_last & (docs_s < doc_pad)
            & (m_must == n_must[:, None]) & (m_should >= msm[:, None]) & (m_not == 0)
            & ((m_should + m_must) > 0)
        )
        if use_coord:
            overlap = jnp.minimum(m_should + m_must, coord.shape[1] - 1)
            coord_fac = jnp.zeros_like(c_s)
            for j in range(coord.shape[1]):
                coord_fac = coord_fac + jnp.where(overlap == j,
                                                  coord[:, j][:, None], 0.0)
            c_s = c_s * coord_fac
    return top(docs_s, c_s, match)


def _sparse_impl(blk_docs, blk_tf, blk_nb, caches, modes,
                 qblk, qw, qconst, qcnt, qfid, n_must, msm, coord,
                 *, k: int, doc_pad: int, passes: int, simple: bool,
                 use_coord: bool):
    import jax.numpy as jnp

    Qb, TB = qblk.shape
    P = TB * BLOCK
    docs, contrib, valid = sparse_candidates(
        blk_docs, blk_tf, blk_nb, caches, modes, qblk, qw, qconst, qfid,
        doc_pad=doc_pad)
    docs = docs.reshape(Qb, P)
    contrib = contrib.reshape(Qb, P)
    cnt = (None if simple
           else jnp.where(valid, qcnt[:, :, None], 0).reshape(Qb, P))
    return sparse_reduce(docs, contrib, cnt, n_must, msm, coord,
                         k=k, doc_pad=doc_pad, passes=passes, simple=simple,
                         use_coord=use_coord)


def _get_sparse_compiled(Qb: int, TB: int, k: int, doc_pad: int, passes: int,
                         simple: bool, use_coord: bool, coord_w: int):
    import jax

    key = ("sparse", Qb, TB, k, doc_pad, passes, simple, use_coord, coord_w)
    fn = _compiled_cache.get(key)
    if fn is None:
        def wrapper(blk_docs, blk_tf, blk_nb, caches, modes, slots, qplane):
            import jax.numpy as jnp

            # the launch's two operand planes, taken apart inside the program
            qw = jax.lax.bitcast_convert_type(slots[_S_QW], jnp.float32)
            return _sparse_impl(
                blk_docs, blk_tf, blk_nb, caches, modes,
                slots[_S_QBLK], qw, slots[_S_QCONST] != 0, slots[_S_QCNT],
                slots[_S_QFID], *_unpack_qplane(qplane),
                k=k, doc_pad=doc_pad, passes=passes, simple=simple,
                use_coord=use_coord)

        fn = jax.jit(_named("scoring.sparse", wrapper))
        _compiled_cache[key] = fn
    return fn


def score_sparse_batch_async(packed: PackedSegment, sb: SparseBatch, k: int,
                             sim=None):
    """Launch one sparse bucket; returns device arrays (scores, docs, totals)
    without syncing. `sim` is the SimTables the planner resolved fids against
    (device_index.ensure_sim_tables); defaults to the segment's current one."""
    sim = sim if sim is not None else packed.sim
    Qb, TB = sb.qblk.shape
    P = TB * BLOCK
    k_eff = min(k, P)
    use_coord = not sb.simple and not bool(np.all(sb.coord == 1.0))
    params = (Qb, TB, k_eff, packed.doc_pad, sb.passes, sb.simple, use_coord,
              sb.coord.shape[1])
    fn = _get_sparse_compiled(*params)
    args = (packed.blk_docs, packed.blk_tf, packed.blk_nb, sim.caches, sim.modes,
            *_put_operands(sb.slots, sb.qplane))
    # what the launch scans against what the queries name: [Qb, TB] block
    # slots, each BLOCK postings of (doc id i32, tf, norm byte)
    LAUNCHES.add(sb.blocks_real, Qb * TB,
                 Qb * P * (4 + packed.blk_tf.dtype.itemsize + 1))
    return _launch(fn, args, "scoring.sparse", "sparse", params)


def plan_sparse_buckets(clause_lists: list, n_must: np.ndarray, msm: np.ndarray,
                        coord: np.ndarray, sentinel_row: int, *, tb_max: int = 512,
                        slot_budget: int = 32768, simple: bool = False,
                        scratch: SparseScratchPool | None = None):
    """Bucket queries by block count and build SparseBatches.

    clause_lists: per query, list of (b0, b1, weight, group, is_const, fid)
    block ranges — `fid` is the clause field's SimTables cache row
    (device_index.ensure_sim_tables), the in-scan decode's LUT index.
    Returns (batches, overflow_qids): overflow queries (TB > tb_max) need the dense
    fallback; queries with zero blocks appear in no batch (zero hits).

    `scratch` (the packed segment's SparseScratchPool) supplies the [Qb, TB]
    staging arrays; callers that pass one MUST give the arrays back after the
    device launch (launch_flat_sparse does) — None allocates fresh arrays the
    caller owns outright."""
    Q = len(clause_lists)
    tb_q = np.array([sum(b1 - b0 for (b0, b1, _w, _g, _c, _fi) in cl)
                     for cl in clause_lists], dtype=np.int64)
    overflow = [qi for qi in range(Q) if tb_q[qi] > tb_max]
    tb_host = tb_q.tolist()  # one host conversion, not a per-qi scalar read
    buckets: dict[int, list[int]] = {}
    for qi in range(Q):
        if 0 < tb_host[qi] <= tb_max:
            # block-count rung rides the autotuned ladder (pow-2 until the
            # observed histogram commits a tighter fit — common/compilecache)
            tb = _ladder_bucket("sparse_tb", tb_host[qi], 8)
            buckets.setdefault(tb, []).append(qi)

    batches = []
    for tb, qis in sorted(buckets.items()):
        max_q = max(1, slot_budget // tb)
        for start in range(0, len(qis), max_q):
            chunk = qis[start: start + max_q]
            Qb = _ladder_bucket("sparse_qb", len(chunk), 8)
            slots = (scratch.take(Qb, tb, sentinel_row) if scratch is not None
                     else _blank_slots(Qb, tb, sentinel_row))
            qblk, qconst, qcnt, qfid = (
                slots[r] for r in (_S_QBLK, _S_QCONST, _S_QCNT, _S_QFID))
            qw = slots[_S_QW].view(np.float32)
            qids = np.full(Qb, -1, np.int32)
            bn_must = np.zeros(Qb, np.int32)
            bmsm = np.zeros(Qb, np.int32)
            bcoord = np.ones((Qb, coord.shape[1]), np.float32)
            maxc = 1
            for row, qi in enumerate(chunk):
                qids[row] = qi
                bn_must[row] = n_must[qi]
                bmsm[row] = msm[qi]
                bcoord[row] = coord[qi]
                maxc = max(maxc, len(clause_lists[qi]))
                off = 0
                for (b0, b1, w, g, is_const, fid) in clause_lists[qi]:
                    nb = b1 - b0
                    if nb <= 0:
                        continue
                    qblk[row, off: off + nb] = np.arange(b0, b1, dtype=np.int32)
                    qw[row, off: off + nb] = 0.0 if g == GROUP_MUST_NOT else w
                    qconst[row, off: off + nb] = is_const
                    qcnt[row, off: off + nb] = (
                        1 if g == GROUP_SHOULD
                        else (1 << _MUST_SHIFT) if g == GROUP_MUST
                        else (1 << _NOT_SHIFT))
                    qfid[row, off: off + nb] = fid
                    off += nb
            passes = max(0, (maxc - 1).bit_length())
            batches.append(SparseBatch(
                n_queries=len(chunk), qids=qids, slots=slots,
                qplane=_pack_qplane(bn_must, bmsm, bcoord), qblk=qblk, qw=qw, qconst=qconst, qcnt=qcnt, qfid=qfid,
                n_must=bn_must, msm=bmsm, coord=bcoord,
                passes=passes, simple=simple,
                blocks_real=sum(tb_host[qi] for qi in chunk)))
    return batches, overflow


def launch_flat_sparse(packed: PackedSegment, clause_lists: list,
                       n_must: np.ndarray, msm: np.ndarray, coord: np.ndarray,
                       k: int, *, simple: bool = False, tb_max: int = 512,
                       breaker=None, sim=None):
    """Plan + launch every sparse bucket of a flat-query batch WITHOUT syncing.

    Returns (launches, overflow_qids, release) where launches =
    [(SparseBatch, device result triple)] and `release` is a zero-arg
    callback returning the borrowed staging arrays to the segment's scratch
    pool — the caller MUST invoke it only after the batch's device_get
    (transfers are async; see SparseScratchPool). collect_flat_sparse
    scatters the pulled results into [Q, k] host arrays. The dispatch half of
    the serving path's dispatch-then-merge split — it never calls
    jax.device_get.

    Staging accounting happens here, per BATCH: the padded [Qb, TB] staging
    arrays for the whole coalesced launch are reserved on the request breaker
    in one sum (the launch is the allocation, not the per-request share) and
    released once the buckets are launched."""
    sentinel_row = packed.blk_docs.shape[0] - 1
    scratch = packed.sparse_scratch
    if scratch is None:
        scratch = packed.sparse_scratch = SparseScratchPool()
    batches, overflow = plan_sparse_buckets(
        clause_lists, n_must, msm, coord, sentinel_row, tb_max=tb_max,
        simple=simple, scratch=scratch)
    est = sum(SparseScratchPool.staging_bytes(*sb.qblk.shape) for sb in batches)
    with reserve(breaker, est, "<sparse_staging>"):
        launches = [(sb, score_sparse_batch_async(packed, sb, k, sim=sim))
                    for sb in batches]

    def release():
        for sb in batches:
            scratch.give(sb.slots)

    return launches, overflow, release


def collect_flat_sparse(launches: list, pulled: list, Q: int, k: int,
                        doc_pad: int):
    """Scatter pulled bucket results (host triples, same order as `launches`)
    into [Q, k] host arrays — the merge half's pure-host counterpart of
    launch_flat_sparse."""
    scores = np.full((Q, k), -np.inf, np.float32)
    docs = np.full((Q, k), doc_pad, np.int32)
    totals = np.zeros(Q, np.int64)
    for (sb, _r), (s, d, t) in zip(launches, pulled):
        rows = sb.qids >= 0
        qid = sb.qids[rows]
        kk = s.shape[1]
        scores[qid, :kk] = s[rows]
        docs[qid, :kk] = d[rows]
        totals[qid] = t[rows]
    return scores, docs, totals


def build_term_batch(entries: list, n_queries: int, n_must: np.ndarray, msm: np.ndarray,
                     coord: np.ndarray, norm_fields: list[str], caches: np.ndarray,
                     nb_pad_row: int, head_pad_row: int = 0,
                     floor: int = 0) -> TermBatch:
    """Lay a batch's clauses out as the head-slot plane and the flat triple
    plane, bucket-padded.

    `entries` = one (qidx, b0, b1, weight, fidx, group, tfmode, row) per
    resolved clause (execute._dense_entries). A clause whose term has a row
    (row >= 0) takes the next of its query's HEAD_SLOTS, in entry order;
    padded slots point at `head_pad_row` (the plane's last row, always zero)
    with every other column zero. Every other clause, and a query's head
    clauses past its slots, contributes a triple per block row of [b0, b1),
    clauses in entry order, blocks ascending. The expansion is one np.repeat
    of the clause columns, never a Python loop over blocks; padding triples
    point at `nb_pad_row` (a row of doc_pad sentinels — contributes nothing)
    with every other column zero. M rides the `terms` ladder from
    TAIL_FLOOR and is never under `floor` (a launch whose rung is fixed by
    its query count: execute.launch_flat_dismax); the coord table's width
    rides the pow-2 ladder from 4."""
    used = [0] * n_queries
    heads, tail = [], []
    for e in entries:
        q = e[0]
        if e[7] < 0 or used[q] == HEAD_SLOTS:
            tail.append(e)
        else:
            heads.append((*e, used[q]))
            used[q] += 1
    cols = np.zeros((6, len(tail)), np.int32)
    nb = np.zeros(len(tail), np.int64)
    if tail:
        q, b0, b1, w, f, g, m, _row = zip(*tail)
        b0 = np.asarray(b0, np.int64)
        nb = np.maximum(np.asarray(b1, np.int64) - b0, 0)
        cols[_T_QIDX], cols[_T_FIDX], cols[_T_GROUP], cols[_T_TFMODE] = q, f, g, m
        cols[_T_WEIGHT] = np.asarray(w, np.float32).view(np.int32)
        # a clause's first block row less the index of its first triple: adding
        # the triple index back (below) walks [b0, b1)
        cols[_T_BLK] = b0 - (np.cumsum(nb) - nb)
    n = int(nb.sum())
    M = max(floor, _ladder_bucket("terms", max(n, 1), TAIL_FLOOR))
    # the coord table's width is a dimension of the program's key too: up the
    # pow-2 ladder, each row continued with its last value (what the overlap
    # clamp reads past a row's end anyway)
    wide = np.empty((n_queries, _pow2_bucket(coord.shape[1], 4)), np.float32)
    wide[:, :coord.shape[1]] = coord
    wide[:, coord.shape[1]:] = coord[:, -1:]
    coord = wide
    # the launch's ONE operand plane, filled in place through its views
    plane = np.zeros(6 * M + n_queries * (2 + coord.shape[1])
                     + 5 * n_queries * HEAD_SLOTS, np.int32)
    tri, qplane, head = _plane_views(plane, M, n_queries)
    head[_H_ROW] = head_pad_row
    blocks_as_rows = 0
    if heads:
        q, b0, b1, w, f, g, m, row, slot = zip(*heads)
        head[:, q, slot] = (row, np.asarray(w, np.float32).view(np.int32),
                            f, g, m)
        blocks_as_rows = sum(b1) - sum(b0)
    tri[:, :n] = np.repeat(cols, nb, axis=1)
    tri[_T_BLK, :n] += np.arange(n, dtype=np.int32)
    tri[_T_BLK, n:] = nb_pad_row
    n_must, msm = n_must.astype(np.int32), msm.astype(np.int32)
    _pack_qplane(n_must, msm, coord, out=qplane)
    return TermBatch(
        n_queries=n_queries, plane=plane, tri=tri, qplane=qplane, head=head,
        qidx=tri[_T_QIDX], blk=tri[_T_BLK], weight=tri[_T_WEIGHT].view(np.float32),
        fidx=tri[_T_FIDX], group=tri[_T_GROUP], tfmode=tri[_T_TFMODE],
        n_must=n_must, msm=msm, coord=coord, norm_fields=norm_fields,
        caches=caches, blocks_real=n, head_slots=len(heads),
        head_trips=max(used, default=0), blocks_as_rows=blocks_as_rows,
    )


# ---------------------------------------------------------------------------
# compaction concat: re-block merged postings planes from resident sources
# ---------------------------------------------------------------------------


def _concat_impl(blk_term, blk_j0, cum, starts, bases, doc_pads,
                 src_docs, src_tf, src_nb, *, doc_pad_new: int,
                 tf_layout: str):
    """One fused gather/select program assembling a merged segment's
    quantized postings planes from its sources' RESIDENT planes — the
    device half of ops/device_index.pack_segment_concat (HBM → HBM, no host
    staging of the O(postings) data).

    Per output slot (block row nb, lane): the owning merged term is
    `blk_term[nb]` (blocks never span terms), the within-term flat offset is
    `blk_j0[nb] + lane`, and the per-term cumulative source counts `cum`
    pick WHICH source holds that posting; the gather index into that
    source's flat plane is its own block start plus the within-source
    offset. Source slots masked to the source's doc_pad sentinel (dead /
    non-parent docs) map to the NEW sentinel; everything else shifts by the
    source's doc base. tf widens along the choose_tf_layout ladder
    (u8 → i16 → f32) with a plain astype — exact for the integral rungs the
    eligibility gate admits. Pad rows carry a huge `blk_j0`, so every select
    misses and the sentinel/zero initializers survive — bitwise identical to
    what pack_segment writes there."""
    import jax.numpy as jnp

    from .device_index import _TF_DTYPE

    W = len(src_docs)
    NB = blk_term.shape[0]
    B = src_docs[0].shape[1]
    j = blk_j0[:, None] + jnp.arange(B, dtype=jnp.int32)[None, :]
    out_docs = jnp.full((NB, B), doc_pad_new, dtype=jnp.int32)
    out_tf = jnp.zeros((NB, B), dtype=_TF_DTYPE[tf_layout])
    out_nb = jnp.zeros((NB, B), dtype=jnp.uint8)
    for s in range(W):
        lo = cum[s][blk_term][:, None]
        hi = cum[s + 1][blk_term][:, None]
        sel = (j >= lo) & (j < hi)
        slot = starts[s][blk_term][:, None] * B + (j - lo)
        slot = jnp.clip(slot, 0, src_docs[s].size - 1)
        d = jnp.take(src_docs[s].reshape(-1), slot)
        d = jnp.where(d >= doc_pads[s], jnp.int32(doc_pad_new), d + bases[s])
        out_docs = jnp.where(sel, d, out_docs)
        out_tf = jnp.where(
            sel, jnp.take(src_tf[s].reshape(-1), slot).astype(out_tf.dtype),
            out_tf)
        out_nb = jnp.where(sel, jnp.take(src_nb[s].reshape(-1), slot),
                           out_nb)
    return out_docs, out_tf, out_nb


@functools.lru_cache(maxsize=None)
def _get_concat_compiled(doc_pad_new: int, tf_layout: str):
    import jax

    return jax.jit(_named(
        "scoring.concat",
        functools.partial(_concat_impl, doc_pad_new=doc_pad_new,
                          tf_layout=tf_layout)))


def concat_pack_planes(blk_term, blk_j0, cum, starts, bases, doc_pads,
                       src_docs, src_tf, src_nb, *, doc_pad_new: int,
                       tf_layout: str):
    """Launch the concat program (executables cached per sentinel/layout;
    jit re-specializes per source-shape set, which the pow-2 shape buckets
    keep bounded). Inputs stay on device; outputs are the merged segment's
    resident planes — no pull here. The postings planes alone: a source's
    positions planes are not re-blocked, the merged segment's first phrase
    faults its own in from the host copy (device_index.ensure_positions)."""
    fn = _get_concat_compiled(int(doc_pad_new), tf_layout)
    return fn(blk_term, blk_j0, cum, starts, bases, doc_pads,
              tuple(src_docs), tuple(src_tf), tuple(src_nb))


# ---------------------------------------------------------------------------
# exact phrases: merge the terms' position lists
# ---------------------------------------------------------------------------
#
# Lucene's ExactPhraseScorer walks the n terms' position lists of one document
# at a time. Here the whole lists meet at once: every term's keys
# (device_index.PositionsPlane: doc << pos_bits | position, ascending) are
# moved up by R - rel_pos[i], so that the n keys of one occurrence of the
# phrase are EQUAL, the lists are merged into one ascending line (they arrive
# sorted, so a bitonic merge of log2 stages does it, no sort), and a run of n
# equal keys is one occurrence. A running sum of the run ends, read at each
# document's last key, is the phrase's frequency there; that last key is a
# marker of the plane and carries the document's norm byte, so the 256-entry
# similarity table is read without a gather over documents; a deleted
# document's marker carries the dead code in the byte's place and matches
# nothing (the plane keeps deleted documents: device_index.masked_positions).
#
# A phrase can only occur in a document that holds every one of its terms, so
# only in the documents of its RAREST term, the lead (ExactPhraseScorer is
# driven by that conjunction). The host therefore names, of every term's block
# rows, those whose document range holds a document of the lead
# (PositionsPlane.rows_holding), and the program gathers that LIST. The answer
# is the whole lists' answer bit for bit. Every key of a candidate document is
# there in every term (the rows' bounds are inclusive on both sides), so its
# runs, its frequency, its marker and its norm byte are what they were. A
# document that is no candidate lacks the lead term, and a term's keys are
# strictly ascending, so no run of n equal keys can form in it: a kept row may
# hold some or all of its keys, its frequency is 0, it matches nothing and
# adds nothing to the total, whatever key ends its group on the line. The
# kept rows stay ascending, so the merge still gets ascending lists, and the
# matching documents keep their order on the line, so top_k breaks ties as it
# did.

# A launch's line is as long as its plans have terms: `slots` slots of `rows`
# block rows, `slots` a static of the program that the launch reads from its
# members (phrase_slots, execute.launch_flat_phrase): 2 where every plan of
# the launch is a pair, else PHRASE_SLOTS. Two ascending lists are ONE bitonic
# merge over a line of 2 * rows rows; four are two merges, the second over
# 4 * rows. A pair on a line of four would merge its two lists, merge two
# slots of sentinels, and merge the two results: the same keys in the same
# order on the first half of a line whose second half is sentinels, so the
# shorter line's answer is the longer's bit for bit at half the places and a
# quarter of the merge passes' bytes. A phrase of three rides the line of four
# (one merge tree, two shapes).
PHRASE_SLOTS = 4  # terms a phrase plan may hold: a slot of the line each
# block rows a term's slot holds, up the ladder. Few and far apart: every
# rung is a program, a first sighting compiles for tens of seconds on the one
# drainer, and a warm-up has to meet every rung (padding rows cost a merge
# stage's share of microseconds). A term whose rows beside the lead term's
# documents pass the last rung goes to the host.
#
# The LAST rung is no program where the ladder has three rungs: a plan whose
# longest kept list rides it is cut by document ranges into tiles of the rung
# before, a launch each (phrase_tile_rows, execute._phrase_tiles). A phrase
# is matched inside one document, so a range of documents with the rows that
# hold its candidates is a whole problem of its own, and a merge costs more
# than its length (64.6 ms for a line of 4 x 32,768 rows against 9.4 for
# 4 x 8,192 on a v5e: PERF.md section 6, PR 47): two or three launches of
# 9.4 ms stand where one of 64.6 stood, and the eight clients of a closed
# loop wait behind a third of the stall. A row at a tile's edge holds keys of
# the next tile's documents too, so each launch is told the documents it
# answers for (_P_LO, _P_HI) and counts a match there alone; the host adds
# the tiles' totals and merges their hits as it does segments'.
PHRASE_RUNGS = (1024, 8192, 32768)
# columns of a phrase launch's operand plane, int32 [Q, 16]: the weight's
# bits, the SimTables row, the number of terms; a slot's shift a column; then
# the documents the plan's launch answers for, [lo, hi) (a tile's range, or
# every document: PHRASE_ALL_DOCS)
_P_WEIGHT, _P_FID, _P_TERMS, _P_SHIFT, _P_LO, _P_HI = 0, 1, 2, 4, 8, 9
_P_COLS = 16
PHRASE_ALL_DOCS = (0, int(np.iinfo(np.int32).max))


def phrase_tile_rows() -> int | None:
    """The block rows a slot holds in a launch of a plan whose longest kept
    list rides the LAST rung: the rung before it, where the ladder has three
    rungs or more (no such launch on a shorter ladder). Such a plan is cut by
    document ranges into launches of that rung (execute.launch_flat_phrase),
    so the last rung is no program any more: it is the most rows a term may
    keep before the host answers."""
    return PHRASE_RUNGS[-2] if len(PHRASE_RUNGS) > 2 else None


def phrase_rung(blocks: int) -> int | None:
    """The rung of PHRASE_RUNGS that holds a term of `blocks` block rows in
    a launch (the rows its list names, not the term's whole list)."""
    for rung in PHRASE_RUNGS:
        if blocks <= rung:
            return rung
    return None


def _line_shift(x, s: int, fill):
    """[Q, R, B] read as one row-major line a query, moved by `s` places:
    place j of the result holds place j - s (|s| < B), `fill` off the ends."""
    import jax
    import jax.numpy as jnp

    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
    edge = jnp.full_like(x[:, :1], fill)
    if s > 0:
        other = jnp.concatenate([edge, x[:, :-1]], axis=1)  # the row before
        return jnp.where(lane >= s, jnp.roll(x, s, axis=2),
                         jnp.roll(other, s, axis=2))
    other = jnp.concatenate([x[:, 1:], edge], axis=1)  # the row after
    return jnp.where(lane < BLOCK + s, jnp.roll(x, s, axis=2),
                     jnp.roll(other, s, axis=2))


def _line_scan(x, op, identity):
    """Inclusive scan of `op` along each query's row-major line of [Q, R, B]:
    log2 doubling inside the rows, then over the rows' totals."""
    import jax
    import jax.numpy as jnp

    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
    s = 1
    while s < BLOCK:
        x = op(x, jnp.where(lane >= s, jnp.roll(x, s, axis=2), identity))
        s *= 2
    tot = x[:, :, -1]  # [Q, R]
    s = 1
    while s < tot.shape[1]:
        tot = op(tot, jnp.concatenate(
            [jnp.full((tot.shape[0], s), identity, tot.dtype), tot[:, :-s]],
            axis=1))
        s *= 2
    before = jnp.concatenate(
        [jnp.full((tot.shape[0], 1), identity, tot.dtype), tot[:, :-1]], axis=1)
    return op(x, before[:, :, None])


def _bitonic_merge(x):
    """[Q, G, L, B]: each [L, B] row-major line ascending over its first half
    and descending over its second comes back ascending. log2(L * B) stages
    of one compare-exchange each: whole rows while the stride is a row or
    more, lanes inside a row after."""
    import jax
    import jax.numpy as jnp

    Q, G, L, B = x.shape
    d = L // 2
    while d >= 1:
        if d >= 8:  # whole (8, 128) tiles change places
            y = x.reshape(Q, G, L // (2 * d), 2, d, B)
            a, b = y[:, :, :, 0], y[:, :, :, 1]
            x = jnp.stack([jnp.minimum(a, b), jnp.maximum(a, b)], axis=3)
        else:  # a few rows: as halves of one long minor axis, no short one
            y = x.reshape(Q, G, L // (2 * d), 2 * d * B)
            a, b = y[..., : d * B], y[..., d * B:]
            x = jnp.concatenate([jnp.minimum(a, b), jnp.maximum(a, b)],
                                axis=-1)
        x = x.reshape(Q, G, L, B)
        d //= 2
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 3)
    d = B // 2
    while d >= 1:
        upper = (lane & d) != 0
        other = jnp.where(upper, jnp.roll(x, d, axis=3),
                          jnp.roll(x, -d, axis=3))
        x = jnp.where(upper, jnp.maximum(x, other), jnp.minimum(x, other))
        d //= 2
    return x


def _pair_up(x):
    """[Q, G, L, B] ascending lines -> [Q, G / 2, 2 L, B]: every second line
    turned round behind the one before it, the shape _bitonic_merge takes."""
    import jax.numpy as jnp

    # lax.rev, not a negative-step index: that one lowers to a gather of
    # every key (36 ms for 8.4M of them on a v5e, three quarters of a launch)
    return jnp.concatenate(
        [x[:, 0::2], jnp.flip(x[:, 1::2], axis=(2, 3))], axis=2)


def _lut256(table, byte):
    """table[q, byte[q, ...]] of a [Q, 256] table, as 256 compare-selects a
    place: a gather of as many indices costs the chip 8 ns each, where these
    fuse into one pass over the line."""
    import jax.numpy as jnp

    out = jnp.zeros(byte.shape, table.dtype)
    for b in range(256):
        out = jnp.where(byte == b, table[:, b][:, None, None], out)
    return out


def _phrase_impl(pos_keys, caches, modes, qplane, blk, *, k: int,
                 pos_bits: int):
    import jax
    import jax.numpy as jnp

    Q = qplane.shape[0]
    mark_base = positions_mark_base(pos_bits)
    pos_mask = (1 << pos_bits) - 1
    slots = blk.shape[1]  # the launch's line: 2 (every plan a pair) or 4
    with jax.named_scope("gather_positions"):
        shift = qplane[:, _P_SHIFT: _P_SHIFT + slots, None, None]
        keys = pos_keys[blk]  # [Q, slots, rows, B]
        keys = jnp.where(keys == POS_SENTINEL, POS_SENTINEL, keys + shift)
    with jax.named_scope("phrase_join"):
        for _ in range(slots.bit_length() - 1):  # a merge a halving of the lists
            keys = _bitonic_merge(_pair_up(keys))
        keys = keys[:, 0]  # [Q, slots * rows, B]
        n = qplane[:, _P_TERMS, None, None]
        # markers sort behind every position and take no part in a run
        word = (keys & pos_mask) < mark_base
        run_end = jnp.where(n == 2, keys == _line_shift(keys, 1, -1),
                            jnp.where(n == 3, keys == _line_shift(keys, 2, -1),
                                      keys == _line_shift(keys, 3, -1)))
        hit = word & run_end
    with jax.named_scope("segment_sum"):
        after = _line_shift(keys, -1, POS_SENTINEL)
        last = (keys != POS_SENTINEL) & (
            (after == POS_SENTINEL)
            | ((after >> pos_bits) != (keys >> pos_bits)))
        seen = _line_scan(hit.astype(jnp.int32), jnp.add, 0)
        # the sum at the last key of the document before
        before = _line_shift(
            _line_scan(jnp.where(last, seen, 0), jnp.maximum, 0), 1, 0)
        freq = seen - before
        # a live document's last key is a marker that carries its norm byte;
        # a deleted one's carries POS_DEAD_CODE and matches nothing
        nb = ((keys & pos_mask) - mark_base) >> POS_MARK_SHIFT
        # a tile answers for its own documents alone: a row at its edge holds
        # keys of the tile beside it too, whose launch counts those
        doc = keys >> pos_bits
        mine = (doc >= qplane[:, _P_LO, None, None]) \
            & (doc < qplane[:, _P_HI, None, None])
        match = last & (freq > 0) & (nb < POS_DEAD_CODE) & mine
    with jax.named_scope("top_k"):
        fid = qplane[:, _P_FID]
        cv = _lut256(caches[fid], nb)
        f = freq.astype(jnp.float32)
        w = jax.lax.bitcast_convert_type(qplane[:, _P_WEIGHT], jnp.float32)
        # tf factor first, then weight: the host's order (HostScorer._eval_phrase)
        tfn = jnp.where(modes[fid][:, None, None] == TFN_BM25,
                        f / (f + cv), jnp.sqrt(f) * cv)
        scores = jnp.where(match, w[:, None, None] * tfn, -jnp.inf)
        top_scores, idx = jax.lax.top_k(scores.reshape(Q, -1), k)
        top_docs = jnp.take_along_axis(doc.reshape(Q, -1), idx, axis=1)
        return top_scores, top_docs, match.sum(axis=(1, 2), dtype=jnp.int32)


def _get_phrase_compiled(n_queries: int, rows: int, k: int, pos_bits: int,
                         slots: int):
    """The phrase program of a launch of `n_queries` plans on a line of
    `slots` (2 or PHRASE_SLOTS) times `rows` block rows. `slots` is in the
    key and in the warm params though the program reads it from `blk`'s shape:
    jit would specialise on the shape anyway, and a restart's replay has to
    know which of the two lines a launch rode."""
    import jax

    key = ("phrase", n_queries, rows, k, pos_bits, slots)
    fn = _compiled_cache.get(key)
    if fn is None:
        def wrapper(pos_keys, caches, modes, qplane, blk):
            return _phrase_impl(pos_keys, caches, modes, qplane, blk, k=k,
                                pos_bits=pos_bits)

        fn = jax.jit(_named("scoring.phrase", wrapper))
        _compiled_cache[key] = fn
    return fn


def phrase_slots(entries: list) -> int:
    """The slots of the line a launch of these plans rides: 2 where every
    plan holds two terms, else PHRASE_SLOTS (a phrase of three keeps the line
    of four). Read from the launch's members alone."""
    return 2 if all(len(terms) == 2 for _w, _fid, terms, _docs in entries) \
        else PHRASE_SLOTS


def phrase_operands(entries: list, n_queries: int, rows: int, pad_row: int):
    """The operands of one phrase launch: the plane of columns _P_* and the
    block rows each slot gathers, int32 [Q, slots, rows], `slots` the
    entries' own (phrase_slots). `entries` holds a plan's (weight f32,
    SimTables row, [(block rows ascending, block rows of the term's whole
    list, shift) a term], (lo, hi): the documents the launch answers for, a
    tile's or PHRASE_ALL_DOCS); places past a term's rows, slots past a
    plan's terms and plans past the entries name `pad_row`, the plane's row
    of POS_SENTINEL, so they match nothing (a plan past the entries answers
    for no document at all). The plane keeps its PHRASE_SLOTS shift columns
    on either line; those past the line's slots are not read."""
    qplane = np.zeros((n_queries, _P_COLS), np.int32)
    blk = np.full((n_queries, phrase_slots(entries), rows), pad_row, np.int32)
    for q, (w, fid, terms, (lo, hi)) in enumerate(entries):
        qplane[q, _P_WEIGHT] = np.float32(w).view(np.int32)
        qplane[q, _P_FID] = fid
        qplane[q, _P_TERMS] = len(terms)
        qplane[q, _P_LO], qplane[q, _P_HI] = lo, hi
        for i, (named, _whole, shift) in enumerate(terms):
            blk[q, i, : len(named)] = named
            qplane[q, _P_SHIFT + i] = shift
    return qplane, blk


def score_phrase_batch_async(plane: PositionsPlane, sim, entries: list,
                             n_queries: int, rows: int, k: int,
                             note_t0: float | None = None):
    """Launch the phrase program over one segment's positions plane for the
    plans of `entries` (phrase_operands) at a width of `n_queries`, a slot
    of `rows` block rows a term on a line of phrase_slots(entries) slots;
    returns the device arrays (scores [Q, k], docs [Q, k], totals [Q])
    without syncing. `sim` is the SimTables whose
    rows the plane names. `note_t0`: when the host began to assemble this
    launch's operands; from there to the end of their one device_put is the
    span `shard.phrase_plan` (a note inside the running `dispatch.stage`)."""
    operands = phrase_operands(entries, n_queries, rows,
                               len(plane.host_keys) - 1)
    slots = operands[1].shape[1]
    params = (n_queries, rows, min(k, slots * rows * BLOCK), plane.pos_bits,
              slots)
    fn = _get_phrase_compiled(*params)
    args = (plane.keys, sim.caches, sim.modes, *_put_operands(*operands))
    if note_t0 is not None:
        _tracing.note("shard.phrase_plan", note_t0)
    # what the launch gathers: the line's `slots` slots of `rows` block rows
    # a plan, BLOCK keys of 4 B each, padding included; the places no term
    # named (the sentinel row, gathered again and again) are the padding.
    # What the terms named is what the lead term left of their whole lists
    launched = n_queries * slots * rows
    terms = [term for _w, _fid, terms, _docs in entries for term in terms]
    named = sum(len(rows_named) for rows_named, _whole, _shift in terms)
    listed = sum(whole for _rows_named, whole, _shift in terms)
    LAUNCHES.bump(phrase=1, phrase_pair_launches=int(slots == 2),
                  position_bytes=launched * BLOCK * 4,
                  position_pad_bytes=(launched - named) * BLOCK * 4,
                  position_list_bytes=listed * BLOCK * 4,
                  position_skip_bytes=(listed - named) * BLOCK * 4)
    return _launch(fn, args, "scoring.phrase", "phrase", params)


# ---------------------------------------------------------------------------
# multi-term masks (prefix, wildcard, regexp)
# ---------------------------------------------------------------------------
#
# A multi-term query or filter matches every document that holds at least
# one term its pattern names (search/multiterm.py). Every term owns a run of
# block rows of `blk_docs` in term-id order, so a prefix is ONE slab of the
# plane and a wildcard a list of runs inside its head's slab: the host names
# the rows (filters.MultiTermFilter.block_rows) and this program gathers them
# and ORs their documents into a mask row a search. No new plane, and no
# tail of its own: the rows are the `fmask` of the dense and unscored
# launches (execute._filter_mask_matrix), which gate the match by the live
# documents as they always did. A dead or nested document's slots hold
# `doc_pad` in the plane already, as its padding slots do, so they fall out
# of the scatter with the padding rows a launch names.

# block rows a search's row gathers, up the ladder. Every rung is a program
# a query count: few and far apart, so that a warm-up meets them all. A
# pattern that names more rows than the last rung holds stays on the host
# (a query: lower_fallback_reason `multiterm_expansion`; a filter: its row is
# evaluated there and put).
MULTITERM_RUNGS = (256, 2048, 8192)
_MULTITERM_GROUP = 8  # searches of the first rung one launch builds rows for


def multiterm_rung(rows: int) -> int | None:
    """The rung of MULTITERM_RUNGS that holds `rows` block rows."""
    for rung in MULTITERM_RUNGS:
        if rows <= rung:
            return rung
    return None


def _multiterm_impl(blk_docs, rows, *, doc_pad: int):
    """(bool [G, doc_pad], the same G rows apart): a row a search, the
    documents in the block rows `rows` (int32 [G, rung]) names of
    `blk_docs`. A search whose places all name padding (one past the
    launch's own) matches nothing. Both forms, because the caller takes the
    matrix whole where the launch built every mask row of its batch, and
    otherwise stacks single rows with resident and host rows in any order:
    a row sliced out on the host would be a dispatch, and a program, a
    place."""
    import jax
    import jax.numpy as jnp

    n = rows.shape[0]
    with jax.named_scope("gather_rows"):
        docs = blk_docs[rows]  # [G, rung, B]; a padding slot holds doc_pad
        flat = jnp.where(
            docs < doc_pad,
            jnp.arange(n, dtype=jnp.int32)[:, None, None] * doc_pad + docs,
            n * doc_pad).reshape(-1)
    with jax.named_scope("scatter_or"):
        # out-of-range slots index past the end and are dropped
        hits = jnp.zeros(n * doc_pad, jnp.int32).at[flat].add(
            1, mode="drop").reshape(n, doc_pad) > 0
    return hits, tuple(hits[g] for g in range(n))


def _get_multiterm_compiled(n_queries: int, rung: int, doc_pad: int):
    import jax

    key = ("multiterm", n_queries, rung, doc_pad)
    fn = _compiled_cache.get(key)
    if fn is None:
        def wrapper(blk_docs, rows):
            return _multiterm_impl(blk_docs, rows, doc_pad=doc_pad)

        fn = jax.jit(_named("scoring.multiterm", wrapper))
        _compiled_cache[key] = fn
    return fn


def multiterm_launches(row_lists: list) -> list:
    """(places in `row_lists`, query count, rung) a launch: the lists of the
    first rung together, _MULTITERM_GROUP at a time and their count up the
    pow-2 ladder; a list of a longer rung alone (a window meets few of them,
    and a program for every count of them would be met by no warm-up). A
    list with no row is in no launch."""
    first = [i for i, rows in enumerate(row_lists)
             if 0 < len(rows) <= MULTITERM_RUNGS[0]]
    groups = [first[i: i + _MULTITERM_GROUP]
              for i in range(0, len(first), _MULTITERM_GROUP)]
    return [(group, _pow2_bucket(len(group), 1), MULTITERM_RUNGS[0])
            for group in groups] + [
        ([i], 1, multiterm_rung(len(rows))) for i, rows in enumerate(row_lists)
        if len(rows) > MULTITERM_RUNGS[0]]


def build_multiterm_rows(packed: PackedSegment, row_lists: list,
                         note_t0: float | None = None) -> list:
    """(places in `row_lists`, device bool [n, doc_pad], its rows apart) a
    launch (multiterm_launches): row q holds the documents of the segment's
    postings plane that the block rows `row_lists[places[q]]` (int32, at
    most MULTITERM_RUNGS[-1] of them) hold, and the rows past the launch's
    places match nothing. A list with no row is in no launch.
    The operands of every launch go down in ONE device_put; places past a
    list's rows and lists past the launch's name the plane's last row, all
    padding. `note_t0`: when the host began the expansion; from there to the
    end of the put is the span `shard.multiterm_expand` (a note inside the
    running `dispatch.stage`). Returns without syncing."""
    launches = multiterm_launches(row_lists)
    pad_row = packed.blk_docs.shape[0] - 1
    operands = []
    for places, n_queries, rung in launches:
        blk = np.full((n_queries, rung), pad_row, np.int32)
        for q, at in enumerate(places):
            blk[q, : len(row_lists[at])] = row_lists[at]
        operands.append(blk)
    device = _put_operands(*operands) if operands else ()
    if note_t0 is not None:
        _tracing.note("shard.multiterm_expand", note_t0)
    # what the launches gather: BLOCK document ids of 4 B a block row,
    # padding included; the rows no list named are the padding
    launched = sum(blk.size for blk in operands)
    named = sum(len(rows) for rows in row_lists)
    LAUNCHES.bump(multiterm=len(launches),
                  multiterm_bytes=launched * BLOCK * 4,
                  multiterm_pad_bytes=(launched - named) * BLOCK * 4)
    built = []
    for (places, n_queries, rung), blk in zip(launches, device):
        params = (n_queries, rung, packed.doc_pad)
        # the outermost scope wins: a row built inside a sorted or aggregated
        # launch's staging compiles under that family
        with compile_tag("filtered"):
            built.append((places, *_launch(
                _get_multiterm_compiled(*params), (packed.blk_docs, blk),
                "scoring.multiterm", "filtered", params)))
    return built


# ---------------------------------------------------------------------------
# dis_max: an accumulator a disjunct, combined before the top-k cut
# ---------------------------------------------------------------------------
#
# A dis_max of one-field OR queries (a `multi_match` of type best_fields is
# one, a `match` a field) scores a document `best + tie * (total - best)`
# over its disjuncts' sums (the reference's DisjunctionMaxQuery; HostScorer's
# DisMaxQuery branch). No plain program can: each adds every clause into ONE
# accumulator a document, and two plain launches merged on the host cannot
# either, since a document outside both fields' top k can lead the combined
# order. So the dense core runs with an accumulator a (query, disjunct): a
# launch of Q plans of D disjuncts is a TermBatch of Q * D rows, row q * D + d
# holding the clauses of plan q's disjunct d (execute.launch_flat_dismax), and
# the combine folds the D planes of a query into one before _top_k_tail. Every
# clause is a SHOULD of positive weight under BM25, so a disjunct matches
# where its sum is positive and the query where its best disjunct does: no
# counter plane (the dense programs' `simple` case). A plan of fewer disjuncts
# than the launch's leaves its last rows empty: zero is the neutral element
# of both the max and the sum.

# the most disjuncts a dis_max plan may hold: more stay on the host
# (lower_fallback_reason `dismax_disjuncts`); the count is static in a
# program's key (2, 3 or 4)
DISMAX_SLOTS = 4


def _dismax_impl(blk_docs, blk_freqs, head_rows, live_parent, doc_table,
                 plane, m, *, n_queries: int, disjuncts: int, doc_pad: int,
                 k: int):
    """The dense launch ABI (_dense_abi's operands; `plane` holds Q * D rows'
    batch and then the Q tie-breakers' f32 bits) behind the dis_max combine."""
    import jax
    import jax.numpy as jnp

    rows = n_queries * disjuncts
    cut = plane.shape[0] - n_queries
    tie = jax.lax.bitcast_convert_type(plane[cut:], jnp.float32)
    tri, _qplane, head = _plane_views(plane[:cut], m, rows)
    with jax.named_scope("disjunct_accumulate"):
        sums, _counts = _dense_accumulate(
            blk_docs, blk_freqs, head_rows, doc_table, tri, head,
            Q=rows, doc_pad=doc_pad, counters=False)
    with jax.named_scope("dismax_combine"):
        # the host's order (HostScorer, DisMaxQuery): best and total over the
        # disjuncts in turn, then best + tie * (total - best), all float32
        sums = sums.reshape(n_queries, disjuncts, doc_pad)
        best = total = sums[:, 0]
        for d in range(1, disjuncts):
            best = jnp.maximum(best, sums[:, d])
            total = total + sums[:, d]
        scores = best + tie[:, None] * (total - best)
        match = (best > 0.0) & live_parent[None, :]
    return _top_k_tail(scores, match, k=k)


def _get_dismax_compiled(n_queries: int, disjuncts: int, k: int, doc_pad: int):
    import jax

    key = ("dismax", n_queries, disjuncts, k, doc_pad)
    fn = _compiled_cache.get(key)
    if fn is None:
        def wrapper(blk_docs, blk_freqs, head_rows, live_parent, doc_table,
                    plane, m):
            return _dismax_impl(blk_docs, blk_freqs, head_rows, live_parent,
                                doc_table, plane, m, n_queries=n_queries,
                                disjuncts=disjuncts, doc_pad=doc_pad, k=k)

        fn = jax.jit(_named("scoring.dismax", wrapper),
                     static_argnums=_DENSE_STATIC_ARGNUMS)
        _compiled_cache[key] = fn
    return fn


def score_dismax_batch_async(packed: PackedSegment, batch: TermBatch, k: int,
                             ties: tuple, disjuncts: int,
                             note_t0: float | None = None):
    """Launch the dis_max program over one segment for len(`ties`) plans of
    `disjuncts` accumulators each: `batch` holds their product of rows, row
    q * disjuncts + d the clauses of plan q's disjunct d, and `ties` the
    plans' tie-breakers, which ride the batch's one plane as their bits.
    Returns the device arrays (scores [Q, k], docs [Q, k], totals [Q])
    without syncing. `note_t0`: when the host began to stage this launch;
    from there to the end of the operands' one device_put is the span
    `shard.dismax_plan` (a note inside the running `dispatch.stage`)."""
    n_queries = len(ties)
    params = (n_queries, disjuncts, min(k, packed.doc_pad), packed.doc_pad)
    fn = _get_dismax_compiled(*params)
    args = _dense_args(packed, batch, scalars=ties)
    if note_t0 is not None:
        _tracing.note("shard.dismax_plan", note_t0)
    # what the launch reads, by its own reckoning: a dense launch's bytes for
    # the batch's rows (_dense_bytes: the triples' slots, the head loop's
    # planes, the accumulators, which here the combine reads) and the
    # combined plane a query that top_k reads; the triples past the blocks
    # the batch names are padding
    m = len(batch.blk)
    LAUNCHES.bump(
        dismax=1, dismax_blocks=m, dismax_pad_blocks=m - batch.blocks_real,
        dismax_bytes=_dense_bytes(packed, batch)
        + n_queries * packed.doc_pad * 4)
    return _launch(fn, args, "scoring.dismax", "dis_max", params)


# ---------------------------------------------------------------------------
# compile-warm builders (common/compilecache)
# ---------------------------------------------------------------------------
# Each builder maps a WarmSpec's recorded params back to the SAME jitted
# callable the launch site uses (same _compiled_cache key), so the warmer's
# dummy invocation populates exactly the dispatch-cache entry a real query
# will hit. The script function_score variant has no builder on purpose: its
# executable closes over a live sandboxed script object.


@_WARM.builder("scoring.dense")
def _build_dense(params):
    return _get_compiled(*params)


@_WARM.builder("scoring.sorted")
def _build_sorted(params):
    return _get_sorted_compiled(*params)


@_WARM.builder("scoring.aggs")
def _build_aggs(params):
    return _get_agg_compiled(*params)


@_WARM.builder("scoring.sorted_unscored")
def _build_sorted_unscored(params):
    return _get_sorted_compiled(*params, _unscored_abi, "unscored")


@_WARM.builder("scoring.aggs_unscored")
def _build_aggs_unscored(params):
    return _get_agg_compiled(*params, _unscored_abi, "unscored")


def _fs_rows_from(params, abi=_dense_abi, suffix: str = ""):
    n_queries, k, doc_pad, bmode, use_min_score, no_functions = params
    return _get_fs_compiled("rows", n_queries, k, doc_pad, abi, suffix,
                            bmode=bmode, use_min_score=use_min_score,
                            no_functions=no_functions)


@_WARM.builder("scoring.fs_rows")
def _build_fs_rows(params):
    return _fs_rows_from(params)


@_WARM.builder("scoring.fs_rows_unscored")
def _build_fs_rows_unscored(params):
    return _fs_rows_from(params, _unscored_abi, "unscored")


@_WARM.builder("scoring.sparse")
def _build_sparse(params):
    return _get_sparse_compiled(*params)


@_WARM.builder("scoring.phrase")
def _build_phrase(params):
    return _get_phrase_compiled(*params)


@_WARM.builder("scoring.multiterm")
def _build_multiterm(params):
    return _get_multiterm_compiled(*params)


@_WARM.builder("scoring.dismax")
def _build_dismax(params):
    return _get_dismax_compiled(*params)
