"""Mesh search: every shard on its own device, one SPMD program per query batch.

This is the TPU-native replacement for the reference's coordinator-loop scatter/gather
(SURVEY.md §5.8): an N-shard index maps 1:1 onto an N-device mesh axis "shards", and the
query and merge phases of a search become ONE jitted program with collectives inside:

  DFS phase      → df/maxDoc/sumTTF summed over the shards ON THE HOST, which assembles
                   every shard's clause table anyway (ref: DfsPhase +
                   SearchPhaseController.aggregateDfs). idf and the BM25 norm cache come
                   from the similarity classes in f64-then-f32, the numbers the
                   single-shard path feeds its kernel: an f32 log on the device is not
                   the host's (MeshSearchExecutor._clause_weights)
  query phase    → per-shard fused scoring (same math as ops/scoring.py)
  top-k merge    → all_gather of per-shard top-k, then a second lax.top_k
                   (ref: SearchPhaseController.sortDocs — the coordinator merge)

Tie-breaking matches Lucene's merge: candidates are gathered shard-major, and XLA's
top_k prefers lower indices on equal scores, so equal-score hits order by (shard asc,
doc asc) exactly like the reference.

A second mesh axis "replicas" data-parallelizes the QUERY BATCH — the direct analogue of
the reference's replica groups serving different requests concurrently (read scaling),
but as one SPMD program instead of a load balancer.

Mesh layout (scaling-book recipe: pick a mesh, annotate shardings, let XLA insert
collectives):
    mesh  = Mesh(devices.reshape(R, S), ("replicas", "shards"))
    index arrays  [S, ...]        → P("shards", ...)   replicated over "replicas"
    query entries [R, S, M, ...]  → P("replicas", "shards", ...)
    outputs       [R, Qd, k]      → P("replicas", ...)

A launch crosses the host-device boundary once each way: what changes between
launches (the entry arrays, clause weights and per-query rows _assemble makes)
goes down as ONE int32 plane [S, L], a row a shard, in one device_put
(_pack_plane / _on_plane); the index and the BM25 norm cache stay on the mesh;
the plain search's outputs come up as one packed plane in one device_get.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field

import numpy as np

from ..common.jaxenv import compile_tag
from ..common.smallfloat import jnp_norm_table
from ..index.engine import Searcher
from ..ops.device_index import (
    _TF_DTYPE,
    BLOCK,
    _ladder_bucket,
    choose_tf_layout,
    expand_ranges,
    tf_plane_itemsize,
)
from ..search.execute import (
    GROUP_MUST_NOT,
    MODE_BM25,
    MODE_TFIDF,
    Clause,
    FlatPlan,
    ShardContext,
    lower_flat,
)
from ..search.similarity import BM25Similarity, TFIDFSimilarity

_MUST_SHIFT, _NOT_SHIFT = 10, 20


# ---------------------------------------------------------------------------
# packing: searchers → stacked mesh arrays
# ---------------------------------------------------------------------------


@dataclass
class ShardCSR:
    """One shard's postings flattened across its segments (doc ids rebased)."""

    term_ids: dict  # (field, term) -> tid
    post_offsets: np.ndarray
    post_docs: np.ndarray
    post_freqs: np.ndarray
    norms: dict  # field -> uint8[D]
    doc_count: int
    live_parent: np.ndarray
    max_doc: int
    sum_ttf: dict  # field -> int
    field_doc_count: dict


def _combine_segments(searcher: Searcher) -> ShardCSR:
    """Concatenate a shard's segments into one CSR (host-side, at mesh-pack time)."""
    term_ids: dict = {}
    rows: dict = {}
    D = searcher.max_doc
    norms_fields = set()
    for seg in searcher.segments:
        norms_fields.update(seg.norms)
    norms = {f: np.zeros(D, dtype=np.uint8) for f in norms_fields}
    live = np.zeros(D, dtype=bool)
    sum_ttf: dict = {}
    field_doc_count: dict = {}
    for seg, base in zip(searcher.segments, searcher.bases):
        live[base: base + seg.doc_count] = seg.live & seg.parent_mask
        for f, arr in seg.norms.items():
            norms[f][base: base + seg.doc_count] = arr
        for f, st in seg.field_stats.items():
            sum_ttf[f] = sum_ttf.get(f, 0) + st.sum_ttf
            field_doc_count[f] = field_doc_count.get(f, 0) + st.doc_count
        # one batched pull of the offsets per segment; the per-term int() pair
        # was a scalar extraction per posting list (the _merge_seg_hits shape)
        offs = seg.post_offsets.tolist()
        for f, td in seg.term_dict.items():
            for term, tid in td.items():
                s, e = offs[tid], offs[tid + 1]
                key = (f, term)
                row = rows.get(key)
                if row is None:
                    rows[key] = [seg.post_docs[s:e] + base], [seg.post_freqs[s:e]]
                else:
                    row[0].append(seg.post_docs[s:e] + base)
                    row[1].append(seg.post_freqs[s:e])
    offsets = [0]
    docs_parts, freqs_parts = [], []
    for i, (key, (dparts, fparts)) in enumerate(sorted(rows.items())):
        term_ids[key] = i
        d = np.concatenate(dparts)
        docs_parts.append(d)
        freqs_parts.append(np.concatenate(fparts))
        offsets.append(offsets[-1] + len(d))
    return ShardCSR(
        term_ids=term_ids,
        post_offsets=np.asarray(offsets, dtype=np.int64),
        post_docs=np.concatenate(docs_parts) if docs_parts else np.zeros(0, np.int32),
        post_freqs=np.concatenate(freqs_parts) if freqs_parts else np.zeros(0, np.float32),
        norms=norms,
        doc_count=D,
        live_parent=live,
        max_doc=D,
        sum_ttf=sum_ttf,
        field_doc_count=field_doc_count,
    )


@dataclass
class ShardedIndex:
    """N shards packed to COMMON shapes and stacked along the mesh "shards" axis."""

    n_shards: int
    doc_pad: int
    nb_pad: int
    fields: list  # norm field order (fidx)
    blk_docs: object  # [S, NB, B] int32 (device, sharded)
    blk_tf: object  # [S, NB, B] quantized term freqs (u8/i16; f32 escape) —
    # widened to f32 INSIDE the SPMD program; norms stay a separate per-doc
    # byte plane (below), so mesh-resident postings are 5 B/posting in the
    # common uint8 layout
    tf_layout: str  # device_index.TF_* ladder, chosen over ALL shards
    norms: object  # [S, F, Dpad] uint8
    live: object  # [S, Dpad] bool
    shard_term_blocks: list  # per shard: (field, term) -> (blk_start, blk_end)
    shard_term_df: list  # per shard: (field, term) -> df
    max_doc: np.ndarray  # [S] int64 (host: statistics never reach the device)
    sum_ttf: np.ndarray  # [S, F] int64 (host)
    mesh: object = None
    # fused-agg state (built lazily by mesh_serving, lives and dies with this
    # packed generation): per-FIELD host rows so overlapping field sets never
    # recompute, plus a bounded cache of per-tuple device stacks
    agg_field_rows: dict = dc_field(default_factory=dict)  # field -> np [S, 5, Dpad]
    agg_stacks: dict = dc_field(default_factory=dict)  # fields-tuple -> device
    searchers: list = dc_field(default_factory=list)  # for lazy agg-row builds

    def resident_postings_bytes(self) -> int:
        """Device-resident postings-plane bytes across all shards (docs i32 +
        quantized tf) — surfaced by mesh_serving's repack log/stats so the
        quantized-layout win shows up in capacity planning."""
        slots = self.n_shards * self.nb_pad * BLOCK
        return slots * (4 + tf_plane_itemsize(self.tf_layout))


def build_sharded_index(searchers: list[Searcher], fields: list[str],
                        mesh=None) -> ShardedIndex:
    """Pack each shard to the max bucket shapes and stack; place on `mesh` axis
    "shards" when given (device_put with NamedSharding), else host arrays."""
    import jax
    import jax.numpy as jnp

    csrs = [_combine_segments(s) for s in searchers]
    S = len(csrs)
    doc_pad = _ladder_bucket("docs", max(max(c.doc_count for c in csrs), 1),
                             128)
    nb_needed = []
    for c in csrs:
        counts = np.diff(c.post_offsets)
        nb_needed.append(int(((counts + BLOCK - 1) // BLOCK).sum()))
    nb_pad = _ladder_bucket("nb", max(nb_needed) + 1, 64)

    blk_docs = np.full((S, nb_pad, BLOCK), doc_pad, dtype=np.int32)
    blk_freqs = np.zeros((S, nb_pad, BLOCK), dtype=np.float32)  # f32 staging
    norms = np.zeros((S, len(fields), doc_pad), dtype=np.uint8)
    live = np.zeros((S, doc_pad), dtype=bool)
    shard_term_blocks = []
    shard_term_df = []
    max_doc = np.zeros(S, dtype=np.int64)
    sum_ttf = np.zeros((S, len(fields)), dtype=np.int64)

    for si, c in enumerate(csrs):
        counts = np.diff(c.post_offsets)
        nblks = (counts + BLOCK - 1) // BLOCK
        blk_start = np.zeros(len(counts) + 1, dtype=np.int64)
        np.cumsum(nblks, out=blk_start[1:])
        flat_docs = blk_docs[si].reshape(-1)
        flat_freqs = blk_freqs[si].reshape(-1)
        if len(c.post_docs):
            slots = expand_ranges(blk_start[:-1] * BLOCK, counts)
            flat_docs[slots] = c.post_docs
            flat_freqs[slots] = c.post_freqs
        tb = {}
        tdf = {}
        bs = blk_start.tolist()  # batched: one pull instead of 2 per term
        cnt = counts.tolist()
        for key, tid in c.term_ids.items():
            tb[key] = (bs[tid], bs[tid + 1])
            tdf[key] = cnt[tid]
        shard_term_blocks.append(tb)
        shard_term_df.append(tdf)
        live[si, : c.doc_count] = c.live_parent
        for fi, f in enumerate(fields):
            if f in c.norms:
                norms[si, fi, : c.doc_count] = c.norms[f]
            sum_ttf[si, fi] = c.sum_ttf.get(f, 0)
        max_doc[si] = c.max_doc

    def put(arr, spec):
        if mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(arr, NamedSharding(mesh, spec))

    from jax.sharding import PartitionSpec as P

    spec = P("shards") if mesh is not None else None
    # quantize the stacked tf plane with the narrowest exact dtype over ALL
    # shards (one dtype per stacked array; the SPMD program widens in-scan)
    tf_layout = choose_tf_layout(blk_freqs.reshape(-1))
    blk_tf = blk_freqs.astype(_TF_DTYPE[tf_layout])
    return ShardedIndex(
        n_shards=S, doc_pad=doc_pad, nb_pad=nb_pad, fields=list(fields),
        blk_docs=put(blk_docs, spec),
        blk_tf=put(blk_tf, spec),
        tf_layout=tf_layout,
        norms=put(norms, spec),
        live=put(live, spec),
        shard_term_blocks=shard_term_blocks,
        shard_term_df=shard_term_df,
        max_doc=max_doc,
        sum_ttf=sum_ttf,
        mesh=mesh,
        searchers=list(searchers),
    )


_AGG_STACK_CACHE_MAX = 8  # distinct fields-tuples kept on device per generation


def ensure_mesh_agg_stack(index: ShardedIndex, fields: tuple):
    """Device [S, F, 5, Dpad] per-doc metric folds for `fields`, sharded along
    "shards" — or None when float32 does not hold a whole-number column's
    values or a sum a shard can form of them (device_index.agg_device_exact;
    serving falls back to the transport path, whose one-shard program adds
    integer limbs). Per-field host rows are computed once per packed
    generation; per-tuple device stacks are FIFO-bounded so rotating agg field
    sets can't grow device memory unboundedly."""
    import jax
    import jax.numpy as jnp

    stack = index.agg_stacks.get(fields)
    if stack is not None:
        return stack
    from ..ops.device_index import (_pad_agg_rows, agg_device_exact,
                                    agg_doc_rows)

    S = index.n_shards
    for f in fields:
        if f in index.agg_field_rows:
            continue
        # this program reduces in float32 (ROADMAP S13: the limbs have not
        # come to the mesh; no cell sends it an aggregation)
        if not all(agg_device_exact(searcher.segments, f, needs_values=True,
                                    f32_sums=True)
                   for searcher in index.searchers):
            index.agg_field_rows[f] = None
            continue
        host_f = np.zeros((S, 5, index.doc_pad), dtype=np.float32)
        host_f[:, 2] = np.inf
        host_f[:, 3] = -np.inf
        for si, searcher in enumerate(index.searchers):
            for seg, base in zip(searcher.segments, searcher.bases):
                _pad_agg_rows(agg_doc_rows(seg, f), index.doc_pad, base,
                              out=host_f[si])
        index.agg_field_rows[f] = host_f
    if any(index.agg_field_rows[f] is None for f in fields):
        return None
    host = np.stack([index.agg_field_rows[f] for f in fields], axis=1)
    if index.mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        stack = jax.device_put(host, NamedSharding(index.mesh, P("shards")))
    else:
        stack = jnp.asarray(host)
    while len(index.agg_stacks) >= _AGG_STACK_CACHE_MAX:
        index.agg_stacks.pop(next(iter(index.agg_stacks)))
    index.agg_stacks[fields] = stack
    return stack


# ---------------------------------------------------------------------------
# the SPMD program
# ---------------------------------------------------------------------------


def _mesh_score_program(k: int, n_queries: int, doc_pad: int, similarity_kind: int,
                        use_filter: bool = False, use_aggs: bool = False,
                        use_post: bool = False, use_min_score: bool = False,
                        use_sort: bool = False, sort_desc: bool = False,
                        use_active: bool = False, use_stack: bool = False,
                        bucket_specs: tuple = ()):
    """Returns the shard_map-able function (static shapes closed over).

    Term statistics never enter the program: each shard's slice of `weight_c`
    and `norm_cache` carries them, resolved on the host from index-wide
    (dfs_query_then_fetch) or shard-local (query_then_fetch) stats, so both
    search types run the SAME executable.
    use_filter adds per-shard FilteredQuery masks; use_aggs adds fused metric-agg
    stats (device_index.agg_doc_rows folds reduced under the match mask, gathered
    per shard — the SPMD embodiment of the reference's per-shard agg collect +
    coordinator reduce).

    Round-5 feature parity with the single-shard device path
    (service.execute_query_phase's device branches):
      use_post       — post_filter masks gate HITS and totals, never aggs
                       (ref: DefaultSearchContext.parsedPostFilter semantics)
      use_min_score  — score threshold applied to match BEFORE aggs (host
                       mask path order, service.py execute_query_phase)
      use_sort       — single-field sort: per-shard top-k over pre-folded key
                       rows, global merge by (key, shard, doc) — the SPMD form
                       of execute.launch_flat_sorted + the coordinator merge
      use_active     — shard-subset serving (routing/preference selected a
                       subset): inactive shards mask out of match entirely
      use_stack      — the agg_rows stack input is present (metric aggs and/or
                       bucket metric sub-aggs need per-doc folds)
      bucket_specs   — static per bucket agg: (n_buckets, sub_row_idx|None);
                       counts scatter exactly like ops.scoring._bucket_scatter
                       and ride all_gather back per shard
    """
    import jax
    import jax.numpy as jnp

    # device-side byte315 decode (common/smallfloat.py): norms stay 1 B/doc
    # into the program; this 1 KB table folds as a compile-time constant
    NORM_DECODE = jnp_norm_table()

    def program(blk_docs, blk_tf, norms, live,  # local shard slices [1, ...]
                qidx, blk, clause_id, fidx, group, tfmode,  # entries [1, M]
                weight_c, norm_cache,  # host-resolved stats [1, C], [1, F, 256]
                n_must, msm, coord,  # per query [Qd], [Qd], [Qd, C+1]
                *extra):  # optional inputs gated by the use_* flags, in order:
        # filter_masks [1, Qd, Dpad] | agg_rows [1, F, 5, Dpad] |
        # post_masks [1, Qd, Dpad] | min_score scalar | sort_keys [1, Dpad] |
        # active [1] bool | per bucket agg: pdoc [1, P], pbucket [1, P]
        ei = 0
        filter_masks = extra[ei] if use_filter else None
        ei += 1 if use_filter else 0
        agg_rows = extra[ei] if use_stack else None
        ei += 1 if use_stack else 0
        post_masks = extra[ei] if use_post else None
        ei += 1 if use_post else 0
        min_score = extra[ei] if use_min_score else None
        ei += 1 if use_min_score else 0
        sort_keys = extra[ei] if use_sort else None
        ei += 1 if use_sort else 0
        active = extra[ei] if use_active else None
        ei += 1 if use_active else 0
        bucket_pairs = []
        for _nb, _sub in bucket_specs:
            bucket_pairs.append((extra[ei], extra[ei + 1]))
            ei += 2
        blk_docs = blk_docs[0]
        blk_tf = blk_tf[0]
        norms_l = norms[0]
        live_l = live[0]
        qidx, blk, clause_id = qidx[0], blk[0], clause_id[0]
        fidx, group, tfmode = fidx[0], group[0], tfmode[0]
        # per-clause weight (idf x boost x (k1+1); TF-IDF: idf^2 x boost x
        # queryNorm; 0 where df == 0) and the per-field BM25 norm cache
        weight_c = weight_c[0]
        norm_cache = norm_cache[0]

        # ---- query phase: fused scoring (same pipeline as ops/scoring.py) ----
        docs = blk_docs[blk]  # [M, B]
        freqs = blk_tf[blk].astype(jnp.float32)  # quantized plane, widened in-scan
        valid = docs < doc_pad
        docs_safe = jnp.where(valid, docs, 0)
        nb = norms_l[fidx[:, None], docs_safe].astype(jnp.int32)
        w = weight_c[clause_id][:, None]  # [M, 1]
        # tf factor first, then weight — the rounding order every other scorer uses
        # (ops/device_index.tfn_values, HostScorer._term_scores)
        if similarity_kind == 0:
            cache_vals = norm_cache[fidx[:, None], nb]
            contrib = w * (freqs / (freqs + cache_vals))
        else:
            contrib = w * (jnp.sqrt(freqs) * NORM_DECODE[nb])
        scoring = (group[:, None] != GROUP_MUST_NOT) & valid
        contrib = jnp.where(scoring, contrib, 0.0)

        counters = (
            jnp.where(group == 0, 1, 0)
            + jnp.where(group == 1, 1 << _MUST_SHIFT, 0)
            + jnp.where(group == 2, 1 << _NOT_SHIFT, 0)
        ).astype(jnp.int32)
        counter_vals = jnp.where(valid, counters[:, None], 0)
        flat_idx = jnp.where(valid, qidx[:, None] * (doc_pad + 1) + docs_safe,
                             n_queries * (doc_pad + 1))
        scores = jnp.zeros(n_queries * (doc_pad + 1), jnp.float32).at[
            flat_idx.reshape(-1)].add(contrib.reshape(-1), mode="drop"
        ).reshape(n_queries, doc_pad + 1)[:, :doc_pad]
        counts = jnp.zeros(n_queries * (doc_pad + 1), jnp.int32).at[
            flat_idx.reshape(-1)].add(counter_vals.reshape(-1), mode="drop"
        ).reshape(n_queries, doc_pad + 1)[:, :doc_pad]

        m_should = counts & 0x3FF
        m_must = (counts >> _MUST_SHIFT) & 0x3FF
        m_not = counts >> _NOT_SHIFT
        match = (m_must == n_must[:, None]) & (m_should >= msm[:, None]) & (m_not == 0)
        match = match & ((m_should + m_must) > 0) & live_l[None, :]
        if filter_masks is not None:
            # FilteredQuery: the filter gates matching, never scoring (ref:
            # FilteredQuery's scorer — score comes from the wrapped query alone)
            match = match & filter_masks[0]

        # coord multiplies BEFORE min_score: the threshold sees the final score
        # (the fs-kernel semantics the single-shard min_score path uses)
        overlap = jnp.minimum(m_should + m_must, coord.shape[1] - 1)
        scores = scores * jnp.take_along_axis(coord, overlap, axis=1)

        if min_score is not None:
            # min_score prunes match itself — totals AND aggs see the pruned
            # set (host mask path order: service.execute_query_phase)
            match = match & (scores >= min_score)
        if active is not None:
            # shard-subset serving: an unselected shard contributes nothing —
            # no hits, no totals, no agg partials
            match = match & active[0]

        if use_aggs and agg_rows is not None:
            # fused metric aggs under the match mask (ops/scoring.agg_stat_reduction
            # — the SAME reduction the single-shard dense kernel runs); per-shard
            # partials gathered so serving synthesizes transport-identical
            # ShardQueryResult.agg_partials
            from ..ops.scoring import agg_stat_reduction

            local_counts, local_stats, _limbs = agg_stat_reduction(
                match, agg_rows[0])
            agg_counts = jax.lax.all_gather(local_counts, "shards")  # [S, Qd, F]
            agg_stats = jax.lax.all_gather(local_stats, "shards")  # [S, Qd, F, 4]

        bucket_outs = []
        if bucket_specs:
            # bucket aggs reduce over the PRE-post_filter match (the reference's
            # faceting idiom), per-shard results gathered so serving assembles
            # shard-level partials with each shard's own key list
            from ..ops.scoring import _bucket_scatter

            for (nb, sub_idx), (pdoc, pbucket) in zip(bucket_specs, bucket_pairs):
                # sub_idx is a static tuple; jnp.asarray keeps the row-select
                # a device gather instead of an f64 numpy constant built at
                # trace time (TPU001/TPU009)
                sub_stack = (agg_rows[0][jnp.asarray(sub_idx)]
                             if sub_idx else None)
                cnts, sub_cnt, sub_stats, _limbs = _bucket_scatter(
                    match, pdoc[0], pbucket[0], nb, sub_stack)
                out = [jax.lax.all_gather(cnts, "shards")]  # [S, Qd, nb]
                if sub_idx:
                    out.append(jax.lax.all_gather(sub_cnt, "shards"))
                    out.append(jax.lax.all_gather(sub_stats, "shards"))
                bucket_outs.append(out)

        # post_filter gates hits and totals only — aggs above saw full match
        hits_match = match & post_masks[0] if post_masks is not None else match

        neg_inf = jnp.float32(-jnp.inf)
        masked_scores = jnp.where(hits_match, scores, neg_inf)
        # per-shard max_score spans ALL post-filtered matches (host parity for
        # sorted searches, where winners' scores aren't the shard max)
        qmax = jax.lax.all_gather(jnp.max(masked_scores, axis=1), "shards")  # [S, Qd]
        shard_idx = jax.lax.axis_index("shards")

        if use_sort:
            sign = jnp.float32(1.0 if sort_desc else -1.0)
            sortable = jnp.where(hits_match, sort_keys[0][None, :] * sign, neg_inf)
            local_keys, local_docs = jax.lax.top_k(sortable, k)  # [Qd, k]
            local_scores = jnp.take_along_axis(masked_scores, local_docs, axis=1)
            finite = jnp.isfinite(local_keys)
        else:
            local_scores, local_docs = jax.lax.top_k(masked_scores, k)  # [Qd, k]
            local_keys = None
            finite = jnp.isfinite(local_scores)
        local_ids = jnp.where(finite, shard_idx * doc_pad + local_docs,
                              jnp.int32(-1))

        # ---- reduce phase: global top-k via all_gather (shard-major → Lucene
        # tie-break order); per-shard totals gathered so serving can synthesize
        # per-shard query results (ShardQueryResult) without a second pass ----
        def gather_major(x):  # [Qd, k] per shard → [Qd, S*k] shard-major
            g = jax.lax.all_gather(x, "shards")  # [S, Qd, k]
            return jnp.transpose(g, (1, 0, 2)).reshape(n_queries, -1)

        g_scores = gather_major(local_scores)
        g_ids = gather_major(local_ids)
        if use_sort:
            g_keys = gather_major(local_keys)
            top_sortable, pos = jax.lax.top_k(g_keys, k)
            top_keys = top_sortable * (jnp.float32(1.0) if sort_desc
                                       else jnp.float32(-1.0))
            top_scores = jnp.take_along_axis(g_scores, pos, axis=1)
        else:
            top_scores, pos = jax.lax.top_k(g_scores, k)
            top_keys = None
        top_ids = jnp.take_along_axis(g_ids, pos, axis=1)
        shard_totals = jax.lax.all_gather(
            hits_match.sum(axis=1).astype(jnp.int32), "shards")  # [S, Qd]

        outs = [top_scores[None], top_ids[None], shard_totals[None], qmax[None]]
        if use_sort:
            outs.append(top_keys[None])
        if use_aggs and agg_rows is not None:
            outs.append(agg_counts[None])
            outs.append(agg_stats[None])
        for out in bucket_outs:
            outs.extend(o[None] for o in out)
        return tuple(outs)

    return program


# ---------------------------------------------------------------------------
# the launch's operand plane: what changes between launches, in ONE transfer
# ---------------------------------------------------------------------------

_ENTRY_ROWS = 6  # qidx, blk, clause_id, fidx, group, tfmode


def _pack_plane(qidx, blk, clause_id, fidx, group, tfmode, weight_c,
                n_must, msm, coord) -> np.ndarray:
    """_assemble's ten operands as one int32 plane [S, L], a row a shard:
    the six entry arrays [S, M], the shard's clause weights [S, C], then the
    per-query plane of the one-shard launches (scoring._pack_qplane: n_must,
    msm and the coord row of each query, a few dozen bytes, the same in every
    shard's row). Floats go as their bits, so nothing is rounded.
    L = 6M + C + Qp(2 + W): the sizes that already decide the program."""
    from ..ops.scoring import _pack_qplane

    S, m = qidx.shape
    c = weight_c.shape[1]
    qplane = _pack_qplane(n_must, msm, coord).reshape(-1)
    plane = np.empty((S, _ENTRY_ROWS * m + c + qplane.size), np.int32)
    o = 0
    for a in (qidx, blk, clause_id, fidx, group, tfmode):
        plane[:, o:o + m] = a
        o += m
    plane[:, o:o + c] = np.ascontiguousarray(weight_c, np.float32).view(np.int32)
    plane[:, o + c:] = qplane
    return plane


def _unpack_plane(plane, m: int, n_queries: int, width: int):
    """_pack_plane's ten operands back from a plane [rows, L] inside a
    program, by static slices (C is what is left of L) and bit-casts: the
    entries and weights keep the plane's rows, the per-query values are read
    from its first row."""
    import jax
    import jax.numpy as jnp

    from ..ops.scoring import _unpack_qplane

    o = _ENTRY_ROWS * m
    c = plane.shape[1] - o - n_queries * (2 + width)
    entries = [plane[:, i * m:(i + 1) * m] for i in range(_ENTRY_ROWS)]
    weight_c = jax.lax.bitcast_convert_type(plane[:, o:o + c], jnp.float32)
    return (*entries, weight_c, *_unpack_qplane(
        plane[0, o + c:].reshape(n_queries, 2 + width)))


def _on_plane(program, m: int, n_queries: int, width: int):
    """`program` (_mesh_score_program's, untouched) behind the launch ABI
    (blk_docs, blk_tf, norms, live, norm_cache, plane, *extra): the resident
    index and norm cache, one operand plane, the variants' own large
    operands. The plain search's four outputs come back as one int32 plane
    (_unpack_outs is the host's side); a variant's further outputs follow."""
    import jax
    import jax.numpy as jnp

    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32).reshape(-1)

    def launch(blk_docs, blk_tf, norms, live, norm_cache, plane, *extra):
        ops = _unpack_plane(plane, m, n_queries, width)
        top_scores, top_ids, shard_totals, qmax, *more = program(
            blk_docs, blk_tf, norms, live, *ops[:7], norm_cache, *ops[7:],
            *extra)
        packed = jnp.concatenate([bits(top_scores), top_ids.reshape(-1),
                                  shard_totals.reshape(-1), bits(qmax)])
        return (packed, *more)

    return launch


def _unpack_outs(packed: np.ndarray, n_queries: int, k: int, n_shards: int):
    """(top_scores [Qp, k] f32, top_ids [Qp, k], shard_totals [S, Qp],
    qmax [S, Qp] f32) as views of _on_plane's packed output."""
    a, b = n_queries * k, n_shards * n_queries
    return (packed[:a].view(np.float32).reshape(n_queries, k),
            packed[a:2 * a].reshape(n_queries, k),
            packed[2 * a:2 * a + b].reshape(n_shards, n_queries),
            packed[2 * a + b:].view(np.float32).reshape(n_shards, n_queries))


@dataclass
class MeshTopDocs:
    scores: np.ndarray  # [Q, k]
    shard: np.ndarray  # [Q, k] (-1 = no hit)
    doc: np.ndarray  # [Q, k] local doc id within shard
    totals: np.ndarray  # [Q] — global matches (sum over shards)
    shard_totals: np.ndarray = None  # [S, Q] per-shard matches
    agg_counts: np.ndarray = None  # [S, Q, F] int per-shard matched value counts
    agg_stats: np.ndarray = None  # [S, Q, F, 4] per-shard (sum, min, max, sumsq)
    qmax: np.ndarray = None  # [S, Q] per-shard max score over matches (-inf none)
    sort_keys: np.ndarray = None  # [Q, k] winning sort keys (sorted searches)
    # per bucket agg: (counts [S, Q, NB], sub_cnt [S, Q, Fs, NB]|None,
    #                  sub_stats [S, Q, Fs, NB, 4]|None)
    bucket_results: list = None
    # the dispatch's stage / launch / device_pull intervals
    # (tracing.DispatchClock), set by the batcher's mesh family
    clock: object = None


class MeshSearchExecutor:
    """Executes flat query plans against a ShardedIndex on a device mesh.

    mesh axes: "shards" (index partition, required) and optionally "replicas"
    (query-batch data parallelism)."""

    def __init__(self, index: ShardedIndex, mesh, similarity="BM25",
                 k1: float = 1.2, b: float = 0.75, use_global_stats: bool = True,
                 compiled: dict | None = None):
        self.index = index
        self.mesh = mesh
        self.similarity_kind = 0 if str(similarity).upper() == "BM25" else 1
        self.k1, self.b = k1, b
        self.use_global_stats = use_global_stats
        # statistics are inputs, not program structure: executors over one
        # ShardedIndex that differ only in use_global_stats share executables
        self._compiled: dict = {} if compiled is None else compiled
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._by_shard = NamedSharding(mesh, P("shards"))
        self._replicated = NamedSharding(mesh, P())
        # a constant of the packed generation: it lives on the mesh beside
        # the index it belongs to, and no launch sends it down again
        self._norm_cache = jax.device_put(self._norm_caches(), self._by_shard)

    # -- host-side statistics (the DFS phase) -------------------------------
    def _norm_caches(self) -> np.ndarray:
        """[S, F, 256] f32 BM25 norm cache per shard and field, from index-wide
        or shard-local sumTTF/maxDoc — BM25Similarity.norm_cache itself, so a
        mesh score decodes norms with the single-shard path's table. Fixed for
        the life of the packed generation."""
        from types import SimpleNamespace

        idx = self.index
        out = np.ones((idx.n_shards, max(len(idx.fields), 1), 256), np.float32)
        if self.similarity_kind != 0:
            return out  # TF-IDF decodes norms with a constant table in-program
        sim = BM25Similarity(self.k1, self.b)
        max_doc, sum_ttf = idx.max_doc, idx.sum_ttf
        if self.use_global_stats:
            max_doc = np.full_like(max_doc, max_doc.sum())
            sum_ttf = np.broadcast_to(sum_ttf.sum(axis=0), sum_ttf.shape)
        for si, (n, ttfs) in enumerate(zip(max_doc.tolist(), sum_ttf.tolist())):
            for fi, ttf in enumerate(ttfs):
                out[si, fi] = sim.norm_cache(SimpleNamespace(sum_ttf=ttf), n)
        return out

    def _clause_weights(self, df_local: np.ndarray, boost: np.ndarray,
                        clause_qidx: np.ndarray, clause_scoring: np.ndarray,
                        n_queries: int) -> np.ndarray:
        """[S, C] f32 clause weights from index-wide (DFS: df and maxDoc summed
        over the shards) or shard-local statistics. idf is the similarity
        classes' own (f64 log, then f32 — Lucene's order), the product is f32
        in finalize_flat's order: what the single-shard kernel is fed."""
        max_doc = self.index.max_doc
        if self.use_global_stats:
            df = np.broadcast_to(df_local.sum(axis=0), df_local.shape)
            max_doc = np.full_like(max_doc, max_doc.sum())
        else:
            df = df_local
        idf_of = (BM25Similarity.idf if self.similarity_kind == 0
                  else TFIDFSimilarity.idf)
        idf = np.zeros(df.shape, np.float32)
        for si, (n, dfs) in enumerate(zip(max_doc.tolist(), df.tolist())):
            idf[si] = [idf_of(d, n) if d > 0 else 0.0 for d in dfs]
        if self.similarity_kind == 0:
            return idf * boost[None, :] * np.float32(self.k1 + 1.0)
        # TF-IDF: queryNorm spans a query's scoring clauses with df > 0
        w_unnorm = np.where(clause_scoring[None, :], idf * boost[None, :],
                            np.float32(0.0))
        ssw = np.zeros((df.shape[0], n_queries), np.float32)
        for si in range(df.shape[0]):
            np.add.at(ssw[si], clause_qidx, w_unnorm[si] * w_unnorm[si])
        qn = np.where(ssw > 0, np.float32(1.0) / np.sqrt(np.maximum(ssw, 1e-38)),
                      np.float32(1.0)).astype(np.float32)
        return idf * idf * boost[None, :] * qn[:, clause_qidx]

    # -- host-side batch assembly -------------------------------------------
    def _assemble(self, plans: list[FlatPlan]):
        """Global clause table + per-shard entry arrays."""
        idx = self.index
        clauses = []  # (qi, field, term, boost, group, mode)
        for qi, plan in enumerate(plans):
            for c in plan.clauses:
                mode = MODE_BM25 if self.similarity_kind == 0 else MODE_TFIDF
                clauses.append((qi, c.field, c.term, c.boost * plan.boost, c.group, mode))
        C = max(len(clauses), 1)
        boost = np.zeros(C, np.float32)
        clause_qidx = np.zeros(C, np.int32)
        clause_scoring = np.zeros(C, bool)
        fidx_c = np.zeros(C, np.int32)
        group_c = np.zeros(C, np.int32)
        df_local = np.zeros((idx.n_shards, C), np.int32)
        field_pos = {f: i for i, f in enumerate(idx.fields)}
        for ci, (qi, f, t, bst, grp, mode) in enumerate(clauses):
            boost[ci] = bst
            clause_qidx[ci] = qi
            clause_scoring[ci] = grp != GROUP_MUST_NOT
            fidx_c[ci] = field_pos.get(f, 0)
            group_c[ci] = grp
            for si in range(idx.n_shards):
                df_local[si, ci] = idx.shard_term_df[si].get((f, t), 0)
        # entries per shard, vectorized block expansion (clause block-RANGES expand to
        # per-block rows with repeat/cumsum — no Python loop over blocks)
        S = idx.n_shards
        per_shard = []
        for si in range(S):
            tb = idx.shard_term_blocks[si]
            rows = [(rng[0], rng[1], qi, ci, field_pos.get(f, 0), grp, mode)
                    for ci, (qi, f, t, bst, grp, mode) in enumerate(clauses)
                    if (rng := tb.get((f, t))) is not None]
            if not rows:
                per_shard.append(None)
                continue
            b0 = np.array([r[0] for r in rows], np.int64)
            counts = np.array([r[1] for r in rows], np.int64) - b0
            per_shard.append((
                np.repeat(np.array([r[2] for r in rows], np.int32), counts),  # qidx
                expand_ranges(b0, counts).astype(np.int32),  # blk
                np.repeat(np.array([r[3] for r in rows], np.int32), counts),  # clause
                np.repeat(np.array([r[4] for r in rows], np.int32), counts),  # fidx
                np.repeat(np.array([r[5] for r in rows], np.int32), counts),  # group
                np.repeat(np.array([r[6] for r in rows], np.int32), counts),  # mode
            ))
        M = _ladder_bucket("terms",
                           max(max((len(p[0]) for p in per_shard
                                    if p is not None), default=1), 1), 16)
        qidx = np.zeros((S, M), np.int32)
        blk = np.full((S, M), idx.nb_pad - 1, np.int32)
        clause_id = np.zeros((S, M), np.int32)
        fidx = np.zeros((S, M), np.int32)
        group = np.zeros((S, M), np.int32)
        tfmode = np.zeros((S, M), np.int32)
        for si, p in enumerate(per_shard):
            if p is None:
                continue
            n = len(p[0])
            qidx[si, :n], blk[si, :n], clause_id[si, :n] = p[0], p[1], p[2]
            fidx[si, :n], group[si, :n], tfmode[si, :n] = p[3], p[4], p[5]
        # per-query bool semantics — padded to the "q" ladder bucket so the
        # executable cache in search() keys on the bucket ladder, not raw
        # len(plans) (one compiled program per QUERY-COUNT BUCKET, not per
        # distinct batch size). Padding queries have zero clauses and zero
        # must/msm; their output rows are sliced off before MeshTopDocs.
        Q = len(plans)
        Qp = _ladder_bucket("q", Q, 1)
        n_scoring_max = max(
            (sum(1 for c in p.clauses if c.group != GROUP_MUST_NOT) for p in plans),
            default=1) or 1
        n_must = np.zeros(Qp, np.int32)
        msm = np.zeros(Qp, np.int32)
        coord = np.ones((Qp, n_scoring_max + 1), np.float32)
        for qi, p in enumerate(plans):
            n_must[qi] = p.n_must
            msm[qi] = p.msm
            n_sc = sum(1 for c in p.clauses if c.group != GROUP_MUST_NOT)
            if p.coord_enabled and self.similarity_kind == 1 and n_sc > 0:
                row = np.arange(n_scoring_max + 1, dtype=np.float32) / np.float32(n_sc)
                coord[qi] = np.minimum(row, 1.0)
                coord[qi, : n_sc + 1] = np.arange(n_sc + 1, dtype=np.float32) / np.float32(n_sc)
        weight_c = self._clause_weights(df_local, boost, clause_qidx,
                                        clause_scoring, Qp)
        return (qidx, blk, clause_id, fidx, group, tfmode, weight_c,
                n_must, msm, coord)

    def search(self, plans: list[FlatPlan], k: int,
               filter_masks: np.ndarray | None = None,
               agg_rows=None, use_metric_aggs: bool | None = None,
               post_masks: np.ndarray | None = None,
               min_score: float | None = None,
               sort_keys: np.ndarray | None = None, sort_desc: bool = False,
               active: np.ndarray | None = None,
               bucket_pairs: list | None = None) -> MeshTopDocs:
        """filter_masks: optional bool [S, Q, doc_pad] — per-shard, per-query
        FilteredQuery masks (host-evaluated via the filter cache, sharded onto the
        mesh; they gate matching, not scoring). agg_rows: optional [S, F, 5, Dpad]
        f32 per-doc metric folds (device_index.agg_doc_rows) — fused agg stats
        come back per shard in MeshTopDocs.agg_stats; the stack may carry extra
        rows used only by bucket metric sub-aggs (use_metric_aggs=False then
        skips the top-level stat outputs). post_masks: bool [S, Q, doc_pad]
        post_filter masks (hits/totals only). min_score: score threshold
        pre-aggs. sort_keys: f32 [S, doc_pad] single-field sort key rows
        (sorting.device_sort_key_row per segment, shard-rebased); sort_desc
        mirrors SortSpec.reverse. active: bool [S] shard-subset mask.
        bucket_pairs: per bucket agg (pdoc [S, P], pbucket [S, P], nb,
        sub_row_idx tuple|None) — results in MeshTopDocs.bucket_results."""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..ops.scoring import _launch, _named, _pull, _put_operands

        idx = self.index
        Q = len(plans)
        operands = self._assemble(plans)
        qidx, *_entries, n_must, _msm, coord = operands
        # the pow-2 query bucket _assemble padded to — the program and its
        # cache key are shaped by Qp, outputs slice back to the real Q below
        Qp = n_must.shape[0]
        if filter_masks is not None and filter_masks.shape[1] != Qp:
            filter_masks = np.pad(
                filter_masks, ((0, 0), (0, Qp - filter_masks.shape[1]), (0, 0)))
        if post_masks is not None and post_masks.shape[1] != Qp:
            post_masks = np.pad(
                post_masks, ((0, 0), (0, Qp - post_masks.shape[1]), (0, 0)))

        bucket_pairs = bucket_pairs or []
        has_filter = filter_masks is not None
        has_stack = agg_rows is not None
        # metric-agg outputs require the stack: normalizing here keeps the
        # program's emission guard (use_aggs AND stack) and the host-side
        # output popping in lockstep for every caller
        has_aggs = has_stack and (True if use_metric_aggs is None
                                  else use_metric_aggs)
        has_post = post_masks is not None
        has_min = min_score is not None
        has_sort = sort_keys is not None
        has_active = active is not None
        bucket_specs = tuple((int(nb), tuple(sub) if sub else None)
                             for (_pd, _pb, nb, sub) in bucket_pairs)
        M, W = qidx.shape[1], coord.shape[1]
        key = (Qp, k, M, W, has_filter, has_stack,
               has_aggs, has_post, has_min, has_sort, sort_desc, has_active,
               bucket_specs)
        # the variants' own large operands, in the program's order, each with
        # its placement; they ride the operand plane's one device_put
        by_shard, replicated = self._by_shard, self._replicated
        extras = []
        if has_filter:
            extras.append((filter_masks, by_shard))
        if has_stack:
            extras.append((agg_rows, by_shard))
        if has_post:
            extras.append((post_masks, by_shard))
        if has_min:
            extras.append((np.float32(min_score), replicated))
        if has_sort:
            extras.append((sort_keys, by_shard))
        if has_active:
            extras.append((active, by_shard))
        for (pd, pb, _nb, _sub) in bucket_pairs:
            extras.append((pd, by_shard))
            extras.append((pb, by_shard))
        fn = self._compiled.get(key)
        if fn is None:
            program = _mesh_score_program(k, Qp, idx.doc_pad, self.similarity_kind,
                                          use_filter=has_filter,
                                          use_aggs=has_aggs,
                                          use_post=has_post,
                                          use_min_score=has_min,
                                          use_sort=has_sort, sort_desc=sort_desc,
                                          use_active=has_active,
                                          use_stack=has_stack,
                                          bucket_specs=bucket_specs)
            n_out = 1 + (1 if has_sort else 0) + (2 if has_aggs else 0) \
                + sum(3 if sub else 1 for (_nb, sub) in bucket_specs)
            fn = shard_map(
                _on_plane(program, M, Qp, W), mesh=self.mesh,
                # index x4, norm cache, operand plane, then the extras
                in_specs=(P("shards"),) * 6 + tuple(
                    placed.spec for _a, placed in extras),
                out_specs=tuple(P() for _ in range(n_out)),
                check_vma=False,  # outputs here are P() by construction
            )
            # named like the scoring launch sites (ops/scoring._named): the
            # device trace reads `jit_estpu_mesh_search`, not `jit_<lambda>`
            fn = jax.jit(_named("mesh.search", fn))
            self._compiled[key] = fn
        # compile_tag: first sightings of a (Qp, shapes, feature-set) key trace
        # and compile HERE — attribute them to the "mesh" ledger family (the
        # same family the batcher's mesh launches carry)
        with compile_tag("mesh"):
            # ONE explicit transfer down, with the program's exact shardings
            # (transfer_guard("disallow") rejects an implicit one): the plane
            # and whatever the variant adds. The index and the norm cache are
            # on the mesh already and are passed as they are; so is a cached
            # agg stack, which device_put hands back untouched.
            plane, *placed = _put_operands(
                _pack_plane(*operands), *(a for a, _p in extras),
                shardings=(by_shard, *(p for _a, p in extras)))
            # ONE explicit pull for every program output. _launch / _pull
            # close the dispatch clock's stage, launch and device_pull
            outs = list(_pull(_launch(fn, (
                idx.blk_docs, idx.blk_tf, idx.norms, idx.live,
                self._norm_cache, plane, *placed))))
        top_scores, top_ids, shard_totals, qmax = _unpack_outs(
            outs.pop(0), Qp, k, idx.n_shards)
        # every per-query axis slices from the padded Qp back to the real Q
        top_scores, top_ids = top_scores[:Q], top_ids[:Q]
        shard_totals, qmax = shard_totals[:, :Q], qmax[:, :Q]  # [S, Q]
        out_sort_keys = outs.pop(0)[0][:Q] if has_sort else None
        agg_counts = agg_stats = None
        if has_aggs:
            agg_counts = outs.pop(0)[0][:, :Q]  # [S, Q, F]
            agg_stats = outs.pop(0)[0][:, :Q]  # [S, Q, F, 4]
        bucket_results = []
        for (_nb, sub) in bucket_specs:
            cnts = outs.pop(0)[0][:, :Q]  # [S, Q, NB]
            sc = ss = None
            if sub:
                sc = outs.pop(0)[0][:, :Q]  # [S, Q, Fs, NB]
                ss = outs.pop(0)[0][:, :Q]  # [S, Q, Fs, NB, 4]
            bucket_results.append((cnts, sc, ss))
        valid_rank = np.isfinite(out_sort_keys if has_sort else top_scores)
        shard = np.where((top_ids >= 0) & valid_rank, top_ids // idx.doc_pad, -1)
        doc = np.where(shard >= 0, top_ids % idx.doc_pad, -1)
        return MeshTopDocs(scores=top_scores, shard=shard, doc=doc,
                           totals=shard_totals.sum(axis=0).astype(np.int64),
                           shard_totals=shard_totals, agg_counts=agg_counts,
                           agg_stats=agg_stats, qmax=qmax,
                           sort_keys=out_sort_keys,
                           bucket_results=bucket_results)
