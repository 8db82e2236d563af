"""Device-resident packed postings — quantized layout.

This is the TPU replacement for Lucene's on-heap postings traversal (SURVEY.md §2.8:
"device-resident packed postings blocks, vmapped BM25 scoring, lax.top_k"), playing the
role of Lucene's packed postings codecs (PAPER.md §0): the resident form is quantized,
not raw floats. A frozen segment's CSR postings are re-blocked into fixed-shape device
tensors:

    blk_docs : int32 [NB, B]      — local doc ids, padded with `doc_pad` (out of range)
    blk_tf   : uint8/int16 [NB, B] — term frequencies, quantized (raw tf is a
               small integer; segments whose tf overflows the int ladder take the
               float32 escape hatch — see choose_tf_layout)
    blk_nb   : uint8 [NB, B]      — the posting's doc norm byte for the block's
               owning field (Lucene's byte315 encoding, decoded IN the scan via a
               256-entry similarity LUT — common/smallfloat.py)

6 B/posting resident in the common uint8 layout (docs 4 + tf 1 + nb 1), down from the
12 B/posting of the former f32 (freqs + baked-tfn) planes. The dense-fallback kernels
still want an f32 freqs plane; it is NOT packed — `ensure_blk_freqs` uploads it lazily
from the host copy the first time a segment actually feeds the dense path
(ARCHITECTURE.md "HBM budget": the `blk_freqs`-drop rule).

Each term owns a contiguous run of blocks (`term_blk_start[t] .. term_blk_start[t+1]`),
so a query term's postings are a static-shape slice of block indices — the host builds
flat (query, block, weight) triples and the scoring kernel is pure gather + decode +
FMA + scatter-add, no data-dependent shapes (XLA-friendly by construction).

Shapes are padded to power-of-two buckets (NB rows, D docs) so recompilation stops once
the shape buckets stabilize — segment churn from NRT refresh reuses cached executables.

Norm bytes stay uint8 on device; similarity-specific 256-entry decode tables
(ensure_sim_tables) are gathered at score time, preserving Lucene's exact 1-byte
quantization.

Head terms also live as rows over documents:

    head_rows : uint8/int16 [Hpad, Dpad] — one row of term frequencies per term
               that matches Dpad / HEAD_DF_SHARE documents of the segment or
               more, in the tf plane's dtype; rows from H on are zero

For such a term the postings list is the wrong form on this chip: the dense
programs add the row to their [Q, Dpad] accumulator in one elementwise pass,
where they paid a serial gather and scatter for each posting. The plane is
faulted in with the dense f32 plane (`ensure_head_rows`); the term → row map
is host arithmetic at pack time. The postings of a head term stay where they
are: the sparse program and the mesh packer read them.

Positions live beside the postings, a plane a field (`PositionsPlane`):

    keys : int32 [NPBpad, B] — every occurrence of every term of the field as
               doc << pos_bits | position, a term's occurrences contiguous and
               ascending (document, then position); after a document's last
               occurrence of the term one MARKER, doc << pos_bits |
               (mark_base + code << 4), which sorts behind every position
               of the document; its code is the document's norm byte, or
               POS_DEAD_CODE for a deleted document (no byte: it matches
               nothing); blocked to the lane width as the postings are,
               padded with POS_SENTINEL

The exact-phrase program (ops/scoring.py, "exact phrases") merges the lists
of a phrase's terms, each moved up by its place in the phrase: n equal keys
are one occurrence of the phrase, and the last key of a document that holds
the phrase is a marker, so the norm byte needs no gather. The plane is
faulted in by the first phrase a segment's field meets (`ensure_positions`),
from the host's own `FrozenSegment.positions`; no pack builds it, so an index
that is never sent a phrase holds none. Non-parent documents are left out at
the fault; DELETED documents stay in, and the plane takes a tombstone as the
postings do: `_perform_pack`'s remask writes the dead code into the deleted
documents' markers on the raw host copy (`host_keys`, as `host_docs` is the
postings') and puts the plane again, with no second pass over the segment. A
merged or a delta segment starts without one.

Beside the keys the host keeps each block row's first and last document
(`blk_first`, `blk_last`): a phrase can only occur in the documents of its
rarest term, so a launch names, of every term's rows, those whose document
range holds one of them (`docs_below`, `PositionsPlane.rows_holding`) and the
program gathers that list. A tombstone changes a marker's code, never its document:
a re-mask leaves the bounds alone.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field as dc_field, replace as dc_replace

import numpy as np

from ..common import profile as _profile
from ..common.breaker import reserve
from ..common.devicehealth import tag_domain as _tag_domain
from ..common.errors import CircuitBreakingError
from ..common.jaxenv import pool_label
from ..index.segment import FrozenSegment
from ..transport.faults import DEVICE_FAULTS as _DEVICE_FAULTS

BLOCK = 128  # lane width


def _pow2_bucket(n: int, minimum: int = 128) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _ladder_bucket(dim: str, n: int, minimum: int) -> int:
    """Autotuned bucket ladder (common/compilecache.LADDERS): records n into
    the dimension's shape histogram and returns its committed rung, with the
    exact `_pow2_bucket` as the cold fallback — bit-identical to the fixed
    pow-2 ladder until a warm-cycle autotune commits a fitted one. Every
    shape-relevant bucket site routes through here (or _pow2_bucket): the
    compile-surface lattice (tools/tpulint TPU018+) classifies both as
    `bucketed`."""
    from ..common.compilecache import LADDERS

    return LADDERS.bucket(dim, n, minimum)


def expand_ranges(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Flatten half-open ranges [starts[i], starts[i]+counts[i]) into one int64 array
    — the CSR expansion idiom (repeat + within-range offset) shared by segment
    packing and the mesh assembler."""
    total = int(counts.sum())
    excl = np.zeros(len(counts), dtype=np.int64)
    if len(counts) > 1:
        np.cumsum(counts[:-1], out=excl[1:])
    within = np.arange(total, dtype=np.int64) - np.repeat(excl, counts)
    return np.repeat(np.asarray(starts, dtype=np.int64), counts) + within


# tf-plane layout ladder: uint8 covers real-text term frequencies (tf ≤ 255 for
# essentially every (term, doc)); int16 is the overflow rung; float32 the escape
# hatch for non-integral or >2^15-1 frequencies (synthetic corpora, index-time
# boost folding). One dtype per segment plane — the decode in the scan is a
# plain astype either way.
TF_U8, TF_I16, TF_F32 = "u8", "i16", "f32"
_TF_DTYPE = {TF_U8: np.uint8, TF_I16: np.int16, TF_F32: np.float32}


def choose_tf_layout(post_freqs: np.ndarray) -> str:
    """Pick the narrowest exact tf-plane dtype for a segment's raw frequencies.

    Allocation-light on purpose — this runs inside pack_estimate_bytes, i.e.
    BEFORE the breaker reservation: max() allocates nothing, and the
    integrality scan works in bounded chunks (≤ 4 MB of temporaries) instead
    of materializing floor/compare arrays over all postings at once."""
    if len(post_freqs) == 0:
        return TF_U8
    mx = float(post_freqs.max())
    if mx > 32767:
        return TF_F32
    if post_freqs.dtype.kind not in "iu":
        chunk = 1 << 20
        for i in range(0, len(post_freqs), chunk):
            c = post_freqs[i: i + chunk]
            if not np.all(c == np.floor(c)):
                return TF_F32
    return TF_U8 if mx <= 255 else TF_I16


def tf_plane_itemsize(layout: str) -> int:
    return np.dtype(_TF_DTYPE[layout]).itemsize


def tf_plane_integral(post_freqs: np.ndarray, layout: str) -> bool:
    """Whether a segment's raw tf values are all integers. u8/i16 rungs are
    integral by construction; the f32 escape covers both huge-but-integral
    tf (> 2^15-1) and genuinely fractional tf (index-time boost folding) —
    only the former is exactly reconstructible from positions, which is
    what gates the compaction concat path (merge_segments rebuilds freq as
    the position count). Chunked like choose_tf_layout's scan."""
    if layout != TF_F32:
        return True
    chunk = 1 << 20
    for i in range(0, len(post_freqs), chunk):
        c = post_freqs[i: i + chunk]
        if not np.all(c == np.floor(c)):
            return False
    return True


# A term has a row in `head_rows` when df * HEAD_DF_SHARE >= doc_pad. Chosen on
# the chip between 6 (a u8 row is then never larger than the postings it
# shadows: doc_pad B against df x 6 B) and 32; PERF.md section 6, PR 28, has
# the measurement that chose it.
HEAD_DF_SHARE = 16


def head_terms(post_offsets: np.ndarray, doc_pad: int, tf_layout: str) -> dict:
    """term id -> row of `head_rows`, rows in term order: the terms whose df
    reaches doc_pad / HEAD_DF_SHARE. Empty on the TF_F32 escape layout, whose
    segments run the dense programs on postings alone. Host arithmetic over
    the CSR offsets; ensure_head_rows builds the plane these rows index."""
    if tf_layout == TF_F32:
        return {}
    df = np.diff(post_offsets)
    tids = np.nonzero(df * HEAD_DF_SHARE >= doc_pad)[0]
    return {int(t): i for i, t in enumerate(tids.tolist())}


@dataclass
class SimTables:
    """Stacked per-field similarity decode state for the quantized sparse scan:
    one 256-entry f32 cache row + TFN_* mode per field. Replaces the old
    per-posting baked-tfn plane — a table swap on avgdl drift costs 1 KB/field
    instead of a full postings re-bake + HBM upload."""

    fields: list  # field order = fid
    fid: dict  # field -> row index
    modes: object  # jnp int32 [F]
    caches: object  # jnp float32 [F, 256]
    key: dict  # field -> (mode, cache bytes) — staleness fingerprint


@dataclass
class PackedSegment:
    """Device tensors + host lookup tables for one frozen segment."""

    gen: int
    doc_count: int  # real docs
    doc_pad: int  # padded D (bucketed)
    blk_docs: object  # jnp int32 [NBpad, B] — dead/non-parent docs masked to doc_pad
    term_blk_start: np.ndarray  # host int64 [T+1]
    live_parent: object  # jnp bool [Dpad] — live & parent (searchable docs)
    norm_bytes: dict  # field -> jnp uint8 [Dpad]
    dv_single: dict = dc_field(default_factory=dict)  # field -> jnp float32/float64 [Dpad] single-valued fast path (NaN missing)
    live_version: int = 0
    # quantized sparse-path planes (the resident layout — see module docstring):
    # tf decoded + normalized INSIDE the scan via the SimTables LUT, so no
    # second f32 plane and no per-(field, similarity) re-bake
    blk_tf: object = None  # jnp uint8/int16/float32 [NBpad, B]
    blk_nb: object = None  # jnp uint8 [NBpad, B] — per-posting norm byte
    tf_layout: str = TF_U8  # TF_U8 | TF_I16 | TF_F32
    # raw tf values are all integers (always true for the u8/i16 rungs; the
    # f32 escape scans once at pack time). Merge compaction may device-concat
    # source planes ONLY when every source is integral — merge_segments
    # rebuilds freq as the position count, so fractional tf would diverge
    tf_integral: bool = True
    sim: SimTables | None = None  # ensure_sim_tables state
    # dense-fallback plane, uploaded LAZILY (ensure_blk_freqs): most segments
    # only ever serve the sparse path and never pay these 4 B/posting
    blk_freqs: object = None  # jnp float32 [NBpad, B] or None until dense use
    # head terms as rows over documents (module docstring): the host map is
    # pack-time arithmetic, the plane is faulted in with blk_freqs
    head_row_of: dict = dc_field(default_factory=dict)  # term id -> row
    head_rows: object = None  # jnp tf dtype [Hpad, Dpad] or None until dense use
    # device metric-agg state: per-doc (count, sum, min, max, sumsq) rows per
    # numeric field, exact for MULTI-valued columns because the per-doc folds
    # happen host-side at build time (ops/scoring.score_agg_batch_async reduces them
    # under the match mask — SURVEY §5.7 "shard-level parallel reduce")
    agg_rows: dict = dc_field(default_factory=dict)  # field -> HOST (f32 [5, Dpad], int32 limbs [L, Dpad] | None)
    agg_stacks: dict = dc_field(default_factory=dict)  # fields-tuple -> AggStack (device rows + limbs), FIFO-bounded
    bucket_cols: dict = dc_field(default_factory=dict)  # bucket-agg cache key -> device (pair_doc, pair_bucket, zeros[NB])
    # field-sort key rows (execute._sort_key_row), FIFO-bounded:
    # (field, mode, order, missing) -> device f32 [Dpad], the column's values
    # where float32 holds them and their dense ranks where it does not
    sort_rows: dict = dc_field(default_factory=dict)
    # the dense launches' per-document table (scoring._doc_table), FIFO-bounded:
    # fields-tuple -> (host caches f32 [F, 256], device norms_stack u8 [F, Dpad],
    # device table f32 [F, Dpad]) — a warmed launch restacks and remakes neither
    dense_tables: dict = dc_field(default_factory=dict)
    # reusable [Qb, TB] staging arrays for the sparse planner (scoring.
    # SparseScratchPool, lazily created) — the per-bucket padding scratch lives
    # WITH the segment cache so warmed repeat batches re-pad in place instead
    # of re-materializing four arrays per bucket per launch
    sparse_scratch: object = None
    # host copies for re-bakes (live-mask refresh / similarity-stats drift)
    host_docs: np.ndarray | None = None  # int32 [NBpad*B] RAW (unmasked) doc ids
    host_freqs: np.ndarray | None = None  # float32 [NBpad*B]
    blk_field: np.ndarray | None = None  # int32 [NBpad] field ordinal per block (-1 pad)
    field_names: list = dc_field(default_factory=list)  # ordinal -> field name
    # field -> PositionsPlane, faulted in by the first phrase on the field
    # (ensure_positions); re-masked with the postings when the live mask moves
    positions: dict = dc_field(default_factory=dict)

    def blocks_for_term(self, tid: int) -> tuple[int, int]:
        return int(self.term_blk_start[tid]), int(self.term_blk_start[tid + 1])


POS_SENTINEL = np.int32(2**31 - 1)  # pads a term's last block; sorts last
# a marker's position field is mark_base + (norm byte << POS_MARK_SHIFT): the
# low bits stay free for the place in the phrase a launch adds to every key
POS_MARK_SHIFT = 4
POS_MAX_SHIFT = (1 << POS_MARK_SHIFT) - 1
POS_DEAD_CODE = 256  # a deleted document's marker: past every norm byte


@dataclass
class PositionsPlane:
    """One field's occurrences on the device (module docstring), or the
    record that they do not fit (`keys` None: the host answers its phrases).
    `blk_start[t] .. blk_start[t + 1]` are term t's block rows (no row for a
    term of another field); row NPBpad - 1 is all POS_SENTINEL, the row a
    launch's padding slots name. `blk_first` / `blk_last` are the documents
    of a row's first and last key (a row with no key: last -1)."""

    pos_bits: int  # key = doc << pos_bits | position
    pos_max: int  # the largest position the plane holds
    blk_start: np.ndarray | None = None  # host int64 [T+1]
    keys: object = None  # jnp int32 [NPBpad, B], the view's dead markers set
    host_keys: np.ndarray | None = None  # int32 [NPBpad, B], no document dead
    blk_first: np.ndarray | None = None  # host int32 [NPBpad]
    blk_last: np.ndarray | None = None  # host int32 [NPBpad]

    @property
    def mark_base(self) -> int:
        return positions_mark_base(self.pos_bits)

    def room_for(self, shift: int) -> bool:
        """Whether every position moved up by `shift` still lies under the
        markers, and the shift inside a marker's free bits: the narrow
        layout's range (a phrase it cannot hold goes to the host, never to a
        wrapped key)."""
        return self.keys is not None and 0 <= shift <= POS_MAX_SHIFT \
            and self.pos_max + shift < self.mark_base

    def blocks_for_term(self, tid: int) -> tuple[int, int]:
        return int(self.blk_start[tid]), int(self.blk_start[tid + 1])

    def rows_holding(self, tid: int, below: np.ndarray) -> np.ndarray:
        """Term `tid`'s block rows, ascending, whose [first, last] document
        holds a candidate document, where `below` (docs_below) counts the
        candidates under each document: every row with a key of a candidate,
        both bounds inclusive, so a document that straddles rows keeps them
        all. Two reads of the count a row, whatever the candidates number."""
        b0, b1 = self.blocks_for_term(tid)
        held = below[self.blk_last[b0:b1] + 1] > below[self.blk_first[b0:b1]]
        return (b0 + np.flatnonzero(held)).astype(np.int32)

    def rows_between(self, tid: int, lo: int, hi: int) -> int:
        """How many of term `tid`'s block rows hold a key of a document in
        [lo, hi): the term's whole list inside a tile's documents (all of
        its rows over every document)."""
        b0, b1 = self.blocks_for_term(tid)
        return int(np.count_nonzero((self.blk_last[b0:b1] >= lo)
                                    & (self.blk_first[b0:b1] < hi)))


def docs_below(docs: np.ndarray, n_docs: int) -> np.ndarray:
    """int32 [n_docs + 1]: how many of `docs` (document ids under `n_docs`)
    lie below each document, so that `below[b + 1] - below[a]` of them lie in
    [a, b] (PositionsPlane.rows_holding asks that of every row)."""
    below = np.zeros(n_docs + 1, np.int32)
    below[docs + 1] = 1
    return np.cumsum(below, out=below)


def positions_mark_base(pos_bits: int) -> int:
    """The first position field a marker uses: 256 norm bytes and the dead
    code, 1 << POS_MARK_SHIFT keys each, end a step under the field's top,
    which only POS_SENTINEL reaches."""
    return (1 << pos_bits) - ((POS_DEAD_CODE + 2) << POS_MARK_SHIFT)


def masked_positions(plane: PositionsPlane, live: np.ndarray):
    """`plane.host_keys` with the dead code in the marker of every document
    `live` (bool, a document each) does not hold, as a device array: what a
    view with tombstones launches over. One pass over the keys and one put;
    the lists stay ascending (a marker keeps its document and its place
    behind the document's positions)."""
    import jax.numpy as jnp

    keys = plane.host_keys
    if live.all():
        return jnp.asarray(keys)
    pos_mask = (1 << plane.pos_bits) - 1
    dead = (~live)[np.minimum(keys >> plane.pos_bits, len(live) - 1)] \
        & ((keys & pos_mask) >= plane.mark_base) & (keys != POS_SENTINEL)
    code = np.int32(plane.mark_base + (POS_DEAD_CODE << POS_MARK_SHIFT))
    return jnp.asarray(np.where(dead, (keys & ~np.int32(pos_mask)) | code,
                                keys))


# Meta fields whose every term is one document. Blocks never span terms, so
# packed they would take a 128-slot block a document each (two thirds of a
# log index's planes, and what refused a segment of a million documents):
# their postings stay on the host, where such a term is a dictionary lookup,
# and execute.lower_flat sends a clause on one to the host scorer.
HOST_ONLY_FIELDS = ("_id", "_uid")


def device_counts(seg: FrozenSegment) -> np.ndarray:
    """Postings per term as the device planes hold them: the segment's own
    counts, with the terms of HOST_ONLY_FIELDS at 0 (no block, no slot)."""
    counts = np.diff(seg.post_offsets)
    for f in HOST_ONLY_FIELDS:
        td = seg.term_dict.get(f)
        if td:
            counts[np.fromiter(td.values(), dtype=np.int64, count=len(td))] = 0
    return counts


def _device_postings(seg: FrozenSegment, counts: np.ndarray):
    """(post_docs, post_freqs) of the terms `counts` keeps, in CSR order."""
    full = np.diff(seg.post_offsets)
    if np.array_equal(full, counts):
        return seg.post_docs, seg.post_freqs
    keep = np.repeat(counts > 0, full)
    return seg.post_docs[keep], seg.post_freqs[keep]


def pack_shape_math(seg: FrozenSegment) -> tuple[int, int, str]:
    """(NBpad, Dpad, tf_layout) — the one shape+layout derivation shared by
    pack_estimate_bytes and pack_segment, so the breaker estimate can never
    drift from what the pack actually allocates. Memoized on the segment's
    device cache: the estimate→pack sequence (packed_for) derives it once,
    not once per caller (the layout scan is O(postings)). The positions
    planes are no part of it: no pack allocates them (ensure_positions
    reserves its own bytes), so neither pack estimate counts them."""
    cache = getattr(seg, "_device_cache", None)
    if cache is not None:
        sm = cache.get("shape_math")
        if sm is not None:
            return sm
    counts = device_counts(seg)
    nblks = (counts + BLOCK - 1) // BLOCK
    NBpad = _ladder_bucket("nb", int(nblks.sum()) + 1, 64)
    Dpad = _ladder_bucket("docs", max(seg.doc_count, 1), 128)
    sm = (NBpad, Dpad, choose_tf_layout(seg.post_freqs))
    if cache is not None:
        cache["shape_math"] = sm
    return sm


# pack-time host transients per slot, beyond the retained/uploaded planes:
# the live-masked doc-id np.where result (4 B) plus the fid_per_slot ordinal
# expansion (4 B) and the boolean gather/select masks (~4 B across real/sel
# temps) — freed by the end of the pack but live at its allocation peak,
# which is what the breaker reservation must cover
PACK_TRANSIENT_SLOT_BYTES = 12


def pack_estimate_bytes(seg: FrozenSegment) -> int:
    """Host-staging + device-upload bytes pack_segment will allocate — the
    estimate the fielddata breaker checks BEFORE the first np.full. Derived
    from the same shape+layout math as the pack itself (pack_shape_math):
    docs i32 and freqs f32 are staged host-side (kept for live-mask re-masks
    and the lazy dense plane); the DEVICE copy is the quantized layout —
    docs i32 + tf (u8/i16/f32 per choose_tf_layout) + norm byte u8 — plus the
    quantize/nb staging, a PACK_TRANSIENT_SLOT_BYTES allowance for the
    masking/ordinal temps live at the pack's peak, and the Dpad-wide
    masks/columns. The lazy dense plane is NOT in here — ensure_blk_freqs
    reserves it at its own allocation site."""
    NBpad, Dpad, layout = pack_shape_math(seg)
    tf_b = tf_plane_itemsize(layout)
    n_norm_fields = len(seg.norms)
    n_dv = len(seg.dv_num)
    # host staging: docs i32 + freqs f32 + tf + nb;  device: docs i32 + tf + nb
    per_slot = (4 + 4 + tf_b + 1) + (4 + tf_b + 1) + PACK_TRANSIENT_SLOT_BYTES
    # + live mask (host + device) + norms u8 + single-valued dv f64 columns
    return (NBpad * BLOCK * per_slot + Dpad * 2
            + Dpad * n_norm_fields + Dpad * 8 * n_dv)


def packed_resident_bytes(packed: PackedSegment) -> int:
    """Actual device-RESIDENT postings-plane bytes of a packed segment (docs +
    tf + nb, plus the dense f32 plane and the head rows if they have been
    faulted in) — what the breaker-estimate test compares against."""
    total = 0
    for plane in (packed.blk_docs, packed.blk_tf, packed.blk_nb,
                  packed.blk_freqs, packed.head_rows):
        if plane is not None:
            total += int(np.prod(plane.shape)) * np.dtype(plane.dtype).itemsize
    return total


def _plane_bytes(plane) -> int:
    return 0 if plane is None else \
        int(np.prod(plane.shape)) * np.dtype(plane.dtype).itemsize


def packed_tier_bytes(packed: PackedSegment) -> dict:
    """Device-resident bytes of one packed segment broken down by TIER — the
    device capacity ledger's taxonomy (ARCHITECTURE.md "Observability"):

      postings     the quantized sparse planes (blk_docs i32 + blk_tf + blk_nb)
      dense_plane  the lazily-faulted f32 freqs plane and the head-term rows
                   (0 until dense use)
      positions_plane  the fields' position planes, i32 keys
                   (0 until a phrase meets the segment)
      sim_tables   the stacked per-field similarity LUTs (modes + caches)
      agg_rows     FIFO-bounded device metric-agg stacks (float32 folds)
      agg_limbs    their integer limb rows (exact sums of whole-number columns)
      sort_keys    FIFO-bounded field-sort key rows (values or exact ranks)
      norms        per-field norm-byte columns + live mask + dv columns

    Pure host arithmetic over already-known shapes — no device sync, no
    packing side effects. `filter_masks` and `function_rows` are accounted
    separately (their holders live on the segment, not the PackedSegment —
    see capacity walk callers)."""
    postings = (_plane_bytes(packed.blk_docs) + _plane_bytes(packed.blk_tf)
                + _plane_bytes(packed.blk_nb))
    sim = 0
    if packed.sim is not None:
        sim = _plane_bytes(packed.sim.caches) + _plane_bytes(packed.sim.modes)
    stacks = list(packed.agg_stacks.values())
    norms = _plane_bytes(packed.live_parent)
    for col in packed.norm_bytes.values():
        norms += _plane_bytes(col)
    for col in packed.dv_single.values():
        norms += _plane_bytes(col)
    for (_host, norms_stack, table) in list(packed.dense_tables.values()):
        norms += _plane_bytes(norms_stack) + _plane_bytes(table)
    return {
        "postings": postings,
        "dense_plane": (_plane_bytes(packed.blk_freqs)
                        + _plane_bytes(packed.head_rows)),
        "positions_plane": sum(
            _plane_bytes(plane.keys)
            for plane in list(packed.positions.values())),
        "sim_tables": sim,
        "agg_rows": sum(_plane_bytes(stack.rows) for stack in stacks),
        "agg_limbs": sum(_plane_bytes(stack.limbs) for stack in stacks),
        "sort_keys": sum(_plane_bytes(row)
                         for row in list(packed.sort_rows.values())),
        "norms": norms,
    }


# ledger kind= vocabulary: "pack" (initial/full pack), "delta_pack" (a
# refresh-frozen increment — bounded by the buffer, not the index),
# "remask" (tombstone-driven live-mask refresh), "compact" (a merged
# segment's pack, device-concat from the sources' resident planes when
# eligible, host-staged otherwise — the event's method= field says which)
PACK_KINDS = ("pack", "delta_pack", "remask", "compact")
_KIND_COUNTER = {"pack": "packs", "delta_pack": "delta_packs",
                 "remask": "remasks", "compact": "compacts"}


class PackLedger:
    """Process-wide pack/repack timing ledger, keyed by index.

    `packed_for` records every segment pack (delta pack, compaction pack,
    and live-mask remask) here with its wall time, resident bytes, tf
    layout, the PACK_KINDS kind, the threadpool that did the work (pool=
    "warmer"/"merge"/"search"/"other" — the query-path-vs-background
    attribution the writes acceptance pins), and for compaction packs the
    method ("concat" = device-side plane concat, "staged" = host re-stage).
    The capacity report joins these against the live per-segment tier walk.
    Process-wide like search/service.SERVING_COUNTERS (in-process test
    clusters share it); bounded: at most MAX_INDICES index entries (LRU)
    each holding cumulative counters + a RING of recent events. `_lock` is
    a LEAF (dict mutation only) and recording happens on the already-cold
    pack path — the warmed serving loop never touches it."""

    MAX_INDICES = 256
    RING = 16

    def __init__(self):
        self._lock = threading.Lock()
        self._by_index: "OrderedDict[str, dict]" = OrderedDict()
        self._in_flight = 0  # packs between begin() and end()
        self._last_end: float | None = None
        self._pending = None  # the newest pack's device output, till ready

    def begin(self) -> None:
        with self._lock:
            self._in_flight += 1

    def end(self, device_result=None) -> None:
        """The host's part of a pack is over. `device_result` is an array the
        pack's device program writes (a compaction's concat runs on for a
        minute and more after the host has enqueued it): the pack stays in
        flight until it is ready, which idle_s() asks without waiting."""
        with self._lock:
            self._in_flight -= 1
            self._last_end = time.monotonic()
            if device_result is not None:
                self._pending = device_result

    def idle_s(self) -> float | None:
        """Seconds since this process last finished a pack, 0 while one is in
        flight on the host or still running on the device, None where it never
        packed. A search that waits for a merged segment's pack waits on a
        node that is busy, not wedged (the coordinator's attempt timer asks,
        actions.A_QUERY_PROGRESS)."""
        with self._lock:
            pending = self._pending
            if self._in_flight:
                return 0.0
        if pending is not None:
            if not pending.is_ready():
                return 0.0
            with self._lock:
                if self._pending is pending:
                    self._pending = None
                    self._last_end = time.monotonic()
        with self._lock:
            return None if self._last_end is None \
                else time.monotonic() - self._last_end

    def record(self, index: str | None, gen: int, ms: float, nbytes: int,
               layout: str, kind: str = "pack", pool: str | None = None,
               method: str | None = None) -> None:
        index = index or "_unattributed"
        pool = pool or pool_label()
        with self._lock:
            entry = self._by_index.get(index)
            if entry is None:
                entry = {"packs": 0, "delta_packs": 0, "remasks": 0,
                         "compacts": 0, "pack_ms_total": 0.0,
                         "pools": {}, "recent": []}
                self._by_index[index] = entry
                while len(self._by_index) > self.MAX_INDICES:
                    self._by_index.popitem(last=False)
            else:
                self._by_index.move_to_end(index)
            entry[_KIND_COUNTER.get(kind, "packs")] += 1
            entry["pack_ms_total"] += ms
            pools = entry["pools"]
            pools[pool] = pools.get(pool, 0) + 1
            recent = entry["recent"]
            event = {"kind": kind, "generation": int(gen),
                     "ms": round(ms, 3), "bytes": int(nbytes),
                     "tf_layout": layout, "pool": pool}
            if method is not None:
                event["method"] = method
            recent.append(event)
            if len(recent) > self.RING:
                del recent[: len(recent) - self.RING]

    def forget(self, index: str) -> None:
        """An index deleted from the cluster releases its ledger entry —
        label cardinality tracks LIVE indices, not history."""
        with self._lock:
            self._by_index.pop(index, None)

    @staticmethod
    def _row(e: dict) -> dict:
        return {"packs": e["packs"], "delta_packs": e["delta_packs"],
                "remasks": e["remasks"], "compacts": e["compacts"],
                "pack_ms_total": round(e["pack_ms_total"], 3),
                "pools": dict(e["pools"]),
                "recent": list(e["recent"])}

    def stats(self, index: str | None = None) -> dict:
        with self._lock:
            if index is not None:
                e = self._by_index.get(index)
                return {} if e is None else self._row(e)
            return {idx: self._row(e) for idx, e in self._by_index.items()}


PACK_LEDGER = PackLedger()


def segment_capacity(seg: FrozenSegment) -> dict | None:
    """The ledger row for one live segment: tier bytes + the bytes of its
    row holders (DeviceFilterCache's key spaces, a tier each), or None when
    the segment never packed (nothing resident). Pure host reads — safe from
    any stats/scrape path."""
    cache = getattr(seg, "_device_cache", {})
    packed = cache.get("packed")
    held = {space: int(cache[space].bytes) if space in cache else 0
            for space in ROW_SPACES}
    if packed is None and not any(held.values()):
        return None
    tiers = packed_tier_bytes(packed) if packed is not None else {
        "postings": 0, "dense_plane": 0, "positions_plane": 0,
        "sim_tables": 0, "agg_rows": 0, "agg_limbs": 0, "sort_keys": 0,
        "norms": 0}
    tiers.update(held)
    return {
        "generation": int(seg.gen),
        "tf_layout": packed.tf_layout if packed is not None else None,
        "sort_key_rows": len(packed.sort_rows) if packed is not None else 0,
        "tiers": tiers,
        "total_bytes": int(sum(tiers.values())),
    }


def capacity_report(indices_service, index=None) -> dict:
    """The device capacity ledger: per-index, per-segment HBM residency by
    tier + the pack/repack timing rollup — `/_nodes/stats` `device` section
    and the `/{index}/_stats` device stanza. Walks this NODE's live shard
    searchers (host arithmetic only; acquire_searcher on a closed engine is
    skipped, same as the Prometheus HBM gauge). `index` narrows the walk to
    one name or a collection of names — an index-scoped stats call must not
    pay the whole node's segment walk."""
    from ..common.errors import SearchEngineError

    wanted = None
    if index is not None:
        wanted = (set(index) if isinstance(index, (set, frozenset, list,
                                                   tuple))
                  else {index})
    indices_out = {}
    node_totals: dict[str, int] = {}
    for name, svc in list(indices_service.indices.items()):
        if wanted is not None and name not in wanted:
            continue
        shards_out = {}
        idx_totals: dict[str, int] = {}
        for sid, shard in sorted(svc.shards.items()):
            try:
                searcher = shard.engine.acquire_searcher()
            except SearchEngineError:
                continue
            segs = []
            for seg in searcher.segments:
                row = segment_capacity(seg)
                if row is None:
                    continue
                segs.append(row)
                for tier, b in row["tiers"].items():
                    idx_totals[tier] = idx_totals.get(tier, 0) + b
            if segs:
                shards_out[str(sid)] = segs
        entry = {
            "shards": shards_out,
            "totals": dict(idx_totals),
            "total_bytes": int(sum(idx_totals.values())),
            "pack": PACK_LEDGER.stats(name),
        }
        indices_out[name] = entry
        for tier, b in idx_totals.items():
            node_totals[tier] = node_totals.get(tier, 0) + b
    return {
        "indices": indices_out,
        "totals": dict(node_totals),
        "total_bytes": int(sum(node_totals.values())),
    }


def bytes_per_posting(layout: str, dense_resident: bool = False) -> int:
    """Resident bytes per posting slot for a tf layout: docs i32 + tf + nb
    (+ the lazy dense f32 plane when faulted in)."""
    return 4 + tf_plane_itemsize(layout) + 1 + (4 if dense_resident else 0)


def _host_columns(seg: FrozenSegment, Dpad: int, put,
                  fields: list[str] | None = None):
    """The O(D) per-doc columns every pack uploads — live mask, per-field
    norm bytes, single-valued numeric dv — shared by pack_segment and the
    compaction concat pack (which re-stages ONLY these small columns from
    host; the O(P) postings planes concat device-side)."""
    live_parent = np.zeros(Dpad, dtype=bool)
    live_parent[: seg.doc_count] = seg.live & seg.parent_mask

    norm_bytes = {}
    for f, arr in seg.norms.items():
        if fields is not None and f not in fields:
            continue
        padded = np.zeros(Dpad, dtype=np.uint8)
        padded[: seg.doc_count] = arr
        norm_bytes[f] = put(padded)

    dv_single = {}
    for f, (off, vals) in seg.dv_num.items():
        counts_dv = np.diff(off)
        if counts_dv.max(initial=0) <= 1:
            col = np.full(Dpad, np.nan, dtype=np.float64)
            has = counts_dv == 1
            col[: seg.doc_count][has] = vals
            dv_single[f] = put(col)
    return live_parent, norm_bytes, dv_single


def pack_segment(seg: FrozenSegment, fields: list[str] | None = None,
                 device_put=None) -> PackedSegment:
    """Pack a frozen segment's postings + norms + single-valued numeric columns for
    device execution. `fields` limits norm upload (None = all text fields).
    Breaker-guarded callers (packed_for) reserve pack_estimate_bytes around
    this call — estimate-before-allocate; the pack itself is host-side numpy +
    device_put, never traced."""
    import jax.numpy as jnp

    put = device_put or (lambda x: jnp.asarray(x))

    T = len(seg.post_offsets) - 1
    counts = device_counts(seg)
    nblks = (counts + BLOCK - 1) // BLOCK
    blk_start = np.zeros(T + 1, dtype=np.int64)
    np.cumsum(nblks, out=blk_start[1:])
    NB = int(blk_start[-1])
    # +1 guarantees at least one all-sentinel row past the real blocks — the scoring
    # batch points its padding triples at row NBpad-1, which must never hold postings
    # (shape+layout math shared with pack_estimate_bytes via pack_shape_math)
    NBpad, Dpad, tf_layout = pack_shape_math(seg)

    flat_docs = np.full(NBpad * BLOCK, Dpad, dtype=np.int32)  # pad → out-of-range slot
    flat_freqs = np.zeros(NBpad * BLOCK, dtype=np.float32)
    if NB:
        # slot of entry j of term t = (blk_start[t]*B) + (j - post_offsets[t])
        slots = expand_ranges(blk_start[:-1] * BLOCK, counts)
        flat_docs[slots], flat_freqs[slots] = _device_postings(seg, counts)

    # block -> owning field ordinal (blocks never span terms, terms never span fields)
    field_names = list(seg.term_dict.keys())
    fid_of_tid = np.full(T, -1, dtype=np.int32)
    for fo, f in enumerate(field_names):
        tids = np.fromiter(seg.term_dict[f].values(), dtype=np.int64,
                           count=len(seg.term_dict[f]))
        fid_of_tid[tids] = fo
    blk_field = np.full(NBpad, -1, dtype=np.int32)
    if NB:
        blk_field[:NB] = np.repeat(fid_of_tid, nblks)

    live_parent, norm_bytes, dv_single = _host_columns(seg, Dpad, put,
                                                       fields=fields)

    # dead/non-parent docs are masked to the sentinel IN the uploaded postings, so no
    # scoring path needs a per-posting live gather; host_docs keeps the raw ids for
    # re-masking when tombstones change
    masked_docs = np.where(live_parent[np.minimum(flat_docs, Dpad - 1)]
                           & (flat_docs < Dpad), flat_docs,
                           Dpad).astype(np.int32, copy=False)

    # quantized tf plane (exact by layout choice: u8/i16 for small-int tf,
    # f32 escape otherwise) + per-posting norm byte of the block's owning
    # field — the two 1-byte planes the sparse scan decodes on device
    flat_tf = flat_freqs.astype(_TF_DTYPE[tf_layout])
    flat_nb = np.zeros(NBpad * BLOCK, dtype=np.uint8)
    fid_per_slot = np.repeat(blk_field, BLOCK)
    real = flat_docs < seg.doc_count
    for fo, fname in enumerate(field_names):
        norms = seg.norms.get(fname)
        if norms is None:
            continue  # norm-less field (meta fields): byte stays 0
        sel = (fid_per_slot == fo) & real
        if sel.any():
            flat_nb[sel] = norms[flat_docs[sel]]

    return PackedSegment(
        gen=seg.gen,
        doc_count=seg.doc_count,
        doc_pad=Dpad,
        blk_docs=put(masked_docs.reshape(NBpad, BLOCK)),
        blk_tf=put(flat_tf.reshape(NBpad, BLOCK)),
        blk_nb=put(flat_nb.reshape(NBpad, BLOCK)),
        tf_layout=tf_layout,
        tf_integral=tf_plane_integral(seg.post_freqs, tf_layout),
        term_blk_start=blk_start,
        live_parent=put(live_parent),
        norm_bytes=norm_bytes,
        dv_single=dv_single,
        host_docs=flat_docs,
        host_freqs=flat_freqs,
        blk_field=blk_field,
        field_names=field_names,
        head_row_of=head_terms(seg.post_offsets, Dpad, tf_layout),
    )


# ---------------------------------------------------------------------------
# compaction concat pack: a merged segment's planes from its sources' planes
# ---------------------------------------------------------------------------

# per-slot transient allowance for the concat program's live gather/select
# buffers on device (a handful of [NB, B] i32/f32 temporaries alive at the
# fused program's peak, amortized per output slot)
CONCAT_TRANSIENT_SLOT_BYTES = 8

_TF_RANK = {TF_U8: 0, TF_I16: 1, TF_F32: 2}


def concat_source_packs(sources) -> list[PackedSegment] | None:
    """The sources' resident packs when the compaction concat path is legal,
    else None (callers fall back to the host-staged pack_segment):

    - every source must be fully live (a tombstoned source's postings are
      dropped by merge_segments, so j-th-posting alignment breaks) and its
      pack resident + current (no pending remask);
    - every source's tf plane must be integral (merge rebuilds freq as the
      position count — fractional f32 tf would diverge bitwise).
    """
    packs = []
    for src in sources:
        cache = getattr(src, "_device_cache", None)
        packed = cache.get("packed") if cache is not None else None
        if packed is None or cache.get("live") is None:
            return None
        if not bool(src.live.all()):
            return None
        if not packed.tf_integral:
            return None
        packs.append(packed)
    return packs


def concat_estimate_bytes(merged: FrozenSegment, sources) -> int:
    """Host-staging + device-allocation bytes pack_segment_concat will use —
    the fielddata-breaker estimate for the compaction pack, exact for the
    concat layout the way pack_estimate_bytes is for the staged one. The
    O(P) postings planes are DEVICE outputs plus retained host copies (for
    future remasks / the lazy dense plane) — the host→device upload is only
    the O(NB + W·T + D) tables and columns, which is the whole point."""
    NBpad, Dpad, layout = pack_shape_math(merged)
    tf_b = tf_plane_itemsize(layout)
    W = len(sources)
    T = int(np.count_nonzero(device_counts(merged)))  # the tables' columns
    n_norm_fields = len(merged.norms)
    n_dv = len(merged.dv_num)
    # retained host planes + device output planes + fused-program transients
    per_slot = (4 + 4) + (4 + tf_b + 1) + CONCAT_TRANSIENT_SLOT_BYTES
    # + blk_term/blk_j0 rows, the [W+1,T] cum + [W,T] start tables (host
    # build + device copy), and the Dpad-wide masks/columns
    return (NBpad * BLOCK * per_slot + NBpad * 4 * 2
            + (2 * W + 1) * T * 4 * 2 + Dpad * 2
            + Dpad * n_norm_fields + Dpad * 8 * n_dv)


def pack_segment_concat(merged: FrozenSegment,
                        sources) -> PackedSegment | None:
    """Assemble a merged segment's pack by CONCATENATING its sources'
    already-resident device planes — re-blocked to the merged term layout by
    one fused gather/select program (ops/scoring.concat_pack_planes), tf
    rungs widened per the choose_tf_layout ladder — instead of re-staging
    O(postings) bytes from host. Legal exactly when merge preserved every
    posting in source order (concat_source_packs); the result is bitwise
    identical to pack_segment(merged) by construction, pinned by the writes
    parity tests. Returns None when ineligible or when the layout cross-check
    fails (callers fall back to the staged pack). The sources' positions
    planes are not concatenated: the merged segment starts without one and
    its first phrase faults it in from the merged host copy."""
    import jax.numpy as jnp

    from ..common.jaxenv import compile_tag
    from .scoring import concat_pack_planes

    packs = concat_source_packs(sources)
    if packs is None or not len(merged.post_docs):
        return None
    if merged.doc_count != sum(s.doc_count for s in sources):
        return None  # docs were dropped: source order ≠ merged order
    NBpad, Dpad, layout = pack_shape_math(merged)
    widest = max((p.tf_layout for p in packs), key=lambda l: _TF_RANK[l])
    if layout != widest:
        return None  # freq drift between sources and merged CSR: re-stage

    T = len(merged.post_offsets) - 1
    counts_m = device_counts(merged)
    W = len(sources)
    # the tables hold a column for each term the planes hold (a term of
    # HOST_ONLY_FIELDS has no block to re-block)
    col_of = np.full(T, -1, dtype=np.int32)
    held = np.flatnonzero(counts_m)
    col_of[held] = np.arange(len(held), dtype=np.int32)
    if not len(held):
        return None  # nothing the planes would hold: the staged pack's case
    # per (source, merged-term): posting count + the source's block start.
    # Terms resolve by name through each source's term dict (O(T·W) dict
    # lookups — proportional to vocabulary, not postings)
    cnt = np.zeros((W, len(held)), dtype=np.int32)
    starts = np.zeros((W, len(held)), dtype=np.int32)
    for s, (src, packed_s) in enumerate(zip(sources, packs)):
        src_counts = np.diff(src.post_offsets)
        for f, td_m in merged.term_dict.items():
            td_s = src.term_dict.get(f)
            if not td_s or f in HOST_ONLY_FIELDS:
                continue
            for term, tid_m in td_m.items():
                tid_s = td_s.get(term)
                if tid_s is not None:
                    cnt[s, col_of[tid_m]] = src_counts[tid_s]
                    starts[s, col_of[tid_m]] = packed_s.term_blk_start[tid_s]
    cum = np.zeros((W + 1, len(held)), dtype=np.int32)
    np.cumsum(cnt, axis=0, out=cum[1:])
    if not np.array_equal(cum[-1], counts_m[held]):
        return None  # per-term counts disagree with the merged CSR

    nblks = (counts_m + BLOCK - 1) // BLOCK
    blk_start = np.zeros(T + 1, dtype=np.int64)
    np.cumsum(nblks, out=blk_start[1:])
    NB = int(blk_start[-1])
    blk_term = np.zeros(NBpad, dtype=np.int32)
    # pad rows: a huge within-term offset makes every select miss, so the
    # outputs keep their sentinel/zero initializers — same bytes the staged
    # pack writes there
    blk_j0 = np.full(NBpad, 1 << 30, dtype=np.int32)
    if NB:
        blk_term[:NB] = np.repeat(col_of, nblks)
        blk_j0[:NB] = ((np.arange(NB, dtype=np.int64)
                        - np.repeat(blk_start[:-1], nblks))
                       * BLOCK).astype(np.int32)
    bases = np.asarray(
        np.cumsum([0] + [s.doc_count for s in sources[:-1]]), dtype=np.int32)
    doc_pads = np.asarray([p.doc_pad for p in packs], dtype=np.int32)

    with compile_tag("compact"):
        out_docs, out_tf, out_nb = concat_pack_planes(
            jnp.asarray(blk_term), jnp.asarray(blk_j0), jnp.asarray(cum),
            jnp.asarray(starts), jnp.asarray(bases), jnp.asarray(doc_pads),
            tuple(p.blk_docs for p in packs),
            tuple(p.blk_tf for p in packs),
            tuple(p.blk_nb for p in packs),
            doc_pad_new=Dpad, tf_layout=layout)

    # retained host copies (live-mask remasks, the lazy dense plane) — host
    # numpy only, never uploaded here
    flat_docs = np.full(NBpad * BLOCK, Dpad, dtype=np.int32)
    flat_freqs = np.zeros(NBpad * BLOCK, dtype=np.float32)
    slots = expand_ranges(blk_start[:-1] * BLOCK, counts_m)
    flat_docs[slots], flat_freqs[slots] = _device_postings(merged, counts_m)

    field_names = list(merged.term_dict.keys())
    fid_of_tid = np.full(T, -1, dtype=np.int32)
    for fo, f in enumerate(field_names):
        tids = np.fromiter(merged.term_dict[f].values(), dtype=np.int64,
                           count=len(merged.term_dict[f]))
        fid_of_tid[tids] = fo
    blk_field = np.full(NBpad, -1, dtype=np.int32)
    if NB:
        blk_field[:NB] = np.repeat(fid_of_tid, nblks)

    put = jnp.asarray
    live_parent, norm_bytes, dv_single = _host_columns(merged, Dpad, put)
    return PackedSegment(
        gen=merged.gen,
        doc_count=merged.doc_count,
        doc_pad=Dpad,
        blk_docs=out_docs,
        blk_tf=out_tf,
        blk_nb=out_nb,
        tf_layout=layout,
        tf_integral=True,  # gated on every source being integral
        term_blk_start=blk_start,
        live_parent=put(live_parent),
        norm_bytes=norm_bytes,
        dv_single=dv_single,
        host_docs=flat_docs,
        host_freqs=flat_freqs,
        blk_field=blk_field,
        field_names=field_names,
        head_row_of=head_terms(merged.post_offsets, Dpad, layout),
    )


def ensure_blk_freqs(packed: PackedSegment, breaker=None):
    """Lazily fault in the dense-fallback f32 freqs plane (the `blk_freqs`-drop
    rule: pack_segment no longer uploads it, so sparse-only segments stay at
    the quantized 6 B/posting). Idempotent; a concurrent double-upload is
    benign (same values, last assignment wins).

    `breaker` (fielddata) reserves the plane's bytes around the upload — the
    same transient estimate-before-allocate contract as packed_for, and the
    same graceful degradation: a trip raises CircuitBreakingError and serving
    falls back to the host scorer. The dense call sites in search/execute.py
    pass it; the unaccounted default exists only for the direct-kernel tests
    and for segments whose plane is already resident."""
    prof = _profile.current()
    if packed.blk_freqs is None:
        import jax.numpy as jnp

        with reserve(breaker, packed.host_freqs.nbytes, "<dense_freqs>"):
            packed.blk_freqs = jnp.asarray(
                packed.host_freqs.reshape(-1, BLOCK))
        if prof is not None:
            prof.event("blk_freqs", cache="fault",
                       bytes=int(packed.host_freqs.nbytes))
    elif prof is not None:
        prof.event("blk_freqs", cache="resident")
    return packed.blk_freqs


def ensure_head_rows(packed: PackedSegment, breaker=None):
    """Lazily fault in the head-term rows (module docstring), beside the dense
    f32 plane and under the same contract: built from the pack's own host
    copy, reserved on `breaker` around the build and the upload, idempotent.

    Row i holds the raw tf of term i's postings by doc id, deleted and
    non-parent documents included: every dense program ends in
    `& live_parent`, so a live-mask change re-bakes nothing here. The row
    count rides the pow-2 ladder (it shapes the dense programs' operand);
    rows from len(head_row_of) on are zero, so the last row always is, and
    padding slots point there."""
    if packed.head_rows is None:
        import jax.numpy as jnp

        dtype = _TF_DTYPE[TF_U8 if packed.tf_layout == TF_F32
                          else packed.tf_layout]
        n_rows = _pow2_bucket(len(packed.head_row_of) + 1, 1)
        est = n_rows * packed.doc_pad * np.dtype(dtype).itemsize
        with reserve(breaker, est, "<head_rows>"):
            rows = np.zeros((n_rows, packed.doc_pad), dtype)
            for tid, row in packed.head_row_of.items():
                b0, b1 = packed.blocks_for_term(tid)
                docs = packed.host_docs[b0 * BLOCK: b1 * BLOCK]
                real = docs < packed.doc_pad
                rows[row, docs[real]] = \
                    packed.host_freqs[b0 * BLOCK: b1 * BLOCK][real]
            packed.head_rows = jnp.asarray(rows)
    return packed.head_rows


# host transients per key at the fault's peak, beyond the staged and uploaded
# plane: the key's document, term and slot (i64 each at the peak) and the
# boolean selections over postings and occurrences
POSITIONS_TRANSIENT_BYTES = 32


def positions_shape_math(seg: FrozenSegment, field: str) -> tuple[int, int]:
    """(NPBpad, keys) of a field's positions plane before deleted documents
    are left out, a key an occurrence and a marker a posting: what
    ensure_positions reserves by."""
    td = seg.term_dict.get(field) or {}
    tids = np.fromiter(td.values(), dtype=np.int64, count=len(td))
    p0, p1 = seg.post_offsets[tids], seg.post_offsets[tids + 1]
    counts = (seg.pos_offsets[p1] - seg.pos_offsets[p0]) + (p1 - p0)
    nblks = int(((counts + BLOCK - 1) // BLOCK).sum())
    return _pow2_bucket(nblks + 1, 64), int(counts.sum())


def ensure_positions(seg: FrozenSegment, packed: PackedSegment, field: str,
                     breaker=None) -> PositionsPlane:
    """Lazily fault in `field`'s positions plane (module docstring), under
    the contract of ensure_head_rows: built from the host's own copy,
    reserved on `breaker` (fielddata) around the build and the upload,
    idempotent; a concurrent double build is benign (same values, the last
    assignment wins). The plane holds every parent document, the deleted
    ones under the dead code of this view's live mask; a later tombstone
    re-masks it (_perform_pack), it is not built again. The row count rides
    the pow-2 ladder (it shapes the phrase program's operand)."""
    plane = packed.positions.get(field)
    if plane is not None:
        return plane
    pos_bits = 31 - max(1, int(packed.doc_pad - 1).bit_length())
    mark_base = positions_mark_base(pos_bits)
    NPBpad, n_keys = positions_shape_math(seg, field)
    # the staged and the uploaded plane, the rows' two bounds, the transients
    est = NPBpad * (BLOCK * 4 * 2 + 8) + n_keys * POSITIONS_TRANSIENT_BYTES
    with reserve(breaker, est, f"<positions>{field}"):
        T = len(seg.post_offsets) - 1
        td = seg.term_dict.get(field) or {}
        of_field = np.zeros(T, dtype=bool)
        of_field[np.fromiter(td.values(), dtype=np.int64, count=len(td))] = True
        per_term = np.diff(seg.post_offsets)
        # the postings the plane keeps, and each one's term, document, norm
        # byte and occurrences
        kept = np.repeat(of_field, per_term) & seg.parent_mask[seg.post_docs]
        p_tid = np.repeat(np.arange(T, dtype=np.int64), per_term)[kept]
        p_doc = seg.post_docs[kept].astype(np.int64)
        p_occ = np.diff(seg.pos_offsets)[kept]
        norms = seg.norms.get(field)
        p_nb = norms[p_doc].astype(np.int64) if norms is not None \
            else np.zeros(len(p_doc), np.int64)
        pos = seg.positions[expand_ranges(seg.pos_offsets[:-1][kept],
                                          p_occ)].astype(np.int64)
        pos_max = int(pos.max(initial=0))
        if mark_base <= 0 or pos_max >= mark_base \
                or int(pos.min(initial=0)) < 0:
            # the host answers this field's phrases
            plane = PositionsPlane(pos_bits, pos_max)
            packed.positions[field] = plane
            return plane
        # a posting's occurrences, then its marker
        n_post = len(p_doc)
        o_post = np.repeat(np.arange(n_post, dtype=np.int64), p_occ)
        keys = np.empty(len(pos) + n_post, dtype=np.int64)
        tids = np.empty(len(keys), dtype=np.int64)
        ends = np.cumsum(p_occ) + np.arange(n_post, dtype=np.int64)
        at = np.arange(len(pos), dtype=np.int64) + o_post
        keys[at] = (p_doc[o_post] << pos_bits) | pos
        keys[ends] = (p_doc << pos_bits) | (mark_base + (p_nb << POS_MARK_SHIFT))
        tids[at], tids[ends] = p_tid[o_post], p_tid
        # the merge wants every term's list strictly ascending; the host
        # scorer reads positions as sets, so order and repeats are not its
        order = (tids << 32) | keys
        if len(order) > 1 and not bool(np.all(order[1:] > order[:-1])):
            order = np.unique(order)
            tids, keys = order >> 32, order & 0xFFFFFFFF
        counts = np.bincount(tids, minlength=T).astype(np.int64)
        blk_start = np.zeros(T + 1, dtype=np.int64)
        np.cumsum((counts + BLOCK - 1) // BLOCK, out=blk_start[1:])
        flat = np.full(NPBpad * BLOCK, POS_SENTINEL, dtype=np.int32)
        flat[expand_ranges(blk_start[:-1] * BLOCK, counts)] = \
            keys.astype(np.int32)
        host_keys = flat.reshape(NPBpad, BLOCK)
        n_real = (host_keys != POS_SENTINEL).sum(axis=1)
        last_key = host_keys[np.arange(NPBpad), np.maximum(n_real - 1, 0)]
        plane = PositionsPlane(
            pos_bits, pos_max, blk_start, host_keys=host_keys,
            blk_first=host_keys[:, 0] >> pos_bits,
            blk_last=np.where(n_real > 0, last_key >> pos_bits,
                              -1).astype(np.int32))
        plane.keys = masked_positions(plane, seg.live)
    packed.positions[field] = plane
    prof = _profile.current()
    if prof is not None:
        prof.event("positions_plane", cache="fault", field=field,
                   bytes=int(NPBpad * BLOCK * 4))
    return plane


def _whole_numbers(vals: np.ndarray) -> bool:
    return bool(np.all(vals == np.floor(vals)))


def _agg_column_facts(seg: FrozenSegment, field: str) -> tuple | None:
    """What agg_device_exact asks of one numeric column of a segment, kept on
    the segment: None for a column with a fractional value or with no value
    at all (no exact answer to lose), else (float32 holds every value, the
    integer limbs hold every document's sum, the absolute values' sum)."""
    ckey = ("agg_facts", field)
    if ckey not in seg._device_cache:
        col = seg.dv_num.get(field)
        facts = None
        if col is not None and len(col[1]) and _whole_numbers(col[1]):
            off, vals = col
            mags = np.abs(vals)
            facts = (
                np.array_equal(vals.astype(np.float32).astype(np.float64), vals),
                seg.doc_count <= LIMB_MAX_DOCS
                and float(mags.max()) * int(np.diff(off).max()) < 2.0 ** 62,
                float(mags.sum()))
        seg._device_cache[ckey] = facts
    return seg._device_cache[ckey]


def agg_device_exact(segs: list, field: str, needs_values: bool,
                     f32_sums: bool = False) -> bool:
    """THE rule of whether a device program answers a metric aggregation of
    `field` over `segs` as exactly as the host collectors do; both serving
    paths ask it (service._try_device_aggs, mesh_search.ensure_mesh_agg_stack)
    and a refusal sends the search to the host collectors, or from the mesh
    to the transport path. A fractional column always rides: inherently
    approximate reals, ~1e-7 relative rounding in float32, same as an ES
    `float`-typed field. A column of whole numbers is semantically exact
    (epoch millis shifted by f32 rounding would be a wrong answer), so:

    - `needs_values` (the agg serves a min or a max:
      aggregations.device_agg_needs_values): float32 has to hold every value
      (longs/dates past 2^24 do not survive the round trip);
    - its sum: the one-shard program adds integer limbs (agg_int_limbs),
      which have to hold every document's sum (an int64's worth, in a segment
      of at most LIMB_MAX_DOCS documents); the mesh program (`f32_sums`)
      reduces in float32, where every partial sum stays a whole number below
      2^24 only if the absolute values of `segs` together add up to less.

    No whole-number sum is served from a float32 accumulator that could have
    rounded it."""
    total = 0.0
    for seg in segs:
        facts = _agg_column_facts(seg, field)
        if facts is None:
            continue
        f32_values, limbs_hold, abs_sum = facts
        if needs_values and not f32_values:
            return False
        if not f32_sums and not limbs_hold:
            return False
        total += abs_sum
    return not f32_sums or total < float(1 << 24)


def agg_doc_rows(seg: FrozenSegment, field: str) -> np.ndarray:
    """Per-doc metric folds of one numeric column: float32 [5, doc_count] rows
    (count, sum, min, max, sumsq). Whether a value or a sum served from them
    is exact is agg_device_exact's to say, and both serving paths ask it
    first; the one-shard program zeroes the sum row of a column of whole
    numbers and adds its limbs (ensure_agg_rows).

    Multi-valued docs fold exactly (cumsum difference / reduceat over the CSR);
    docs with no value carry count 0 and ±inf min/max so the kernel's masked
    reductions ignore them."""
    D = seg.doc_count
    rows = np.zeros((5, D), dtype=np.float32)
    rows[2] = np.inf
    rows[3] = -np.inf
    col = seg.dv_num.get(field)
    if col is None:
        return rows
    off, vals = col
    counts = np.diff(off)
    c = np.zeros(len(vals) + 1)
    np.cumsum(vals, out=c[1:])
    sums = c[off[1:]] - c[off[:-1]]
    c2 = np.zeros(len(vals) + 1)
    np.cumsum(np.asarray(vals, dtype=np.float64) ** 2, out=c2[1:])
    sumsq = c2[off[1:]] - c2[off[:-1]]
    has = counts > 0
    if len(vals):
        # reduceat over the value-holding docs' true start offsets: consecutive
        # starts delimit exactly each such doc's value run (clipping off[:-1]
        # would TRUNCATE the previous doc's run when trailing docs are empty)
        starts = off[:-1][has]
        rows[2][has] = np.minimum.reduceat(vals, starts)
        rows[3][has] = np.maximum.reduceat(vals, starts)
    rows[0] = counts
    rows[1] = sums
    rows[4] = sumsq
    return rows


# An integer column's sum, exact on the device whatever its magnitude: each
# document's sum of values is split into limbs of LIMB_BITS bits held as int32
# rows, the program adds the limbs of the matched documents as int32 (a limb
# is below 2^11 and a segment holds at most LIMB_MAX_DOCS documents, so no
# limb's total reaches 2^31), and the host puts the totals together as Python
# integers (limb_totals). The low limbs are unsigned and the top one carries
# the sign (an arithmetic shift), so negative values add up as they should.
LIMB_BITS = 11
LIMB_MAX_DOCS = 1 << 20
_LIMB_RUNGS = (3, 6)  # limbs a column: 33 signed bits, or all of an int64


def agg_int_limbs(seg: FrozenSegment, field: str) -> np.ndarray | None:
    """int32 [L, doc_count] limb rows of each document's summed values, L the
    first of _LIMB_RUNGS that holds the segment's largest; None for a column
    with a fractional value (its sum is a float32 sum: agg_doc_rows) and for
    one this segment lacks. The caller has asked agg_device_exact whether the
    limbs hold the column."""
    if _agg_column_facts(seg, field) is None:
        return None
    off, vals = seg.dv_num[field]
    c = np.zeros(len(vals) + 1, np.int64)
    np.cumsum(vals.astype(np.int64), out=c[1:])
    sums = c[off[1:]] - c[off[:-1]]
    peak = int(np.abs(sums).max()) if len(sums) else 0
    n = next(n for n in _LIMB_RUNGS
             if peak < 1 << (LIMB_BITS * n - 1) or n == _LIMB_RUNGS[-1])
    limbs = np.empty((n, seg.doc_count), np.int32)
    for i in range(n - 1):
        limbs[i] = (sums >> (LIMB_BITS * i)) & ((1 << LIMB_BITS) - 1)
    limbs[n - 1] = sums >> (LIMB_BITS * (n - 1))
    return limbs


def limb_totals(limbs: np.ndarray, axis: int) -> np.ndarray:
    """The limbs' totals (the program's int32 reductions, `axis` the limb
    axis) put together as Python integers, in an object array."""
    weights = np.array([1 << (LIMB_BITS * i) for i in range(limbs.shape[axis])],
                       dtype=object)
    shape = [1] * limbs.ndim
    shape[axis] = -1
    return (limbs.astype(object) * weights.reshape(shape)).sum(axis=axis)


def _pad_agg_rows(rows: np.ndarray, doc_pad: int, base: int = 0,
                  out: np.ndarray | None = None) -> np.ndarray:
    """Place [5, D] rows at `base` inside a [5, doc_pad] canvas (empty slots:
    count 0, ±inf min/max)."""
    if out is None:
        out = np.zeros((5, doc_pad), dtype=np.float32)
        out[2] = np.inf
        out[3] = -np.inf
    out[:, base: base + rows.shape[1]] = rows
    return out


@dataclass(frozen=True)
class AggStack:
    """The device-resident per-doc folds of a tuple of fields on one packed
    segment: `rows` f32 [F, 5, Dpad] (agg_doc_rows; the sum and sumsq rows of
    a limbed field are zero: nothing adds whole numbers up in float32) and
    `limbs` int32 [F, L, Dpad] (agg_int_limbs; zero rows for a field without
    them and past a field's own count, L = 0 where no field has any).
    `limbed[i]` says whether field i's sum is its limbs' to give."""

    rows: object
    limbs: object
    limbed: tuple


def ensure_agg_rows(seg: FrozenSegment, packed: PackedSegment, fields: list[str],
                    breaker=None) -> AggStack | None:
    """The segment's AggStack for `fields`, or None where the limbs do not
    hold a column of whole numbers (agg_device_exact; callers fall back to the
    host collectors). Per-field rows cache HOST-side; only the per-tuple
    device stacks (FIFO-bounded) hold device memory — mirroring
    ensure_mesh_agg_stack.

    `breaker` (fielddata) reserves the [F, 5, Dpad] f32 stack and the limb
    stack (host rows + device copy) before they are built — the per-doc fold
    columns are the fielddata-load analogue on this engine."""
    import jax.numpy as jnp

    key = tuple(fields)
    stack = packed.agg_stacks.get(key)
    if stack is not None:
        return stack
    if not all(agg_device_exact([seg], f, needs_values=False) for f in fields):
        return None
    est = len(fields) * (5 + _LIMB_RUNGS[-1]) * packed.doc_pad * 4 * 2
    with reserve(breaker, est, f"<agg_rows>{list(fields)}"):
        for f in fields:
            if f not in packed.agg_rows:
                rows = _pad_agg_rows(agg_doc_rows(seg, f), packed.doc_pad)
                limbs = agg_int_limbs(seg, f)
                if limbs is not None:
                    rows[1] = rows[4] = 0.0
                    limbs = np.pad(
                        limbs, ((0, 0), (0, packed.doc_pad - limbs.shape[1])))
                packed.agg_rows[f] = (rows, limbs)
        held = [packed.agg_rows[f] for f in fields]
        n_limbs = max((limbs.shape[0] for _r, limbs in held
                       if limbs is not None), default=0)
        limb_stack = np.zeros((len(fields), n_limbs, packed.doc_pad), np.int32)
        for i, (_rows, limbs) in enumerate(held):
            if limbs is not None:
                limb_stack[i, : limbs.shape[0]] = limbs
        stack = AggStack(
            jnp.asarray(np.stack([rows for rows, _l in held]) if fields
                        else np.zeros((0, 5, packed.doc_pad), np.float32)),
            jnp.asarray(limb_stack),
            tuple(limbs is not None for _r, limbs in held))
        while len(packed.agg_stacks) >= 8:
            packed.agg_stacks.pop(next(iter(packed.agg_stacks)))
        packed.agg_stacks[key] = stack
    return stack


# ---------------------------------------------------------------------------
# device-resident filter/bitset cache
# ---------------------------------------------------------------------------


class RecentKeys:
    """How often each key was among the last `horizon` sightings (Lucene's
    UsageTrackingQueryCachingPolicy keeps the same history of 256): a filter
    earns a cache entry by recurring while it is remembered, so bounds that
    never recur, a dashboard's time windows, are counted once, forgotten,
    and fill neither filter cache. No lock of its own: the caller's leaf
    lock serializes it."""

    __slots__ = ("_ring", "_count", "_horizon")

    def __init__(self, horizon: int = 256):
        self._ring: deque = deque()
        self._count: dict = {}
        self._horizon = horizon

    def sight(self, key, times: int = 1) -> int:
        """Count `key` `times` more; its count within the horizon."""
        ring, count = self._ring, self._count
        for _ in range(times):
            ring.append(key)
            count[key] = count.get(key, 0) + 1
            if len(ring) > self._horizon:
                old = ring.popleft()
                if count[old] == 1:
                    del count[old]
                else:
                    count[old] -= 1
        return count.get(key, 0)

    def get(self, key) -> int:
        return self._count.get(key, 0)

    def items(self):
        return self._count.items()

    def clear(self) -> None:
        self._ring.clear()
        self._count.clear()


# The store's key spaces. A space's name is its holder's slot in
# `seg._device_cache` and its tier in the device capacity ledger.
FILTER_MASKS = "filter_masks"    # Filter.key() -> one bool [Dpad] mask row
FUNCTION_ROWS = "function_rows"  # a function_score spec's row key -> the
#                                  tuple of [Dpad] rows its launch reads
ROW_SPACES = (FILTER_MASKS, FUNCTION_ROWS)
_BREAKER_LABEL = {FILTER_MASKS: "<filter_mask>",
                  FUNCTION_ROWS: "<function_rows>"}


class _SegmentRows:
    """Per-segment holder of one key space's device-resident rows, living in
    `seg._device_cache[<space>]`. Copy-on-write tombstoning
    (FrozenSegment.with_deletes) shallow-copies the device cache, so views of
    one segment SHARE this holder — eviction therefore keys on the holder
    object (is it still referenced by any live segment?), not on the segment
    wrapper identity. The rows are live-mask independent (filters gate
    MATCHING and function rows hold a value for every document; liveness is
    the kernel's separate live_parent gate), so sharing across tombstone
    views is exact."""

    __slots__ = ("entries", "seen", "bytes", "dead")

    def __init__(self, dead: bool = False):
        self.entries: dict = {}  # key -> (device rows, nbytes)
        self.seen = RecentKeys()  # key -> sightings it is remembered for
        self.bytes = 0
        self.dead = dead  # evicted with its segment: never re-stores


class _SpaceCounters:
    """The node-level tallies of one key space (DeviceFilterCache._lock
    guards them)."""

    __slots__ = ("hits", "misses", "builds", "evictions", "rejections",
                 "bytes", "entries")

    def __init__(self):
        self.hits = self.misses = self.builds = self.evictions = 0
        self.rejections = 0  # breaker-tripped stores
        self.bytes = self.entries = 0

    def stats(self) -> dict:
        n = self.hits + self.misses
        return {
            "memory_size_in_bytes": self.bytes,
            "hits": self.hits,
            "misses": self.misses,
            "builds": self.builds,
            "evictions": self.evictions,
            "rejections": self.rejections,
            "hit_rate": round(self.hits / n, 4) if n else 0.0,
        }


class DeviceFilterCache:
    """Node-level accounting + policy for per-segment device-resident rows:
    one store, two key spaces.

    FILTER_MASKS: hot filters keep their packed per-segment doc masks
    resident in HBM, keyed by (segment identity, filter fingerprint —
    `Filter.key()`), so a cached filtered plan skips host mask construction
    AND the host→device mask transfer entirely; the dense kernel consumes
    the resident row with bitwise-identical scores (the mask VALUES are
    identical — filters gate matching, never scoring). FUNCTION_ROWS: the
    rows a function_score launch reads of a segment (the host-combined
    function row and its applies row, or a script's column rows and masks:
    execute._fs_segment_rows), keyed by the part of the spec the rows are
    computed from; the stored arrays are the ones the host built, so the
    launch reads the same bits resident as handed down.

    Both spaces are populated by sighting, each with a history of its own:
    the first evaluation of a key on a segment only counts it (the Profile
    API's `bool_filter_clause` fallback counter motivated exactly this
    "which filters are hot" signal); the `min_sightings`-th (default 2nd)
    among the segment's last 256 misses (RecentKeys) takes the padded rows
    the caller built host-side OUTSIDE any lock, `jax.device_put`s them once
    under the transfer guard, charges the fielddata breaker (next to
    `packed_resident_bytes` — this is device-resident state), and publishes
    under the leaf lock. Keys that never recur, a dashboard's time windows
    or decay origins nobody repeats, are never admitted. Rows are evicted
    with their segment on refresh/merge (the engine's view listeners) and by
    `POST /_cache/clear?filter=true`, releasing the breaker bytes.

    Lock discipline: `_lock` is a LEAF guarding dicts and counters only —
    the row build and the device_put always happen outside it (the
    build-outside/publish-under idiom, pinned by the tpulint TPU004
    fixtures)."""

    def __init__(self, settings=None, breaker=None):
        from ..common.settings import Settings

        settings = settings or Settings.EMPTY
        self.enabled = bool(
            settings.get_bool("indices.filter_cache.enable", True))
        self.min_sightings = max(1, int(
            settings.get_int("indices.filter_cache.min_sightings", 2)))
        self.breaker = breaker
        self._lock = threading.Lock()
        self._spaces = {space: _SpaceCounters() for space in ROW_SPACES}

    @staticmethod
    def _holder(seg, space: str) -> _SegmentRows:
        holder = seg._device_cache.get(space)
        if holder is None:
            # benign setdefault race: both racers publish an empty holder,
            # one wins, neither has accounted bytes yet
            holder = seg._device_cache.setdefault(space, _SegmentRows())
        return holder

    @staticmethod
    def _profile_event(space: str, cache: str, key, **kv) -> None:
        prof = _profile.current()
        if prof is None:
            return
        if space == FILTER_MASKS:
            prof.event("filter_cache", cache=cache, filter=key, **kv)
        else:
            prof.event(space, cache=cache, key=key, **kv)

    def lookup(self, seg, key, space: str = FILTER_MASKS):
        """The resident device rows for (segment, key) in `space`, or None.
        Counts the sighting — the miss path's counter is what promotes a key
        to resident on its next appearance."""
        holder = self._holder(seg, space)
        tally = self._spaces[space]
        with self._lock:
            entry = holder.entries.get(key)
            if entry is not None:
                tally.hits += 1
            else:
                tally.misses += 1
                holder.seen.sight(key)
        self._profile_event(space, "hit" if entry else "miss", key)
        return entry[0] if entry is not None else None

    def maybe_store(self, seg, key, padded, space: str = FILTER_MASKS):
        """Promote freshly evaluated rows to device residency when their key
        has reached `min_sightings`. `padded` is what the caller built
        OUTSIDE any lock, one host [Dpad] row (a filter's mask) or a tuple
        of them (a function_score launch's rows); the device_put happens
        here, also outside the leaf lock, and only the publish goes under
        it. Returns the device rows in `padded`'s own structure (freshly
        stored or a concurrent winner's), or None when the key is still
        cold / the tier is off / the breaker tripped."""
        if not self.enabled:
            return None
        holder = self._holder(seg, space)
        tally = self._spaces[space]
        with self._lock:
            if holder.dead:
                return None  # segment already evicted: a stale searcher
                # must not repopulate bytes nobody will ever release
            entry = holder.entries.get(key)
            if entry is not None:
                return entry[0]
            if holder.seen.get(key) < self.min_sightings:
                return None
        import jax

        nbytes = sum(int(row.nbytes)
                     for row in jax.tree_util.tree_leaves(padded))
        if self.breaker is not None:
            try:
                self.breaker.add_estimate_and_maybe_break(
                    nbytes, _BREAKER_LABEL[space])
            except CircuitBreakingError:
                with self._lock:      # out of fielddata budget: the host
                    tally.rejections += 1  # rows still serve this request
                return None
        rows = jax.device_put(padded)  # the ONE transfer, outside _lock
        release = 0
        with self._lock:
            if holder.dead:
                release = nbytes
                rows = None
            else:
                entry = holder.entries.get(key)
                if entry is not None:
                    release = nbytes  # concurrent winner: keep theirs
                    rows = entry[0]
                else:
                    holder.entries[key] = (rows, nbytes)
                    holder.bytes += nbytes
                    tally.bytes += nbytes
                    tally.entries += 1
                    tally.builds += 1
        if release and self.breaker is not None:
            self.breaker.release(release)
        if rows is not None and release == 0:
            self._profile_event(space, "build", key, bytes=nbytes)
        return rows

    # -- eviction ------------------------------------------------------------
    def _drop_locked(self, holder: _SegmentRows, space: str) -> tuple:
        """Empty `holder` (caller holds `_lock`): (entries dropped, bytes to
        release to the breaker)."""
        tally = self._spaces[space]
        n, released = len(holder.entries), holder.bytes
        tally.bytes -= released
        tally.entries -= n
        tally.evictions += n
        holder.entries.clear()
        holder.seen.clear()
        holder.bytes = 0
        return n, released

    def evict_dropped(self, dropped, live) -> int:
        """Evict the rows of segments a new view dropped, in every key
        space. `live` is the new view's segment list: a with_deletes view
        SHARES its predecessor's holders, so a holder still referenced by
        any live segment is retained (same filters and functions, same
        documents — only tombstones changed)."""
        released = 0
        evicted = 0
        for space in ROW_SPACES:
            live_holders = {id(s._device_cache.get(space)) for s in live
                            if s._device_cache.get(space) is not None}
            for seg in dropped:
                holder = seg._device_cache.get(space)
                if holder is None:
                    # plant a DEAD holder so a straggler request still
                    # holding the old searcher can't create a fresh one after
                    # this eviction ran (its stores would be unreleasable
                    # bytes)
                    dead = _SegmentRows(dead=True)
                    holder = seg._device_cache.setdefault(space, dead)
                    if holder is dead:
                        continue  # nothing was resident; tombstone planted
                if id(holder) in live_holders:
                    continue
                with self._lock:
                    if holder.dead:
                        continue
                    holder.dead = True
                    n, nbytes = self._drop_locked(holder, space)
                evicted += n
                released += nbytes
        if released and self.breaker is not None:
            self.breaker.release(released)
        return evicted

    def clear_segment(self, seg) -> int:
        """`POST /_cache/clear?filter=true` on a LIVE segment: drop its
        resident rows and sighting counters in every key space (rebuildable
        — the holders stay alive), returning the breaker bytes."""
        released = 0
        evicted = 0
        for space in ROW_SPACES:
            holder = seg._device_cache.get(space)
            if holder is None:
                continue
            with self._lock:
                n, nbytes = self._drop_locked(holder, space)
            evicted += n
            released += nbytes
        if released and self.breaker is not None:
            self.breaker.release(released)
        return evicted

    # -- warmer integration --------------------------------------------------
    def hot_keys(self, segs) -> set:
        """The filter keys that earned residency (or at least the sighting
        threshold) on any of `segs` — what the warmer carries from a dropped
        view's holders onto the new view's segments."""
        keys: set = set()
        with self._lock:
            for seg in segs:
                holder = seg._device_cache.get(FILTER_MASKS)
                if holder is None:
                    continue
                keys.update(holder.entries.keys())
                keys.update(k for k, c in holder.seen.items()
                            if c >= self.min_sightings)
        return keys

    def seed(self, seg, keys) -> int:
        """Warmer pre-seeding: mark `keys` as already-seen on a segment so
        the NEXT evaluation (the warm replay, or the first live sighting)
        promotes the mask to device residency immediately instead of paying
        the min_sightings ramp on every fresh delta segment. Counter work
        only — no masks are built or uploaded here."""
        if not self.enabled or not keys:
            return 0
        holder = self._holder(seg, FILTER_MASKS)
        seeded = 0
        with self._lock:
            if holder.dead:
                return 0
            for k in keys:
                short = self.min_sightings - holder.seen.get(k)
                if k not in holder.entries and short > 0:
                    holder.seen.sight(k, short)
                    seeded += 1
        return seeded

    # -- observability -------------------------------------------------------
    def stats(self) -> dict:
        """The filter masks' tallies under the names they always had
        (`indices.filter_cache` in /_nodes/stats), the function rows' under
        `function_rows`."""
        with self._lock:
            masks = self._spaces[FILTER_MASKS]
            rows = self._spaces[FUNCTION_ROWS]
            return {"enabled": self.enabled, "masks": masks.entries,
                    **masks.stats(),
                    FUNCTION_ROWS: {"entries": rows.entries, **rows.stats()}}


TFN_BM25 = 0  # tfn = f / (f + cache[norm_byte])        — weight multiplies outside
TFN_TFIDF = 1  # tfn = sqrt(f) * cache[norm_byte]


def tfn_values(freqs: np.ndarray, nb: np.ndarray, cache: np.ndarray,
               mode: int) -> np.ndarray:
    """The per-posting tfn formula — the single HOST definition of what the
    quantized scan computes on device (ops/scoring.sparse_candidates decodes
    blk_tf/blk_nb and applies exactly this, f32 op order included). Kept as
    the reference the parity tests check against."""
    cv = cache[nb]
    if mode == TFN_BM25:
        return (freqs / (freqs + cv)).astype(np.float32)
    return np.sqrt(freqs, dtype=np.float32) * cv


def ensure_sim_tables(packed: PackedSegment,
                      tables: dict[str, tuple[int, np.ndarray]]) -> SimTables:
    """Ensure the stacked per-field similarity LUTs for the given tables
    ({field: (TFN_* mode, float32[256] cache)}) and return the SimTables whose
    `fid` maps fields to cache rows for this launch.

    This replaced the per-posting tfn bake: the tf→tfn normalization now
    happens INSIDE the sparse scan (quantized tf + norm byte + this LUT), so a
    cache-table change — for BM25 whenever avgdl (sum_ttf/max_doc) moves, i.e.
    after indexing activity — costs a 1 KB/field table swap instead of a numpy
    pass over every posting plus a full-plane HBM upload. Fields accumulate
    across calls (stable fid rows per merged set); callers must use the
    RETURNED object's fid/caches for the launch they plan — a concurrent
    re-ensure swaps packed.sim but never mutates an existing SimTables."""
    prof = _profile.current()
    cur = packed.sim
    if cur is not None and all(
        f in cur.key and cur.key[f] == (mode, cache.tobytes())
        for f, (mode, cache) in tables.items()
    ):
        if prof is not None:
            prof.event("sim_tables", cache="hit", fields=len(cur.fields))
        return cur
    import jax.numpy as jnp

    merged = dict(cur.key) if cur is not None else {}
    for f, (mode, cache) in tables.items():
        merged[f] = (mode, cache.tobytes())
    fields = list(merged.keys())
    if fields:
        modes = np.array([merged[f][0] for f in fields], dtype=np.int32)
        caches = np.stack([np.frombuffer(merged[f][1], dtype=np.float32)
                           for f in fields])
    else:
        # fieldless batch (e.g. empty analyzed query): one neutral row so the
        # kernel ABI keeps its [F, 256] shape — only padding slots (zeroed by
        # the valid mask) ever read it
        modes = np.zeros(1, dtype=np.int32)
        caches = np.ones((1, 256), dtype=np.float32)
    sim = SimTables(fields=fields, fid={f: i for i, f in enumerate(fields)},
                    modes=jnp.asarray(modes), caches=jnp.asarray(caches),
                    key=merged)
    packed.sim = sim
    if prof is not None:
        prof.event("sim_tables", cache="swap", fields=len(fields))
    return sim


# coordinates the per-segment pack/remask futures: a LEAF lock guarding only
# _device_cache dict reads/writes — the pack compute, every device_put, and
# every Future wait happen OUTSIDE it (the PR-6 discipline). One module-level
# lock instead of a per-segment dial lock: it is only ever taken on the cold
# miss/publish paths, never on the warmed packed-and-live fast path
_PACK_LOCK = threading.Lock()


def begin_warm(seg: FrozenSegment):
    """Install the in-flight marker for a segment whose pack (or remask) is
    about to be scheduled off the query path. Returns the Future a racing
    search will wait on, or None when the segment is already fully live or
    another pack is in flight. Dict work only — safe to call from an engine
    view listener (which runs under the engine lock).

    The marker is CLAIMABLE: the pack is performed by whoever claims it
    first — normally the scheduled warmer/merge task, but a search (or a
    warm query) that arrives before the task starts STEALS the work and
    packs inline, resolving the same future. Waiting therefore only ever
    happens on a pack that is actively RUNNING on some thread, which
    completes without needing any pool slot — a waiter can never deadlock
    behind pack work queued on its own pool."""
    from concurrent.futures import Future

    cache = seg._device_cache
    with _PACK_LOCK:
        if cache.get("packed") is not None and cache.get("live") is not None:
            return None
        if cache.get("pack_future") is not None:
            return None
        fut: Future = Future()
        cache["pack_future"] = fut
        cache["pack_claimed"] = False
        return fut


def cancel_warm(seg: FrozenSegment, fut) -> None:
    """Withdraw a begin_warm future whose pool submission was rejected
    (node shutting down / saturated): clear the marker and resolve the
    future with None so any racer that started waiting re-enters the
    packed_for loop and packs inline with its own budget. A no-op when a
    racer already claimed the work — the claimant owns the future now."""
    cache = seg._device_cache
    with _PACK_LOCK:
        if cache.get("pack_future") is not fut or cache.get("pack_claimed"):
            return
        cache.pop("pack_future", None)
        cache.pop("pack_claimed", None)
    if not fut.done():
        fut.set_result(None)


def run_warm(seg: FrozenSegment, fut, breaker=None,
             owner: str | None = None):
    """Execute the pack/remask a `begin_warm` future stands for — the
    warmer/merge pool worker body. Returns immediately (None) when a racing
    search already claimed the work: the claimant resolves the future on
    its own thread, so parking this pool slot to wait would buy nothing.
    Exceptions (a fielddata breaker trip, a device error) resolve the
    future so query-path waiters degrade exactly as an inline pack failure
    would, and the marker is cleared so a later query retries with its own
    budget."""
    cache = seg._device_cache
    with _PACK_LOCK:
        if cache.get("pack_future") is not fut or cache.get("pack_claimed"):
            return None
        cache["pack_claimed"] = True
    return _perform_pack(seg, fut, breaker, owner)


def _perform_pack(seg: FrozenSegment, fut, breaker,
                  owner: str | None) -> PackedSegment:
    """Pack (full, delta, or compaction-concat) or remask one segment and
    publish under the leaf lock. The caller owns `fut` (installed in
    seg._device_cache["pack_future"]); every waiter observes the publish
    through it."""
    import jax.numpy as jnp

    cache = seg._device_cache
    prof = _profile.current()
    try:
        # seeded device-error seam (transport/faults.DEVICE_FAULTS): one
        # plain attr read disarmed; armed, the pack fails HERE — before any
        # publish — so the existing exception path below proves no
        # half-packed PackedSegment ever lands in the cache
        if _DEVICE_FAULTS.active:
            _DEVICE_FAULTS.check(f"pack:{owner}")
        packed: PackedSegment | None = cache.get("packed")
        if packed is None:
            hint = cache.get("pack_hint") or {}
            kind = hint.get("kind", "pack")
            sources = hint.get("sources")
            t0 = time.monotonic()
            new_packed = None
            method = "staged" if kind == "compact" else None
            PACK_LEDGER.begin()
            try:
                if kind == "compact" and sources:
                    with reserve(breaker, concat_estimate_bytes(seg, sources),
                                 f"<segment_compact>[{seg.gen}]"):
                        new_packed = pack_segment_concat(seg, sources)
                    if new_packed is not None:
                        method = "concat"
                if new_packed is None:
                    with reserve(breaker, pack_estimate_bytes(seg),
                                 f"<segment_pack>[{seg.gen}]"):
                        new_packed = pack_segment(seg)
            finally:
                # the concat is a device program over every slot of the merged
                # planes (84-120 s of device time at 67M slots on a v5e while
                # the host enqueues it in 6 s: PERF.md section 6, PR 31); the
                # first search queues behind it, so the ledger watches it
                PACK_LEDGER.end(new_packed.blk_docs if method == "concat"
                                else None)
            with _PACK_LOCK:
                cache["packed"] = new_packed
                cache["live"] = True
                cache.pop("pack_future", None)
                cache.pop("pack_claimed", None)
                cache.pop("pack_hint", None)  # drops the source refs
            ms = (time.monotonic() - t0) * 1000.0
            PACK_LEDGER.record(owner, seg.gen, ms,
                               packed_resident_bytes(new_packed),
                               new_packed.tf_layout, kind=kind, method=method)
            if prof is not None:
                prof.event("packed_segment", gen=int(seg.gen), cache=kind,
                           ms=round(ms, 4),
                           resident_bytes=int(
                               packed_resident_bytes(new_packed)),
                           tf_layout=new_packed.tf_layout)
            fut.set_result(new_packed)
            return new_packed
        # remask: the pack is resident but the view's tombstones moved
        t0 = time.monotonic()
        live_parent = np.zeros(packed.doc_pad, dtype=bool)
        live_parent[: seg.doc_count] = seg.live & seg.parent_mask
        lp_dev = jnp.asarray(live_parent)
        # postings carry the live mask inline (sparse path has no per-posting
        # live gather) — re-mask from the raw host copy
        masked = np.where(live_parent[np.minimum(packed.host_docs,
                                                 packed.doc_pad - 1)]
                          & (packed.host_docs < packed.doc_pad),
                          packed.host_docs,
                          packed.doc_pad).astype(np.int32, copy=False)
        docs_dev = jnp.asarray(masked.reshape(-1, BLOCK))
        # the positions planes likewise, from their raw host copies: new
        # objects, a copy-on-write view's parent keeps its own
        planes = {
            field: plane if plane.keys is None else dc_replace(
                plane, keys=masked_positions(plane, seg.live))
            for field, plane in list(packed.positions.items())}
        with _PACK_LOCK:
            packed.live_parent = lp_dev
            packed.blk_docs = docs_dev
            packed.positions = planes
            cache["live"] = True
            cache.pop("pack_future", None)
            cache.pop("pack_claimed", None)
        PACK_LEDGER.record(owner, seg.gen, (time.monotonic() - t0) * 1000.0,
                           packed_resident_bytes(packed), packed.tf_layout,
                           kind="remask")
        if prof is not None:
            prof.event("packed_segment", gen=int(seg.gen),
                       cache="live_remask")
        fut.set_result(packed)
        return packed
    except BaseException as e:  # noqa: BLE001 — waiters must never hang
        if isinstance(e, Exception):
            _tag_domain(e, f"pack:{owner}")  # fault-domain attribution
        with _PACK_LOCK:
            if cache.get("pack_future") is fut:
                cache.pop("pack_future", None)
                cache.pop("pack_claimed", None)
        fut.set_exception(e)
        raise


def packed_for(seg: FrozenSegment, breaker=None,
               owner: str | None = None) -> PackedSegment:
    """Per-segment cached packing; refreshes the live mask when tombstones
    changed. The warmed fast path is one unlocked dict read; the cold path
    coordinates through a per-segment in-flight Future so a search racing a
    scheduled warmer/merge pack WAITS for it (the PR-6 mesh `_executor_for`
    idiom) instead of duplicating the work — in the warmed continuous-
    indexing loop, every query-path call is a cache hit and all pack work
    lands on the warmer/merge pools (PACK_LEDGER pool attribution).

    `breaker` (the node's fielddata child) is consulted ONLY when this call
    ends up owning the pack: the estimate covers the pack's host staging +
    device upload and is released once the pack lands — transient
    accounting, so a drained node reads 0. A trip raises
    CircuitBreakingError; serving falls back to the host scorer (the one
    graceful-degradation edge the reference lacks). A failed WARM pack
    propagates the same way to any waiter, and later calls retry inline.

    `owner` (the index name, from ShardContext) attributes the pack's wall
    time to the capacity ledger (PACK_LEDGER). The pack/remask paths are
    cold by construction (once per segment per view), so timing them always
    is within the zero-added-clocks contract — the cache-HIT path stays
    clock-free."""
    cache = seg._device_cache
    packed: PackedSegment | None = cache.get("packed")
    if packed is not None and cache.get("live") is not None:
        prof = _profile.current()
        if prof is not None:
            prof.event("packed_segment", gen=int(seg.gen), cache="hit")
        return packed
    while True:
        with _PACK_LOCK:
            packed = cache.get("packed")
            if packed is not None and cache.get("live") is not None:
                return packed
            fut = cache.get("pack_future")
            if fut is None:
                from concurrent.futures import Future

                fut = Future()
                cache["pack_future"] = fut
                cache["pack_claimed"] = True
                own = True
            elif not cache.get("pack_claimed"):
                # a scheduled warm pack that hasn't STARTED yet: steal it —
                # this call performs the pack inline and resolves the shared
                # future (run_warm sees the claim and returns). Waiting is
                # therefore reserved for packs actively running on another
                # thread, which finish without needing any pool slot — a
                # warm query on the warmer pool can never deadlock behind
                # pack tasks queued on that same pool
                cache["pack_claimed"] = True
                own = True
            else:
                own = False
        if own:
            return _perform_pack(seg, fut, breaker, owner)
        # another thread is actively packing: wait OUTSIDE every lock; its
        # failure (e.g. breaker trip) propagates here and degrades to the
        # host scorer exactly like an inline trip
        prof = _profile.current()
        if prof is not None:
            prof.event("packed_segment", gen=int(seg.gen), cache="pack_wait")
        fut.result()
        with _PACK_LOCK:
            # defensive: a resolved future must never be waited on twice
            # (guarantees loop progress even if a publish went missing)
            if cache.get("pack_future") is fut:
                cache.pop("pack_future", None)
                cache.pop("pack_claimed", None)
