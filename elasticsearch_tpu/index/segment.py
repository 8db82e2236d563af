"""Write-once segments: the framework's Lucene-core equivalent.

The reference's per-shard performance core is Lucene's inverted index (SURVEY.md §2.8:
postings traversal + scoring is "the hot loop the TPU build replaces"). Here a segment is
a set of flat numpy arrays laid out for direct device packing:

- postings: CSR over term ids — `post_offsets[t]:post_offsets[t+1]` slices `post_docs`
  (sorted local doc ids) and `post_freqs`; per-term positions likewise for phrase queries.
- norms: ONE uint8 PER DOC PER FIELD via the SmallFloat byte315 codec — identical
  quantization to Lucene 4.7 (required for hit-ordering parity, SURVEY.md §7).
- doc values: columnar numeric (float64 CSR for multi-valued) and string-ordinal columns
  — the analogue of index/fielddata/ (SURVEY.md §2.3: "the natural device tensor").
- stored fields: _source dicts + ids/routing, host-side (fetch phase is host work).
- nested docs are real docs in block order (children before parent, Lucene block-join
  layout); `parent_mask` restricts top-level searches.

Segments are immutable after freeze(); deletes are tombstones in a `live` bitmap
(exactly Lucene's liveDocs). Merging = concatenating live docs into a new segment.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field

import numpy as np

from ..common.smallfloat import encode_norm
from ..mapper.core import ParsedDocument

_LIVE_GEN = 0  # process-wide tombstone generation (see FrozenSegment.live_gen)


@dataclass
class FieldStats:
    """Per-field corpus statistics a similarity needs (ref: Lucene CollectionStatistics):
    doc_count = docs with the field, sum_ttf = total term occurrences (for avgdl)."""

    doc_count: int = 0
    sum_ttf: int = 0
    sum_dfs: int = 0

    def merged(self, other: "FieldStats") -> "FieldStats":
        return FieldStats(
            self.doc_count + other.doc_count,
            self.sum_ttf + other.sum_ttf,
            self.sum_dfs + other.sum_dfs,
        )


class SegmentBuilder:
    """Accumulates parsed documents, freezes into a FrozenSegment.
    The analogue of Lucene's in-RAM IndexWriter buffer (DWPT).

    Postings accumulation is the bulk-index hot loop (the reference's is inside
    native Lucene); when the C extension is available it runs in
    estpu_native.PostingsBuilder — C hash-table slots with append-time doc
    grouping, freezing straight to the FrozenSegment CSR layout. The Python dict
    path below is the always-available fallback and the behavioral reference."""

    def __init__(self, gen: int):
        self.gen = gen
        from ..native import get_native

        native = get_native()
        self._pb = (native.PostingsBuilder()
                    if native is not None and hasattr(native, "PostingsBuilder")
                    else None)
        self._pb_fids: dict[str, int] = {}
        # term postings: (field, term) -> list of (local_doc, freq, positions)
        self._postings: dict[tuple[str, str], list] = {}
        self._field_lengths: dict[str, list[tuple[int, int]]] = {}
        self._dv_num: dict[str, list[tuple[int, float]]] = {}
        self._dv_str: dict[str, list[tuple[int, str]]] = {}
        self._stored: list[dict | None] = []
        self._ids: list[str | None] = []
        self._types: list[str | None] = []
        self._routings: list[str | None] = []
        self._versions: list[int] = []
        self._parent_mask: list[bool] = []
        self._nested_paths: list[str | None] = []
        self.doc_count = 0
        self.ram_bytes = 0

    def ram_docs(self) -> int:
        return self.doc_count

    def _add_fields(self, doc: ParsedDocument, local: int):
        # cheap RAM accounting for the IndexingMemoryController (counts postings,
        # columnar values, and a per-doc overhead — not exact, monotonic is enough)
        self.ram_bytes += 128
        for terms in doc.postings.values():
            self.ram_bytes += 40 * len(terms)
        for vals in doc.doc_values_num.values():
            self.ram_bytes += 24 * len(vals)
        for vals in doc.doc_values_str.values():
            self.ram_bytes += sum(48 + 2 * len(str(v)) for v in vals)
        if self._pb is not None:
            for field_name, terms in doc.postings.items():
                if not terms:
                    # a field whose every value analyzed to zero tokens must not
                    # register (the Python path keys off actual (field, term)
                    # entries — a phantom empty term_dict entry would differ)
                    continue
                fid = self._pb_fids.setdefault(field_name, len(self._pb_fids))
                self._pb.add(fid, local, terms)
        else:
            for field_name, terms in doc.postings.items():
                # group into freq + positions per term
                per_term: dict[str, list[int]] = {}
                for term, pos in terms:
                    per_term.setdefault(term, []).append(pos)
                for term, positions in per_term.items():
                    self._postings.setdefault((field_name, term), []).append(
                        (local, len(positions), positions)
                    )
        for field_name, length in doc.field_lengths.items():
            self._field_lengths.setdefault(field_name, []).append((local, length))
        for field_name, vals in doc.doc_values_num.items():
            col = self._dv_num.setdefault(field_name, [])
            for v in vals:
                col.append((local, v))
        for field_name, vals in doc.doc_values_str.items():
            col = self._dv_str.setdefault(field_name, [])
            for v in vals:
                col.append((local, v))

    def add(self, doc: ParsedDocument, version: int = 1) -> int:
        """Add one parsed document (children-first block order for nested docs).
        Returns the parent's local doc id."""
        for path, sub in doc.nested_docs:
            local = self.doc_count
            self.doc_count += 1
            self._add_fields(sub, local)
            self._stored.append(None)
            self._ids.append(doc.id)
            self._types.append("__nested__")
            self._routings.append(None)
            self._versions.append(version)
            self._parent_mask.append(False)
            self._nested_paths.append(path)
        local = self.doc_count
        self.doc_count += 1
        self._add_fields(doc, local)
        self._stored.append(doc.source)
        self._ids.append(doc.id)
        self._types.append(doc.type)
        self._routings.append(doc.routing)
        self._versions.append(version)
        self._parent_mask.append(True)
        self._nested_paths.append(None)
        return local

    def _freeze_postings(self):
        """(term_dict, post_offsets, post_docs, post_freqs, pos_offsets,
        positions, sum_dfs_by_field) — from the C accumulator when present, else
        the Python dict path. Both produce the identical CSR layout (fields
        sorted by name, terms sorted per field — UTF-8 byte order equals
        Python's code-point sort — docs ascending per term)."""
        if self._pb is not None:
            names = sorted(self._pb_fids)
            name_rank = {n: r for r, n in enumerate(names)}
            fid_rank = [0] * len(self._pb_fids)
            for n, fid in self._pb_fids.items():
                fid_rank[fid] = name_rank[n]
            (terms_lists, off_b, docs_b, freqs_b, poff_b, pos_b) = \
                self._pb.freeze(fid_rank)
            term_dict: dict[str, dict[str, int]] = {}
            tid = 0
            for name in names:
                terms = terms_lists[name_rank[name]]
                term_dict[name] = {t: tid + i for i, t in enumerate(terms)}
                tid += len(terms)
            post_offsets = np.frombuffer(off_b, dtype=np.int64)
            counts = np.diff(post_offsets)
            sum_dfs_by_field = {}
            lo = 0
            for name in names:
                hi = lo + len(term_dict[name])
                sum_dfs_by_field[name] = int(counts[lo:hi].sum())
                lo = hi
            return (term_dict, post_offsets,
                    np.frombuffer(docs_b, dtype=np.int32),
                    np.frombuffer(freqs_b, dtype=np.float32),
                    np.frombuffer(poff_b, dtype=np.int64),
                    np.frombuffer(pos_b, dtype=np.int32),
                    sum_dfs_by_field)

        by_field: dict[str, list[str]] = {}
        for f, t in self._postings:
            by_field.setdefault(f, []).append(t)
        term_dict = {}
        offsets = [0]
        docs_parts, freqs_parts, pos_offsets, pos_parts = [], [], [0], []
        tid = 0
        sum_dfs_by_field = {}
        for f in sorted(by_field):
            terms = sorted(by_field[f])
            td: dict[str, int] = {}
            for t in terms:
                plist = self._postings[(f, t)]
                sum_dfs_by_field[f] = sum_dfs_by_field.get(f, 0) + len(plist)
                plist.sort(key=lambda e: e[0])
                td[t] = tid
                docs_parts.append(np.fromiter((e[0] for e in plist), dtype=np.int32, count=len(plist)))
                freqs_parts.append(np.fromiter((e[1] for e in plist), dtype=np.float32, count=len(plist)))
                for e in plist:
                    pos_parts.extend(e[2])
                    pos_offsets.append(len(pos_parts))
                offsets.append(offsets[-1] + len(plist))
                tid += 1
            term_dict[f] = td
        post_docs = np.concatenate(docs_parts) if docs_parts else np.zeros(0, np.int32)
        post_freqs = np.concatenate(freqs_parts) if freqs_parts else np.zeros(0, np.float32)
        return (term_dict, np.asarray(offsets, dtype=np.int64), post_docs,
                post_freqs, np.asarray(pos_offsets, dtype=np.int64),
                np.asarray(pos_parts, dtype=np.int32), sum_dfs_by_field)

    def freeze(self) -> "FrozenSegment":
        D = self.doc_count
        # term dictionary: per field, terms sorted (Lucene term dict is sorted; sorted
        # ordinals make range/prefix queries on keyword fields array slices)
        (term_dict, post_offsets, post_docs, post_freqs, pos_offsets, positions,
         sum_dfs_by_field) = self._freeze_postings()

        norms: dict[str, np.ndarray] = {}
        field_stats: dict[str, FieldStats] = {}
        for f, entries in self._field_lengths.items():
            lengths = np.zeros(D, dtype=np.int64)
            for local, ln in entries:
                lengths[local] += ln
            norms[f] = encode_norm(lengths)
            field_stats[f] = FieldStats(
                doc_count=int((lengths > 0).sum()), sum_ttf=int(lengths.sum()),
                sum_dfs=sum_dfs_by_field.get(f, 0),
            )

        dv_num: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        for f, entries in self._dv_num.items():
            entries.sort(key=lambda e: e[0])
            counts = np.zeros(D + 1, dtype=np.int64)
            for local, _ in entries:
                counts[local + 1] += 1
            off = np.cumsum(counts)
            vals = np.fromiter((v for _, v in entries), dtype=np.float64, count=len(entries))
            dv_num[f] = (off, vals)

        dv_str: dict[str, tuple[list[str], np.ndarray, np.ndarray]] = {}
        for f, entries in self._dv_str.items():
            entries.sort(key=lambda e: e[0])
            uniq = sorted({v for _, v in entries})
            ord_map = {v: i for i, v in enumerate(uniq)}
            counts = np.zeros(D + 1, dtype=np.int64)
            for local, _ in entries:
                counts[local + 1] += 1
            off = np.cumsum(counts)
            ords = np.fromiter((ord_map[v] for _, v in entries), dtype=np.int32, count=len(entries))
            dv_str[f] = (uniq, off, ords)

        return FrozenSegment(
            gen=self.gen,
            doc_count=D,
            term_dict=term_dict,
            post_offsets=post_offsets,
            post_docs=post_docs,
            post_freqs=post_freqs,
            pos_offsets=pos_offsets,
            positions=positions,
            norms=norms,
            field_stats=field_stats,
            dv_num=dv_num,
            dv_str=dv_str,
            stored=list(self._stored),
            ids=list(self._ids),
            types=list(self._types),
            routings=list(self._routings),
            versions=np.asarray(self._versions, dtype=np.int64),
            live=np.ones(D, dtype=bool),
            parent_mask=np.asarray(self._parent_mask, dtype=bool),
            nested_paths=list(self._nested_paths),
        )


@dataclass
class FrozenSegment:
    gen: int
    doc_count: int
    term_dict: dict[str, dict[str, int]]
    post_offsets: np.ndarray  # int64[T+1]
    post_docs: np.ndarray  # int32[P]
    post_freqs: np.ndarray  # float32[P]
    pos_offsets: np.ndarray  # int64[P+1]
    positions: np.ndarray  # int32[PP]
    norms: dict[str, np.ndarray]  # field -> uint8[D]
    field_stats: dict[str, FieldStats]
    dv_num: dict[str, tuple[np.ndarray, np.ndarray]]  # field -> (offsets[D+1], values)
    dv_str: dict[str, tuple[list[str], np.ndarray, np.ndarray]]  # (sorted terms, offsets, ords)
    stored: list[dict | None]
    ids: list[str | None]
    types: list[str | None]
    routings: list[str | None]
    versions: np.ndarray  # int64[D]
    live: np.ndarray  # bool[D] — mutable tombstones
    parent_mask: np.ndarray  # bool[D]
    nested_paths: list[str | None]
    _device_cache: dict = dc_field(default_factory=dict, repr=False, compare=False)
    # monotonic tombstone generation: any change to `live` bumps it (process-wide
    # counter so copy-on-write views get distinct generations) — cheap freshness key
    # for device-side caches of the live mask (e.g. the mesh serving ShardedIndex)
    live_gen: int = 0

    # --- term access --------------------------------------------------------
    def term_id(self, field: str, term: str) -> int | None:
        td = self.term_dict.get(field)
        if td is None:
            return None
        return td.get(term)

    def doc_freq(self, field: str, term: str) -> int:
        tid = self.term_id(field, term)
        if tid is None:
            return 0
        return int(self.post_offsets[tid + 1] - self.post_offsets[tid])

    def postings(self, field: str, term: str) -> tuple[np.ndarray, np.ndarray]:
        tid = self.term_id(field, term)
        if tid is None:
            return np.zeros(0, np.int32), np.zeros(0, np.float32)
        s, e = self.post_offsets[tid], self.post_offsets[tid + 1]
        return self.post_docs[s:e], self.post_freqs[s:e]

    def term_positions(self, field: str, term: str) -> list[np.ndarray]:
        """Per matching doc, the token positions of this term (for phrase queries)."""
        tid = self.term_id(field, term)
        if tid is None:
            return []
        s, e = int(self.post_offsets[tid]), int(self.post_offsets[tid + 1])
        return [
            self.positions[self.pos_offsets[i] : self.pos_offsets[i + 1]]
            for i in range(s, e)
        ]

    def sorted_terms(self, field: str) -> tuple[list[str], int]:
        """(the field's terms in dictionary order, the first one's term id).
        Term ids were handed out in (field, term) order (freeze, store
        recovery), so a field's dict IS its sorted dictionary and term i of
        the list has the id `first + i`: a range of the list is a range of
        term ids, of the CSR postings and of the device's block rows. Built at
        first use and kept with the segment's other derived state (views
        after deletes share it: the dictionary never changes)."""
        kept = self._device_cache.setdefault("sorted_terms", {})
        entry = kept.get(field)
        if entry is None:
            td = self.term_dict.get(field) or {}
            terms = list(td)
            entry = kept[field] = (terms, td[terms[0]] if terms else 0)
        return entry

    def terms_for_field(self, field: str) -> list[str]:
        """The field's terms in dictionary order: the kept list, not a copy."""
        return self.sorted_terms(field)[0]

    # --- doc access ---------------------------------------------------------
    def live_count(self) -> int:
        # memoized on the tombstone generation: the merge policy's live-
        # prorated sizing calls this for EVERY segment on every 0.5 s
        # periodic tick, and the raw count is an O(doc_count) numpy pass.
        # delete_doc/with_deletes bump live_gen, invalidating the memo; the
        # one direct `live` replacement (store recovery's tombstone load)
        # happens on a fresh segment before any count is taken
        cached = self._device_cache.get("live_count")
        if cached is not None and cached[0] == self.live_gen:
            return cached[1]
        n = int((self.live & self.parent_mask).sum())
        self._device_cache["live_count"] = (self.live_gen, n)
        return n

    def delete_doc(self, local: int):
        """Tombstone a doc and its nested children block (in place — use with_deletes
        for copy-on-write semantics that preserve already-acquired searchers)."""
        global _LIVE_GEN
        self.live[local] = False
        self._device_cache.pop("live", None)
        _LIVE_GEN += 1
        self.live_gen = _LIVE_GEN
        i = local - 1
        while i >= 0 and not self.parent_mask[i] and self.nested_paths[i] is not None \
                and self.ids[i] == self.ids[local]:
            self.live[i] = False
            i -= 1

    def with_deletes(self, locals_to_delete) -> "FrozenSegment":
        """Copy-on-write tombstoning: returns a NEW segment object sharing all large
        arrays but with a fresh live bitmap (and a fresh packed-live device view), so a
        previously acquired Searcher keeps an immutable point-in-time liveDocs — the
        invariant Lucene readers guarantee (Engine.acquireSearcher semantics)."""
        import dataclasses

        new = dataclasses.replace(self, live=self.live.copy(),
                                  _device_cache=dict(self._device_cache))
        # pack coordination state is PER VIEW: a copied in-flight future would
        # resolve against the OLD view's cache dict and strand this view's
        # waiters in a done-future loop (ops/device_index.packed_for); the
        # new view re-coordinates its own pack/remask
        new._device_cache.pop("pack_future", None)
        new._device_cache.pop("pack_hint", None)
        for local in locals_to_delete:
            new.delete_doc(local)
        # share the packed postings but give the new view its own live mask
        packed = new._device_cache.get("packed")
        if packed is not None:
            new._device_cache["packed"] = dataclasses.replace(packed)
            new._device_cache.pop("live", None)
        return new

    def num_values(self, field: str, local: int) -> np.ndarray:
        col = self.dv_num.get(field)
        if col is None:
            return np.zeros(0)
        off, vals = col
        return vals[off[local] : off[local + 1]]

    def str_values(self, field: str, local: int) -> list[str]:
        col = self.dv_str.get(field)
        if col is None:
            return []
        uniq, off, ords = col
        return [uniq[o] for o in ords[off[local] : off[local + 1]]]

    def estimated_bytes(self) -> int:
        # memoized: the merge policy sizes every segment on every
        # periodic_refresh tick (2 Hz × shards × segments on the write-heavy
        # path); the underlying arrays are immutable post-freeze, so the sum
        # never changes. Copy-on-write views share the arrays AND the cached
        # value (with_deletes shallow-copies the device cache)
        n = self._device_cache.get("est_bytes")
        if n is not None:
            return n
        n = self.post_docs.nbytes + self.post_freqs.nbytes + self.positions.nbytes
        n += sum(a.nbytes for a in self.norms.values())
        n += sum(o.nbytes + v.nbytes for o, v in self.dv_num.values())
        self._device_cache["est_bytes"] = n
        return n


def merge_segments(segments: list[FrozenSegment], gen: int) -> FrozenSegment:
    """Merge live docs of several segments into one new segment (Lucene merge
    equivalent). Rebuilds through a SegmentBuilder keyed on raw postings — exact since
    segments already hold analyzed terms."""
    builder = SegmentBuilder(gen)
    for seg in segments:
        # reconstruct per-doc postings from CSR (invert)
        per_doc_postings: list[dict[str, list[tuple[str, int]]]] = [
            {} for _ in range(seg.doc_count)
        ]
        for f, td in seg.term_dict.items():
            for term, tid in td.items():
                s, e = int(seg.post_offsets[tid]), int(seg.post_offsets[tid + 1])
                for i in range(s, e):
                    local = int(seg.post_docs[i])
                    poss = seg.positions[seg.pos_offsets[i] : seg.pos_offsets[i + 1]]
                    per_doc_postings[local].setdefault(f, []).extend(
                        (term, int(p)) for p in poss
                    )
        local = 0
        while local < seg.doc_count:
            # collect one block: children (non-parent) run + their parent
            block_start = local
            while local < seg.doc_count and not seg.parent_mask[local]:
                local += 1
            if local >= seg.doc_count:
                break
            parent = local
            local += 1
            if not seg.live[parent]:
                continue
            doc = ParsedDocument(
                id=seg.ids[parent] or "",
                type=seg.types[parent] or "",
                uid=f"{seg.types[parent]}#{seg.ids[parent]}",
                source=seg.stored[parent] or {},
                routing=seg.routings[parent],
            )
            doc.postings = {
                f: sorted(terms, key=lambda tp: tp[1])
                for f, terms in per_doc_postings[parent].items()
            }
            # norm-bearing fields only: the mapper never records lengths for
            # meta fields (_uid/_id/_type), so a merged segment must not
            # manufacture norms the sources lacked — scores (and the
            # compaction concat pack) stay identical across a merge
            doc.field_lengths = {f: len(t) for f, t in doc.postings.items()
                                 if f in seg.norms}
            for f, (off, vals) in seg.dv_num.items():
                v = vals[off[parent] : off[parent + 1]]
                if len(v):
                    doc.doc_values_num[f] = list(v)
            for f in seg.dv_str:
                v = seg.str_values(f, parent)
                if v:
                    doc.doc_values_str[f] = v
            for child in range(block_start, parent):
                sub = ParsedDocument(
                    id=doc.id, type=doc.type, uid=doc.uid,
                    source={},
                )
                sub.postings = {
                    f: sorted(terms, key=lambda tp: tp[1])
                    for f, terms in per_doc_postings[child].items()
                }
                sub.field_lengths = {f: len(t) for f, t in sub.postings.items()
                                     if f in seg.norms}
                doc.nested_docs.append((seg.nested_paths[child] or "", sub))
            builder.add(doc, version=int(seg.versions[parent]))
    return builder.freeze()
