"""Filter tree: non-scoring matchers evaluated per segment as boolean doc masks.

Analogue of the reference's 29 filter parsers (index/query/*FilterParser.java —
SURVEY.md §2.3) and its per-index weighted-LRU filter cache (index/cache/filter/).
A filter evaluates to bool[doc_count] per segment; masks combine with numpy logical ops
and feed the device scorer as a score mask (filters never contribute to scores, matching
FilteredQuery/BooleanFilter semantics).

Evaluation is host-side numpy over the segment's CSR postings / columnar doc values —
cheap, and the per-(segment, filter-key) result is cached exactly like the reference's
filter cache. Range/term filters over single-valued numeric columns additionally have a
device fast path via PackedSegment.dv_single (used by function_score and sort).
"""

from __future__ import annotations

import re
import threading
from dataclasses import dataclass, field as dc_field
from typing import Any

import numpy as np

from ..common.errors import QueryParsingError
from ..index.segment import FrozenSegment
from ..mapper.core import parse_date_math
from ..ops.device_index import HOST_ONLY_FIELDS, RecentKeys
from . import multiterm


class Filter:
    def key(self) -> str:
        raise NotImplementedError

    def evaluate(self, seg: FrozenSegment, ctx) -> np.ndarray:
        raise NotImplementedError

    def cacheable(self) -> bool:
        """False for masks that depend on state OUTSIDE the segment (e.g. the
        parent/child join spans the whole shard): the per-segment filter cache
        would serve stale results after other segments change. Composites
        propagate from their children."""
        return True

    def block_rows(self, seg, packed, ctx):
        """Where the chip can build this filter's row from block rows of the
        segment's postings plane (execute._filter_mask_matrix): (the rows
        int32 ascending, terms matched, runs of rows, whether the whole
        field's dictionary was tested). None: the host evaluates the row."""
        return None


_SIGHTINGS_LOCK = threading.Lock()  # leaf: guards every segment's RecentKeys


def segment_mask(seg: FrozenSegment, f: Filter, ctx) -> np.ndarray:
    """Cached evaluation (the segment's host filter cache). A mask is kept
    from its second sighting within the recent history on (RecentKeys, as
    the device filter cache admits): a filter that never recurs is evaluated
    and dropped. ctx carries the mapper service."""
    if not f.cacheable():
        return f.evaluate(seg, ctx)
    cache = seg._device_cache.setdefault("filters", {})
    k = f.key()
    m = cache.get(k)
    if m is None:
        m = f.evaluate(seg, ctx)
        with _SIGHTINGS_LOCK:
            seen = seg._device_cache.get("filter_sightings")
            if seen is None:
                seen = seg._device_cache["filter_sightings"] = RecentKeys()
            keep = seen.sight(k) >= 2
        if keep:
            cache[k] = m
    return m


def _postings_mask(seg: FrozenSegment, field: str, term: str) -> np.ndarray:
    mask = np.zeros(seg.doc_count, dtype=bool)
    docs, _ = seg.postings(field, str(term))
    mask[docs] = True
    return mask


def _num_column_mask(seg: FrozenSegment, field: str, pred) -> np.ndarray:
    col = seg.dv_num.get(field)
    mask = np.zeros(seg.doc_count, dtype=bool)
    if col is None:
        return mask
    off, vals = col
    if len(vals) == 0:
        return mask
    # each value's document, a pure function of the immutable column: kept
    # with the segment, so a filter that is the whole query (a dashboard's
    # window over a million events) pays the predicate and one indexed store
    ckey = ("doc_of_val", field)
    doc_of_val = seg._device_cache.get(ckey)
    if doc_of_val is None:
        doc_of_val = seg._device_cache[ckey] = np.repeat(
            np.arange(seg.doc_count), np.diff(off))
    mask[doc_of_val[pred(vals)]] = True
    return mask


@dataclass
class TermFilter(Filter):
    field: str
    value: Any

    def key(self):
        return f"term:{self.field}:{self.value}"

    def evaluate(self, seg, ctx):
        ft = ctx.field_type(self.field)
        if ft is not None and ft.is_numeric:
            coerced = ft.coerce(self.value)
            return _num_column_mask(seg, self.field, lambda v: v == float(coerced))
        return _postings_mask(seg, self.field, _index_term(ctx, self.field, self.value))


@dataclass
class TermsFilter(Filter):
    field: str
    values: list

    def key(self):
        return f"terms:{self.field}:{sorted(map(str, self.values))!r}"

    def evaluate(self, seg, ctx):
        ft = ctx.field_type(self.field)
        mask = np.zeros(seg.doc_count, dtype=bool)
        if ft is not None and ft.is_numeric:
            coerced = {float(ft.coerce(v)) for v in self.values}
            arr = np.asarray(sorted(coerced))
            return _num_column_mask(seg, self.field, lambda v: np.isin(v, arr))
        for v in self.values:
            mask |= _postings_mask(seg, self.field, _index_term(ctx, self.field, v))
        return mask


@dataclass
class RangeFilter(Filter):
    field: str
    gte: Any = None
    gt: Any = None
    lte: Any = None
    lt: Any = None

    def key(self):
        return f"range:{self.field}:{self.gte}:{self.gt}:{self.lte}:{self.lt}"

    def _bounds_numeric(self, ft) -> tuple[float, float, bool, bool]:
        def conv(v):
            if ft is not None and ft.type == "date" and isinstance(v, str):
                return float(parse_date_math(v))
            return float(ft.coerce(v)) if ft is not None and ft.is_numeric else float(v)

        lo, lo_inc = -np.inf, True
        hi, hi_inc = np.inf, True
        if self.gte is not None:
            lo = conv(self.gte)
        if self.gt is not None:
            lo, lo_inc = conv(self.gt), False
        if self.lte is not None:
            hi = conv(self.lte)
        if self.lt is not None:
            hi, hi_inc = conv(self.lt), False
        return lo, hi, lo_inc, hi_inc

    def evaluate(self, seg, ctx):
        ft = ctx.field_type(self.field)
        if ft is None or ft.is_numeric:
            lo, hi, lo_inc, hi_inc = self._bounds_numeric(ft)

            def pred(v):
                lower = v >= lo if lo_inc else v > lo
                upper = v <= hi if hi_inc else v < hi
                return lower & upper

            return _num_column_mask(seg, self.field, pred)
        # lexicographic range over the sorted term dictionary (keyword fields)
        mask = np.zeros(seg.doc_count, dtype=bool)
        for term in seg.terms_for_field(self.field):
            if self.gte is not None and term < str(self.gte):
                continue
            if self.gt is not None and term <= str(self.gt):
                continue
            if self.lte is not None and term > str(self.lte):
                break
            if self.lt is not None and term >= str(self.lt):
                break
            mask |= _postings_mask(seg, self.field, term)
        return mask


@dataclass(kw_only=True)
class MultiTermFilter(Filter):
    """A filter whose match set is the union of the postings of the terms a
    pattern names (`kind`, `pattern`: search/multiterm.py, the ONE expansion
    the host scorer shares): PrefixFilter, WildcardFilter, RegexpFilter.

    `evaluate` is the host's row. execute._filter_mask_matrix asks
    `block_rows` first: the block rows of the postings plane the expansion
    names, which a launch ORs into the mask row ON the chip
    (scoring.build_multiterm_rows), or None where the row stays the host's:
    a field whose postings are not in the device planes, or more rows than
    the ladder's last rung. `cached` False is the wrapper of a multi-term
    QUERY (execute._multiterm_lowering): the reference caches filters, never
    queries, so every such search builds its row. That lowering expands the
    pattern to count its rows, and hands what it expanded to the launch in
    `expanded`: (segment, Expansion) pairs that HOLD their segments, so only
    a filter made for one request carries any. A parsed filter can outlive
    its request and its segments (a registered percolator query meets a new
    segment every document) and expands where it is asked."""

    cached: bool = True
    expanded: tuple = dc_field(default=(), repr=False, compare=False)
    kind = ""  # multiterm.PREFIX | WILDCARD | REGEXP; `pattern` is the subclass'

    def key(self):
        return f"{self.kind}:{self.field}:{self.pattern}"

    def cacheable(self):
        return self.cached

    def expansion(self, seg):
        for held, exp in self.expanded:
            if held is seg:
                return exp
        return multiterm.expand(seg, self.field, self.kind, self.pattern)

    def evaluate(self, seg, ctx):
        return multiterm.docs_mask(seg, self.expansion(seg).tids)

    def host_reason(self, ctx) -> str | None:
        """Why the field's postings are not in the device planes (the
        profile API's fallback reason), or None where they are."""
        if self.field in HOST_ONLY_FIELDS:
            return "host_only_field"
        ft = ctx.field_type(self.field)
        if ft is not None and ft.is_numeric:
            return "multiterm_numeric_field"
        return None

    def block_rows(self, seg, packed, ctx):
        from ..ops.scoring import MULTITERM_RUNGS

        if self.host_reason(ctx):
            return None
        exp = self.expansion(seg)
        if exp.rows > MULTITERM_RUNGS[-1]:
            return None
        rows, runs = multiterm.ranges_of(packed.term_blk_start, exp.tids)
        return rows.astype(np.int32), len(exp.tids), runs, exp.whole_field


@dataclass
class PrefixFilter(MultiTermFilter):
    field: str
    prefix: str
    kind = "prefix"

    @property
    def pattern(self):
        return self.prefix


@dataclass
class WildcardFilter(MultiTermFilter):
    """`*` any run of characters, `?` any one (the wrapper of a
    WildcardQuery: ES 1.x has no wildcard filter of its own)."""

    field: str
    pattern: str
    kind = "wildcard"


@dataclass
class ExistsFilter(Filter):
    field: str

    def key(self):
        return f"exists:{self.field}"

    def evaluate(self, seg, ctx):
        mask = np.zeros(seg.doc_count, dtype=bool)
        td = seg.term_dict.get(self.field)
        if td:
            for tid in td.values():
                s, e = seg.post_offsets[tid], seg.post_offsets[tid + 1]
                mask[seg.post_docs[s:e]] = True
        col = seg.dv_num.get(self.field)
        if col is not None:
            off, _ = col
            mask |= np.diff(off) > 0
        scol = seg.dv_str.get(self.field)
        if scol is not None:
            _, off, _ = scol
            mask |= np.diff(off) > 0
        return mask


@dataclass
class MissingFilter(Filter):
    field: str

    def key(self):
        return f"missing:{self.field}"

    def evaluate(self, seg, ctx):
        return ~ExistsFilter(self.field).evaluate(seg, ctx)


@dataclass
class IdsFilter(Filter):
    ids: list
    types: list = dc_field(default_factory=list)

    def key(self):
        return f"ids:{sorted(self.types)}:{sorted(map(str, self.ids))!r}"

    def evaluate(self, seg, ctx):
        mask = np.zeros(seg.doc_count, dtype=bool)
        idset = set(map(str, self.ids))
        for local in range(seg.doc_count):
            if seg.parent_mask[local] and seg.ids[local] in idset:
                if not self.types or seg.types[local] in self.types:
                    mask[local] = True
        return mask


@dataclass
class TypeFilter(Filter):
    type: str

    def key(self):
        return f"type:{self.type}"

    def evaluate(self, seg, ctx):
        return np.asarray([t == self.type for t in seg.types], dtype=bool)


@dataclass
class MatchAllFilter(Filter):
    def key(self):
        return "match_all"

    def evaluate(self, seg, ctx):
        return np.ones(seg.doc_count, dtype=bool)


@dataclass
class BoolFilter(Filter):
    must: list = dc_field(default_factory=list)
    should: list = dc_field(default_factory=list)
    must_not: list = dc_field(default_factory=list)

    def key(self):
        return (
            "bool:" + "&".join(f.key() for f in self.must)
            + "|" + ";".join(f.key() for f in self.should)
            + "!" + ";".join(f.key() for f in self.must_not)
        )

    def evaluate(self, seg, ctx):
        mask = np.ones(seg.doc_count, dtype=bool)
        for f in self.must:
            mask &= segment_mask(seg, f, ctx)
        if self.should:
            smask = np.zeros(seg.doc_count, dtype=bool)
            for f in self.should:
                smask |= segment_mask(seg, f, ctx)
            mask &= smask
        for f in self.must_not:
            mask &= ~segment_mask(seg, f, ctx)
        return mask

    def cacheable(self):
        return all(f.cacheable()
                   for f in (*self.must, *self.should, *self.must_not))


@dataclass
class NotFilter(Filter):
    inner: Filter

    def key(self):
        return f"not:{self.inner.key()}"

    def evaluate(self, seg, ctx):
        return ~segment_mask(seg, self.inner, ctx)

    def cacheable(self):
        return self.inner.cacheable()


@dataclass
class QueryWrapperFilter(Filter):
    """Wraps a scoring query as a filter (ref: FQueryFilterParser / query filter)."""

    query: Any  # Query — evaluated via the host scorer for its match mask

    def key(self):
        return f"query:{self.query!r}"

    def evaluate(self, seg, ctx):
        from .execute import host_match_mask

        return host_match_mask(self.query, seg, ctx)


@dataclass
class NestedFilter(Filter):
    path: str
    inner: Any  # Query or Filter on child docs

    def key(self):
        return f"nested:{self.path}:{getattr(self.inner, 'key', lambda: repr(self.inner))()}"

    def evaluate(self, seg, ctx):
        from .execute import child_match_to_parents

        return child_match_to_parents(seg, ctx, self.path, self.inner)[0]


@dataclass
class GeoDistanceFilter(Filter):
    field: str
    lat: float
    lon: float
    distance_m: float

    def key(self):
        return f"geodist:{self.field}:{self.lat}:{self.lon}:{self.distance_m}"

    def evaluate(self, seg, ctx):
        return _geo_points_mask(
            seg, self.field,
            lambda lats, lons: haversine_m(self.lat, self.lon, lats, lons)
            <= self.distance_m)


def _geo_points_mask(seg, field: str, hit_fn) -> np.ndarray:
    """Doc mask from the multi-valued point columns: hit_fn(lats, lons) -> bool[V]
    per value, OR-scattered to docs — shared by every point-based geo filter."""
    lat_col = seg.dv_num.get(f"{field}.lat")
    lon_col = seg.dv_num.get(f"{field}.lon")
    mask = np.zeros(seg.doc_count, dtype=bool)
    if lat_col is None or lon_col is None:
        return mask
    off, lats = lat_col
    _, lons = lon_col
    hit = hit_fn(lats, lons)
    counts = np.diff(off)
    doc_of_val = np.repeat(np.arange(seg.doc_count), counts)
    np.logical_or.at(mask, doc_of_val, hit)
    return mask


@dataclass
class GeoShapeFilter(Filter):
    """Docs whose stored shape relates to the query shape.

    ref: GeoShapeFilter/GeoShapeQueryParser.java:1 — the reference tests prefix-tree
    cell terms; here the shape column is decoded once per segment (cached) and the
    relation computed exactly (common/geo.py)."""

    field: str
    shape: tuple  # normalized (kind, data)
    relation: str = "intersects"  # intersects | within | disjoint

    def key(self):
        import json

        return f"geoshape:{self.field}:{self.relation}:" \
               f"{json.dumps(self.shape, sort_keys=True)}"

    def _doc_shapes(self, seg):
        """Parsed per-doc shape lists, cached on the segment."""
        import json

        cache = seg._device_cache.setdefault("geo_shapes", {})
        parsed = cache.get(self.field)
        if parsed is None:
            parsed = [None] * seg.doc_count
            for d in range(seg.doc_count):
                vals = seg.str_values(self.field, d)
                if vals:
                    parsed[d] = [tuple(json.loads(v)) for v in vals]
            cache[self.field] = parsed
        return parsed

    def evaluate(self, seg, ctx):
        from ..common.geo import shape_within, shapes_intersect

        mask = np.zeros(seg.doc_count, dtype=bool)
        q = self.shape
        for d, shapes in enumerate(self._doc_shapes(seg)):
            if not shapes:
                continue
            if self.relation == "within":
                mask[d] = any(shape_within(s, q) for s in shapes)
            elif self.relation == "disjoint":
                mask[d] = not any(shapes_intersect(s, q) for s in shapes)
            else:
                mask[d] = any(shapes_intersect(s, q) for s in shapes)
        return mask


@dataclass
class GeohashCellFilter(Filter):
    """Docs whose geo_point falls in the given geohash cell (optionally + the 8
    neighbors). ref: index/query/GeohashCellFilter.java:1 — the reference matches
    indexed geohash prefix terms; here the cell is a bbox test over the point
    columns (identical semantics: a point is in the cell iff the cell geohash
    prefixes the point's geohash)."""

    field: str
    geohash: str
    neighbors: bool = False

    def key(self):
        return f"geohashcell:{self.field}:{self.geohash}:{self.neighbors}"

    def evaluate(self, seg, ctx):
        from ..common.geo import geohash_bbox, geohash_neighbors

        cells = [self.geohash] + (geohash_neighbors(self.geohash)
                                  if self.neighbors else [])

        def hit(lats, lons):
            h = np.zeros(len(lats), dtype=bool)
            for cell in cells:
                lat_lo, lat_hi, lon_lo, lon_hi = geohash_bbox(cell)
                h |= ((lats >= lat_lo) & (lats < lat_hi)
                      & (lons >= lon_lo) & (lons < lon_hi))
            return h

        return _geo_points_mask(seg, self.field, hit)


@dataclass
class GeoBoundingBoxFilter(Filter):
    field: str
    top: float
    left: float
    bottom: float
    right: float

    def key(self):
        return f"geobb:{self.field}:{self.top}:{self.left}:{self.bottom}:{self.right}"

    def evaluate(self, seg, ctx):
        def hit(lats, lons):
            h = (lats <= self.top) & (lats >= self.bottom)
            if self.left <= self.right:
                return h & (lons >= self.left) & (lons <= self.right)
            return h & ((lons >= self.left) | (lons <= self.right))  # dateline

        return _geo_points_mask(seg, self.field, hit)


@dataclass
class ScriptFilter(Filter):
    script: str
    params: dict = dc_field(default_factory=dict)

    def key(self):
        return f"script:{self.script}:{sorted(self.params.items())!r}"

    def evaluate(self, seg, ctx):
        from ..script import compile_script

        fn = compile_script(self.script, self.params)
        mask = np.zeros(seg.doc_count, dtype=bool)
        for local in range(seg.doc_count):
            if seg.parent_mask[local]:
                mask[local] = bool(fn(DocAccess(seg, local)))
        return mask


@dataclass
class RegexpFilter(MultiTermFilter):
    field: str
    pattern: str
    kind = "regexp"


MULTI_TERM_FILTERS = {cls.kind: cls for cls in (
    PrefixFilter, WildcardFilter, RegexpFilter)}


EARTH_RADIUS_M = 6371008.7714


@dataclass
class HasChildFilter(Filter):
    """Parent docs with a matching child — the non-scoring filter form
    (ref: index/query/HasChildFilterParser.java:1). Wraps the query-form's
    cross-segment join (execute._shard_join) because parent/child links span
    segments; the per-segment mask slices out of that shard-level join."""

    query: Any  # HasChildQuery or HasParentQuery with score_mode "none"

    def key(self):
        q = self.query
        inner_key = repr(q.query)
        return f"haschildf:{type(q).__name__}:{getattr(q, 'child_type', getattr(q, 'parent_type', None))}:{inner_key}"

    def cacheable(self):
        # the join spans the whole shard: a per-segment cached mask would go
        # stale when a child lands in (or leaves) ANOTHER segment
        return False

    def evaluate(self, seg, ctx):
        from .execute import _shard_join

        # one join per (searcher, filter): the searcher's segment set is
        # immutable for its lifetime, so caching there is both correct and
        # avoids recomputing the shard-wide join once per segment
        cache = getattr(ctx.searcher, "_join_cache", None)
        if cache is None:
            cache = ctx.searcher._join_cache = {}
        join = cache.get(self.key())
        if join is None:
            join = cache[self.key()] = _shard_join(ctx, self.query)
        for si, s in enumerate(ctx.searcher.segments):
            if s is seg:
                return join[si][1]
        return np.zeros(seg.doc_count, dtype=bool)


@dataclass
class GeoPolygonFilter(Filter):
    """Docs with a point inside the polygon (ray casting over the value columns).

    ref: index/query/GeoPolygonFilterParser.java:1 + GeoPolygonFilter.java —
    the reference walks polygon edges per point (pointInPolygon); here the
    crossing test vectorizes over every stored point at once."""

    field: str
    points: tuple  # ((lat, lon), ...) — closed or open ring, either works

    def key(self):
        return f"geopoly:{self.field}:{self.points}"

    def evaluate(self, seg, ctx):
        pts = [p for p in self.points]
        if len(pts) > 1 and pts[0] == pts[-1]:
            pts = pts[:-1]  # drop the explicit closing point
        lat_v = np.asarray([p[0] for p in pts])
        lon_v = np.asarray([p[1] for p in pts])

        def inside(lats, lons):
            hit = np.zeros(len(lats), dtype=bool)
            n = len(lat_v)
            for i in range(n):
                j = (i - 1) % n
                crosses = ((lat_v[i] > lats) != (lat_v[j] > lats)) & (
                    lons < (lon_v[j] - lon_v[i]) * (lats - lat_v[i])
                    / (lat_v[j] - lat_v[i] + 1e-300) + lon_v[i])
                hit ^= crosses
            return hit

        return _geo_points_mask(seg, self.field, inside)


@dataclass
class GeoDistanceRangeFilter(Filter):
    """Docs whose point distance from the origin falls in [from, to).

    ref: index/query/GeoDistanceRangeFilterParser.java:1 — the ring/doughnut
    variant of geo_distance; bounds honor include_lower/include_upper."""

    field: str
    lat: float
    lon: float
    from_m: float | None = None
    to_m: float | None = None
    include_lower: bool = True
    include_upper: bool = True

    def key(self):
        return (f"geodistrange:{self.field}:{self.lat}:{self.lon}:"
                f"{self.from_m}:{self.to_m}:{self.include_lower}:{self.include_upper}")

    def evaluate(self, seg, ctx):
        def hit(lats, lons):
            d = haversine_m(self.lat, self.lon, lats, lons)
            ok = np.ones(len(d), dtype=bool)
            if self.from_m is not None:
                ok &= (d >= self.from_m) if self.include_lower else (d > self.from_m)
            if self.to_m is not None:
                ok &= (d <= self.to_m) if self.include_upper else (d < self.to_m)
            return ok

        return _geo_points_mask(seg, self.field, hit)


@dataclass
class IndicesFilter(Filter):
    """Filter that applies only when searching the named indices; other indices
    see no_match_filter (default all — ref: IndicesFilterParser.java:1).
    Needs the shard's index name: ShardContext.index_name (None = assume match,
    the single-index embedded case)."""

    indices: tuple
    filter: Any = None
    no_match_filter: Any = None  # None = match_all
    no_match_none: bool = False

    def key(self):
        inner_key = getattr(self.filter, "key", lambda: repr(self.filter))()
        nm_key = (getattr(self.no_match_filter, "key",
                          lambda: repr(self.no_match_filter))()
                  if self.no_match_filter is not None else "all")
        return f"indices:{self.indices}:{inner_key}:{nm_key}:{self.no_match_none}"

    def cacheable(self):
        return (self.filter is None or self.filter.cacheable()) and (
            self.no_match_filter is None or self.no_match_filter.cacheable())

    def _matches_index(self, ctx) -> bool:
        name = getattr(ctx, "index_name", None)
        if name is None:
            return True
        import fnmatch

        return any(fnmatch.fnmatch(name, pat) for pat in self.indices)

    def evaluate(self, seg, ctx):
        if self._matches_index(ctx):
            return segment_mask(seg, self.filter, ctx)
        if self.no_match_none:
            return np.zeros(seg.doc_count, dtype=bool)
        if self.no_match_filter is None:
            return np.ones(seg.doc_count, dtype=bool)
        return segment_mask(seg, self.no_match_filter, ctx)


def haversine_m(lat1, lon1, lat2, lon2):
    la1, lo1 = np.radians(lat1), np.radians(lon1)
    la2, lo2 = np.radians(lat2), np.radians(lon2)
    a = np.sin((la2 - la1) / 2) ** 2 + np.cos(la1) * np.cos(la2) * np.sin((lo2 - lo1) / 2) ** 2
    return 2 * EARTH_RADIUS_M * np.arcsin(np.sqrt(np.clip(a, 0, 1)))


_DIST_RE = re.compile(r"^\s*([\d.]+)\s*([a-zA-Z]*)\s*$")
_DIST_UNITS = {
    "m": 1.0, "meters": 1.0, "km": 1000.0, "kilometers": 1000.0,
    "mi": 1609.344, "miles": 1609.344, "yd": 0.9144, "ft": 0.3048,
    "in": 0.0254, "cm": 0.01, "mm": 0.001, "nmi": 1852.0, "": 1.0,
}


def parse_distance(s) -> float:
    if isinstance(s, (int, float)):
        return float(s)
    m = _DIST_RE.match(str(s))
    if not m:
        raise QueryParsingError(f"failed to parse distance [{s}]")
    return float(m.group(1)) * _DIST_UNITS.get(m.group(2).lower(), 1.0)


class DocAccess:
    """Per-doc field access for scripts: doc['field'].value style."""

    def __init__(self, seg: FrozenSegment, local: int):
        self.seg = seg
        self.local = local

    def __getitem__(self, field: str):
        nums = self.seg.num_values(field, self.local)
        if len(nums):
            return FieldVal(list(nums))
        return FieldVal(self.seg.str_values(field, self.local))


class FieldVal:
    def __init__(self, values: list):
        self.values = values

    @property
    def value(self):
        return self.values[0] if self.values else None

    @property
    def empty(self):
        return not self.values


def _index_term(ctx, field: str, value) -> str:
    """How a term/terms filter value maps to an indexed token: not_analyzed fields keep
    the raw value; analyzed fields take the single analyzed token (ES term filter
    semantics: no analysis — we mirror that by using the raw value lowercased only when
    the target field is analyzed with a lowercasing chain is NOT applied — raw match)."""
    return str(value)
