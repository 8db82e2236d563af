"""Sort: field / score / geo-distance / script sort keys over fielddata columns.

Analogue of search/sort/ (SURVEY.md §2.5): sort builders → per-doc comparators over
fielddata. Here: per-segment vectorized key extraction → np.lexsort, with the standard
multi-valued `mode` reductions (min/max/avg/sum) and `missing` handling (_last/_first
or a constant). Sort tuples travel with hits so the multi-shard merge can re-compare
them (SearchPhaseController.sortDocs field-sort variant).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from ..common.errors import QueryParsingError
from .filters import haversine_m, parse_distance


class SortSpec:
    __slots__ = ("field", "order", "mode", "missing", "kind", "lat", "lon", "unit",
                 "script", "params")

    def __init__(self, field: str, order: str = "asc", mode: str | None = None,
                 missing: Any = "_last", kind: str = "field", lat=0.0, lon=0.0,
                 unit=1.0, script=None, params=None):
        self.field = field
        self.order = order
        self.mode = mode
        self.missing = missing
        self.kind = kind
        self.lat = lat
        self.lon = lon
        self.unit = unit
        self.script = script
        self.params = params or {}

    @property
    def reverse(self) -> bool:
        return self.order == "desc"


def parse_sort(spec) -> list[SortSpec]:
    """"sort": ["_score", {"price": "desc"}, {"_geo_distance": {...}}, "field"]"""
    if spec is None:
        return []
    if not isinstance(spec, list):
        spec = [spec]
    out: list[SortSpec] = []
    for item in spec:
        if isinstance(item, str):
            if item == "_score":
                out.append(SortSpec("_score", "desc", kind="score"))
            else:
                out.append(SortSpec(item, "asc"))
            continue
        if not isinstance(item, dict) or len(item) != 1:
            raise QueryParsingError(f"invalid sort spec {item!r}")
        (field, opts), = item.items()
        if field == "_score":
            order = opts if isinstance(opts, str) else opts.get("order", "desc")
            out.append(SortSpec("_score", order, kind="score"))
        elif field == "_geo_distance":
            opts = dict(opts)
            order = opts.pop("order", "asc")
            unit = parse_distance("1" + opts.pop("unit", "km"))
            mode = opts.pop("mode", None)
            (gfield, point), = opts.items()
            if isinstance(point, dict):
                lat, lon = float(point["lat"]), float(point["lon"])
            elif isinstance(point, str):
                lat, lon = (float(x) for x in point.split(","))
            else:
                lon, lat = float(point[0]), float(point[1])
            out.append(SortSpec(gfield, order, mode, kind="geo", lat=lat, lon=lon, unit=unit))
        elif field == "_script":
            out.append(SortSpec("_script", opts.get("order", "asc"), kind="script",
                                script=opts.get("script"), params=opts.get("params")))
        else:
            if isinstance(opts, str):
                out.append(SortSpec(field, opts))
            else:
                out.append(SortSpec(field, opts.get("order", "asc"), opts.get("mode"),
                                    opts.get("missing", "_last")))
    return out


def _reduce_multi(off: np.ndarray, vals: np.ndarray, D: int, mode: str) -> np.ndarray:
    out = np.full(D, np.nan)
    counts = np.diff(off)
    has = counts > 0
    if not has.any():
        return out
    if mode in (None, "min"):
        red = np.minimum.reduceat(vals, off[:-1][has])
    elif mode == "max":
        red = np.maximum.reduceat(vals, off[:-1][has])
    elif mode in ("sum", "avg"):
        red = np.add.reduceat(vals, off[:-1][has])
        if mode == "avg":
            red = red / counts[has]
    else:
        raise QueryParsingError(f"unknown sort mode [{mode}]")
    out[has] = red
    return out


def sort_key_column(spec: SortSpec, seg, ctx, scores: np.ndarray | None) -> np.ndarray:
    """One float64 key per doc; NaN = missing. Ascending semantics (caller negates for
    desc through lexsort ordering)."""
    D = seg.doc_count
    if spec.kind == "score":
        return (scores if scores is not None else np.zeros(D)).astype(np.float64)
    if spec.kind == "geo":
        lat_col = seg.dv_num.get(f"{spec.field}.lat")
        lon_col = seg.dv_num.get(f"{spec.field}.lon")
        if lat_col is None or lon_col is None:
            return np.full(D, np.nan)
        off, lats = lat_col
        _, lons = lon_col
        d = haversine_m(spec.lat, spec.lon, lats, lons) / spec.unit
        mode = spec.mode or "min"
        return _reduce_multi(off, d, D, mode if mode in ("min", "max", "avg", "sum") else "min")
    if spec.kind == "script":
        from ..script import compile_script
        from .filters import DocAccess
        from .functions import vectorized_script_eval

        fn = compile_script(spec.script or "0", spec.params)
        # _script sorts expose the document's _score (reference semantics)
        score_arr = (scores if scores is not None
                     else np.zeros(D)).astype(np.float64)
        out = np.full(D, np.nan)
        # column-lowered fast path (shared contract with script_score: identical
        # or fall back per doc — here, per-doc errors become NaN keys)
        vec = vectorized_script_eval(fn, seg, score_arr)
        if vec is not None:
            vals, ok = vec
            out[ok] = vals[ok]
            rest = np.nonzero(seg.parent_mask & ~ok)[0]
        else:
            rest = np.nonzero(seg.parent_mask)[0]
        for local in rest:
            try:
                out[local] = float(fn(DocAccess(seg, int(local)),
                                      _score=float(score_arr[local])))
            except Exception:  # noqa: BLE001 — missing fields etc. → NaN key
                pass
        return out
    col = seg.dv_num.get(spec.field)
    if col is not None:
        if spec.mode in (None, "min", "max"):
            return _sort_fold(spec, seg)[0]  # cached with the segment
        off, vals = col
        return _reduce_multi(off, vals, D, spec.mode)
    scol = seg.dv_str.get(spec.field)
    if scol is not None:
        # string sort via GLOBAL ordinals would not merge across segments/shards;
        # hits carry the raw string (see sort_values_for_docs) — here we return the
        # segment-local ordinal as a float key for segment-local top-k only
        uniq, off, ords = scol
        counts = np.diff(off)
        out = np.full(D, np.nan)
        has = counts > 0
        if has.any():
            red = np.minimum.reduceat(ords.astype(np.float64), off[:-1][has])
            out[has] = red
        return out
    return np.full(D, np.nan)


_F32_MAX = float(np.finfo(np.float32).max)
# a rank row is exact in float32 while its ranks, and the half ranks a custom
# `missing` may take between two of them, stay below 2**23
_RANKS_MAX = 1 << 23


def _fold_mode(spec: SortSpec) -> str:
    return spec.mode or ("min" if spec.order == "asc" else "max")


def _sort_fold(spec: SortSpec, seg):
    """(per-document key f64 [D] with NaN = missing, how the device may hold
    it) of a numeric column under the spec's mode: "f32" where every value is
    exactly a float32, "rank" where the values are whole numbers a float32
    cannot hold (epoch milliseconds need 40 bits) and few enough distinct for
    their dense rank to be one, else None (fractional float64: the host
    sorts). A pure function of the immutable (segment column, mode), cached
    with the segment: hot sorted searches re-scan no column."""
    ckey = ("sort_keys", spec.field, _fold_mode(spec))
    held = seg._device_cache.get(ckey)
    if held is None:
        col = seg.dv_num.get(spec.field)
        if col is None:
            held = (np.full(seg.doc_count, np.nan), "f32")
        else:
            off, vals = col
            keys = _reduce_multi(off, vals, seg.doc_count, _fold_mode(spec))
            if not len(vals) or (
                    np.array_equal(vals.astype(np.float32).astype(np.float64),
                                   vals)
                    and np.abs(vals).max() < _F32_MAX / 2):
                held = (keys, "f32")
            elif np.array_equal(np.rint(vals), vals):
                held = (keys, "rank")
            else:
                held = (keys, None)
        seg._device_cache[ckey] = held
    return held


def _missing_fill(spec: SortSpec) -> float | None:
    """The float32 key a missing document takes, or None (a custom numeric
    fill that float32 does not hold). ±FLT_MAX, not ±inf: the kernel ranks
    missing documents after real keys and before its ±inf padding."""
    if spec.missing == "_last":
        return _F32_MAX if not spec.reverse else -_F32_MAX
    if spec.missing == "_first":
        return -_F32_MAX if not spec.reverse else _F32_MAX
    try:
        fill = float(spec.missing)
    except (TypeError, ValueError):
        return _F32_MAX
    return fill if float(np.float32(fill)) == fill else None


def _padded_row(keys: np.ndarray, spec: SortSpec, doc_pad: int) -> np.ndarray:
    row = np.full(doc_pad, _F32_MAX if not spec.reverse else -_F32_MAX,
                  dtype=np.float32)
    row[: len(keys)] = keys.astype(np.float32)
    return row


def device_sort_key_row(spec: SortSpec, seg, doc_pad: int) -> np.ndarray | None:
    """float32 [doc_pad] ascending-semantics key row of the VALUES for the
    device sort kernels, or None when the spec/column needs another path.

    Sort order is deterministic user-visible state, so only columns whose values
    are EXACTLY float32-representable ride as values (rounding could swap strict
    orderings); avg/sum modes divide/accumulate in f64 on the host and stay
    there. Custom numeric missing fills must be f32-exact too. The mesh
    program compares keys across shards, so values are all it can take; one
    segment's launch also takes ranks (device_sort_rank_row)."""
    if spec.kind != "field" or spec.mode in ("avg", "sum"):
        return None
    if spec.field in seg.dv_str and spec.field not in seg.dv_num:
        return None
    keys, how = _sort_fold(spec, seg)
    fill = _missing_fill(spec)
    if how != "f32" or fill is None:
        return None
    return _padded_row(np.where(np.isnan(keys), fill, keys), spec, doc_pad)


def device_sort_rank_row(spec: SortSpec, seg, doc_pad: int) -> np.ndarray | None:
    """float32 [doc_pad] key row for ONE segment's launch where the column's
    whole numbers pass float32: each document's key is the dense rank of its
    value among the segment's distinct values, which orders as the values do,
    keeps ties (so the kernel's lower-index preference breaks them by doc id
    as before) and is exact below 2**23 distinct values. A custom numeric
    `missing` takes the rank of its value, or the half rank between its
    neighbours. None where the fold is not "rank" (device_sort_key_row serves
    an f32-exact column, the host a fractional one). Ranks of two segments do
    not compare: launch_flat_sorted merges by the fold's exact values."""
    if spec.kind != "field" or spec.mode in ("avg", "sum"):
        return None
    keys, how = _sort_fold(spec, seg)
    if how != "rank":
        return None
    ckey = ("sort_ranks", spec.field, _fold_mode(spec))
    held = seg._device_cache.get(ckey)
    if held is None:
        has = ~np.isnan(keys)
        uniq, inverse = np.unique(keys[has], return_inverse=True)
        ranks = np.full(len(keys), np.nan)
        ranks[has] = inverse
        held = seg._device_cache[ckey] = (uniq, ranks)
    uniq, ranks = held
    if len(uniq) >= _RANKS_MAX:
        return None
    if spec.missing in ("_last", "_first"):
        fill = _missing_fill(spec)
    else:
        try:
            value = float(spec.missing)
        except (TypeError, ValueError):
            value = np.inf
        at = int(np.searchsorted(uniq, value))
        fill = float(at) if at < len(uniq) and uniq[at] == value else at - 0.5
    return _padded_row(np.where(np.isnan(ranks), fill, ranks), spec, doc_pad)


def exact_sort_keys(spec: SortSpec, seg, locals_=slice(None)) -> np.ndarray:
    """The exact float64 sort keys of a segment's documents `locals_` with the
    `missing` policy applied: what launch_flat_sorted merges segments'
    winners by."""
    return apply_missing(_sort_fold(spec, seg)[0][locals_], spec)


def apply_missing(keys: np.ndarray, spec: SortSpec) -> np.ndarray:
    missing = spec.missing
    if missing == "_last":
        fill = np.inf if not spec.reverse else -np.inf
    elif missing == "_first":
        fill = -np.inf if not spec.reverse else np.inf
    else:
        try:
            fill = float(missing)
        except (TypeError, ValueError):
            fill = np.inf
    return np.where(np.isnan(keys), fill, keys)


def sort_values_for_docs(specs: list[SortSpec], seg, ctx, locals_: np.ndarray,
                         scores: np.ndarray | None):
    """Per-hit sort VALUE tuples (travel with hits for cross-shard merge + response
    "sort" arrays). Strings stay strings so merges compare lexicographically."""
    out: list[list] = [[] for _ in range(len(locals_))]
    for spec in specs:
        if spec.kind == "field" and spec.field in seg.dv_str and spec.field not in seg.dv_num:
            for i, local in enumerate(locals_):
                vals = seg.str_values(spec.field, int(local))
                out[i].append(min(vals) if vals else None)
        else:
            col = sort_key_column(spec, seg, ctx, scores)
            for i, local in enumerate(locals_):
                v = col[int(local)]
                out[i].append(None if np.isnan(v) else float(v))
    return out


def compare_sort_values(a: list, b: list, specs: list[SortSpec]) -> int:
    """Cross-shard comparator over sort-value tuples (None = missing)."""
    for av, bv, spec in zip(a, b, specs):
        if av == bv:
            continue
        if av is None:
            return 1 if spec.missing == "_last" else -1
        if bv is None:
            return -1 if spec.missing == "_last" else 1
        lt = av < bv
        if spec.reverse:
            return 1 if lt else -1
        return -1 if lt else 1
    return 0
