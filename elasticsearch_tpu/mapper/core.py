"""Document mapping: JSON docs → indexable fields.

Analogue of index/mapper/ in the reference (MapperService, DocumentMapper, field mappers —
SURVEY.md §2.3): type registry, JSON parsing into per-field token streams + columnar
values, meta-fields, dynamic mapping of unseen fields, and mapping merges with conflict
detection (ref: index/mapper/MapperService.java, DocumentMapper.java, MergeContext).

TPU-native departure from Lucene: numeric/date/boolean fields are NOT trie-encoded into
postings terms (Lucene's NumericField approach, built for term-dictionary range scans).
They land in columnar doc-value arrays — device-resident f64/i64 columns — and range/term
queries on them compile to vectorized comparisons, which is the natural TPU layout
(SURVEY.md §2.3 fielddata note: "the natural device tensor").
"""

from __future__ import annotations

import datetime as _dt
import re
from dataclasses import dataclass, field as dc_field
from typing import Any

from ..analysis import AnalysisService, Analyzer
from ..common.errors import MapperParsingError
from ..common.settings import Settings

# ---------------------------------------------------------------------------
# date parsing (subset of Joda patterns the reference defaults to)
# ---------------------------------------------------------------------------

_ISO_RE = re.compile(
    r"^(\d{4})-(\d{2})-(\d{2})(?:[T ](\d{2}):(\d{2})(?::(\d{2})(?:\.(\d{1,9}))?)?"
    r"(Z|[+-]\d{2}:?\d{2})?)?$"
)


def parse_date(value: Any, formats: list[str] | None = None) -> int:
    """Parse a date value → epoch millis (UTC). Supports epoch_millis ints,
    strict_date_optional_time (ISO-8601), yyyy/MM/dd style, and %-style custom formats."""
    if isinstance(value, bool):
        raise MapperParsingError(f"cannot parse boolean [{value}] as date")
    if isinstance(value, (int, float)):
        return int(value)
    s = str(value).strip()
    if s.isdigit() or (s.startswith("-") and s[1:].isdigit()):
        return int(s)
    m = _ISO_RE.match(s)
    if m:
        y, mo, d = int(m.group(1)), int(m.group(2)), int(m.group(3))
        hh = int(m.group(4) or 0)
        mm = int(m.group(5) or 0)
        ss = int(m.group(6) or 0)
        frac = m.group(7) or "0"
        micros = int(float("0." + frac) * 1e6)
        tz = m.group(8)
        tzinfo = _dt.timezone.utc
        if tz and tz != "Z":
            sign = 1 if tz[0] == "+" else -1
            tz = tz[1:].replace(":", "")
            tzinfo = _dt.timezone(sign * _dt.timedelta(hours=int(tz[:2]), minutes=int(tz[2:] or 0)))
        dt = _dt.datetime(y, mo, d, hh, mm, ss, micros, tzinfo=tzinfo)
        return int(dt.timestamp() * 1000)
    for fmt in formats or ("%Y/%m/%d %H:%M:%S", "%Y/%m/%d", "%d-%m-%Y", "%m/%d/%Y"):
        try:
            dt = _dt.datetime.strptime(s, fmt).replace(tzinfo=_dt.timezone.utc)
            return int(dt.timestamp() * 1000)
        except ValueError:
            continue
    raise MapperParsingError(f"failed to parse date field [{value}]")


# "now-1d/d" style date math used by range queries
_DATE_MATH_RE = re.compile(r"^now(?:([+-]\d+)([yMwdhHms]))?(?:/([yMwdhHms]))?$")
_UNIT_MILLIS = {
    "y": 365 * 86400_000, "M": 30 * 86400_000, "w": 7 * 86400_000,
    "d": 86400_000, "h": 3600_000, "H": 3600_000, "m": 60_000, "s": 1000,
}


def date_math_reads_clock(value: str) -> bool:
    """Whether parse_date_math resolves `value` against the clock (`now...`)."""
    return _DATE_MATH_RE.match(value) is not None


def parse_date_math(value: str, now_ms: int | None = None) -> int:
    import time

    m = _DATE_MATH_RE.match(value)
    if not m:
        return parse_date(value)
    t = now_ms if now_ms is not None else int(time.time() * 1000)
    if m.group(1):
        t += int(m.group(1)) * _UNIT_MILLIS[m.group(2)]
    if m.group(3):
        unit = _UNIT_MILLIS[m.group(3)]
        t = (t // unit) * unit
    return t


# ---------------------------------------------------------------------------
# field types
# ---------------------------------------------------------------------------

TEXT_TYPES = {"string", "text"}
NUMERIC_TYPES = {"long", "integer", "short", "byte", "double", "float", "date", "boolean",
                 "ip", "token_count"}

_INT_BOUNDS = {
    "byte": (-(2**7), 2**7 - 1),
    "short": (-(2**15), 2**15 - 1),
    "integer": (-(2**31), 2**31 - 1),
    "long": (-(2**63), 2**63 - 1),
}


def parse_ip(value: str) -> int:
    parts = str(value).split(".")
    if len(parts) != 4:
        raise MapperParsingError(f"failed to parse ip [{value}]")
    n = 0
    for p in parts:
        b = int(p)
        if not 0 <= b <= 255:
            raise MapperParsingError(f"failed to parse ip [{value}]")
        n = (n << 8) | b
    return n


def format_ip(n: int) -> str:
    return ".".join(str((n >> s) & 0xFF) for s in (24, 16, 8, 0))


@dataclass
class FieldType:
    """Resolved, immutable view of one field's mapping."""

    name: str
    type: str = "string"
    index: str = "analyzed"  # analyzed | not_analyzed | no
    store: bool = False
    boost: float = 1.0
    analyzer: str | None = None
    search_analyzer: str | None = None
    formats: list[str] | None = None  # date formats
    null_value: Any = None
    include_in_all: bool = True
    precision_step: int | None = None  # accepted for parity; unused (columnar ranges)
    doc_values: bool = True
    copy_to: list[str] = dc_field(default_factory=list)
    nested: bool = False
    properties: dict | None = None  # for object/nested

    @property
    def is_text(self) -> bool:
        return self.type in TEXT_TYPES

    @property
    def is_numeric(self) -> bool:
        return self.type in NUMERIC_TYPES

    @property
    def searchable(self) -> bool:
        return self.index != "no"

    @property
    def analyzed(self) -> bool:
        return self.is_text and self.index == "analyzed"

    def coerce(self, value: Any):
        """Coerce a raw JSON value to this field's storage representation
        (numerics → int/float, dates → epoch millis, bools → 0/1, ip → int)."""
        t = self.type
        if value is None:
            value = self.null_value
            if value is None:
                return None
        if t in ("long", "integer", "short", "byte", "token_count"):
            try:
                v = int(float(value)) if not isinstance(value, bool) else int(value)
            except (TypeError, ValueError):
                raise MapperParsingError(f"failed to parse [{self.name}] value [{value}] as {t}")
            lo, hi = _INT_BOUNDS.get(t, _INT_BOUNDS["long"])
            if not lo <= v <= hi:
                raise MapperParsingError(f"value [{value}] out of range for {t} field [{self.name}]")
            return v
        if t in ("double", "float"):
            try:
                return float(value)
            except (TypeError, ValueError):
                raise MapperParsingError(f"failed to parse [{self.name}] value [{value}] as {t}")
        if t == "date":
            return parse_date(value, self.formats)
        if t == "boolean":
            if isinstance(value, bool):
                return 1 if value else 0
            return 1 if str(value).lower() in ("true", "1", "on", "yes") else 0
        if t == "ip":
            return parse_ip(value) if isinstance(value, str) else int(value)
        return value

    def to_mapping(self) -> dict:
        d: dict[str, Any] = {"type": "string" if self.type == "text" else self.type}
        if self.is_text and self.index != "analyzed":
            d["index"] = self.index
        elif not self.is_text and self.index == "no":
            d["index"] = "no"
        if self.store:
            d["store"] = True
        if self.boost != 1.0:
            d["boost"] = self.boost
        if self.analyzer:
            d["analyzer"] = self.analyzer
        if self.null_value is not None:
            d["null_value"] = self.null_value
        if self.copy_to:
            d["copy_to"] = self.copy_to
        return d


# meta-fields (ref: index/mapper/internal/ — _uid,_id,_type,_source,_all,_routing,...)
META_FIELDS = ("_uid", "_id", "_type", "_source", "_all", "_routing", "_parent",
               "_timestamp", "_ttl", "_version", "_size", "_index", "_boost")


@dataclass
class ParsedDocument:
    """Output of DocumentMapper.parse — what the segment builder consumes."""

    id: str
    type: str
    uid: str
    source: dict
    routing: str | None = None
    timestamp: int | None = None
    ttl: int | None = None
    parent: str | None = None
    # field → list[(term, position)] for analyzed/keyword postings
    postings: dict[str, list[tuple[str, int]]] = dc_field(default_factory=dict)
    # field → token count (for norms)
    field_lengths: dict[str, int] = dc_field(default_factory=dict)
    # field → numeric value(s) for columnar doc-values (list for multi-valued)
    doc_values_num: dict[str, list[float]] = dc_field(default_factory=dict)
    # field → raw keyword bytes values for columnar term store
    doc_values_str: dict[str, list[str]] = dc_field(default_factory=dict)
    # nested sub-documents (block-join style): list of (path, ParsedDocument-lite)
    nested_docs: list[tuple[str, "ParsedDocument"]] = dc_field(default_factory=list)


class FieldMapper:
    """One field's parse behavior. Kept minimal: FieldType + analyzer binding."""

    def __init__(self, ft: FieldType, analysis: AnalysisService):
        self.ft = ft
        self.analysis = analysis

    @property
    def index_analyzer(self) -> Analyzer:
        return self.analysis.analyzer(self.ft.analyzer)

    @property
    def search_analyzer(self) -> Analyzer:
        return self.analysis.analyzer(self.ft.search_analyzer or self.ft.analyzer)


def _infer_dynamic_type(value: Any, dynamic_date: bool = True) -> str | None:
    if isinstance(value, bool):
        return "boolean"
    if isinstance(value, int):
        return "long"
    if isinstance(value, float):
        return "double"
    if isinstance(value, str):
        if dynamic_date and _ISO_RE.match(value.strip()):
            return "date"
        return "string"
    if isinstance(value, dict):
        return "object"
    return None


class DocumentMapper:
    """Parses docs of one mapping type; holds the field-type registry for that type.
    (ref: index/mapper/DocumentMapper.java)"""

    def __init__(self, type_name: str, mapping: dict | None, analysis: AnalysisService,
                 index_settings: Settings | None = None):
        self.type = type_name
        self.analysis = analysis
        self.settings = index_settings or Settings.EMPTY
        mapping = mapping or {}
        self.meta = mapping.get("_meta", {})
        self.dynamic = mapping.get("dynamic", True)
        self.date_detection = mapping.get("date_detection", True)
        self.source_enabled = mapping.get("_source", {}).get("enabled", True)
        self.all_enabled = mapping.get("_all", {}).get("enabled", True)
        self.routing_required = mapping.get("_routing", {}).get("required", False)
        self.routing_path = mapping.get("_routing", {}).get("path")
        self.parent_type = mapping.get("_parent", {}).get("type")
        self.timestamp_enabled = mapping.get("_timestamp", {}).get("enabled", False)
        self.timestamp_path = mapping.get("_timestamp", {}).get("path")
        self.ttl_enabled = mapping.get("_ttl", {}).get("enabled", False)
        self.default_ttl = mapping.get("_ttl", {}).get("default")
        self.fields: dict[str, FieldType] = {}
        self.multi_fields: dict[str, list[str]] = {}  # field -> its sub-fields
        self._mapping_dirty = False
        self._parse_properties(mapping.get("properties", {}), prefix="", nested_path=None)

    # mapping registration ---------------------------------------------------
    def _parse_properties(self, props: dict, prefix: str, nested_path: str | None):
        for name, spec in props.items():
            full = f"{prefix}{name}"
            if not isinstance(spec, dict):
                raise MapperParsingError(f"invalid mapping for field [{full}]")
            ftype = spec.get("type")
            if ftype in (None, "object", "nested") and ("properties" in spec or ftype in ("object", "nested")):
                is_nested = ftype == "nested"
                self.fields[full] = FieldType(
                    name=full, type="object", nested=is_nested, properties=spec.get("properties", {})
                )
                self._parse_properties(
                    spec.get("properties", {}), prefix=f"{full}.",
                    nested_path=full if is_nested else nested_path,
                )
                continue
            if ftype == "multi_field":
                # legacy multi_field: subfields full.sub, default subfield aliased to full
                for sub, subspec in spec.get("fields", {}).items():
                    sub_full = full if sub == name else f"{full}.{sub}"
                    self.fields[sub_full] = self._field_type_from_spec(sub_full, subspec)
                continue
            ft = self._field_type_from_spec(full, spec)
            self.fields[full] = ft
            for sub, subspec in spec.get("fields", {}).items():
                self.fields[f"{full}.{sub}"] = self._field_type_from_spec(f"{full}.{sub}", subspec)
                # a multi-field: the parent's values indexed once more under
                # the sub-field's own type (`request.raw`, not analysed)
                subs = self.multi_fields.setdefault(full, [])
                if f"{full}.{sub}" not in subs:
                    subs.append(f"{full}.{sub}")

    def _field_type_from_spec(self, full: str, spec: dict) -> FieldType:
        ftype = spec.get("type", "string")
        if ftype == "text":
            ftype = "string"
        if ftype == "keyword":  # forward-compat alias: not_analyzed string
            ftype = "string"
            spec = {**spec, "index": "not_analyzed"}
        index = spec.get("index", "analyzed" if ftype in TEXT_TYPES else "yes")
        if index == "yes":
            index = "analyzed" if ftype in TEXT_TYPES else "not_analyzed"
        copy_to = spec.get("copy_to", [])
        if isinstance(copy_to, str):
            copy_to = [copy_to]
        return FieldType(
            name=full,
            type=ftype,
            index=index,
            store=bool(spec.get("store", False) in (True, "yes", "true")),
            boost=float(spec.get("boost", 1.0)),
            analyzer=spec.get("analyzer") or spec.get("index_analyzer"),
            search_analyzer=spec.get("search_analyzer"),
            formats=[spec["format"]] if "format" in spec else None,
            null_value=spec.get("null_value"),
            include_in_all=spec.get("include_in_all", True),
            precision_step=spec.get("precision_step"),
            doc_values=spec.get("doc_values", True),
            copy_to=copy_to,
        )

    def field_type(self, name: str) -> FieldType | None:
        return self.fields.get(name)

    # parsing ----------------------------------------------------------------
    def parse(self, source: dict, doc_id: str, routing: str | None = None,
              timestamp=None, ttl=None, parent: str | None = None) -> ParsedDocument:
        if not isinstance(source, dict):
            raise MapperParsingError("document source must be an object")
        doc = ParsedDocument(
            id=doc_id, type=self.type, uid=f"{self.type}#{doc_id}", source=source,
            routing=routing, parent=parent,
        )
        # an explicit timestamp always takes effect (it drives _ttl expiry); the
        # _timestamp docvalue is only stored when the meta-field is enabled
        if timestamp is not None:
            doc.timestamp = parse_date(timestamp)
        elif self.timestamp_enabled:
            if self.timestamp_path and self.timestamp_path in source:
                doc.timestamp = parse_date(source[self.timestamp_path])
            else:
                import time

                doc.timestamp = int(time.time() * 1000)
        if self.ttl_enabled:
            from ..common.units import parse_time

            raw_ttl = ttl if ttl is not None else self.default_ttl
            if raw_ttl is not None:
                doc.ttl = int(parse_time(raw_ttl) * 1000) if isinstance(raw_ttl, str) else int(raw_ttl)
        if self.routing_path and routing is None and self.routing_path in source:
            doc.routing = str(source[self.routing_path])
        if parent is not None:
            # child doc: store the parent pointer for join queries and route by it
            doc.parent = str(parent)
            doc.doc_values_str["_parent"] = [doc.parent]
            doc.postings["_parent"] = [(f"{self.parent_type or 'doc'}#{doc.parent}", 0)]
            if doc.routing is None:
                doc.routing = doc.parent
        if doc.ttl is not None:
            import time as _time

            base_ts = doc.timestamp if doc.timestamp is not None else int(
                _time.time() * 1000)
            expiry = base_ts + doc.ttl
            if expiry < int(_time.time() * 1000):
                from ..common.errors import AlreadyExpiredError

                raise AlreadyExpiredError(
                    f"already expired [{doc_id}]: expiry [{expiry}] < now")
            doc.doc_values_num["_expiry"] = [float(expiry)]
        if doc.timestamp is not None and self.timestamp_enabled:
            doc.doc_values_num["_timestamp"] = [float(doc.timestamp)]
        all_terms: list[tuple[str, int]] = []
        self._parse_object(source, "", doc, all_terms, nested_path=None)
        if self.all_enabled and all_terms:
            doc.postings["_all"] = all_terms
            doc.field_lengths["_all"] = len(all_terms)
        # _uid postings so ids queries/lookups work like any term query
        doc.postings["_uid"] = [(doc.uid, 0)]
        doc.postings["_id"] = [(doc.id, 0)]
        doc.postings["_type"] = [(self.type, 0)]
        return doc

    def _parse_object(self, obj: dict, prefix: str, doc: ParsedDocument,
                      all_terms: list, nested_path: str | None):
        for key, value in obj.items():
            if key in META_FIELDS:
                continue
            full = f"{prefix}{key}"
            ft = self.fields.get(full)
            if isinstance(value, dict) and (ft is None or ft.type == "object"):
                if ft is None:
                    if self.dynamic == "strict":
                        raise MapperParsingError(f"strict dynamic mapping: unknown field [{full}]")
                    if not self.dynamic:
                        continue
                    self.fields[full] = FieldType(name=full, type="object", properties={})
                    self._mapping_dirty = True
                    ft = self.fields[full]
                if ft.nested:
                    sub = ParsedDocument(id=doc.id, type=self.type, uid=doc.uid, source=value)
                    sub_all: list = []
                    self._parse_object(value, f"{full}.", sub, sub_all, nested_path=full)
                    doc.nested_docs.append((full, sub))
                else:
                    self._parse_object(value, f"{full}.", doc, all_terms, nested_path)
                continue
            values = value if isinstance(value, list) else [value]
            if values and all(isinstance(v, dict) for v in values) and ft is not None and ft.nested:
                for v in values:
                    sub = ParsedDocument(id=doc.id, type=self.type, uid=doc.uid, source=v)
                    sub_all: list = []
                    self._parse_object(v, f"{full}.", sub, sub_all, nested_path=full)
                    doc.nested_docs.append((full, sub))
                continue
            if values and all(isinstance(v, dict) for v in values) and (
                    ft is None or ft.type not in ("geo_point", "geo_shape")):
                # array of objects, non-nested: flatten each (geo types consume
                # their dict form as a leaf value: {lat,lon} / GeoJSON shape)
                for v in values:
                    self._parse_object(v, f"{full}.", doc, all_terms, nested_path)
                continue
            if ft is None:
                if self.dynamic == "strict":
                    raise MapperParsingError(f"strict dynamic mapping: unknown field [{full}]")
                if not self.dynamic:
                    continue
                sample = next((v for v in values if v is not None), None)
                inferred = _infer_dynamic_type(sample, self.date_detection)
                if inferred is None:
                    continue
                ft = self._field_type_from_spec(full, {"type": inferred})
                self.fields[full] = ft
                self._mapping_dirty = True
            self._index_values(ft, values, doc, all_terms)
            for sub in self.multi_fields.get(full, ()):
                self._index_values(self.fields[sub], values, doc, all_terms=[])
            for target in ft.copy_to:
                tft = self.fields.get(target)
                if tft is None:
                    tft = self._field_type_from_spec(target, {"type": ft.type})
                    self.fields[target] = tft
                    self._mapping_dirty = True
                self._index_values(tft, values, doc, all_terms=[])

    def _index_values(self, ft: FieldType, values: list, doc: ParsedDocument, all_terms: list):
        if not ft.searchable and not ft.doc_values:
            return
        if ft.is_text:
            mapper = FieldMapper(ft, self.analysis)
            terms = doc.postings.setdefault(ft.name, [])
            pos_base = doc.field_lengths.get(ft.name, 0)
            for v in values:
                if v is None:
                    if ft.null_value is None:
                        continue
                    v = ft.null_value
                text = str(v)
                if ft.analyzed:
                    toks = mapper.index_analyzer.index_tokens(text)
                    for term, pos in toks:
                        terms.append((term, pos_base + pos))
                        if ft.include_in_all and self.all_enabled:
                            all_terms.append((term, len(all_terms)))
                    pos_base += len(toks) + 100  # position gap between values (Lucene default)
                else:
                    terms.append((text, pos_base))
                    pos_base += 1
                    if ft.include_in_all and self.all_enabled:
                        all_terms.append((text, len(all_terms)))
                doc.doc_values_str.setdefault(ft.name, []).extend(
                    t for t in ([text] if not ft.analyzed else [text])
                )
            doc.field_lengths[ft.name] = len(terms)
        elif ft.is_numeric:
            col = doc.doc_values_num.setdefault(ft.name, [])
            for v in values:
                cv = ft.coerce(v)
                if cv is not None:
                    col.append(float(cv))
            if not col:
                doc.doc_values_num.pop(ft.name, None)
        elif ft.type == "geo_point":
            for lat, lon in _parse_geo_points(values):
                doc.doc_values_num.setdefault(f"{ft.name}.lat", []).append(lat)
                doc.doc_values_num.setdefault(f"{ft.name}.lon", []).append(lon)
        elif ft.type == "geo_shape":
            # shape stored columnar as canonical JSON (the dv_str column persists
            # with the segment); relations evaluate host-side from the parsed form —
            # the TPU-framework replacement for the reference's spatial prefix-tree
            # terms (ref: index/mapper/geo/GeoShapeFieldMapper.java)
            import json as _json

            from ..common.geo import normalize_shape

            for v in values:
                if not isinstance(v, dict):
                    raise MapperParsingError(f"failed to parse geo_shape [{v}]")
                try:
                    kind, data = normalize_shape(v)
                except ValueError as e:
                    raise MapperParsingError(str(e))
                doc.doc_values_str.setdefault(ft.name, []).append(
                    _json.dumps([kind, data], separators=(",", ":")))
        elif ft.type == "binary":
            pass  # stored via _source only
        else:
            # unknown types degrade to keyword storage
            for v in values:
                if v is not None:
                    doc.doc_values_str.setdefault(ft.name, []).append(str(v))

    # mapping output / merge -------------------------------------------------
    def to_mapping(self) -> dict:
        props: dict[str, Any] = {}
        multi = []  # (parent_parts, leaf, ft) — rendered under the parent's "fields"
        for name, ft in sorted(self.fields.items()):
            if ft.type == "object":
                continue
            parts = name.split(".")
            parent = self.fields.get(".".join(parts[:-1])) if len(parts) > 1 else None
            if parent is not None and parent.type != "object":
                multi.append((parts[:-1], parts[-1], ft))
                continue
            node = props
            for p in parts[:-1]:
                obj_ft = self.fields.get(".".join(parts[: parts.index(p) + 1]))
                node = node.setdefault(p, {"type": "nested"} if obj_ft and obj_ft.nested else {})
                node = node.setdefault("properties", {})
            node[parts[-1]] = ft.to_mapping()
        for parent_parts, leaf, ft in multi:
            node = props
            for i, p in enumerate(parent_parts):
                if i:
                    node = node.setdefault("properties", {})
                node = node.setdefault(p, {})
            node.setdefault("fields", {})[leaf] = ft.to_mapping()
        out: dict[str, Any] = {"properties": props}
        if not self.source_enabled:
            out["_source"] = {"enabled": False}
        if not self.all_enabled:
            out["_all"] = {"enabled": False}
        if self.routing_required or self.routing_path:
            out["_routing"] = {k: v for k, v in
                               (("required", self.routing_required), ("path", self.routing_path)) if v}
        if self.parent_type:
            out["_parent"] = {"type": self.parent_type}
        if self.timestamp_enabled:
            out["_timestamp"] = {"enabled": True}
        if self.ttl_enabled:
            out["_ttl"] = {"enabled": True}
        return out

    def merge(self, new_mapping: dict, simulate: bool = False) -> list[str]:
        """Merge another mapping for this type; returns conflict messages.
        (ref: DocumentMapper merge + MergeContext conflict collection)"""
        other = DocumentMapper(self.type, new_mapping, self.analysis, self.settings)
        conflicts = []
        for name, ft in other.fields.items():
            mine = self.fields.get(name)
            if mine is None:
                if not simulate:
                    self.fields[name] = ft
            else:
                if mine.type != ft.type and not {mine.type, ft.type} <= {"object"}:
                    conflicts.append(
                        f"mapper [{name}] of different type, current [{mine.type}], merged [{ft.type}]"
                    )
                elif mine.index != ft.index:
                    conflicts.append(f"mapper [{name}] has different index values")
                elif mine.analyzer != ft.analyzer:
                    conflicts.append(f"mapper [{name}] has different analyzer")
        return conflicts


def _parse_geo_points(values: list) -> list[tuple[float, float]]:
    """One or many points: dict {lat,lon} / "lat,lon" / geohash / [lon,lat] —
    a bare numeric pair is ONE point (GeoJSON), anything else is per-element."""
    if len(values) == 2 and all(isinstance(x, (int, float)) for x in values):
        return [(float(values[1]), float(values[0]))]
    return [_parse_geo_point(v) for v in values]


def _parse_geo_point(v) -> tuple[float, float]:
    if isinstance(v, dict):
        return float(v["lat"]), float(v["lon"])
    if isinstance(v, str):
        if "," in v:
            lat, lon = v.split(",")
            return float(lat), float(lon)
        # bare string = geohash (ref: GeoPointFieldMapper geohash support)
        from ..common.geo import geohash_decode

        try:
            return geohash_decode(v.strip().lower())
        except (KeyError, ValueError):
            raise MapperParsingError(f"failed to parse geohash [{v}]")
    if isinstance(v, list):
        if len(v) == 2 and all(isinstance(x, (int, float)) for x in v):
            return float(v[1]), float(v[0])  # GeoJSON order [lon, lat]
    raise MapperParsingError(f"failed to parse geo_point [{v}]")


class MapperService:
    """type → DocumentMapper registry for one index (ref: index/mapper/MapperService.java).
    Looks up field types across all mapping types; `smart_field` resolves `type.field`."""

    DEFAULT_TYPE = "_default_"

    def __init__(self, index_settings: Settings | None = None,
                 analysis: AnalysisService | None = None):
        self.settings = index_settings or Settings.EMPTY
        self.analysis = analysis or AnalysisService(self.settings)
        self.mappers: dict[str, DocumentMapper] = {}
        self._default_mapping: dict = {}

    def put_mapping(self, type_name: str, mapping: dict, merge: bool = True) -> list[str]:
        body = mapping.get(type_name, mapping)
        if type_name == self.DEFAULT_TYPE:
            self._default_mapping = body
            return []
        existing = self.mappers.get(type_name)
        if existing is not None and merge:
            conflicts = existing.merge(body)
            if conflicts:
                from ..common.errors import MapperParsingError as MPE

                raise MPE(f"mapping merge conflicts: {conflicts}")
            return conflicts
        merged_body = dict(self._default_mapping)
        merged_body.update(body)
        self.mappers[type_name] = DocumentMapper(type_name, merged_body, self.analysis, self.settings)
        return []

    def mapper_for(self, type_name: str, create_if_missing: bool = True) -> DocumentMapper:
        m = self.mappers.get(type_name)
        if m is None:
            if not create_if_missing:
                from ..common.errors import TypeMissingError

                raise TypeMissingError(f"no mapping for type [{type_name}]")
            m = DocumentMapper(type_name, dict(self._default_mapping), self.analysis, self.settings)
            self.mappers[type_name] = m
        return m

    def types(self) -> list[str]:
        return list(self.mappers)

    def field_type(self, field: str, types: list[str] | None = None) -> FieldType | None:
        for tname, mapper in self.mappers.items():
            if types and tname not in types:
                continue
            ft = mapper.field_type(field)
            if ft is not None:
                return ft
        return None

    def search_analyzer_for(self, field: str) -> Analyzer:
        ft = self.field_type(field)
        if ft is None or not ft.is_text:
            return self.analysis.analyzer("default")
        return FieldMapper(ft, self.analysis).search_analyzer

    def mappings_dict(self) -> dict:
        return {t: m.to_mapping() for t, m in self.mappers.items()}
