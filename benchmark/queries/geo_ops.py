"""The search operations of the Rally track `geonames` (challenge
`append-no-conflicts`) that rank by a function of a document's fields, facet by a
keyword with a sum, and filter by a keyword, as a gazetteer, travel or catalogue
application sends them.

Parameters: `size`, and `op`, one of
- `field_value`: `field_value_function_score`: `function_score` over `match_all` with
  one `field_value_factor` on `field` (`factor`, `modifier` `log1p`): rank every
  place by log10(factor x population + 1);
- `gauss`: `decay_geo_gauss_function_score`: `function_score` over `match_all` with one
  `gauss` decay on the point `field` (`scale`, `offset`, `decay`): rank every place by
  nearness to an origin. The origin is drawn for each search from the corpus' own
  places, weighted by population (a user's position: the place's own point); the
  first search of the pool sends the track's fixed origin (`origin`);
- `expression`: `function_score` over `match_all` with one `script_score` that reads
  `_score` (`script`, `lang`);
- `country_agg`: `country_agg_uncached`: `size: 0` and a `terms` aggregation on `field`
  with a `sum` of `sum_field` under it (the configuration's `search.params` switch the
  request cache off, as the track's own operation does);
- `term`: a `term` query on `field`, the country drawn by the countries' own
  frequencies; scored hits (BM25 of a field of one token).

The reference's side: function values from the corpus' own columns in float64 by the
published formulas (FunctionScoreQueryParser and the reference guide's function_score
page): `log10(factor x v + 1)`; `exp(-max(0, d - offset)^2 / (2 sigma^2))` with
`sigma^2 = -scale^2 / (2 ln decay)` over the haversine distance d in metres on a sphere
of `earth_radius_m`; the expression as written; each multiplied into the sub-query's
score (`match_all`: 1) under the default `boost_mode`, `multiply`. Scores are compared
to the configuration's tolerance (`check_hits`); the facet exactly: every bucket's key
and `doc_count` in the response's order (count descending, key ascending on ties, the
first ten), and every bucket's sum as the integer it is (`agg_sum_off`, limit 0: a sum
accumulated in float32 is rounded at 2^24 and fails it). The plan (origin, country)
comes from the mix's own generator; the corpus from `--seed`.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness.reference import check_hits, hits_answer, round_to

# the numbers `compare` adds to the shared ones, each with its limit (exact)
LIMITS = {"agg_buckets_off": 0, "agg_counts_off": 0, "agg_sum_off": 0}
# what the window keeps of a response beyond total, ids and scores
KEEP = {"response": ["aggregations"]}

_UNITS_M = {"km": 1000.0, "m": 1.0}
_TERMS_SIZE = 10  # the `terms` aggregation's default `size`, which the track leaves


def plan(params: dict, rng, n: int) -> list:
    return [float(u) for u in rng.random(n)]


def _metres(text: str) -> float:
    for unit in sorted(_UNITS_M, key=len, reverse=True):
        if text.endswith(unit):
            return float(text[: -len(unit)]) * _UNITS_M[unit]
    raise ValueError(f"a distance with no unit this family knows: {text!r}")


def build(params: dict, ref, plans: list) -> list:
    return [_build_one(params, ref, u, first=i == 0) for i, u in enumerate(plans)]


def _match_all_under(function: dict) -> dict:
    return {"function_score": {"query": {"match_all": {}}, "functions": [function]}}


def _build_one(params: dict, ref, u: float, first: bool) -> dict:
    op, size = params["op"], params["size"]
    q = {"op": op, "terms": [], "must_all": False, "size": size, "allowed": None}
    if op == "field_value":
        q["value"] = (params["field"], float(params["factor"]))
        q["body"] = {"query": _match_all_under({"field_value_factor": {
            "field": params["field"], "factor": params["factor"],
            "modifier": params["modifier"]}}), "size": size}
    elif op == "gauss":
        if first:
            lat, lon = (float(x) for x in params["origin"].split(","))
        else:
            # a user's position: one of the corpus' own places, drawn by population
            weight = np.cumsum(ref.corpus.columns["population"], dtype=np.float64)
            doc = int(np.searchsorted(weight, u * weight[-1], side="right"))
            lat = float(ref.corpus.degrees("lat")[doc])
            lon = float(ref.corpus.degrees("lon")[doc])
        q["decay"] = (lat, lon, _metres(params["scale"]), _metres(params["offset"]),
                      float(params["decay"]), float(params["earth_radius_m"]))
        q["body"] = {"query": _match_all_under({"gauss": {params["field"]: {
            "origin": {"lat": lat, "lon": lon}, "scale": params["scale"],
            "offset": params["offset"], "decay": params["decay"]}}}), "size": size}
    elif op == "expression":
        q["body"] = {"query": _match_all_under({"script_score": {
            "script": params["script"], "lang": params["lang"]}}), "size": size}
    elif op == "country_agg":
        q["size"] = 0
        q["facet"] = (params["sum_field"],)
        q["body"] = {"size": 0, "aggs": {"country_population": {
            "terms": {"field": params["field"]},
            "aggs": {"sum_pop": {"sum": {"field": params["sum_field"]}}}}}}
    elif op == "term":
        # a country by the countries' own frequencies
        by_df = ref.by_df[:ref.n_present]
        share = np.cumsum(ref.df[by_df], dtype=np.float64)
        term = int(by_df[np.searchsorted(share, u * share[-1], side="right")])
        q["terms"] = [term]
        q["body"] = {"query": {"term": {params["field"]: ref.corpus.country(term)}},
                     "size": size}
    else:
        raise ValueError(f"no operation {op!r} in the family geo_ops")
    return q


def _haversine_m(lat0: float, lon0: float, lat, lon, radius_m: float):
    p0, p = math.radians(lat0), np.radians(lat)
    half_dlat = (p - p0) / 2.0
    half_dlon = (np.radians(lon) - math.radians(lon0)) / 2.0
    a = np.sin(half_dlat) ** 2 + math.cos(p0) * np.cos(p) * np.sin(half_dlon) ** 2
    return 2.0 * radius_m * np.arcsin(np.sqrt(np.clip(a, 0.0, 1.0)))


def _function_values(ref, q: dict) -> np.ndarray:
    """The operation's function of every document, float64."""
    corpus = ref.corpus
    if q["op"] == "field_value":
        field, factor = q["value"]
        return np.log10(factor * corpus.columns[field].astype(np.float64) + 1.0)
    if q["op"] == "gauss":
        lat0, lon0, scale, offset, decay, radius = q["decay"]
        d = np.maximum(0.0, _haversine_m(lat0, lon0, corpus.degrees("lat"),
                                         corpus.degrees("lon"), radius) - offset)
        sigma2 = -scale * scale / (2.0 * math.log(decay))
        return np.exp(-d * d / (2.0 * sigma2))
    # the expression, as the configuration's departures write it, with _score 1
    pop = corpus.columns["population"].astype(np.float64)
    return np.abs(np.log(np.abs(pop) + 1.0) + corpus.degrees("lon")
                  + corpus.degrees("lat")) * 1.0


def expected(ref, q: dict):
    """(scores, matched) over the whole corpus: BM25 for `term`; every document at
    its function's value times the constant 1 for the function scores; every document
    at 1 under the facet."""
    if q["op"] == "term":
        return ref.score_all(q["terms"], False)
    matched = np.ones(ref.n_docs, bool)
    if q["op"] == "country_agg":
        return matched.astype(np.float32), matched
    value = round_to(_function_values(ref, q).astype(np.float32), ref.precision)
    return round_to(np.float32(1.0) * value, ref.precision), matched


def _facet(ref, q: dict) -> list:
    """[(country code, documents, summed population as a Python integer)], the first
    ten by documents descending, code ascending on ties."""
    (sum_field,) = q["facet"]
    corpus = ref.corpus
    n = corpus.n_vocab
    counts = np.bincount(corpus.tokens, minlength=n)
    values = corpus.columns[sum_field]
    order = np.argsort(corpus.tokens, kind="stable")
    ends = np.cumsum(counts)
    sums = [sum(values[order[e - c: e]].tolist())  # Python integers: no rounding
            for c, e in zip(counts.tolist(), ends.tolist())]
    rows = [(corpus.country(t), int(counts[t]), sums[t]) for t in range(n) if counts[t]]
    rows.sort(key=lambda r: (-r[1], r[0]))
    return rows[:_TERMS_SIZE]


def _sum_off(got, want: int) -> int:
    """|got - want| of one bucket's sum; a sum that is no whole number is off by at
    least one."""
    if isinstance(got, (int, float)) and not isinstance(got, bool) \
            and math.isfinite(got) and float(got).is_integer():
        return abs(int(got) - want)
    return max(1, want)


def compare(ref, q: dict, resp: dict, tol: float) -> dict:
    scores, matched = expected(ref, q)
    out = check_hits(ref, scores, matched, q["size"], resp, tol)
    if out["not_whole"] or q["op"] != "country_agg":
        return out
    want = _facet(ref, q)
    buckets = ((resp.get("aggregations") or {}).get("country_population")
               or {}).get("buckets") or []
    got_keys = [b.get("key") for b in buckets]
    want_keys = [r[0] for r in want]
    # buckets missing, extra, or not at the reference's rank
    out["agg_buckets_off"] = abs(len(got_keys) - len(want_keys)) + sum(
        g != w for g, w in zip(got_keys, want_keys))
    by_key = {b.get("key"): b for b in buckets}
    out["agg_counts_off"] = sum(
        abs(int((by_key.get(k) or {}).get("doc_count", 0)) - c) for k, c, _s in want)
    out["agg_sum_off"] = sum(
        _sum_off(((by_key.get(k) or {}).get("sum_pop") or {}).get("value"), s)
        for k, _c, s in want)
    return out


def answer(ref, q: dict) -> dict:
    """What `ref` itself would serve. A control below float32 adds a bucket's
    populations up in float32, as a program without exact integer sums would."""
    scores, matched = expected(ref, q)
    resp = hits_answer(ref, scores, matched, q["size"])
    if q["op"] == "country_agg":
        exact = ref.precision == "float32"
        resp["aggregations"] = {"country_population": {"buckets": [
            {"key": k, "doc_count": c, "sum_pop": {
                "value": float(s) if exact else _float32_sum(ref, q, k)}}
            for k, c, s in _facet(ref, q)]}}
    return resp


def _float32_sum(ref, q: dict, code: str) -> float:
    (sum_field,) = q["facet"]
    term = ref.corpus.codes.index(code)
    values = ref.corpus.columns[sum_field][ref.corpus.tokens == term]
    return float(np.cumsum(values.astype(np.float32), dtype=np.float32)[-1])
