"""Seeded gazetteer: points of interest as the Rally track `geonames` maps them
(`geonameid`, `name`, `asciiname`, `alternatenames`, `feature_class`, `feature_code`,
`country_code`, `cc2`, `admin1_code` … `admin4_code`, `population`, `elevation`, `dem`,
`timezone`, `location`), every string with its not-analysed `raw` sub-field. Short,
structured documents: a name, codes, a population, a point. A field a place does not
have is left out of its document, as in the track's own file.

Parameters (from the configuration's file): `countries` with `country_zipf_a`;
`centres` (population-weighted cluster centres, each in one country) with
`centre_spread_deg`; `unpopulated_share`, `population_log_mean`,
`population_log_sigma`, `population_max`, `large_odd_places` (places given an odd
population over 2^24, so that float32 cannot hold the column, as it cannot hold the
source's); `name_words` with `name_zipf_a`; `alternatenames_share`,
`alternatenames_mean`, `alternatenames_max`; `feature_classes` {letter: share},
`feature_codes`; `timezones`; `elevation_share`; `admin_shares` (admin1..admin4);
`cc2_share`; `text_field` (the field the harness's late writes are searched on:
`country_code.raw`).

What the harness's `Reference` sees: every document is ONE token, the id of its
country, so BM25 over it is Lucene's for a field of one term a document. A point is
held in whole 1e-5 degrees (`lat_e5`, `lon_e5`) and written with five decimals, so the
double a server parses is the double the reference divides out. No two documents share
a point.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.reference import word

_COLUMNS = ("geonameid", "population", "lat_e5", "lon_e5")
_LETTERS = "ABCDEFGHIJKLMNOPQRSTUVWXYZ"
_REGIONS = ("Africa", "America", "Antarctica", "Asia", "Atlantic", "Australia",
            "Europe", "Indian", "Pacific")


def _country_codes(n: int) -> list:
    """`n` distinct two-letter codes, the same for every seed (the sandbox holds no
    ISO table): a fixed walk over the 676 pairs."""
    return [_LETTERS[(i * 37 % 676) // 26] + _LETTERS[(i * 37 % 676) % 26]
            for i in range(n)]


def _zipf_pick(rng, n: int, a: float, size: int) -> np.ndarray:
    """`size` draws over 0..n-1 with weight (rank + 1) ** -a."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -a
    return rng.choice(n, size, p=w / w.sum())


def _populations(params: dict, rng, n: int) -> np.ndarray:
    pop = np.floor(np.exp(rng.normal(params["population_log_mean"],
                                     params["population_log_sigma"], n)))
    pop = np.clip(pop, 1, params["population_max"]).astype(np.int64)
    pop[rng.random(n) < params["unpopulated_share"]] = 0
    # the largest places: odd and over 2^24, which float32 cannot hold
    big = rng.choice(n, min(params["large_odd_places"], n), replace=False)
    room = (params["population_max"] - (1 << 24) - 1) // 2
    pop[big] = (1 << 24) + 1 + 2 * rng.integers(0, room, len(big))
    return pop


def _points(params: dict, rng, n: int, centre_of_doc: np.ndarray, centres: dict):
    """Whole 1e-5 degrees around each document's centre, no two documents on one."""
    spread = params["centre_spread_deg"]
    lat = centres["lat"][centre_of_doc] + rng.normal(0.0, spread, n)
    lon = centres["lon"][centre_of_doc] + rng.normal(0.0, spread * 1.5, n)
    lat_e5 = np.rint(np.clip(lat, -85.0, 85.0) * 1e5).astype(np.int64)
    lon_e5 = np.rint(((lon + 180.0) % 360.0 - 180.0) * 1e5).astype(np.int64)
    lon_e5 = np.clip(lon_e5, -17_999_999, 17_999_999)
    while True:
        key = lat_e5 * 36_000_000 + (lon_e5 + 18_000_000)
        _u, first, counts = np.unique(key, return_index=True, return_counts=True)
        if (counts == 1).all():
            return lat_e5, lon_e5
        again = np.setdiff1d(np.arange(n), first)
        lat_e5[again] = np.clip(lat_e5[again] + rng.integers(-500, 501, len(again)),
                                -8_500_000, 8_500_000)


class GeoCorpus:
    """What `harness/reference.py`'s `Corpus` is to the harness (`n_docs`, `lengths`,
    `tokens`, `n_vocab`, `text_field`, `columns`, `sources`, `extended`), over places:
    a document's one token is its country's id. `columns` holds what the reference
    computes from, as int64; `rest` each document's other fields, rendered."""

    def __init__(self, tokens, columns: dict, rest: list, codes: list,
                 text_field: str, n_vocab: int | None = None):
        self.tokens = np.asarray(tokens, np.int64)
        self.lengths = np.ones(len(self.tokens), np.int64)
        self.columns = {k: np.asarray(columns[k], np.int64) for k in _COLUMNS}
        self.rest = rest
        self.codes = codes
        self.text_field = text_field
        self.n_vocab = int(n_vocab if n_vocab is not None else len(codes))

    @property
    def n_docs(self) -> int:
        return len(self.tokens)

    def country(self, term: int) -> str:
        """The country code of a token id; past the table (a late write), the word the
        harness searches for."""
        return self.codes[term] if term < len(self.codes) else word(term)

    def degrees(self, field: str) -> np.ndarray:
        """`lat` or `lon` of every document, as the double a server parses."""
        return self.columns[field + "_e5"] / 1e5

    def extended(self, extra_docs: list, extra_columns: dict) -> "GeoCorpus":
        """A copy with `extra_docs` (one token id each, possibly >= n_vocab) appended."""
        flat = np.array([t for d in extra_docs for t in d], np.int64)
        cols = {k: np.concatenate([v, np.asarray(extra_columns[k], np.int64)])
                for k, v in self.columns.items()}
        return GeoCorpus(np.concatenate([self.tokens, flat]), cols,
                         self.rest + list(extra_columns["rest"]), self.codes,
                         self.text_field, max(self.n_vocab, int(flat.max()) + 1))

    def sources(self, lo: int, hi: int) -> list:
        """The `_source` of documents lo..hi-1, as JSON text."""
        c = self.columns
        out = []
        for i, gid, t, pop, la, lo_ in zip(
                range(lo, hi), c["geonameid"][lo:hi].tolist(),
                self.tokens[lo:hi].tolist(), c["population"][lo:hi].tolist(),
                c["lat_e5"][lo:hi].tolist(), c["lon_e5"][lo:hi].tolist()):
            out.append('{"geonameid":%d,%s,"country_code":"%s","population":%d,'
                       '"location":[%s,%s]}' % (
                           gid, self.rest[i], self.country(t), pop,
                           _e5(lo_), _e5(la)))
        return out


def _e5(v: int) -> str:
    """Whole 1e-5 degrees as a decimal with five places: no float is formatted."""
    return "%s%d.%05d" % ("-" if v < 0 else "", abs(v) // 100_000, abs(v) % 100_000)


def _names(rng, params: dict, n: int) -> list:
    """1-4 words a name, Zipf over `name_words`."""
    k = rng.integers(1, 5, n)
    words = _zipf_pick(rng, params["name_words"], params["name_zipf_a"], int(k.sum()))
    ends = np.cumsum(k)
    w = words.tolist()
    return [" ".join("n%d" % t for t in w[e - c: e])
            for c, e in zip(k.tolist(), ends.tolist())]


def _rest(params: dict, rng, n: int, country: np.ndarray, elevation: np.ndarray) -> list:
    """Every field but `geonameid`, `country_code`, `population` and `location`,
    rendered; a field a place lacks is left out."""
    names = _names(rng, params, n)
    has_alt = rng.random(n) < params["alternatenames_share"]
    n_alt = np.where(has_alt, np.clip(rng.geometric(
        1.0 / params["alternatenames_mean"], n), 1, params["alternatenames_max"]), 0)
    alts = _names(rng, params, int(n_alt.sum()))
    alt_ends = np.cumsum(n_alt).tolist()
    classes = list(params["feature_classes"])
    share = np.array([params["feature_classes"][c] for c in classes], np.float64)
    cls = rng.choice(len(classes), n, p=share / share.sum())
    per_class = max(1, params["feature_codes"] // len(classes))
    code_rank = _zipf_pick(rng, per_class, 1.0, n)
    code_rng = np.random.default_rng(20261003)  # the code table is the same every seed
    table = [[classes[c] + "".join(_LETTERS[j] for j in code_rng.integers(0, 26, 1 + r % 3))
              + ("%d" % r if r >= 26 else "") for r in range(per_class)]
             for c in range(len(classes))]
    zones_per = max(1, params["timezones"] // params["countries"] + 1)
    zone = rng.integers(0, zones_per, n)
    admin = [rng.random(n) < s for s in params["admin_shares"]]
    admin_v = [rng.integers(0, m, n) for m in (60, 400, 3000, 9000)]
    has_cc2 = rng.random(n) < params["cc2_share"]
    cc2 = rng.integers(0, params["countries"], n)
    has_elev = rng.random(n) < params["elevation_share"]
    codes = _country_codes(params["countries"])
    # Python lists from here on: the loop below reads every array once a place
    n_alt, cls, code_rank, zone, cc2, has_cc2, has_elev, elevation, country = (
        a.tolist() for a in (n_alt, cls, code_rank, zone, cc2, has_cc2, has_elev,
                             np.asarray(elevation), np.asarray(country)))
    admin = [a.tolist() for a in admin]
    admin_v = [a.tolist() for a in admin_v]
    out = []
    for i in range(n):
        name = names[i]
        parts = ['"name":"%s","asciiname":"%s"' % (name, name)]
        if n_alt[i]:
            parts.append('"alternatenames":"%s"' % ",".join(
                alts[alt_ends[i] - n_alt[i]: alt_ends[i]]))
        parts.append('"feature_class":"%s","feature_code":"%s"' % (
            classes[cls[i]], table[cls[i]][code_rank[i]]))
        if has_cc2[i]:
            parts.append('"cc2":"%s"' % codes[cc2[i]])
        for level in range(4):
            if not admin[level][i]:
                break  # a finer division only under a coarser one
            parts.append('"admin%d_code":"%02d"' % (level + 1, admin_v[level][i]))
        if has_elev[i]:
            parts.append('"elevation":%d' % elevation[i])
        c = country[i]
        parts.append('"dem":"%d","timezone":"%s/%s_%d"' % (
            elevation[i], _REGIONS[c % len(_REGIONS)], codes[c] if c < len(codes)
            else word(c), zone[i]))
        out.append(",".join(parts))
    return out


def _centres(params: dict, rng) -> dict:
    n = params["centres"]
    weight = np.exp(rng.normal(0.0, 1.0, n))
    return {"lat": np.degrees(np.arcsin(rng.uniform(-0.75, 0.92, n))),
            "lon": rng.uniform(-180.0, 180.0, n),
            "country": _zipf_pick(rng, params["countries"], params["country_zipf_a"], n),
            "elevation": np.clip(rng.exponential(400.0, n), 0, 5000),
            "weight": weight / weight.sum()}


def generate(params: dict, seed: int, n_docs: int) -> GeoCorpus:
    rng = np.random.default_rng(seed)
    centres = _centres(params, rng)
    country = _zipf_pick(rng, params["countries"], params["country_zipf_a"], n_docs)
    # a place lies around one of its country's centres (any centre, for a country
    # that drew none)
    by_country = [np.flatnonzero(centres["country"] == c)
                  for c in range(params["countries"])]
    centre = rng.choice(params["centres"], n_docs, p=centres["weight"])
    u = rng.random(n_docs)
    for c in np.unique(country):
        own = by_country[c]
        if len(own):
            sel = np.flatnonzero(country == c)
            centre[sel] = own[(u[sel] * len(own)).astype(np.int64)]
    lat_e5, lon_e5 = _points(params, rng, n_docs, centre, centres)
    elevation = np.floor(np.clip(
        centres["elevation"][centre] + rng.normal(0.0, 150.0, n_docs), -400, 8000)
    ).astype(np.int64)
    columns = {"geonameid": np.cumsum(rng.integers(1, 114, n_docs)),
               "population": _populations(params, rng, n_docs),
               "lat_e5": lat_e5, "lon_e5": lon_e5}
    return GeoCorpus(country, columns, _rest(params, rng, n_docs, country, elevation),
                     _country_codes(params["countries"]), params["text_field"])


def late_documents(params: dict, corpus: GeoCorpus, seed: int, n: int):
    """`n` new places, each in a country no other place is in (token id `n_vocab + j`,
    written as the harness's `word` of it). Returns (docs as lists of token ids, their
    columns)."""
    rng = np.random.default_rng(seed)
    country = corpus.n_vocab + np.arange(n)
    top = int(corpus.columns["geonameid"].max())
    columns = {"geonameid": top + 1 + np.arange(n),
               "population": _populations({**params, "large_odd_places": 0}, rng, n),
               # beside no place of the corpus: latitudes past its clip
               "lat_e5": 8_600_000 + np.arange(n) * 1000,
               "lon_e5": rng.integers(-17_000_000, 17_000_000, n),
               "rest": _rest(params, rng, n, country, rng.integers(0, 3000, n))}
    return [[int(t)] for t in country], columns
