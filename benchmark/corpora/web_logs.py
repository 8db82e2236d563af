"""Seeded web-server access log: one week of time-stamped events as the Rally track
`http_logs` maps them (`@timestamp`, `clientip`, `request` with its not-analysed
`raw` sub-field, `status`, `size`). Events arrive in time order from several servers
whose clocks differ by a few seconds, so document order is close to time order and
never equal to it.

Parameters (from the configuration's file): `first_day` (ISO, UTC) and `days`;
`diurnal_swing` (the daily cycle's amplitude as a share of the mean) and `bursts`
[[hour of the week, hours, factor]] (match times); `server_jitter_s`; `requests` (the
distinct request lines) with `request_zipf_a`; `clients` with `client_zipf_a`;
`methods`, `protocols` and `statuses` as {value: share}; `size_log_mean` and
`size_log_sigma` (the log-normal response size of a 200); `text_field` (the field the
harness's late writes are searched on: `request.raw`).

What the harness's `Reference` sees: every document is ONE token, the id of its
request line, so BM25 over it is Lucene's for a field of one term a document.
"""

from __future__ import annotations

import datetime

import numpy as np

from benchmark.harness.reference import word

_DIRS = ("english", "french", "images", "news", "teams", "venues", "history",
         "competition", "tickets", "hosts", "playing", "individuals", "frntpage",
         "member", "nav", "enfetes", "cup", "match", "scores", "stats", "photos",
         "body", "splash", "lang", "home", "comp", "tour", "info", "press", "fans")
_EXTS = ("gif", "html", "htm", "jpg", "gif", "gif", "html", "class", "cgi", "txt")
_COLUMNS = ("@timestamp", "clientip", "status", "size")


def _first_ms(params: dict) -> int:
    day = datetime.date.fromisoformat(params["first_day"])
    return int(datetime.datetime(day.year, day.month, day.day,
                                 tzinfo=datetime.timezone.utc).timestamp()) * 1000


def _choice(rng, table: dict, n: int) -> np.ndarray:
    values = list(table)
    p = np.array([table[v] for v in values], np.float64)
    return np.array(values, dtype=object)[rng.choice(len(values), n, p=p / p.sum())]


def _request_lines(params: dict, rng) -> np.ndarray:
    """`requests` distinct request lines: method, a path of 2-5 segments, protocol."""
    n = params["requests"]
    methods = _choice(rng, params["methods"], n)
    protocols = _choice(rng, params["protocols"], n)
    depth = rng.integers(1, 5, n)
    dirs = rng.integers(0, len(_DIRS), (n, 4))
    exts = rng.integers(0, len(_EXTS), n)
    out = np.empty(n, dtype=object)
    for i in range(n):
        path = "/".join(_DIRS[d] for d in dirs[i, :depth[i]])
        out[i] = f"{methods[i]} /{path}/{_DIRS[dirs[i, 0]][:4]}_{i:x}.{_EXTS[exts[i]]} " \
                 f"{protocols[i]}"
    return out


def _times(params: dict, rng, n: int) -> np.ndarray:
    """`n` arrival times in whole seconds from the week's start, ascending: a daily
    cycle with bursts at match times, a Poisson count in every minute."""
    minutes = params["days"] * 1440
    hour = np.arange(minutes) / 60.0
    rate = 1.0 + params["diurnal_swing"] * np.sin(2 * np.pi * (hour % 24.0 - 9.0) / 24.0)
    for start, hours, factor in params["bursts"]:
        rate[(hour >= start) & (hour < start + hours)] *= factor
    minute = np.sort(rng.choice(minutes, n, p=rate / rate.sum()))
    return np.sort(minute * 60 + rng.integers(0, 60, n))


def _draw_columns(params: dict, rng, n: int, clients: np.ndarray) -> dict:
    status = _choice(rng, {int(k): v for k, v in params["statuses"].items()},
                     n).astype(np.int64)
    size = np.exp(rng.normal(params["size_log_mean"], params["size_log_sigma"], n))
    size = np.where(status == 200, size, np.where(status == 206, size / 4, 0.0))
    who = (rng.zipf(params["client_zipf_a"], n) - 1) % len(clients)
    return {"clientip": clients[who], "status": status,
            "size": np.floor(size).astype(np.int64)}


class LogCorpus:
    """What `harness/reference.py`'s `Corpus` is to the harness (`n_docs`, `lengths`,
    `tokens`, `n_vocab`, `text_field`, `columns`, `sources`, `extended`), over log
    events: a document's one token is its request line's id."""

    def __init__(self, tokens, columns: dict, lines: np.ndarray, text_field: str,
                 n_vocab: int | None = None):
        self.tokens = np.asarray(tokens, np.int64)
        self.lengths = np.ones(len(self.tokens), np.int64)
        self.columns = {k: np.asarray(columns[k], np.int64) for k in _COLUMNS}
        self.lines = lines
        self.text_field = text_field
        self.n_vocab = int(n_vocab if n_vocab is not None else len(lines))

    @property
    def n_docs(self) -> int:
        return len(self.tokens)

    def request_line(self, term: int) -> str:
        """The request line of a token id; past the table (a late write), the word
        the harness searches for."""
        return str(self.lines[term]) if term < len(self.lines) else word(term)

    def extended(self, extra_docs: list, extra_columns: dict) -> "LogCorpus":
        """A copy with `extra_docs` (one token id each, possibly >= n_vocab) appended."""
        flat = np.array([t for d in extra_docs for t in d], np.int64)
        cols = {k: np.concatenate([v, np.asarray(extra_columns[k], np.int64)])
                for k, v in self.columns.items()}
        return LogCorpus(np.concatenate([self.tokens, flat]), cols, self.lines,
                         self.text_field, max(self.n_vocab, int(flat.max()) + 1))

    def sources(self, lo: int, hi: int) -> list:
        """The `_source` of documents lo..hi-1, as JSON text."""
        c = self.columns
        ip = c["clientip"][lo:hi]
        return ['{"@timestamp":%d,"clientip":"%d.%d.%d.%d","request":"%s",'
                '"status":%d,"size":%d}' % (
                    t, a >> 24, (a >> 16) & 255, (a >> 8) & 255, a & 255,
                    self.request_line(r), s, z)
                for t, a, r, s, z in zip(
                    c["@timestamp"][lo:hi].tolist(), ip.tolist(),
                    self.tokens[lo:hi].tolist(), c["status"][lo:hi].tolist(),
                    c["size"][lo:hi].tolist())]


def generate(params: dict, seed: int, n_docs: int) -> LogCorpus:
    rng = np.random.default_rng(seed)
    lines = _request_lines(params, rng)
    clients = rng.integers(1 << 24, 223 << 24, params["clients"]).astype(np.int64)
    arrival = _times(params, rng, n_docs)
    jitter = rng.integers(-params["server_jitter_s"], params["server_jitter_s"] + 1,
                          n_docs)
    stamp = np.clip(arrival + jitter, 0, params["days"] * 86_400 - 1)
    tokens = (rng.zipf(params["request_zipf_a"], n_docs) - 1) % len(lines)
    columns = _draw_columns(params, rng, n_docs, clients)
    columns["@timestamp"] = _first_ms(params) + stamp.astype(np.int64) * 1000
    return LogCorpus(tokens, columns, lines, params["text_field"])


def late_documents(params: dict, corpus: LogCorpus, seed: int, n: int):
    """`n` new events, each with a request no other event has (token id `n_vocab + j`,
    written as the harness's `word` of it). Returns (docs as lists of token ids, their
    columns)."""
    rng = np.random.default_rng(seed)
    columns = _draw_columns(params, rng, n, corpus.columns["clientip"][:1024])
    columns["@timestamp"] = _first_ms(params) + rng.integers(
        0, params["days"] * 86_400, n).astype(np.int64) * 1000
    return [[corpus.n_vocab + j] for j in range(n)], columns
