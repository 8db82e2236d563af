"""Chip smoke: the served path, once, on the chip — client and server in two processes.

The parent (this script) is the CLIENT. It never imports JAX: it starts the server the
way a user does (`python -m elasticsearch_tpu --data <dir> --http-port 0 --transport
local`), reads the port from the server's start line, and talks HTTP only. The child
is the one process that holds the chip.

One chip (no arguments — how the driver runs it): the r03 / BASELINE config 2 shape,
100,000 seeded Zipf documents of ~60 terms over a 50,000-word vocabulary in one index
of one shard, ingested through `_bulk`, searched with BM25 as ingested (many segments)
and again after a force-merge (one segment); every hit list is compared with a numpy
BM25 kept in this file, independent of the package. Then ten late writes are read back
by `_search` and by `GET`, and `/_nodes/stats` has to show that the device — not the
host fallback — served every search.

`--chips 4` (run by hand, never by the driver): only the co-located multi-shard path,
one index of 4 shards served by one shard_map program, and what it is compared with.
It is the bring-up smoke of that path and claims no speed: the four-chip deployment
is measured by the benchmark's cell `passage.mesh4.single` (`benchmark/run.py`).

Every line printed is one JSON object. The last line is the contract:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}` and exit 0
only when every phase passed on a TPU. Any failed check: `"ok": false`, exit 1. A run
the caller started with JAX_PLATFORMS=cpu is a rehearsal: it makes every other check,
says so, and exits 2 with `"ok": false` — never a pass.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from importlib import metadata

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

VOCAB = 50_000
AVG_LEN = 60
BULK = 2_000
K1, B = 1.2, 0.75
INDEX = "c"
FIELD = "body"
REL_TOL = 1e-5  # one chip and four: weights and norm tables come from the host
SEARCH_TIMEOUT = 600.0  # pack + compile land on whichever search meets a new bucket
CUT = ("100k documents, not a deployment's tens of millions: host ingest is "
       "~2k documents/s")


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class SmokeFailure(Exception):
    """A phase failed; the message names it."""


# ---------------------------------------------------------------------------
# corpus and the plain reference (numpy only, independent of the package)
# ---------------------------------------------------------------------------

def word(term_id: int) -> str:
    return f"w{term_id}"


class Corpus:
    """Documents as term ids: doc i is `lengths[i]` ids from `tokens`."""

    def __init__(self, lengths: np.ndarray, tokens: np.ndarray, n_vocab: int = VOCAB):
        self.lengths, self.tokens, self.n_vocab = lengths, tokens, n_vocab

    @classmethod
    def seeded(cls, seed: int, n_docs: int) -> "Corpus":
        """Poisson(60) lengths, Zipf(1.35) term ids folded into the vocabulary."""
        rng = np.random.default_rng(seed)
        lengths = np.clip(rng.poisson(AVG_LEN, n_docs), 5, 400).astype(np.int64)
        raw = rng.zipf(1.35, int(lengths.sum())).astype(np.int64)
        return cls(lengths, (raw - 1) % VOCAB)

    def extended(self, extra_docs: list) -> "Corpus":
        """A copy with `extra_docs` (lists of term ids, possibly >= VOCAB) appended."""
        flat = np.array([t for d in extra_docs for t in d], np.int64)
        return Corpus(
            np.concatenate([self.lengths,
                            np.array([len(d) for d in extra_docs], np.int64)]),
            np.concatenate([self.tokens, flat]),
            max(self.n_vocab, int(flat.max()) + 1))

    @property
    def n_docs(self) -> int:
        return len(self.lengths)

    def doc_body(self, i: int, starts: np.ndarray) -> str:
        return " ".join(word(t) for t in self.tokens[starts[i]: starts[i + 1]])

    def starts(self) -> np.ndarray:
        s = np.zeros(self.n_docs + 1, np.int64)
        np.cumsum(self.lengths, out=s[1:])
        return s


def float_to_byte315(f: np.ndarray) -> np.ndarray:
    """Lucene SmallFloat.floatToByte315: 3 mantissa bits, 5 exponent bits, zero
    exponent 15 — from the IEEE-754 definition."""
    bits = np.asarray(f, np.float32).view(np.int32)
    small = bits >> 21
    floor = (63 - 15) << 3
    out = np.clip(small - floor, 0, 255)
    out = np.where(small <= floor, np.where(bits <= 0, 0, 1), out)
    return out.astype(np.uint8)


def byte315_to_float(b: np.ndarray) -> np.ndarray:
    b = np.asarray(b, np.uint8)
    bits = (b.astype(np.int32) << 21) + ((63 - 15) << 24)
    return np.where(b == 0, np.float32(0), bits.view(np.float32))


class Reference:
    """Lucene 4.x BM25 (k1 1.2, b 0.75, one-byte norms) over a whole corpus:
    idf = ln(1 + (N - df + .5)/(df + .5)); score = sum idf*(k1+1)*f/(f + k1*(1 - b +
    b*dl/avgdl)) with dl decoded from the norm byte. float32 like Lucene."""

    def __init__(self, corpus: Corpus):
        n = corpus.n_docs
        self.n_docs = n
        doc_of_tok = np.repeat(np.arange(n, dtype=np.int64), corpus.lengths)
        uniq, counts = np.unique(corpus.tokens * n + doc_of_tok, return_counts=True)
        terms = uniq // n
        self.post_docs = (uniq % n).astype(np.int64)
        self.post_freqs = counts.astype(np.float32)
        self.df = np.bincount(terms, minlength=corpus.n_vocab).astype(np.int64)
        self.offsets = np.zeros(corpus.n_vocab + 1, np.int64)
        np.cumsum(self.df, out=self.offsets[1:])
        with np.errstate(divide="ignore"):
            norm = float_to_byte315(
                (1.0 / np.sqrt(corpus.lengths.astype(np.float64))).astype(np.float32))
        f = byte315_to_float(np.arange(256, dtype=np.uint8)).astype(np.float64)
        with np.errstate(divide="ignore"):
            dl = np.where(f > 0, 1.0 / (f * f), 0.0).astype(np.float32)
        avgdl = np.float32(corpus.lengths.sum() / n)
        table = (K1 * (1.0 - B + B * dl / avgdl)).astype(np.float32)
        self.denom = table[norm]  # [n_docs]
        self.idf = np.log(
            1.0 + (n - self.df + 0.5) / (self.df + 0.5)).astype(np.float32)

    def postings(self, t: int):
        s, e = self.offsets[t], self.offsets[t + 1]
        return self.post_docs[s:e], self.post_freqs[s:e]

    def score_all(self, terms, must_all: bool):
        """(scores[n_docs] f32, matched[n_docs] bool) for an OR (or AND) of terms."""
        scores = np.zeros(self.n_docs, np.float32)
        seen = np.zeros(self.n_docs, np.int32)
        for t in terms:
            d, f = self.postings(t)
            w = np.float32(self.idf[t] * np.float32(K1 + 1.0))
            scores[d] += w * (f / (f + self.denom[d]))
            seen[d] += 1
        matched = seen == len(terms) if must_all else seen > 0
        return scores, matched


def check_hits(ref: Reference, query: dict, resp: dict, ids_and_scores: bool) -> float:
    """Totals exactly; every returned doc's score, the top-k score list, and ids
    wherever the gap to both neighbours is clear of REL_TOL. Returns the largest
    relative deviation of a returned doc's score from its reference score."""
    if resp.get("timed_out") or resp["_shards"]["failed"] or \
            resp["_shards"]["successful"] != resp["_shards"]["total"]:
        raise SmokeFailure(f"search did not answer whole: {resp['_shards']} "
                           f"timed_out={resp.get('timed_out')} for {query['label']}")
    scores, matched = ref.score_all(query["terms"], query["must_all"])
    total = int(matched.sum())
    got_total = resp["hits"]["total"]
    if got_total != total:
        raise SmokeFailure(
            f"total {got_total} != reference {total} for {query['label']}")
    if not ids_and_scores:
        return 0.0
    hits = resp["hits"]["hits"]
    k = min(query["size"], total)
    if len(hits) != k:
        raise SmokeFailure(f"{len(hits)} hits, expected {k} for {query['label']}")
    if k == 0:
        return 0.0
    cand = np.flatnonzero(matched)
    ranked = cand[np.lexsort((cand, -scores[cand]))]  # score desc, then doc id
    order = ranked[:k]
    ref_scores = scores[order]
    got_ids = np.array([int(h["_id"]) for h in hits], np.int64)
    got_scores = np.array([h["_score"] for h in hits], np.float32)

    def around(i):  # what both sides hold near rank i, for the failure message
        lo, hi = max(0, i - 1), min(k, i + 2)
        got = [(int(d), float(x), float(scores[d]))
               for d, x in zip(got_ids[lo:hi], got_scores[lo:hi])]
        want = [(int(d), float(scores[d])) for d in order[lo:hi]]
        return (f"ranks {lo}..{hi - 1}: got {got} (id, score, its reference score), "
                f"reference {want}")

    if not np.all(matched[got_ids]):
        raise SmokeFailure(f"a returned doc does not match for {query['label']}")
    own = scores[got_ids]
    deviation = np.abs(got_scores - own) / np.maximum(own, 1e-9)
    if deviation.max() > REL_TOL:
        i = int(deviation.argmax())
        raise SmokeFailure(f"score of a returned doc is not its reference score for "
                           f"{query['label']}: {around(i)}")
    tol = REL_TOL * np.maximum(np.abs(ref_scores), 1e-9)
    if not np.all(np.abs(got_scores - ref_scores) <= tol):
        i = int(np.argmax(np.abs(got_scores - ref_scores) - tol))
        raise SmokeFailure(f"score at rank {i} for {query['label']}: {around(i)}")
    gap = np.abs(np.diff(ref_scores)) > tol[:-1]
    # the hit just past k closes the last gap
    last_clear = len(ranked) == k or \
        abs(ref_scores[-1] - scores[ranked[k]]) > tol[-1]
    clear = np.concatenate([[True], gap]) & np.concatenate([gap, [last_clear]])
    if not np.array_equal(got_ids[clear], order[clear]):
        i = int(np.flatnonzero(clear & (got_ids != order))[0])
        raise SmokeFailure(f"id at rank {i} for {query['label']}: {around(i)}")
    return float(deviation.max())


def make_queries(seed: int, ref: Reference) -> list:
    """64 four-term should queries at size 100, 8 one-term and 8 two-term `and`
    queries at size 10, terms log-uniform over the whole frequency curve."""
    rng = np.random.default_rng(seed + 1)
    ranked = np.argsort(-ref.df, kind="stable")
    present = int((ref.df > 0).sum())

    def draw(n, hi=present):
        while True:
            r = np.floor(np.exp(rng.uniform(0, math.log(hi), n))).astype(np.int64) - 1
            if len(set(r.tolist())) == n:
                return [int(t) for t in ranked[r]]

    out = []
    for i in range(64):
        terms = draw(4)
        out.append({"label": f"should4#{i}", "terms": terms, "must_all": False,
                    "size": 100,
                    "body": {"query": {"bool": {"should": [
                        {"match": {FIELD: word(t)}} for t in terms]}}, "size": 100}})
    for i in range(8):
        terms = draw(1)
        out.append({"label": f"term1#{i}", "terms": terms, "must_all": False,
                    "size": 10,
                    "body": {"query": {"match": {FIELD: word(terms[0])}}, "size": 10}})
    for i in range(8):
        terms = draw(2, hi=min(present, 2000))  # frequent enough to intersect
        out.append({"label": f"and2#{i}", "terms": terms, "must_all": True,
                    "size": 10,
                    "body": {"query": {"match": {FIELD: {
                        "query": " ".join(word(t) for t in terms),
                        "operator": "and"}}}, "size": 10}})
    return out


def burst_of(queries: list) -> list:
    """16 searches of every class, to send at once."""
    pick = queries[:12] + queries[64:66] + queries[72:74]
    return pick if len(pick) == 16 else (queries * 16)[:16]


def slice_of(queries: list) -> list:
    """Four four-term queries, one one-term and one `and` query: the part of the
    mix the many-segment state answers. Each (segment size, Qb, TB, k) bucket is
    one compile of up to ~18 s for the chip, and a live index of six segment sizes
    compiles ~70 of them under the whole mix, ~30 under twelve queries."""
    return queries[0:64:16] + queries[64:65] + queries[72:73]


# ---------------------------------------------------------------------------
# the child server and the HTTP client
# ---------------------------------------------------------------------------

class Server:
    def __init__(self, out_dir: str, env_extra: dict | None = None):
        self.data = os.path.join(out_dir, "data")
        self.log_path = os.path.join(out_dir, "server.log")
        os.makedirs(self.data, exist_ok=True)
        env = dict(os.environ)
        env.update(env_extra or {})
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "elasticsearch_tpu", "--data", self.data,
             "--http-port", "0", "--transport", "local"],
            cwd=HERE, env=env, stdout=self.log, stderr=subprocess.STDOUT)
        self.port = None

    def wait_started(self, timeout: float = 300.0) -> int:
        t_end = time.monotonic() + timeout
        while time.monotonic() < t_end:
            with open(self.log_path, "rb") as f:
                for line in f.read().decode("utf-8", "replace").splitlines():
                    if line.startswith("[estpu] node [") and "http port " in line:
                        self.port = int(line.rsplit("http port ", 1)[1].split()[0])
                        return self.port
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited with {self.proc.returncode} before it started")
            time.sleep(0.2)
        raise SmokeFailure(f"server printed no start line within {timeout:.0f} s")

    def check_alive(self) -> None:
        if self.proc.poll() is not None:
            raise SmokeFailure(f"server exited early with {self.proc.returncode}")

    def stop(self) -> int | None:
        """SIGTERM, then kill. Returns the exit code, None where it had to be killed."""
        rc = self.proc.poll()
        if rc is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                rc = None
        self.log.close()
        # the index is rebuilt from the seed every run; only server.log stays
        shutil.rmtree(self.data, ignore_errors=True)
        return rc

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path, "rb") as f:
            return "\n".join(f.read().decode("utf-8", "replace").splitlines()[-n:])


class Client:
    def __init__(self, server: Server):
        self.server = server

    def call(self, method: str, path: str, body=None, timeout: float = 120.0):
        self.server.check_alive()
        data = None
        if body is not None:
            data = body if isinstance(body, bytes) else json.dumps(body).encode()
        conn = http.client.HTTPConnection("127.0.0.1", self.server.port,
                                          timeout=timeout)
        try:
            conn.request(method, path, body=data)
            resp = conn.getresponse()
            text = resp.read().decode()
        finally:
            conn.close()
        if resp.status >= 400:
            raise SmokeFailure(f"{method} {path} -> {resp.status}: {text[:400]}")
        return json.loads(text)

    def node_stats(self, metrics: str) -> dict:
        nodes = self.call("GET", f"/_nodes/stats/{metrics}")["nodes"]
        return next(iter(nodes.values()))

    def search(self, body: dict, params: str = "", timeout: float = 120.0) -> dict:
        return self.call("POST", f"/{INDEX}/_search{params}", body, timeout=timeout)


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if not n.startswith("."))
    except OSError:
        return 0


def versions() -> dict:
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

class Smoke:
    def __init__(self, args, server: Server, rehearsal: bool):
        self.args = args
        self.server = server
        self.http = Client(server)
        self.rehearsal = rehearsal
        self.tag = {"rehearsal": True} if rehearsal else {}
        self.searches_sent = 0
        self.first_search = True
        self.idle_hbm: list = []

    def line(self, obj: dict) -> None:
        say({**obj, **self.tag})

    # -- device ---------------------------------------------------------------
    def device(self) -> dict:
        rt = self.http.node_stats("runtime")["runtime"]
        devs = rt["devices"]
        if not devs:
            raise SmokeFailure("the server reports no device")
        dev = {"platform": devs[0]["platform"], "kind": devs[0]["device_kind"],
               "count": rt["device_count"]}
        self.idle_hbm = [d.get("hbm_bytes_in_use") for d in devs]
        self.line({"phase": "device", **dev,
                   "hbm_bytes_in_use": self.idle_hbm,
                   "hbm_bytes_limit": [d.get("hbm_bytes_limit") for d in devs],
                   "versions": versions()})
        if dev["platform"] != "tpu" and not self.rehearsal:
            raise SmokeFailure(
                f"no accelerator: the server runs on {dev['platform']!r} and the "
                "caller did not ask for a CPU rehearsal (JAX_PLATFORMS=cpu)")
        if dev["count"] != self.args.chips and not self.rehearsal:
            raise SmokeFailure(
                f"{dev['count']} devices, this run needs {self.args.chips}")
        return dev

    # -- ingest ---------------------------------------------------------------
    def ingest(self, corpus: Corpus, shards: int) -> None:
        self.http.call("PUT", f"/{INDEX}", {
            "settings": {"number_of_shards": shards, "number_of_replicas": 0,
                         "index.similarity.default.type": "BM25"},
            "mappings": {"doc": {"_all": {"enabled": False},
                                 "properties": {FIELD: {"type": "string"}}}}})
        starts = corpus.starts()
        t0 = time.monotonic()
        for lo in range(0, corpus.n_docs, BULK):
            lines = []
            for i in range(lo, min(lo + BULK, corpus.n_docs)):
                lines.append('{"index":{"_id":"%d"}}' % i)
                lines.append('{"%s":"%s"}' % (FIELD, corpus.doc_body(i, starts)))
            r = self.http.call("POST", f"/{INDEX}/doc/_bulk",
                               ("\n".join(lines) + "\n").encode(), timeout=600.0)
            if r.get("errors"):
                raise SmokeFailure(f"_bulk reported errors at document {lo}")
        self.refresh()
        secs = time.monotonic() - t0
        count = sum(sh["docs"]["count"] for sh in self.shard_stats())
        self.line({"phase": "ingest", "documents": corpus.n_docs, "bulk": BULK,
                   "seconds": round(secs, 3), "count": count})
        if count != corpus.n_docs:
            raise SmokeFailure(f"_count {count} != {corpus.n_docs} ingested")

    def refresh(self) -> None:
        r = self.http.call("POST", f"/{INDEX}/_refresh", timeout=600.0)
        if r["_shards"]["failed"]:
            raise SmokeFailure(f"_refresh failed: {r}")

    def shard_stats(self) -> list:
        shards = self.http.call("GET", f"/{INDEX}/_stats")["indices"][INDEX]["shards"]
        return [shards[k] for k in sorted(shards, key=int)]

    def segments(self) -> list:
        return [sh["segments"] for sh in self.shard_stats()]

    # -- searches -------------------------------------------------------------
    def search(self, q: dict, params: str = "") -> dict:
        t0 = time.monotonic()
        resp = self.http.search(q["body"], params, timeout=SEARCH_TIMEOUT)
        self.searches_sent += 1
        if self.first_search:
            self.first_search = False
            self.line({"phase": "first_search",
                       "seconds": round(time.monotonic() - t0, 3),
                       "took_ms": resp.get("took")})
        return resp

    def search_at_once(self, queries: list, params: str = "") -> list:
        """One search per query, all sent at once, a thread each, so the batcher
        coalesces them (Qb > 1 buckets)."""
        results: list = [None] * len(queries)

        def one(i):
            try:
                results[i] = self.search(queries[i], params)
            except Exception as e:  # noqa: BLE001 — re-raised below, on the caller
                results[i] = e

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in results:
            if isinstance(r, Exception):
                raise r
        return results

    def run_queries(self, state: str, ref: Reference, queries: list) -> None:
        """Every query twice in turn, then 16 of them at once."""
        t0 = time.monotonic()
        worst = 0.0
        for _ in range(2):
            for q in queries:
                worst = max(worst, check_hits(ref, q, self.search(q), True))
        burst = burst_of(queries)
        for q, r in zip(burst, self.search_at_once(burst)):
            worst = max(worst, check_hits(ref, q, r, True))
        self.line({"phase": "searches", "state": state,
                   "queries": 2 * len(queries) + len(burst), "concurrent": len(burst),
                   "compared_with_reference": "ids, scores, totals",
                   "rel_tol": REL_TOL, "max_rel_score_deviation": worst,
                   "seconds": round(time.monotonic() - t0, 3)})

    def report_state(self, state: str) -> None:
        st = self.http.node_stats("device,runtime,search_serving")
        dev = st["device"]
        idx = dev["indices"].get(INDEX, {})
        pack = idx.get("pack", {})
        self.line({
            "phase": "state", "state": state, "segments_per_shard": self.segments(),
            "resident_bytes": idx.get("totals"),
            "resident_total_bytes": idx.get("total_bytes"),
            "hbm_bytes_in_use": [d.get("hbm_bytes_in_use")
                                 for d in st["runtime"]["devices"]],
            "hbm_bytes_limit": [d.get("hbm_bytes_limit")
                                for d in st["runtime"]["devices"]],
            "pack": {k: pack.get(k) for k in
                     ("packs", "delta_packs", "compacts", "pack_ms_total", "pools")},
            "compile_events": dev["compile"],
            "compile_specs": dev["compile_warming"]["specs"],
            "serving": st["search_serving"]})

    def optimize(self) -> None:
        t0 = time.monotonic()
        r = self.http.call("POST", f"/{INDEX}/_optimize?max_num_segments=1",
                           timeout=1800.0)
        self.line({"phase": "optimize", "seconds": round(time.monotonic() - t0, 3),
                   "_shards": r["_shards"]})
        if r["_shards"]["failed"] or not r["_shards"]["successful"]:
            raise SmokeFailure(f"_optimize failed: {r}")

    # -- durability -----------------------------------------------------------
    def late_writes(self, corpus: Corpus) -> None:
        """Ten new documents, each with one term no other document has: read back
        by `_search` (id and score against the grown corpus) and by `GET`."""
        rng = np.random.default_rng(self.args.seed + 2)
        n0 = corpus.n_docs
        docs = []
        for j in range(10):
            body = [int(t) for t in
                    (rng.zipf(1.35, 20).astype(np.int64) - 1) % VOCAB]
            docs.append(body + [VOCAB + j])
        grown = corpus.extended(docs)
        starts = grown.starts()
        for j in range(10):
            r = self.http.call("PUT", f"/{INDEX}/doc/{n0 + j}",
                               {FIELD: grown.doc_body(n0 + j, starts)})
            if not r.get("created"):
                raise SmokeFailure(f"late write {n0 + j} not acknowledged: {r}")
        self.refresh()
        ref = Reference(grown)
        for j in range(10):
            term = VOCAB + j
            q = {"label": f"late#{j}", "terms": [term], "must_all": False, "size": 10,
                 "body": {"query": {"match": {FIELD: word(term)}}, "size": 10}}
            resp = self.search(q)
            check_hits(ref, q, resp, True)
            if [h["_id"] for h in resp["hits"]["hits"]] != [str(n0 + j)]:
                raise SmokeFailure(f"late write {n0 + j} not found by _search")
            got = self.http.call("GET", f"/{INDEX}/doc/{n0 + j}")
            if not got.get("found") or \
                    got["_source"] != {FIELD: grown.doc_body(n0 + j, starts)}:
                raise SmokeFailure(f"late write {n0 + j} not read back by GET")
        self.line({"phase": "late_writes", "documents": 10,
                   "read_back": "_search and GET"})

    # -- the device really served ---------------------------------------------
    def check_served(self) -> dict:
        st = self.http.node_stats("device,runtime,search_serving")
        sv = st["search_serving"]
        warm = st["device"]["compile_warming"]
        health = st["device"]["health"]
        problems = []
        if self.args.chips == 1:
            served = sum(v for k, v in sv.items()
                         if k.startswith("device_") and k not in
                         ("device_errors", "device_percolate_fallbacks"))
            if served != self.searches_sent:
                problems.append(f"device served {served} of {self.searches_sent}")
        else:
            if sv["mesh_spmd"] != self.searches_sent:
                problems.append(
                    f"mesh_spmd {sv['mesh_spmd']} != sent {self.searches_sent}")
            for key in ("mesh_fallbacks", "mesh_rebuilds"):
                if sv[key]:
                    problems.append(f"{key} == {sv[key]}")
        for key in ("host", "device_errors", "degraded"):
            if sv[key]:
                problems.append(f"{key} == {sv[key]}")
        if warm["warm_failures"] or warm["mesh_warm_failures"]:
            problems.append(f"compile warm failures: {warm['warm_failures']} + "
                            f"{warm['mesh_warm_failures']}")
        if health["any_open"] or health["trips"] or \
                any(health["failures"].values()):
            problems.append(f"device fault domains: {health}")
        devs = st["runtime"]["devices"]
        platforms = sorted({d["platform"] for d in devs})
        if not self.rehearsal:
            if platforms != ["tpu"] or len(devs) != self.args.chips:
                problems.append(f"devices {platforms} x{len(devs)}, expected tpu "
                                f"x{self.args.chips}")
            if self.args.chips > 1:
                for i, d in enumerate(devs):
                    idle = self.idle_hbm[i] or 0
                    if not (d.get("hbm_bytes_in_use") or 0) > idle:
                        problems.append(
                            f"device {i} holds no index bytes "
                            f"({d.get('hbm_bytes_in_use')} <= idle {idle})")
        self.line({"phase": "served", "searches_sent": self.searches_sent,
                   "serving": sv, "native": st["runtime"]["native"],
                   "per_device_bytes_check": ("skipped: memory_stats() is empty "
                                              "off the chip") if self.rehearsal
                   else [d.get("hbm_bytes_in_use") for d in devs]})
        if problems:
            raise SmokeFailure("; ".join(problems))
        return st


def run_one_chip(smoke: Smoke, corpus: Corpus, ref: Reference, queries: list) -> None:
    smoke.ingest(corpus, shards=1)
    smoke.run_queries("ingested", ref, slice_of(queries))
    smoke.report_state("ingested")
    smoke.optimize()
    smoke.first_search = True  # the merged segment packs and compiles anew
    smoke.run_queries("force_merged", ref, queries)
    smoke.report_state("force_merged")
    if smoke.segments() != [1]:
        raise SmokeFailure(f"{smoke.segments()} segments after _optimize")
    smoke.late_writes(corpus)


def run_four_chips(smoke: Smoke, corpus: Corpus, ref: Reference,
                   queries: list) -> None:
    """One index of 4 shards on one node: every search rides the shard_map program.
    DFS searches have index-wide statistics, so the whole-corpus reference holds for
    ids and scores; plain ones have shard-local statistics: totals only."""
    smoke.ingest(corpus, shards=4)
    t0 = time.monotonic()
    worst = 0.0
    for i, q in enumerate(queries):
        dfs = i % 2 == 0
        resp = smoke.search(q, "?search_type=dfs_query_then_fetch" if dfs else "")
        worst = max(worst, check_hits(ref, q, resp, dfs))
    burst = burst_of(queries)
    for q, r in zip(burst, smoke.search_at_once(burst)):
        check_hits(ref, q, r, ids_and_scores=False)
    smoke.line({"phase": "searches", "state": "4 shards, mesh",
                "queries": len(queries) + len(burst), "concurrent": len(burst),
                "compared_with_reference": "dfs half: ids, scores, totals; "
                "plain half and the burst: totals",
                "rel_tol": REL_TOL, "max_rel_score_deviation": worst,
                "seconds": round(time.monotonic() - t0, 3)})
    smoke.report_state("4 shards, mesh")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--docs", type=int, default=100_000)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--reference-seed", type=int, default=None,
                    help="build the reference from another seed (it must then "
                    "disagree: the script's own test uses this)")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out", "chip_smoke"))
    args = ap.parse_args(argv)

    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    out_dir = os.path.join(args.out, f"run_{int(time.time())}_{os.getpid()}")
    os.makedirs(out_dir, exist_ok=True)
    device = {"platform": None, "kind": None, "count": 0}
    ok = False
    failure = None
    t_start = time.monotonic()
    env_extra = {}
    if rehearsal and args.chips > 1:
        # the rehearsal of the mesh path: four virtual CPU devices
        env_extra["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                  " --xla_force_host_platform_device_count=4").strip()
    # a caller's SIGTERM (a time limit) still stops the child: exit through `finally`
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    server = Server(out_dir, env_extra)
    try:
        tag = {"rehearsal": True} if rehearsal else {}
        say({"phase": "sizes", "documents": args.docs, "vocabulary": VOCAB,
             "terms_per_document": AVG_LEN, "shards": args.chips, "replicas": 0,
             "bulk": BULK, "seed": args.seed, "chips": args.chips,
             "cut": CUT if args.docs == 100_000 else
             f"{args.docs} documents, not the default 100k: a run by hand", **tag})
        server.wait_started()
        smoke = Smoke(args, server, rehearsal)
        device = smoke.device()
        # the server says where its persistent cache is; relative to its cwd, HERE
        cache_dir = smoke.http.node_stats("device")["device"]["compile_warming"][
            "persistent_cache_dir"]
        if not cache_dir:
            raise SmokeFailure("the server armed no persistent compile cache")
        cache_dir = os.path.join(HERE, cache_dir)
        cache_before = cache_entries(cache_dir)
        corpus = Corpus.seeded(args.seed, args.docs)
        truth = Reference(corpus)
        ref = truth if args.reference_seed is None else \
            Reference(Corpus.seeded(args.reference_seed, args.docs))
        queries = make_queries(args.seed, truth)
        if args.chips == 1:
            run_one_chip(smoke, corpus, ref, queries)
        else:
            run_four_chips(smoke, corpus, ref, queries)
        smoke.check_served()
        say({"phase": "compile_cache", "directory": cache_dir,
             "entries_before": cache_before,
             "entries_after": cache_entries(cache_dir), **tag})
        rc = server.stop()
        if rc != 0:
            raise SmokeFailure(f"server exit code {rc} after SIGTERM "
                               "(None: it had to be killed)")
        if "jax" in sys.modules:
            raise SmokeFailure("the client imported JAX: it would hold the chip")
        say({"phase": "checks", "passed": True, "client_imported_jax": False,
             "seconds": round(time.monotonic() - t_start, 3), **tag})
        ok = not rehearsal
    except Exception as e:  # noqa: BLE001 — every failed phase ends in the last line
        failure = f"{type(e).__name__}: {e}"
        say({"phase": "checks", "passed": False, "error": failure[:2000]})
        sys.stderr.write(server.log_tail() + "\n")
    finally:
        server.stop()
    say({"ok": ok, "device": device})
    if ok:
        return 0
    return 2 if failure is None and rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
