"""The load generator: threads of the parent, one connection per client.

Open loop: arrivals on a schedule made beforehand, latency counted from when a search
was due, so a stall is paid by every search that waits behind it. Closed loop: each
client sends its next search when the last one has answered. Either way the generator
reports how late it ran and its own CPU time, so that a starved generator is not read
as a fast server.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np

from .server import Connection

REQUEST_TIMEOUT_S = 10.0  # no answer within it counts in `failed`


def schedule(rate_per_s: float, seconds: float, plan_rng, order_rng) -> np.ndarray:
    """Due times in [0, seconds): Poisson arrivals whose gaps come from the mix's own
    generator, so that every seed offers the same set of gaps, in an order drawn from
    the seed."""
    n = int(rate_per_s * seconds * 1.5) + 64
    gaps = plan_rng.exponential(1.0 / rate_per_s, n)
    due = np.cumsum(order_rng.permutation(gaps))
    return due[due < seconds]


class LoadResult:
    """What one window sent and got back. Times are seconds from the window's start."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.due: list = []
        self.sent: list = []
        self.done: list = []
        self.query: list = []      # index into the pool
        self.ok: list = []         # answered whole: 2xx, no failed shard, not timed out
        self.answer: list = []     # (total, ids, scores[, kept parts]) or None
        self.spans: list = []      # (sent, done, [(name, t0, t1), ...]) of sampled searches
        self.cpu_s = 0.0
        self.wall_s = 0.0
        self.errors: list = []     # a few messages, for the failure line

    def arrays(self):
        return (np.array(self.due), np.array(self.sent), np.array(self.done),
                np.array(self.ok, bool))


def _flatten(tree: dict, out: list) -> list:
    out.append((tree["name"], tree["t0"], tree["t1"]))
    for child in tree.get("children", ()):
        _flatten(child, out)
    return out


def _digest(status: int, body: bytes, keep: dict | None = None):
    """(answered whole, compact answer, spans or None) of one response. `keep` is what
    the search's query family asks the window to keep beyond total, ids and scores:
    `{"response": [keys of the response], "hit": [keys of each hit]}`, kept as one
    string of JSON (one object to hold, whatever the number of buckets or hits)."""
    if status >= 300:
        return False, None, None
    resp = json.loads(body)
    sh = resp.get("_shards", {})
    whole = not resp.get("timed_out") and not sh.get("failed") and \
        sh.get("successful") == sh.get("total")
    hits = resp["hits"]["hits"]
    answer = (resp["hits"]["total"], tuple(h["_id"] for h in hits),
              tuple(h["_score"] for h in hits))
    if keep:
        answer += (json.dumps(
            {"response": {k: resp[k] for k in keep.get("response", ()) if k in resp},
             "hit": {k: [h.get(k) for h in hits] for k in keep.get("hit", ())}},
            separators=(",", ":")),)
    spans = _flatten(resp["trace"]["tree"], []) if "trace" in resp else None
    return whole, answer, spans


def as_response(answer) -> dict:
    """A compact answer in the shape the comparison reads, the kept parts back in
    their places."""
    total, ids, scores, *kept = answer
    hits = [{"_id": i, "_score": s} for i, s in zip(ids, scores)]
    resp = {"_shards": {"total": 1, "successful": 1, "failed": 0}, "timed_out": False,
            "hits": {"total": total, "hits": hits}}
    if kept:
        parts = json.loads(kept[0])
        resp.update(parts["response"])
        for key, values in parts["hit"].items():
            for hit, value in zip(hits, values):
                hit[key] = value
    return resp


def run_load(port: int, path: str, bodies: list, order: np.ndarray, seconds: float,
             clients: int, due: np.ndarray | None, keep_alive: bool = True,
             trace_every: int = 0, meanwhile=None,
             count: int | None = None, keeps: list | None = None) -> LoadResult:
    """One window. `bodies` are the pool's encoded searches and `order` the sequence
    in which the pool is sent (cycled). With `due` (open loop) `clients` threads send
    on that schedule; without (closed loop) each of `clients` threads sends its next
    search as soon as its last one is answered, until `seconds` have passed or, with
    `count`, until that many searches have been sent. `keeps`, where a family of the
    pool asks for it, holds for each search of the pool what `_digest` keeps of its
    responses beyond total, ids and scores.
    `meanwhile(t0)` runs on the caller's thread while the load runs."""
    result = LoadResult(seconds)
    lock = threading.Lock()
    state = {"next": 0}
    per_worker: list = [[] for _ in range(clients)]
    traced_path = path + ("&" if "?" in path else "?") + "trace=true"
    t0 = time.perf_counter() + 0.05
    t_end = t0 + seconds
    n_order = len(order)

    def worker(slot: int) -> None:
        conn = Connection(port, REQUEST_TIMEOUT_S, keep_alive)
        mine = per_worker[slot]
        while True:
            with lock:
                i = state["next"]
                state["next"] = i + 1
            if due is not None:
                if i >= len(due):
                    break
                t_due = t0 + due[i]
                wait = t_due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
            else:
                t_due = max(time.perf_counter(), t0)
                if t_due >= t_end or (count is not None and i >= count):
                    break
                wait = t0 - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
            q = int(order[i % n_order])
            traced = trace_every and i % trace_every == 0
            t_sent = time.perf_counter()
            try:
                status, body = conn.request(
                    "POST", traced_path if traced else path, bodies[q])
                t_done = time.perf_counter()
                whole, answer, spans = _digest(
                    status, body, keeps[q] if keeps is not None else None)
                err = None if whole else f"status {status}: {body[:200]!r}"
            except (OSError, http.client.HTTPException, ValueError, KeyError) as e:
                t_done = time.perf_counter()
                whole, answer, spans, err = False, None, None, f"{type(e).__name__}: {e}"
            mine.append((t_due - t0, t_sent - t0, t_done - t0, q, whole, answer,
                         spans, err))
        conn.close()

    threads = [threading.Thread(target=worker, args=(s,), daemon=True)
               for s in range(clients)]
    cpu0, wall0 = time.process_time(), time.perf_counter()
    for t in threads:
        t.start()
    if meanwhile is not None:
        meanwhile(t0)
    for t in threads:
        t.join()
    result.cpu_s = time.process_time() - cpu0
    result.wall_s = time.perf_counter() - wall0
    rows = sorted((r for mine in per_worker for r in mine), key=lambda r: r[0])
    for t_due, t_sent, t_done, q, whole, answer, spans, err in rows:
        result.due.append(t_due)
        result.sent.append(t_sent)
        result.done.append(t_done)
        result.query.append(q)
        result.ok.append(whole)
        result.answer.append(answer)
        if spans is not None:
            result.spans.append((t_sent, t_done, spans))
        if err and len(result.errors) < 5:
            result.errors.append(err)
    return result
