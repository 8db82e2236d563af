"""One run of one cell: set-up, warm-up, the measured window, the comparison with the
reference, the late writes, and the result line. General code: which configuration,
mix and metrics a cell has comes from `BENCHMARK.json` and the files it names."""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
import urllib.parse

import numpy as np

from . import readers, registry, xplane
from .loadgen import as_response, run_load, schedule
from .reference import Reference, check_hits, hits_answer, word
from .server import BenchFailure, Client, Server


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def search_path(index: str, config: dict) -> str:
    """The path of every `_search` a run sends for this configuration: its
    `search.params`, where it has any, as the URL's query string."""
    params = (config.get("search") or {}).get("params")
    path = f"/{index}/_search"
    return path + "?" + urllib.parse.urlencode(params) if params else path


class Pool:
    """The cell's searches: plans from the mix's own generator (the same shapes for
    every seed), words from the seeded corpus. A query family compares a response as
    `check_hits` does unless it brings a `compare` of its own, with the `LIMITS` of the
    numbers that returns; `KEEP` says what the window has to keep of its responses
    beyond total, ids and scores, and `answer` what the reference itself would serve
    (the control's answer) where that is more than hits."""

    def __init__(self, mix: dict, ref: Reference, path: str, base_limits: dict):
        plan_rng = np.random.default_rng(mix["plan_seed"])
        weights = np.array([f["weight"] for f in mix["families"]], np.float64)
        counts = np.floor(weights / weights.sum() * mix["pool"]).astype(int)
        counts[0] += mix["pool"] - counts.sum()
        self.queries = []
        self.family = []
        self.limits = dict(base_limits)
        for fam, n in zip(mix["families"], counts):
            mod = registry.module("queries", fam["family"])
            for name, limit in getattr(mod, "LIMITS", {}).items():
                if self.limits.setdefault(name, limit) != limit:
                    raise BenchFailure(
                        f"query family {fam['family']!r} gives {name} the limit "
                        f"{limit}; it has {self.limits[name]}")
            first = len(self.queries)
            for q in mod.build(fam["params"], ref, mod.plan(fam["params"], plan_rng, n)):
                self.queries.append(q)
                self.family.append(mod)
            if n:
                # the family's numbers on the reference's own answer, now: a number
                # with no limit stops the run here, not after the window
                unnamed = set(self.compare(
                    ref, first, self.answer(ref, first), 0.0)) - set(self.limits)
                if unnamed:
                    raise BenchFailure(
                        f"query family {fam['family']!r} compares {sorted(unnamed)} "
                        "and gives no limit for it (LIMITS)")
        self.bodies = [json.dumps(q["body"]).encode() for q in self.queries]
        self.path = path
        keeps = [getattr(mod, "KEEP", None) for mod in self.family]
        self.keeps = keeps if any(keeps) else None

    def compare(self, ref: Reference, i: int, resp: dict, tol: float) -> dict:
        q, mod = self.queries[i], self.family[i]
        if hasattr(mod, "compare"):
            return mod.compare(ref, q, resp, tol)
        scores, matched = mod.expected(ref, q)
        return check_hits(ref, scores, matched, q["size"], resp, tol)

    def answer(self, ref: Reference, i: int) -> dict:
        q, mod = self.queries[i], self.family[i]
        if hasattr(mod, "answer"):
            return mod.answer(ref, q)
        scores, matched = mod.expected(ref, q)
        return hits_answer(ref, scores, matched, q["size"])


class Compared:
    """The numbers one sample of responses gave, each beside its limit."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.numbers = {k: 0 for k in limits}
        self.compared = 0

    def add(self, numbers: dict) -> None:
        self.compared += 1
        for k, v in numbers.items():
            self.numbers[k] = max(self.numbers[k], v) if isinstance(v, float) \
                else self.numbers[k] + v

    def line(self, sample: str) -> dict:
        return {"phase": "compare", "sample": sample, "compared": self.compared,
                "numbers": {k: {"value": self.numbers[k], "limit": self.limits[k]}
                            for k in self.limits}}

    @property
    def passed(self) -> bool:
        return self.compared > 0 and \
            all(self.numbers[k] <= self.limits[k] for k in self.limits)


class Run:
    def __init__(self, args, t_process: float, server_env: dict | None = None,
                 assume_chip: bool = False, settings: dict | None = None):
        """`server_env`, `assume_chip` and `settings` are for the tests under
        benchmark/tests: a child started with a fault in it, a run that skips the
        look for a chip, and a shorter warm-up."""
        self.args = args
        self.server_env = server_env
        self.t_process = t_process
        self.bench = registry.benchmark()
        self.cell = registry.cell(self.bench, args.workload)
        self.config = registry.config(self.bench, self.cell["config"])
        self.mix = registry.mix(self.cell["traffic"])
        self.settings = {**registry.settings(), **(settings or {})}
        self.index = self.settings["index"]
        self.type = self.settings["doc_type"]
        self.assume_chip = assume_chip
        self.on_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        self.rehearsal = not assume_chip and self.on_cpu
        if args.docs is not None and not self.rehearsal and not assume_chip:
            raise BenchFailure("--docs is for the CPU rehearsal (JAX_PLATFORMS=cpu): "
                               "a run at another size is not the cell")
        self.n_docs = args.docs or self.config["documents"]
        self.tag = {"rehearsal": True} if self.rehearsal else {}
        self.reference_s = 0.0  # the reference's side before the window: not set-up
        self.server = None
        self.profile = None
        self.profiled_spans: list = []
        self.obs = readers.Observations(self.index)
        self.problems: list = []
        self.compared: dict = {}  # every number compared: name -> [value, limit]
        self.hbm_seen: list = []
        self.order_rng = np.random.default_rng(args.seed)
        self.plan = None
        self.limits = dict(self.settings["limits"],
                           rel_dev=self.config["guarantees"]["score_rel_tol"])
        self.generator = registry.module("corpora", self.config["corpus"]["generator"])

    def line(self, obj: dict) -> None:
        say({**obj, **self.tag})

    def compare_line(self, short: str, sample: str, got: Compared, **more) -> None:
        """One sample's numbers: a `compare` line now, and under `short` in the
        result's last key and the last lines of standard error."""
        line = got.line(sample)
        for k, v in line["numbers"].items():
            self.compared[f"{short}.{k}"] = [v["value"], v["limit"]]
        self.line({**line, **more})

    # -- set-up -----------------------------------------------------------------
    def start(self) -> None:
        root = os.path.join(registry.CHECKOUT, self.settings["run_directory"])
        os.makedirs(root, exist_ok=True)
        self.run_dir = tempfile.mkdtemp(prefix="run_", dir=root)
        env = dict(self.server_env or {})
        if self.on_cpu and self.cell["chips"] > 1:
            # on the CPU a cell of several chips gets as many virtual devices
            flags = env.get("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
            env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count="
                                f"{self.cell['chips']}").strip()
        self.server = Server(registry.CHECKOUT, self.run_dir, env)
        self.http = Client(self.server)
        self.line({"phase": "sizes", "workload": self.cell["name"],
                   "config": self.cell["config"], "traffic": self.cell["traffic"],
                   "documents": self.n_docs, "seed": self.args.seed,
                   "seconds": self.args.seconds, "trace": self.args.trace,
                   "reduced": self.config["reduced"]})

    def make_corpus(self) -> None:
        t0 = time.perf_counter()
        params = self.config["corpus"]["params"]
        self.corpus = self.generator.generate(params, self.args.seed, self.n_docs)
        bulk = self.config["bulk_documents_per_request"]
        self.bulk_bodies = []
        for lo in range(0, self.n_docs, bulk):
            hi = min(lo + bulk, self.n_docs)
            lines = []
            for i, src in zip(range(lo, hi), self.corpus.sources(lo, hi)):
                lines.append('{"index":{"_id":"%d"}}' % i)
                lines.append(src)
            self.bulk_bodies.append(("\n".join(lines) + "\n").encode())
        self.line({"phase": "corpus", "generator": self.config["corpus"]["generator"],
                   "documents": self.n_docs, "tokens": int(self.corpus.lengths.sum()),
                   "seconds": round(time.perf_counter() - t0, 3)})

    def device(self) -> dict:
        rt = self.http.node_stats("runtime")["runtime"]
        devs = rt["devices"]
        if not devs:
            raise BenchFailure("the server reports no device")
        dev = {"platform": devs[0]["platform"], "kind": devs[0]["device_kind"],
               "count": rt["device_count"]}
        self.line({"phase": "device", **dev,
                   "hbm_bytes_limit": [d.get("hbm_bytes_limit") for d in devs]})
        if not self.rehearsal and not self.assume_chip:
            if dev["platform"] != "tpu":
                raise BenchFailure(
                    f"no accelerator: the server runs on {dev['platform']!r} and the "
                    "caller did not ask for a CPU rehearsal (JAX_PLATFORMS=cpu)")
            if dev["count"] != self.cell["chips"]:
                raise BenchFailure(
                    f"{dev['count']} devices, this cell needs {self.cell['chips']}")
            registry.peaks(dev["kind"])  # an unknown device is an error
        return dev

    def ingest(self) -> None:
        self.http.call("PUT", f"/{self.index}", self.config["index"])
        t0 = time.perf_counter()
        for lo, body in enumerate(self.bulk_bodies):
            r = self.http.call("POST", f"/{self.index}/{self.type}/_bulk", body,
                               timeout=600.0)
            if r.get("errors"):
                raise BenchFailure(f"_bulk reported errors in request {lo}")
        self.refresh()
        secs = time.perf_counter() - t0
        count = self.http.call("GET", f"/{self.index}/_count")["count"]
        self.obs.facts["ingest.docs_per_s"] = self.n_docs / secs
        self.line({"phase": "ingest", "documents": self.n_docs, "count": count,
                   "seconds": round(secs, 3),
                   "docs_per_s": round(self.n_docs / secs, 1)})
        if count != self.n_docs:
            raise BenchFailure(f"_count {count} != {self.n_docs} ingested")
        self.bulk_bodies = None
        t0 = time.perf_counter()
        r = self.http.call("POST", f"/{self.index}/_optimize?max_num_segments=1",
                           timeout=1800.0)
        if r["_shards"]["failed"] or not r["_shards"]["successful"]:
            raise BenchFailure(f"_optimize failed: {r}")
        self.line({"phase": "optimize",
                   "seconds": round(time.perf_counter() - t0, 3)})

    def refresh(self) -> None:
        r = self.http.call("POST", f"/{self.index}/_refresh", timeout=600.0)
        if r["_shards"]["failed"]:
            raise BenchFailure(f"_refresh failed: {r}")

    def stats(self) -> dict:
        st = self.http.node_stats("device,runtime,search_serving,search")
        self.hbm_seen.extend(d.get("hbm_bytes_in_use") or 0
                             for d in st["runtime"]["devices"])
        return st

    def make_reference(self) -> None:
        t0 = time.perf_counter()
        sim = self.config["similarity"]
        self.ref = Reference(self.corpus, sim["k1"], sim["b"])
        self.pool = Pool(self.mix, self.ref, search_path(self.index, self.config),
                         self.limits)
        self.reference_s += time.perf_counter() - t0
        self.line({"phase": "reference", "pool": len(self.pool.queries),
                   "seconds": round(self.reference_s, 3), "counted_in_setup": False})

    def first_answers(self) -> None:
        """A seeded sample of the cell's own searches, one at a time: the first packs
        and compiles. Kept, and compared once the window has closed."""
        n = min(self.settings["sample"], len(self.pool.queries))
        self.pre_sample = [int(i) for i in
                           self.order_rng.choice(len(self.pool.queries), n, False)]
        self.pre_answers = []
        t0 = time.perf_counter()
        for j, i in enumerate(self.pre_sample):
            self.pre_answers.append(self.http.call(
                "POST", self.pool.path, self.pool.bodies[i], timeout=600.0))
            if j == 0:
                self.line({"phase": "first_answer",
                           "seconds": round(time.perf_counter() - t0, 3)})
        self.line({"phase": "first_answers", "searches": n,
                   "seconds": round(time.perf_counter() - t0, 3)})

    def load(self, trace_every: int = 0, meanwhile=None):
        """The window's traffic: the same searches in the same order on the same
        schedule every time it is called, so that a rehearsal meets the batches, and
        so the compiled shapes, that the window will meet."""
        if self.plan is None:
            order = self.order_rng.permutation(len(self.pool.bodies))
            due = None
            if self.mix["loop"] == "open":
                due = schedule(self.mix["rate_per_s"], self.args.seconds,
                               np.random.default_rng(self.mix["plan_seed"]),
                               self.order_rng)
            self.plan = (order, due)
        order, due = self.plan
        return run_load(self.server.port, self.pool.path, self.pool.bodies, order,
                        self.args.seconds, self.mix["clients"], due,
                        self.mix["keep_alive"], trace_every, meanwhile,
                        keeps=self.pool.keeps)

    def warm_up(self) -> None:
        """Every search of the pool once, from a few closed-loop clients (each
        distinct search is a shape the window can meet, and a cold compile cache
        stalls nobody), then rehearsals of the window itself until one adds no
        compile event or the limit is reached; a rehearsal that still compiles then
        is reported, not hidden."""
        w = self.settings["warmup"]
        t0 = time.perf_counter()
        before = self.stats()["device"]["compile"]["total"]
        n = len(self.pool.bodies)
        res = run_load(self.server.port, self.pool.path, self.pool.bodies,
                       self.order_rng.permutation(n), w["pool_pass_max_seconds"],
                       self.mix["warmup_clients"], None, self.mix["keep_alive"], count=n)
        after = self.stats()["device"]["compile"]["total"]
        self.line({"phase": "warm_up_pool", "searches": len(res.done),
                   "failed": len(res.ok) - sum(res.ok), "of_pool": n,
                   "clients": self.mix["warmup_clients"],
                   "compile_events": after - before,
                   "seconds": round(time.perf_counter() - t0, 3)})
        before = after
        t0 = time.perf_counter()
        passes = []
        quiet = False
        while not quiet and len(passes) < w["rehearsals"]:
            res = self.load()
            after = self.stats()["device"]["compile"]["total"]
            passes.append({"searches": len(res.done), "failed": len(res.ok) - sum(res.ok),
                           "compile_events": after - before})
            quiet = after == before
            before = after
        self.line({"phase": "warm_up", "rehearsals": passes, "quiet": quiet,
                   "seconds": round(time.perf_counter() - t0, 3)})

    # -- the window -------------------------------------------------------------
    def window(self) -> None:
        tr = self.settings["trace"]
        traced = bool(self.args.trace)
        self.obs.stats_before = self.stats()
        t_window = time.perf_counter()
        self.obs.facts["setup.seconds"] = \
            t_window - self.t_process - self.reference_s
        res = self.load(tr["span_every"] if traced else 0,
                        self.profile_the_end if traced else None)
        self.obs.stats_after = self.stats()
        self.obs.window = res
        if self.profile is not None:
            self.profiled_spans = res.spans
            self.obs.until = self.profile["start"]
            res.spans = [sp for sp in res.spans if sp[1] <= self.profile["start"]]
        due, sent, done, ok = res.arrays()
        late = (sent - due) * 1000.0
        self.attempted = len(ok)
        self.failed = int((~ok).sum())
        lat = (done - due) * 1000.0
        slow = np.sort(due[lat > 5 * np.median(lat)])
        episodes = int((np.diff(slow) > 0.25).sum() + 1) if len(slow) else 0
        rt0, rt1 = self.obs.stats_before["runtime"], self.obs.stats_after["runtime"]
        fam0 = self.obs.stats_before["device"]["compile"]["by_family"]
        fam1 = self.obs.stats_after["device"]["compile"]["by_family"]
        self.line({"phase": "window", "loop": self.mix["loop"],
                   "latency_ms": {f"p{q}": round(float(np.percentile(lat, q)), 3)
                                  for q in (50, 90, 95, 99, 100)},
                   "slow": {"over_5x_median": int(len(slow)), "episodes": episodes},
                   "server": {
                       "compile_events_by_family": {
                           k: v - fam0.get(k, 0) for k, v in fam1.items()
                           if v - fam0.get(k, 0)},
                       "gc_collections": rt1["gc"]["collections"] - rt0["gc"]["collections"]},
                   "seconds": self.args.seconds, "attempted": self.attempted,
                   "failed": self.failed, "errors": res.errors,
                   "completed_in_window": int((ok & (done <= res.seconds)).sum()),
                   "sampled_spans": len(res.spans),
                   "generator": {
                       "late_ms_p50": float(np.percentile(late, 50)) if len(late) else None,
                       "late_ms_p95": float(np.percentile(late, 95)) if len(late) else None,
                       "late_ms_max": float(late.max()) if len(late) else None,
                       "cpu_s": round(res.cpu_s, 3), "wall_s": round(res.wall_s, 3),
                       "cpu_share_of_one_core": round(res.cpu_s / res.wall_s, 3),
                       "threads": self.mix["clients"]},
                   "setup_seconds": round(self.obs.facts["setup.seconds"], 3),
                   "reference_s_not_in_setup": round(self.reference_s, 3)})
        if not self.attempted:
            raise BenchFailure("the window sent no search")
        for path in self.must_not_rise():
            rose = self.obs.delta(path)
            self.compared[f"rose.{path}"] = [rose, 0]
            if rose is None or rose > 0:
                self.problems.append(f"{path} rose by {rose} during the window")

    def must_not_rise(self) -> list:
        """The counters that may not rise over the window: those of every cell and
        those the configuration adds for its own."""
        return self.settings["must_not_rise"] + self.config.get("must_not_rise", [])

    def reductions(self) -> list:
        """The reductions of a traced run: those `settings.json` lists, and every one
        that a per-layer metric of the cell names."""
        named = [d.get("reduction") for _m, d in registry.metrics_of(
            self.bench, self.cell["name"], "per_layer", "layer_metrics")]
        return list(dict.fromkeys(
            self.settings["trace"]["reductions"] + [n for n in named if n]))

    def profile_the_end(self, t0: float) -> None:
        """One profiler window over the window's last seconds, opened and closed over
        REST: only the server's process can trace the chip. The profiler's Python
        tracer slows the server's host severalfold and cannot be switched off from
        outside, so the spans are read from the searches that finished before it
        started, and the trace is written out after the window has closed."""
        tr = self.settings["trace"]
        length = min(tr["profile_seconds"], self.args.seconds / 2.0)
        begin = t0 + self.args.seconds - length
        time.sleep(max(0.0, begin - time.perf_counter()))
        directory = os.path.join(self.run_dir, "profile")
        epoch_at_t0 = time.time() - (time.perf_counter() - t0)
        t_start = time.perf_counter()
        self.http.call("POST", "/_nodes/_local/profiler/start", {"dir": directory})
        t_started = time.perf_counter()
        time.sleep(length)
        t_stop = time.perf_counter()
        files = self.http.call("POST", "/_nodes/_local/profiler/stop",
                               timeout=300.0)["files"]
        self.profile = {"files": files, "epoch_at_t0": epoch_at_t0,
                        "start": t_started - t0, "stop": t_stop - t0,
                        "start_call_s": t_started - t_start,
                        "stop_call_s": time.perf_counter() - t_stop}

    def reduce_trace(self) -> None:
        tr = self.settings["trace"]
        p = self.profile
        traces = [f for f in p["files"] if f.endswith(".xplane.pb")]
        if not traces:
            raise BenchFailure(f"the profiler wrote no .xplane.pb: {p['files']}")
        t0 = time.perf_counter()
        planes = xplane.read_planes(traces[0], tr["device_plane_prefix"])
        planes = {k: v for k, v in planes.items()
                  if not any(s in k for s in tr["skip_planes_with"])}
        start_ns, stop_ns = xplane.profile_times(traces[0])
        res = self.obs.window
        _due, sent, done, ok = res.arrays()
        # the client's clock (seconds from the window's start) onto the profiler's,
        # and the traced window as the profiler itself timed it
        if start_ns is not None and stop_ns is not None:
            shift = p["epoch_at_t0"] - start_ns / 1e9
            window_s = (stop_ns - start_ns) / 1e9
        else:
            shift = -p["start"]
            window_s = p["stop"] - p["start"]
        host_spans = []
        offsets = [(s + d) / 2 - (spans[0][1] + spans[0][2]) / 2
                   for s, d, spans in res.spans]
        if offsets:
            server_to_client = float(np.median(offsets))
            for _s, _d, spans in self.profiled_spans:
                host_spans.extend((name, a + server_to_client + shift,
                                   b + server_to_client + shift)
                                  for name, a, b in spans)
        trace = {"planes": planes, "window_s": window_s,
                 "host_spans": host_spans, "requests": (sent + shift, done + shift)}
        in_profile = ok & (done + shift >= 0) & (done + shift <= window_s)
        self.obs.facts["profile.searches"] = int(in_profile.sum())
        for name in self.reductions():
            self.obs.reduced[name] = registry.module("reductions", name).reduce(trace)
        self.line({"phase": "trace", "file_bytes": os.path.getsize(traces[0]),
                   "planes": {k: {n: len(l["names"]) for n, l in v["lines"].items()}
                              for k, v in planes.items()},
                   "profile_times_known": start_ns is not None,
                   "window_s": window_s, "asked_s": p["stop"] - p["start"],
                   "start_call_s": round(p["start_call_s"], 3),
                   "stop_call_s": round(p["stop_call_s"], 3),
                   "searches_in_profile": self.obs.facts["profile.searches"],
                   "reduce_seconds": round(time.perf_counter() - t0, 3)})
        if tr.get("keep_trace_in") and os.environ.get("BENCH_KEEP_TRACE"):
            keep = os.path.join(registry.CHECKOUT, tr["keep_trace_in"])
            os.makedirs(keep, exist_ok=True)
            xplane.copy_planes(
                traces[0], os.path.join(
                    keep, f"{self.cell['name']}.{self.args.seed}.xplane.pb"),
                lambda name: name.startswith(tr["device_plane_prefix"])
                or name == "Task Environment")

    # -- after the window -------------------------------------------------------
    def compare(self) -> None:
        tol = self.limits["rel_dev"]
        pre = Compared(self.pool.limits)
        for i, resp in zip(self.pre_sample, self.pre_answers):
            pre.add(self.pool.compare(self.ref, i, resp, tol))
        self.compare_line("before", "before the window", pre)
        res = self.obs.window
        done_ok = [j for j, ok in enumerate(res.ok) if ok]
        n = min(self.settings["sample"], len(done_ok))
        win = Compared(self.pool.limits)
        if n:
            picks = set(int(j) for j in self.order_rng.choice(done_ok, n, False))
            # the longest search the window finished is always in the sample
            picks.add(max(done_ok, key=lambda j: len(self.pool.queries[res.query[j]]["terms"])))
            for j in sorted(picks):
                win.add(self.pool.compare(self.ref, res.query[j],
                                          as_response(res.answer[j]), tol))
        self.compare_line("window", "the window's own responses", win)
        for name, c in (("before the window", pre), ("the window", win)):
            if not c.passed:
                self.problems.append(f"a response of the sample {name} differs from "
                                     f"the reference: {c.numbers}")

    def late_writes(self) -> None:
        """New documents, each with one term no other document has: acknowledged,
        then read back by `GET` at once and by `_search` after the next refresh."""
        n = self.settings["late_writes"]
        params = self.config["corpus"]["params"]
        docs, columns = self.generator.late_documents(
            params, self.corpus, self.args.seed + 2, n)
        grown = self.corpus.extended(docs, columns)
        n0 = self.corpus.n_docs
        sources = grown.sources(n0, n0 + n)
        bad = []
        for j, src in enumerate(sources):
            r = self.http.call("PUT", f"/{self.index}/{self.type}/{n0 + j}", src.encode())
            if not r.get("created"):
                bad.append(f"write {n0 + j} not acknowledged: {r}")
            got = self.http.call("GET", f"/{self.index}/{self.type}/{n0 + j}")
            if not got.get("found") or got["_source"] != json.loads(src):
                bad.append(f"write {n0 + j} not read back by GET")
        self.refresh()
        sim = self.config["similarity"]
        ref = Reference(grown, sim["k1"], sim["b"])
        field = self.corpus.text_field
        found = Compared(self.limits)
        for j in range(n):
            term = self.corpus.n_vocab + j
            resp = self.http.call("POST", self.pool.path,
                                  {"query": {"match": {field: word(term)}}, "size": 10})
            scores, matched = ref.score_all([term], False)
            found.add(check_hits(ref, scores, matched, 10, resp, self.limits["rel_dev"]))
            if [h["_id"] for h in resp["hits"]["hits"]] != [str(n0 + j)]:
                bad.append(f"write {n0 + j} not found by _search after the refresh")
        self.compare_line("late_writes", "late writes", found, documents=n,
                          read_back="GET at once, _search after _refresh", problems=bad)
        self.compared["late_writes.not_read_back"] = [len(bad), 0]
        if bad or not found.passed:
            self.problems.append(f"late writes: {bad or found.numbers}")

    # -- the result -------------------------------------------------------------
    def metrics(self, group: str, directory: str) -> dict:
        out = {}
        for entry, definition in registry.metrics_of(
                self.bench, self.cell["name"], group, directory):
            value = readers.read(definition, self.obs)
            if value is not None:
                out[entry["name"]] = {"value": value, "unit": entry["unit"]}
        return out

    def result(self, dev: dict) -> dict:
        traced = bool(self.args.trace)
        device = {**dev, "memory_peak_bytes": max(self.hbm_seen, default=0)}
        out = {"correct": not self.problems and not self.rehearsal and
               (dev["platform"] == "tpu" or self.assume_chip),
               "attempted": self.attempted, "failed": self.failed}
        if traced:
            tr = self.settings["trace"]
            out["metrics"] = self.metrics("per_layer", "layer_metrics")
            busy = self.obs.reduced.get(tr["busy"]) or {}
            if busy:
                device.update({k: busy[k] for k in ("busy_s", "window_s",
                                                    "busy_s_by_chip") if k in busy})
            elif not self.rehearsal and not self.assume_chip:
                self.problems.append("no operation ran on the device in the traced "
                                     "window")
                out["correct"] = False
            b = tr["breakdown"]
            ops = (self.obs.reduced.get(b["device_ops"]["reduction"]) or {}).get(
                b["device_ops"]["field"]) or []
            gaps = (self.obs.reduced.get(b["idle_gaps"]["reduction"]) or {}).get(
                b["idle_gaps"]["field"]) or []
            out["breakdown"] = {"device_ops": ops[:10], "idle_gaps": gaps[:10]}
        else:
            out["metrics"] = self.metrics("end_to_end", "end_to_end")
        out["device"] = device
        out["compared"] = self.compared  # last: every number compared, beside its limit
        return out


def run(args, t_process: float, **test_options) -> int:
    run_ = None
    try:
        run_ = Run(args, t_process, **test_options)
        run_.start()
        run_.make_corpus()
        run_.server.wait_started()
        dev = run_.device()
        run_.ingest()
        run_.make_reference()
        run_.first_answers()
        run_.warm_up()
        run_.window()
        if run_.profile is not None:
            run_.reduce_trace()
        run_.compare()
        run_.late_writes()
        result = run_.result(dev)
        rc = run_.server.stop()
        if rc != 0:
            run_.problems.append(f"server exit code {rc} after SIGTERM (None: killed)")
            result["correct"] = False
        if "jax" in sys.modules:
            raise BenchFailure("the client imported JAX: it would hold the chip")
        run_.line({"phase": "checks", "passed": not run_.problems,
                   "problems": run_.problems,
                   "seconds": round(time.perf_counter() - t_process, 3)})
        say(result)
        for name, (value, limit) in run_.compared.items():
            sys.stderr.write(f"compared {name}: {value} (limit {limit})\n")
        if run_.rehearsal:
            return 2
        return 0 if result["correct"] else 1
    except Exception as e:  # noqa: BLE001: every failed phase ends here, with no result
        say({"phase": "failed", "error": f"{type(e).__name__}: {e}"[:2000]})
        if run_ is not None and run_.server is not None:
            sys.stderr.write(run_.server.log_tail() + "\n")
        return 1
    finally:
        if run_ is not None and run_.server is not None:
            run_.server.stop()
            shutil.rmtree(run_.run_dir, ignore_errors=True)
