"""Compile warming: shape-driven executable pre-warming + autotuned bucket ladders.

Every first sighting of a (plan family × bucket shape) pays a full XLA compile on
the serving path: a stall of the one drainer. This module is the
off-path answer (ROADMAP item 5), three legs sharing one registry:

  * **WarmSpec registry** — every kernel launch site records, once per distinct
    (site, static params, arg shapes/dtypes) signature, a JSON-able WarmSpec
    (`record_launch`). The warmer drains the registry on the `warmer` pool
    (`warm_cycle`): for each spec not yet executed in this process it rebuilds
    the jitted callable through a per-site builder and invokes it ONCE with
    zero-filled `jax.device_put` dummies under `compile_tag(family)`. Invoking
    the real callable (not `.lower().compile()`) is load-bearing: on jax 0.4.x
    an AOT-compiled executable does NOT populate the jit dispatch cache, so a
    later serving call would recompile anyway — the dummy invocation is what
    makes the next real call a cache hit. A spec recorded by a serving launch
    is already warm by construction (that launch populated the cache), so
    steady-state warm cycles do zero device work; only manifest-restored specs
    (restart) execute.
  * **Autotuned bucket ladders** (`BucketLadder`/`LadderBook`) — the fixed
    pow-2 `_pow2_bucket`/`_k_bucket` ladders become per-dimension ladders
    fitted to the observed shape histogram: bounded rung count, monotone,
    exact pow-2 fallback while cold (bit-identical to the old behavior until
    an autotune commits). Fits run off-path inside warm cycles and only
    commit past a sample floor AND a padding-waste improvement threshold, so
    committed rungs are stable — a refit mid-serving would re-cliff first
    sightings. tools/tpulint's compile-surface lattice knows `_ladder_bucket`
    as a bucketed classifier.
  * **Shape manifest persistence** — specs + ladders + mesh plan signatures
    persist to `<path.data>/compile_manifest.json` (atomic rename) on warm
    cycles and node close; a restarted node loads the manifest and its startup
    warm cycle replays exactly what production ran. Paired with the persistent
    XLA compilation cache (jaxenv.enable_persistent_compile_cache: at
    JAX_COMPILATION_CACHE_DIR where set, else `<checkout>/.jax_cache`), the
    restart warm pays a disk deserialize, not a fleet recompile. NOTE: a persistent-cache HIT still emits a
    backend_compile_duration event (pxla wraps compile_or_get_cached), so the
    manifest replay — not the disk cache — is what buys the serving path its
    zero-event steady state.

Fault containment: each spec warms under its family's `compile:<family>`
device-health circuit — an open circuit skips the spec (never blocks serving),
and a warm failure records into the circuit off-path (devicehealth taxonomy).

Import discipline: this module imports stdlib only at module scope — ops/,
search/, and parallel/ modules import it (ladder call sites + builder
registration), so it must never import them back. jax imports are lazy inside
the warm path.
"""

from __future__ import annotations

import json
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass

# ladder dimension vocabulary (fixed → bounded Prometheus label set):
#   q         query-count bucket (batcher flat/mesh coalescing, mesh Qp)
#   k         top-k bucket (batcher _k_bucket)
#   docs      segment doc_pad (device_index pack + mesh build)
#   nb        posting-block pad (device_index pack + mesh build)
#   terms     flat term-entry pad (scoring.build_term_batch, mesh assemble)
#   sparse_tb sparse per-query block-count bucket (plan_sparse_buckets)
#   sparse_qb sparse queries-per-bucket chunk (plan_sparse_buckets)
LADDER_DIMS = ("q", "k", "docs", "nb", "terms", "sparse_tb", "sparse_qb")


def _pow2(n: int, minimum: int) -> int:
    b = max(1, minimum)
    while b < n:
        b *= 2
    return b


class BucketLadder:
    """One dimension's bucket ladder: observed-value histogram + fitted rungs.

    `bucket(n, minimum)` is the hot-path call (one leaf lock, O(rungs) scan):
    it records n into a bounded histogram and returns the smallest committed
    rung ≥ n, falling back to the exact pow-2 ladder while cold or past the
    top rung. `autotune()` (warm cycle, off-path) fits ≤ max_rungs monotone
    rungs minimizing count-weighted padding waste over the histogram, and
    commits only when the fit beats pow-2 waste by `improvement` AND the
    histogram holds ≥ min_samples observations — committed rungs must be worth
    the one-time recompile their adoption costs."""

    HIST_CAP = 256  # distinct (rounded) values tracked; smallest-count evicts

    def __init__(self, dim: str, max_rungs: int = 8):
        self.dim = dim
        self.max_rungs = max(2, max_rungs)
        self._lock = threading.Lock()  # leaf: dict/tuple ops only
        self._hist: dict[int, int] = {}  # rounded value -> sightings
        self._total = 0
        self._rungs: tuple[int, ...] | None = None  # committed, sorted
        self._quantum = 1  # rounding lane (the call sites' `minimum`)
        self.commits = 0

    # -- hot path -------------------------------------------------------------
    def bucket(self, n: int, minimum: int) -> int:
        n = max(int(n), 1)
        q = max(int(minimum), 1)
        v = ((n + q - 1) // q) * q  # round up to the lane multiple
        with self._lock:
            self._quantum = q
            c = self._hist.get(v)
            if c is not None:
                self._hist[v] = c + 1
            elif len(self._hist) < self.HIST_CAP:
                self._hist[v] = 1
            else:  # evict the coldest rounded value (rare: cap overflow only)
                coldest = min(self._hist, key=self._hist.get)
                if self._hist[coldest] <= 1:
                    del self._hist[coldest]
                    self._hist[v] = 1
            self._total += 1
            rungs = self._rungs
        if rungs is not None:
            for r in rungs:
                if r >= n and r >= q:
                    return r
        return _pow2(n, q)

    # -- off-path fit ---------------------------------------------------------
    def autotune(self, min_samples: int, improvement: float) -> bool:
        """Fit and maybe commit; returns True when a new ladder committed."""
        with self._lock:
            if self._total < min_samples or not self._hist:
                return False
            items = sorted(self._hist.items())
            quantum = self._quantum
        vals = [v for v, _ in items]
        cnts = [c for _, c in items]
        pow2_waste = sum(c * (_pow2(v, quantum) - v)
                         for v, c in zip(vals, cnts))
        rungs = self._fit(vals, cnts)
        fit_waste = 0
        ri = 0
        for v, c in zip(vals, cnts):
            while rungs[ri] < v:
                ri += 1
            fit_waste += c * (rungs[ri] - v)
        # pow-2 waste can legitimately be 0 (every observed value already a
        # pow-2 lane multiple) — then there is nothing to win, keep fallback
        if pow2_waste <= 0 or fit_waste > pow2_waste * (1.0 - improvement):
            return False
        with self._lock:
            if tuple(rungs) == self._rungs:
                return False
            self._rungs = tuple(rungs)
            self.commits += 1
        return True

    def _fit(self, vals: list[int], cnts: list[int]) -> list[int]:
        """Weighted-waste optimal ≤ max_rungs rung placement (DP over the
        sorted distinct values; a rung at vals[j] covers every value ≤ it)."""
        m = len(vals)
        R = min(self.max_rungs, m)
        # prefix sums for O(1) segment waste: waste(i..j) = sum c_l*(v_j - v_l)
        pc = [0] * (m + 1)  # prefix counts
        pw = [0] * (m + 1)  # prefix c*v
        for i, (v, c) in enumerate(zip(vals, cnts)):
            pc[i + 1] = pc[i] + c
            pw[i + 1] = pw[i] + c * v

        def seg(i: int, j: int) -> int:  # values i..j inclusive, rung at v_j
            return vals[j] * (pc[j + 1] - pc[i]) - (pw[j + 1] - pw[i])

        INF = float("inf")
        dp = [[INF] * (R + 1) for _ in range(m)]
        arg = [[0] * (R + 1) for _ in range(m)]
        for j in range(m):
            dp[j][1] = seg(0, j)
            for r in range(2, R + 1):
                for i in range(j):
                    if dp[i][r - 1] == INF:
                        continue
                    cand = dp[i][r - 1] + seg(i + 1, j)
                    if cand < dp[j][r]:
                        dp[j][r] = cand
                        arg[j][r] = i
        best_r = min(range(1, R + 1), key=lambda r: dp[m - 1][r])
        rungs = []
        j, r = m - 1, best_r
        while r >= 1:
            rungs.append(vals[j])
            j, r = arg[j][r], r - 1
        return sorted(rungs)

    # -- persistence / stats --------------------------------------------------
    def to_json(self) -> dict:
        with self._lock:
            return {"hist": {str(v): c for v, c in self._hist.items()},
                    "total": self._total, "quantum": self._quantum,
                    "rungs": list(self._rungs) if self._rungs else None}

    def load_json(self, data: dict) -> None:
        with self._lock:
            for v, c in (data.get("hist") or {}).items():
                vi = int(v)
                self._hist[vi] = self._hist.get(vi, 0) + int(c)
            self._total += int(data.get("total", 0))
            self._quantum = int(data.get("quantum", self._quantum))
            rungs = data.get("rungs")
            if rungs and self._rungs is None:
                self._rungs = tuple(sorted(int(r) for r in rungs))

    def stats(self) -> dict:
        with self._lock:
            return {"observations": self._total,
                    "distinct": len(self._hist),
                    "rungs": list(self._rungs) if self._rungs else None,
                    "commits": self.commits}


class LadderBook:
    """The process's named ladders (LADDER_DIMS vocabulary). `bucket` is the
    single hot-path entry — ops/device_index._ladder_bucket delegates here."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ladders: dict[str, BucketLadder] = {}
        self.max_rungs = 8

    def ladder(self, dim: str) -> BucketLadder:
        lad = self._ladders.get(dim)
        if lad is None:
            with self._lock:
                lad = self._ladders.setdefault(
                    dim, BucketLadder(dim, self.max_rungs))
        return lad

    def bucket(self, dim: str, n: int, minimum: int) -> int:
        return self.ladder(dim).bucket(n, minimum)

    def autotune_all(self, min_samples: int, improvement: float) -> int:
        return sum(1 for lad in list(self._ladders.values())
                   if lad.autotune(min_samples, improvement))

    def to_json(self) -> dict:
        return {dim: lad.to_json() for dim, lad in self._ladders.items()}

    def load_json(self, data: dict) -> None:
        for dim, frag in (data or {}).items():
            if dim in LADDER_DIMS:
                self.ladder(dim).load_json(frag)

    def stats(self) -> dict:
        return {dim: lad.stats() for dim, lad in self._ladders.items()}

    def reset(self) -> None:  # test hook
        with self._lock:
            self._ladders.clear()


LADDERS = LadderBook()


# ---------------------------------------------------------------------------
# argument-signature encoding: JSON-able, roundtrip-stable
# ---------------------------------------------------------------------------
# array leaf  -> {"s": [shape], "d": "<dtype str>"}
# literal     -> {"v": <int|float|bool|str|None>}  (static python args)
# tuple       -> {"t": [...]}   (tuple-vs-list matters: jit pytrees use tuples)
# list        -> [...]
# None        -> None


def encode_args(args) -> list:
    return [_encode(a) for a in args]


def _encode(a):
    if a is None:
        return None
    shape = getattr(a, "shape", None)
    if shape is not None and hasattr(a, "dtype"):
        return {"s": [int(d) for d in shape], "d": str(a.dtype)}
    if isinstance(a, tuple):
        return {"t": [_encode(x) for x in a]}
    if isinstance(a, list):
        return [_encode(x) for x in a]
    if isinstance(a, (bool, int, float, str)):
        return {"v": a}
    raise TypeError(f"unencodable launch arg of type {type(a).__name__}")


def shape_sig(args) -> tuple:
    """Hashable signature of encode_args — the registry's fast dedup key."""
    return tuple(_sig(a) for a in args)


def _sig(a):
    if a is None:
        return None
    shape = getattr(a, "shape", None)
    if shape is not None and hasattr(a, "dtype"):
        return (tuple(int(d) for d in shape), str(a.dtype))
    if isinstance(a, (tuple, list)):
        return (type(a).__name__,) + tuple(_sig(x) for x in a)
    return ("v", a)


def materialize(argspec: list):
    """Zero-filled device dummies for one encoded arg list — compilation (and
    the dispatch-cache key) depends on shapes/dtypes only, never values.
    Explicit device_put keeps the warm path legal under
    transfer_guard("disallow")."""
    import jax
    import numpy as np

    def mk(e):
        if e is None:
            return None
        if isinstance(e, dict):
            if "s" in e:
                return jax.device_put(
                    np.zeros(tuple(e["s"]), dtype=np.dtype(e["d"])))
            if "t" in e:
                return tuple(mk(x) for x in e["t"])
            return e.get("v")
        if isinstance(e, list):
            return [mk(x) for x in e]
        raise TypeError(f"bad argspec node: {e!r}")

    return [mk(e) for e in argspec]


def _freeze(x):
    """Params as recorded vs params as JSON-roundtripped must hash equal."""
    if isinstance(x, (tuple, list)):
        return tuple(_freeze(v) for v in x)
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    return x


def _thaw_params(params):
    """JSON lists back to tuples (builder getters key caches on tuples)."""
    if isinstance(params, list):
        return tuple(_thaw_params(v) for v in params)
    return params


@dataclass
class WarmSpec:
    """One warmable executable: site names the builder, params feed it, and
    argspec shapes the dummy invocation."""

    site: str
    family: str
    params: tuple
    argspec: list

    def key(self) -> tuple:
        return (self.site, _freeze(self.params), _freeze_spec(self.argspec))

    def to_json(self) -> dict:
        return {"site": self.site, "family": self.family,
                "params": list(self.params), "args": self.argspec}

    @staticmethod
    def from_json(d: dict) -> "WarmSpec":
        return WarmSpec(site=str(d["site"]), family=str(d["family"]),
                        params=_thaw_params(d.get("params", [])),
                        argspec=d.get("args", []))


def _freeze_spec(argspec) -> tuple:
    def fz(e):
        if e is None:
            return None
        if isinstance(e, dict):
            if "s" in e:
                return (tuple(e["s"]), e["d"])
            if "t" in e:
                return ("tuple",) + tuple(fz(x) for x in e["t"])
            return ("v", e.get("v"))
        if isinstance(e, list):
            return ("list",) + tuple(fz(x) for x in e)
        return ("v", e)

    return tuple(fz(e) for e in argspec)


MANIFEST_NAME = "compile_manifest.json"
# the launch sites' operand lists are part of every recorded argspec: a
# manifest written under another list would replay calls the programs no
# longer take, so its specs are dropped at load (the ladders are kept)
# 2: operand planes (scoring.TermBatch.tri / SparseBatch.slots); 3: the dense
# launches take the head rows and the head-slot plane (TermBatch.head); 4: the
# phrase launch takes the block rows it gathers as a list (phrase_operands);
# 5: the dense launches take ONE packed operand plane and M as a literal
# (scoring.TermBatch.plane), and a mask may be a tuple of rows; 6: the
# phrase launch's params end in the slots of its line (scoring.phrase_slots)
MANIFEST_VERSION = 6
_MESH_RING = 4  # recent mesh plan batches kept per index


class CompileWarmRegistry:
    """Process-wide warm registry: spec capture, builders, warm cycles, the
    shape manifest, and mesh plan-signature rings. One instance (`REGISTRY`);
    nodes configure it with their settings/path.data (multi-node test
    processes share it — the union of observed shapes warms everywhere, which
    is exactly the fleet semantics)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = True
        self.persist = True
        self.max_specs = 256
        self.autotune_min_samples = 512
        self.autotune_improvement = 0.10
        self._builders: dict = {}
        self._specs: "OrderedDict[tuple, WarmSpec]" = OrderedDict()
        self._warmed: set = set()  # spec keys already executed in-process
        self._mesh: dict[str, list] = {}  # index -> [entry dicts], newest last
        self._mesh_plans: dict[str, list] = {}  # index -> live plan payloads
        self._dirty = False
        # counters (leaf lock)
        self.specs_recorded = 0
        self.specs_loaded = 0
        self.warmed_total = 0
        self.warm_failures = 0
        self.warm_skipped_circuit = 0
        self.warm_cycles = 0
        self.ladder_commits = 0
        self.manifest_saves = 0
        self.mesh_warms = 0
        self.mesh_warm_failures = 0
        self.last_reason = None
        # compile events observed by family×pool (jaxenv listener feed) — the
        # runtime proof of "pool=warmer/startup only" on a warmed node
        self.compiles_by_pool: dict[str, int] = {}

    # -- wiring ---------------------------------------------------------------
    def configure(self, settings, data_path: str | None) -> None:
        """Node-boot hook: read knobs, load this path's manifest, arm the
        persistent XLA compilation cache (placed by jaxenv's one rule)."""
        self.enabled = bool(settings.get_bool("node.compile_warming.enabled",
                                              True))
        self.persist = bool(settings.get_bool("node.compile_warming.persist",
                                              True))
        self.max_specs = max(16, settings.get_int(
            "node.compile_warming.max_specs", 256))
        self.autotune_min_samples = max(1, settings.get_int(
            "node.compile_warming.autotune_min_samples", 512))
        self.autotune_improvement = settings.get_float(
            "node.compile_warming.autotune_improvement", 0.10)
        LADDERS.max_rungs = max(2, settings.get_int(
            "node.compile_warming.max_rungs", 8))
        if not self.enabled or not data_path:
            return
        if self.persist:
            self.load_manifest(os.path.join(data_path, MANIFEST_NAME))
        if settings.get_bool("node.compile_cache.persist", True):
            from . import jaxenv

            jaxenv.enable_persistent_compile_cache()
        from . import jaxenv

        jaxenv.register_compile_observer(self._on_compile_event)

    def _on_compile_event(self, family: str, pool: str) -> None:
        """jaxenv compile-listener feed: per-pool attribution (warm-queue
        pressure signal — a compile on a serving pool is a cold spec the next
        warm cycle should already know about via record_launch)."""
        with self._lock:
            k = f"{family}/{pool}"
            self.compiles_by_pool[k] = self.compiles_by_pool.get(k, 0) + 1

    def builder(self, site: str):
        """Decorator: register `site`'s params -> jitted-callable builder."""

        def deco(fn):
            self._builders[site] = fn
            return fn

        return deco

    # -- capture (hot path: one sig walk + one dict hit per launch) -----------
    def record_launch(self, site: str, family: str, params: tuple,
                      args) -> None:
        if not self.enabled:
            return
        try:
            key = (site, _freeze(params), shape_sig(args))
        except Exception:  # noqa: BLE001 — unhashable arg: not warmable
            return
        with self._lock:
            if key in self._specs:
                self._warmed.add(key)
                self._specs.move_to_end(key)
                return
        # encode OUTSIDE the lock (slow path: first sighting only)
        try:
            spec = WarmSpec(site=site, family=family, params=_freeze(params),
                            argspec=encode_args(args))
        except TypeError:
            return
        with self._lock:
            if key in self._specs:
                return
            self._specs[key] = spec
            self._warmed.add(key)  # this launch itself populated the cache
            self.specs_recorded += 1
            self._dirty = True
            while len(self._specs) > self.max_specs:
                old, _ = self._specs.popitem(last=False)
                self._warmed.discard(old)

    # -- mesh plan signatures --------------------------------------------------
    def record_mesh(self, index: str, plans, k: int, plan_dicts) -> None:
        """Remember a recently served mesh batch: live plan objects for
        same-process executor-rebuild warming, JSON dicts for the manifest."""
        if not self.enabled:
            return
        entry = {"k": int(k), "plans": plan_dicts, "q": len(plan_dicts)}
        sig = (entry["q"], entry["k"],
               tuple(len(p.get("clauses", ())) for p in plan_dicts))
        with self._lock:
            ring = self._mesh.setdefault(index, [])
            sigs = [(e["q"], e["k"],
                     tuple(len(p.get("clauses", ())) for p in e["plans"]))
                    for e in ring]
            if sig in sigs:
                return
            ring.append(entry)
            del ring[:-_MESH_RING]
            live = self._mesh_plans.setdefault(index, [])
            live.append({"k": int(k), "plans": list(plans)})
            del live[:-_MESH_RING]
            self._dirty = True

    def mesh_entries(self, index: str):
        """(live plan payloads, manifest plan dicts) for one index — the
        executor-rebuild warm replays live payloads when present (same
        process), else the manifest dicts (restart)."""
        with self._lock:
            return (list(self._mesh_plans.get(index, ())),
                    list(self._mesh.get(index, ())))

    def note_mesh_warm(self, ok: bool) -> None:
        with self._lock:
            if ok:
                self.mesh_warms += 1
            else:
                self.mesh_warm_failures += 1

    # -- warm cycle (warmer pool only) ----------------------------------------
    def warm_cycle(self, reason: str, save_path: str | None = None) -> dict:
        """Autotune ladders, replay every not-yet-warm spec, persist the
        manifest. Runs on the warmer pool (node startup, searcher install,
        manual warm); never on a serving thread."""
        if not self.enabled:
            return {"warmed": 0, "failed": 0, "skipped": 0}
        from .devicehealth import DEVICE_HEALTH
        from .jaxenv import compile_tag

        committed = LADDERS.autotune_all(self.autotune_min_samples,
                                         self.autotune_improvement)
        with self._lock:
            self.ladder_commits += committed
            if committed:
                self._dirty = True
            pending = [(k, s) for k, s in self._specs.items()
                       if k not in self._warmed]
            self.warm_cycles += 1
            self.last_reason = reason
        # builders register at their module's import; after a restart the
        # manifest can hold specs for modules nothing imported yet — pull the
        # known builder homes in lazily (function scope: common/ never imports
        # ops/ at module scope)
        if any(self._builders.get(s.site) is None for _, s in pending):
            try:
                from ..ops import scoring  # noqa: F401 — registers scoring.*
            except Exception:  # noqa: BLE001 — missing deps: specs stay pending
                pass
        warmed = failed = skipped = 0
        for key, spec in pending:
            domain = f"compile:{spec.family}"
            if DEVICE_HEALTH.blocked((domain,)):
                skipped += 1
                continue
            build = self._builders.get(spec.site)
            if build is None:
                continue  # builder module not imported yet; next cycle
            try:
                import jax

                fn = build(spec.params)
                args = materialize(spec.argspec)
                with compile_tag(spec.family):
                    out = fn(*args)
                jax.block_until_ready(out)
            except Exception as e:  # noqa: BLE001 — warm failure is off-path
                failed += 1
                DEVICE_HEALTH.record_failure(domain, e)
                continue
            warmed += 1
            DEVICE_HEALTH.note_success((domain,))
            with self._lock:
                self._warmed.add(key)
        with self._lock:
            self.warmed_total += warmed
            self.warm_failures += failed
            self.warm_skipped_circuit += skipped
        if save_path and self.persist:
            self.save_manifest(os.path.join(save_path, MANIFEST_NAME))
        return {"warmed": warmed, "failed": failed, "skipped": skipped,
                "ladders_committed": committed}

    def pending_count(self) -> int:
        with self._lock:
            return sum(1 for k in self._specs if k not in self._warmed)

    # -- persistence -----------------------------------------------------------
    def save_manifest(self, path: str) -> None:
        with self._lock:
            if not self._dirty:
                return
            payload = {"version": MANIFEST_VERSION,
                       "specs": [s.to_json() for s in self._specs.values()],
                       "ladders": LADDERS.to_json(),
                       "mesh": {i: list(r) for i, r in self._mesh.items()}}
            self._dirty = False
            self.manifest_saves += 1
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
        except OSError:
            with self._lock:
                self._dirty = True  # retry on the next cycle/close

    def load_manifest(self, path: str) -> int:
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            return 0
        LADDERS.load_json(payload.get("ladders") or {})
        loaded = 0
        specs = (payload.get("specs", ())
                 if payload.get("version") == MANIFEST_VERSION else ())
        for d in specs:
            try:
                spec = WarmSpec.from_json(d)
                key = spec.key()
            except (KeyError, TypeError):
                continue
            with self._lock:
                if key not in self._specs:
                    self._specs[key] = spec  # NOT in _warmed: startup warms it
                    loaded += 1
        with self._lock:
            for index, ring in (payload.get("mesh") or {}).items():
                cur = self._mesh.setdefault(index, [])
                for e in ring:
                    if e not in cur:
                        cur.append(e)
                del cur[:-_MESH_RING]
            self.specs_loaded += loaded
        return loaded

    def reset(self) -> None:
        """Test hook: forget ALL in-process warm state. Paired with
        jax.clear_caches() (and a LADDERS.reset()) this simulates a process
        restart inside one interpreter — the restarted 'node' must re-earn
        its warmth from the manifest, exactly like a real rolling restart."""
        with self._lock:
            self._specs.clear()
            self._warmed.clear()
            self._mesh.clear()
            self._mesh_plans.clear()
            self._dirty = False
            self.specs_recorded = 0
            self.specs_loaded = 0
            self.warmed_total = 0
            self.warm_failures = 0
            self.warm_skipped_circuit = 0
            self.warm_cycles = 0
            self.ladder_commits = 0
            self.manifest_saves = 0
            self.mesh_warms = 0
            self.mesh_warm_failures = 0
            self.last_reason = None
            self.compiles_by_pool.clear()

    # -- observability ---------------------------------------------------------
    def stats(self) -> dict:
        from . import jaxenv

        with self._lock:
            return {
                "enabled": self.enabled,
                "persistent_cache_dir": jaxenv.armed_compile_cache_dir(),
                "specs": len(self._specs),
                "specs_recorded": self.specs_recorded,
                "specs_loaded": self.specs_loaded,
                "pending": sum(1 for k in self._specs
                               if k not in self._warmed),
                "warmed_total": self.warmed_total,
                "warm_failures": self.warm_failures,
                "warm_skipped_circuit": self.warm_skipped_circuit,
                "warm_cycles": self.warm_cycles,
                "last_reason": self.last_reason,
                "ladder_commits": self.ladder_commits,
                "manifest_saves": self.manifest_saves,
                "mesh_indices": len(self._mesh),
                "mesh_warms": self.mesh_warms,
                "mesh_warm_failures": self.mesh_warm_failures,
                "compiles_by_pool": dict(self.compiles_by_pool),
                "ladders": LADDERS.stats(),
            }


REGISTRY = CompileWarmRegistry()
