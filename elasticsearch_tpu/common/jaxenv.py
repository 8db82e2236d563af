"""JAX platform selection, the compile-cache placement rule and the runtime sanitizer.

JAX picks its platform itself: the TPU where one is attached, the CPU where the
caller set JAX_PLATFORMS=cpu. Nothing in the package overrides that choice; the one
exception is `force_cpu_platform`, the tests' way to get virtual CPU devices.

This module is the ONLY sanctioned writer of JAX_PLATFORMS / jax_platforms /
XLA_FLAGS / jax_compilation_cache_dir — tools/tpulint rule TPU005 enforces that
statically.

It also hosts the runtime half of the tpulint story: `sanitize()` arms
jax.transfer_guard around a query phase and counts compile events, so tests can
assert a per-phase compile budget and a zero-implicit-transfer invariant — the
dynamic check backing the static TPU001/TPU002 rules (see tests/test_sanitizer.py
and the `jax_sanitizer` conftest fixture).
"""

from __future__ import annotations

import contextlib
import os
import re
import sys
import threading
import time
from dataclasses import dataclass, field


def force_cpu_platform(n_devices: int | None = None) -> None:
    """The tests' way to get `n_devices` virtual CPU devices: pin jax to the CPU
    backend, optionally with n virtual host devices.

    Safe to call before or after `import jax` (but before first device use). An
    existing --xla_force_host_platform_device_count flag is replaced, not skipped —
    a pre-pinned smaller count would otherwise defeat the requested mesh size.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={n_devices}"
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(r"--xla_force_host_platform_device_count=\d+", flag, flags)
        else:
            flags = (flags + " " + flag).strip()
        os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")


# The persistent compilation cache has ONE placement rule: where the caller set
# JAX_COMPILATION_CACHE_DIR, jax has already read it and no code sets another
# directory; where it is unset, the cache lives at <checkout>/.jax_cache. The
# path is part of nothing's identity — never a temporary name, a pid or a time —
# so a restarted process finds what the last one compiled.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_persistent_cache_armed = False


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses in this process."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(_CHECKOUT, ".jax_cache")


def armed_compile_cache_dir() -> str | None:
    """Where this process's persistent cache writes: jax's own setting once
    enable_persistent_compile_cache() armed it, None before. What
    `/_nodes/stats/compile_warming` reports, so nothing outside recomputes the
    placement rule."""
    if not _persistent_cache_armed:
        return None
    import jax

    return jax.config.jax_compilation_cache_dir


def enable_persistent_compile_cache() -> None:
    """Arm jax's persistent compilation cache at compile_cache_dir() so a
    process restart deserializes executables from disk instead of re-running
    XLA. Thresholds drop to zero — serving kernels on the CPU test backend
    compile in milliseconds and must still persist, or the restart warm cycle
    re-pays full compiles. Idempotent: the directory never changes in a process.

    NOTE a persistent-cache HIT still emits a backend_compile_duration event
    (pxla times compile_or_get_cached wholesale), so compile counting is
    unchanged by arming this — the disk cache makes warm-cycle replays cheap,
    it does not hide them from the sanitizer."""
    global _persistent_cache_armed

    if _persistent_cache_armed:
        return
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax reads the config once, at its first cache use — a compile may already
    # have happened (test suites boot nodes mid-process), so reset and let the
    # next compile initialize against the directory above
    compilation_cache.reset_cache()
    _persistent_cache_armed = True


# ---------------------------------------------------------------------------
# runtime sanitizer: transfer guard + compile-event counting
# ---------------------------------------------------------------------------

# every backend compile emits exactly one of these duration events
# (jax 0.4.x: /jax/core/compile/backend_compile_duration); counting them is
# backend-agnostic and — unlike parsing jax_log_compiles output — race-free
_COMPILE_EVENT_SUBSTR = "backend_compile"
# the persistent compilation cache's own plain events (jax/_src/compiler.py,
# compilation_cache.py): a retrieval that succeeded, one that did not
# what a first sighting stalls its thread for BEFORE the backend compile:
# tracing the function to a jaxpr, and lowering that to an MLIR module. Kept
# as seconds beside the compile's; not compile events (nothing counts them)
_STALL_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s"}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


@dataclass
class SanitizerReport:
    """What happened inside one sanitize() scope."""

    compiles: int = 0
    compile_events: list = field(default_factory=list)  # (event_key,) per compile
    # lock-trace counters (common/locktrace.py) snapshotted on scope exit when
    # ESTPU_LOCKTRACE=1 armed the tracer; None when the tracer is off
    locks: dict | None = None
    # collective-trace counters (common/meshtrace.py) snapshotted on scope
    # exit when ESTPU_MESHTRACE=1 armed the tracer; None when the tracer is off
    mesh: dict | None = None

    def note(self, key: str) -> None:
        self.compiles += 1
        self.compile_events.append(key)


# thread-local plan-family tag for compile attribution: the launch sites in
# search/execute.py (and the mesh dispatch) wrap their kernel calls in
# compile_tag("sparse"|"dense"|...), and since XLA compiles synchronously on
# the triggering thread, the listener below can bucket every compile event by
# the plan family that caused it — the device capacity ledger's "who is
# eating my compile budget" signal. Fixed vocabulary, so the per-family
# counter dict (and its Prometheus label set) is bounded by construction.
_tag_local = threading.local()

COMPILE_FAMILIES = ("sparse", "dense", "function_score", "filtered",
                    "phrase", "dis_max", "sorted", "aggs", "percolate",
                    "mesh", "compact", "pack", "untagged")
_FAMILY_SET = frozenset(COMPILE_FAMILIES)


@contextlib.contextmanager
def compile_tag(tag: str):
    """Attribute backend compiles triggered inside the scope to `tag` (one
    thread-local write per batch launch — never per posting, never per doc).
    OUTERMOST scope wins: the workload that triggered the launch owns its
    compiles — a percolation's inner sparse-kernel launch stays "percolate",
    not "sparse"."""
    prev = getattr(_tag_local, "tag", None)
    if prev is not None:
        yield
        return
    _tag_local.tag = tag if tag in _FAMILY_SET else "untagged"
    try:
        yield
    finally:
        _tag_local.tag = None


def current_compile_family() -> str | None:
    """The compile_tag family active on this thread (None outside any scope)
    — compilecache.record_launch attributes specs to the workload that
    actually triggered the launch (percolate owning its inner sparse, etc.)."""
    return getattr(_tag_local, "tag", None)


_thread_compiles = threading.local()


def thread_compile_totals() -> tuple[int, float]:
    """(events, seconds) of the backend compiles THIS thread has triggered so
    far — one thread-local read. The difference across a launch says whether
    that launch compiled, and for how long (search/batcher.py tags the batch)."""
    return (getattr(_thread_compiles, "n", 0),
            getattr(_thread_compiles, "s", 0.0))


_lent = threading.local()


@contextlib.contextmanager
def serving_pool(name: str):
    """The calling thread runs one of the named pool's tasks for the scope:
    the pool lent it a slot and no thread (threadpool._BoundedPool
    .run_inline), so where work is attributed to pools it answers to that
    pool's name and not to its own."""
    prev = getattr(_lent, "pool", None)
    _lent.pool = name
    try:
        yield
    finally:
        _lent.pool = prev


def pool_label() -> str:
    """Which named threadpool the current thread works for — pool workers are
    named "estpu[<pool>]_N" (threadpool._BoundedPool), a thread inside
    `serving_pool` works for the pool that lent it a slot; anything else (a
    test's main thread, a raw Thread) reads as "other". The compile listener's
    and the pack ledger's pool attribution: the warmed-node invariant is that
    steady-state compile events and packs show pool=warmer/merge only."""
    lent = getattr(_lent, "pool", None)
    if lent is not None:
        return lent
    name = threading.current_thread().name
    if name.startswith("estpu[") and "]" in name:
        return name[len("estpu["): name.index("]")]
    return "other"


# untagged-origin capture: bounded — a runaway untagged site can't grow the
# dict past this many distinct call sites
_ORIGIN_CAP = 64


def _package_origin() -> str | None:
    """First stack frame inside elasticsearch_tpu/ (this module excluded) on
    the thread that triggered an untagged compile — names the launch site that
    compiled outside every compile_tag scope. Test-local eager jnp compiles
    have no package frame and return None: the conftest compile_surface_gate
    only fails on PACKAGE-originated untagged compiles."""
    marker = os.sep + "elasticsearch_tpu" + os.sep
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        i = fn.find(marker)
        if i >= 0 and not fn.endswith("jaxenv.py"):
            return f"{fn[i + 1:]}:{f.f_lineno}"
        f = f.f_back
    return None


class _CompileCounter:
    """Process-wide compile-event listener fanning out to active scopes.

    jax.monitoring has register-only semantics (no unregister), so ONE listener
    is installed lazily and forever; scopes subscribe/unsubscribe from it.
    Thread-safe: serving runs queries from pool threads.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._installed = False
        self._active: list[SanitizerReport] = []
        # process-lifetime compile-event count (since the listener was first
        # installed) — the Prometheus estpu_jax_compile_events_total series
        self.total = 0
        # monotonic time of the last compile event (seconds_since_compile)
        self.last_at: float | None = None
        # plan-family attribution (compile_tag): family -> count
        self.by_family: dict = {}
        # what the events cost: the listener's `duration` summed, whole and
        # by family (a persistent-cache hit still takes its retrieval time),
        # beside the persistent cache's own hit/miss events
        self.seconds = 0.0
        self.seconds_by_family: dict = {}
        # the seconds of tracing and of lowering that came before the
        # compiles (_STALL_EVENTS): with `seconds`, what first sightings
        # stalled their threads for
        self.stall_s = {"trace_s": 0.0, "lower_s": 0.0}
        self.cache_hits = 0
        self.cache_misses = 0
        # untagged-compile origin sites ("path:line" -> count), recorded only
        # when record_untagged_origins() armed it — the runtime twin of the
        # compile-surface manifest's families cross-check
        self.untagged_origins: dict = {}
        self._record_origins = False
        # threadpool attribution (pool -> count): the compile-warming
        # invariant's runtime surface — a warmed node's steady-state events
        # must all land on warmer/merge pools, never a serving pool
        self.by_pool: dict = {}
        # external observers fed OUTSIDE the lock, e.g. the compilecache
        # warm-queue feed (family, pool) per compile event. Append-only like
        # jax.monitoring itself; exceptions are swallowed — telemetry must
        # never break a compile.
        self.observers: list = []

    def _listener(self, key: str, duration: float, **_kw) -> None:
        if _COMPILE_EVENT_SUBSTR not in key:
            part = _STALL_EVENTS.get(key)
            if part is not None:
                with self._lock:
                    self.stall_s[part] += duration
            return
        family = getattr(_tag_local, "tag", None) or "untagged"
        # stack walk OUTSIDE the lock — frame inspection is slow-path work and
        # must not extend the critical section other compiling threads share
        origin = _package_origin() \
            if family == "untagged" and self._record_origins else None
        pool = pool_label()
        # XLA compiles on the triggering thread, so the thread's own tally
        # lets the batcher drainer name the batch a stall fell into
        _thread_compiles.n = getattr(_thread_compiles, "n", 0) + 1
        _thread_compiles.s = getattr(_thread_compiles, "s", 0.0) + duration
        # note() under the lock: concurrent pool-thread compiles must not lose
        # increments, or a blown budget could pass silently
        with self._lock:
            self.total += 1
            self.last_at = time.monotonic()
            self.by_family[family] = self.by_family.get(family, 0) + 1
            self.seconds += duration
            self.seconds_by_family[family] = \
                self.seconds_by_family.get(family, 0.0) + duration
            self.by_pool[pool] = self.by_pool.get(pool, 0) + 1
            if origin is not None and (origin in self.untagged_origins
                                       or len(self.untagged_origins)
                                       < _ORIGIN_CAP):
                self.untagged_origins[origin] = \
                    self.untagged_origins.get(origin, 0) + 1
            for r in self._active:
                r.note(key)
            observers = list(self.observers)
        for cb in observers:
            try:
                cb(family, pool)
            except Exception:  # noqa: BLE001
                pass

    def _cache_listener(self, key: str, **_kw) -> None:
        if key == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1
        elif key == _CACHE_MISS_EVENT:
            with self._lock:
                self.cache_misses += 1

    def ensure_installed(self) -> None:
        import jax.monitoring

        with self._lock:
            if not self._installed:
                jax.monitoring.register_event_duration_secs_listener(self._listener)
                jax.monitoring.register_event_listener(self._cache_listener)
                self._installed = True

    def subscribe(self, report: SanitizerReport) -> None:
        self.ensure_installed()
        with self._lock:
            self._active.append(report)

    def unsubscribe(self, report: SanitizerReport) -> None:
        with self._lock:
            if report in self._active:
                self._active.remove(report)


_counter = _CompileCounter()


def compile_events_total() -> int:
    """Process-lifetime backend-compile count for telemetry (Prometheus /
    /_nodes/stats). Installs the process-wide listener on first call; counts
    start from then — a warmed node therefore reads ~0, and any growth IS a
    retrace signal worth alerting on."""
    try:
        _counter.ensure_installed()
    except Exception:  # noqa: BLE001 — no jax in this process: count stays 0
        pass
    return _counter.total


def seconds_since_compile() -> float | None:
    """Seconds since this process last finished an XLA compile; None where it
    never has. A cold device compiles on the query path, one program after
    another: a node whose last compile is recent is busy, not wedged (the
    coordinator's attempt timer asks, actions.A_QUERY_PROGRESS). Installs the
    listener like compile_events_total: a node booted with compile warming off
    has none until something asks."""
    try:
        _counter.ensure_installed()
    except Exception:  # noqa: BLE001 — no jax in this process: never compiled
        pass
    last = _counter.last_at
    return None if last is None else time.monotonic() - last


def compile_events_by_family() -> dict:
    """Process-lifetime backend-compile counts bucketed by the plan family
    that triggered them (compile_tag scopes at the kernel launch sites) —
    the device capacity ledger's compile attribution. Keys are drawn from
    COMPILE_FAMILIES, so the dict (and its Prometheus label set) is bounded."""
    try:
        _counter.ensure_installed()
    except Exception:  # noqa: BLE001 — no jax in this process: empty
        pass
    with _counter._lock:
        return dict(_counter.by_family)


def compile_seconds() -> dict:
    """What the counted compile events cost: `seconds` whole and
    `seconds_by_family` (sums to it), plus the persistent compilation cache's
    `cache_hits` / `cache_misses` — the `/_nodes/stats` `device.compile`
    fields beside `total` and `by_family`. `trace_s` and `lower_s` are the
    seconds of jaxpr tracing and of lowering to MLIR that came before those
    compiles, and `stall_s` their sum with `seconds`: what first sightings
    really stalled their threads for."""
    try:
        _counter.ensure_installed()
    except Exception:  # noqa: BLE001 — no jax in this process: zeros
        pass
    with _counter._lock:
        return {"seconds": _counter.seconds,
                "seconds_by_family": dict(_counter.seconds_by_family),
                **_counter.stall_s,
                "stall_s": _counter.seconds + sum(_counter.stall_s.values()),
                "cache_hits": _counter.cache_hits,
                "cache_misses": _counter.cache_misses}


def compile_events_by_pool() -> dict:
    """Process-lifetime backend-compile counts bucketed by the threadpool the
    triggering thread belonged to ("estpu[<pool>]" worker naming; "other" for
    non-pool threads). On a warmed node every increment outside
    warmer/merge is an on-path compile stall — the compile-warming
    acceptance invariant reads this surface."""
    try:
        _counter.ensure_installed()
    except Exception:  # noqa: BLE001 — no jax in this process: empty
        pass
    with _counter._lock:
        return dict(_counter.by_pool)


def register_compile_observer(cb) -> None:
    """Register `cb(family, pool)` to run after every backend-compile event
    (outside the counter lock). Register-only, deduplicated by identity —
    mirrors jax.monitoring's own semantics. The compilecache registry feeds
    its warm queue from here."""
    try:
        _counter.ensure_installed()
    except Exception:  # noqa: BLE001 — no jax: nothing will ever fire
        pass
    with _counter._lock:
        if cb not in _counter.observers:
            _counter.observers.append(cb)


def record_untagged_origins(enable: bool = True) -> None:
    """Arm (or disarm) package-origin capture for untagged compile events: the
    listener walks the triggering thread's stack and records the first
    elasticsearch_tpu/ frame per event. Used by the conftest
    compile_surface_gate — a tier-1 run must end with zero package-originated
    untagged compiles, i.e. every package launch site sits under a
    compile_tag scope registered in tools/compile_surface.json."""
    try:
        _counter.ensure_installed()
    except Exception:  # noqa: BLE001 — no jax in this process: nothing to arm
        pass
    _counter._record_origins = enable


def untagged_package_origins() -> dict:
    """{"path:line": count} for untagged compiles whose stack crossed the
    package, since record_untagged_origins() armed capture. Empty when every
    package-originated compile carried a compile_tag family."""
    with _counter._lock:
        return dict(_counter.untagged_origins)


class CompileBudgetExceeded(AssertionError):
    """Raised when a sanitize(max_compiles=N) scope observed more than N
    backend compiles — a retrace hazard made loud (tpulint TPU002's runtime
    twin)."""


_UNSET = object()


@contextlib.contextmanager
def sanitize(max_compiles: int | None | object = _UNSET,
             transfers: str | None = None):
    """Arm the JAX runtime sanitizers around a query phase.

    - transfer guard at level `transfers` ("disallow" = implicit transfers
      raise; explicit jax.device_put/device_get stay legal, so correctly
      batched host pulls pass while a stray float(device_scalar) fails;
      "log" = warn only; "off" = disabled),
    - compile-event counting: the yielded SanitizerReport carries .compiles;
      if max_compiles is not None the scope raises CompileBudgetExceeded on
      exit when the budget was blown.

    Defaults come from the environment so the conftest gate, CI, and ad-hoc
    debugging share one knob (the tpulint baseline is empty, so "disallow"
    is the standing mode — ROADMAP burn-down item, PR 2):

      ESTPU_SANITIZE        transfer level when `transfers` is None
                            (default "disallow"; set =log as the escape
                            hatch while debugging a new implicit transfer,
                            =off to disarm entirely)
      ESTPU_COMPILE_BUDGET  int; when `max_compiles` is not given, a HARD
                            per-scope ceiling — the scope raises
                            CompileBudgetExceeded beyond it (empty/unset =
                            count but don't enforce)

    Usage (the test-harness invariant: a warmed query path neither recompiles
    nor implicitly transfers):

        with sanitize(max_compiles=0) as rep:
            run_query_again()
        assert rep.compiles == 0  # implied by max_compiles=0
    """
    import jax

    if transfers is None:
        transfers = os.environ.get("ESTPU_SANITIZE", "disallow")
    if max_compiles is _UNSET:
        budget = os.environ.get("ESTPU_COMPILE_BUDGET")
        max_compiles = int(budget) if budget else None

    report = SanitizerReport()
    _counter.subscribe(report)
    guard = (jax.transfer_guard(transfers) if transfers != "off"
             else contextlib.nullcontext())
    try:
        with guard:
            yield report
    finally:
        _counter.unsubscribe(report)
        from .locktrace import TRACER
        from .meshtrace import TRACER as MESH_TRACER

        if TRACER.enabled:
            report.locks = TRACER.snapshot()
        if MESH_TRACER.enabled:
            report.mesh = MESH_TRACER.snapshot()
    if max_compiles is not None and report.compiles > max_compiles:
        raise CompileBudgetExceeded(
            f"compile budget exceeded: {report.compiles} backend compile(s) "
            f"observed, budget {max_compiles} — a shape/static-arg drifted and "
            f"the executable cache missed (events: {report.compile_events})")
