"""Request-scoped search profiling — the white-box `profile` API substrate.

Where tracing (common/tracing.py) answers *where* a request spent its time
(spans across REST → coordinator → shard → batcher → device pull), the
profiler answers *why*: which clause, which segment, which execution path
(composed sparse vs dense fallback vs host scorer), which
cache miss (segment pack, SimTables swap, lazy dense-plane fault, scratch
checkout) made it expensive, and how many postings/blocks/bytes the plan
actually touched. The response shape is the reference's `profile` section:
per-shard entries merged next to `_shards` by the coordinator.

Design rules (the repo's device + lock discipline applies here too):

- **Zero overhead when off.** A hook is one thread-local read and a None
  check — no allocation, no locking, no clock reads on the unprofiled path.
  `activate(None)` is never entered: call sites branch on the collector
  before wrapping, so an unprofiled request touches this module only through
  `current()`.
- **Sync only when opted in.** Profiled requests get precise per-phase
  device timings by blocking on the dispatched launches (PER REQUEST —
  legal because `"profile": true` is the opt-in). The unprofiled serving
  path adds ZERO device syncs (pinned by tests/test_profile.py).
- **Batcher interaction is explicit.** A profiled request bypasses the
  cross-request DeviceBatcher (recorded as `batcher: {bypassed, reason:
  "profile"}`) so its device phases are its own, not a coalesced batch's —
  and so the collector stays single-writer: execution never leaves the
  request thread, which is why recording needs no locks.
- **Record under leaf code only.** Hooks append to plain lists/dicts owned
  by one thread; they never block, never dispatch device work, and never
  run under a lock that isn't their caller's own leaf lock.

Fallback-reason vocabulary (ARCHITECTURE.md "Profile API"): why a query
left the fused device path —
  fuzzy_match, bool_filter_clause, non_term_subclause,
  function_score_no_query, function_score_ineligible,
  non_flat_subquery, similarity_not_fused, host_only_field,
  sloppy_phrase, phrase_prefix, long_phrase (the phrase forms that stay on
  the host: a slop, a prefix on the last term, more terms than the phrase
  program's line holds; a phrase inside another query reads as that query's
  reason: non_term_subclause, non_flat_subquery, unsupported_query:<Type>),
  scoring_rewrite, multiterm_expansion, multiterm_numeric_field, fuzzy_query,
  span_multi (a prefix, wildcard or regexp lowers to an unscored plan whose
  mask row the chip builds, but for: a scoring rewrite, an expansion of more
  block rows on a segment than the ladder's last rung holds, a numeric field
  (a field of _id / _uid reads host_only_field); `fuzzy` has no exact host
  semantics to hold a program to, `span_multi` is a span's),
  multi_match_type, dismax_subquery, dismax_similarity, dismax_disjuncts,
  dismax_tail (a `multi_match` of type best_fields and a `dis_max` of
  one-field OR queries lower to a dis_max plan, most_fields to a plain one,
  but for: another multi_match type; a sub-query that is not a `term` or an
  OR `match` with no minimum_should_match above 1, a numeric field, a boost
  that is not positive; a TF-IDF default similarity or a field that is not
  BM25's; more disjuncts than the program's slots; a dis_max plan under
  `filtered` or `function_score`, which has no tail to carry them),
  unsupported_query:<Type>,
  device_disabled, features:<f1,f2,...>, device_error:<Type>.
"""

from __future__ import annotations

import contextlib
import threading
import time

_local = threading.local()


def current() -> "ProfileCollector | None":
    """The thread's active collector, or None when the request is unprofiled
    (the common case — one thread-local read, nothing else)."""
    return getattr(_local, "prof", None)


@contextlib.contextmanager
def activate(prof: "ProfileCollector"):
    """Make `prof` the thread's collector for the scope. Call sites only
    enter this when a collector exists — the unprofiled path never pays the
    context manager."""
    prev = getattr(_local, "prof", None)
    _local.prof = prof
    try:
        yield prof
    finally:
        _local.prof = prev


# per-segment keys that accumulate across multiple launches of one request
# (e.g. the agg launch + the post-filter hit launch touch the same segment
# twice); everything else is identity info and overwrites
_ADDITIVE = {"blocks_scanned", "postings_scanned", "staged_bytes", "ms",
             "launches", "dense_overflow", "buckets"}


class ProfileCollector:
    """One shard-scoped (or mesh-launch-scoped) profile of one request.

    Single-writer by construction: profiled requests bypass the batcher, so
    every hook fires on the request thread — recording is plain appends with
    no locks. All recorded values are plain Python scalars so the result
    crosses the wire through the binary codec and renders as JSON unchanged.
    """

    MAX_EVENTS = 256  # cache-attribution events kept (drops counted)
    MAX_RESERVATIONS = 128  # breaker reservations kept (drops counted)

    __slots__ = ("node", "index", "shard", "t0", "_phases", "_plan",
                 "_outcome", "_fallback", "_segments", "_seg_order",
                 "_events", "_events_dropped", "_breakers", "_breaker_bytes",
                 "_breakers_dropped", "_batcher", "_mesh")

    def __init__(self, node: str = "node", index: str = "", shard: int = 0):
        self.node = node
        self.index = index
        self.shard = shard
        self.t0 = time.monotonic()
        self._phases: dict[str, float] = {}  # name -> ms
        self._plan: dict | None = None
        self._outcome: str | None = None
        self._fallback: str | None = None
        self._segments: dict[int, dict] = {}  # gen -> record
        self._seg_order: list[int] = []
        self._events: list[dict] = []
        self._events_dropped = 0
        self._breakers: list[dict] = []
        self._breaker_bytes = 0
        self._breakers_dropped = 0
        self._batcher: dict | None = None
        self._mesh: dict | None = None

    # -- phases --------------------------------------------------------------
    def phase_s(self, name: str, seconds: float) -> None:
        """Accumulate wall time into a named phase (seconds in, ms out)."""
        self._phases[name] = self._phases.get(name, 0.0) + seconds * 1000.0

    # -- plan ----------------------------------------------------------------
    def set_plan(self, plan: dict) -> None:
        """First writer wins — the query-phase entry point records the plan
        once; later re-lowerings (device-agg probes etc.) must not clobber."""
        if self._plan is None:
            self._plan = plan

    def outcome(self, path: str) -> None:
        """The resolved execution path (service.SERVING_COUNTERS vocabulary
        plus "mesh_spmd"); first writer wins."""
        if self._outcome is None:
            self._outcome = path

    def fallback(self, reason: str) -> None:
        """Why the fused device path was declined (module vocabulary)."""
        if self._fallback is None:
            self._fallback = reason

    # -- per-segment counters ------------------------------------------------
    def segment(self, gen: int, **kv) -> None:
        """Merge counters into the per-segment record: _ADDITIVE keys sum
        across launches, identity keys (path, tf_layout, docs) overwrite."""
        d = self._segments.get(gen)
        if d is None:
            d = {"generation": int(gen)}
            self._segments[gen] = d
            self._seg_order.append(gen)
        for k, v in kv.items():
            if k in _ADDITIVE and k in d:
                d[k] = d[k] + v
            else:
                d[k] = v

    # -- cache attribution / breaker accounting ------------------------------
    def event(self, kind: str, **kv) -> None:
        """A cache-attribution event (packed_segment hit/pack, sim_tables
        hit/swap, blk_freqs resident/fault, scratch reuse/alloc,
        device_error, mesh_executor hit/build)."""
        if len(self._events) < self.MAX_EVENTS:
            self._events.append({"kind": kind, **kv})
        else:
            self._events_dropped += 1

    def breaker_reserve(self, breaker: str, label: str, nbytes: int) -> None:
        self._breaker_bytes += int(nbytes)
        if len(self._breakers) < self.MAX_RESERVATIONS:
            self._breakers.append({"breaker": breaker, "label": label,
                                   "bytes": int(nbytes)})
        else:
            self._breakers_dropped += 1

    # -- batcher / mesh ------------------------------------------------------
    def batcher_bypass(self, reason: str) -> None:
        self._batcher = {"bypassed": True, "reason": reason}

    def mesh_info(self, **kv) -> None:
        self._mesh = {**(self._mesh or {}), **kv}

    # -- assembly ------------------------------------------------------------
    def to_dict(self) -> dict:
        phases = {k: round(v, 4) for k, v in self._phases.items()}
        phases["total"] = round((time.monotonic() - self.t0) * 1000.0, 4)
        segments = []
        for g in self._seg_order:
            d = dict(self._segments[g])
            for k, v in d.items():
                if isinstance(v, float):
                    d[k] = round(v, 4)
            segments.append(d)
        plan = {"outcome": self._outcome or "unknown",
                "fallback_reason": self._fallback}
        if self._plan:
            plan.update(self._plan)
        out = {
            "id": f"[{self.node}][{self.index}][{self.shard}]",
            "node": self.node,
            "index": self.index,
            "shard": int(self.shard),
            "plan": plan,
            "segments": segments,
            "phases_ms": phases,
            "cache": {"events": list(self._events),
                      "dropped": self._events_dropped},
            "breakers": {"reservations": list(self._breakers),
                         "reserved_bytes_total": self._breaker_bytes,
                         "dropped": self._breakers_dropped},
        }
        if self._batcher is not None:
            out["batcher"] = self._batcher
        if self._mesh is not None:
            out["mesh"] = self._mesh
        return out
