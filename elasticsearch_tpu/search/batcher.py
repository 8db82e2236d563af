"""Cross-request device micro-batching — the serving-path throughput lever.

The device path is batch-hungry (one launch scores many queries) yet live
serving dispatched ONE request per
device launch, paying a full launch + host merge per query under concurrent
load. DeviceBatcher coalesces concurrent `execute_query_phase` calls into one
bucketed `execute_flat_batch` launch — the same continuous/micro-batching
lever inference servers use (Orca-style iteration batching; the shape of the
reference's per-shard search pooling):

    search pool threads                drainer ("search_batcher" pool)
    ───────────────────                ─────────────────────────────────
    enqueue(plan, key)──►[bounded coalescing queue]
    wait(future)                          │ collect same-key items
         ▲                                ▼
         │                        dispatch batch N+1 ──► device
         └────── fan-out ◄─────── merge batch N     ◄── device

Items coalesce only under an identical key: same segment point-in-time view +
mapper/similarity services + k bucket (k rounds up to a power of two so mixed
page sizes share executables — the kernel runs at the bucket, fan-out trims).
DFS-stats requests bypass the queue entirely (their per-request global stats
would poison the batch's shared weights).

This is the ONE served launch route of a one-shard search. The key holds no
kind of search: plain, function_score, filtered, aggregated and sorted plans
on one view share a collect (seven operations behind 8 clients would
otherwise collect batches of one and pay a linger each), an aggregated or
sorted plan carries its execute.FlatTail on its item, and
execute.dispatch_flat_batch launches each group of the batch once a segment
(execute._flat_groups, by its row of execute.GROUP_KINDS); every group but
the plain one is pulled with the others, in one device_get at the dispatch's
end (execute._run_flat_groups). Only what a shared batch cannot serve launches on
its request thread: profiled and DFS requests and a node without a batcher
(service._execute_flat_single). `stats()["kinds"]` tells the launches apart.

Flush policy — whichever fires first:
  * batch-full  : `search.batch.max_batch` same-key plans are waiting
  * alone       : the drainer is idle and the batcher's own record says a
                  linger buys (almost) no companion here and now — the head
                  is taken at once, with whatever is queued beside it and
                  without the floor of `min_linger_ms` (the record: below)
  * linger      : the oldest item has waited `linger_eff`, where
                  linger_eff = linger_ms * (1 - queued/max_batch), floored at
                  `search.batch.min_linger_ms` — a hot queue shrinks the
                  linger toward zero because latency is only spent when it
                  buys occupancy; a lone request pays at most linger_ms, and
                  pays it only while the record says lingers buy companions
  * deadline    : now >= tightest enqueued Deadline - EWMA(batch service
                  time) — flushing early leaves budget for the device launch
                  AND the host merge, so PR-3 timeout semantics survive
                  coalescing
  * pending     : a dispatched batch is waiting to be merged — lingering
                  would hold its answered futures hostage to the NEXT batch's
                  linger window; with the device already busy, waiting buys
                  no occupancy, so the queue flushes immediately

The linger is a bet, and the batcher keeps its score. For every head an idle
drainer takes it counts the COMPANIONS: the items of the head's key enqueued
before a lone head's linger would end (`linger_eff` at one item queued, from
the head's own enqueue) and before the head is answered, counted where they
arrive (`_submit`), whether the head waited for them or not. That reads
arrivals, which the policy does not cause, and not the occupancy of the
batches it made, which it does (a shorter linger makes smaller batches makes
a shorter linger), and it needs no linger to be kept, so the policy cannot
starve its own evidence. Over the last `_RECORD_HEADS` such heads the mean is
what a linger buys (`stats()["linger_bought"]`, companions a head). While that
stays at `_LONELY_BELOW` or above the queue lingers as described; once a FULL
record reads less, a head that finds the drainer idle is flushed `alone`. A
fresh batcher has no record and lingers: the bet stands until it is seen to
lose. A burst on a lonely node sends its first search alone and the rest in
one `pending` batch behind it, and is itself the evidence that turns the
linger back on for the next head. `linger_ms: 0` never lingers and keeps no
record.

Double buffering: the drainer dispatches batch N+1 BEFORE merging batch N, so
batch N's host merge overlaps batch N+1's device compute. The dispatch half
never calls jax.device_get; the merge half performs the batch's single batched
pull (execute._merge_flat_plain) — the tpulint TPU001 baseline stays empty.

Breaker rule: sparse staging buffers and merge canvases are reserved per
BATCH on the request breaker (the coalesced launch is the allocation, not the
per-request share — ops/scoring.launch_flat_sparse). When a coalesced launch
trips a breaker (or fails any other way), the drainer replays each item
individually so only the request that is actually oversized fails with the
429; its neighbors keep their answers.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future

from ..common import insights as _insights
from ..common import tracing
from ..common.deadline import NO_DEADLINE, Deadline
from ..common.errors import RejectedExecutionError
from ..common.logging import get_logger
from ..common.metrics import HistogramMetric
from ..ops.device_index import _ladder_bucket

_K_MIN = 16  # smallest k bucket (top-10 pages and top-16 share executables)
# the linger's record (module docstring): the heads it remembers, and the
# companions a head under which a full record flushes a head `alone`. What the
# cells read, companions a head: one client 0, Poisson at 62.5/s 0.09 (the 32
# heads then hold 7 or more in 3% of the moments), a closed loop of eight 2-3
# (its sets of four arrive together); `wiki.aggs` loses an eighth of its rate
# where it never lingers and `passage.steady` gains 22% of its median (PERF.md
# section 6, PR 48). 0.2 is one companion in five heads: far from both, and
# under the 7 in 32 that ONE burst of eight leaves, so a burst turns the
# linger back on for the next head. 32 heads are half a second of a lone
# stream at 62.5/s: long enough that two neighbours inside one linger do not
# tip it, short enough that a node that has gone quiet stops paying within a
# second.
_RECORD_HEADS = 32
_LONELY_BELOW = 0.2
# the kinds of launch /_nodes/stats tells apart under search.batcher.kinds:
# execute.GROUP_KINDS' keys (tests/test_launch_seam.py holds this to them) and
# the mesh family's
_KINDS = ("plain", "function_score", "filtered", "phrase", "dis_max", "aggs",
          "sorted", "mesh")


def _k_bucket(k: int) -> int:
    # autotuned ladder (compilecache "k" dimension) with pow-2-from-16
    # fallback while cold — one executable per k RUNG, not per distinct k
    return _ladder_bucket("k", k, _K_MIN)


class _Item:
    __slots__ = ("family", "key", "payload", "k", "kb", "deadline", "future",
                 "t_enq", "t_done", "span", "obs")

    def __init__(self, family, key, payload, k: int, kb: int,
                 deadline: Deadline):
        self.family = family
        self.key = key
        self.payload = payload
        self.k = k  # the request's own k (fan-out trims to it)
        self.kb = kb  # the bucketed launch k
        self.deadline = deadline
        self.future: Future = Future()
        self.t_enq = time.monotonic()
        # where the drainer finished the item's batch (the end of its
        # batcher.merge), stamped before the future resolves: a sampled
        # waiter's wake-up starts there. None where no merge resolved it
        self.t_done: float | None = None
        # the enqueuing request's active span (None when untraced): the
        # drainer attributes the shared batch's queue/dispatch/merge/pull
        # timings back to EVERY member's trace through this handle
        self.span = tracing.current_span()
        # the request's always-on insights observation (common/insights.py;
        # None when insights are off): the drainer writes the batch's queue
        # wait + the existing pull window into it with clocks it already
        # reads — the item's Future resolution is the happens-before edge
        # back to the reader
        self.obs = _insights.current()


class _Line:
    """The line of the linger's record that is still being counted: the head
    an idle drainer took last (`t_head`, its t_enq, names it), the moment
    until which an arrival of its `key` is a companion (the end of a lone
    head's linger, or the head's answer where that came sooner), and the
    companions so far. Read and written under the batcher's condition."""

    __slots__ = ("key", "t_head", "t_end", "companions")

    def __init__(self, key, t_head: float, t_end: float, companions: int):
        self.key = key
        self.t_head = t_head
        self.t_end = t_end
        self.companions = companions


class _FlatFamily:
    """Coalesces single-shard FlatPlans into execute_flat_batch launches.
    payload = (plan, ShardContext, FlatTail | None); the batch runs with the
    LEADER item's context — the key guarantees every member sees the identical
    segment view and stats sources, so per-plan weights are identical either
    way. The key does not hold the kind: plain, filtered, aggregated and
    sorted searches on one view share a collect, and execute_flat_batch
    launches each group of the batch (execute._flat_groups), so a mix of
    operations pays one linger and not one a kind. A member with a tail is
    handed its executor's result as it is (its slices of the group's launch,
    or None: the host serves); every other one TopDocs trimmed to its k."""

    name = "flat"

    @staticmethod
    def key(ctx, kb: int):
        s = ctx.searcher
        return ("flat", id(ctx.mapper_service), id(ctx.similarity_service),
                tuple(id(seg) for seg in s.segments), kb)

    @staticmethod
    def dispatch(items, kb: int):
        from .execute import dispatch_flat_batch

        ctx = items[0].payload[1]
        return dispatch_flat_batch([it.payload[0] for it in items], ctx, kb,
                                   [it.payload[2] for it in items])

    @staticmethod
    def fan_out(handle, items):
        from .execute import TopDocs

        merged = handle.merge()
        return [res if it.payload[2] is not None else
                TopDocs(total=res.total, hits=res.hits[: it.k],
                        max_score=res.max_score, timed_out=res.timed_out)
                for it, res in zip(items, merged)]

    @staticmethod
    def execute_single(item):
        from .execute import execute_flat_batch

        plan, ctx, tail = item.payload
        return execute_flat_batch([plan], ctx, item.k, [tail])[0]


class _MeshFamily:
    """Coalesces plain mesh searches into one SPMD program launch.
    payload = (plan, MeshSearchExecutor); results fan out as per-query host
    row tuples (shard_row, score_row, doc_row, shard_totals_col, qmax_col) —
    exactly what mesh_serving's assembly consumes. The plan list pads to the
    "q" bucket ladder with zero-clause plans (msm=1 matches nothing) so batch
    sizes share compiled programs."""

    name = "mesh"

    @staticmethod
    def key(executor, kb: int):
        return ("mesh", id(executor), kb)

    @staticmethod
    def dispatch(items, kb: int):
        from .execute import FlatPlan

        executor = items[0].payload[1]
        plans = [it.payload[0] for it in items]
        # the k bucket may round past the program's doc space (the request's
        # own k was validated against doc_pad by mesh_serving) — clamp it
        kb = min(kb, executor.index.doc_pad)
        qb = _ladder_bucket("q", len(plans), 1)
        plans += [FlatPlan([], msm=1, n_must=0, coord_enabled=False, boost=1.0)
                  for _ in range(qb - len(plans))]
        # executor.search pulls its program output itself (one device_get for
        # the whole result pytree) — the mesh family merges at dispatch time,
        # so its clock holds the pull beside the stage and the launch
        from ..common.jaxenv import compile_tag

        with tracing.timing_dispatch() as clock, compile_tag("mesh"):
            out = executor.search(plans, kb)
        out.clock = clock
        return out

    @staticmethod
    def fan_out(out, items):
        results = []
        for qi, it in enumerate(items):
            results.append((out.shard[qi].tolist(), out.scores[qi].tolist(),
                            out.doc[qi].tolist(),
                            out.shard_totals[:, qi].tolist(),
                            out.qmax[:, qi].tolist()))
        return results

    @staticmethod
    def execute_single(item):
        plan, executor = item.payload
        out = executor.search([plan], min(item.kb, executor.index.doc_pad))
        return (out.shard[0].tolist(), out.scores[0].tolist(),
                out.doc[0].tolist(), out.shard_totals[:, 0].tolist(),
                out.qmax[:, 0].tolist())


class DeviceBatcher:
    """Per-node coalescing queue + drainer for cross-request device batching.

    Grouping is per coalesce key — which embeds the shard's point-in-time
    segment view — so this IS per-shard batching; one node-level queue simply
    lets a single drainer double-buffer across shards too."""

    def __init__(self, settings=None, threadpool=None, node_name: str = "node"):
        from ..common.settings import Settings

        settings = settings or Settings.EMPTY
        self.enabled = bool(settings.get_bool("search.batch.enabled", True))
        self.max_batch = max(1, settings.get_int("search.batch.max_batch", 64))
        self.linger_s = max(
            0.0, settings.get_float("search.batch.linger_ms", 1.5)) / 1000.0
        self.min_linger_s = max(
            0.0, settings.get_float("search.batch.min_linger_ms", 0.1)) / 1000.0
        self.queue_cap = max(1, settings.get_int("search.batch.queue_size", 1024))
        self.logger = get_logger("search.batcher", node=node_name)
        self._threadpool = threadpool
        self._queue: deque[_Item] = deque()
        self._cv = threading.Condition()
        self._shutdown = False
        self._drainer_started = False
        self._drainer_dead = False
        # EWMA of batch service time (dispatch start -> fan-out done): what the
        # deadline flush subtracts so launch + merge still fit in the budget
        self._ewma_cost = 0.004
        self._stats_lock = threading.Lock()
        self._launches = 0
        self._items_launched = 0  # total items served via coalesced launches
        # the same two by kind of launch: [groups launched, their members]. A
        # batch of the flat family launches a group for each kind and key it
        # holds (execute._flat_groups), so the members add up to `coalesced`
        # and the groups to `launches` plus the mixed batches' extra groups
        self._kinds = {kind: [0, 0] for kind in _KINDS}
        self._full_flushes = 0
        self._linger_flushes = 0
        self._deadline_flushes = 0
        self._pending_flushes = 0  # flushed early because a merge was waiting
        self._alone_flushes = 0  # flushed at once: lingers buy nothing here
        # the linger's record (module docstring), under _cv: the companions
        # of each of the last heads an idle drainer took, and the line still
        # being counted (_submit counts the arrivals, the next such head
        # closes it). _linger_bought is the record's mean, written by the
        # drainer and read unlocked by stats()
        self._bought: deque[int] = deque(maxlen=_RECORD_HEADS)
        self._line: _Line | None = None
        self._linger_bought = 0.0
        self._bypassed = 0  # queue full / disabled / drainer dead -> inline
        # profiled requests bypass BEFORE enqueueing (service._execute_flat_
        # single: their per-request sync must not serialize a shared batch) —
        # counted separately so occupancy regressions aren't blamed on load
        self._profile_bypassed = 0
        self._splits = 0  # coalesced launch failed -> per-item replay
        self._device_splits = 0  # splits whose trigger classified as a
        # device fault (common/devicehealth taxonomy) — the containment
        # counter: one poisoned plan replayed away from its neighbors
        # batch service-time tail (dispatch start -> fan-out done): percentile
        # twin of _ewma_cost, exported in /_nodes/stats + Prometheus
        self.service_hist = HistogramMetric()
        self._batch_ids = itertools.count(1)  # trace tag joining members
        # in-flight (dispatching-or-unmerged) batches, OLDEST FIRST, written
        # ONLY by the drainer and read unlocked by the stall watchdog:
        # (batch_id, t_dispatch, family name, occupancy, shard label).
        # Appended BEFORE family.dispatch so a hang INSIDE dispatch (the
        # mesh family executes + pulls there) is visible too; the head is
        # the oldest unresolved batch, so double-buffering (N merging while
        # N+1 is dispatched) still ages N, not N+1. Deque ops under the GIL;
        # a torn watchdog read is at worst one batch stale.
        self._inflight_q: deque[tuple] = deque()
        self._flat = _FlatFamily()
        self._mesh = _MeshFamily()
        # drainer state-seconds: where the ONE thread that feeds the device
        # spent its wall time, every batch, sampled member or not. Written
        # only by the drainer (each _tick books the time since the previous
        # one, so the states partition its lifetime), read unlocked by stats()
        self._state_s = {"wait": 0.0, "linger": 0.0, "dispatch": 0.0,
                         "merge": 0.0, "pull": 0.0}
        self._t_state = 0.0
        # the same partition in seconds of the drainer thread's CPU (_tick)
        self._cpu_s = dict.fromkeys(self._state_s, 0.0)
        self._t_cpu = 0.0
        self._batches = 0
        # the drainer's phases as annotations on the profiler's own clock (its
        # host plane, beside `XLA Ops`) for EVERY batch: a flag check each
        # while no profiler session is on
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation

    # -- public entry points -------------------------------------------------
    def execute(self, plan, ctx, k: int, deadline: Deadline = NO_DEADLINE,
                tail=None):
        """Coalesce one shard-local FlatPlan with concurrent callers; blocks
        until the batch lands and returns this plan's TopDocs (hits trimmed
        to k) or, for an aggregated or sorted search (`tail`, an
        execute.FlatTail), its executor's result. Falls back to a direct
        single-plan launch when batching is disabled, the queue is
        saturated, or the drainer has died."""
        k = max(k, 1)
        kb = _k_bucket(k)
        item = _Item(self._flat, self._flat.key(ctx, kb), (plan, ctx, tail),
                     k, kb, deadline or NO_DEADLINE)
        return self._submit(item)

    def execute_mesh(self, plan, executor, k: int,
                     deadline: Deadline = NO_DEADLINE):
        """Coalesce one plain mesh search; returns the per-query host rows
        (shard, score, doc, shard_totals, qmax) mesh_serving assembles from."""
        k = max(k, 1)
        kb = _k_bucket(k)
        item = _Item(self._mesh, self._mesh.key(executor, kb),
                     (plan, executor), k, kb, deadline or NO_DEADLINE)
        return self._submit(item)

    def _submit(self, item: _Item):
        if not self.enabled:
            with self._stats_lock:
                self._bypassed += 1
            return item.family.execute_single(item)
        with self._cv:
            # _drainer_dead is re-checked HERE, under the condition: the death
            # path flips it and drains the queue under the same lock, so an
            # item can never land in a queue nobody will ever service
            if (self._shutdown or self._drainer_dead
                    or len(self._queue) >= self.queue_cap):
                inline = True
            else:
                self._queue.append(item)
                self._cv.notify_all()
                inline = False
                line = self._line
                if (line is not None and item.t_enq <= line.t_end
                        and item.key == line.key):
                    line.companions += 1
        if inline:
            # a saturated coalescing queue must not become a second rejection
            # layer on top of the search pool's — serve directly instead
            with self._stats_lock:
                self._bypassed += 1
            return item.family.execute_single(item)
        self._ensure_drainer()
        remaining = item.deadline.remaining()
        # generous slack past the deadline: the flush logic targets the
        # deadline itself, this wait only guards against a wedged drainer
        timeout = None if remaining is None else remaining + 30.0
        result = item.future.result(timeout=timeout)
        tracing.record_wake(item.span, item.t_done, "batcher")
        return result

    # -- drainer -------------------------------------------------------------
    def _ensure_drainer(self):
        if self._drainer_started:
            return
        with self._cv:
            if self._drainer_started or self._shutdown:
                return
            self._drainer_started = True
        if self._threadpool is not None:
            try:
                # a named pool so the drainer shows in /_nodes/stats thread_pool.
                # Submitted with no span current: the pool records a sampled
                # submitter's wait under its span (`pool.wait`), and the
                # drainer's start is no part of the search that happened to
                # come first (its own wait is `batcher.queue`)
                with tracing.activate(None):
                    self._threadpool.submit("search_batcher", self._drain_loop)
                return
            except Exception:  # noqa: BLE001 — pool missing/closed: plain thread
                pass
        threading.Thread(target=self._drain_loop, daemon=True,
                         name="estpu[search_batcher]").start()

    def _drain_loop(self):
        try:
            self._drain()
        except BaseException as e:  # noqa: BLE001 — a dead drainer must not
            # strand waiters: flag it (under the condition, so no _submit can
            # slip an item into the queue after the drain below) and fail
            # anything already queued; later submits bypass to direct execution
            with self._cv:
                self._drainer_dead = True
            self.logger.warning(f"batcher drainer died ({type(e).__name__}: "
                                f"{e}); serving falls back to direct launches")
            self._fail_queued(e)

    def _tick(self, state: str, now: float | None = None) -> float:
        """Book the drainer's time since its last tick to `state`: the wall
        seconds, and beside them the seconds of CPU its thread was given in
        them (time.thread_time(), so a state's CPU seconds under its wall
        seconds are what the drainer stood still for: the device, the
        condition, the interpreter lock). The `pull` state is carved out of
        dispatch and merge afterwards from the dispatch clock's wall
        interval, which has no CPU reading: `cpu.pull_s` stays 0 and the
        CPU of a pull stays with the state it ran in."""
        now = time.monotonic() if now is None else now
        cpu = time.thread_time()
        self._state_s[state] += now - self._t_state
        self._cpu_s[state] += cpu - self._t_cpu
        self._t_state = now
        self._t_cpu = cpu
        return now

    def _drain(self):
        self._t_state = time.monotonic()
        self._t_cpu = time.thread_time()
        pending = None  # _finish's arguments — dispatched, not merged
        while True:
            batch = None
            with self._cv:
                while not self._queue and not self._shutdown:
                    if pending is not None:
                        break  # merge the in-flight batch instead of idling
                    self._cv.wait(0.1)
                    self._tick("wait")
                t0 = self._tick("wait")
                if self._queue and not self._shutdown:
                    with self._annotate("estpu.batch.collect") as note:
                        batch = self._collect_locked(urgent=pending is not None)
                        batch_id = next(self._batch_ids)
                        note.set_metadata(batch=batch_id, reason=batch[1],
                                          occupancy=len(batch[0]),
                                          family=batch[0][0].family.name)
                    # queued items in hand: the batch starts here
                    t0 = self._tick("linger")
            if batch is None:
                if pending is not None:
                    self._finish(*pending)
                    pending = None
                    continue
                if self._shutdown:
                    break
                continue
            items, reason = batch
            traced = [it for it in items if it.span]
            # enqueue-wait: t_enq -> the drainer taking the batch (span
            # recording happens OUTSIDE the condition/stats locks — trace
            # locks are leaves, and record() never blocks or dispatches)
            for it in traced:
                it.span.record("batcher.queue", it.t_enq, t0, batch=batch_id,
                               reason=reason, occupancy=len(items))
            # always-on insights: the coalescing-queue wait, from the SAME
            # t_enq/t0 clock pair the trace spans above use (plain attribute
            # writes; the item futures resolve after these, so readers see
            # them without locks)
            for it in items:
                if it.obs is not None:
                    it.obs.queue_s = t0 - it.t_enq
            family = items[0].family
            # publish the in-flight marker BEFORE dispatching: a hang inside
            # dispatch itself (the mesh family's whole execution + pull live
            # there) must age for the watchdog exactly like a wedged merge.
            # Label extraction must never throw — a drainer death strands
            # every queued future (payload shape is per-family: (plan, ctx)
            # for flat, (plan, executor) for mesh, opaque in unit fakes)
            payload = items[0].payload
            ctx0 = payload[1] if isinstance(payload, tuple) \
                and len(payload) > 1 else None
            self._inflight_q.append(
                (batch_id, t0, family.name, len(items),
                 getattr(ctx0, "index_name", None) or family.name))
            self._batches += 1
            clock = None
            with self._annotate("estpu.batch.dispatch", batch=batch_id,
                                family=family.name, occupancy=len(items),
                                reason=reason) as note:
                try:
                    # dispatch-then-merge double buffering: batch N+1's device
                    # work is enqueued BEFORE batch N's host merge runs, so the
                    # merge overlaps device compute (no device_get in this half)
                    handle = family.dispatch(items, items[0].kb)
                except Exception as e:  # noqa: BLE001 — replay decides per item
                    self._retire_inflight(batch_id)
                    self._split(family, items, e)
                    self._tick("dispatch")
                    continue
                # the dispatch's own stage / launch intervals (and, where the
                # family pulls inside its dispatch, the pull)
                clock = getattr(handle, "clock", None)
                if clock is not None and clock.compiled:
                    # a compile stall names its batch
                    note.set_metadata(compiled=clock.compiled,
                                      compile_s=round(clock.compile_s, 6))
            t_disp = self._tick("dispatch")
            if clock is not None and clock.pull_s:
                self._state_s["dispatch"] -= clock.pull_s
                self._state_s["pull"] += clock.pull_s
            for it in traced:
                disp = it.span.record("batcher.dispatch", t0, t_disp,
                                      batch=batch_id, occupancy=len(items),
                                      family=family.name)
                if clock is not None:
                    clock.record_under(disp, batch=batch_id)
            self._note_flush(reason)
            if pending is not None:
                self._finish(*pending)
            pending = (family, items, handle, t0, batch_id, t_disp)
            with self._cv:
                queue_empty = not self._queue
            if queue_empty:
                self._finish(*pending)
                pending = None
        if pending is not None:
            self._finish(*pending)
        self._fail_queued(RejectedExecutionError(
            "search batcher is shut down"))

    def _collect_locked(self, urgent: bool = False):
        """Pick the oldest item's key and wait (under the condition) until a
        flush trigger fires; pops and returns (items, reason). Called with
        the condition held; may release it while waiting.

        `urgent` means a dispatched batch is waiting to be MERGED: lingering
        here would hold batch N's answered futures hostage to batch N+1's
        linger window (the drainer's merge-delay bug, PR 6). Take whatever is
        queued immediately — the device is busy anyway, so the linger's
        latency-for-occupancy trade buys nothing."""
        head = self._queue[0]
        key = head.key
        # an idle drainer's head is one more line of the linger's record, and
        # the record says whether this head lingers (linger_ms 0 keeps none)
        lonely = (not urgent and self.linger_s > 0.0
                  and self._lingers_buy_nothing(head))
        while True:
            same = [it for it in self._queue if it.key == key]
            n = len(same)
            if n >= self.max_batch:
                reason = "full"
                break
            if urgent:
                reason = "pending"
                break
            if lonely:
                reason = "alone"
                break
            now = time.monotonic()
            flush_at = head.t_enq + self._linger_eff(n)
            reason = "linger"
            for it in same:
                rem = it.deadline.remaining()
                if rem is None:
                    continue
                # leave one expected batch service time (launch + merge) of
                # budget so the flushed batch can still answer in time
                dl_at = now + rem - self._ewma_cost
                if dl_at < flush_at:
                    flush_at = dl_at
                    reason = "deadline"
            if now >= flush_at or self._shutdown:
                break
            self._cv.wait(min(flush_at - now, 0.05))
        taken: list[_Item] = []
        rest: deque[_Item] = deque()
        for it in self._queue:
            if it.key == key and len(taken) < self.max_batch:
                taken.append(it)
            else:
                rest.append(it)
        self._queue.clear()
        self._queue.extend(rest)
        return taken, reason

    def _linger_eff(self, n: int) -> float:
        """The adaptive linger of a head with `n` same-key items queued: it
        shrinks linearly as the queue fills — waiting longer only pays when
        it buys occupancy."""
        return max(self.min_linger_s,
                   self.linger_s * (1.0 - n / float(self.max_batch)))

    def _lingers_buy_nothing(self, head: _Item) -> bool:
        """Close the record's open line, open `head`'s, and say whether the
        record as it now stands tells this head to go alone (module
        docstring, "The linger is a bet"). Drainer only, with the condition
        held. The new line starts with the companions already queued behind
        the head; _submit counts the later ones, _finish ends the count
        where the head is answered."""
        if self._line is not None:
            self._bought.append(self._line.companions)
            self._linger_bought = sum(self._bought) / len(self._bought)
        t_end = head.t_enq + self._linger_eff(1)
        self._line = _Line(head.key, head.t_enq, t_end, sum(
            1 for it in self._queue
            if it is not head and it.key == head.key and it.t_enq <= t_end))
        return (len(self._bought) == _RECORD_HEADS
                and self._linger_bought < _LONELY_BELOW)

    def _finish(self, family, items, handle, t0: float, batch_id: int = 0,
                t_disp: float | None = None):
        """Merge a dispatched batch and fan results out to the item futures.
        `t_disp` is where its dispatch ended: what lies between that and
        this merge is `batcher.hold` in a sampled member's trace, the batch
        held while the drainer collected and dispatched the NEXT one (the
        double buffering; tens of microseconds where the queue was empty)."""
        t_m0 = time.monotonic()
        with self._annotate("estpu.batch.merge", batch=batch_id,
                            family=family.name, occupancy=len(items)):
            try:
                results = family.fan_out(handle, items)
            except Exception as e:  # noqa: BLE001 — replay decides per item
                self._retire_inflight(batch_id)
                self._split(family, items, e)
                self._tick("merge")
                return
        t_m1 = time.monotonic()
        self._retire_inflight(batch_id)  # merged: the stall marker retires
        dt = t_m1 - t0
        # merge span + the batch's ONE device pull, attributed to EVERY
        # coalesced member (the pull timestamps were stamped by
        # execute._merge_flat_plain on the pending handle — span end-times
        # ride the existing batched device_get, no extra sync)
        pull_t0 = getattr(handle, "pull_t0", None)
        pull_t1 = getattr(handle, "pull_t1", None)
        merged_pull = pull_t0 is not None and pull_t1 is not None
        if merged_pull:
            pull_s = pull_t1 - pull_t0
        else:
            # a family that pulls inside its dispatch timed it on the dispatch
            # clock (and its device_pull is under batcher.dispatch already)
            clock = getattr(handle, "clock", None)
            pull_s = clock.pull_s if clock is not None else 0.0
        for it in items:
            if it.obs is not None:
                # device time rides the batch's existing single pull window
                # (zero added clocks/syncs — the insights contract)
                if pull_s:
                    it.obs.device_s = pull_s
                it.obs.occupancy = len(items)
            if not it.span:
                continue
            it.t_done = t_m1
            if t_disp is not None:
                it.span.record("batcher.hold", t_disp, t_m0, batch=batch_id)
            merge_span = it.span.record("batcher.merge", t_m0, t_m1,
                                        batch=batch_id)
            if merged_pull:
                merge_span.record("device_pull", pull_t0, pull_t1,
                                  batch=batch_id)
        self.service_hist.observe(dt)  # own stripe locks — outside _stats_lock
        # what the batch launched, by kind: the flat family's handles say
        # (a group for each kind and key of the batch), any other batch is
        # one group of its family's name
        kinds = getattr(handle, "kinds", None) or ((family.name, len(items)),)
        with self._stats_lock:
            self._ewma_cost = 0.2 * dt + 0.8 * self._ewma_cost
            self._launches += 1
            self._items_launched += len(items)
            for kind, members in kinds:
                tally = self._kinds.setdefault(kind, [0, 0])
                tally[0] += 1
                tally[1] += members
        line = self._line
        if line is not None and line.t_head == items[0].t_enq:
            # the head being counted is answered: its line counts no further
            with self._cv:
                line.t_end = min(line.t_end, t_m1)
        for it, res in zip(items, results):
            it.future.set_result(res)
        # everything since the dispatch tick — the merge, its bookkeeping and
        # waking the waiters — is merge time, less the pull inside it
        self._tick("merge")
        if merged_pull:
            self._state_s["merge"] -= pull_s
            self._state_s["pull"] += pull_s

    def _split(self, family, items, err):
        """A coalesced launch failed (breaker trip, device error): replay every
        item individually so only the request that actually trips carries the
        error — its neighbors must not inherit a 429 sized for the batch.

        Device containment (common/devicehealth) rides this same path: a
        classified XLA error inside a shared launch replays each member, so
        one poisoned plan degrades ITS request to the host scorer while the
        N-1 neighbors re-launch and serve from the device. Per-item verdicts
        reach the circuit tracker through the members' own futures
        (service._device_failed classifies the tagged exception); the batch-
        level error is NOT recorded — the replay re-derives who is actually
        poisoned, and neighbors' collateral must never advance a circuit."""
        from ..common.devicehealth import classify_device_error

        if len(items) == 1:
            items[0].future.set_exception(err)
            return
        with self._stats_lock:
            self._splits += 1
            if classify_device_error(err) is not None:
                self._device_splits += 1
        for it in items:
            try:
                res = family.execute_single(it)
            except Exception as e:  # noqa: BLE001 — per-item verdict
                it.future.set_exception(e)
            else:
                it.future.set_result(res)

    def _note_flush(self, reason: str):
        with self._stats_lock:
            if reason == "full":
                self._full_flushes += 1
            elif reason == "deadline":
                self._deadline_flushes += 1
            elif reason == "pending":
                self._pending_flushes += 1
            elif reason == "alone":
                self._alone_flushes += 1
            else:
                self._linger_flushes += 1

    def _fail_queued(self, err):
        with self._cv:
            items, self._queue = list(self._queue), deque()
        for it in items:
            if not it.future.done():
                it.future.set_exception(err)

    def _retire_inflight(self, batch_id: int):
        """Drop one batch's in-flight marker (drainer thread only). The
        retiring batch is almost always the head; the fallback filter covers
        the dispatch-failed-while-older-batch-pending interleaving."""
        q = self._inflight_q
        try:
            if q and q[0][0] == batch_id:
                q.popleft()
                return
        except IndexError:
            return
        for entry in list(q):
            if entry[0] == batch_id:
                try:
                    q.remove(entry)
                except ValueError:
                    pass
                return

    def inflight(self) -> dict | None:
        """The OLDEST in-flight (dispatching-or-unmerged) batch as the stall
        watchdog sees it: {batch, age_s, family, occupancy, shard}, or None.
        One unlocked deque head read of drainer-written state — the
        watchdog's clock, never a serving thread's."""
        try:
            batch_id, t0, family, occupancy, label = self._inflight_q[0]
        except IndexError:
            return None
        return {"batch": batch_id, "age_s": time.monotonic() - t0,
                "family": family, "occupancy": occupancy, "shard": label}

    def note_profile_bypass(self):
        """A profiled request served itself directly instead of coalescing
        (search/service._execute_flat_single — the `reason: profile` bypass)."""
        with self._stats_lock:
            self._profile_bypassed += 1

    # -- lifecycle / observability -------------------------------------------
    def shutdown(self):
        with self._cv:
            self._shutdown = True
            self._cv.notify_all()

    def stats(self) -> dict:
        with self._stats_lock:
            launches = self._launches
            items = self._items_launched
            out = {
                "launches": launches,
                "coalesced": items,
                "occupancy_mean": round(items / launches, 3) if launches else 0.0,
                "full_flushes": self._full_flushes,
                "linger_flushes": self._linger_flushes,
                "deadline_flushes": self._deadline_flushes,
                "pending_flushes": self._pending_flushes,
                "alone_flushes": self._alone_flushes,
                # what a linger buys here and now: companions a head over the
                # record's last heads (drainer-written, one float)
                "linger_bought": round(self._linger_bought, 3),
                "bypassed": self._bypassed,
                "profile_bypassed": self._profile_bypassed,
                "splits": self._splits,
                "device_splits": self._device_splits,
                "queue": len(self._queue),
                "ewma_batch_ms": round(self._ewma_cost * 1000.0, 3),
                "kinds": {kind: {"launches": n, "coalesced": members}
                          for kind, (n, members) in self._kinds.items()},
            }
        # drainer state-seconds (drainer-written, read unlocked: each value
        # is one float, a reading is at most one batch stale). They sum to
        # the drainer's lifetime: wait = queue empty and nothing to merge,
        # linger = items queued inside _collect_locked, dispatch / merge less
        # the device_get inside them, which is pull
        out["drainer"] = {**{k + "_s": v for k, v in self._state_s.items()},
                          "cpu": {k + "_s": v for k, v in self._cpu_s.items()},
                          "batches": self._batches}
        # batch service-time percentiles (HistogramMetric — the tail the EWMA
        # can't show); stripe locks are leaves, summed outside _stats_lock
        out["batch"] = self.service_hist.stats()
        return out
