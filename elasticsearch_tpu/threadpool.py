"""Named thread pools + scheduler, with BOUNDED queues.

Analogue of threadpool/ThreadPool.java + EsThreadPoolExecutor: named executors
(search/index/bulk/get/management/generic/...) with individual sizes AND
individual queue bounds. A pool whose queue is full REJECTS the task with
RejectedExecutionError (HTTP 429, transient for the write-path retry policy)
instead of queueing it forever — unbounded queues convert overload into
latency and eventually OOM; bounded queues convert it into fast, retryable
backpressure (PAPER.md layer 1/9's EsRejectedExecutionException).

TPU note: device compute itself is dispatched asynchronously by JAX's runtime;
these pools serve the HOST side — request fan-out, IO, recovery streaming,
periodic maintenance.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor

from .common import tracing
from .common.errors import RejectedExecutionError
from .common.jaxenv import serving_pool
from .common.logging import get_logger
from .common.metrics import HistogramMetric

logger = get_logger("threadpool")

Names = (
    "same",
    "generic",
    "get",
    "index",
    "bulk",
    # replica-side write ops get their own pool (deviation from the reference, which
    # runs them on INDEX but never parks a thread awaiting acks — our primaries block
    # for sync replication, so sharing a pool would allow a cross-node wait cycle:
    # A's primaries hold all index workers waiting on B's replicas and vice versa)
    "replica",
    "search",
    # the cross-request micro-batching drainer (search/batcher.py) runs here:
    # one long-lived loop that coalesces queued FlatPlans into bucketed device
    # launches — a named pool so its liveness shows in /_nodes/stats
    "search_batcher",
    "suggest",
    "percolate",
    "management",
    "flush",
    "merge",
    "refresh",
    "warmer",
    "snapshot",
    "optimize",
)

_DEFAULT_SIZES = {
    "generic": 8,
    "get": 4,
    "index": 4,
    "bulk": 4,
    "replica": 4,
    "search": 8,
    "search_batcher": 1,
    "suggest": 2,
    "percolate": 2,
    "management": 2,
    "flush": 2,
    "merge": 2,
    "refresh": 2,
    "warmer": 2,
    "snapshot": 2,
    "optimize": 1,
}

# Queue bounds (`threadpool.<name>.queue_size`; -1 = unbounded). The dispatch
# trampoline ("generic") and cluster-management pool stay unbounded — rejecting
# the dispatcher would drop requests before any typed error could travel back.
_DEFAULT_QUEUES = {
    "generic": -1,
    "management": -1,
    "index": 200,
    "bulk": 200,
    "replica": 200,
    "search": 1000,
    "get": 1000,
    # the batcher drainer is one long-lived task — bounding its queue would
    # reject the drainer itself, never a request (requests queue in the
    # batcher's own bounded coalescing queue)
    "search_batcher": -1,
    # off-query-path device packing (ISSUE 14): a rejected warmer/merge task
    # silently degrades the serving path back to query-path packing, and the
    # task count is already bounded by the live segment count (pack futures
    # dedupe per segment) — so these queues stay unbounded
    "warmer": -1,
    "merge": -1,
}
_DEFAULT_QUEUE_SIZE = 1000

# the period of the wheel's own deadline, whose lateness is the GIL-wait gauge
# (ThreadPool._timer_loop, _book_probe): twenty wake-ups a second
_GIL_PROBE_S = 0.05


class ScheduledTimer:
    """Handle for one entry on the shared timer wheel — the
    threading.Timer-compatible surface (cancel/finished/is_alive/join) the
    serving path relies on, with no thread of its own. `finished` is set by
    cancel() OR by the wheel at fire time, so `is_alive()` means "may still
    fire", exactly like the stdlib Timer's contract."""

    __slots__ = ("deadline", "pool", "fn", "finished")

    def __init__(self, deadline: float, pool: str, fn):
        self.deadline = deadline
        self.pool = pool
        self.fn = fn
        self.finished = threading.Event()

    def cancel(self):
        self.finished.set()

    def is_alive(self) -> bool:
        return not self.finished.is_set()

    def join(self, timeout=None):
        """threading.Timer parity: wait until the timer can no longer fire
        (there is no per-timer thread to join)."""
        self.finished.wait(timeout)


class _ScheduledTask:
    def __init__(self, interval: float, fn, pool_submit, fixed_delay: bool = True):
        self.interval = interval
        self.fn = fn
        self.cancelled = threading.Event()
        self._submit = pool_submit

    def cancel(self):
        self.cancelled.set()


class _BoundedPool:
    """`size` slots over a ThreadPoolExecutor of `size` threads, tracking
    queued/active/rejected/completed and enforcing the queue bound. A task
    takes a slot where it arrives (`_enter`), at once while one is free and
    else a place in the line for one, and hands it on where it ends
    (`_leave`), to the head of the line: so slots are granted in arrival
    order, to a pooled task (`submit`: one of the pool's threads runs it)
    and to an inline one (`run_inline`: the calling thread does) alike, and
    the two kinds together never run more than `size` at once. `active`
    counts the slots held, `queued` the tasks in the line; rejection
    triggers when the line exceeds the bound plus the free slots (a free
    slot consumes an arrival at once, so it is headroom, not queue)."""

    def __init__(self, name: str, size: int, queue_size: int):
        self.name = name
        self.size = size
        self.queue_size = queue_size
        self.executor = ThreadPoolExecutor(max_workers=size,
                                           thread_name_prefix=f"estpu[{name}]")
        self._lock = threading.Lock()
        # the line for a slot: one gate a waiting task, opened by the task
        # that hands its slot on (or by shutdown). Not empty only while
        # every slot is held
        self._line: deque = deque()
        self._closed = False
        self.queued = 0
        self.active = 0
        self.rejected = 0
        self.completed = 0
        # queue-wait (arrival → the task runs) per task: the histogram that
        # separates "slow because queued" from "slow because device" in
        # /_nodes/stats (lock-striped, own leaf locks)
        self.queue_wait = HistogramMetric()

    def _enter(self):
        """Admission, the same for a pooled and an inline task: reject where
        the queue is full, else take a slot or a place in the line. Returns
        the gate to wait at, None where the slot is already held."""
        with self._lock:
            if self._closed:
                self.rejected += 1
                raise RejectedExecutionError(
                    f"rejected execution on [{self.name}]: pool is shut down")
            if self.queue_size >= 0:
                idle = max(0, self.size - self.active)
                if self.queued - idle >= self.queue_size:
                    self.rejected += 1
                    raise RejectedExecutionError(
                        f"rejected execution on [{self.name}]: queue capacity "
                        f"[{self.queue_size}] full "
                        f"(queued [{self.queued}], active [{self.active}])")
            if self.active < self.size and not self._line:
                self.active += 1
                return None
            gate = threading.Event()
            self._line.append(gate)
            self.queued += 1
            return gate

    def _leave(self):
        """Give the slot up: to the head of the line where there is one (the
        slot changes hands, `active` stands), else back to the pool."""
        with self._lock:
            self.completed += 1
            if self._line:
                self.queued -= 1
                self._line.popleft().set()
            else:
                self.active -= 1

    def _start(self, gate, t_arrived: float, span):
        """Wait at `gate` for the slot, then book the wait: the histogram's
        sample and, of a sampled request (`span`, current where the task
        arrived), its `pool.wait`."""
        if gate is not None:
            gate.wait()
            if self._closed:
                # shutdown opened every gate: nothing runs on a closed pool
                raise RejectedExecutionError(
                    f"rejected execution on [{self.name}]: pool is shut down")
        t_run = time.monotonic()
        self.queue_wait.observe(t_run - t_arrived)
        if span:
            span.record("pool.wait", t_arrived, t_run, pool=self.name)

    def submit(self, fn, *args, **kwargs) -> Future:
        gate = self._enter()
        try:
            # the span current at submit (one thread-local read; None or the
            # falsy NOOP span where the request is not sampled) rides beside
            # the arrival stamp: the worker records its wait under it
            return self.executor.submit(self._run, fn, args, kwargs, gate,
                                        time.monotonic(),
                                        tracing.current_span())
        except RuntimeError:
            # executor shut down — still a rejection, just a terminal one
            with self._lock:
                self.rejected += 1
                if gate is None:
                    self.active -= 1
                elif gate in self._line:
                    self._line.remove(gate)
                    self.queued -= 1
            raise RejectedExecutionError(
                f"rejected execution on [{self.name}]: pool is shut down") \
                from None

    def _run(self, fn, args, kwargs, gate, t_submit: float, span=None):
        self._start(gate, t_submit, span)
        try:
            return fn(*args, **kwargs)
        finally:
            self._leave()

    def run_inline(self, fn, *args):
        """Run `fn(*args)` on the CALLING thread as one of the pool's `size`
        workers: the pool lends a slot, not a thread. Admission is `submit`'s
        (the queue bound and its RejectedExecutionError, the counters, the
        `queue_wait` sample, the `pool.wait` span under the span current
        here, whose length is the wait for a slot), and while `fn` runs the
        thread answers to the pool's name where work is attributed to pools
        (jaxenv.pool_label). For a caller that would block for the task's
        result anyway: it pays no hand-over to a pool thread and none back.
        What bounds the wait for a slot is what bounds a pooled task's wait
        in the queue: the tasks ahead of it."""
        t_arrived = time.monotonic()
        self._start(self._enter(), t_arrived, tracing.current_span())
        try:
            with serving_pool(self.name):
                return fn(*args)
        finally:
            self._leave()

    def shutdown(self):
        """Close the pool: pending pooled tasks are cancelled, and every task
        in the line for a slot is let go with a rejection (a cancelled task
        hands no slot on, so the line would never move again)."""
        with self._lock:
            self._closed = True
            line, self._line = self._line, deque()
            self.queued -= len(line)
        for gate in line:
            gate.set()
        self.executor.shutdown(wait=False, cancel_futures=True)

    def stats(self) -> dict:
        with self._lock:
            out = {
                "threads": self.size,
                "queue": self.queued,
                "queue_size": self.queue_size,
                "active": self.active,
                "rejected": self.rejected,
                "completed": self.completed,
            }
        # histogram has its own stripe locks — summarize OUTSIDE _lock
        out["queue_wait"] = self.queue_wait.stats()
        return out


class ThreadPool:
    def __init__(self, settings=None):
        from .common.settings import Settings

        settings = settings or Settings.EMPTY
        self._pools: dict[str, _BoundedPool] = {}
        for name in Names:
            if name == "same":
                continue
            size = settings.get_int(f"threadpool.{name}.size", _DEFAULT_SIZES.get(name, 2))
            queue_size = settings.get_int(
                f"threadpool.{name}.queue_size",
                _DEFAULT_QUEUES.get(name, _DEFAULT_QUEUE_SIZE))
            self._pools[name] = _BoundedPool(name, size, queue_size)
        self._scheduler_tasks: list[_ScheduledTask] = []
        # one-shot schedule() timers ride a shared TIMER WHEEL (one heap, one
        # thread) instead of a threading.Timer per call: every search
        # schedules 1-2 timers (attempt timeout, hedge delay) and a Timer is
        # a whole OS thread — ~1ms of spawn per timer, which on the
        # request-cache HIT path was the single largest remaining cost.
        # Shutdown still cancels everything (a timer surviving the node
        # would fire its callback into dead services).
        self._timer_heap: list[tuple[float, int, ScheduledTimer]] = []
        self._timer_seq = itertools.count()
        self._timer_cv = threading.Condition()
        self._scheduler_thread = threading.Thread(target=self._scheduler_loop, daemon=True, name="estpu[scheduler]")
        self._shutdown = threading.Event()
        # the GIL-wait gauge (`/_nodes/stats` runtime.gil): how late the wheel
        # woke up for a periodic deadline of its own (_timer_loop). Written by
        # the wheel's thread, read (and the maximum reset) by gil_stats, both
        # with the timers' condition held
        self._gil_probes = 0
        self._gil_late_s = 0.0
        self._gil_late_max_s = 0.0
        self._scheduler_thread.start()
        self._timer_thread = threading.Thread(target=self._timer_loop,
                                              daemon=True,
                                              name="estpu[timers]")
        self._timer_thread.start()

    # execution --------------------------------------------------------------
    def executor(self, name: str) -> ThreadPoolExecutor:
        return self._pools[name if name != "same" else "generic"].executor

    def submit(self, name: str, fn, *args, **kwargs) -> Future:
        """Run fn on the named pool. "same" runs inline (caller thread), like the
        reference's ThreadPool.Names.SAME. Raises RejectedExecutionError when
        the pool's bounded queue is full or the pool is shut down."""
        if name == "same":
            f: Future = Future()
            try:
                f.set_result(fn(*args, **kwargs))
            except BaseException as e:  # noqa: BLE001 - mirror executor behavior
                f.set_exception(e)
            return f
        return self._pools[name].submit(fn, *args, **kwargs)

    def run_inline(self, name: str, fn, *args):
        """Run fn on the calling thread inside one of the named pool's slots
        and return its result (_BoundedPool.run_inline); "same" has no slots
        to lend and just calls it."""
        if name == "same":
            return fn(*args)
        return self._pools[name].run_inline(fn, *args)

    # scheduling -------------------------------------------------------------
    def schedule(self, delay_s: float, name: str, fn) -> "ScheduledTimer":
        """One-shot timer on the shared wheel. Returns a handle with the
        threading.Timer surface the callers use (cancel/finished/is_alive/
        join) but NO thread of its own — cancellation is lazy (the wheel
        drops cancelled heads when it reaches them), which bounds heap
        growth to the outstanding-timer count."""
        t = ScheduledTimer(time.monotonic() + max(0.0, float(delay_s)),
                           name, fn)
        with self._timer_cv:
            if self._shutdown.is_set():
                t.cancel()
                return t
            heapq.heappush(self._timer_heap,
                           (t.deadline, next(self._timer_seq), t))
            self._timer_cv.notify()
        return t

    def _timer_loop(self):
        """The wheel: sleep until the earliest live deadline, then fire it.
        The submit happens OUTSIDE the condition (pool locks are the
        submit's own; the cv stays a leaf); waits are always timed.

        The wheel also keeps one periodic deadline of its own, every
        _GIL_PROBE_S, which fires nothing: it books how late it woke up for
        it (_book_probe). The deadline is no entry of the heap: a live head
        that is always 50 ms away would keep the cancelled timers behind it
        (every search leaves one, armed for a minute) from ever being
        dropped."""
        probe_at = time.monotonic() + _GIL_PROBE_S
        while True:
            with self._timer_cv:
                while not self._shutdown.is_set():
                    # lazily drop cancelled heads so they neither delay the
                    # wakeup math nor accumulate
                    while self._timer_heap and \
                            self._timer_heap[0][2].finished.is_set():
                        heapq.heappop(self._timer_heap)
                    now = time.monotonic()
                    if now >= probe_at:
                        self._book_probe(now - probe_at)
                        # from now, so a late probe is not followed by a burst
                        probe_at = now + _GIL_PROBE_S
                    if self._timer_heap and self._timer_heap[0][0] <= now:
                        break
                    due = self._timer_heap[0][0] if self._timer_heap \
                        else probe_at
                    self._timer_cv.wait(min(due, probe_at) - now)
                if self._shutdown.is_set():
                    return
                _deadline, _seq, t = heapq.heappop(self._timer_heap)
            if t.finished.is_set():
                continue  # cancelled between pop and fire
            t.finished.set()
            if self._shutdown.is_set():
                return
            try:
                self.submit(t.pool, t.fn)
            except RejectedExecutionError:
                pass  # timer work is droppable when the node is saturated/closed
            except Exception:  # noqa: BLE001 — ONE bad timer (unknown pool
                # name, a submit-time failure) must not kill the shared wheel
                # thread: with the wheel dead, no attempt-timeout or hedge
                # timer ever fires again node-wide. The per-timer
                # threading.Timer design isolated such failures to one timer;
                # the wheel keeps that property by containing them here.
                logger.warning("timer fire failed (pool=%s)", t.pool,
                               exc_info=True)

    def _book_probe(self, late_s: float):
        """One reading of the GIL-wait gauge, booked with the timers'
        condition held: `late_s` is the wheel's clock at its wake-up, read in
        its loop before any pool hop, less the periodic deadline it slept to.
        On an idle node that is the kernel timer's slack; while other threads
        hold the interpreter lock it is what a woken thread waits to run
        again, which every hand-over of a search between threads pays."""
        self._gil_probes += 1
        self._gil_late_s += late_s
        self._gil_late_max_s = max(self._gil_late_max_s, late_s)

    def gil_stats(self) -> dict:
        """`/_nodes/stats` runtime.gil: `late_s` over `probes` is the mean
        wait of a woken thread; `late_max_s` is the largest since the last
        read (a stall of seconds shows in it, and a read resets it)."""
        with self._timer_cv:
            out = {"probes": self._gil_probes, "late_s": self._gil_late_s,
                   "late_max_s": self._gil_late_max_s}
            self._gil_late_max_s = 0.0
        return out

    def thread_ids(self) -> dict:
        """name → the kernel's ids of the pool's live worker threads: what
        `/_nodes/stats` runtime.cpu reads each role's CPU seconds by, from
        /proc and when stats are asked (monitor.cpu_stats), so that a task
        pays no clock read for it."""
        return {name: [t.native_id
                       for t in list(getattr(pool.executor, "_threads", ()))
                       if t.native_id is not None]
                for name, pool in self._pools.items()}

    def schedule_with_fixed_delay(self, interval_s: float, fn, name: str = "generic") -> _ScheduledTask:
        task = _ScheduledTask(interval_s, fn, lambda f: self.submit(name, f))
        task._next = time.monotonic() + interval_s  # type: ignore[attr-defined]
        self._scheduler_tasks.append(task)
        return task

    def _scheduler_loop(self):
        while not self._shutdown.wait(0.05):
            now = time.monotonic()
            for task in list(self._scheduler_tasks):
                if task.cancelled.is_set():
                    self._scheduler_tasks.remove(task)
                    continue
                if now >= getattr(task, "_next", 0):
                    task._next = now + task.interval  # type: ignore[attr-defined]
                    try:
                        task._submit(task.fn)
                    except (RuntimeError, RejectedExecutionError):
                        if self._shutdown.is_set():
                            return  # pool shut down
                        # saturated pool: skip this tick, keep the schedule

    # lifecycle --------------------------------------------------------------
    def shutdown(self):
        self._shutdown.set()
        for task in self._scheduler_tasks:
            task.cancel()
        # cancel outstanding one-shot timers BEFORE closing the pools: a timer
        # firing after shutdown would submit into a dead executor (harmless)
        # or, worse, run a callback against torn-down services
        with self._timer_cv:
            heap, self._timer_heap = self._timer_heap, []
            self._timer_cv.notify_all()
        for _deadline, _seq, t in heap:
            t.cancel()
        self._scheduler_thread.join(timeout=1.0)
        self._timer_thread.join(timeout=1.0)
        for pool in self._pools.values():
            pool.shutdown()

    def stats(self) -> dict:
        return {name: pool.stats() for name, pool in self._pools.items()}

    def queue_depth(self, name: str) -> int:
        """One pool's queued-task backlog as a plain unlocked int read — the
        load signal query-phase responses piggyback for adaptive replica
        selection (a torn read is at worst one task stale, which a decayed
        routing signal absorbs; taking the pool lock per response would not
        be)."""
        pool = self._pools.get(name)
        return 0 if pool is None else pool.queued

    def pool_histograms(self) -> dict:
        """name → queue-wait HistogramMetric (the Prometheus exposition reads
        the full bucket vectors; /_nodes/stats only carries the summary)."""
        return {name: pool.queue_wait for name, pool in self._pools.items()}
