"""Metrics primitives: counters, means, meters, EWMA, histograms.

Analogue of common/metrics/{CounterMetric,MeanMetric,MeterMetric,EWMA}.java. Thread-safe
via a lock per metric (the reference uses LongAdder/atomics). `HistogramMetric`
adds what the mean-only metrics cannot answer — tail percentiles (p50/p95/p99)
over fixed log-spaced buckets, lock-STRIPED so concurrent pool threads don't
serialize on one hot lock."""

from __future__ import annotations

import bisect
import math
import threading
import time


class CounterMetric:
    __slots__ = ("_lock", "_count")

    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0

    def inc(self, n: int = 1):
        with self._lock:
            self._count += n

    def dec(self, n: int = 1):
        with self._lock:
            self._count -= n

    @property
    def count(self) -> int:
        return self._count


class MeanMetric:
    """Tracks (count, sum) — e.g. query count + total time."""

    __slots__ = ("_lock", "_count", "_sum")

    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0
        self._sum = 0.0

    def inc(self, value: float):
        with self._lock:
            self._count += 1
            self._sum += value

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def mean(self) -> float:
        return self._sum / self._count if self._count else 0.0


class EWMA:
    """Exponentially-weighted moving average (ref: common/metrics/EWMA.java)."""

    def __init__(self, alpha: float, interval_s: float):
        self._alpha = alpha
        self._interval = interval_s
        self._rate = 0.0
        self._uncounted = 0
        self._initialized = False
        self._lock = threading.Lock()

    @classmethod
    def one_minute(cls, tick_s: float = 5.0) -> "EWMA":
        return cls(1 - math.exp(-tick_s / 60.0), tick_s)

    def update(self, n: int = 1):
        with self._lock:
            self._uncounted += n

    def tick(self):
        with self._lock:
            instant_rate = self._uncounted / self._interval
            self._uncounted = 0
            if self._initialized:
                self._rate += self._alpha * (instant_rate - self._rate)
            else:
                self._rate = instant_rate
                self._initialized = True

    @property
    def rate(self) -> float:
        return self._rate


class MeterMetric:
    """Throughput meter with 1m EWMA (ref: common/metrics/MeterMetric.java)."""

    def __init__(self):
        self._counter = CounterMetric()
        self._ewma = EWMA.one_minute()
        self._start = time.monotonic()
        self._last_tick = self._start

    def mark(self, n: int = 1):
        self._counter.inc(n)
        self._ewma.update(n)
        now = time.monotonic()
        if now - self._last_tick >= 5.0:
            self._ewma.tick()
            self._last_tick = now

    @property
    def count(self) -> int:
        return self._counter.count

    @property
    def mean_rate(self) -> float:
        elapsed = time.monotonic() - self._start
        return self._counter.count / elapsed if elapsed > 0 else 0.0


class _HistogramStripe:
    """One stripe of a HistogramMetric: its own lock + counts. A thread maps
    to a stripe by identity, so concurrent observers mostly touch distinct
    locks (the LongAdder idea, sized for ~10s of pool threads)."""

    __slots__ = ("lock", "counts", "count", "sum")

    def __init__(self, n_buckets: int):
        self.lock = threading.Lock()
        self.counts = [0] * n_buckets
        self.count = 0
        self.sum = 0.0


class HistogramMetric:
    """Latency histogram over fixed log-spaced buckets (seconds).

    Default bounds double from 100µs to ~105s (21 bounds + overflow), which
    holds any serving-path latency this node can legally produce at <2x
    relative error per bucket — enough for p50/p95/p99 operator questions
    ("slow because queued or slow because device?") without per-sample
    storage. Percentiles interpolate linearly inside the winning bucket.

    Lock-striped: `observe` takes exactly one leaf stripe lock (never blocks,
    never dispatches — safe anywhere the TPU004/TPU011 rules reach);
    `snapshot`/`percentile` sum across stripes.
    """

    DEFAULT_BOUNDS = tuple(1e-4 * (2.0 ** i) for i in range(21))
    STRIPES = 8

    __slots__ = ("_bounds", "_stripes")

    def __init__(self, bounds=None):
        self._bounds = tuple(bounds) if bounds is not None else self.DEFAULT_BOUNDS
        n = len(self._bounds) + 1  # + overflow (+Inf) bucket
        self._stripes = [_HistogramStripe(n) for _ in range(self.STRIPES)]

    def observe(self, seconds: float) -> None:
        v = max(0.0, float(seconds))
        idx = bisect.bisect_left(self._bounds, v)
        # NOT `ident % STRIPES`: on glibc get_ident() is the page-aligned
        # pthread descriptor address, so the low bits are identical for every
        # thread and all observers would alias one stripe — shift past the
        # alignment before folding
        stripe = self._stripes[(threading.get_ident() >> 12) % self.STRIPES]
        with stripe.lock:
            stripe.counts[idx] += 1
            stripe.count += 1
            stripe.sum += v

    def snapshot(self) -> tuple[list[int], int, float]:
        """(per-bucket counts incl. overflow, total count, value sum)."""
        counts = [0] * (len(self._bounds) + 1)
        total = 0
        vsum = 0.0
        for stripe in self._stripes:
            with stripe.lock:
                for i, c in enumerate(stripe.counts):
                    counts[i] += c
                total += stripe.count
                vsum += stripe.sum
        return counts, total, vsum

    @property
    def count(self) -> int:
        return self.snapshot()[1]

    @property
    def sum(self) -> float:
        return self.snapshot()[2]

    def percentile(self, q: float) -> float:
        """q in (0,1] → seconds; 0.0 when empty."""
        counts, total, _ = self.snapshot()
        return self._percentile_from(counts, total, q)

    def _percentile_from(self, counts, total, q: float) -> float:
        if total <= 0:
            return 0.0
        target = q * total
        cum = 0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c >= target:
                lo = self._bounds[i - 1] if i > 0 else 0.0
                hi = self._bounds[i] if i < len(self._bounds) \
                    else self._bounds[-1] * 2.0
                return lo + (hi - lo) * (target - cum) / c
            cum += c
        return self._bounds[-1] * 2.0

    def stats(self) -> dict:
        """Summary for /_nodes/stats: count + mean/p50/p95/p99 in ms."""
        counts, total, vsum = self.snapshot()
        return {
            "count": total,
            "mean_ms": round(vsum / total * 1000.0, 3) if total else 0.0,
            "p50_ms": round(self._percentile_from(counts, total, 0.50) * 1000.0, 3),
            "p95_ms": round(self._percentile_from(counts, total, 0.95) * 1000.0, 3),
            "p99_ms": round(self._percentile_from(counts, total, 0.99) * 1000.0, 3),
        }

    def cumulative(self) -> tuple[list[tuple[float, int]], int, float]:
        """Prometheus view: ([(le_bound_seconds, cumulative_count)...] with a
        final (inf, total), total count, value sum)."""
        counts, total, vsum = self.snapshot()
        out = []
        cum = 0
        for bound, c in zip(self._bounds, counts):
            cum += c
            out.append((bound, cum))
        out.append((float("inf"), total))
        return out, total, vsum
