"""The host's time between the spans (PR 37): a pool's wait and CPU seconds
(threadpool._BoundedPool), the timer wheel's own periodic timer whose lateness
is the GIL-wait gauge (ThreadPool._timer_loop, _book_probe), and the transport's hand-down
of a sampled request's span across its two pool hops. None of these tests
asserts a duration: they hold where an interval starts and ends, who its
parent is, and that counters rise and never fall."""

import threading
import time

import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.common.tracing import NOOP_SPAN, Tracer
from elasticsearch_tpu.threadpool import ThreadPool
from elasticsearch_tpu.transport.local import LocalTransport, LocalTransportRegistry
from elasticsearch_tpu.transport.service import TransportService

POOLS = ["generic", "search"]


@pytest.fixture
def pools():
    tp = ThreadPool(Settings.EMPTY)
    try:
        yield tp
    finally:
        tp.shutdown()


def _tracer():
    return Tracer(Settings.from_flat({"search.trace.sample_rate": "0"}),
                  node_name="test")


def _burn():
    return sum(i * i for i in range(200_000))


class TestPoolWait:
    @pytest.mark.parametrize("pool", POOLS)
    def test_a_sampled_submit_records_its_wait_under_the_current_span(
            self, pools, pool):
        trace = _tracer().start_trace("rest", force=True)
        seen = []
        with tracing.activate(trace.root):
            t_before = time.monotonic()
            fut = pools.submit(pool, lambda: seen.append(tracing.current_span()))
        fut.result(10)
        t_after = time.monotonic()
        trace.root.end()
        (wait,) = [s for s in trace.span_dicts() if s["name"] == "pool.wait"]
        assert wait["tags"] == {"pool": pool}
        assert wait["parent"] == trace.root.span_id
        assert t_before <= wait["t0"] <= wait["t1"] <= t_after
        # the worker keeps no span current: a handler continues its trace
        # from the wire context, and a task that never ends (the batcher's
        # drainer) must not hold a finished search's span
        assert seen == [None]

    @pytest.mark.parametrize("pool", POOLS)
    @pytest.mark.parametrize("current", [None, NOOP_SPAN],
                             ids=["no_decision", "declined"])
    def test_an_unsampled_submit_records_nothing(self, pools, monkeypatch, pool,
                                                 current):
        recorded = []
        monkeypatch.setattr(tracing.Span, "record",
                            lambda self, name, *a, **kw: recorded.append(name))
        with tracing.activate(current):
            pools.submit(pool, _burn).result(10)
        assert recorded == []
        assert pools.stats()[pool]["queue_wait"]["count"] == 1

    @pytest.mark.parametrize("pool", POOLS)
    def test_a_pools_cpu_seconds_are_its_workers_own(self, pools, pool):
        """runtime.cpu.threads reads each role's seconds from /proc by the
        kernel's ids of the pool's workers, when stats are asked: a task pays
        no clock read, and work on one pool shows under that pool alone."""
        from types import SimpleNamespace

        from elasticsearch_tpu.monitor import cpu_stats, thread_cpu_s

        assert pools.thread_ids()[pool] == []  # workers start with the work
        for _ in range(4):
            pools.submit(pool, _burn).result(10)
        ids = pools.thread_ids()
        assert set(ids) == set(pools.stats()) and len(ids[pool]) >= 1
        assert all(ids[name] == [] for name in ids if name != pool)
        node = SimpleNamespace(threadpool=pools)
        give_up = time.monotonic() + 10.0  # the kernel accounts a tick late
        while cpu_stats(node)["threads"][f"{pool}_s"] == 0.0 \
                and time.monotonic() < give_up:
            pools.submit(pool, _burn).result(10)
        got = cpu_stats(node)["threads"]
        assert got[f"{pool}_s"] > 0.0
        assert all(v == 0.0 for role, v in got.items() if role != f"{pool}_s")
        assert got[f"{pool}_s"] == pytest.approx(
            sum(thread_cpu_s(t) for t in ids[pool]), abs=0.5)

    def test_a_threads_seconds_come_from_schedstat_or_from_stat(self, tmp_path):
        from elasticsearch_tpu.monitor import _TICK_S, thread_cpu_s

        task = tmp_path / "self" / "task" / "77"
        task.mkdir(parents=True)
        (task / "stat").write_text(
            "77 (estpu[search] (x)) S 1 77 77 0 -1 4194304 10 0 0 0 "
            "250 50 0 0 20 0 9 0 1 1 1\n")
        assert thread_cpu_s(77, str(tmp_path)) == pytest.approx(300 * _TICK_S)
        (task / "schedstat").write_text("1500000000 7 3\n")
        assert thread_cpu_s(77, str(tmp_path)) == 1.5
        assert thread_cpu_s(78, str(tmp_path)) == 0.0  # a thread that is gone
        # this thread's own, against its own clock
        here = thread_cpu_s(threading.get_native_id())
        assert 0.0 < here <= time.thread_time() + 0.05


class TestGilGauge:
    def test_the_wheel_probes_while_idle_and_sums_never_fall(self, pools):
        first = pools.gil_stats()
        assert set(first) == {"probes", "late_s", "late_max_s"}
        give_up = time.monotonic() + 10.0
        while pools.gil_stats()["probes"] < first["probes"] + 3 \
                and time.monotonic() < give_up:
            time.sleep(0.02)
        later = pools.gil_stats()
        assert later["probes"] >= first["probes"] + 3
        assert later["late_s"] >= first["late_s"] >= 0.0

    def test_the_maximum_is_since_the_last_read(self, pools):
        with pools._timer_cv:
            pools._book_probe(0.75)  # a stall, as the wheel would book it
        assert pools.gil_stats()["late_max_s"] == 0.75
        # read and reset: the next reading holds what came after it alone
        assert pools.gil_stats()["late_max_s"] < 0.75
        booked = pools.gil_stats()
        assert booked["late_s"] >= 0.75 and booked["probes"] >= 1

    def test_other_timers_fire_beside_the_probe(self, pools):
        fired = threading.Event()
        timer = pools.schedule(0.01, "generic", fired.set)
        assert fired.wait(10) and not timer.is_alive()
        cancelled = pools.schedule(30.0, "generic", lambda: None)
        cancelled.cancel()
        probes = pools.gil_stats()["probes"]
        give_up = time.monotonic() + 10.0
        while pools.gil_stats()["probes"] == probes and time.monotonic() < give_up:
            time.sleep(0.02)
        assert pools.gil_stats()["probes"] > probes

    def test_cancelled_timers_do_not_pile_up_behind_the_probe(self, pools):
        """Every search leaves a cancelled timer armed for a minute: the
        wheel drops them as it wakes, which a periodic entry at the heap's
        head would have stopped (the probe is a deadline of the loop's own)."""
        timers = [pools.schedule(60.0, "generic", lambda: None)
                  for _ in range(200)]
        assert len(pools._timer_heap) == 200
        for t in timers:
            t.cancel()
        give_up = time.monotonic() + 10.0
        while pools._timer_heap and time.monotonic() < give_up:
            time.sleep(0.02)
        assert pools._timer_heap == []

    def test_shutdown_ends_the_probing(self):
        tp = ThreadPool(Settings.EMPTY)
        tp.shutdown()
        tp._timer_thread.join(5)
        assert not tp._timer_thread.is_alive()
        n = tp.gil_stats()["probes"]
        time.sleep(0.12)
        assert tp.gil_stats()["probes"] == n


class TestTransportHandsTheSpanDown:
    """A self-addressed request crosses two pools, `generic` and then the
    handler's own: both waits are recorded under the request's transport
    span, with the codec's two round trips, and the handler's thread has no
    span current."""

    @pytest.fixture
    def service(self, pools):
        registry = LocalTransportRegistry()
        backend = LocalTransport("local[self]", registry)

        class _Node:
            transport_address = "local[self]"

        svc = TransportService(backend, _Node(), pools)
        try:
            yield svc
        finally:
            svc.close()

    @pytest.mark.parametrize("executor,hops", [
        ("search", ["generic", "search"]), ("same", ["generic"])])
    def test_both_hops_and_both_round_trips(self, service, executor, hops):
        seen = []

        def handler(request, channel):
            seen.append((threading.current_thread().name,
                         tracing.current_span() if executor != "same" else None,
                         request.get(tracing.TRACE_WIRE_KEY)))
            return {"ok": True}

        service.register_handler("test/echo", handler, executor=executor)
        trace = _tracer().start_trace("rest", force=True)
        with tracing.activate(trace.root):
            fut = service.send_request(service.local_node, "test/echo", {"n": 1})
            assert fut.result(10) == {"ok": True}
            tracing.record_wake(trace.root, tracing.round_trip_end(fut),
                                "transport")
        trace.root.end()
        spans = trace.span_dicts()
        (tspan,) = [s for s in spans if s["name"] == "transport[test/echo]"]
        inner = sorted((s for s in spans if s["parent"] == tspan["id"]),
                       key=lambda s: s["t0"])
        assert [s["name"] for s in inner] == \
            ["transport.codec"] + ["pool.wait"] * len(hops) + ["transport.codec"]
        assert [s["tags"]["pool"] for s in inner if s["name"] == "pool.wait"] \
            == hops
        for a, b in zip(inner, inner[1:]):
            assert a["t1"] <= b["t0"]
        assert tspan["t0"] <= inner[0]["t0"] and inner[-1]["t1"] == tspan["t1"]
        # the waiter's wake-up starts where the round-trip ended
        (wake,) = [s for s in spans if s["name"] == "thread.wake"]
        assert wake["t0"] == tspan["t1"] and wake["tags"] == {"after": "transport"}
        ((thread, current, wire),) = seen
        assert thread.startswith(f"estpu[{hops[-1]}]") and current is None
        assert wire.span_id == tspan["id"]

    def test_an_unsampled_request_pays_no_span(self, service, monkeypatch):
        made = []
        monkeypatch.setattr(tracing.Span, "record",
                            lambda self, name, *a, **kw: made.append(name))
        service.register_handler("test/echo", lambda r, c: {"ok": True},
                                 executor="search")
        fut = service.send_request(service.local_node, "test/echo", {"n": 1})
        assert fut.result(10) == {"ok": True}
        assert made == [] and tracing.round_trip_end(fut) is None
        assert not hasattr(fut, "trace_span")
