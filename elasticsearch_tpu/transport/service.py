"""TransportService: action-string-keyed async RPC.

Analogue of transport/TransportService.java (SURVEY.md §2.2): a handler registry
(`register_handler(action, fn)`), `send_request(node, action, body)` returning a Future,
per-request timeouts, and pluggable backends (LocalTransport in-process; NettyTransport's
role is filled by tcp.py). Payloads are JSON-able dicts; every message round-trips
through the wire codec even in-process, so serialization bugs surface in unit tests
exactly like the reference's AssertingLocalTransport (SURVEY.md §4.3). The one way
past the codec is `call_local`, which sends no message: a blocking caller runs a
handler of its own node on its own thread.
"""

from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Any, Callable

from ..common import tracing
from ..common.errors import (
    ActionNotFoundError,
    NodeNotConnectedError,
    ReceiveTimeoutError,
    SearchEngineError,
    TransportError,
)
from ..common.logging import get_logger
from ..common.stream import StreamInput, StreamOutput


def fut_result(fut: Future, timeout: float | None = 30.0):
    """Await a transport future, converting timeout.

    Catches BOTH timeout classes: before Python 3.11,
    concurrent.futures.TimeoutError is NOT the builtin TimeoutError — catching
    only the builtin let raw futures timeouts leak to callers (the
    test_handler_slow_response_timeout seed failure)."""
    try:
        return fut.result(timeout=timeout)
    except (TimeoutError, FutureTimeoutError):
        raise ReceiveTimeoutError("request timed out") from None


def complete_fut(fut: Future, result=None, error: Exception | None = None) -> bool:
    """Resolve a future exactly once. Transport futures race between the
    response path, injected faults, and response-timeout timers — whichever
    lands first wins and the rest become no-ops."""
    try:
        if error is not None:
            fut.set_exception(error)
        else:
            fut.set_result(result)
        return True
    except InvalidStateError:
        return False


class TransportRequestHandler:
    """Handler signature: fn(request_dict, channel) — respond via channel, or return a
    dict to auto-respond."""

    def __init__(self, fn: Callable, executor: str = "same"):
        self.fn = fn
        self.executor = executor


class TransportChannel:
    def __init__(self, respond: Callable[[dict | None, Exception | None], None]):
        self._respond = respond
        self._done = False

    def send_response(self, response: dict | None):
        if not self._done:
            self._done = True
            self._respond(response, None)

    def send_failure(self, error: Exception):
        if not self._done:
            self._done = True
            self._respond(None, error)


def _encode(payload: Any) -> bytes:
    out = StreamOutput()
    out.write_value(payload)
    return out.bytes()


def _roundtrip(payload: Any) -> Any:
    """Serialize + deserialize through the wire codec (asserts wire-compatibility)."""
    return StreamInput(_encode(payload)).read_value()


class TransportService:
    def __init__(self, backend, local_node=None, threadpool=None):
        self.backend = backend
        self.local_node = local_node
        self.threadpool = threadpool
        self.handlers: dict[str, TransportRequestHandler] = {}
        self._req_ids = itertools.count(1)
        self.logger = get_logger("transport")
        self.stats = {"rx_count": 0, "tx_count": 0, "timed_out_count": 0,
                      "faults_injected": 0}
        # MockTransportService-style fault injection (transport/faults.py):
        # installed on live nodes by chaos tests, None in production
        self.fault_policy = None
        # in-flight-requests circuit breaker (the node wires its
        # CircuitBreakerService child here): every outbound message's encoded
        # size is reserved until the response future resolves, so a flood of
        # huge requests trips 429 instead of buffering the node to death
        self.in_flight_breaker = None
        # outstanding reservations (future -> expiry): blocking callers pass
        # no future-level timeout, so a response that never comes would pin
        # its bytes forever — the backstop sweep below fails such futures
        self._inflight: dict = {}
        self._inflight_lock = threading.Lock()
        backend.bind(self)

    # --- registry -----------------------------------------------------------
    def register_handler(self, action: str, fn: Callable, executor: str = "same"):
        self.handlers[action] = TransportRequestHandler(fn, executor)

    # --- sending ------------------------------------------------------------
    def _is_local(self, node) -> bool:
        if self.local_node is None:
            return False
        address = getattr(node, "transport_address", node)
        return address == self.local_node.transport_address

    def send_request(self, node, action: str, request: dict,
                     timeout: float | None = None) -> Future:
        """Dispatch `request` to `node`, returning a Future for the response.

        A non-None `timeout` arms a timer that fails the future with
        ReceiveTimeoutError when no response lands in time — for
        callback-driven callers with no thread parked in fut_result. Blocking
        callers should pass no timeout here (fut_result bounds the wait
        without a timer thread per request). Late responses to an already
        timed-out future are discarded (complete_fut)."""
        fut: Future = Future()
        self.stats["tx_count"] += 1
        # distributed tracing: when the calling thread carries a sampled span,
        # wrap the round-trip in a transport span and ship the trace context
        # INSIDE the request payload (common/stream.py serializes TraceContext
        # as a typed wire value, so it crosses both the in-process roundtrip
        # and the TCP frames) — handlers pick it up via request["_trace"].
        # Unsampled requests pay one thread-local read and nothing else.
        parent_span = tracing.current_span()
        if parent_span:  # truthy = sampled (the NOOP span means decided-off)
            tspan = parent_span.child(f"transport[{action}]")
            request = {**request,
                       tracing.TRACE_WIRE_KEY: tracing.wire_context(tspan)}
            # end at response resolution, whichever path resolves it first —
            # Span.end is idempotent and only appends under the trace's leaf
            # lock, so the callback is safe from any resolving thread
            fut.add_done_callback(lambda _f: tspan.end())
            # the span rides the future: _send_now records the codec and the
            # pool hops under it, and the waiter reads where the round-trip
            # ended off it (tracing.round_trip_end): its wake-up starts there
            fut.trace_span = tspan  # type: ignore[attr-defined]
        if timeout is not None:
            self._arm_response_timeout(fut, action, timeout)
        try:
            rule = None if self.fault_policy is None else \
                self.fault_policy.decide(action, getattr(node, "transport_address",
                                                         node), request, "send")
            if rule is not None:
                self.stats["faults_injected"] += 1
                if self._apply_send_fault(rule, fut, node, action, request):
                    return fut
            self._send_now(node, action, request, fut)
        except SearchEngineError as e:
            complete_fut(fut, error=e)
        except Exception as e:  # noqa: BLE001
            complete_fut(fut, error=TransportError(str(e), cause=e))
        return fut

    INFLIGHT_BACKSTOP_S = 300.0

    def _charge_in_flight(self, raw: bytes, action: str, fut: Future):
        """Reserve the message's encoded size on the in-flight breaker; the
        reservation rides the response future and releases exactly once when
        it resolves. Raises CircuitBreakingError — callers convert it into a
        failed future.

        Blocking callers (submit_request / fut_result) never resolve the
        future on THEIR timeout, so a hung handler or a dropped message with
        no armed timer would pin its bytes forever. Each charge therefore
        lazily sweeps reservations older than INFLIGHT_BACKSTOP_S, failing
        those futures (ReceiveTimeoutError) — which triggers their release
        callback exactly once. No timer thread per request; the sweep rides
        the next send."""
        br = self.in_flight_breaker
        if br is None:
            return
        # sweep BEFORE charging: with the breaker wedged full of expired
        # reservations, a charge-first order would trip and return without
        # ever reaching the sweep — permanently 429ing every send
        now = time.monotonic()
        with self._inflight_lock:
            expired = [f for f, expiry in self._inflight.items()
                       if expiry <= now]
        for f in expired:
            # failing the future runs its done-callback → release + untrack
            complete_fut(f, error=ReceiveTimeoutError(
                "in-flight reservation expired with no response "
                f"(> {self.INFLIGHT_BACKSTOP_S:.0f}s)"))
        size = len(raw)
        br.add_estimate_and_maybe_break(size, f"<transport_request>[{action}]")
        with self._inflight_lock:
            self._inflight[fut] = now + self.INFLIGHT_BACKSTOP_S

        def on_done(_f):
            br.release(size)
            with self._inflight_lock:
                self._inflight.pop(fut, None)

        fut.add_done_callback(on_done)

    def _wire_copy(self, request: dict, action: str, fut: Future, tspan):
        """The request as the receiver reads it: encoded, charged to the
        in-flight breaker by its encoded size, decoded again. A sampled
        request (`tspan`, its transport span) records the round trip as
        `transport.codec`; any other pays one truth test."""
        t0 = time.monotonic() if tspan else 0.0
        raw = _encode(request)
        self._charge_in_flight(raw, action, fut)
        payload = StreamInput(raw).read_value()
        if tspan:
            tspan.record("transport.codec", t0, time.monotonic())
        return payload

    def _dispatch_under(self, tspan, action: str, request: Any,
                        channel: TransportChannel):
        """`dispatch` of a sampled local request on its `generic` thread,
        with the sender's transport span current: the hop to the handler's
        own pool is submitted under it, and that pool records the wait there
        (threadpool._BoundedPool.submit keeps the span current at submit).
        The handler itself runs on its pool's thread with no span current
        and continues the trace from the wire context, as a remote one does."""
        with tracing.activate(tspan):
            self.dispatch(action, request, channel)

    def _send_now(self, node, action: str, request: dict, fut: Future):
        tspan = getattr(fut, "trace_span", None)  # sampled: its transport span
        # Self-addressed requests short-circuit past the backend (the reference
        # TransportService does the same for localNode): still codec-roundtripped
        # for wire-compat assertions, but no socket / simulated-network hop.
        # They still cross two pools, `generic` here and the handler's own in
        # _dispatch_now, and the caller of a future needs that. A caller that
        # would block on the future at once, for one answer of its own node,
        # has `call_local` instead: no message, no pool thread, no future.
        if self._is_local(node):
            payload = self._wire_copy(request, action, fut, tspan)

            def respond(response, error):
                if error is not None:
                    complete_fut(fut, error=error)
                elif tspan:
                    # the responder's thread: the response's round trip, and
                    # the transport span ends with it, BEFORE the future
                    # resolves — so the waiter's wake-up starts at an instant
                    # that is written before it can wake (the done-callback's
                    # own end() is then a no-op)
                    t0 = time.monotonic()
                    response = _roundtrip(response)
                    t1 = time.monotonic()
                    tspan.record("transport.codec", t0, t1)
                    tspan.end(t1)
                    complete_fut(fut, response)
                else:
                    complete_fut(fut, _roundtrip(response))

            channel = TransportChannel(respond)
            if self.threadpool is None:
                self.dispatch(action, payload, channel)
            elif tspan:
                # both pool hops of a sampled request, `generic` here and the
                # handler's own in _dispatch_now, record their wait under
                # the transport span
                with tracing.activate(tspan):
                    self.threadpool.submit("generic", self._dispatch_under,
                                           tspan, action, payload, channel)
            else:
                self.threadpool.submit("generic", self.dispatch, action, payload,
                                       channel)
            return
        # Backends that truly serialize (TCP) skip the assert-roundtrip AND
        # this layer's breaker charge — double-encoding just for a size would
        # defeat the point, so their wire framing charges the in-flight
        # breaker from the actual frame bytes (tcp.py send); the in-process
        # path charges here from the bytes it encodes anyway.
        if getattr(self.backend, "serializes", False):
            payload = request
        else:
            payload = self._wire_copy(request, action, fut, tspan)
        self.backend.send(node, action, payload, fut)

    def _apply_send_fault(self, rule, fut: Future, node, action: str,
                          request: dict) -> bool:
        """Apply a send-side fault rule. True = the send was consumed (do not
        forward); False = forward normally (delay rules re-enter via timer)."""
        if rule.kind == "drop":
            return True  # message lost; only a response timeout resolves fut
        if rule.kind in ("disconnect", "error"):
            complete_fut(fut, error=rule.make_error())
            return True
        # delay: deliver the real send after delay_s on a daemon timer
        def fire():
            try:
                self._send_now(node, action, request, fut)
            except Exception as e:  # noqa: BLE001 — timer thread must not die silent
                complete_fut(fut, error=TransportError(str(e), cause=e))

        t = threading.Timer(rule.delay_s, fire)
        t.daemon = True
        t.start()
        return True

    def _arm_response_timeout(self, fut: Future, action: str, timeout: float):
        def on_timeout():
            if complete_fut(fut, error=ReceiveTimeoutError(
                    f"[{action}] received no response within [{timeout}s]")):
                self.stats["timed_out_count"] += 1

        timer = threading.Timer(max(0.0, timeout), on_timeout)
        timer.daemon = True
        timer.start()
        fut.add_done_callback(lambda _f: timer.cancel())

    def submit_request(self, node, action: str, request: dict,
                       timeout: float | None = 30.0) -> dict:
        """Blocking convenience. The bound comes from fut_result's blocking
        wait — no per-request timer thread; send_request's future-level
        timeout is for CALLBACK-driven callers that have no thread parked."""
        return fut_result(self.send_request(node, action, request), timeout)

    def runs_locally(self, node, action: str) -> bool:
        """Whether `call_local` would run `action`'s handler on the calling
        thread: `node` is this node, the action has a handler here, and no
        fault rule could match it (send side or receive side, whatever its
        probability or `where`: the rules were written for messages, so a
        node under fault injection sends messages). Asking draws nothing
        from the policy's RNG and records no hit."""
        return self._is_local(node) and action in self.handlers and \
            (self.fault_policy is None or not self.fault_policy.may_match(
                action, self.local_node.transport_address))

    def call_local(self, action: str, request: dict):
        """Blocking, for an action of THIS node: run its handler on the
        calling thread, inside one of its executor's slots
        (threadpool.run_inline: the pool's admission, bound and counters, no
        hand-over to a pool thread and none back), with `request` as it is,
        and return what the handler answered as it is; the handler's
        exception, or the pool's RejectedExecutionError, is raised here.

        No message is made: no `_wire_copy`, no `_roundtrip`, no `generic`
        hop, no future, so nothing is charged to the in-flight breaker
        either, which charges the encoded bytes of messages in flight. Request
        and answer are SHARED with the handler, not copies: neither side may
        mutate what it was handed. `tx_count` / `rx_count` count the call as
        the message it stands for. Where `runs_locally` says no (a fault rule
        could match), the request takes the ordinary path, `submit_request`
        to this node. Wire compatibility stays asserted where `send_request`
        is used, which is everywhere else."""
        if not self.runs_locally(self.local_node, action):
            return self.submit_request(self.local_node, action, request)
        self.stats["tx_count"] += 1
        self.stats["rx_count"] += 1
        handler = self.handlers[action]
        answer: list = []

        def respond(response, error):
            answer.append((response, error))

        channel = TransportChannel(respond)
        if self.threadpool is None:
            result = handler.fn(request, channel)
        else:
            result = self.threadpool.run_inline(handler.executor, handler.fn,
                                                request, channel)
        if result is not None:
            return result
        if not answer:
            raise TransportError(
                f"handler of [{action}] answered nothing on the calling thread")
        response, error = answer[0]
        if error is not None:
            raise error
        return response

    # --- receiving (called by backends) -------------------------------------
    def dispatch(self, action: str, request: Any, channel: TransportChannel):
        self.stats["rx_count"] += 1
        # recv-side rules match the RECEIVING node's own address (the sender
        # is not identified at this layer)
        rule = None if self.fault_policy is None else \
            self.fault_policy.decide(action, getattr(self.backend, "address", ""),
                                     request, "recv")
        if rule is not None:
            self.stats["faults_injected"] += 1
            if rule.kind == "drop":
                return  # handler never runs; the sender's timeout surfaces it
            if rule.kind in ("disconnect", "error"):
                channel.send_failure(rule.make_error())
                return
            # delay: run the handler after delay_s — the deterministic "slow
            # handler" that response-timeout tests are built on
            t = threading.Timer(rule.delay_s,
                                lambda: self._dispatch_now(action, request, channel))
            t.daemon = True
            t.start()
            return
        self._dispatch_now(action, request, channel)

    def _dispatch_now(self, action: str, request: Any, channel: TransportChannel):
        handler = self.handlers.get(action)
        if handler is None:
            channel.send_failure(ActionNotFoundError(f"no handler for action [{action}]"))
            return

        def run():
            try:
                result = handler.fn(request, channel)
                if result is not None:
                    channel.send_response(result)
            except Exception as e:  # noqa: BLE001
                channel.send_failure(e)

        if handler.executor == "same" or self.threadpool is None:
            run()
            return
        try:
            self.threadpool.submit(handler.executor, run)
        except SearchEngineError as e:
            # bounded-queue rejection (RejectedExecutionError): the typed 429
            # travels back to the sender instead of the request silently
            # vanishing into a saturated pool (which would read as a timeout)
            channel.send_failure(e)

    def close(self):
        self.backend.close()
