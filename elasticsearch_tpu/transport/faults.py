"""Fault-injecting transport rules — the MockTransportService analogue.

The reference's test suite turns every network pathology into a deterministic
rule (test/transport/MockTransportService.java: addFailToSendNoConnectRule,
addUnresponsiveRule, delayed forwarding). Same shape here: a `FaultPolicy` holds
seeded, per-(action, node) `FaultRule`s and installs onto a live
`TransportService` (`policy.install(node.transport)`), so chaos tests can
exercise coordinator failover, deadline expiry, and write-path retry without
wall-clock races or real dead nodes.

Rule kinds:

- ``disconnect`` — fail the send immediately with NodeNotConnectedError (the
  reference's fail-to-send no-connect rule): the deterministic "node is gone".
- ``error``     — fail with an arbitrary error instance/factory (remote handler
  blew up, typed error crossed the wire).
- ``drop``      — the message vanishes: the future never completes and the
  caller's response timeout is what surfaces it (unresponsive rule).
- ``delay``     — deliver after ``delay_s`` (delayed-forwarding rule): the
  deterministic "slow network/handler" that deadline tests are built on.

Rules apply on the *send* side by default; ``direction="recv"`` applies inside
``dispatch`` on the receiving service instead (a slow/lost handler rather than a
slow/lost wire). Matching is fnmatch over the action name and target node
address, plus an optional ``where(action, address, request)`` refinement for
request-content matches (e.g. one specific shard id). ``probability`` draws from
the policy's seeded RNG; ``max_hits`` disarms a rule after N matches.
"""

from __future__ import annotations

import fnmatch
import random
import threading
from dataclasses import dataclass, field

from ..common.errors import NodeNotConnectedError, TransportError

KINDS = ("drop", "delay", "error", "disconnect")


def _glob_match(value: str, pattern: str) -> bool:
    """fnmatch with `[`/`]` taken LITERALLY: action names carry brackets
    ("indices:data/write/index[r]") that fnmatch would read as character
    classes, silently matching nothing. Patterns without wildcards compare
    exactly."""
    if "*" not in pattern and "?" not in pattern:
        return value == pattern
    return fnmatch.fnmatchcase(value, pattern.replace("[", "[[]"))


@dataclass
class FaultRule:
    kind: str = "disconnect"
    action: str = "*"             # fnmatch pattern over the action string
    node: str = "*"               # fnmatch pattern over the target address
    direction: str = "send"       # "send" (on the sender) | "recv" (in dispatch)
    delay_s: float = 0.0          # for kind="delay"
    error: object = None          # Exception prototype or factory; for "error"
    probability: float = 1.0      # matched via the policy's seeded RNG
    max_hits: int | None = None   # disarm after N injections (None = forever)
    where: object = None          # optional (action, address, request) -> bool
    hits: int = field(default=0, compare=False)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind [{self.kind}] (want one of {KINDS})")
        if self.direction not in ("send", "recv"):
            raise ValueError(f"unknown fault direction [{self.direction}]")

    def make_error(self) -> Exception:
        """A FRESH exception per injection: one shared instance raised from
        many threads would interleave __traceback__/__context__ mutations
        across unrelated requests."""
        if self.error is None:
            return TransportError(f"injected fault ({self.action} -> {self.node})")
        if isinstance(self.error, Exception):
            try:
                return type(self.error)(*self.error.args)
            except TypeError:  # error classes with exotic signatures: best effort
                return self.error
        err = self.error("injected fault") if callable(self.error) else None
        return err if isinstance(err, Exception) else TransportError(str(self.error))


class FaultPolicy:
    """A seeded rule set installable on one TransportService.

    Thread-safe: transports consult it from sender and dispatcher threads.
    All randomness flows through one seeded RNG, so a chaos run replays
    identically from its seed (the TestCluster idiom).
    """

    def __init__(self, seed: int | None = 0):
        self.rng = random.Random(seed)
        self._rules: list[FaultRule] = []
        self._lock = threading.Lock()
        self.injected = 0  # total injections, all rules

    # --- rule management ---------------------------------------------------
    def add_rule(self, rule: FaultRule) -> FaultRule:
        with self._lock:
            self._rules.append(rule)
        return rule

    def drop(self, action: str = "*", node: str = "*", **kw) -> FaultRule:
        return self.add_rule(FaultRule(kind="drop", action=action, node=node, **kw))

    def delay(self, delay_s: float, action: str = "*", node: str = "*",
              **kw) -> FaultRule:
        return self.add_rule(FaultRule(kind="delay", delay_s=delay_s, action=action,
                                       node=node, **kw))

    def error(self, error=None, action: str = "*", node: str = "*",
              **kw) -> FaultRule:
        return self.add_rule(FaultRule(kind="error", error=error, action=action,
                                       node=node, **kw))

    def disconnect(self, action: str = "*", node: str = "*", **kw) -> FaultRule:
        return self.add_rule(
            FaultRule(kind="disconnect",
                      error=NodeNotConnectedError("injected disconnect"),
                      action=action, node=node, **kw))

    def clear(self):
        with self._lock:
            self._rules.clear()

    def remove_rule(self, rule: FaultRule):
        with self._lock:
            if rule in self._rules:
                self._rules.remove(rule)

    # --- matching ----------------------------------------------------------
    def decide(self, action: str, address: str, request=None,
               direction: str = "send") -> FaultRule | None:
        """First armed matching rule, with its hit recorded — or None.

        The probability draw happens ONLY for rules that match action+node, so
        unrelated traffic does not advance the RNG and runs stay replayable.
        """
        with self._lock:
            for rule in self._rules:
                if rule.direction != direction:
                    continue
                if rule.max_hits is not None and rule.hits >= rule.max_hits:
                    continue
                if not _glob_match(action, rule.action):
                    continue
                if not _glob_match(str(address), rule.node):
                    continue
                if rule.where is not None and not rule.where(action, address, request):
                    continue
                if rule.probability < 1.0 and self.rng.random() >= rule.probability:
                    continue
                rule.hits += 1
                self.injected += 1
                return rule
        return None

    def may_match(self, action: str, address: str) -> bool:
        """Whether some armed rule, of either direction, names this action at
        this address; a rule's `where` and `probability` are not asked, so the
        answer errs towards yes. No hit is recorded and the RNG is not
        advanced: for a caller that must know BEFORE it chooses a path on
        which the rules do not apply (TransportService.call_local)."""
        with self._lock:
            return any(
                (rule.max_hits is None or rule.hits < rule.max_hits)
                and _glob_match(action, rule.action)
                and _glob_match(str(address), rule.node)
                for rule in self._rules)

    # --- installation ------------------------------------------------------
    def install(self, transport_service) -> "FaultPolicy":
        """Attach to a live TransportService (e.g. a TestCluster node's
        `node.transport`). One policy per service; installing replaces any
        previous policy."""
        transport_service.fault_policy = self
        return self

    @staticmethod
    def uninstall(transport_service):
        transport_service.fault_policy = None


# ---------------------------------------------------------------------------
# device-side fault injection: the serving path's ONE device pull
# ---------------------------------------------------------------------------


class DevicePullFaults:
    """Deterministic stall injection for the serving path's single batched
    device pull (execute._merge_flat_plain) — the device-side sibling of the
    transport rules above, built for the stall-watchdog chaos tests: a
    transport rule can wedge a wire, but only this can wedge the drainer's
    merge half the way a hung runtime / preempted device would.

    The hot-path gate is one module attribute read (`active` is a plain
    bool): disarmed — the shipped default — costs exactly that. Armed, a pull
    whose owning index matches `index` sleeps `delay_s` before the
    device_get, at most `times` total injections (then auto-disarms).
    `delay()`/`maybe_stall()` never touch a lock on the disarmed path and
    take only the leaf `_lock` for the countdown when armed."""

    def __init__(self):
        self.active = False  # the one hot-path read
        self._lock = threading.Lock()
        self._delay_s = 0.0
        self._index = "*"
        self._remaining = 0
        self.injected = 0

    def arm(self, delay_s: float, index: str = "*", times: int = 1):
        with self._lock:
            self._delay_s = float(delay_s)
            self._index = index
            self._remaining = int(times)
            self.active = True
        return self

    def disarm(self):
        with self._lock:
            self.active = False
            self._remaining = 0

    def delay_for(self, index: str | None) -> float:
        """The stall to apply to one pull (0.0 = none). Decrements the
        injection budget under the leaf lock; the caller sleeps OUTSIDE it."""
        with self._lock:
            if not self.active or self._remaining <= 0:
                return 0.0
            if not _glob_match(str(index), self._index):
                return 0.0
            self._remaining -= 1
            if self._remaining <= 0:
                self.active = False
            self.injected += 1
            return self._delay_s


DEVICE_PULL = DevicePullFaults()


# ---------------------------------------------------------------------------
# device fault injection: seeded XLA-error seams for the fault-domain circuits
# ---------------------------------------------------------------------------

# error kind -> the XLA status-prefixed message jaxlib would surface; the
# classification (common/devicehealth.classify_device_error) reads the prefix,
# so each kind lands deterministically in its transient/persistent bucket.
_DEVICE_ERROR_MESSAGES = {
    "oom": "RESOURCE_EXHAUSTED: injected: out of memory allocating scratch",
    "timeout": "DEADLINE_EXCEEDED: injected: device execution timed out",
    "unavailable": "UNAVAILABLE: injected: device unreachable",
    "launch": "INTERNAL: injected: failed to launch executable on device",
    "transfer": "FAILED_PRECONDITION: injected: device-to-host transfer failed",
    "internal": "INTERNAL: injected: generic device failure",
}

DEVICE_ERROR_KINDS = tuple(_DEVICE_ERROR_MESSAGES)


def make_device_error(kind: str) -> Exception:
    """A FRESH injected XlaRuntimeError per injection (same rationale as
    FaultRule.make_error: shared instances interleave tracebacks across
    threads). Falls back to RuntimeError where jax is absent so the seam
    stays importable in host-only tooling."""
    msg = _DEVICE_ERROR_MESSAGES[kind]
    try:
        from jax.errors import JaxRuntimeError
    except Exception:  # noqa: BLE001 — jax-less environment
        return RuntimeError(msg)
    return JaxRuntimeError(msg)


class DeviceFaults:
    """Deterministic device-error injection for the fault-domain circuits
    (common/devicehealth) — error type × domain glob × count, mirroring
    DevicePullFaults above. Seam call sites sit at the four domain
    touchpoints (`pack:<index>` before the pack publishes, `compile:<family>`
    around the launch, `mesh:<index>` before the mesh launch, `pull:<index>`
    next to the batched device_get) so every trip/probe/recovery transition
    replays identically under test.

    Hot-path contract matches the sibling: `active` is ONE plain attribute
    read and the shipped default is disarmed; `check()` takes only the leaf
    `_lock` for the countdown when armed, and raises OUTSIDE it."""

    def __init__(self):
        self.active = False  # the one hot-path read
        self._lock = threading.Lock()
        self._error = "internal"
        self._domain = "*"
        self._remaining = 0
        self.injected = 0

    def arm(self, error: str = "internal", domain: str = "*", times: int = 1):
        if error not in _DEVICE_ERROR_MESSAGES:
            raise ValueError(f"unknown device error kind [{error}] "
                             f"(want one of {DEVICE_ERROR_KINDS})")
        with self._lock:
            self._error = error
            self._domain = domain
            self._remaining = int(times)
            self.active = True
        return self

    def disarm(self):
        with self._lock:
            self.active = False
            self._remaining = 0

    def check(self, domain: str) -> None:
        """Raise the armed error if `domain` matches (decrements the budget,
        auto-disarms at zero). Call sites guard with the `active` attr read so
        the disarmed serving path pays exactly that."""
        with self._lock:
            if not self.active or self._remaining <= 0:
                return
            if not _glob_match(str(domain), self._domain):
                return
            self._remaining -= 1
            if self._remaining <= 0:
                self.active = False
            self.injected += 1
            kind = self._error
        raise make_device_error(kind)


DEVICE_FAULTS = DeviceFaults()
