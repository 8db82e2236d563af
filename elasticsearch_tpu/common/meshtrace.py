"""Runtime collective-trace sanitizer — the dynamic twin of tpulint TPU014-016.

The static SPMD rules (tools/tpulint/spmd.py) prove what a mesh program CAN
do; this module records what each trace actually DID, completing the repo's
static/runtime pairings (TPU001 <-> transfer_guard, TPU002 <-> compile
budget, TPU004/TPU011 <-> locktrace). The hazard: on a multi-host fleet every
process traces the SAME program, and if host-divergent state (wall clock, env,
unseeded RNG) steers the trace, processes enqueue DIFFERENT collective launch
sequences — the mesh deadlocks on the first mismatched collective, with no
error message, on hardware only. Under `ESTPU_MESHTRACE=1`:

- `jax.shard_map` is wrapped so each traced mesh program records its collective launch sequence:
  every patched `jax.lax` collective (psum/pmax/pmin/pmean/all_gather/
  all_to_all/ppermute/psum_scatter/axis_index) appends a
  (primitive, axis, shape, call site) entry while the program body is being
  traced. Sequences are aggregated per PROGRAM KEY — (qualname, closure-cell
  fingerprint, local arg shapes/dtypes) — so the factory pattern
  (mesh_search._mesh_score_program closes over static config; different
  configs legitimately emit different sequences) gets one node per variant
  instead of a false "divergence" between them.
- every later launch of the same key is compared against the first recorded
  sequence; any difference in the (primitive, axis, shape) triples is a
  mismatch, reported with BOTH call sites at the first divergence point.
- the session gate (tests/conftest.py) calls `TRACER.replay_all()` then
  `TRACER.check()`: replay re-traces every registered program via
  `jax.eval_shape` at teardown time — a program whose trace depends on
  wall-clock/env state diverges from its original recording exactly the way a
  second host would, so single-process CI catches the multi-host deadlock.
  check() raises CollectiveTraceMismatch naming both sites.

Overhead is exactly zero when the knob is off: `maybe_install()` returns
without importing or touching jax. When on, the cost is trace-time only —
compiled executions never re-enter the Python wrappers. Counters surface
through the existing sanitizer report (jaxenv.sanitize() attaches a snapshot
to SanitizerReport.mesh).
"""

from __future__ import annotations

import functools
import os
import sys
import threading

# the tracer's own lock must stay a REAL lock even under ESTPU_LOCKTRACE
_REAL_LOCK = threading.Lock

_REPO_MARKERS = (f"{os.sep}elasticsearch_tpu{os.sep}", f"{os.sep}tests{os.sep}")
_SELF_FILE = os.path.abspath(__file__)

COLLECTIVES = ("psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
               "ppermute", "psum_scatter", "axis_index")


class CollectiveTraceMismatch(AssertionError):
    """Two traces of one mesh program enqueued different collective
    sequences — on a multi-host fleet this is a silent SPMD deadlock. The
    message names the first differing collective site in BOTH traces."""


_REL_CACHE: dict = {}


def _rel(fn: str) -> str:
    r = _REL_CACHE.get(fn)
    if r is None:
        r = _REL_CACHE[fn] = os.path.relpath(fn)
    return r


def _call_site() -> str:
    """file:line of the first repo frame below the patched collective —
    the line inside the mesh program that launched it."""
    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename
        if fn != _SELF_FILE and (any(m in fn for m in _REPO_MARKERS)
                                 or "tpulint_fixtures" in fn):
            return f"{_rel(fn)}:{f.f_lineno}"
        f = f.f_back
    return "<external>"


def _value_tag(v, depth: int = 0) -> str:
    """Stable fingerprint for one closure cell / static argument. Containers
    recurse (bounded depth/width): a factory's static config often rides in a
    list of nested tuples (mesh_search bucket_specs), and two variants that
    fingerprint identically would false-positive as a collective-sequence
    divergence between them."""
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    # callable guard: a module cell (numpy) exposes shape/dtype as FUNCTIONS
    if shape is not None and dtype is not None and not callable(shape):
        try:
            return f"arr[{tuple(shape)}:{dtype}]"
        except TypeError:
            pass
    if isinstance(v, (int, float, bool, str, bytes, type(None))):
        return repr(v)
    if depth < 3 and isinstance(v, (list, tuple)):
        kind = "t" if isinstance(v, tuple) else "l"
        inner = ",".join(_value_tag(e, depth + 1) for e in v[:16])
        return f"{kind}({inner}{',...' if len(v) > 16 else ''})"
    if depth < 3 and isinstance(v, dict):
        items = sorted(v.items(), key=lambda kv: repr(kv[0]))[:16]
        inner = ",".join(f"{k!r}:{_value_tag(e, depth + 1)}" for k, e in items)
        return f"d({inner}{',...' if len(v) > 16 else ''})"
    if depth < 3 and getattr(v, "__closure__", None):
        # a program wrapped by another closure (mesh_search._on_plane around
        # _mesh_score_program's): the wrapped program's static config is what
        # tells the variants apart
        inner = ",".join(_closure_fp(v, depth + 1))
        return f"fn:{getattr(v, '__qualname__', '?')}({inner})"
    return type(v).__name__


def _closure_fp(fn, depth: int = 0) -> tuple:
    cells = getattr(fn, "__closure__", None) or ()
    out = []
    for c in cells:
        try:
            out.append(_value_tag(c.cell_contents, depth))
        except ValueError:  # empty cell
            out.append("<empty>")
    return tuple(out)


def _args_fp(args, kwargs) -> tuple:
    out = [_value_tag(a) for a in args]
    out.extend(f"{k}={_value_tag(v)}" for k, v in sorted(kwargs.items()))
    return tuple(out)


def _program_key(fn, args, kwargs) -> tuple:
    return (getattr(fn, "__qualname__", repr(fn)), _closure_fp(fn),
            _args_fp(args, kwargs))


def _axis_of(name: str, args, kwargs):
    if "axis_name" in kwargs:
        return str(kwargs["axis_name"])
    idx = 0 if name == "axis_index" else 1
    if len(args) > idx:
        return str(args[idx])
    return "?"


class MeshTracer:
    """Process-wide recorder: per-thread active-program stacks, the
    per-program first-witness sequences, and the replay registry."""

    def __init__(self):
        self._glock = _REAL_LOCK()
        self._tls = threading.local()
        self.enabled = False
        # program key -> first recorded sequence of (prim, axis, shape, site)
        self.programs: dict = {}
        # replay registry: outer key -> (f, sm_args, sm_kwargs, arg specs)
        self.replayable: dict = {}
        self.mismatches: list = []
        self.counters = {
            "programs": 0,
            "launches": 0,
            "collectives": 0,
            "mismatches": 0,
            "replayed": 0,
            "replay_errors": 0,
        }

    # -- per-thread active-program stack --------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def push_program(self) -> list:
        seq: list = []
        self._stack().append(seq)
        return seq

    def pop_program(self) -> list:
        return self._stack().pop()

    def on_collective(self, prim: str, axis, shape) -> None:
        st = self._stack()
        if st:
            st[-1].append((prim, axis, shape, _call_site()))

    # -- aggregation ----------------------------------------------------------
    def on_program(self, key: tuple, seq: list) -> None:
        with self._glock:
            self.counters["launches"] += 1
            self.counters["collectives"] += len(seq)
            prev = self.programs.get(key)
            if prev is None:
                self.programs[key] = seq
                self.counters["programs"] += 1
                return
            if [e[:3] for e in prev] != [e[:3] for e in seq]:
                self.counters["mismatches"] += 1
                self.mismatches.append(self._describe(key, prev, seq))

    @staticmethod
    def _describe(key: tuple, prev: list, seq: list) -> dict:
        i = 0
        while i < len(prev) and i < len(seq) and prev[i][:3] == seq[i][:3]:
            i += 1

        def ent(s, j):
            if j < len(s):
                prim, axis, shape, site = s[j]
                return {"prim": f"lax.{prim}", "axis": axis,
                        "shape": list(shape), "site": site}
            return {"prim": "<end of sequence>", "axis": "", "shape": [],
                    "site": s[-1][3] if s else "<none>"}

        return {"program": key[0], "index": i,
                "first": ent(prev, i), "second": ent(seq, i)}

    # -- replay ---------------------------------------------------------------
    def register_replay(self, key: tuple, f, sm_args: tuple, sm_kwargs: dict,
                        specs: tuple) -> None:
        with self._glock:
            if key not in self.replayable:
                self.replayable[key] = (f, sm_args, sm_kwargs, specs)

    def replay_all(self) -> None:
        """Re-trace every registered mesh program via jax.eval_shape. A
        program whose trace rides host-divergent state (clock/env) diverges
        from its original recording here exactly as it would on another host;
        the divergence lands in self.mismatches for check()."""
        with self._glock:
            entries = list(self.replayable.values())
        if not entries:
            return
        import jax
        for f, sm_args, sm_kwargs, specs in entries:
            try:
                wrapped = _REAL_SHARD_MAP(_shim(f), *sm_args, **sm_kwargs)
                jax.eval_shape(wrapped, *specs)
                with self._glock:
                    self.counters["replayed"] += 1
            except Exception:
                with self._glock:
                    self.counters["replay_errors"] += 1

    # -- the gate -------------------------------------------------------------
    def check(self) -> None:
        with self._glock:
            mms = list(self.mismatches)
        if mms:
            lines = []
            for m in mms:
                a, b = m["first"], m["second"]
                lines.append(
                    f"  program `{m['program']}` diverges at collective "
                    f"#{m['index']}:\n"
                    f"    one trace launched {a['prim']}(axis={a['axis']!r}, "
                    f"shape={tuple(a['shape'])}) at {a['site']}\n"
                    f"    another trace launched {b['prim']}(axis="
                    f"{b['axis']!r}, shape={tuple(b['shape'])}) at "
                    f"{b['site']}")
            raise CollectiveTraceMismatch(
                "collective launch sequences diverged between traces of the "
                "same mesh program — on a multi-host fleet every process "
                "must enqueue the identical sequence or the mesh deadlocks:\n"
                + "\n".join(lines) +
                "\nhoist host-dependent branches out of the device program "
                "(tpulint TPU014/TPU016 are the static twins of this check)")

    def snapshot(self) -> dict:
        with self._glock:
            return {**self.counters, "mismatches_detail": list(self.mismatches)}


TRACER = MeshTracer()

_REAL_SHARD_MAP = None  # the unpatched shard_map, set by install()


def _shim(f):
    """Wrap the user's mesh program so its trace records a collective
    sequence under the program's key (computed from the per-shard view)."""

    @functools.wraps(f)
    def recorded(*args, **kwargs):
        key = _program_key(f, args, kwargs)
        TRACER.push_program()
        try:
            out = f(*args, **kwargs)
        finally:
            seq = TRACER.pop_program()
        TRACER.on_program(key, seq)
        return out

    return recorded


def _spec_of(a):
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        import jax
        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    return a


def _wrap_shard_map(real):
    @functools.wraps(real)
    def shard_map(f, *sm_args, **sm_kwargs):
        mapped = real(_shim(f), *sm_args, **sm_kwargs)

        @functools.wraps(f)
        def dispatch(*args, **kwargs):
            # register for session-end replay once per (program, arg-shape)
            # variant; under jit the args are tracers, whose shape/dtype is
            # exactly what eval_shape needs — no device traffic here
            specs = tuple(_spec_of(a) for a in args)
            key = (_program_key(f, (), {}), _args_fp(specs, {}))
            TRACER.register_replay(key, f, sm_args, sm_kwargs, specs)
            return mapped(*args, **kwargs)

        return dispatch

    shard_map._estpu_meshtrace = True
    return shard_map


def _wrap_collective(lax_mod, name: str) -> None:
    real = getattr(lax_mod, name, None)
    if real is None or getattr(real, "_estpu_meshtrace", False):
        return

    @functools.wraps(real)
    def collective(*args, **kwargs):
        TRACER.on_collective(
            name, _axis_of(name, args, kwargs),
            tuple(getattr(args[0], "shape", ())) if args else ())
        return real(*args, **kwargs)

    collective._estpu_meshtrace = True
    setattr(lax_mod, name, collective)


def install() -> MeshTracer:
    """Arm the tracer (idempotent). Prefer maybe_install() — the env knob.
    Must run after jax is importable; patches jax.lax collectives plus
    jax.shard_map, the one shard_map entry point the package uses."""
    global _REAL_SHARD_MAP
    if TRACER.enabled:
        return TRACER
    import jax

    for name in COLLECTIVES:
        _wrap_collective(jax.lax, name)

    real = jax.shard_map
    if not getattr(real, "_estpu_meshtrace", False):
        _REAL_SHARD_MAP = real
        jax.shard_map = _wrap_shard_map(real)
    TRACER.enabled = True
    return TRACER


def maybe_install() -> MeshTracer | None:
    """Install iff ESTPU_MESHTRACE=1 (same env-knob conventions as
    ESTPU_SANITIZE / ESTPU_LOCKTRACE). Zero cost when off: jax is neither
    imported nor touched."""
    if os.environ.get("ESTPU_MESHTRACE", "") not in ("1", "on", "true"):
        return None
    return install()
