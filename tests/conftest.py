"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the reference tests multi-node behavior with an
in-JVM TestCluster — SURVEY.md §4.2; we test multi-chip sharding with virtual XLA host
devices). Must be set before jax is imported anywhere.
"""

# A test process's persistent compile cache goes to a directory of its own
# (jaxenv's one rule: where the variable is set, no code sets another) — not to
# the checkout's .jax_cache, which the xdist workers and every later run would
# share. Set before jax is imported: jax reads the variable once. The workers
# inherit the controller's directory; the controller removes it at exit.
import atexit
import os
import shutil
import tempfile

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = tempfile.mkdtemp(prefix="estpu-test-jax-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
    atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

# Lock-trace sanitizer (common/locktrace.py), the runtime twin of the tpulint
# concurrency family: under ESTPU_LOCKTRACE=1 every repo-constructed
# threading.Lock/RLock records per-thread acquisition order and device pulls
# timed under a held lock; the session gate below fails the run on any
# lock-order cycle. Off by default — maybe_install() is a no-op then, so the
# recorder costs exactly nothing (same env-knob conventions as ESTPU_SANITIZE).
# Installed FIRST — before jaxenv is imported — so even module-import-time
# locks (jaxenv's _CompileCounter._lock) are constructed through the patched
# factory and participate in the order graph.
from elasticsearch_tpu.common.locktrace import TRACER, maybe_install

maybe_install()

from elasticsearch_tpu.common.jaxenv import force_cpu_platform

# the tests' eight virtual CPU devices (must precede first device use)
force_cpu_platform(n_devices=8)

# second call: now that jax is imported, the device_get timing wrapper can arm
# (the first call ran pre-jax so the threading patch covered all repo locks)
maybe_install()

# Collective-trace sanitizer (common/meshtrace.py), the runtime twin of the
# tpulint SPMD family (TPU014-016): under ESTPU_MESHTRACE=1 every shard_map
# trace records its collective launch sequence per program; the session gate
# below replays each program and fails the run on any sequence mismatch —
# the single-process rehearsal of the multi-host trace-divergence deadlock.
# Installed AFTER jax is up (it patches jax.lax collectives + shard_map).
from elasticsearch_tpu.common import meshtrace

meshtrace.maybe_install()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


# Device-heavy test modules run under the runtime sanitizer
# (common/jaxenv.sanitize): transfer-guard HARD "disallow" (the tpulint
# TPU001 baseline is empty — every hot-path pull is an explicit
# jax.device_get/.tolist() batch now, so any implicit transfer is a
# regression and raises) plus compile-event counting. Env knobs, both read
# by sanitize() itself: ESTPU_SANITIZE=log is the debugging escape hatch
# (warn instead of raise); ESTPU_COMPILE_BUDGET=<n> makes the compile count
# a hard per-test ceiling — the runtime twin of tpulint TPU001/TPU002.
_SANITIZED_MODULES = {
    "test_sparse_program",
    "test_quantized_postings",
    "test_device_aggs",
    "test_device_sort",
    "test_parallel_search",
    "test_mesh_serving",
    "test_mesh_launch",
}


@pytest.fixture(scope="session", autouse=True)
def lock_order_gate():
    """With ESTPU_LOCKTRACE=1, fail the run if the whole-session lock-order
    graph ever grew a cycle (TRACER.check raises LockOrderViolation naming
    both acquisition sites)."""
    yield
    if TRACER.enabled:
        TRACER.check()


@pytest.fixture(scope="session", autouse=True)
def collective_trace_gate():
    """With ESTPU_MESHTRACE=1, replay every mesh program the session traced
    and fail the run on any collective-sequence divergence
    (meshtrace.TRACER.check raises CollectiveTraceMismatch naming the first
    differing collective site in both traces)."""
    yield
    if meshtrace.TRACER.enabled:
        meshtrace.TRACER.replay_all()
        meshtrace.TRACER.check()


@pytest.fixture(scope="session", autouse=True)
def compile_surface_gate():
    """Runtime twin of the compile-surface manifest (tools/compile_surface.json,
    tpulint TPU018-TPU021): arm jaxenv's untagged-origin capture for the whole
    session, then assert (a) zero PACKAGE-originated untagged compile events —
    every elasticsearch_tpu/ launch site that compiled sat under a compile_tag
    scope the manifest knows — and (b) every observed family is in the
    COMPILE_FAMILIES vocabulary. Test-local eager jnp compiles have no package
    frame and are out of scope by construction (they are the tests' own, not
    serving-path, compiles)."""
    from elasticsearch_tpu.common import jaxenv

    jaxenv.record_untagged_origins(True)
    yield
    origins = jaxenv.untagged_package_origins()
    assert not origins, (
        "package-originated compile events outside every compile_tag scope "
        f"(site -> count): {origins} — wrap each launch in "
        "jaxenv.compile_tag(<family>) and regenerate the manifest with "
        "`python -m tools.tpulint --compile-surface --write`")
    observed = set(jaxenv.compile_events_by_family())
    unknown = observed - set(jaxenv.COMPILE_FAMILIES)
    assert not unknown, (
        f"compile families outside the COMPILE_FAMILIES vocabulary: {unknown}")


@pytest.fixture(autouse=True)
def jax_sanitizer(request):
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if mod not in _SANITIZED_MODULES:
        yield None
        return
    from elasticsearch_tpu.common.jaxenv import sanitize

    with sanitize() as report:
        yield report
