"""Runtime collective-trace sanitizer (common/meshtrace.py).

The dynamic twin of the tpulint SPMD family (TPU014-TPU016): under
ESTPU_MESHTRACE=1 every shard_map trace records its collective launch
sequence per program, and the conftest session gate replays each program and
fails on any cross-trace divergence — the single-process rehearsal of the
multi-host SPMD deadlock (every process must enqueue the identical collective
sequence or the mesh hangs on hardware with no error). Covered here:

- the recorder costs exactly ZERO when the env knob is off (jax.lax
  collectives and shard_map are the pristine functions, no wrapper anywhere);
- a program whose trace branches on host-divergent state (the seeded
  ESTPU_FAKE_HOST env read below — exactly what TPU014 flags statically)
  fails the gate with a report naming the first differing collective site in
  BOTH traces;
- a divergence-free program traced repeatedly (and replayed) stays clean;
- a warmed mesh-serving loop (build_sharded_index + MeshSearchExecutor over
  a 2-shard device mesh) records real collective traffic with no sequence
  mismatch and 0 recompiles under the hard transfer guard, and the replay
  leg re-traces it cleanly.

Subprocesses are used wherever the tracer must be armed: installing it
patches jax.lax/shard_map process-wide, which must never leak into the rest
of the suite.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SELF = os.path.abspath(__file__)


def _site_line(tag: str) -> int:
    with open(SELF, encoding="utf-8") as f:
        for i, ln in enumerate(f.read().splitlines(), 1):
            if f"# {tag}" in ln:
                return i
    raise AssertionError(f"no line marked # {tag}")


def _run(mode, env_extra=None, timeout=300):
    env = {**os.environ}
    env.pop("ESTPU_MESHTRACE", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-m", "tests.test_meshtrace", mode],
                          capture_output=True, text=True, cwd=REPO,
                          timeout=timeout, env=env)


# ---------------------------------------------------------------------------
# env knob off: zero overhead, nothing patched
# ---------------------------------------------------------------------------


def test_overhead_zero_when_knob_off():
    """Importing meshtrace must patch NOTHING by itself; with the knob unset,
    maybe_install is a no-op and jax.lax / shard_map stay pristine. (When the
    suite itself runs under ESTPU_MESHTRACE=1 — the CI mesh leg — the tracer
    is armed instead and the session gate replays + checks the programs.)"""
    import jax

    from elasticsearch_tpu.common import meshtrace

    if os.environ.get("ESTPU_MESHTRACE", "") in ("1", "on", "true"):
        assert meshtrace.TRACER.enabled
        assert getattr(jax.lax.psum, "_estpu_meshtrace", False)
        return
    assert meshtrace.maybe_install() is None
    assert not meshtrace.TRACER.enabled
    for name in meshtrace.COLLECTIVES:
        fn = getattr(jax.lax, name, None)
        assert fn is None or not getattr(fn, "_estpu_meshtrace", False), name
    assert not getattr(jax.shard_map, "_estpu_meshtrace", False)


# ---------------------------------------------------------------------------
# the divergent program under the tracer
# ---------------------------------------------------------------------------


def test_divergent_traces_fail_naming_both_sites():
    """The driver traces ONE program twice with different ESTPU_FAKE_HOST
    values — the single-process stand-in for two fleet processes tracing the
    same program. The branch steers the collective order, so the gate must
    fail with a CollectiveTraceMismatch naming the first differing collective
    site of BOTH traces by file:line."""
    res = _run("divergent", {"ESTPU_MESHTRACE": "1"})
    assert res.returncode != 0, res.stdout + res.stderr
    assert "CollectiveTraceMismatch" in res.stderr
    assert "diverge" in res.stderr
    for tag in ("SITE_A", "SITE_B"):
        line_no = _site_line(tag)
        assert f"test_meshtrace.py:{line_no}" in res.stderr, \
            (tag, line_no, res.stderr)


def test_divergence_free_traces_pass_and_replay_clean():
    res = _run("uniform", {"ESTPU_MESHTRACE": "1"})
    assert res.returncode == 0, res.stdout + res.stderr
    snap = json.loads(res.stdout.splitlines()[-1])
    assert snap["programs"] == 1
    assert snap["launches"] >= 3  # two traces + at least one replay
    assert snap["replayed"] >= 1
    assert snap["replay_errors"] == 0
    assert snap["mismatches"] == 0


def test_driver_runs_clean_without_the_knob():
    res = _run("uniform")
    assert res.returncode == 0, res.stdout + res.stderr
    snap = json.loads(res.stdout.splitlines()[-1])
    assert snap == {}  # tracer off: nothing recorded, nothing patched


# ---------------------------------------------------------------------------
# warmed mesh serving: real collective traffic, no mismatch, 0 recompiles
# ---------------------------------------------------------------------------


def test_warmed_mesh_serving_records_clean_sequences():
    """The real SPMD serving path (2-shard mesh, all_gather top-k)
    with the tracer armed: the warmed loop must run with 0 recompiles under
    the hard transfer guard, record real collective launches, show ZERO
    sequence mismatches, and replay cleanly at the end — the invariant the
    ESTPU_MESHTRACE=1 CI leg holds over the whole mesh subset."""
    res = _run("serving", {"ESTPU_MESHTRACE": "1"}, timeout=420)
    assert res.returncode == 0, res.stdout + res.stderr
    snap = json.loads(res.stdout.splitlines()[-1])
    assert snap["launches"] > 0
    assert snap["collectives"] > 0
    assert snap["mismatches"] == 0, snap
    assert snap["replayed"] > 0
    assert snap["replay_errors"] == 0, snap


# ---------------------------------------------------------------------------
# subprocess drivers
# ---------------------------------------------------------------------------


def _mesh_and_relax():
    import jax
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()), ("d",))
    return shard_map, mesh, {"check_vma": False}


def _divergent_program(x):
    import jax

    if os.environ.get("ESTPU_FAKE_HOST") == "0":
        s = jax.lax.psum(x, "d")  # SITE_A
        return jax.lax.all_gather(s, "d")
    g = jax.lax.all_gather(x, "d")  # SITE_B
    return jax.lax.psum(g, "d")


def _uniform_program(x):
    import jax

    s = jax.lax.psum(x, "d")
    return jax.lax.all_gather(s, "d")


def _trace_twice(program, fake_hosts) -> None:
    """Trace `program` once per entry in fake_hosts (fresh shard_map wrapper
    each time — two processes never share a trace cache), then replay and
    run the gate exactly like the conftest session fixture."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from elasticsearch_tpu.common import meshtrace

    shard_map, mesh, relax = _mesh_and_relax()
    for host in fake_hosts:
        os.environ["ESTPU_FAKE_HOST"] = host
        f = shard_map(program, mesh=mesh, in_specs=(P("d"),),
                      out_specs=P(None, "d"), **relax)
        jax.eval_shape(f, jax.ShapeDtypeStruct((len(mesh.devices), 2),
                                               jnp.float32))
    if meshtrace.TRACER.enabled:
        meshtrace.TRACER.replay_all()
        meshtrace.TRACER.check()
    print(json.dumps(meshtrace.TRACER.snapshot()
                     if meshtrace.TRACER.enabled else {}))


def _serving_driver() -> None:
    import tempfile

    import numpy as np

    from elasticsearch_tpu.common import meshtrace
    from elasticsearch_tpu.common.jaxenv import sanitize
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index import Engine
    from elasticsearch_tpu.mapper import MapperService
    from elasticsearch_tpu.search import ShardContext, parse_query
    from elasticsearch_tpu.search.execute import lower_flat
    from elasticsearch_tpu.search.similarity import SimilarityService

    assert meshtrace.TRACER.enabled, "driver requires ESTPU_MESHTRACE=1"

    import jax
    from jax.sharding import Mesh

    from elasticsearch_tpu.parallel.mesh_search import (
        MeshSearchExecutor,
        build_sharded_index,
    )

    words = ["quick", "brown", "fox", "lazy", "dog", "summer", "red", "bear"]
    settings = Settings.from_flat({})
    svc = MapperService(settings)
    with tempfile.TemporaryDirectory() as td:
        searchers = []
        engines = []
        for si in range(2):
            e = Engine(os.path.join(td, f"shard{si}"), svc)
            for i in range(24):
                e.index("doc", str(i), {
                    "body": f"{words[(si + i) % 8]} {words[(si + i + 3) % 8]}"})
            e.refresh()
            engines.append(e)
            searchers.append(e.acquire_searcher())
        try:
            mesh = Mesh(np.array(jax.devices()[:2]), ("shards",))
            sidx = build_sharded_index(searchers, fields=["body"], mesh=mesh)
            ex = MeshSearchExecutor(sidx, mesh, similarity="BM25")
            ctx = ShardContext(searchers[0], svc,
                               SimilarityService(settings, mapper_service=svc))
            plan = lower_flat(parse_query({"match": {"body": "quick brown"}}),
                              ctx)
            warm = ex.search([plan], k=5)  # first run compiles + traces freely
            with sanitize(max_compiles=0, transfers="disallow") as rep:
                for _ in range(3):
                    again = ex.search([plan], k=5)  # the warmed serving loop
            assert rep.compiles == 0, rep.compile_events
            assert rep.mesh is not None and rep.mesh["mismatches"] == 0, rep.mesh
            np.testing.assert_array_equal(again.doc, warm.doc)
        finally:
            for e in engines:
                e.close()

    meshtrace.TRACER.replay_all()
    meshtrace.TRACER.check()  # any sequence divergence fails the driver
    snap = meshtrace.TRACER.snapshot()
    assert snap["launches"] > 0 and snap["collectives"] > 0, snap
    print(json.dumps(snap))


def _main(mode: str) -> int:
    from elasticsearch_tpu.common.jaxenv import force_cpu_platform

    force_cpu_platform(n_devices=4 if mode != "serving" else 2)

    from elasticsearch_tpu.common import meshtrace

    meshtrace.maybe_install()
    if mode == "divergent":
        _trace_twice(_divergent_program, ("0", "1"))
    elif mode == "uniform":
        _trace_twice(_uniform_program, ("0", "0"))
    elif mode == "serving":
        _serving_driver()
    else:
        raise SystemExit(f"unknown mode {mode!r}")
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1]))
