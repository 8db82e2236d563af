"""Compile-surface manifest + runtime untagged-compile gate self-tests.

Tier-1 runs this, so CI pins the whole PR-17 contract with no new
infrastructure:

- COVERAGE: an independent AST sweep of the package (not the analyzer's own
  entry enumeration) must agree with tools/compile_surface.json exactly — a
  new jit/shard_map/pallas_call ctor anywhere in elasticsearch_tpu/ that the
  manifest misses fails here;
- DETERMINISM: two consecutive builds are byte-identical, with the parse
  cache cold or hot, and both match the committed file;
- the CLI exit-code contract for `--compile-surface` (0 in-sync / 1 drift /
  2 usage), documented in tools/tpulint/__main__.py;
- the jaxenv runtime half: `_package_origin` frame attribution, the
  `record_untagged_origins` / `untagged_package_origins` accessors, and the
  COMPILE_FAMILIES vocabulary the manifest's `runtime_families` mirrors.
"""

import ast
import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from elasticsearch_tpu.common import jaxenv  # noqa: E402
from tools.tpulint import compilesurface as cs  # noqa: E402
from tools.tpulint.engine import clear_parse_cache  # noqa: E402

PKG = os.path.join(REPO, "elasticsearch_tpu")

# the same ctor vocabulary compilesurface.py recognizes — restated here so
# this sweep stays independent of the analyzer's own entry enumeration
_CTOR_NAMES = {"jit", "pjit", "shard_map", "xmap", "pallas_call"}


def _last_name(node):
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _sweep_package_entry_points() -> set:
    """(relpath, line) of every executable-ctor call site in the package,
    found by a plain AST walk — no shared code with the analyzer beyond the
    ctor-name vocabulary."""
    found = set()
    for dirpath, _dirs, names in os.walk(PKG):
        for name in sorted(names):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, REPO).replace(os.sep, "/")
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) \
                        and _last_name(node.func) in _CTOR_NAMES:
                    found.add((rel, node.lineno))
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        if _last_name(dec) in _CTOR_NAMES or (
                                isinstance(dec, ast.Call)
                                and any(_last_name(a) in _CTOR_NAMES
                                        for a in dec.args)):
                            found.add((rel, node.lineno))
    return found


def _committed() -> dict:
    with open(cs.MANIFEST_PATH, encoding="utf-8") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# coverage: the manifest IS the package's compile surface
# ---------------------------------------------------------------------------


def test_manifest_covers_every_entry_point():
    swept = _sweep_package_entry_points()
    assert swept, "package sweep found no entry points — sweep is broken"
    listed = {(r["file"], r["line"]) for r in _committed()["entry_points"]}
    assert swept == listed, (
        f"manifest/package disagree — missing from manifest: "
        f"{sorted(swept - listed)}; stale in manifest: "
        f"{sorted(listed - swept)}; regenerate with "
        "`python -m tools.tpulint --compile-surface --write`")


def test_every_entry_point_has_a_family():
    man = _committed()
    untagged = [r for r in man["entry_points"] if not r["families"]]
    assert not untagged, [f"{r['file']}:{r['line']}" for r in untagged]
    vocab = set(man["runtime_families"])
    for r in man["entry_points"]:
        assert set(r["families"]) <= vocab, (r["qualname"], r["families"])
        assert "untagged" not in r["families"], r["qualname"]


def test_runtime_vocabulary_matches_jaxenv():
    man = _committed()
    assert set(man["runtime_families"]) == set(jaxenv.COMPILE_FAMILIES)
    assert "untagged" in man["runtime_families"]


# ---------------------------------------------------------------------------
# determinism: committed == rebuilt, cold or hot parse cache
# ---------------------------------------------------------------------------


def test_manifest_deterministic_and_in_sync():
    clear_parse_cache()
    cold = cs.canonical_json(cs.build_manifest())
    hot = cs.canonical_json(cs.build_manifest())
    assert cold == hot, "parse-cache hot/cold builds differ"
    again = cs.canonical_json(cs.build_manifest())
    assert hot == again, "two consecutive builds differ"
    assert cs.load_committed() == cold, (
        "tools/compile_surface.json is stale — regenerate with "
        "`python -m tools.tpulint --compile-surface --write`")


# ---------------------------------------------------------------------------
# CLI exit-code contract
# ---------------------------------------------------------------------------


def _cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "tools.tpulint", *argv],
        cwd=REPO, capture_output=True, text=True)


def test_cli_in_sync_exits_zero():
    p = _cli("--compile-surface")
    assert p.returncode == 0, p.stderr
    assert "in sync" in p.stderr


def test_cli_json_prints_canonical_manifest():
    p = _cli("--compile-surface", "--json")
    assert p.returncode == 0, p.stderr
    assert p.stdout == cs.load_committed()
    assert json.loads(p.stdout)["version"] == 1


def test_cli_drift_exits_one():
    with open(cs.MANIFEST_PATH, encoding="utf-8") as f:
        saved = f.read()
    try:
        with open(cs.MANIFEST_PATH, "w", encoding="utf-8") as f:
            f.write(saved.replace('"version": 1', '"version": 0'))
        p = _cli("--compile-surface")
        assert p.returncode == 1, (p.returncode, p.stderr)
        assert "DRIFT" in p.stderr
    finally:
        with open(cs.MANIFEST_PATH, "w", encoding="utf-8") as f:
            f.write(saved)


def test_cli_usage_errors_exit_two():
    assert _cli("--write").returncode == 2
    assert _cli("--compile-surface", "--check").returncode == 2
    assert _cli("--compile-surface", "elasticsearch_tpu").returncode == 2
    assert _cli("--compile-surface", "--update-baseline").returncode == 2


# ---------------------------------------------------------------------------
# the runtime half: package-origin attribution for untagged compiles
# ---------------------------------------------------------------------------


def _fake_package_fn(body: str, relname: str):
    """Compile `body` (a function named probe) under a filename inside a
    fictitious elasticsearch_tpu/ tree, so its frames read as package frames
    to jaxenv._package_origin."""
    path = os.path.join(os.sep + "nonexistent", "elasticsearch_tpu", relname)
    ns: dict = {}
    exec(compile(body, path, "exec"), ns)
    return ns["probe"]


def test_package_origin_sees_package_frames_only():
    # a test frame has no elasticsearch_tpu/ path component -> None
    assert jaxenv._package_origin() is None
    probe = _fake_package_fn(
        "from elasticsearch_tpu.common import jaxenv\n"
        "def probe():\n"
        "    return jaxenv._package_origin()\n",
        os.path.join("ops", "fake_probe.py"))
    assert probe() == "elasticsearch_tpu/ops/fake_probe.py:3"


def test_untagged_package_compile_is_attributed_and_capped():
    """An eager jnp launch from a (fake) package frame, outside every
    compile_tag scope, lands in untagged_package_origins under its
    package-relative site; a tagged launch does not. White-box cleanup keeps
    the session-scoped conftest gate green."""
    probe = _fake_package_fn(
        "import jax.numpy as jnp\n"
        "def probe(n, tag):\n"
        "    from elasticsearch_tpu.common.jaxenv import compile_tag\n"
        "    if tag is None:\n"
        "        return jnp.arange(n, dtype=jnp.float32) * 3.0\n"
        "    with compile_tag(tag):\n"
        "        return jnp.arange(n, dtype=jnp.float32) * 3.0\n",
        os.path.join("ops", "fake_untagged.py"))
    jaxenv.record_untagged_origins(True)
    before = jaxenv.untagged_package_origins()
    try:
        probe(733, None)  # unique shape: forces a fresh executable
        after = jaxenv.untagged_package_origins()
        new = {k: v for k, v in after.items() if k not in before}
        assert any(k.startswith("elasticsearch_tpu/ops/fake_untagged.py:")
                   for k in new), (before, after)
        probe(737, "pack")  # tagged: attributed to the family, no origin
        after2 = jaxenv.untagged_package_origins()
        assert {k: v for k, v in after2.items() if k not in after} == {}
        assert jaxenv.compile_events_by_family().get("pack", 0) >= 1
    finally:
        # scrub the fabricated origins so the session gate stays meaningful
        with jaxenv._counter._lock:
            for k in list(jaxenv._counter.untagged_origins):
                if k.startswith("elasticsearch_tpu/ops/fake_untagged.py:"):
                    del jaxenv._counter.untagged_origins[k]


def test_origin_dict_is_capped():
    assert jaxenv._ORIGIN_CAP == 64
    # the recording branch refuses NEW keys at the cap but keeps counting
    # existing ones — sanity-check the guard expression directly
    d = {f"elasticsearch_tpu/x.py:{i}": 1 for i in range(jaxenv._ORIGIN_CAP)}
    assert not ("elasticsearch_tpu/y.py:1" in d
                or len(d) < jaxenv._ORIGIN_CAP)
    assert ("elasticsearch_tpu/x.py:0" in d
            or len(d) < jaxenv._ORIGIN_CAP)


def test_scalar_f32_idiom_is_committed():
    """The TPU021 fix idiom: jax.device_put(np.float32(x)) produces a
    committed float32, not a weak-typed scalar — the dtype family every
    call site of a shared executable should agree on."""
    import jax

    v = jax.device_put(np.float32(0.5))
    assert v.dtype == np.float32
    assert not getattr(v, "weak_type", False)


def test_every_launch_site_lowers_under_its_own_name():
    """Each scoring launch site compiles a program named after it
    (`jit_estpu_<site>[_<variant>]`): the device trace's `XLA Modules` line
    tells the sparse launch from the dense overflow, the filtered family from
    the aggregations. None is `jit_wrapper` any more."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import scoring

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    nb, dpad, m, q, f = 16, 256, 32, 2, 1
    dense = (s((nb, 128), jnp.int32), s((nb, 128), jnp.float32),
             s((4, dpad), jnp.uint8),  # head rows
             s((dpad,), jnp.bool_), s((f, dpad), jnp.float32),  # doc table
             # the launch's one operand plane (TermBatch.plane: tri | qplane |
             # head), then M, a literal
             s((6 * m + q * (2 + 2) + 5 * q * scoring.HEAD_SLOTS,), jnp.int32),
             m)
    with_scalars = (*dense[:5], s((dense[5].shape[0] + 3,), jnp.int32), m)
    sparse = (s((nb, 128), jnp.int32), s((nb, 128), jnp.uint8),
              s((nb, 128), jnp.uint8), s((f, 256), jnp.float32),
              s((f,), jnp.int32),
              # SparseBatch.slots / .qplane
              s((5, 8, 8), jnp.int32), s((8, 2 + 2), jnp.int32))
    no_aggs = (s((0, 5, dpad), jnp.float32), s((0, 0, dpad), jnp.int32), (),
               s((q, dpad), jnp.bool_))
    term = s((4,), jnp.int32)
    plane = tuple(s((4, 128), d) for d in (jnp.int32, jnp.uint8, jnp.uint8))
    sites = [
        (scoring._get_compiled(q, 10, dpad, True), dense),
        (scoring._get_compiled(q, 10, dpad, False), dense),
        (scoring._get_fs_compiled("rows", q, 10, dpad, bmode="multiply",
                                  use_min_score=False, no_functions=False),
         # max_boost, boost and min_score ride the plane's end
         with_scalars + (s((1, 1), jnp.bool_), s((dpad,), jnp.float32),
                         s((dpad,), jnp.bool_))),
        (scoring._get_sorted_compiled(q, 10, dpad, False),
         dense + (s((1, 1), jnp.bool_), s((dpad,), jnp.float32))),
        (scoring._get_agg_compiled(q, 10, dpad, 0), dense + no_aggs),
        (scoring._get_agg_compiled(q, 10, dpad, 0, True), dense + no_aggs),
        (scoring._get_sparse_compiled(8, 8, 10, dpad, 1, True, False, 2),
         sparse),
        (scoring._get_doc_table_compiled(),
         (s((f, dpad), jnp.uint8), s((f, 256), jnp.float32))),
        (scoring._get_concat_compiled(dpad, "u8"),
         (term, term, s((2, 4), jnp.int32), s((1, 4), jnp.int32),
          s((1,), jnp.int32), s((1,), jnp.int32),
          (plane[0],), (plane[1],), (plane[2],))),
    ]
    names = [fn.lower(*args).as_text().split("@", 1)[1].split(" ", 1)[0]
             for fn, args in sites]
    assert names == [
        "jit_estpu_scoring_dense_simple", "jit_estpu_scoring_dense_bool",
        "jit_estpu_scoring_fs_rows", "jit_estpu_scoring_sorted",
        "jit_estpu_scoring_aggs", "jit_estpu_scoring_aggs_filtered",
        "jit_estpu_scoring_sparse", "jit_estpu_scoring_doc_table",
        "jit_estpu_scoring_concat"], names
    # the stages inside them carry names too (metadata only: no operation is
    # added, removed or reordered by a named_scope)
    text = sites[6][0].lower(*sparse).as_text(debug_info=True)
    for scope in ("gather_decode", "sort_by_doc", "segment_sum",
                  "match_coord", "top_k"):
        assert scope in text, scope
    text = sites[1][0].lower(*dense).as_text(debug_info=True)
    for scope in ("head_rows", "gather_decode", "scatter_add",
                  "match_coord", "top_k"):
        assert scope in text, scope
