"""Two checks of the tree against itself: the package reads no environment
variable outside a list written here (a user-set switch between two kernels
is sent back: ROADMAP aim 3), and the documents a reader is sent to name no
file that is not in the checkout."""

import ast
import functools
import os
import re

import pytest

from tools.tpulint.engine import discover_default_paths, parse_file

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tracers, the settings file, and what common/jaxenv.py (the one sanctioned
# reader and writer of JAX's own variables) reads
ALLOWED_ENV = {
    "ESTPU_TRACE", "ESTPU_SETTINGS", "ESTPU_LOCKTRACE", "ESTPU_LOCKTRACE_HELD_MS",
    "ESTPU_MESHTRACE"}
JAXENV_ONLY = {
    "JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR", "ESTPU_SANITIZE",
    "ESTPU_COMPILE_BUDGET"}


def _is_environ(node) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            and isinstance(node.value, ast.Name) and node.value.id == "os")


def _env_keys(tree):
    """(key or None, line) for every use of os.environ / os.getenv: None where
    the key is not a literal the scan can read."""
    parents = {c: p for p in ast.walk(tree) for c in ast.iter_child_nodes(p)}

    def literal(args):
        ok = args and isinstance(args[0], ast.Constant)
        return args[0].value if ok else None

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "os":
            for a in node.names:
                if a.name in ("environ", "getenv", "putenv"):
                    yield None, node.lineno
        elif (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
              and node.func.attr == "getenv"
              and isinstance(node.func.value, ast.Name)
              and node.func.value.id == "os"):
            yield literal(node.args), node.lineno
        elif _is_environ(node):
            up = parents[node]
            if isinstance(up, ast.Subscript) and isinstance(up.slice, ast.Constant):
                yield up.slice.value, node.lineno
            elif (isinstance(up, ast.Attribute)
                  and isinstance(parents[up], ast.Call)
                  and up.attr in ("get", "pop", "setdefault")):
                yield literal(parents[up].args), node.lineno
            elif (isinstance(up, ast.Compare) and len(up.ops) == 1
                  and isinstance(up.ops[0], (ast.In, ast.NotIn))
                  and isinstance(up.left, ast.Constant)):
                yield up.left.value, node.lineno
            else:
                yield None, node.lineno


def test_package_reads_only_the_listed_environment_variables():
    found, offenders = set(), []
    for path in discover_default_paths():  # every .py of the package
        src = parse_file(path)
        assert src is not None, path
        allowed = ALLOWED_ENV | (
            JAXENV_ONLY if src.relpath == "elasticsearch_tpu/common/jaxenv.py"
            else set())
        for key, line in _env_keys(src.tree):
            found.add(key)
            if key not in allowed:
                offenders.append(f"{src.relpath}:{line}: {key!r}")
    assert not offenders, offenders
    # the scan sees what it is meant to see
    assert ALLOWED_ENV | JAXENV_ONLY <= found


DOCUMENTS = ["README.md", "ARCHITECTURE.md", "BASELINE.md",
             os.path.join(".claude", "skills", "verify", "SKILL.md")]
_SKIP_DIRS = {".git", "__pycache__", "chiprun_out", ".clean_copy", ".bench_run",
              ".jax_cache", ".pytest_cache"}
# files a run makes, named by the documents as what to look for: the warm
# manifest and the compile-cache directory's index in a node's data path, the
# chip tool's record of its last call
_MADE_AT_RUN_TIME = {"compile_manifest.json", ".last_call.json"}
_PATH = re.compile(r"[\w./-]*[\w-]\.(?:py|json)\b")


@functools.cache
def _checkout_files():
    out = []
    for dirpath, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        out.extend(os.path.relpath(os.path.join(dirpath, n), ROOT).replace(
            os.sep, "/") for n in names)
    return out


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_only_files_that_exist(document):
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        text = f.read()
    files = _checkout_files()
    named = set()
    # inline spans, and every line of a fenced block
    prose, spans = text.split("```")[::2], []
    for block in text.split("```")[1::2]:
        spans.extend(block.splitlines())
    for part in prose:
        spans.extend(re.findall(r"`([^`\n]+)`", part))
    for span in spans:
        for m in _PATH.finditer(span):
            # the tail of a pattern (`tools/*_x.py`) or a path under another
            # root (`/tmp/x.py`, `<dir>/x.json`) is no claim about this checkout
            if span[:m.start()].endswith("*") or m.group().startswith("/"):
                continue
            named.add(m.group().removeprefix("./"))
    assert named, document
    missing = sorted(
        p for p in named
        if os.path.basename(p) not in _MADE_AT_RUN_TIME
        and not any(f == p or f.endswith("/" + p) for f in files))
    assert not missing, (document, missing)
