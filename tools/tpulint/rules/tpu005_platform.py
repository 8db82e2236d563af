"""TPU005 — platform drift: JAX platform writes outside common/jaxenv.py.

JAX picks its platform itself (the TPU where one is attached, the CPU where the
caller set JAX_PLATFORMS=cpu). A write from package code silently flips the
backend for every later import, and once jax is imported a bare
`os.environ["JAX_PLATFORMS"] = ...` does not even stick (the live jax config
must move too). jaxenv.py is the single sanctioned writer (force_cpu_platform,
the tests' virtual CPU devices); everything else must call it. This rule flags,
everywhere else in the package:

  a. `os.environ["JAX_PLATFORMS"] = ...`, `del os.environ["JAX_PLATFORMS"]`,
     `os.environ.setdefault/pop("JAX_PLATFORMS", ...)`, and
     `os.environ.update({... "JAX_PLATFORMS": ...})`
  b. `jax.config.update("jax_platforms", ...)`
  c. writes to XLA_FLAGS (device-count pinning belongs to jaxenv too)
  d. `jax.config.update("jax_compilation_cache_dir", ...)` — the persistent
     compile cache has one placement rule, jaxenv.enable_persistent_compile_cache
"""

from __future__ import annotations

import ast

from ..engine import Finding, SourceFile

RULE_ID = "TPU005"
DOC = "platform drift: JAX_PLATFORMS/jax_platforms/XLA_FLAGS writes outside jaxenv"

_ENV_KEYS = {"JAX_PLATFORMS", "XLA_FLAGS"}
_CONFIG_KEYS = {"jax_platforms", "jax_compilation_cache_dir"}


def _const_str(node: ast.AST) -> str | None:
    return node.value if isinstance(node, ast.Constant) and \
        isinstance(node.value, str) else None


def _is_os_environ(node: ast.AST) -> bool:
    return isinstance(node, ast.Attribute) and node.attr == "environ" and \
        isinstance(node.value, ast.Name) and node.value.id == "os"


def _environ_sub_key(node: ast.AST) -> str | None:
    """os.environ["KEY"] → "KEY"."""
    if isinstance(node, ast.Subscript) and _is_os_environ(node.value):
        return _const_str(node.slice)
    return None


def _flag(out, sf, node, msg):
    out.append(Finding(sf.relpath, node.lineno, RULE_ID, msg))


def run(files: list[SourceFile], project=None) -> list[Finding]:
    out: list[Finding] = []
    for sf in files:
        if not sf.platform_checked:
            continue
        for node in ast.walk(sf.tree):
            # a. subscript writes and deletes
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    key = _environ_sub_key(t)
                    if key in _ENV_KEYS:
                        _flag(out, sf, node,
                              f"os.environ[{key!r}] written outside "
                              "common/jaxenv.py — use force_cpu_platform() so "
                              "the live jax config moves with the env")
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    key = _environ_sub_key(t)
                    if key in _ENV_KEYS:
                        _flag(out, sf, node,
                              f"os.environ[{key!r}] deleted outside "
                              "common/jaxenv.py — platform drift")
            elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                f = node.func
                # a. setdefault/pop/update on os.environ
                if _is_os_environ(f.value) and f.attr in ("setdefault", "pop"):
                    if node.args and _const_str(node.args[0]) in _ENV_KEYS:
                        _flag(out, sf, node,
                              f"os.environ.{f.attr}({_const_str(node.args[0])!r}) "
                              "outside common/jaxenv.py — platform drift")
                elif _is_os_environ(f.value) and f.attr == "update":
                    for a in node.args:
                        if isinstance(a, ast.Dict) and any(
                                _const_str(k) in _ENV_KEYS for k in a.keys if k):
                            _flag(out, sf, node,
                                  "os.environ.update({..JAX platform key..}) "
                                  "outside common/jaxenv.py — platform drift")
                    for kw in node.keywords:
                        if kw.arg in _ENV_KEYS:
                            _flag(out, sf, node,
                                  f"os.environ.update({kw.arg}=...) outside "
                                  "common/jaxenv.py — platform drift")
                # b./d. jax.config.update("jax_platforms" | cache dir, ...)
                elif f.attr == "update" and isinstance(f.value, ast.Attribute) \
                        and f.value.attr == "config" and node.args \
                        and _const_str(node.args[0]) in _CONFIG_KEYS:
                    _flag(out, sf, node,
                          f"jax.config.update({_const_str(node.args[0])!r}, ...) "
                          "outside common/jaxenv.py — use force_cpu_platform() "
                          "/ enable_persistent_compile_cache()")
    return out
