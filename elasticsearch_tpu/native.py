"""Loader for the C extension (native/estpu_native.c) with transparent fallback.

Tries, in order: an already-built .so on sys.path, building via native/build.py (gcc),
else None — callers keep their pure-Python implementations (the framework never
hard-requires a compiler at runtime)."""

from __future__ import annotations

import os
import sys

_NATIVE = None
_TRIED = False


def get_native():
    global _NATIVE, _TRIED
    if _TRIED:
        return _NATIVE
    _TRIED = True
    native_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                              "native")
    if native_dir not in sys.path:
        sys.path.insert(0, native_dir)
    try:
        import estpu_native  # type: ignore

        _NATIVE = estpu_native
        return _NATIVE
    except ImportError:
        pass
    try:
        sys.path.insert(0, native_dir)
        from importlib import import_module

        build = import_module("build")
        if hasattr(build, "build") and build.__file__ and \
                os.path.dirname(build.__file__) == native_dir:
            if build.build(verbose=False):
                import estpu_native  # type: ignore

                _NATIVE = estpu_native
    except Exception:  # noqa: BLE001 — fall back silently
        _NATIVE = None
    finally:
        # avoid shadowing other modules named "build"
        sys.modules.pop("build", None)
    return _NATIVE


def implementation() -> str:
    """Which implementation serves the native entry points in this process
    (nodes stats `runtime.native`): the C extension, the pure-Python fallbacks,
    or "not_loaded" before the first caller asked — a stats read never builds."""
    if not _TRIED:
        return "not_loaded"
    return "c_extension" if _NATIVE is not None else "python_fallback"
