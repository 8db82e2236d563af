"""`chip_smoke.py` rehearsed on the CPU: the script the driver runs on the chip, at
2,000 documents, as a subprocess the way the driver starts it. With JAX_PLATFORMS=cpu
set by the caller it is a rehearsal — it makes every check it would make on the chip,
says so on every line, and exits 2 with `"ok": false`: never a pass."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(tmp_path, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the tests' eight virtual devices are not the smoke's
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--docs", "2000",
         "--out", str(tmp_path), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]  # every line parses
    assert lines, proc.stderr[-2000:]
    return proc, lines


def test_rehearsal_makes_every_check_and_is_never_a_pass(tmp_path):
    proc, lines = _run(tmp_path)
    assert proc.returncode == 2, (proc.stdout[-2000:], proc.stderr[-2000:])
    last = lines[-1]
    assert set(last) == {"ok", "device"}
    assert last["ok"] is False
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    checks = lines[-2]
    assert checks["phase"] == "checks" and checks["passed"] is True
    assert checks["rehearsal"] is True
    assert checks["client_imported_jax"] is False
    assert all(ln.get("rehearsal") is True for ln in lines[:-1])
    phases = [ln["phase"] for ln in lines[:-1]]
    for phase in ("sizes", "device", "ingest", "first_search", "searches", "state",
                  "optimize", "late_writes", "served", "compile_cache", "checks"):
        assert phase in phases, phase
    served = next(ln for ln in lines if ln["phase"] == "served")
    sv = served["serving"]
    assert sv["device_sparse"] == served["searches_sent"] > 0
    assert sv["host"] == sv["device_errors"] == sv["degraded"] == 0
    assert served["native"] in ("c_extension", "python_fallback")
    device = next(ln for ln in lines if ln["phase"] == "device")
    assert device["versions"]["jax"]


def test_a_reference_that_disagrees_is_exit_1(tmp_path):
    proc, lines = _run(tmp_path, "--reference-seed", "99")
    assert proc.returncode == 1, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert lines[-1]["ok"] is False
    checks = lines[-2]
    assert checks["phase"] == "checks" and checks["passed"] is False
    assert "reference" in checks["error"]
