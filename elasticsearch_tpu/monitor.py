"""Monitor service: OS / process / fs / runtime metrics.

Analogue of monitor/ (SURVEY.md §2.9): the reference loads native Sigar libraries for
os/process/network stats with pure-Java fallbacks; here the native source of truth is
/proc (what Sigar reads underneath) plus resource/os modules — no JVM, so "jvm stats"
map to the Python runtime + the JAX device: heap → RSS, GC → gc module, plus TPU HBM
numbers from jax's memory_stats when a device is live.
"""

from __future__ import annotations

import gc
import os
import resource
import time


class GcPauses:
    """Seconds the process stood still in full (generation 2) collections:
    a `gc.callbacks` hook that reads the clock at the start and the stop of
    each one. The younger generations run thousands of times a second and
    are left alone. One per process, installed by the first MonitorService."""

    def __init__(self):
        self.pause_s = 0.0
        self._t0: float | None = None
        self._installed = False

    def install(self) -> None:
        if not self._installed:
            self._installed = True
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        # called with the GIL held, by whichever thread tripped the collector
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.monotonic()
        elif self._t0 is not None:
            self.pause_s += time.monotonic() - self._t0
            self._t0 = None


GC_PAUSES = GcPauses()


def os_stats(proc: str = "/proc") -> dict:
    """`proc` overrides the procfs root so tests can feed canned fixtures
    (tests/test_monitor.py) — production always reads the real /proc."""
    out: dict = {"timestamp": int(time.time() * 1000)}
    try:
        load = os.getloadavg()
        out["load_average"] = list(load)
    except OSError:
        pass
    try:
        with open(os.path.join(proc, "meminfo")) as fh:
            mem = {}
            for line in fh:
                parts = line.split()
                if parts[0].rstrip(":") in ("MemTotal", "MemFree", "MemAvailable",
                                            "SwapTotal", "SwapFree"):
                    mem[parts[0].rstrip(":")] = int(parts[1]) * 1024
        out["mem"] = {
            "total_in_bytes": mem.get("MemTotal", 0),
            "free_in_bytes": mem.get("MemFree", 0),
            "available_in_bytes": mem.get("MemAvailable", 0),
        }
        out["swap"] = {
            "total_in_bytes": mem.get("SwapTotal", 0),
            "free_in_bytes": mem.get("SwapFree", 0),
        }
    except OSError:
        pass
    out["cpu"] = {"count": os.cpu_count()}
    return out


def process_stats(proc: str = "/proc") -> dict:
    """`proc` overrides the procfs root (canned fixtures in tests)."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {
        "timestamp": int(time.time() * 1000),
        "id": os.getpid(),
        "mem": {"resident_in_bytes": ru.ru_maxrss * 1024},
        "cpu": {
            "user_in_millis": int(ru.ru_utime * 1000),
            "sys_in_millis": int(ru.ru_stime * 1000),
            "total_in_millis": int((ru.ru_utime + ru.ru_stime) * 1000),
        },
    }
    try:
        with open(os.path.join(proc, "self", "status")) as fh:
            for line in fh:
                if line.startswith("Threads:"):
                    out["threads"] = int(line.split()[1])
                elif line.startswith("VmRSS:"):
                    out["mem"]["resident_in_bytes"] = int(line.split()[1]) * 1024
    except OSError:
        pass
    try:
        out["open_file_descriptors"] = len(os.listdir(
            os.path.join(proc, "self", "fd")))
        out["max_file_descriptors"] = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
    except OSError:
        pass
    return out


def fs_stats(paths: list[str]) -> dict:
    data = []
    for p in paths:
        try:
            st = os.statvfs(p)
            data.append({
                "path": p,
                "total_in_bytes": st.f_blocks * st.f_frsize,
                "free_in_bytes": st.f_bfree * st.f_frsize,
                "available_in_bytes": st.f_bavail * st.f_frsize,
            })
        except OSError:
            continue
    return {"timestamp": int(time.time() * 1000), "data": data}


# the pools whose threads a search crosses, each a role of its own under
# runtime.cpu.threads; every other pool's seconds are summed as `other`
_CPU_ROLES = ("search", "generic", "search_batcher")
_TICK_S = 1.0 / os.sysconf("SC_CLK_TCK")


def thread_cpu_s(native_id: int, proc: str = "/proc") -> float:
    """CPU seconds one live thread of this process has had so far: the
    nanoseconds on a CPU of /proc/self/task/<tid>/schedstat or, where the
    kernel keeps none, utime + stime of its `stat` in clock ticks; 0.0 for a
    thread that is gone."""
    base = os.path.join(proc, "self", "task", str(native_id))
    try:
        with open(os.path.join(base, "schedstat")) as fh:
            return int(fh.read().split()[0]) / 1e9
    except (OSError, ValueError, IndexError):
        pass
    try:
        with open(os.path.join(base, "stat")) as fh:
            fields = fh.read().rsplit(")", 1)[1].split()  # after "(comm)"
        return (int(fields[11]) + int(fields[12])) * _TICK_S
    except (OSError, ValueError, IndexError):
        return 0.0


def cpu_stats(node=None, proc: str = "/proc") -> dict:
    """`runtime.cpu`: CPU seconds of the process (time.process_time()) and of
    the node's Python threads by role: the HTTP handlers (one thread a
    connection; those that have ended booked their seconds as they closed,
    http/server.py), the `search` and `generic` pools' workers, the batcher's
    drainer (the one worker of `search_batcher`) and the other pools'. All of
    it is read here, when stats are asked, each live thread's from /proc by
    the kernel's id of it (thread_cpu_s): the serving path pays no clock read
    (time.thread_time() around every pool task and request, as first built,
    cost `wiki.filtered` 5-7% of its searches a second on the chip's host:
    PERF.md section 6, PR 37). Thread CPU time counts C code that has let the
    interpreter lock go (large numpy calls, socket reads and writes) as well:
    the threads' sum is an upper bound on the seconds the lock was held. What
    `process_s` reads over that sum is the runtime's own threads (transfers,
    the compiler, the profiler) and threads outside the pools."""
    threads = dict.fromkeys(("http", *_CPU_ROLES, "other"), 0.0)
    http = getattr(node, "http", None)
    if http is not None:
        live, retired_s = http.threads()
        threads["http"] = retired_s + sum(thread_cpu_s(t, proc) for t in live)
    pools = getattr(node, "threadpool", None)
    if pools is not None:
        for name, ids in pools.thread_ids().items():
            role = name if name in _CPU_ROLES else "other"
            threads[role] += sum(thread_cpu_s(t, proc) for t in ids)
    return {"process_s": time.process_time(),
            "threads": {role + "_s": v for role, v in threads.items()}}


def runtime_stats(node=None) -> dict:
    """The "jvm stats" analogue: Python runtime + (when live) the TPU device.
    With a `node`, also what its threads cost and wait for: `cpu`
    (cpu_stats) and `gil` (threadpool.ThreadPool.gil_stats)."""
    import sys

    counts = gc.get_count()
    out = {
        "timestamp": int(time.time() * 1000),
        "runtime": "python",
        "version": sys.version.split()[0],
        "gc": {"collections": gc.get_stats()[-1].get("collections", 0)
               if gc.get_stats() else 0, "pending": sum(counts),
               # seconds inside those full collections, since the hook went in
               "pause_s": GC_PAUSES.pause_s},
        "uptime_in_millis": int(time.monotonic() * 1000),
        "cpu": cpu_stats(node),
    }
    pools = getattr(node, "threadpool", None)
    if pools is not None:
        out["gil"] = pools.gil_stats()
    try:
        import jax

        devices = jax.devices()
        dev_stats = []
        for d in devices:
            entry = {"platform": d.platform, "device_kind": d.device_kind,
                     "device": str(d)}
            ms = getattr(d, "memory_stats", None)
            if callable(ms):
                try:
                    stats = ms() or {}
                    entry["hbm_bytes_in_use"] = stats.get("bytes_in_use")
                    entry["hbm_bytes_limit"] = stats.get("bytes_limit")
                except Exception:  # noqa: BLE001
                    pass
            dev_stats.append(entry)
        out["devices"] = dev_stats
    except Exception:  # noqa: BLE001 — no device backend in this process
        out["devices"] = []
    out["device_count"] = len(out["devices"])
    from . import native

    out["native"] = native.implementation()
    return out


class MonitorService:
    def __init__(self, node):
        self.node = node
        GC_PAUSES.install()

    def sections(self) -> dict:
        """Monitor stats as name -> thunk, so `/_nodes/stats/{metric}` can
        build ONLY the requested sections (each is its own procfs read)."""
        return {
            "os": os_stats,
            "process": process_stats,
            "fs": lambda: fs_stats([self.node.data_path]),
            "runtime": lambda: runtime_stats(self.node),
        }

    def full_stats(self) -> dict:
        return {name: build() for name, build in self.sections().items()}
