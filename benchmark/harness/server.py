"""The child server and its HTTP clients (a copy of `chip_smoke.py`'s process layout).

The parent is the client and never imports JAX. The child is `python -m
elasticsearch_tpu --data <dir> --http-port 0 --transport local`, the one process on
the chip; its port is read from its start line.
"""

from __future__ import annotations

import http.client
import json
import os
import shutil
import signal
import subprocess
import sys
import time


class BenchFailure(Exception):
    """A phase of the run failed; the message names it."""


class Server:
    def __init__(self, checkout: str, run_dir: str, env_extra: dict | None = None):
        self.run_dir = run_dir
        self.data = os.path.join(run_dir, "data")
        self.log_path = os.path.join(run_dir, "server.log")
        os.makedirs(self.data, exist_ok=True)
        env = dict(os.environ)
        env.update(env_extra or {})
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "elasticsearch_tpu", "--data", self.data,
             "--http-port", "0", "--transport", "local"],
            cwd=checkout, env=env, stdout=self.log, stderr=subprocess.STDOUT)
        self.port = None

    def wait_started(self, timeout: float = 300.0) -> int:
        t_end = time.monotonic() + timeout
        while time.monotonic() < t_end:
            with open(self.log_path, "rb") as f:
                for line in f.read().decode("utf-8", "replace").splitlines():
                    if line.startswith("[estpu] node [") and "http port " in line:
                        self.port = int(line.rsplit("http port ", 1)[1].split()[0])
                        return self.port
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited with {self.proc.returncode} before it started")
            time.sleep(0.1)
        raise BenchFailure(f"server printed no start line within {timeout:.0f} s")

    def check_alive(self) -> None:
        if self.proc.poll() is not None:
            raise BenchFailure(f"server exited early with {self.proc.returncode}")

    def stop(self) -> int | None:
        """SIGTERM, then kill; waits until the child has ended. Returns the exit
        code, None where it had to be killed. The index is rebuilt from the seed in
        every run, so the data directory goes."""
        rc = self.proc.poll()
        if rc is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                rc = None
        if not self.log.closed:
            self.log.close()
        shutil.rmtree(self.data, ignore_errors=True)
        return rc

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path, "rb") as f:
            return "\n".join(f.read().decode("utf-8", "replace").splitlines()[-n:])


class Connection:
    """One keep-alive HTTP connection; reopened after an error or where the mix asks
    for a connection per request."""

    def __init__(self, port: int, timeout: float, keep_alive: bool = True):
        self.port, self.timeout, self.keep_alive = port, timeout, keep_alive
        self.conn = None

    def request(self, method: str, path: str, data: bytes | None):
        """(status, body bytes). Raises OSError / http.client.HTTPException."""
        if self.conn is None:
            self.conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                   timeout=self.timeout)
        try:
            self.conn.request(method, path, body=data)
            resp = self.conn.getresponse()
            body = resp.read()
            status = resp.status
        except BaseException:
            self.close()
            raise
        if not self.keep_alive:
            self.close()
        return status, body

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Client:
    """The control-plane client: one call, one connection, errors raised."""

    def __init__(self, server: Server):
        self.server = server

    def call(self, method: str, path: str, body=None, timeout: float = 120.0):
        self.server.check_alive()
        data = None
        if body is not None:
            data = body if isinstance(body, bytes) else json.dumps(body).encode()
        conn = Connection(self.server.port, timeout, keep_alive=False)
        status, text = conn.request(method, path, data)
        if status >= 400:
            raise BenchFailure(f"{method} {path} -> {status}: {text[:400]!r}")
        return json.loads(text)

    def node_stats(self, metrics: str) -> dict:
        nodes = self.call("GET", f"/_nodes/stats/{metrics}")["nodes"]
        return next(iter(nodes.values()))
