"""HTTP server: the REST surface over real sockets.

Analogue of http/NettyHttpServerTransport.java (SURVEY.md §2.7): binds the REST
controller to a TCP port (default 9200 range), keep-alive, JSON in/out. Stdlib
ThreadingHTTPServer — the request fan-out is the transport layer's job, HTTP is just
the front door, same as the reference.
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qsl, unquote_plus, urlparse

from ..common import xcontent
from ..common.logging import get_logger
from ..common.metrics import HistogramMetric
from ..rest.controller import RestController, RestRequest, RestResponse


class HttpServer:
    def __init__(self, rest_controller: RestController, host: str = "127.0.0.1",
                 port: int = 9200):
        self.rest = rest_controller
        self.logger = get_logger("http")
        rest = self.rest
        # what follows the handler: encoding the response and writing it to
        # the socket. The request's span tree closes before this, so it is
        # timed here for every request (`/_nodes/stats` `http.respond`)
        respond = self.respond = HistogramMetric()
        # the handler threads, one a connection, for `/_nodes/stats`
        # runtime.cpu.threads.http_s: the kernel's ids of the live ones
        # (monitor.cpu_stats reads their CPU seconds from /proc when stats
        # are asked) and the seconds of those that have ended, each booked
        # once as its connection closes. A request pays no clock read
        self.live_threads: set[int] = set()
        self.retired_cpu_s = 0.0
        self.threads_lock = threading.Lock()  # leaf: a set and a float
        server = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def setup(self):
                super().setup()
                with server.threads_lock:
                    server.live_threads.add(threading.get_native_id())

            def finish(self):
                try:
                    super().finish()
                finally:
                    cpu = time.thread_time()  # the connection's thread ends here
                    with server.threads_lock:
                        server.live_threads.discard(threading.get_native_id())
                        server.retired_cpu_s += cpu

            def _handle(self, method: str):
                t_arrival = time.monotonic()
                parsed = urlparse(self.path)
                length = int(self.headers.get("Content-Length") or 0)
                raw_bytes = self.rfile.read(length) if length else b""
                ctype = self.headers.get("Content-Type", "")
                # content negotiation (ref: XContentFactory.xContent — Content-Type
                # first, then byte sniffing): SMILE/CBOR/YAML bodies decode to
                # objects here; JSON keeps the string fallback so ndjson (_bulk,
                # _msearch) and lenient-JSON bodies reach their handlers raw
                fmt = xcontent.from_content_type(ctype)
                if fmt is None and raw_bytes:
                    sniffed = xcontent.detect(raw_bytes)
                    if sniffed in (xcontent.SMILE, xcontent.CBOR):
                        fmt = sniffed
                body: object = ""
                try:
                    if raw_bytes:
                        if fmt in (xcontent.SMILE, xcontent.CBOR, xcontent.YAML):
                            body = xcontent.loads(raw_bytes, fmt)
                        else:
                            raw = raw_bytes.decode()
                            body = raw
                            single_line = "\n" not in raw.strip()
                            if "json" in ctype or (
                                    raw.lstrip().startswith(("{", "["))
                                    and single_line):
                                try:
                                    body = json.loads(raw)
                                except ValueError:
                                    body = raw
                except Exception as e:  # noqa: BLE001 — malformed body → 400,
                    # never a dropped connection (incl. undecodable bytes that
                    # the format sniffer didn't classify as binary)
                    payload = json.dumps({"error": {
                        "type": "parse_exception",
                        "reason": f"failed to parse request body: {e}"},
                        "status": 400}).encode()
                    self.send_response(400)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                    return
                # the _cat flag idiom is a BARE `?v` / `?help` with no value:
                # surface those as "" (truthy flags) — but keep dropping
                # explicit blanks (`?from=`), whose handlers expect absence
                params = dict(parse_qsl(parsed.query))
                for seg in parsed.query.split("&"):
                    if seg and "=" not in seg:
                        params.setdefault(unquote_plus(seg), "")
                request = RestRequest(
                    method=method, path=parsed.path, params=params, body=body,
                    t_arrival=t_arrival)
                response = rest.dispatch(request)
                t_handled = time.monotonic()
                # response rides the request's format, or an explicit ?format=
                out_fmt = xcontent.from_content_type(
                    "application/" + request.params.get("format", "")) or fmt
                try:
                    if (out_fmt and out_fmt != xcontent.JSON
                            and response.content_type == "application/json"
                            and isinstance(response.body, (dict, list))):
                        payload = xcontent.dumps(response.body, out_fmt)
                        content_type = xcontent.CONTENT_TYPES[out_fmt]
                    else:
                        payload = response.payload()
                        content_type = response.content_type
                except Exception as e:  # noqa: BLE001 — unencodable response → 500
                    response = RestResponse(500, {"error": {
                        "type": "serialization_exception", "reason": str(e)},
                        "status": 500})
                    payload = response.payload()
                    content_type = "application/json"
                self.send_response(response.status)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(payload)))
                for name, value in (getattr(response, "headers", None) or {}).items():
                    self.send_header(name, str(value))
                self.end_headers()
                if method != "HEAD":
                    self.wfile.write(payload)
                respond.observe(time.monotonic() - t_handled)

            def do_GET(self):
                self._handle("GET")

            def do_POST(self):
                self._handle("POST")

            def do_PUT(self):
                self._handle("PUT")

            def do_DELETE(self):
                self._handle("DELETE")

            def do_HEAD(self):
                self._handle("HEAD")

            def log_message(self, fmt, *args):  # quiet
                pass

        class Server(ThreadingHTTPServer):
            # socketserver's default accept backlog is 5: a burst of 16
            # connections at once (chip_smoke.py's, PR 22) was reset by the
            # kernel before a handler thread ever saw it
            request_queue_size = 128

        self._server = Server((host, port), Handler)
        self.port = self._server.server_port
        self.host = host
        self._thread: threading.Thread | None = None

    def start(self):
        self._thread = threading.Thread(target=self._server.serve_forever, daemon=True,
                                        name=f"estpu[http:{self.port}]")
        self._thread.start()
        self.logger.info("http listening on %s:%d", self.host, self.port)
        return self

    def threads(self) -> tuple[list, float]:
        """(kernel ids of the live handler threads, CPU seconds of the ended
        ones), one consistent reading."""
        with self.threads_lock:
            return list(self.live_threads), self.retired_cpu_s

    def stats(self) -> dict:
        """`/_nodes/stats` `http`: `respond` is response encode + socket
        write, seconds summed and as percentiles."""
        return {"respond": {**self.respond.stats(), "sum_s": self.respond.sum}}

    def stop(self):
        self._server.shutdown()
        self._server.server_close()
