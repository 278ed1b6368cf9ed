"""Shared stdlib HTTP plumbing for the scrape and serving surfaces.

One ``ThreadingHTTPServer`` wrapper used by both HTTP frontends in the
tree — the observability scrape surface (``io/metrics_http.py``:
/metrics) and the online serving tier
(``serving/frontend.py``: /v1/tables/...; docs/SERVING.md). Factoring
it here keeps the two surfaces byte-for-byte consistent on the parts
that are pure protocol: route dispatch, Content-Type/Content-Length
handling, 404 for unknown paths, 500 for a handler that raises, and
typed non-200 responses with extra headers (the admission controller's
429 + Retry-After rides ``HttpError``).

Dependency-free (``http.server``); one daemon thread per server, each
request handled on its own thread (``ThreadingHTTPServer``) so a slow
client cannot block a concurrent one. Deliberately a LEAF module:
handlers are plain callables injected by the owner — no imports back
into the runtime.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional
from urllib.parse import parse_qs, urlsplit

from ..util import log


class Response:
    """What a route handler returns: status + content type + body bytes
    (+ any extra headers, e.g. the serving tier's X-MV-* metadata)."""

    __slots__ = ("status", "content_type", "body", "headers")

    def __init__(self, body: bytes, content_type: str,
                 status: int = 200,
                 headers: Optional[Dict[str, str]] = None):
        self.status = int(status)
        self.content_type = content_type
        self.body = body
        self.headers = dict(headers or {})


class HttpError(Exception):
    """A typed non-200 answer a handler wants sent — carries the status
    and any extra headers (Retry-After on a 429/503 shed), rendered as
    a small JSON error body so programmatic clients can read the
    machine fields (``retry_after_s``) that the integer-seconds
    Retry-After header cannot carry."""

    def __init__(self, status: int, message: str,
                 headers: Optional[Dict[str, str]] = None,
                 extra: Optional[dict] = None):
        super().__init__(message)
        self.status = int(status)
        self.message = message
        self.headers = dict(headers or {})
        self.extra = dict(extra or {})


#: A route handler: query params (last value per key) -> Response.
Handler = Callable[[Dict[str, str]], Response]


class HttpServer:
    """Threaded HTTP server dispatching GETs through ``resolve``.

    ``resolve(path)`` returns the ``Handler`` for a path or ``None``
    (-> 404 listing ``describe()``). A handler may raise ``HttpError``
    for a typed non-200 answer; any other exception answers 500 —
    a broken renderer must not kill the handler thread mid-response.
    """

    def __init__(self, port: int,
                 resolve: Callable[[str], Optional[Handler]],
                 host: str = "0.0.0.0", name: str = "http"):
        self._name = name
        server = self

        class _Handler(BaseHTTPRequestHandler):
            # Keep-alive: serving clients issue thousands of small
            # GETs, and HTTP/1.0's connection-per-request tears down a
            # TCP handshake per read (~an order of magnitude of the
            # whole request on loopback). Safe because every response
            # path below goes through _send, which always sets
            # Content-Length.
            protocol_version = "HTTP/1.1"

            def do_GET(self):  # noqa: N802 - http.server contract
                server._handle(self)

            def log_message(self, fmt, *args):  # quiet: per-request
                # stderr noise helps nobody; scrapes are periodic and
                # serving traffic is high-rate by design
                log.debug(f"{server._name}: " + fmt, *args)

        self._resolve = resolve
        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        # Local import: io must not pull the runtime package (and its
        # actor/zoo import chain) at module load.
        from ..runtime import thread_roles
        self._thread = thread_roles.spawn(
            thread_roles.BACKGROUND, target=self._httpd.serve_forever,
            name=f"mv-{name}-{self.port}")
        log.info("%s: serving on port %d", self._name, self.port)

    # -- request plumbing --
    def _handle(self, request: BaseHTTPRequestHandler) -> None:
        parts = urlsplit(request.path)
        handler = self._resolve(parts.path)
        if handler is None:
            self._send_json(request, 404,
                            {"error": f"unknown path {parts.path!r}"
                                      f" (served: {self.describe()})"})
            return
        query = {key: values[-1] for key, values
                 in parse_qs(parts.query).items()}
        try:
            response = handler(query)
        except HttpError as exc:
            self._send_json(request, exc.status,
                            {"error": exc.message, **exc.extra},
                            exc.headers)
            return
        except Exception as exc:  # noqa: BLE001 - a broken handler
            # must answer 500, not kill the handler thread mid-response
            self._send_json(request, 500,
                            {"error": f"handler failed: {exc}"})
            return
        self._send(request, response.status, response.content_type,
                   response.body, response.headers)

    @staticmethod
    def _send(request: BaseHTTPRequestHandler, status: int,
              content_type: str, body: bytes,
              headers: Optional[Dict[str, str]] = None) -> None:
        try:
            request.send_response(status)
            request.send_header("Content-Type", content_type)
            request.send_header("Content-Length", str(len(body)))
            for name, value in (headers or {}).items():
                request.send_header(name, value)
            request.end_headers()
            request.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass  # client hung up mid-response; nothing to answer

    @classmethod
    def _send_json(cls, request: BaseHTTPRequestHandler, status: int,
                   doc: dict,
                   headers: Optional[Dict[str, str]] = None) -> None:
        cls._send(request, status, "application/json; charset=utf-8",
                  json.dumps(doc).encode(), headers)

    def describe(self) -> str:
        """Human hint appended to 404 bodies; owners override with
        their route listing."""
        return self._name

    @property
    def port(self) -> int:
        """The actually-bound port (differs from the requested one only
        when constructed with port 0 — tests use the ephemeral bind)."""
        return self._httpd.server_address[1]

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


def json_response(doc: dict, status: int = 200,
                  headers: Optional[Dict[str, str]] = None) -> Response:
    return Response(json.dumps(doc).encode(),
                    "application/json; charset=utf-8", status, headers)
