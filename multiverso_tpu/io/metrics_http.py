"""Tiny stdlib HTTP scrape surface for the observability layer.

Serves the controller's cluster-aggregated metrics view
(docs/OBSERVABILITY.md) on ``-metrics_port``:

- ``GET /metrics`` — Prometheus text exposition format 0.0.4 (the
  contract every Prometheus-compatible scraper speaks).

The HTTP plumbing itself (ThreadingHTTPServer lifecycle, dispatch,
404/500 handling) lives in the shared ``io/http_server.py`` base,
which the online serving tier (``serving/frontend.py``,
docs/SERVING.md) builds on too; this module is just the fixed
exact-path route table over it. Read-only and dependency-free; this is
deliberately NOT a general app server — it is the scrape side, and
stays a leaf: renderers are plain callables injected by the runtime
(no imports back into it).
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

from .http_server import HttpServer, Response

#: path -> () -> (content_type, body_bytes)
Routes = Dict[str, Callable[[], Tuple[str, bytes]]]


class MetricsHttpServer(HttpServer):
    """Threaded HTTP server over a fixed route table."""

    def __init__(self, port: int, routes: Routes,
                 host: str = "0.0.0.0"):
        self._routes = dict(routes)
        super().__init__(port, self._resolve_path, host=host,
                         name="metrics-http")

    def _resolve_path(self, path: str):
        route = self._routes.get(path)
        if route is None:
            return None

        def handler(query):
            ctype, body = route()
            return Response(body, ctype)
        return handler

    def describe(self) -> str:
        return ", ".join(sorted(self._routes))


def prometheus_route(render: Callable[[], str]):
    """Adapt a text renderer to a route (content type per the
    exposition-format spec)."""
    def route():
        return ("text/plain; version=0.0.4; charset=utf-8",
                render().encode())
    return route
