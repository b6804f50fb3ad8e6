"""Prometheus text-exposition HTTP endpoint (`/metrics` + `/healthz`).

Stdlib ``http.server`` only — nothing to install on a TPU VM. OFF by
default: the server starts only when ``obs.metrics_port`` is set, and it
binds 127.0.0.1 unless ``obs.metrics_host`` says otherwise (a training
host should not expose an unauthenticated scrape target to the network;
reach it remotely over an SSH port forward — docs/TPU_VM_SETUP.md).

``/metrics`` renders the shared registry in Prometheus format 0.0.4;
``/healthz`` answers ``ok`` (livenesss for the supervisor or an external
prober: the HTTP thread answering proves the process is not wedged at
the interpreter level). A health PROVIDER (`set_health_provider`)
upgrades the body to JSON progress facts — `last_step_age_s` from the
trainer, `last_dispatch_age_s` + the live registry `model_version` from
the serving plane — so a probe can tell wedged-but-listening (the HTTP
thread answers while the ages grow without bound) from healthy, without
the run watchdog's deeper diagnosis.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional

from novel_view_synthesis_3d_tpu.obs.registry import (
    MetricsRegistry,
    get_registry,
)

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


class MetricsServer:
    """Background /metrics endpoint over one registry; `close()` to stop.

    `port=0` binds an ephemeral port (tests); the actual port is on
    `.port` either way."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 port: int = 0, host: str = "127.0.0.1"):
        self.registry = registry if registry is not None else get_registry()
        self._health_provider: Optional[Callable[[], dict]] = None
        self._metrics_extra: Optional[Callable[[], str]] = None
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                if self.path.split("?")[0] == "/metrics":
                    text = outer.registry.render_prometheus()
                    extra = outer._metrics_extra
                    if extra is not None:
                        try:
                            text += extra()
                        except Exception:
                            pass  # aggregation failure ≠ scrape failure
                    body = text.encode()
                    self.send_response(200)
                    self.send_header("Content-Type", CONTENT_TYPE)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path.split("?")[0] == "/healthz":
                    body, ctype = b"ok\n", "text/plain"
                    provider = outer._health_provider
                    if provider is not None:
                        try:
                            body = (json.dumps(provider()) + "\n").encode()
                            ctype = "application/json"
                        except Exception:
                            # A broken provider must not take liveness
                            # down with it — fall back to the bare ok.
                            body, ctype = b"ok\n", "text/plain"
                    self.send_response(200)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self.send_error(404)

            def log_message(self, fmt, *args):
                pass  # scrapes every few seconds must not spam the run log

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="obs-metrics-http")
        self._thread.start()

    def set_health_provider(
            self, provider: Optional[Callable[[], dict]]) -> None:
        """Install (or clear, with None) the /healthz JSON body source —
        a zero-arg callable returning a JSON-serializable dict, called
        per request on the HTTP thread so the ages it reports are live."""
        self._health_provider = provider

    def set_metrics_extra(
            self, extra: Optional[Callable[[], str]]) -> None:
        """Install (or clear) extra Prometheus exposition text appended
        after the local registry's render — the fleet router hangs its
        replica-relabeled aggregation here, making the router's own
        /metrics the single scrape surface for the whole fleet."""
        self._metrics_extra = extra

    def url(self, path: str = "/metrics") -> str:
        return f"http://{self.host}:{self.port}{path}"

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)


def start_metrics_server(registry: Optional[MetricsRegistry] = None,
                         port: int = 0,
                         host: str = "127.0.0.1") -> MetricsServer:
    return MetricsServer(registry, port, host)
