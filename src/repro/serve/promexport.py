"""Prometheus-style text exposition of ``ServeEngine.metrics()``.

``metrics()`` returns a flat dict whose keys are slash-namespaced
(``slo/ttft_p95_s``, ``cache/pages_free``, ``kernels/mpmm_calls``, ...) and
whose values are numbers, strings, or bools. Prometheus metric names
forbid ``/`` and most punctuation, so the renderer maps every key to a
sanitized ``repro_``-prefixed gauge name AND preserves the exact original
key as a ``key`` label — the exposition is lossless (:func:`parse` inverts
:func:`render` key-for-key, which ``tests/test_trace.py`` gates). String
values become ``repro_info{key=...,value=...} 1`` info-style gauges, the
standard Prometheus idiom for non-numeric facts.

Serving: :class:`MetricsServer` wraps the stdlib ``http.server`` in a
daemon thread (``launch/serve.py --metrics-port``); ``GET /metrics``
renders a fresh snapshot per scrape. :func:`write_exposition` dumps the
same bytes to a file so tests and offline runs don't need a socket.
"""

from __future__ import annotations

import http.server
import threading
from typing import Callable, Optional

_NAME_OK = set("abcdefghijklmnopqrstuvwxyz"
               "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_:")


def _sanitize(key: str) -> str:
    """Map a metrics() key to a legal Prometheus metric name."""
    name = "".join(ch if ch in _NAME_OK else "_" for ch in key)
    if name and name[0].isdigit():
        name = "_" + name
    return "repro_" + name


def _escape_label(v: str) -> str:
    """Label-value escaping per the exposition format spec."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _unescape_label(v: str) -> str:
    out, i = [], 0
    while i < len(v):
        if v[i] == "\\" and i + 1 < len(v):
            nxt = v[i + 1]
            out.append({"\\": "\\", '"': '"', "n": "\n"}.get(nxt, nxt))
            i += 2
        else:
            out.append(v[i])
            i += 1
    return "".join(out)


def render(metrics: dict) -> str:
    """Render a ``metrics()`` dict as Prometheus text exposition (0.0.4).

    Numeric values (bools included — they become 0/1) turn into one gauge
    sample each, named from the sanitized key and labeled with the original;
    strings turn into ``repro_info`` samples. Keys render in sorted order so
    the output is deterministic and diffable.
    """
    lines: list[str] = []
    typed: set[str] = set()
    for key in sorted(metrics):
        val = metrics[key]
        label = _escape_label(str(key))
        if isinstance(val, str):
            name = "repro_info"
            if name not in typed:
                lines.append(f"# TYPE {name} gauge")
                typed.add(name)
            lines.append(
                f'{name}{{key="{label}",value="{_escape_label(val)}"}} 1')
            continue
        if isinstance(val, bool):
            val = int(val)
        name = _sanitize(str(key))
        if name not in typed:
            lines.append(f"# TYPE {name} gauge")
            typed.add(name)
        lines.append(f'{name}{{key="{label}"}} {float(val)!r}')
    return "\n".join(lines) + "\n"


def _split_labels(body: str) -> dict:
    """Parse `k="v",k2="v2"` respecting escapes (values never contain a raw
    double-quote, so quote characters delimit reliably)."""
    labels: dict[str, str] = {}
    i = 0
    while i < len(body):
        eq = body.index("=", i)
        k = body[i:eq].lstrip(",").strip()
        assert body[eq + 1] == '"'
        j = eq + 2
        while True:
            if body[j] == "\\":
                j += 2
                continue
            if body[j] == '"':
                break
            j += 1
        labels[k] = _unescape_label(body[eq + 2:j])
        i = j + 1
    return labels


def parse(text: str) -> dict:
    """Invert :func:`render`: recover ``{original_key: value}`` from the
    exposition (the round-trip test's other half). Strings come back as
    strings, everything numeric as float — callers compare with
    ``float(orig) == parsed`` for ints/bools."""
    out: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name, rest = line.split("{", 1)
        body, value = rest.rsplit("} ", 1)
        labels = _split_labels(body)
        if name == "repro_info":
            out[labels["key"]] = labels["value"]
        else:
            out[labels["key"]] = float(value)
    return out


def write_exposition(path, metrics: dict) -> str:
    """Dump :func:`render` output to ``path`` (the no-socket scrape)."""
    with open(path, "w") as f:
        f.write(render(metrics))
    return str(path)


class MetricsServer:
    """Background ``/metrics`` scrape endpoint over a live metrics source.

    ``source`` is a zero-arg callable returning the metrics dict (pass
    ``engine.metrics`` — each scrape sees current counters). ``port=0``
    binds an ephemeral port; read it back from ``.port``. The serving
    thread is a daemon so an abandoned server never blocks interpreter
    exit, but call :meth:`close` for deterministic shutdown.
    """

    def __init__(self, source: Callable[[], dict], *,
                 host: str = "127.0.0.1", port: int = 0):
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (stdlib handler API)
                if self.path.rstrip("/") not in ("", "/metrics"):
                    self.send_error(404)
                    return
                body = render(outer._source()).encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # scrapes are not stdout events
                pass

        self._source = source
        self._httpd = http.server.ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self._httpd.server_address[:2]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="metrics-server",
            daemon=True)
        self._thread.start()

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}/metrics"

    def close(self) -> None:
        self._httpd.shutdown()
        self._thread.join(timeout=5)
        self._httpd.server_close()


_UNSET = object()


def maybe_serve(source: Callable[[], dict],
                port: Optional[int] = None) -> Optional[MetricsServer]:
    """Launcher helper: start a :class:`MetricsServer` iff a port was
    requested (``--metrics-port`` default None means no server)."""
    if port is None:
        return None
    return MetricsServer(source, port=port)
