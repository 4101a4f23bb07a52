"""Prometheus metrics — dependency-free registry + text exposition
(reference: the per-module Metrics structs generated by
scripts/metricsgen/metricsgen.go and wired at node/node.go:334, served
by the prometheus HTTP handler at node.go:594).

Implements the subset of the Prometheus client model the node needs:
Counter, Gauge, Histogram, labels, and the text exposition format
(version 0.0.4) that real Prometheus servers scrape.
"""

from __future__ import annotations

import socketserver
import threading
import time
from wsgiref.simple_server import WSGIRequestHandler, WSGIServer, make_server

from cometbft_tpu.utils.log import Logger, default_logger
from cometbft_tpu.utils.service import BaseService
from cometbft_tpu.utils.trace import TRACER


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{_escape(v)}"' for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


def _escape(v: str) -> str:
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    if float(v).is_integer():
        return str(int(v))
    return repr(float(v))


class _Child:
    __slots__ = ("_value", "_mtx")

    def __init__(self):
        self._value = 0.0
        self._mtx = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._mtx:
            self._value += amount

    def set(self, value: float) -> None:
        with self._mtx:
            self._value = float(value)

    def get(self) -> float:
        with self._mtx:
            return self._value


class _HistChild:
    __slots__ = ("_buckets", "_counts", "_sum", "_count", "_mtx")

    def __init__(self, buckets: tuple[float, ...]):
        self._buckets = buckets
        self._counts = [0] * len(buckets)
        self._sum = 0.0
        self._count = 0
        self._mtx = threading.Lock()

    def observe(self, value: float) -> None:
        with self._mtx:
            self._sum += value
            self._count += 1
            # per-bucket counts; expose() cumulates them
            for i, ub in enumerate(self._buckets):
                if value <= ub:
                    self._counts[i] += 1
                    break


class Metric:
    """One metric family; `labels(**kv)` returns the per-labelset
    child (itself for label-less metrics)."""

    def __init__(
        self,
        kind: str,
        name: str,
        help_: str,
        label_names: tuple[str, ...] = (),
        buckets: tuple[float, ...] = (),
    ):
        self.kind = kind
        self.name = name
        self.help = help_
        self.label_names = label_names
        self.buckets = tuple(buckets) + (float("inf"),) if buckets else ()
        self._children: dict[tuple[str, ...], object] = {}
        self._mtx = threading.Lock()
        if not label_names:
            self._children[()] = self._new_child()

    def _new_child(self):
        if self.kind == "histogram":
            return _HistChild(self.buckets)
        return _Child()

    def labels(self, **kv: str):
        key = tuple(str(kv.get(n, "")) for n in self.label_names)
        with self._mtx:
            child = self._children.get(key)
            if child is None:
                child = self._new_child()
                self._children[key] = child
            return child

    def children(self) -> dict[tuple[str, ...], object]:
        """Snapshot of the per-labelset children, keyed by label-value
        tuple (in ``label_names`` order) — bench provenance reads this
        to report which dispatch tiers a measured config actually hit
        without scraping the exposition text."""
        with self._mtx:
            return dict(self._children)

    def remove(self, **kv: str) -> None:
        """Drop one labelset's child so a retired label value (a
        departed peer, a closed WS client) stops exposing a stale
        series instead of lingering forever."""
        if not self.label_names:
            return  # the label-less child always exists
        key = tuple(str(kv.get(n, "")) for n in self.label_names)
        with self._mtx:
            self._children.pop(key, None)

    # label-less convenience passthroughs
    def inc(self, amount: float = 1.0) -> None:
        self._children[()].inc(amount)

    def set(self, value: float) -> None:
        self._children[()].set(value)

    def observe(self, value: float) -> None:
        self._children[()].observe(value)

    def expose(self) -> list[str]:
        lines = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} {self.kind}",
        ]
        with self._mtx:
            children = dict(self._children)
        for key, child in sorted(children.items()):
            labels = dict(zip(self.label_names, key))
            if self.kind == "histogram":
                cum = 0
                for ub, c in zip(child._buckets, child._counts):
                    cum += c
                    bl = dict(labels)
                    bl["le"] = _fmt_value(ub)
                    lines.append(
                        f"{self.name}_bucket{_fmt_labels(bl)} {cum}"
                    )
                lines.append(
                    f"{self.name}_sum{_fmt_labels(labels)} "
                    f"{_fmt_value(child._sum)}"
                )
                lines.append(
                    f"{self.name}_count{_fmt_labels(labels)} {child._count}"
                )
            else:
                lines.append(
                    f"{self.name}{_fmt_labels(labels)} "
                    f"{_fmt_value(child.get())}"
                )
        return lines


class Registry:
    """Metric registry; one per node (prometheus.Registry analog)."""

    def __init__(self, namespace: str = "cometbft"):
        self.namespace = namespace
        self._metrics: list[Metric] = []
        self._mtx = threading.Lock()

    def _register(self, metric: Metric) -> Metric:
        with self._mtx:
            if any(m.name == metric.name for m in self._metrics):
                raise ValueError(f"duplicate metric {metric.name}")
            self._metrics.append(metric)
        return metric

    def _full_name(self, subsystem: str, name: str) -> str:
        parts = [p for p in (self.namespace, subsystem, name) if p]
        return "_".join(parts)

    def counter(
        self, subsystem: str, name: str, help_: str,
        labels: tuple[str, ...] = (),
    ) -> Metric:
        return self._register(
            Metric("counter", self._full_name(subsystem, name), help_, labels)
        )

    def gauge(
        self, subsystem: str, name: str, help_: str,
        labels: tuple[str, ...] = (),
    ) -> Metric:
        return self._register(
            Metric("gauge", self._full_name(subsystem, name), help_, labels)
        )

    def histogram(
        self, subsystem: str, name: str, help_: str,
        buckets: tuple[float, ...],
        labels: tuple[str, ...] = (),
    ) -> Metric:
        return self._register(
            Metric(
                "histogram", self._full_name(subsystem, name), help_,
                labels, buckets,
            )
        )

    def expose(self) -> str:
        with self._mtx:
            metrics = list(self._metrics)
        lines: list[str] = []
        for m in metrics:
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"


DEFAULT_TIME_BUCKETS = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


class _QuietHandler(WSGIRequestHandler):
    def log_message(self, *args):  # noqa: D102 — silence per-scrape noise
        pass


class _ThreadingWSGIServer(socketserver.ThreadingMixIn, WSGIServer):
    """Per-request daemon threads: a slow debug handler (notably
    /debug/fleet, which fans out to peer scrapes and blocks up to the
    scrape timeout) must not starve concurrent /metrics scrapes — and
    two nodes aggregating EACH OTHER must not mutually deadlock their
    single-threaded servers.  Every payload producer behind these
    routes is already lock-protected or snapshot-based."""

    daemon_threads = True
    # don't block shutdown on an in-flight fleet scrape
    block_on_close = False


#: the debug surface, served at ``/debug`` so the route set stays
#: discoverable as it grows (docs/observability.md): every entry is
#: (path, server, description) — ``metrics`` paths live on THIS
#: server, ``rpc`` names are JSON-RPC routes on the node's RPC server
#: (inspect mode serves the same names over a stopped node's stores)
DEBUG_ENDPOINTS = (
    ("/metrics", "metrics",
     "Prometheus text exposition of every registered family."),
    ("/trace", "metrics",
     "Span-tracer ring as Chrome trace-event JSON (Perfetto)."),
    ("/debug/flight", "metrics",
     "Always-on flight recorder: recent replication events."),
    ("/debug/perf", "metrics",
     "Device-health plane: tier health + probe latencies, launch "
     "watchdog, perf-ledger tail (a launch's timing is /trace: "
     "batch_verify and its steps, dur; tdur on the root and its "
     "fetch)."),
    ("/debug/dispatch", "metrics",
     "Failover dispatch ladder: per-tier demotion/cool-down state, "
     "the recent transition trail, and the armed chaos plan."),
    ("/debug/fleet", "metrics",
     "Fleet plane: scrape the peers in CMT_TPU_FLEET_PEERS, merge "
     "self in-process, and serve the rollup (per-node height + lag, "
     "dispatch tier, queue depths) plus stitched cross-node height "
     "trees; ?trace=1 inlines the merged Chrome trace."),
    ("/debug/profile", "metrics",
     "Always-on sampling profiler: span-tagged folded stacks + leaf "
     "hotspots; ?seconds=N windows, &format=collapsed for flame-graph "
     "text (CMT_TPU_PROFILE_HZ, default 19; 0 disables)."),
    ("/debug", "metrics", "This index."),
    ("wire", "rpc",
     "Wire-plane snapshot: per-peer MConnection status + queue "
     "gauges (also /net_info connection_status)."),
    ("debug/flight", "rpc",
     "Flight-recorder dump over JSON-RPC (inspect mode included)."),
    ("debug/perf", "rpc",
     "Device-health/perf payload over JSON-RPC (inspect mode "
     "included)."),
    ("debug/dispatch", "rpc",
     "Dispatch-ladder state over JSON-RPC (inspect mode included)."),
    ("debug/fleet", "rpc",
     "Fleet rollup + stitched heights over JSON-RPC (inspect mode "
     "included)."),
    ("debug/profile", "rpc",
     "Sampling-profiler payload over JSON-RPC (inspect mode "
     "included)."),
)


class MetricsServer(BaseService):
    """Serves ``/metrics`` in the text exposition format
    (node/node.go:594 prometheus http server), plus ``/trace`` — the
    span tracer's retained ring as Chrome trace-event JSON, loadable in
    Perfetto — ``/debug/flight``, the always-on flight recorder's
    recent replication events, ``/debug/perf``, the device-health and
    perf-ledger snapshot, and ``/debug``, an index of every debug
    endpoint (see docs/observability.md)."""

    def __init__(
        self,
        registry: Registry,
        listen_addr: str = ":26660",
        logger: Logger | None = None,
    ):
        super().__init__(name="metrics-server")
        self.registry = registry
        host, _, port = listen_addr.rpartition(":")
        self._host = host.split("//")[-1] or "0.0.0.0"
        self._port = int(port)
        self.logger = logger or default_logger().with_fields(
            module="metrics"
        )
        self._httpd = None

    def on_start(self) -> None:
        registry = self.registry

        def app(environ, start_response):
            path = environ.get("PATH_INFO", "/")

            def json_response(payload) -> list[bytes]:
                import json as _json

                body = _json.dumps(payload, default=str).encode()
                start_response(
                    "200 OK",
                    [
                        ("Content-Type", "application/json"),
                        ("Content-Length", str(len(body))),
                    ],
                )
                return [body]

            if path in ("/debug", "/debug/"):
                return json_response(
                    {
                        "endpoints": [
                            {"path": p, "server": s, "help": h}
                            for p, s, h in DEBUG_ENDPOINTS
                        ]
                    }
                )
            if path == "/debug/perf":
                from cometbft_tpu.crypto.health import debug_perf_payload

                return json_response(debug_perf_payload())
            if path == "/debug/dispatch":
                from cometbft_tpu.crypto.dispatch import (
                    debug_dispatch_payload,
                )

                return json_response(debug_dispatch_payload())
            if path == "/debug/fleet":
                import os as _os

                from cometbft_tpu.utils import fleetobs

                # peers come over HTTP; SELF is read in-process — a
                # registry handle beats an HTTP round trip through
                # our own server (and kept this safe back when the
                # server was single-threaded)
                scrapes = fleetobs.scrape_fleet(
                    fleetobs.fleet_peer_targets(
                        _os.environ.get("CMT_TPU_FLEET_PEERS")  # env ok: free-form peer list — fleet_peer_targets validates each address
                    ),
                    include_self=True,
                    self_name=f"self:{self.port}",
                    self_registry=registry,
                )
                include_trace = "trace=1" in (
                    environ.get("QUERY_STRING") or ""
                )
                return json_response(
                    fleetobs.fleet_payload(
                        scrapes, include_trace=include_trace
                    )
                )
            if path == "/debug/profile":
                from urllib.parse import parse_qsl

                from cometbft_tpu.utils import profiler as _prof

                q = dict(
                    parse_qsl(environ.get("QUERY_STRING") or "")
                )
                try:
                    seconds = (
                        float(q["seconds"]) if "seconds" in q else None
                    )
                except ValueError:
                    seconds = None
                if q.get("format") == "collapsed":
                    p = _prof.profiler()
                    body = (
                        (p.collapsed(seconds) + "\n").encode()
                        if p is not None else b""
                    )
                    start_response(
                        "200 OK",
                        [
                            ("Content-Type", "text/plain; charset=utf-8"),
                            ("Content-Length", str(len(body))),
                        ],
                    )
                    return [body]
                return json_response(_prof.profile_payload(seconds))
            if path == "/trace":
                body = TRACER.export_json().encode()
                start_response(
                    "200 OK",
                    [
                        ("Content-Type", "application/json"),
                        ("Content-Length", str(len(body))),
                    ],
                )
                return [body]
            if path == "/debug/flight":
                import json as _json

                from cometbft_tpu.utils.flight import FLIGHT

                body = _json.dumps(FLIGHT.export(), default=str).encode()
                start_response(
                    "200 OK",
                    [
                        ("Content-Type", "application/json"),
                        ("Content-Length", str(len(body))),
                    ],
                )
                return [body]
            if path not in ("/metrics", "/"):
                start_response(
                    "404 Not Found", [("Content-Type", "text/plain")]
                )
                return [b"not found\n"]
            body = registry.expose().encode()
            start_response(
                "200 OK",
                [
                    (
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8",
                    ),
                    ("Content-Length", str(len(body))),
                ],
            )
            return [body]

        self._httpd = make_server(
            self._host, self._port, app,
            server_class=_ThreadingWSGIServer,
            handler_class=_QuietHandler,
        )
        threading.Thread(
            target=self._httpd.serve_forever,
            kwargs={"poll_interval": 0.2},
            name="metrics-http",
            daemon=True,
        ).start()
        self.logger.info(
            "metrics server up", addr=f"{self._host}:{self.port}"
        )

    @property
    def port(self) -> int:
        if self._httpd is not None:
            return self._httpd.server_address[1]
        return self._port

    def on_stop(self) -> None:
        httpd, self._httpd = self._httpd, None
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()


class Timer:
    """Context manager observing elapsed seconds into a histogram."""

    def __init__(self, hist):
        self._hist = hist

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._hist.observe(time.perf_counter() - self._t0)
        return False


__all__ = [
    "DEFAULT_TIME_BUCKETS",
    "Metric",
    "MetricsServer",
    "Registry",
    "Timer",
]
