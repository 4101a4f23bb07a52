"""Lightweight span tracing for the device execution path.

The metrics plane (utils/metrics.py) answers "how much / how often";
this module answers "what happened inside THIS commit".  Spans are
context managers with thread-local parenting, retained in a bounded
ring buffer and exported as Chrome trace-event JSON (the
``traceEvents`` object format) that chrome://tracing and Perfetto load
directly — the round-4/5 dispatch-calibration incident (mid-size
batches silently routed to a high-RTT device for a full round) is
exactly the shape of problem a launch-level timeline makes visible
without an ad-hoc bench run.

Design constraints, in order:

- **Hot-path cost**: spans wrap whole consensus steps, VerifyCommit
  calls, and device launches — never per-signature work.  A disabled
  tracer returns one shared no-op span object, so the disabled path
  allocates nothing.
- **Bounded retention**: completed spans land in a ``deque(maxlen=N)``
  (CMT_TPU_TRACE_RING, default 4096) — a long-running node keeps the
  most recent window, never an unbounded log.
- **No dependencies**: stdlib only; importable from every plane
  (crypto, ops, consensus, tools) without dragging jax in.
- **Waiting or working**: a span opened with ``thread_clock=True``
  also reads its thread's CPU clock (``time.thread_time``) at both
  ends and records it as ``tdur``, the Chrome trace-event field for a
  slice's thread-clock duration.  ``dur - tdur`` is the span's off-CPU
  time: the thread was ready or blocked, not running (the interpreter
  lock, the OS's own preemption, I/O, or a device wait).  Only the
  spans a metric reads ask for it: on some hosts the thread clock is a
  system call of several microseconds that advances in scheduler ticks
  (10 ms on the TPU v5e host), so one span's ``tdur`` may read 0 or a
  whole tick more than ``dur``, and only a mean over many spans means
  anything.  ``add_complete`` records it only where the caller passes
  ``thread_s``.
- **One clock with the device trace**: ``set_annotator`` takes a
  ``name -> context manager`` callable (``cometbft_tpu/ops`` installs
  ``jax.profiler.TraceAnnotation``); every lexical span then also
  stands in a ``jax.profiler`` session's host plane under its own
  name, on its own thread, beside the device programs.  Unset by
  default; outside a session the annotation is a no-op in the runtime.

Surfaces: the metrics HTTP server serves ``/trace`` next to
``/metrics``; the Inspector exposes a ``trace`` JSON-RPC route; and
bench.py dumps the same JSON next to its result for provenance.
"""

from __future__ import annotations

import json
import os
import threading
import time
import weakref
from collections import deque


class _NopSpan:
    """Shared do-nothing span — the disabled tracer's return value.

    A singleton so ``tracer.span(...)`` allocates nothing when tracing
    is off (mirrors the metrics plane's ``_Nop``)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NOP_SPAN = _NopSpan()

#: the shared no-op span, for call sites that need an explicitly inert
#: context manager (e.g. consensus skipping spans during WAL replay)
NOP_SPAN = _NOP_SPAN

_DEFAULT_RING = 4096

#: live tracers whose cached pid must be refreshed in fork children
_PID_TRACERS: "weakref.WeakSet[SpanTracer]" = weakref.WeakSet()

if hasattr(os, "register_at_fork"):
    os.register_at_fork(
        after_in_child=lambda: [
            setattr(t, "_pid", os.getpid()) for t in _PID_TRACERS
        ]
    )


class _Span:
    """One in-flight span; records a complete ("ph": "X") event on exit."""

    __slots__ = (
        "_tracer", "name", "cat", "args", "_t0", "_c0", "_parent",
        "_annotation",
    )

    def __init__(self, tracer: "SpanTracer", name: str, cat: str,
                 args: dict, thread_clock: bool):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._annotation = None
        #: the thread clock at entry; None: the span reads no thread clock
        self._c0 = 0.0 if thread_clock else None

    def set(self, **args) -> None:
        """Attach result data discovered mid-span (e.g. batch verdict)."""
        self.args.update(args)

    def __enter__(self):
        stack = self._tracer._stack()
        self._parent = stack[-1].name if stack else None
        stack.append(self)
        # per-thread current-span map: what the sampling profiler
        # (utils/profiler.py) reads to tag a sampled stack with the
        # pipeline stage it ran under.  Plain dict store — atomic under
        # the GIL, and this is the lexical-span hot path.
        self._tracer._active[threading.get_ident()] = self.name
        self._t0 = time.perf_counter()
        if self._c0 is not None:
            # read inside the wall clock's interval at both ends
            self._c0 = time.thread_time()
        # the annotation sits INSIDE the span's own interval (entered
        # after the start is taken, left before the end is), so the
        # ring's duration never reads shorter than the profiler's
        annotate = self._tracer._annotate
        if annotate is not None:
            self._annotation = annotate(self.name)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        c0 = self._c0
        thread_s = None if c0 is None else time.thread_time() - c0
        end = time.perf_counter()
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        tid = threading.get_ident()
        if stack:
            self._tracer._active[tid] = stack[-1].name
        else:
            self._tracer._active.pop(tid, None)
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self._tracer._record(
            self.name, self.cat, self._t0, end - self._t0, self.args,
            self._parent, thread_s,
        )
        return False


class SpanTracer:
    """Bounded ring of completed spans, Chrome-trace-JSON exportable.

    ``span(name, **args)`` is the lexical entry point; spans started on
    the same thread nest (thread-local parent stack, the parent's name
    lands in the child's args).  ``add_complete`` records a span after
    the fact from explicit perf_counter timestamps — used by the
    consensus state machine, whose steps begin and end at different
    call sites; such spans stay ring-only (an annotation cannot be
    written after the fact), and carry ``tdur`` only where the caller
    read its own thread's clock (``thread_s``).
    """

    def __init__(
        self,
        capacity: int | None = None,
        enabled: bool | None = None,
    ):
        if capacity is None:
            # same validation contract as CMT_TPU_FLIGHT_DEPTH
            from cometbft_tpu.utils.flight import ring_size_from_env

            capacity = ring_size_from_env(
                "CMT_TPU_TRACE_RING", _DEFAULT_RING
            )
        if enabled is None:
            from cometbft_tpu.utils.env import flag_from_env

            enabled = flag_from_env("CMT_TPU_TRACE", default=True)
        self.enabled = enabled
        self._events: deque[dict] = deque(maxlen=max(capacity, 1))
        self._mtx = threading.Lock()
        self._tls = threading.local()
        #: tid -> innermost OPEN lexical span name; entries are removed
        #: when a thread's span stack drains, so the map stays bounded
        #: by threads with a span in flight (read by the profiler)
        self._active: dict[int, str] = {}
        #: perf_counter origin; event ts values are microseconds since
        #: this instant (Chrome traces need any consistent monotonic us)
        self.epoch = time.perf_counter()
        #: wall clock captured at the same instant as ``epoch`` — the
        #: anchor that lets the fleet aggregator place this ring's
        #: (monotonic-derived) span timestamps on a cross-node wall
        #: timeline: wall_of(ts_us) = epoch_wall + ts_us/1e6
        self.epoch_wall = time.time()
        self._dropped = 0
        # getpid() is a real syscall on sandboxed kernels (~10us) —
        # cache it; _PID_TRACERS refreshes after fork
        self._pid = os.getpid()
        #: tid -> thread name, captured at record time — a track must
        #: keep its name after its thread exits
        self._thread_names: dict[int, str] = {}
        #: name -> context manager entered around every lexical span
        #: (module docstring); None keeps this module stdlib-only
        self._annotate = None
        _PID_TRACERS.add(self)

    # -- recording -----------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def span(self, name: str, cat: str = "app", thread_clock: bool = False,
             **args):
        """A context-manager span; the shared no-op when disabled.
        ``thread_clock``: record the thread's CPU time as ``tdur`` too
        (module docstring: for the spans a metric reads, not by
        default)."""
        if not self.enabled:
            return _NOP_SPAN
        return _Span(self, name, cat, args, thread_clock)

    def add_complete(
        self,
        name: str,
        start: float,
        duration_s: float,
        cat: str = "app",
        args: dict | None = None,
        thread_s: float | None = None,
    ) -> None:
        """Record a span from explicit ``time.perf_counter()`` values
        (``start`` in perf_counter time, not trace microseconds).
        ``thread_s``: the recording thread's ``time.thread_time()``
        over the same interval, written as ``tdur``; a span that waits
        across threads has none."""
        if not self.enabled:
            return
        self._record(
            name, cat, start, duration_s, args or {}, None, thread_s
        )

    def _record(
        self,
        name: str,
        cat: str,
        start: float,
        duration_s: float,
        args: dict,
        parent: str | None,
        thread_s: float | None = None,
    ) -> None:
        if parent is not None:
            args = dict(args, parent=parent)
        thread = threading.current_thread()
        event = {
            "name": name,
            "cat": cat,
            "ph": "X",
            # tenths of a microsecond; int() because round(x, 1) costs
            # half a microsecond a call (it goes through dtoa)
            "ts": int(max(start - self.epoch, 0.0) * 1e7 + 0.5) / 10,
            "dur": int(max(duration_s, 0.0) * 1e7 + 0.5) / 10,
            "pid": self._pid,
            "tid": thread.ident,
            "args": args,
        }
        if thread_s is not None:
            event["tdur"] = int(max(thread_s, 0.0) * 1e7 + 0.5) / 10
        with self._mtx:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(event)
            self._thread_names[thread.ident] = thread.name
            if len(self._thread_names) > 1024:
                live = {e["tid"] for e in self._events}
                self._thread_names = {
                    t: n
                    for t, n in self._thread_names.items()
                    if t in live
                }

    # -- introspection -------------------------------------------------

    def current_spans(self) -> dict[int, str]:
        """Snapshot of tid -> innermost open lexical span name — the
        attribution seam the sampling profiler tags samples with.
        Spans recorded via ``add_complete`` (the consensus step spans)
        never appear here: they are reconstructed after the fact, not
        open while their work runs."""
        return dict(self._active)

    # -- export --------------------------------------------------------

    def events(self) -> list[dict]:
        """Snapshot of retained span events, oldest first."""
        with self._mtx:
            return list(self._events)

    def export(self) -> dict:
        """Chrome trace-event JSON (object form) — load in Perfetto /
        chrome://tracing.  Thread-name metadata events are synthesized
        (names captured at record time, so a track keeps its name
        after its thread exits) so tracks read as thread names, not
        bare idents."""
        with self._mtx:
            events = list(self._events)
            names = dict(self._thread_names)
        pid = self._pid
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": names.get(tid, f"thread-{tid}")},
            }
            for tid in sorted({e["tid"] for e in events})
        ]
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "dropped_spans": self._dropped,
                # fleet plane: the wall anchor for cross-node stitching
                "wall_epoch": self.epoch_wall,
                "pid": pid,
            },
        }

    def export_json(self) -> str:
        return json.dumps(self.export(), default=str)

    def dump(self, path: str) -> None:
        """Atomically write the export to ``path`` (tmp + rename);
        the shared provenance-dump helper for bench/campaign drivers."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.export_json())
        os.replace(tmp, path)

    def clear(self) -> None:
        with self._mtx:
            self._events.clear()
            self._thread_names.clear()
            self._dropped = 0

    def set_enabled(self, enabled: bool) -> None:
        self.enabled = bool(enabled)

    def set_annotator(self, annotate) -> None:
        """``annotate(name)`` -> a context manager entered inside every
        lexical span from now on (None: none).  A disabled tracer never
        calls it."""
        self._annotate = annotate


#: process-wide tracer — all planes record here, all surfaces read here
TRACER = SpanTracer()


__all__ = ["NOP_SPAN", "SpanTracer", "TRACER"]
