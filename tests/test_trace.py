"""Span tracer tests (utils/trace): Chrome trace-event export,
thread-local parenting, bounded retention, the no-op disabled path,
each span's thread CPU time (``tdur``), and the /trace surface on the
metrics HTTP server."""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.request

import pytest

from cometbft_tpu.utils import trace as trace_mod
from cometbft_tpu.utils.trace import SpanTracer


class TestSpanTracer:
    def test_nested_spans_parent_and_containment(self):
        t = SpanTracer(capacity=64, enabled=True)
        with t.span("outer", cat="test", k=1):
            time.sleep(0.001)
            with t.span("inner", cat="test"):
                time.sleep(0.001)
        events = t.events()
        assert [e["name"] for e in events] == ["inner", "outer"]
        inner, outer = events
        assert inner["args"]["parent"] == "outer"
        assert "parent" not in outer["args"]
        # time containment (what makes Perfetto nest the slices)
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert inner["tid"] == outer["tid"]

    def test_export_round_trips_to_valid_chrome_trace_json(self):
        t = SpanTracer(capacity=64, enabled=True)
        with t.span("a", cat="test", detail="x"):
            pass
        t.add_complete("b", time.perf_counter(), 0.01, cat="test")
        doc = json.loads(t.export_json())
        events = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        span_events = [e for e in events if e["ph"] == "X"]
        assert {e["name"] for e in span_events} == {"a", "b"}
        for e in span_events:
            # the Chrome trace-event required fields, correctly typed
            assert isinstance(e["name"], str)
            assert isinstance(e["cat"], str)
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert isinstance(e["pid"], int)
            assert isinstance(e["tid"], int)
            assert isinstance(e["args"], dict)
        # thread-name metadata events for every tid present
        meta_tids = {
            e["tid"] for e in events if e.get("ph") == "M"
        }
        assert {e["tid"] for e in span_events} <= meta_tids

    def test_ring_buffer_bounds_retention(self):
        t = SpanTracer(capacity=8, enabled=True)
        for i in range(50):
            with t.span(f"s{i}", cat="test"):
                pass
        events = t.events()
        assert len(events) == 8
        # newest retained, oldest dropped
        assert events[-1]["name"] == "s49"
        assert t.export()["otherData"]["dropped_spans"] == 42

    def test_disabled_tracer_is_allocation_free(self):
        t = SpanTracer(capacity=8, enabled=False)
        spans = [t.span("hot", batch=4096) for _ in range(3)]
        # one shared no-op object: the disabled hot path allocates
        # nothing per call
        assert spans[0] is spans[1] is spans[2]
        with spans[0] as sp:
            sp.set(ok=True)
        t.add_complete("x", time.perf_counter(), 0.1)
        assert t.events() == []

    def test_spans_on_different_threads_do_not_cross_parent(self):
        t = SpanTracer(capacity=64, enabled=True)
        done = threading.Event()

        def other():
            with t.span("other-thread", cat="test"):
                pass
            done.set()

        with t.span("main-thread", cat="test"):
            th = threading.Thread(target=other)
            th.start()
            done.wait(5)
            th.join(5)
        by_name = {e["name"]: e for e in t.events()}
        # the concurrent main-thread span is NOT the other thread's
        # parent — parenting is thread-local
        assert "parent" not in by_name["other-thread"]["args"]
        assert by_name["other-thread"]["tid"] != by_name["main-thread"]["tid"]

    def test_exception_inside_span_still_records_and_tags(self):
        t = SpanTracer(capacity=8, enabled=True)
        try:
            with t.span("boom", cat="test"):
                raise ValueError("x")
        except ValueError:
            pass
        (e,) = t.events()
        assert e["name"] == "boom"
        assert e["args"]["error"] == "ValueError"
        # the stack unwound: a following span has no stale parent
        with t.span("after", cat="test"):
            pass
        assert "parent" not in t.events()[-1]["args"]


def _spin_until(deadline: float) -> int:
    """Pure-Python work until ``deadline`` on the perf_counter clock."""
    n = 0
    while time.perf_counter() < deadline:
        n += 1
    return n


def _plain(t, clock):
    with t.span("plain", cat="test", thread_clock=clock):
        _spin_until(time.perf_counter() + 0.002)


def _nested(t, clock):
    with t.span("outer", cat="test", thread_clock=clock):
        with t.span("inner", cat="test", thread_clock=clock):
            _spin_until(time.perf_counter() + 0.001)
        time.sleep(0.002)


def _raising(t, clock):
    with pytest.raises(ValueError):
        with t.span("boom", cat="test", thread_clock=clock):
            raise ValueError("x")


def _annotated(t, clock):
    t.set_annotator(lambda name: _Recorder()(name))
    with t.span("annotated", cat="test", thread_clock=clock):
        pass


def _thread_clock_step_us() -> float:
    """The smallest step this host's thread clock takes, in µs: under
    a microsecond where it is read from the scheduler's own count, a
    whole tick (10 ms on some hosts) where it is sampled."""
    c0 = c = time.thread_time()
    while c == c0:
        c = time.thread_time()
    return (c - c0) * 1e6


class TestThreadTime:
    """``tdur``: the thread's CPU time over a span, beside its wall
    time ``dur``; ``dur - tdur`` is the time the thread was not
    running."""

    @pytest.mark.parametrize("shape", [_plain, _nested, _raising,
                                       _annotated])
    @pytest.mark.parametrize("clock", [True, False])
    def test_a_span_carries_tdur_where_it_reads_the_thread_clock(
        self, shape, clock
    ):
        t = SpanTracer(capacity=16, enabled=True)
        shape(t, clock)
        events = t.events()
        assert events
        if not clock:
            assert not [e for e in events if "tdur" in e]
            return
        step = _thread_clock_step_us()
        for e in events:
            # read inside the wall clock's interval at both ends: over
            # by no more than the two roundings to a tenth of a µs and
            # one step of the thread clock
            assert 0 <= e["tdur"] <= e["dur"] + 1 + step, e

    def test_a_sleeping_span_is_off_cpu(self):
        t = SpanTracer(capacity=8, enabled=True)
        with t.span("sleep", cat="test", thread_clock=True):
            time.sleep(0.05)
        (e,) = t.events()
        assert e["dur"] >= 45_000
        assert e["tdur"] < 0.1 * e["dur"], e

    def test_a_span_racing_a_spinning_thread_waits_for_the_lock(self):
        """Two pure-Python threads share the interpreter lock at the
        default 5 ms switch interval: a span of 50 ms of wall time on
        one of them spends a good share of it waiting, off its CPU."""
        t = SpanTracer(capacity=8, enabled=True)
        stop = threading.Event()

        def spin():
            while not stop.is_set():
                pass

        interval = sys.getswitchinterval()
        sys.setswitchinterval(0.005)
        rival = threading.Thread(target=spin, daemon=True)
        rival.start()
        try:
            time.sleep(0.02)  # the rival holds the lock by now
            with t.span("contended", cat="test", thread_clock=True):
                _spin_until(time.perf_counter() + 0.05)
        finally:
            stop.set()
            rival.join(5)
            sys.setswitchinterval(interval)
        assert not rival.is_alive()
        (e,) = t.events()
        assert e["dur"] - e["tdur"] >= 0.2 * e["dur"], e

    def test_a_disabled_tracer_reads_no_clock(self, monkeypatch):
        class NoClock:
            def __getattr__(self, name):
                raise AssertionError(f"time.{name} read")

        t = SpanTracer(capacity=8, enabled=False)
        monkeypatch.setattr(trace_mod, "time", NoClock())
        with t.span("hot", thread_clock=True, batch=4) as sp:
            sp.set(ok=True)
        t.add_complete("x", 0.0, 0.1, thread_s=0.05)
        monkeypatch.undo()
        assert t.events() == []

    @pytest.mark.parametrize("thread_s, tdur", [
        (0.004, 4000.0), (0.00000123, 1.2), (None, None),
    ])
    def test_add_complete_writes_tdur_only_when_given(self, thread_s,
                                                      tdur):
        t = SpanTracer(capacity=8, enabled=True)
        t.add_complete("step", time.perf_counter(), 0.01, cat="test",
                       thread_s=thread_s)
        (e,) = t.events()
        assert e.get("tdur") == tdur
        assert e["dur"] == 10_000.0


class TestTraceEndpoint:
    def test_metrics_server_serves_trace_next_to_metrics(self):
        from cometbft_tpu.utils.metrics import MetricsServer, Registry

        with trace_mod.TRACER.span("endpoint-test", cat="test"):
            pass
        srv = MetricsServer(Registry(), "127.0.0.1:0")
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            body = urllib.request.urlopen(
                base + "/trace", timeout=5
            ).read()
            doc = json.loads(body)
            names = {
                e["name"] for e in doc["traceEvents"] if e["ph"] == "X"
            }
            assert "endpoint-test" in names
            # /metrics still serves the exposition
            text = urllib.request.urlopen(
                base + "/metrics", timeout=5
            ).read().decode()
            assert text.endswith("\n")
        finally:
            srv.stop()

    def test_global_tracer_default_enabled(self):
        # the process-wide tracer records unless CMT_TPU_TRACE=0
        before = len(trace_mod.TRACER.events())
        with trace_mod.TRACER.span("global-check", cat="test"):
            pass
        assert len(trace_mod.TRACER.events()) >= min(
            before + 1, trace_mod.TRACER._events.maxlen
        )

    def test_explicit_parent_arg_survives_when_stack_empty(self):
        """Cross-thread / after-the-fact spans link into a tree via an
        explicit parent arg (the height-pipeline convention): with no
        lexical parent on the stack, the caller's value is kept."""
        t = SpanTracer(capacity=16, enabled=True)
        with t.span("child", cat="test", parent="synthetic-root"):
            pass
        t.add_complete(
            "mark", time.perf_counter(), 0.0, cat="test",
            args={"parent": "synthetic-root"},
        )
        by_name = {e["name"]: e for e in t.events()}
        assert by_name["child"]["args"]["parent"] == "synthetic-root"
        assert by_name["mark"]["args"]["parent"] == "synthetic-root"


class TestHeightPipeline:
    """ISSUE 5 acceptance (b): a committed height is ONE connected
    span tree — proposal receipt → quorum marks → commit pipeline
    (store save, WAL boundary, ABCI finalize/commit) — rooted at
    height/pipeline (docs/observability.md "Reading a height pipeline
    trace")."""

    def test_committed_height_yields_connected_span_tree(self, tmp_path):
        from cometbft_tpu.abci.kvstore import KVStoreApp
        from cometbft_tpu.config import test_config as make_test_config
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.node import Node
        from cometbft_tpu.privval import FilePV
        from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

        pv = FilePV(ed.priv_key_from_secret(b"pipeline-val"))
        gen = GenesisDoc(
            chain_id="pipeline-chain",
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=(GenesisValidator(pv.pub_key, 10),),
        )
        cfg = make_test_config(str(tmp_path))
        cfg.base.db_backend = "sqlite"  # live WAL -> wal/* spans
        cfg.ensure_dirs()
        # the global ring may hold height-2 spans from OTHER tests'
        # nodes; this tree analysis needs only ours
        trace_mod.TRACER.clear()
        node = Node(cfg, app=KVStoreApp(), genesis=gen, priv_validator=pv)
        node.start()

        def indexed_2() -> bool:
            # the indexer runs on its own thread, behind the commit
            return any(
                e["name"] == "indexer/index_block"
                and e["args"].get("height") == 2
                for e in trace_mod.TRACER.events()
            )

        try:
            deadline = time.time() + 30
            while time.time() < deadline and (
                node.height() < 3 or not indexed_2()
            ):
                time.sleep(0.05)
            assert node.height() >= 3
        finally:
            node.stop()

        events = trace_mod.TRACER.events()
        roots = [
            e
            for e in events
            if e["name"] == "height/pipeline"
            and e["args"].get("height") == 2
        ]
        assert roots, "no height/pipeline root for height 2"
        root = roots[-1]

        # spans of height 2's tree, linked by args.parent chains
        h2 = [
            e
            for e in events
            if e is not root
            and (
                e["args"].get("height") == 2
                or e["args"].get("parent")
                in ("height/commit_pipeline", "exec/apply_block",
                    "exec/finalize", "exec/commit")
            )
        ]
        by_name: dict[str, list[dict]] = {}
        for e in h2:
            by_name.setdefault(e["name"], []).append(e)

        # one stage of each kind exists for height 2
        for required in (
            "consensus/Propose",
            "consensus/Prevote",
            "consensus/Precommit",
            "height/proposal_received",
            "height/quorum_prevote",
            "height/quorum_precommit",
            "height/commit_pipeline",
            "store/save_block",
            "wal/write_end_height",
            "exec/apply_block",
            "exec/finalize",
            "exec/commit",
            "abci/finalize_block",
            "abci/commit",
        ):
            assert required in by_name, (
                f"{required} missing from height-2 tree; "
                f"have {sorted(by_name)}"
            )

        # connectivity: every stage's parent chain reaches the root
        parent_of = {
            "consensus/Propose": "height/pipeline",
            "consensus/Prevote": "height/pipeline",
            "consensus/Precommit": "height/pipeline",
            "height/proposal_received": "height/pipeline",
            "height/quorum_prevote": "height/pipeline",
            "height/quorum_precommit": "height/pipeline",
            "height/commit_pipeline": "height/pipeline",
            "store/save_block": "height/commit_pipeline",
            "wal/write_end_height": "height/commit_pipeline",
            "exec/apply_block": "height/commit_pipeline",
            # the apply's stages (PR 36) between it and the app
            "exec/finalize": "exec/apply_block",
            "exec/commit": "exec/apply_block",
            "abci/finalize_block": "exec/finalize",
            "abci/commit": "exec/commit",
        }
        for name, expected_parent in parent_of.items():
            span = by_name[name][0]
            assert span["args"].get("parent") == expected_parent, (
                name, span["args"],
            )
            # walk to the root
            cur, hops = name, 0
            while cur != "height/pipeline":
                cur = parent_of.get(cur) or by_name[cur][0]["args"].get(
                    "parent"
                )
                hops += 1
                assert cur is not None and hops < 10, name
        # the commit pipeline is time-contained in the root span
        cp = by_name["height/commit_pipeline"][0]
        assert root["ts"] <= cp["ts"]
        assert cp["ts"] + cp["dur"] <= root["ts"] + root["dur"] + 1.0
        # the async indexer span links in by explicit parent
        idx = [
            e
            for e in events
            if e["name"] == "indexer/index_block"
            and e["args"].get("height") == 2
        ]
        assert idx and idx[0]["args"].get("parent") == "height/pipeline"


class _Recorder:
    """An annotator that logs (thread, "enter"/"exit", name)."""

    def __init__(self):
        self.log: list[tuple] = []

    def __call__(self, name):
        rec = self

        class _Ctx:
            def __enter__(self):
                rec.log.append((threading.get_ident(), "enter", name))

            def __exit__(self, *exc):
                rec.log.append((threading.get_ident(), "exit", name))

        return _Ctx()


class TestAnnotator:
    """ISSUE 26: the seam that puts every lexical span on the
    profiler's clock (``cometbft_tpu/ops`` installs
    ``jax.profiler.TraceAnnotation``)."""

    def test_unset_by_default_and_module_stays_stdlib_only(self):
        import ast
        import inspect

        assert SpanTracer(capacity=8, enabled=True)._annotate is None
        tree = ast.parse(inspect.getsource(trace_mod))
        imported = {
            (n.module or "") if isinstance(n, ast.ImportFrom)
            else n.names[0].name
            for n in ast.walk(tree)
            if isinstance(n, (ast.Import, ast.ImportFrom))
        }
        assert not any(m.split(".")[0] == "jax" for m in imported)

    def test_entered_and_left_in_lifo_order_per_thread(self):
        t = SpanTracer(capacity=64, enabled=True)
        rec = _Recorder()
        t.set_annotator(rec)
        gate = threading.Barrier(2, timeout=10)

        def work(tag):
            with t.span(f"{tag}/outer"):
                gate.wait()  # both threads hold a span open at once
                with t.span(f"{tag}/inner"):
                    pass
                with t.span(f"{tag}/second"):
                    pass

        threads = [threading.Thread(target=work, args=(tag,))
                   for tag in ("a", "b")]
        for th in threads:
            th.start()
        for th in threads:
            th.join(10)
            assert not th.is_alive()
        by_thread: dict[int, list] = {}
        for tid, what, name in rec.log:
            by_thread.setdefault(tid, []).append((what, name))
        assert len(by_thread) == 2
        for calls in by_thread.values():
            tag = calls[0][1].split("/")[0]
            assert calls == [
                ("enter", f"{tag}/outer"),
                ("enter", f"{tag}/inner"), ("exit", f"{tag}/inner"),
                ("enter", f"{tag}/second"), ("exit", f"{tag}/second"),
                ("exit", f"{tag}/outer"),
            ]
        assert len(t.events()) == 6

    def test_annotation_sits_inside_the_spans_own_interval(self):
        t = SpanTracer(capacity=8, enabled=True)
        stamps = {}

        class _Ctx:
            def __enter__(self):
                stamps["enter"] = time.perf_counter()

            def __exit__(self, *exc):
                stamps["exit"] = time.perf_counter()

        t.set_annotator(lambda name: _Ctx())
        with t.span("timed"):
            pass
        (e,) = t.events()
        start = t.epoch + e["ts"] / 1e6
        assert start <= stamps["enter"] + 1e-6
        assert stamps["exit"] <= start + e["dur"] / 1e6 + 1e-6

    def test_disabled_tracer_and_add_complete_never_annotate(self):
        rec = _Recorder()
        off = SpanTracer(capacity=8, enabled=False)
        off.set_annotator(rec)
        with off.span("hot", batch=1):
            pass
        assert off.span("a") is off.span("b")  # still the shared no-op
        on = SpanTracer(capacity=8, enabled=True)
        on.set_annotator(rec)
        on.add_complete("after-the-fact", time.perf_counter(), 0.01)
        assert rec.log == []
        assert [e["name"] for e in on.events()] == ["after-the-fact"]

    def test_an_exception_leaves_the_annotation_too(self):
        t = SpanTracer(capacity=8, enabled=True)
        rec = _Recorder()
        t.set_annotator(rec)
        try:
            with t.span("boom"):
                raise ValueError("x")
        except ValueError:
            pass
        assert [c[1:] for c in rec.log] == [
            ("enter", "boom"), ("exit", "boom"),
        ]

    def test_a_span_stands_in_the_profilers_host_plane(self, tmp_path):
        """Under a jax.profiler session (CPU backend) the process-wide
        tracer's spans are in the ``/host:CPU`` plane under their own
        names, as the benchmark's reduction reads them."""
        import os
        import sys

        import jax.numpy as jnp

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if repo not in sys.path:
            sys.path.insert(0, repo)
        from benchmark import run, trace_reduce

        import cometbft_tpu.ops  # noqa: F401 — installs the annotator

        def work():
            with trace_mod.TRACER.span("stagetest/outer", cat="test"):
                with trace_mod.TRACER.span("stagetest/inner", cat="test"):
                    jnp.arange(8).sum().block_until_ready()

        work()  # compile outside the session
        run.traced(work, str(tmp_path))
        planes = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
        spans = trace_reduce.host_spans(planes, ("stagetest/",))
        assert [s[0] for s in spans] == ["stagetest/outer",
                                         "stagetest/inner"]
        (_, o_start, o_dur), (_, i_start, i_dur) = spans
        assert o_start <= i_start and i_start + i_dur <= o_start + o_dur
        ring = [e for e in trace_mod.TRACER.events()
                if e["name"] == "stagetest/outer"]
        # the ring's interval holds the profiler's (entered after the
        # start is taken, left before the end is)
        assert ring[-1]["dur"] * 1e3 >= o_dur
