"""Observability-plane tests: distributed tracing (span trees, oim-trace
propagation across real gRPC hops incl. the transparent proxy), labeled
metrics + histograms in valid Prometheus text format, secret redaction of
repeated/map fields, metrics drift (every canonical metric referenced),
millisecond/JSON logging, and the /debug/spans + bind-host metrics server."""

from __future__ import annotations

import io
import json
import os
import re
import subprocess
import sys
import urllib.request

import grpc
import pytest

from oim_tpu.common import metrics, tracing
from oim_tpu.common import logging as oim_logging
from oim_tpu.common.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsServer,
    Registry,
)
from oim_tpu.common.server import NonBlockingGRPCServer
from oim_tpu.common.tlsutil import dial
from oim_tpu.spec import (
    RegistryServicer,
    RegistryStub,
    add_registry_to_server,
    pb,
)

# A light Prometheus text-format grammar: every non-comment line must be
# `name{labels} value`, optionally followed by an OpenMetrics exemplar
# (` # {trace_id="..."} value timestamp`) on histogram bucket lines.
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\["\\n])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\["\\n])*")*\})?'
    r' -?[0-9.eE+\-]+'
    r'( # \{trace_id="(?:[^"\\\n]|\\["\\n])*"\}'
    r' -?[0-9.eE+\-]+ [0-9.]+)?$')


def assert_valid_prometheus(text: str) -> None:
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE_RE.match(line), f"invalid sample line: {line!r}"


# -- tracing core ----------------------------------------------------------


class TestSpans:
    def test_nesting_and_ids(self):
        with tracing.start_span("parent") as p:
            assert tracing.current() is p
            assert tracing.trace_id() == p.trace_id
            with tracing.start_span("child", volume="v") as c:
                assert c.trace_id == p.trace_id
                assert c.parent_id == p.span_id
                assert c.span_id != p.span_id
        assert tracing.current() is None
        assert len(p.trace_id) == 32 and len(p.span_id) == 16

    def test_explicit_parent_beats_ambient(self):
        remote = tracing.SpanContext("ab" * 16, "cd" * 8)
        with tracing.start_span("ambient"):
            with tracing.start_span("server", parent=remote) as s:
                assert s.trace_id == remote.trace_id
                assert s.parent_id == remote.span_id

    def test_metadata_roundtrip(self):
        with tracing.start_span("op") as span:
            md = tracing.inject([("other", "x")])
        assert ("other", "x") in md
        ctx = tracing.extract(md)
        assert ctx == span.context
        # traceparent shape: 00-<32>-<16>-01
        value = dict(md)[tracing.TRACE_METADATA_KEY]
        assert re.fullmatch(r"00-[0-9a-f]{32}-[0-9a-f]{16}-01", value)

    def test_inject_without_span_is_passthrough(self):
        md = [(tracing.TRACE_METADATA_KEY, "00-" + "a" * 32 + "-" + "b" * 16 + "-01")]
        assert tracing.inject(md) == md  # explicit injection survives

    def test_extract_rejects_garbage(self):
        for bad in ("", "nope", "00-short-short-01", "x-y"):
            assert tracing.extract([(tracing.TRACE_METADATA_KEY, bad)]) is None
        assert tracing.extract(None) is None

    def test_ring_buffer_caps(self):
        rec = tracing.SpanRecorder("t", capacity=4)
        for i in range(10):
            span = tracing.Span(f"s{i}", tracing.SpanContext("a" * 32, "b" * 16))
            span.finish()
            rec.record(span)
        names = [s.name for s in rec.spans()]
        assert names == ["s6", "s7", "s8", "s9"]

    def test_chrome_export_and_streaming(self, tmp_path):
        rec = tracing.SpanRecorder("svc", trace_dir=str(tmp_path))
        with tracing.start_span("op", answer=42) as span:
            pass
        rec.record(span)
        # Complete export.
        out = tmp_path / "full.json"
        rec.export(str(out))
        doc = json.loads(out.read_text())
        events = doc["traceEvents"]
        assert events[0] == {"name": "process_name", "ph": "M",
                             "pid": rec.pid, "args": {"name": "svc"}}
        ev = events[1]
        assert ev["ph"] == "X" and ev["dur"] >= 0 and ev["args"]["answer"] == 42
        # The streamed file parses even though the array is unterminated
        # (the crash-safe property the SIGKILLed daemon relies on).
        rec.close()
        streamed = list(tmp_path.glob("svc-*.trace.json"))
        assert len(streamed) == 1
        assert not streamed[0].read_text().rstrip().endswith("]")
        loaded = tracing.load_trace_file(str(streamed[0]))
        assert any(e.get("ph") == "X" for e in loaded)
        merged = tracing.merge_trace_dir(
            str(tmp_path), str(tmp_path / "merged.json"))
        assert json.loads((tmp_path / "merged.json").read_text())[
            "traceEvents"] == merged


# -- the bridge onto the profiler's clock ------------------------------------


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestProfilerBridge:
    """``annotate`` / ``start_span`` put ``oim.<name>`` into the
    ``jax.profiler`` trace without this module ever importing JAX, and
    without changing what the ring, the file and /debug/spans hold."""

    @pytest.mark.parametrize("body", [
        "with tracing.start_span('x', slot=3): pass",
        "with tracing.annotate('y'): pass",
        "tracing.record_phase('serve.decode', 0.0, 1.0)",
        "assert tracing.annotate('y') is tracing.annotate('z')",
        "from oim_tpu.common import server, tlsutil",
    ], ids=["start_span", "annotate", "record_phase", "one-shared-no-op",
            "rpc-plumbing"])
    def test_never_imports_jax(self, body):
        """In a fresh interpreter (this one has JAX from conftest): what
        ``oim_registry`` and ``oim_controller --backend malloc`` rely on."""
        code = ("import sys\nfrom oim_tpu.common import tracing\n"
                f"{body}\n"
                "bad = [m for m in sys.modules if m == 'jax' "
                "or m.startswith(('jax.', 'jaxlib'))]\n"
                "assert not bad, bad\n"
                "assert len(tracing.recorder().spans()) <= 1\n")
        done = subprocess.run(
            [sys.executable, "-c", code], cwd=_REPO, capture_output=True,
            text=True, timeout=60,
            env={**os.environ, "PYTHONPATH": _REPO})
        assert done.returncode == 0, done.stderr

    @pytest.fixture(scope="class")
    def profiled(self, tmp_path_factory):
        """One CPU ``jax.profiler`` session around a span with attributes,
        a bare annotation inside it, a span on another thread and a span
        whose body raises; the host plane as ``benchmarks/reduce.py`` loads
        it, and what the ring got meanwhile."""
        import threading

        import jax

        from benchmarks import reduce

        rec = tracing.configure("bridge-test")
        trace_dir = str(tmp_path_factory.mktemp("profile"))
        jax.profiler.start_trace(trace_dir)
        try:
            with tracing.start_span("bridge.outer", slot=3, volume="v"):
                with tracing.annotate("bridge.inner"):
                    jax.numpy.ones((8, 8)).sum().block_until_ready()
                tracing.record_phase("bridge.phase", 0.0, 1.0)
            def on_another_thread():
                with tracing.start_span("bridge.thread"):
                    pass

            worker = threading.Thread(target=on_another_thread)
            worker.start()
            worker.join(10)
            with pytest.raises(KeyError):
                with tracing.start_span("bridge.raises"):
                    raise KeyError("x")
        finally:
            jax.profiler.stop_trace()
        with tracing.start_span("bridge.after"):  # no session: inert
            pass
        planes = reduce.load(reduce.find_xplane(trace_dir))["planes"]
        host = [((i, j), name, start, start + dur)  # (i, j): one thread
                for i, plane in enumerate(planes)
                for j, line in enumerate(plane["lines"])
                for name, start, dur in line["events"]]
        yield {"host": host, "ring": rec.spans(), "events": rec.to_events()}
        tracing.configure("oim")

    @pytest.mark.parametrize("name", [
        "oim.bridge.outer", "oim.bridge.inner", "oim.bridge.thread",
        "oim.bridge.raises"])
    def test_live_spans_and_annotations_are_in_the_profile(self, profiled, name):
        # exactly once, under its plain name: attributes are NOT appended
        # (the profiler would write them as #k=v#, one name per value)
        assert [n for _, n, _, _ in profiled["host"]].count(name) == 1

    @pytest.mark.parametrize("name", [
        "oim.bridge.phase", "oim.bridge.after", "bridge.outer"])
    def test_what_is_not_in_the_profile(self, profiled, name):
        """A phase recorded after the fact has no live scope to annotate;
        with no session the annotation is inert; no name goes unprefixed."""
        assert name not in [n for _, n, _, _ in profiled["host"]]

    def test_profile_nests_as_the_code_does_and_keeps_threads_apart(
            self, profiled):
        at = {n: (line, s, e) for line, n, s, e in profiled["host"]}
        outer, inner = at["oim.bridge.outer"], at["oim.bridge.inner"]
        assert outer[0] == inner[0]
        assert outer[1] <= inner[1] <= inner[2] <= outer[2]
        assert at["oim.bridge.thread"][0] != outer[0]
        assert at["oim.bridge.raises"][1] >= outer[2]

    def test_ring_and_debug_spans_are_unchanged_by_the_bridge(self, profiled):
        ring = {s.name: s for s in profiled["ring"]}
        assert list(ring) == [  # the bare annotation left no record
            "bridge.phase", "bridge.outer", "bridge.thread", "bridge.raises",
            "bridge.after"]
        assert ring["bridge.outer"].attrs == {"slot": 3, "volume": "v"}
        assert ring["bridge.phase"].parent_id == ring["bridge.outer"].span_id
        assert ring["bridge.phase"].duration == 1.0
        assert ring["bridge.outer"].duration > 0
        event = next(e for e in profiled["events"]
                     if e["name"] == "bridge.outer")
        assert set(event) == {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                              "args"}
        assert event["args"]["slot"] == 3 and event["cat"] == "oim"
        assert not any(e["name"].startswith("oim.")
                       for e in profiled["events"])


# -- telemetry interceptors over real gRPC ---------------------------------


class _Echo(RegistryServicer):
    def GetValues(self, request, context):
        # from_context() inside a handler must return the trace-bound
        # logger the telemetry interceptor installed.
        oim_logging.from_context().debug("echo", path=request.path)
        if request.path == "boom":
            context.abort(grpc.StatusCode.NOT_FOUND, "no such thing")
        return pb.GetValuesReply(values=[pb.Value(path=request.path, value="v")])


@pytest.fixture()
def echo_server():
    srv = NonBlockingGRPCServer("tcp://localhost:0")
    srv.start(lambda s: add_registry_to_server(_Echo(), s))
    yield srv
    srv.stop()


class TestTelemetryInterceptors:
    def test_client_server_share_one_trace(self, echo_server):
        before = len(tracing.recorder().spans())
        channel = dial(echo_server.addr, None)
        try:
            with tracing.start_span("test-root") as root:
                RegistryStub(channel).GetValues(
                    pb.GetValuesRequest(path="k"), timeout=5)
        finally:
            channel.close()
        spans = tracing.recorder().spans()[before:]
        by_name = {s.name: s for s in spans}
        client = by_name["client:oim.v1.Registry/GetValues"]
        server = by_name["server:oim.v1.Registry/GetValues"]
        assert client.trace_id == server.trace_id == root.trace_id
        assert client.parent_id == root.span_id
        assert server.parent_id == client.span_id
        assert client.attrs["code"] == "OK"
        assert server.attrs["code"] == "OK"

    def test_rpc_metrics_labeled_by_method_and_code(self, echo_server):
        method = "oim.v1.Registry/GetValues"
        ok = metrics.RPC_TOTAL.labels(method=method, code="OK")
        nf = metrics.RPC_TOTAL.labels(method=method, code="NOT_FOUND")
        ok0, nf0 = ok.value, nf.value
        lat_nf = metrics.RPC_LATENCY.labels(method=method, code="NOT_FOUND")
        lat0 = lat_nf.count
        channel = dial(echo_server.addr, None)
        try:
            stub = RegistryStub(channel)
            stub.GetValues(pb.GetValuesRequest(path="k"), timeout=5)
            with pytest.raises(grpc.RpcError):
                stub.GetValues(pb.GetValuesRequest(path="boom"), timeout=5)
        finally:
            channel.close()
        # Client and server vantage each record once per call.
        assert ok.value == ok0 + 2
        assert nf.value == nf0 + 2
        assert lat_nf.count == lat0 + 2

    def test_abort_code_lands_on_server_span(self, echo_server):
        before = len(tracing.recorder().spans())
        channel = dial(echo_server.addr, None)
        try:
            with pytest.raises(grpc.RpcError):
                RegistryStub(channel).GetValues(
                    pb.GetValuesRequest(path="boom"), timeout=5)
        finally:
            channel.close()
        spans = tracing.recorder().spans()[before:]
        server = next(s for s in spans if s.name.startswith("server:"))
        assert server.attrs["code"] == "NOT_FOUND"

    def test_cancelled_stream_still_counted(self):
        """An infinite server stream (the Replicate shape) ends only by
        client cancel — delivered as GeneratorExit to the response
        generator, which must still record the RPC."""
        import time as _time

        class _Forever(RegistryServicer):
            def Replicate(self, request, context):
                while True:
                    yield pb.ReplicateRecord(kind=0, offset=0)
                    _time.sleep(0.01)

        srv = NonBlockingGRPCServer("tcp://localhost:0")
        srv.start(lambda s: add_registry_to_server(_Forever(), s))
        method = "oim.v1.Registry/Replicate"
        counted = metrics.RPC_TOTAL.labels(method=method, code="CANCELLED")
        base = counted.value
        channel = dial(srv.addr, None)
        try:
            call = RegistryStub(channel).Replicate(pb.ReplicateRequest())
            next(iter(call))
            call.cancel()
            # The server-side close is asynchronous to the cancel.
            deadline = _time.monotonic() + 5
            while counted.value < base + 1 and _time.monotonic() < deadline:
                _time.sleep(0.05)
            assert counted.value >= base + 1
        finally:
            channel.close()
            srv.stop()

    def test_trace_id_bound_into_handler_logs(self, echo_server):
        buf = io.StringIO()
        prev = oim_logging.set_global(
            oim_logging.Logger(output=buf, level=oim_logging.DEBUG))
        try:
            channel = dial(echo_server.addr, None)
            try:
                RegistryStub(channel).GetValues(
                    pb.GetValuesRequest(path="k"), timeout=5)
            finally:
                channel.close()
        finally:
            oim_logging.set_global(prev)
        assert "trace_id:" in buf.getvalue()


class TestProxyPropagation:
    def test_one_trace_feeder_to_controller_through_proxy(self):
        """The acceptance chain in-process: a feeder publish crosses the
        registry's transparent proxy into a controller, and every hop's
        span carries one trace_id."""
        from oim_tpu.controller import MallocBackend, controller_server
        from oim_tpu.controller.controller import ControllerService
        from oim_tpu.feeder import Feeder
        from oim_tpu.registry import RegistryService
        from oim_tpu.registry.registry import registry_server

        backend = MallocBackend()
        backend.provision("vol-t", 4)
        controller = controller_server(
            "tcp://localhost:0", ControllerService(backend))
        service = RegistryService()
        registry = registry_server("tcp://localhost:0", service)
        try:
            service.db.set("host-0/address", controller.addr)
            service.db.set("host-0/mesh", "0,0,0")
            feeder = Feeder(registry_address=registry.addr,
                            controller_id="host-0")
            before = len(tracing.recorder().spans())
            pub = feeder.publish(pb.MapVolumeRequest(
                volume_id="vol-t",
                malloc=pb.MallocParams(),
                spec=pb.ArraySpec(shape=[4], dtype="uint8"),
            ), timeout=10)
            assert pub.volume_id == "vol-t"
            spans = tracing.recorder().spans()[before:]
            root = next(s for s in spans if s.name == "feeder.publish")
            same_trace = [s for s in spans if s.trace_id == root.trace_id]
            names = {s.name for s in same_trace}
            # feeder root + client spans + proxy hop spans + controller
            # server spans + the staging span, all on one trace.
            assert any(n.startswith("proxy:oim.v1.Controller/MapVolume")
                       for n in names), names
            assert any(n.startswith("client:oim.v1.Controller/MapVolume")
                       for n in names), names
            assert any(n.startswith("server:oim.v1.Controller/MapVolume")
                       for n in names), names
            assert "stage" in names, names
        finally:
            registry.stop()
            controller.stop()


# -- metrics ---------------------------------------------------------------


class TestLabeledMetrics:
    def test_labels_memoized_and_rendered(self):
        reg = Registry()
        c = reg.counter("t_total", "things", labelnames=("kind",))
        c.labels(kind="a").inc()
        c.labels("a").inc(2)
        c.labels(kind="b").inc()
        text = reg.render()
        assert 't_total{kind="a"} 3.0' in text
        assert 't_total{kind="b"} 1.0' in text
        assert_valid_prometheus(text)

    def test_unlabeled_api_rejected_on_labeled_metric(self):
        reg = Registry()
        c = reg.counter("x_total", labelnames=("a",))
        with pytest.raises(ValueError):
            c.inc()
        with pytest.raises(ValueError):
            c.labels("v", "extra")
        with pytest.raises(ValueError):
            c.labels(b="v")

    def test_relabeling_is_an_error(self):
        reg = Registry()
        reg.counter("y_total", labelnames=("a",))
        with pytest.raises(ValueError):
            reg.counter("y_total", labelnames=("b",))
        with pytest.raises(ValueError):
            reg.gauge("y_total", labelnames=("a",))

    def test_rebucketing_is_an_error(self):
        # Silently returning the first family would put the second
        # caller's observations in the wrong buckets.
        reg = Registry()
        reg.histogram("z_seconds", buckets=(1.0, 10.0))
        assert reg.histogram("z_seconds", buckets=(10.0, 1.0)) is not None
        with pytest.raises(ValueError):
            reg.histogram("z_seconds", buckets=(0.01, 0.1))

    def test_gauge_set_still_works(self):
        reg = Registry()
        g = reg.gauge("g")
        g.set(2.5)
        assert g.value == 2.5
        assert "g 2.5" in reg.render()

    def test_histogram_buckets_cumulative(self):
        reg = Registry()
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        for v in (0.05, 0.05, 0.5, 5.0):
            h.observe(v)
        text = reg.render()
        assert 'lat_seconds_bucket{le="0.1"} 2' in text
        assert 'lat_seconds_bucket{le="1"} 3' in text
        assert 'lat_seconds_bucket{le="+Inf"} 4' in text
        assert "lat_seconds_count 4" in text
        assert h.count == 4 and abs(h.sum - 5.6) < 1e-9
        assert_valid_prometheus(text)

    def test_labeled_histogram_merges_le(self):
        reg = Registry()
        h = reg.histogram("rpc_seconds", labelnames=("method",),
                          buckets=(1.0,))
        h.labels(method="M").observe(0.5)
        text = reg.render()
        assert 'rpc_seconds_bucket{method="M",le="1"} 1' in text
        assert 'rpc_seconds_sum{method="M"} 0.5' in text
        assert_valid_prometheus(text)


class TestTextFormatEscaping:
    def test_help_escapes_newline_and_backslash(self):
        reg = Registry()
        reg.counter("esc_total", 'line1\nline2 back\\slash')
        text = reg.render()
        assert "# HELP esc_total line1\\nline2 back\\\\slash" in text
        assert "\nline2" not in text.replace("\\n", "")
        assert_valid_prometheus(text)

    def test_label_values_escape_quote_newline_backslash(self):
        reg = Registry()
        c = reg.counter("lv_total", labelnames=("v",))
        c.labels(v='say "hi"\nback\\slash').inc()
        text = reg.render()
        assert 'lv_total{v="say \\"hi\\"\\nback\\\\slash"} 1.0' in text
        assert_valid_prometheus(text)

    def test_default_registry_renders_valid(self):
        assert_valid_prometheus(metrics.DEFAULT.render())


class TestMetricsDrift:
    def test_every_canonical_metric_is_referenced(self):
        """Every metric declared in common/metrics.py must be used by at
        least one non-test module — a metric nothing records is a dashboard
        lying about coverage."""
        import ast
        from pathlib import Path

        root = Path(__file__).resolve().parent.parent
        metrics_py = root / "oim_tpu" / "common" / "metrics.py"
        declared = []
        for node in ast.parse(metrics_py.read_text()).body:
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and isinstance(node.value.func.value, ast.Name)
                    and node.value.func.value.id == "DEFAULT"):
                declared += [t.id for t in node.targets
                             if isinstance(t, ast.Name)]
        assert len(declared) >= 20, "metric declaration parse broke"
        sources = ""
        for p in (root / "oim_tpu").rglob("*.py"):
            if p != metrics_py:
                sources += p.read_text()
        unreferenced = [
            name for name in declared
            if not re.search(rf"\b{name}\b", sources)
        ]
        assert not unreferenced, (
            f"canonical metrics never recorded by any module: {unreferenced}")

    def test_slo_plane_metrics_declared_and_shaped(self):
        """The fleet SLO plane's metric names are API (ISSUE 15): the
        monitor's burn gauge must stay labeled by SLO name, and the
        firing census unlabeled — alert dashboards key on both."""
        assert isinstance(metrics.SLO_BURN_RATE, Gauge)
        assert metrics.SLO_BURN_RATE.name == "oim_slo_burn_rate"
        assert metrics.SLO_BURN_RATE.labelnames == ("slo",)
        assert isinstance(metrics.SLO_ALERTS_FIRING, Gauge)
        assert metrics.SLO_ALERTS_FIRING.name == "oim_slo_alerts_firing"
        assert metrics.SLO_ALERTS_FIRING.labelnames == ()

    def test_autoscale_metrics_declared_and_shaped(self):
        """The fleet actuator's metric names are API (ISSUE 16):
        capacity dashboards graph desired-vs-ready as two unlabeled
        gauges, alert runbooks rate() the actions counter BY action,
        and the alert-to-ready histogram's buckets are the SLO ladder
        an episode is read against — none may drift."""
        assert isinstance(metrics.AUTOSCALE_REPLICAS_DESIRED, Gauge)
        assert (metrics.AUTOSCALE_REPLICAS_DESIRED.name
                == "oim_autoscale_replicas_desired")
        assert metrics.AUTOSCALE_REPLICAS_DESIRED.labelnames == ()
        assert isinstance(metrics.AUTOSCALE_REPLICAS_READY, Gauge)
        assert (metrics.AUTOSCALE_REPLICAS_READY.name
                == "oim_autoscale_replicas_ready")
        assert metrics.AUTOSCALE_REPLICAS_READY.labelnames == ()
        assert isinstance(metrics.AUTOSCALE_ACTIONS_TOTAL, Counter)
        assert (metrics.AUTOSCALE_ACTIONS_TOTAL.name
                == "oim_autoscale_actions_total")
        assert metrics.AUTOSCALE_ACTIONS_TOTAL.labelnames == ("action",)
        assert isinstance(metrics.AUTOSCALE_ALERT_TO_READY, Histogram)
        assert (metrics.AUTOSCALE_ALERT_TO_READY.name
                == "oim_autoscale_alert_to_ready_seconds")
        assert metrics.AUTOSCALE_ALERT_TO_READY.buckets == (
            0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)

    def test_kvtier_metrics_declared_and_shaped(self):
        """The KV-tier metric names are API (ISSUE 17): capacity
        dashboards graph the per-tier gauges unlabeled, runbooks
        rate() demotion/promotion/export counters unlabeled, and the
        peer-fetch counter stays labeled BY OUTCOME (hit/miss/error)
        — `oimctl --top` sums it across outcomes for its KV-TIER
        column, so a label rename breaks the operator view."""
        for gauge, name in (
                (metrics.KVTIER_HBM_PAGES, "oim_kvtier_hbm_pages"),
                (metrics.KVTIER_HOST_PAGES, "oim_kvtier_host_pages"),
                (metrics.KVTIER_HOST_BYTES, "oim_kvtier_host_bytes")):
            assert isinstance(gauge, Gauge)
            assert gauge.name == name
            assert gauge.labelnames == ()
        for counter, name in (
                (metrics.KVTIER_DEMOTIONS, "oim_kvtier_demotions_total"),
                (metrics.KVTIER_PROMOTIONS,
                 "oim_kvtier_promotions_total"),
                (metrics.KVTIER_EXPORTS, "oim_kvtier_exports_total"),
                (metrics.SERVE_PREFIX_PEER_TOKENS,
                 "oim_serve_prefix_peer_tokens_total")):
            assert isinstance(counter, Counter)
            assert counter.name == name
            assert counter.labelnames == ()
        assert isinstance(metrics.SERVE_PREFIX_PEER_FETCHES, Counter)
        assert (metrics.SERVE_PREFIX_PEER_FETCHES.name
                == "oim_serve_prefix_peer_fetches_total")
        assert (metrics.SERVE_PREFIX_PEER_FETCHES.labelnames
                == ("outcome",))

    def test_disagg_metrics_declared_and_shaped(self):
        """The disaggregation metric names are API (ISSUE 20): the
        role gauge stays labeled BY ROLE (`oimctl --top`'s ROLE column
        reads the label whose sample is 1), the handoff counter BY
        OUTCOME (split/exported/skipped/export_failed/fallback —
        runbooks rate() the failure outcomes), and the chunk histogram
        is what `--prefill-chunk` is tuned against: a slice must
        outlast a decode step, and these buckets bracket both."""
        assert isinstance(metrics.SERVE_ROLE, Gauge)
        assert metrics.SERVE_ROLE.name == "oim_serve_role"
        assert metrics.SERVE_ROLE.labelnames == ("role",)
        assert isinstance(metrics.SERVE_PREFILL_HANDOFFS, Counter)
        assert (metrics.SERVE_PREFILL_HANDOFFS.name
                == "oim_serve_prefill_handoffs_total")
        assert metrics.SERVE_PREFILL_HANDOFFS.labelnames == ("outcome",)
        assert isinstance(metrics.SERVE_PREFILL_CHUNK_SECONDS, Histogram)
        assert (metrics.SERVE_PREFILL_CHUNK_SECONDS.name
                == "oim_serve_prefill_chunk_seconds")
        assert metrics.SERVE_PREFILL_CHUNK_SECONDS.buckets == (
            0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
            1.0, 2.5)

    def test_control_plane_metrics_declared_and_shaped(self):
        """The control-plane self-metric names are API (ISSUE 18):
        tests/test_scalesim_smoke.py reads them at 50 lite replicas
        and oimctl --top's COMMIT/PICK columns parse them off /metrics
        scrapes — a rename or label change silently blanks both. The
        commit histogram stays labeled BY PHASE (ack/apply/total) and
        the fold histogram BY MODE (scratch/incremental); the rest are
        unlabeled."""
        assert isinstance(metrics.WATCH_FANOUT_SECONDS, Histogram)
        assert (metrics.WATCH_FANOUT_SECONDS.name
                == "oim_watch_fanout_seconds")
        assert metrics.WATCH_FANOUT_SECONDS.labelnames == ()
        assert isinstance(metrics.WATCH_QUEUE_DEPTH, Gauge)
        assert (metrics.WATCH_QUEUE_DEPTH.name
                == "oim_watch_queue_depth_peak")
        assert isinstance(metrics.WATCH_SHED_STREAMS, Counter)
        assert (metrics.WATCH_SHED_STREAMS.name
                == "oim_watch_shed_streams_total")
        assert metrics.WATCH_SHED_STREAMS.labelnames == ()
        assert isinstance(metrics.REGISTRY_COMMIT_SECONDS, Histogram)
        assert (metrics.REGISTRY_COMMIT_SECONDS.name
                == "oim_registry_commit_seconds")
        assert metrics.REGISTRY_COMMIT_SECONDS.labelnames == ("phase",)
        assert isinstance(metrics.REGISTRY_ELECTION_SECONDS, Histogram)
        assert (metrics.REGISTRY_ELECTION_SECONDS.name
                == "oim_registry_election_seconds")
        assert metrics.REGISTRY_ELECTION_SECONDS.labelnames == ()
        assert isinstance(metrics.REGISTRY_READ_LAG, Gauge)
        assert (metrics.REGISTRY_READ_LAG.name
                == "oim_registry_read_lag_records")
        assert metrics.REGISTRY_READ_LAG.labelnames == ()
        assert isinstance(metrics.TOP_MERGE_SECONDS, Histogram)
        assert metrics.TOP_MERGE_SECONDS.name == "oim_top_merge_seconds"
        assert metrics.TOP_MERGE_SECONDS.labelnames == ("mode",)
        assert isinstance(metrics.ROUTER_PICK_SECONDS, Histogram)
        assert (metrics.ROUTER_PICK_SECONDS.name
                == "oim_router_pick_seconds")
        assert metrics.ROUTER_PICK_SECONDS.labelnames == ()


class TestTelemetrySnapshotPayload:
    def test_rows_carry_mergeable_histograms(self):
        """TelemetryRegistration's default collector publishes the
        fleet-mergeable snapshots (obs/merge.py wire format) inside the
        row body: rpc always; the serve-side series only once observed;
        requests_total counters once any request finished."""
        from oim_tpu.common.telemetry import metrics_snapshot
        from oim_tpu.obs import merge

        payload = metrics_snapshot()
        assert "rpc" in payload["hist"]
        merge.validate(payload["hist"]["rpc"])
        metrics.SERVE_TOKEN_LATENCY.labels(kind="first").observe(0.02)
        metrics.SERVE_QUEUE_WAIT.observe(0.003)
        metrics.SERVE_REQUESTS_TOTAL.labels(outcome="eos").inc()
        payload = metrics_snapshot()
        for key in ("first_token", "queue_wait"):
            assert merge.total(payload["hist"][key]) >= 1
        assert payload["counters"]["requests_total"]["eos"] >= 1
        # The whole payload must survive the registry row's JSON trip.
        import json as json_mod

        fleet = merge.FleetHistogram()
        fleet.update("r0", json_mod.loads(
            json_mod.dumps(payload))["hist"]["first_token"])
        assert fleet.merged() is not None

    def test_collect_none_restores_discovery_only_rows(self):
        from oim_tpu.common.telemetry import TelemetryRegistration

        reg = TelemetryRegistration(
            "t0", "serve", "127.0.0.1:1", "localhost:1", collect=None)
        assert set(reg.snapshot()) == {"metrics", "role", "pid"}
        with_payload = TelemetryRegistration(
            "t1", "serve", "127.0.0.1:1", "localhost:1")
        assert "hist" in with_payload.snapshot()


class TestMetricsServer:
    def test_bind_host_and_debug_spans(self):
        srv = MetricsServer(port=0, host="127.0.0.1").start()
        try:
            with tracing.start_span("probe-span"):
                pass
            base = f"http://127.0.0.1:{srv.port}"
            text = urllib.request.urlopen(f"{base}/metrics").read().decode()
            assert "oim_rpc_total" in text
            assert_valid_prometheus(text)
            doc = json.loads(
                urllib.request.urlopen(f"{base}/debug/spans").read())
            names = [e.get("name") for e in doc["traceEvents"]]
            assert "probe-span" in names
            assert "process_name" in names
        finally:
            srv.stop()

    def test_counter_gauge_histogram_types_survive(self):
        assert isinstance(metrics.RPC_TOTAL, Counter)
        assert isinstance(metrics.RPC_LATENCY, Histogram)
        assert isinstance(metrics.TRAIN_MFU, Gauge)


# -- secret redaction ------------------------------------------------------


class TestRedaction:
    def test_map_valued_secrets_redacted(self):
        from oim_tpu.common.interceptors import strip_secrets

        req = pb.PublishVolumeRequest(
            volume_id="v", emulate="ceph",
            secrets={"admin": "hunter2", "key": "k"},
            attributes={"pool": "rbd"})
        out = strip_secrets(req)
        assert "hunter2" not in out and '"k"' not in out
        assert out.count("***stripped***") == 2
        assert "rbd" in out  # non-secret map survives

    def test_singular_and_nested_secret_still_redacted(self):
        from oim_tpu.common.interceptors import strip_secrets

        req = pb.MapVolumeRequest(
            volume_id="v", ceph=pb.CephParams(user="u", secret="tops3cret"))
        out = strip_secrets(req)
        assert "tops3cret" not in out and "***stripped***" in out
        assert "u" in out

    @staticmethod
    def _dynamic_message(fields):
        """Build a message class from (name, type, label) specs in a
        private pool — the committed proto has no repeated string secret,
        and the redactor must still handle one."""
        from google.protobuf import (
            descriptor_pb2,
            descriptor_pool,
            message_factory,
        )

        fdp = descriptor_pb2.FileDescriptorProto()
        fdp.name = "redact_test.proto"
        fdp.package = "redact.test"
        fdp.syntax = "proto3"
        msg = fdp.message_type.add()
        msg.name = "Creds"
        for i, (name, ftype, label) in enumerate(fields, start=1):
            f = msg.field.add()
            f.name, f.number, f.type, f.label = name, i, ftype, label
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fdp)
        return message_factory.GetMessageClass(
            pool.FindMessageTypeByName("redact.test.Creds"))

    def test_repeated_string_secret_redacted(self):
        from google.protobuf import descriptor_pb2

        from oim_tpu.common.interceptors import strip_secrets

        F = descriptor_pb2.FieldDescriptorProto
        cls = self._dynamic_message([
            ("secret", F.TYPE_STRING, F.LABEL_REPEATED),
            ("note", F.TYPE_STRING, F.LABEL_OPTIONAL),
        ])
        msg = cls(secret=["alpha", "bravo"], note="keep")
        out = strip_secrets(msg)
        assert "alpha" not in out and "bravo" not in out
        assert out.count("***stripped***") == 2
        assert "keep" in out


# -- logging ---------------------------------------------------------------


class TestLoggingFormats:
    def test_millisecond_timestamps(self):
        buf = io.StringIO()
        oim_logging.Logger(output=buf).info("hi")
        assert re.search(
            r"^\d{4}-\d{2}-\d{2} \d{2}:\d{2}:\d{2}\.\d{3} INFO hi",
            buf.getvalue())

    def test_json_format_flattens_fields(self):
        buf = io.StringIO()
        log = oim_logging.Logger(output=buf, fmt="json").with_fields(
            component="feeder")
        log.info("published", volume="v-1", bytes=42)
        rec = json.loads(buf.getvalue())
        assert rec["level"] == "INFO" and rec["msg"] == "published"
        assert rec["component"] == "feeder"
        assert rec["volume"] == "v-1" and rec["bytes"] == 42
        assert re.search(r"\.\d{3}$", rec["ts"])

    def test_json_format_one_object_per_line(self):
        buf = io.StringIO()
        log = oim_logging.Logger(output=buf, fmt="json")
        log.info("a")
        log.warning("b", err=ValueError("x"))  # non-JSON value -> repr
        lines = buf.getvalue().strip().split("\n")
        assert len(lines) == 2
        assert json.loads(lines[1])["err"] == "ValueError('x')"

    def test_trace_id_field_in_both_formats(self):
        for fmt in ("text", "json"):
            buf = io.StringIO()
            log = oim_logging.Logger(output=buf, fmt=fmt)
            with tracing.start_span("op") as span:
                log.with_fields(trace_id=tracing.trace_id()).info("x")
            assert span.trace_id in buf.getvalue()

    def test_unknown_format_rejected(self):
        with pytest.raises(ValueError):
            oim_logging.Logger(fmt="yaml")


class TestObservabilityCLIPlumbing:
    def test_flags_present_on_all_daemons(self):
        """Every daemon CLI exposes --metrics-port/--metrics-host/
        --trace-dir and --log-format (the shared plumbing)."""
        from oim_tpu.cli import oim_controller, oim_feeder, oim_registry, oim_trainer

        for mod in (oim_registry, oim_controller, oim_feeder, oim_trainer):
            with pytest.raises(SystemExit) as exc:
                mod.main(["--help"])
            assert exc.value.code == 0

        import argparse

        from oim_tpu.cli.common import add_common_flags, add_observability_flags

        parser = argparse.ArgumentParser()
        add_common_flags(parser)
        add_observability_flags(parser)
        args = parser.parse_args([
            "--metrics-port", "0", "--metrics-host", "0.0.0.0",
            "--trace-dir", "/tmp/t", "--log-format", "json"])
        assert args.metrics_host == "0.0.0.0"
        assert args.trace_dir == "/tmp/t"

    def test_oimctl_metrics_pretty_printer(self):
        from oim_tpu.cli.oimctl import parse_prometheus_text

        text = metrics.DEFAULT.render()
        types, helps, samples = parse_prometheus_text(text)
        assert types["oim_rpc_latency_seconds"] == "histogram"
        assert types["oim_rpc_total"] == "counter"
        assert any(name == "oim_staged_bytes_total" for name, _, _ in samples)

    def test_oimctl_parser_unescapes_in_one_pass(self):
        # A literal backslash before 'n' must round-trip as backslash+n,
        # not decode to a newline (the chained-replace trap).
        from oim_tpu.cli.oimctl import parse_prometheus_text

        reg = Registry()
        c = reg.counter("rt_total", labelnames=("path",))
        for value in ("C:\\new", 'quote"back\\slash', "line\nbreak"):
            c.labels(path=value).inc()
        _, _, samples = parse_prometheus_text(reg.render())
        got = {labels["path"] for _, labels, _ in samples}
        assert got == {"C:\\new", 'quote"back\\slash', "line\nbreak"}

    def test_oimctl_metrics_against_live_server(self, capsys):
        from oim_tpu.cli import oimctl

        metrics.RPC_TOTAL.labels(
            method="oim.v1.Registry/GetValues", code="OK").inc()
        srv = MetricsServer(port=0).start()
        try:
            rc = oimctl.main(["--metrics", f"127.0.0.1:{srv.port}"])
        finally:
            srv.stop()
        assert rc == 0
        out = capsys.readouterr().out
        assert "oim_rpc_latency_seconds [histogram]" in out
        assert "oim_rpc_total [counter]" in out
