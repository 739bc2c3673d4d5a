"""One trace_id followed through the observability plane: a routed
Generate is forced onto a planted dead replica, the router's retry
before the first token leaves a ``router_retry`` flight-recorder event,
and the same id is found on ``/debug/events``, in the span ring, on a
token-latency exemplar of the OpenMetrics scrape, and every telemetry
row renders in ``oimctl --top``."""

import json
import urllib.request

import pytest

from tests import cluster as C


@pytest.fixture(scope="module")
def story():
    from oim_tpu.cli import oimctl
    from oim_tpu.common import events, tracing
    from oim_tpu.common.telemetry import TelemetryRegistration
    from oim_tpu.spec import RegistryStub, pb

    # A fresh span ring: the story must not fish in an earlier test's
    # (the sim starts a flight recorder and a metrics server of its own).
    tracing.configure("obs-smoke", capacity=16384)
    telemetry = []
    try:
        with C.cluster(replicas=2, queue_depth=16) as sim:
            target = f"127.0.0.1:{sim.metrics_srv.port}"
            # One process, one metrics registry: every row advertises the
            # same scrape endpoint, which is all --top needs to render it.
            for name, role in (("r0", "serve"), ("r1", "serve"),
                               ("router", "router")):
                telemetry.append(TelemetryRegistration(
                    name, role, target, sim.registry_address, interval=5.0,
                    pool=sim.pool))
            sim.warm()
            # A row that scores best (999 free slots) and refuses
            # connections: the next pick dials it and must retry.
            reg_stub = RegistryStub(sim.pool.get(sim.registry_address, None))
            reg_stub.SetValue(pb.SetValueRequest(value=pb.Value(
                path="serve/zz-dead", value=json.dumps({
                    "endpoint": "127.0.0.1:1", "free_slots": 999,
                    "queue_depth": 0, "max_batch": 999, "ready": True,
                    "beat": 1}), lease_seconds=120.0)), timeout=10.0)

            def retried():
                assert C.stream(sim, [1, 2, 3, 4], 6, seed=3), \
                    "a routed request produced no tokens"
                return events.recorder().events(type_=events.ROUTER_RETRY)

            retry = C.wait_until(
                retried, "the planted dead replica never caused a router "
                "retry", timeout=120, interval=0.2)[-1]
            for registration in telemetry:
                registration.beat_once()
            rows = oimctl.telemetry_rows(reg_stub)
            yield {
                "trace_id": retry.trace_id,
                "target": target,
                "span_names": {s.name for s in tracing.recorder().spans()
                               if s.trace_id == retry.trace_id},
                "ring": {s.trace_id for s in tracing.recorder().spans()},
                "rows": rows,
                "top": oimctl.render_top(
                    [oimctl.top_row(*r) for r in rows]),
            }
    finally:
        for registration in telemetry:
            registration.stop(deregister=False)
        tracing.configure("tests", capacity=4096)


def test_the_retry_event_carries_the_request_trace_id(story):
    assert story["trace_id"]
    doc = json.loads(urllib.request.urlopen(
        f"http://{story['target']}/debug/events?trace={story['trace_id']}"
    ).read())
    assert "router_retry" in [e.get("type") for e in doc["events"]]


def test_obs_smoke_trace_story(story):
    """The span ring holds the router -> serve tree of the same trace."""
    assert {"router.generate", "serve.generate"} <= story["span_names"]


def test_plain_scrape_carries_no_exemplar(story):
    plain = urllib.request.urlopen(
        f"http://{story['target']}/metrics").read().decode()
    assert "# {trace_id=" not in plain, \
        "a legacy Prometheus parser would fail on this scrape"


def test_token_latency_exemplar_resolves_to_a_kept_span(story):
    from oim_tpu.cli import oimctl

    text = urllib.request.urlopen(urllib.request.Request(
        f"http://{story['target']}/metrics",
        headers={"Accept": "application/openmetrics-text"})).read().decode()
    assert text.rstrip().endswith("# EOF")
    token_traces = {
        trace for name, trace in oimctl.parse_exemplars(text)
        if name.startswith("oim_serve_token_latency_seconds")}
    assert story["trace_id"] in token_traces
    assert story["trace_id"] in story["ring"]


def test_top_renders_every_live_telemetry_row(story):
    live = {row[0] for row in story["rows"] if row[1] == "ALIVE"}
    assert live == {"r0", "r1", "router"}
    for name in live:
        assert name in story["top"]
