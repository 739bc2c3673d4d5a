"""The fleet SLO plane, three stories: the fleet-merged histogram counts
every pooled observation across a replica's restart and lands its p99
within one bucket of the pooled truth; a degraded replica raises exactly
one TTL-leased ``alert/`` row, seen arriving over a registry Watch
stream, and healing deletes it with one fired/resolved event pair; and
``oimctl --autopsy`` names the phases of one real routed request."""

import queue
import random
import threading

import pytest

from tests import cluster as C

FT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
              1.0, 2.5)


def test_merged_p99_is_the_pooled_p99_across_a_counter_reset():
    from oim_tpu.common.metrics import Registry
    from oim_tpu.obs import merge

    rng = random.Random(20260804)
    fleet = merge.FleetHistogram()
    pooled: list[float] = []

    def run_replica(rid, n, slow_frac, parts=1):
        # parts > 1: the replica restarts between parts and republishes a
        # FRESH histogram from zero, the epoch the merger must absorb.
        for _ in range(parts):
            hist = Registry().histogram("ft_seconds", buckets=FT_BUCKETS)
            for _ in range(n // parts):
                v = (rng.uniform(0.2, 0.9) if rng.random() < slow_frac
                     else rng.uniform(0.002, 0.04))
                hist.observe(v)
                pooled.append(v)
                fleet.update(rid, hist.merged_snapshot())

    run_replica("r0", 400, 0.0)
    run_replica("r1", 400, 0.02, parts=2)
    run_replica("r2", 200, 0.08)
    merged = fleet.merged()
    assert merge.total(merged) == len(pooled) == 1000
    pooled_p99 = sorted(pooled)[int(0.99 * (len(pooled) - 1))]
    assert abs(merge.bucket_index(merged, merge.quantile(merged, 0.99))
               - merge.bucket_index(merged, pooled_p99)) <= 1


@pytest.fixture(scope="module")
def episode():
    """A registry, a FleetMonitor and two replicas publishing snapshot-
    bearing telemetry rows; r1 degrades, then heals."""
    from oim_tpu.cli import oimctl
    from oim_tpu.common import events, tlsutil
    from oim_tpu.common.channelpool import ChannelPool
    from oim_tpu.common.metrics import Registry
    from oim_tpu.common.telemetry import TelemetryRegistration
    from oim_tpu.obs.monitor import FleetMonitor
    from oim_tpu.obs.slo import SLO, SloEngine
    from oim_tpu.registry import MemRegistryDB, RegistryService
    from oim_tpu.registry.registry import registry_server
    from oim_tpu.registry.watch import KIND_DELETE, KIND_PUT
    from oim_tpu.spec import RegistryStub, pb

    rng = random.Random(20260804)
    events.configure(capacity=4096)
    pool = ChannelPool()
    reg_srv = registry_server(
        "tcp://localhost:0", RegistryService(db=MemRegistryDB()))
    hists = {rid: Registry().histogram("ft_seconds", buckets=FT_BUCKETS)
             for rid in ("r0", "r1")}
    telemetry = {rid: TelemetryRegistration(
        rid, "serve", "127.0.0.1:0", reg_srv.addr, interval=5.0, pool=pool,
        collect=lambda h=h: {"hist": {"first_token": h.merged_snapshot()}})
        for rid, h in hists.items()}

    def beat(rid, fast=0, slow=0):
        for _ in range(fast):
            hists[rid].observe(rng.uniform(0.002, 0.04))
        for _ in range(slow):
            hists[rid].observe(rng.uniform(0.3, 0.9))
        telemetry[rid].beat_once()

    # The alert namespace watched the way the autoscaler watches it.
    deltas: queue.Queue = queue.Queue()
    watch_channel = tlsutil.dial(reg_srv.addr, None)
    watch_call = RegistryStub(watch_channel).Watch(
        pb.WatchRequest(path="alert"))

    def drain_watch():
        try:
            for event in watch_call:
                deltas.put((event.kind, event.value.path))
        except Exception:  # noqa: BLE001 - cancelled at teardown
            pass

    threading.Thread(target=drain_watch, daemon=True).start()

    def await_delta(kind, path, feed):
        def arrived():
            feed()
            try:
                return deltas.get(timeout=0.25) == (kind, path)
            except queue.Empty:
                return False

        C.wait_until(arrived, f"the Watch stream never delivered kind="
                     f"{kind} for {path}", timeout=60, interval=0.1)

    monitor = FleetMonitor(
        reg_srv.addr,
        SloEngine([SLO(name="first_token_p99", kind="latency",
                       objective=0.99, metric="first_token",
                       threshold_s=0.1)],
                  fast_window_s=0.8, slow_window_s=2.4,
                  burn_threshold=10.0, resolve_hold_s=0.3),
        interval=0.15, pool=pool)
    facts: dict = {}
    try:
        for rid in hists:
            beat(rid, fast=20)
        # Five evaluations of a healthy fleet before the loops start.
        for _ in range(5):
            beat("r0", fast=2)
            beat("r1", fast=2)
            monitor.tick_once()
        facts["healthy_firing"] = list(monitor.engine.firing())
        facts["healthy_deltas"] = []
        while not deltas.empty():
            facts["healthy_deltas"].append(deltas.get_nowait())
        monitor.start()
        await_delta(KIND_PUT, "alert/first_token_p99",
                    lambda: (beat("r0", fast=2), beat("r1", slow=6)))
        stub = RegistryStub(pool.get(reg_srv.addr, None))
        facts["alerts"] = oimctl.alert_rows(stub)
        entries = oimctl.telemetry_rows(stub)
        facts["all_row"] = oimctl.fleet_top_row(entries)
        facts["top"] = oimctl.render_top(
            [facts["all_row"]] + [oimctl.top_row(*e) for e in entries])
        await_delta(KIND_DELETE, "alert/first_token_p99",
                    lambda: (beat("r0", fast=2), beat("r1", fast=2)))
        facts["alerts_after"] = oimctl.alert_rows(stub)
        for name, type_ in (("fired", events.SLO_ALERT_FIRED),
                            ("resolved", events.SLO_ALERT_RESOLVED)):
            facts[name] = [
                e for e in events.recorder().events(type_=type_)
                if e.attrs.get("slo") == "first_token_p99"]
        yield facts
    finally:
        monitor.stop()
        for registration in telemetry.values():
            registration.stop(deregister=False)
        watch_call.cancel()
        watch_channel.close()
        reg_srv.force_stop()
        pool.close()
        events.configure()


def test_a_healthy_fleet_raises_no_alert(episode):
    from oim_tpu.registry.watch import KIND_PUT

    assert not episode["healthy_firing"]
    assert not [d for d in episode["healthy_deltas"]
                if d[0] == KIND_PUT and d[1].startswith("alert/")]


def test_a_degraded_replica_raises_one_alert_row_over_watch(episode):
    # Its arrival over the stream is the fixture's first await_delta.
    assert [a[0] for a in episode["alerts"]] == ["first_token_p99"]
    body = episode["alerts"][0][1]
    assert body["state"] == "firing" and body["burn_fast"] >= 10


def test_top_folds_the_rows_the_monitor_watched(episode):
    assert episode["all_row"]["ft_ms"][0] is not None
    assert "ALL" in episode["top"]


def test_healing_deletes_the_row_with_one_fired_resolved_pair(episode):
    assert episode["alerts_after"] == []
    assert (len(episode["fired"]), len(episode["resolved"])) == (1, 1)


def test_slo_smoke_autopsy_names_the_phases_of_a_routed_request():
    """The engine records the queue and decode phase spans at slot
    retirement, which can land a beat after the stream closes: poll."""
    from oim_tpu.common import tracing
    from oim_tpu.obs import autopsy

    tracing.configure("slo-smoke", capacity=16384)
    try:
        with C.cluster(queue_depth=16) as sim:
            sim.warm()
            with tracing.start_span("test.slo_autopsy") as root:
                assert C.stream(sim, [1, 2, 3, 4], 6, seed=5)

            def attributed():
                report = autopsy.autopsy(
                    root.trace_id, [f"127.0.0.1:{sim.metrics_srv.port}"])
                names = {p["name"] for p in report["phases"]}
                return report if {"prefill", "decode"} <= names else None

            report = C.wait_until(
                attributed, "the autopsy never named prefill and decode",
                timeout=30, interval=0.2)
            assert 0 < report["coverage"] <= 1
            assert "unattributed gap" in autopsy.render(report)
    finally:
        tracing.configure("tests", capacity=4096)
