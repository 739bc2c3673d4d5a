"""The fleet actuator, one episode: an SLO alert scales a one-slot fleet
up through the autoscaler, the new replica's boot finds its weights in
the stage cache (the launcher prestaged them), the alert resolves, and a
rolling v1 -> v2 weight upgrade drains stale replicas one cooldown at a
time under routed load with no client-visible error and no changed
token. The fixture drives the episode; each test holds one gate."""

import dataclasses
import json
import random

import numpy as np
import pytest

from tests.cluster import wait_until


@pytest.fixture(scope="module")
def episode():
    from oim_tpu.autoscale import Autoscaler, FleetSpec
    from oim_tpu.chaos.sim import ClusterSim, SimReplicaLauncher, solo_tokens
    from oim_tpu.common import events, metrics as M
    from oim_tpu.common.metrics import Registry
    from oim_tpu.common.telemetry import TelemetryRegistration
    from oim_tpu.obs.monitor import FleetMonitor
    from oim_tpu.obs.slo import SLO, SloEngine
    from oim_tpu.registry.registry import CONTROLLER_ID_META
    from oim_tpu.spec import ControllerStub, pb

    rng = random.Random(20260806)
    facts: dict = {}
    with ClusterSim(replicas=1, controllers=2, max_batch=1) as sim:
        # Two weight generations as content-addressed raw volumes.
        requests = {v: pb.MapVolumeRequest(
            volume_id=f"weights-{v}",
            file=pb.FileParams(path=sim.tmpfile(
                np.random.RandomState(i).bytes(120_000)), format="raw"))
            for i, v in enumerate(("v1", "v2"))}
        feeder0, feeder1 = sim.feeder("host-0"), sim.feeder("host-1")
        feeder0.publish(requests["v1"], timeout=60)  # the day-0 publish
        ctrl = ControllerStub(sim.pool.get(
            sim.registries[0][1].addr, None, "component.registry"))

        def prestage(version):
            """Publish, fan the volume out to the boot controller and wait
            for the asynchronous stage to land there."""
            request = requests[version or "v1"]
            feeder0.publish(request, timeout=60)
            assert feeder0.prestage_replica(request) == "host-1"
            wait_until(
                lambda: ctrl.PrestageVolume(
                    request, metadata=[(CONTROLLER_ID_META, "host-1")],
                    timeout=10.0).already_cached,
                f"the prestaged {request.volume_id} never landed on host-1")

        boot_cache = {"hits": 0, "misses": 0}

        class Launcher(SimReplicaLauncher):
            """The sim's launcher plus the boot's weight load: the publish
            a booting oim-serve issues against the prestaged controller."""

            def spawn(self, version):
                rid = super().spawn(version)
                h, m = M.STAGE_CACHE_HITS.value, M.STAGE_CACHE_MISSES.value
                feeder1.publish(requests[version or "v1"], timeout=60)
                boot_cache["hits"] += int(M.STAGE_CACHE_HITS.value - h)
                boot_cache["misses"] += int(M.STAGE_CACHE_MISSES.value - m)
                return rid

        launcher = Launcher(sim, prestage_fn=prestage)
        hist = Registry().histogram(
            "ft_seconds", buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                                   0.1, 0.25, 0.5, 1.0, 2.5))
        probe = TelemetryRegistration(
            "probe", "serve", "127.0.0.1:0", sim.registry_address,
            interval=5.0, pool=sim.pool,
            collect=lambda: {"hist": {"first_token": hist.merged_snapshot()}})

        def beat(fast=0, slow=0):
            for _ in range(fast):
                hist.observe(rng.uniform(0.002, 0.04))
            for _ in range(slow):
                hist.observe(rng.uniform(0.3, 0.9))
            probe.beat_once()

        monitor = FleetMonitor(
            sim.registry_address,
            SloEngine([SLO(name="first_token_p99", kind="latency",
                           objective=0.99, metric="first_token",
                           threshold_s=0.1)],
                      fast_window_s=0.8, slow_window_s=2.4,
                      burn_threshold=10.0, resolve_hold_s=0.3),
            interval=0.15, pool=sim.pool)
        spec = FleetSpec(min_replicas=1, max_replicas=2, cooldown_s=0.4,
                         scale_down_hold_s=300.0)
        scaler = Autoscaler(sim.registry_address, spec, launcher,
                            interval=0.2, pool=sim.pool)
        watcher = sim.registry_watcher("")

        def row(path):
            try:
                body = json.loads(watcher.get(path) or "null")
            except ValueError:
                body = None
            return body if isinstance(body, dict) else {}

        def alert():
            return watcher.get("alert/first_token_p99")

        observed = M.AUTOSCALE_ALERT_TO_READY.count
        try:
            monitor.start()
            scaler.start()
            wait_until(lambda: scaler.is_leader,
                       "the autoscaler never took the fleet/ row", timeout=15)
            for _ in range(5):
                beat(fast=20)
            sim.warm()
            # Alert -> spawn -> ready: degrade until the row appears, keep
            # it firing until the actuator acts, then heal.
            wait_until(lambda: beat(slow=6) or alert() is not None,
                       "the degraded probe never raised the alert row",
                       timeout=60, interval=0.05)
            wait_until(lambda: beat(slow=2) or len(sim.replicas) > 1,
                       "the alert never made the autoscaler spawn",
                       timeout=60, interval=0.05)
            wait_until(
                lambda: beat(fast=4)
                or row(f"serve/{sim.replicas[1].rid}").get("ready"),
                "the spawned replica never heartbeat ready", timeout=120,
                interval=0.05)
            # spawn() returns the id first and loads the weights after: the
            # replica can heartbeat ready before that publish is counted.
            wait_until(lambda: sum(boot_cache.values()) >= 1,
                       "the scale-up boot never published its weights")
            facts["boot_cache"] = dict(boot_cache)
            wait_until(
                lambda: beat(fast=6) or (
                    alert() is None
                    and M.AUTOSCALE_ALERT_TO_READY.count > observed),
                "the alert never resolved after capacity landed",
                timeout=60, interval=0.05)
            facts["episodes"] = M.AUTOSCALE_ALERT_TO_READY.count - observed

            # The rolling upgrade under routed load.
            reqs = [([rng.randrange(1, 64) for _ in range(4)], 4, 0.0,
                     rng.randrange(1 << 16)) for _ in range(8)]
            expected = [solo_tokens(p, n, temperature=t, seed=s)
                        for p, n, t, s in reqs]
            scaler.set_spec(dataclasses.replace(spec, version="v2"))

            def versions():
                rows = [row(p) for p in list(watcher.rows)
                        if p.startswith("serve/")]
                return [r.get("version", "") for r in rows if r.get("ready")]

            facts.update(errors=[], diverged=[], checked=0)

            def upgraded():
                beat(fast=2)
                results, errors = sim.routed_load(reqs, concurrency=3,
                                                  timeout=60)
                facts["errors"].extend(errors)
                # Every stream the router closed OK is held to solo: an
                # EMPTY one (a request closed `drained` with no token) is
                # a dropped request, not a pass.
                for want, got in zip(expected, results):
                    if got is not None:
                        facts["checked"] += 1
                        if got != want:
                            facts["diverged"].append((got, want))
                now = versions()
                return len(now) >= 2 and set(now) == {"v2"}

            wait_until(upgraded, "the upgrade wave never converged on v2",
                       timeout=120, interval=0)
            facts["versions"] = versions()
            facts["flips"] = len(
                sim.debug_events(events.AUTOSCALE_UPGRADE_FLIP))
        finally:
            scaler.stop(deregister=True)
            monitor.stop()
            probe.stop(deregister=False)
            launcher.join()
    return facts


def test_scale_up_boot_hits_the_prestaged_stage_cache(episode):
    """Alert -> spawn -> ready happened (the fixture's waits); the boot's
    weight publish read no source byte."""
    assert episode["boot_cache"]["hits"] >= 1
    assert episode["boot_cache"]["misses"] == 0


def test_the_resolved_alert_leaves_one_alert_to_ready_observation(episode):
    assert episode["episodes"] == 1


def test_rolling_upgrade_converges_one_drain_at_a_time(episode):
    assert set(episode["versions"]) == {"v2"} and len(episode["versions"]) >= 2
    assert episode["flips"] >= 1, "no upgrade-flip drain was recorded"


def test_rolling_upgrade_shows_clients_no_error(episode):
    assert not episode["errors"], episode["errors"][:1]


def test_mixed_version_streams_match_solo_generate(episode):
    assert episode["checked"] > 0
    assert not episode["diverged"], episode["diverged"][:1]
