"""Ring-2 e2e: the control plane as REAL OS processes over TCP + mTLS.

The reference's deepest test layer launches its daemons as managed child
processes with readiness polling and death detection
(test/pkg/spdk/spdk.go:84-226, test/e2e/e2e.go:41-183); ring 0/1 here cover
the same services in-process, this file covers them as the README
quickstart actually runs them: `oim-registry` + `oim-controller` spawned
with CmdMonitor, `oimctl` and `oim-trainer` driven against them over real
sockets, soft-state re-registration observed across process boundaries.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from oim_tpu.common.cmdmonitor import CmdMonitor, monitored_popen
from oim_tpu.common.tlsutil import load_tls, secure_channel
from oim_tpu.spec import RegistryStub, pb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # children never touch the real chip
    # These tests READ the children's INFO lines ("done", "step"); the
    # suite-wide OIM_LOG_LEVEL=error (conftest.py) would silence them.
    env["OIM_LOG_LEVEL"] = "info"
    return env


@pytest.fixture(scope="module")
def certs(tmp_path_factory):
    from oim_tpu.common.ca import CertAuthority

    d = tmp_path_factory.mktemp("e2e-ca")
    ca = CertAuthority("oim-e2e-ca")
    for cn in ("component.registry", "controller.host-0", "host.host-0",
               "user.admin"):
        ca.write_files(str(d), cn)
    return d


class Cluster:
    """Registry + one controller as monitored child processes."""

    def __init__(self, certs):
        self.certs = certs
        self.registry_port = free_port()
        self.controller_port = free_port()
        self.procs: list[subprocess.Popen] = []
        self.monitors: dict[str, CmdMonitor] = {}
        self._spawn(
            "registry", "oim_tpu.cli.oim_registry",
            "--endpoint", f"tcp://127.0.0.1:{self.registry_port}",
            "--ca", f"{certs}/ca.crt", "--key", f"{certs}/component.registry",
        )
        # The controller dials a registry that already answers: a first
        # connect that is refused puts its channel into gRPC's reconnect
        # backoff (1 s, growing), and on a loaded box registration then
        # lands past wait_ready's deadline.
        self.wait_registry()
        self._spawn(
            "controller", "oim_tpu.cli.oim_controller",
            "--endpoint", f"tcp://127.0.0.1:{self.controller_port}",
            "--controller-id", "host-0",
            "--controller-address", f"127.0.0.1:{self.controller_port}",
            "--registry", f"127.0.0.1:{self.registry_port}",
            "--registry-delay", "1", "--backend", "malloc",
            "--mesh-coord", "0,0,0",
            "--ca", f"{certs}/ca.crt", "--key", f"{certs}/controller.host-0",
        )

    def _spawn(self, name: str, module: str, *args) -> None:
        proc, monitor = monitored_popen(
            [sys.executable, "-m", module, *args],
            env=child_env(),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        self.procs.append(proc)
        self.monitors[name] = monitor

    def admin_stub(self):
        tls = load_tls(
            f"{self.certs}/ca.crt", f"{self.certs}/user.admin",
            "component.registry",
        )
        channel = secure_channel(f"127.0.0.1:{self.registry_port}", tls)
        return RegistryStub(channel)

    def wait_registry(self, timeout: float = 60.0) -> None:
        """The registry answers. A FRESH channel per probe: one channel
        reused across refused connects sits out the reconnect backoff."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                self.admin_stub().GetValues(
                    pb.GetValuesRequest(path=""), timeout=2)
                return
            except Exception:
                time.sleep(0.1)
        raise TimeoutError("registry never answered")

    def wait_ready(self, timeout: float = 60.0) -> None:
        """Registry answers AND the controller has self-registered."""
        stub = self.admin_stub()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                reply = stub.GetValues(
                    pb.GetValuesRequest(path="host-0"), timeout=2
                )
                if any(v.path == "host-0/address" for v in reply.values):
                    return
            except Exception:
                pass
            time.sleep(0.2)
        raise TimeoutError("cluster not ready: host-0/address never appeared")

    def shutdown(self) -> None:
        for proc in self.procs:
            proc.terminate()
        for proc in self.procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


@pytest.fixture(scope="module")
def cluster(certs):
    c = Cluster(certs)
    try:
        c.wait_ready()
        yield c
    finally:
        c.shutdown()


def run_cli(cluster, module: str, *args, timeout: float = 120.0):
    return subprocess.run(
        [sys.executable, "-m", module, *args],
        env=child_env(), capture_output=True, text=True, timeout=timeout,
    )


class TestReadmeQuickstart:
    def test_oimctl_sees_topology(self, cluster):
        out = run_cli(
            cluster, "oim_tpu.cli.oimctl",
            "--registry", f"127.0.0.1:{cluster.registry_port}",
            "--ca", f"{cluster.certs}/ca.crt",
            "--key", f"{cluster.certs}/user.admin",
            "--get", "host-0",
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert f"host-0/address=127.0.0.1:{cluster.controller_port}" in out.stdout
        assert "host-0/mesh=0,0,0" in out.stdout

    def test_trainer_fed_through_control_plane(self, cluster, tmp_path):
        """The README's final step: oim-trainer publishing a volume through
        the feeder and training on the ReadVolume data window."""
        tokens = np.random.RandomState(0).randint(
            0, 256, 16384
        ).astype(np.int32)
        np.save(tmp_path / "tokens.npy", tokens)
        out = run_cli(
            cluster, "oim_tpu.cli.oim_trainer",
            "--platform", "cpu", "--model", "llama-tiny",
            "--steps", "3", "--batch-size", "2", "--seq-len", "32",
            "--log-every", "1", "--warmup-steps", "1", "--mesh", "data=1",
            "--shuffle", "--shuffle-buffer-records", "8",
            "--registry", f"127.0.0.1:{cluster.registry_port}",
            "--controller-id", "host-0",
            "--volume", "tokens", "--volume-file", str(tmp_path / "tokens.npy"),
            "--ca", f"{cluster.certs}/ca.crt",
            "--key", f"{cluster.certs}/host.host-0",
            timeout=300,
        )
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
        assert "done" in out.stdout + out.stderr

    def test_trainer_fed_from_webdataset_shards(self, cluster, tmp_path):
        """Config-5 shape (BASELINE.json): llama trained from webdataset
        shards staged through MapVolume — here two local tar shards whose
        samples carry raw int32 token payloads."""
        import io
        import tarfile

        rng = np.random.RandomState(1)
        for shard in range(2):
            buf = io.BytesIO()
            with tarfile.open(fileobj=buf, mode="w") as tf:
                for i in range(4):
                    payload = rng.randint(0, 256, 512).astype(np.int32).tobytes()
                    info = tarfile.TarInfo(name=f"{shard:03d}/{i:06d}.bin")
                    info.size = len(payload)
                    tf.addfile(info, io.BytesIO(payload))
            (tmp_path / f"shard-{shard}.tar").write_bytes(buf.getvalue())
        urls = ",".join(str(tmp_path / f"shard-{s}.tar") for s in range(2))
        out = run_cli(
            cluster, "oim_tpu.cli.oim_trainer",
            "--platform", "cpu", "--model", "llama-tiny",
            "--steps", "3", "--batch-size", "2", "--seq-len", "32",
            "--log-every", "1", "--warmup-steps", "1", "--mesh", "data=1",
            "--registry", f"127.0.0.1:{cluster.registry_port}",
            "--controller-id", "host-0",
            "--volume", "wds-tokens", "--volume-webdataset", urls,
            "--ca", f"{cluster.certs}/ca.crt",
            "--key", f"{cluster.certs}/host.host-0",
            timeout=300,
        )
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
        combined = out.stdout + out.stderr
        # Default window > 0 -> the shard-streaming feed.
        assert "webdataset streaming feed" in combined
        assert "done" in combined

    def test_soft_state_reregistration_across_processes(self, cluster):
        """Delete the controller's registration; the 1s re-registration loop
        must restore it (reference controller_test.go:107-127, here across
        real process + socket boundaries)."""
        stub = cluster.admin_stub()
        stub.SetValue(
            pb.SetValueRequest(value=pb.Value(path="host-0/address", value="")),
            timeout=10,
        )
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            reply = stub.GetValues(pb.GetValuesRequest(path="host-0"), timeout=5)
            if any(v.path == "host-0/address" for v in reply.values):
                return
            time.sleep(0.2)
        pytest.fail("controller did not re-register within 10s")


class TestProcessDeath:
    def test_cmdmonitor_detects_child_death(self, certs):
        proc, monitor = monitored_popen(
            [sys.executable, "-c", "import time; time.sleep(600)"],
            env=child_env(),
        )
        assert not monitor.died.is_set()
        proc.kill()
        proc.wait(timeout=10)
        assert monitor.died.wait(timeout=10), "death never detected"

    def test_registry_survives_controller_death(self, certs):
        """Kill the controller: the registry keeps serving and its DB still
        answers (soft state — truth degrades, service does not)."""
        c = Cluster(certs)
        try:
            c.wait_ready()
            c.procs[1].kill()
            assert c.monitors["controller"].died.wait(timeout=10)
            reply = c.admin_stub().GetValues(
                pb.GetValuesRequest(path="host-0"), timeout=5
            )
            assert any(v.path == "host-0/address" for v in reply.values)
        finally:
            c.shutdown()
