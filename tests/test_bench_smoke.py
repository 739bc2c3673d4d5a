"""A small volume through the staging plane and back: controller +
TPUBackend (on the CPU device) + feeder, with chunks small enough that
the parallel pipeline runs several. Staged bytes are the source's, an
identical republish reads no source byte, the staged array feeds a
compiled loop, and a remote feeder reads it back controller-direct
over one pooled channel a target."""

import numpy as np
import pytest

N, D = 256, 64


@pytest.fixture(scope="module")
def staged(tmp_path_factory):
    from oim_tpu.controller.controller import ControllerService
    from oim_tpu.controller.tpu_backend import TPUBackend
    from oim_tpu.data import plane
    from oim_tpu.feeder import Feeder
    from oim_tpu.spec import pb

    raw = np.random.RandomState(7).rand(N, D).astype(np.float32)
    path = tmp_path_factory.mktemp("volume") / "smoke.bin"
    path.write_bytes(raw.tobytes())
    controller = ControllerService(TPUBackend(chunk_bytes=8 << 10))
    feeder = Feeder(controller=controller)
    request = pb.MapVolumeRequest(
        volume_id="smoke",
        spec=pb.ArraySpec(shape=[N, D], dtype="float32"),
        file=pb.FileParams(path=str(path), format="raw"))
    first = np.asarray(feeder.publish(request, timeout=60.0).array)
    stage_calls = plane.STAGE_CALLS
    feeder.unpublish("smoke")
    again = feeder.publish(request, timeout=60.0)
    return (raw, controller, first, again,
            plane.STAGE_CALLS - stage_calls)


def test_staged_array_is_the_source_bytes(staged):
    raw, _, first, _, _ = staged
    assert first.tobytes() == raw.tobytes()


def test_republish_of_an_unchanged_volume_reads_no_source(staged):
    raw, _, _, again, restaged = staged
    assert restaged == 0, "the stage cache missed: the plane staged again"
    assert np.asarray(again.array).tobytes() == raw.tobytes()


def test_bench_smoke_stage_and_train(staged):
    """The staged array is the operand of a jitted least-squares loop
    whose loss falls."""
    import jax
    import jax.numpy as jnp

    data = staged[3].array
    y = jnp.asarray(np.random.RandomState(8).rand(N).astype(np.float32))

    @jax.jit
    def step(w):
        loss, grad = jax.value_and_grad(
            lambda w: jnp.mean((data @ w - y) ** 2))(w)
        return w - 0.02 * grad, loss

    w, losses = jnp.zeros((D,), jnp.float32), []
    for _ in range(5):
        w, loss = step(w)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_remote_windows_ride_the_direct_path_on_one_dial(staged):
    from oim_tpu.common import metrics as M
    from oim_tpu.common.channelpool import ChannelPool
    from oim_tpu.controller.controller import controller_server
    from oim_tpu.feeder import Feeder
    from oim_tpu.registry import MemRegistryDB, RegistryService
    from oim_tpu.registry.registry import registry_server

    raw, controller = staged[0], staged[1]
    ctrl_srv = controller_server("tcp://localhost:0", controller)
    db = MemRegistryDB()
    db.set("smoke-host/address", ctrl_srv.addr)
    reg_srv = registry_server("tcp://localhost:0", RegistryService(db=db))
    pool = ChannelPool()
    try:
        remote = Feeder(registry_address=reg_srv.addr,
                        controller_id="smoke-host", pool=pool)
        direct = M.WINDOW_PATH_TOTAL.labels(path="direct").value
        got = bytearray()
        while len(got) < raw.nbytes:
            window, _, _ = remote.fetch_window("smoke", len(got), 16 << 10)
            got += window.tobytes()
        assert bytes(got) == raw.tobytes()
        assert M.WINDOW_PATH_TOTAL.labels(path="direct").value > direct, \
            "no window was served controller-direct"
        assert max(pool.stats().values()) == 1, \
            f"a target was dialed again for a later window: {pool.stats()}"
        proxied = Feeder(registry_address=reg_srv.addr,
                         controller_id="smoke-host", direct_data=False,
                         pool=pool)
        whole, _, _ = proxied.fetch_window("smoke", 0, 0)
        assert whole.tobytes() == raw.tobytes(), \
            "the registry's proxy changed the window"
    finally:
        pool.close()
        reg_srv.force_stop()
        ctrl_srv.force_stop()
