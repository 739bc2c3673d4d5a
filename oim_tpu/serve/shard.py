"""Tensor-parallel serving: one logical replica spans N member hosts.

The trainer's mesh/shard_map machinery (oim_tpu/parallel) applied to the
decode path. A sharded replica is a mesh of N member processes over ICI:

* **Weights** are Megatron-split — wq/wk/wv and the MLP up/gate
  projections column-split (head-parallel: each member holds a
  contiguous 1/N slice of the query AND KV heads, so the GQA grouping
  survives), wo and the MLP down projection row-split, everything else
  (embeddings, norms, lm_head) replicated. Each member stages only its
  slice of the SAME content-addressed weights volume
  (``weights.restore_weights(shard=, rank=)``) — one publish, one
  manifest, N partial restores.
* **KV pages** shard with the KV heads: the page pool's head axis
  carries ``P("tp")`` so every member's pool holds its own heads' K/V
  for every page. Page IDs and page tables are PLAIN host-local
  integers replicated on every member — the table gather each member
  runs indexes its LOCAL pool, so no page ever crosses ICI. The only
  inter-member traffic is two activation psums per layer
  (:func:`oim_tpu.models.generate._reduce`).
* **Control plane** sees ONE replica: rank 0 publishes the
  ``serve/<id>`` row and serves gRPC; every member additionally holds a
  TTL lease under ``serve/<id>.member.<k>`` (:class:`ShardMembers`).
  Member rows publish NO endpoint, so a router's ``Replica.parse``
  skips them — they are liveness beacons, not routing targets. Any
  member's lease lapse flips the replica's ``ready`` false
  (``ServeEngine.stats()`` via :meth:`ShardMembers.member_counts`) and
  the router rotates away while drain + re-prestage heals.

On CPU the mesh is fake XLA devices (``--xla_force_host_platform_
device_count``, the tests/test_multihost.py trick), which is how the
byte-identity and chaos gates run device-free.
"""

from __future__ import annotations

import functools
import threading
import time

import grpc

from oim_tpu.common import channelpool
from oim_tpu.common.logging import from_context
from oim_tpu.common.pathutil import REGISTRY_SERVE
from oim_tpu.common.telemetry import RegistryRowPublisher
from oim_tpu.common.tlsutil import TLSConfig
from oim_tpu.spec import RegistryStub, pb

# Megatron split of the stacked-L llama leaves: COL leaves slice their
# LAST dim (output features / heads), ROW leaves slice dim 1 (input
# features, after the stacked layer dim 0). Everything else replicates.
COL = frozenset({"wq", "wk", "wv", "w_gate", "w_up"})
ROW = frozenset({"wo", "w_down"})


def leaf_spec(name: str):
    """PartitionSpec for one param leaf by its tree key."""
    from jax.sharding import PartitionSpec as P

    if name in COL:
        return P(None, None, "tp")
    if name in ROW:
        return P(None, "tp", None)
    return P()


def param_specs(params):
    """The in_specs pytree for a params argument (works on concrete
    arrays AND on tracers at jit trace time — only tree paths are
    read)."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, _: leaf_spec(path[-1].key), params)


def pool_specs(leaves=("k", "v")):
    """Page-pool spec, one a leaf of the pool: a GQA leaf [L, n_pages,
    page_tokens, n_kv_heads, hd] shards the KV-head axis — pages live
    whole on every member, each member holding its own heads' slice of
    every page. (A latent pool has no head axis to split: the engine
    refuses it under shard > 1, generate.shard_config.)"""
    from jax.sharding import PartitionSpec as P

    return {name: P(None, None, None, "tp", None) for name in leaves}


@functools.lru_cache(maxsize=8)
def tp_mesh(shard: int):
    """The ``tp`` mesh over the first ``shard`` local XLA devices (one
    per member in a real deployment; fake CPU devices in tests)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < shard:
        raise ValueError(
            f"shard={shard} needs {shard} XLA devices, have "
            f"{len(devices)} (set --xla_force_host_platform_device_count "
            f"for a CPU mesh)")
    return Mesh(np.asarray(devices[:shard]), ("tp",))


def member_weight_bytes(params, shard: int) -> int:
    """Bytes of params ONE member holds: split leaves contribute 1/shard
    of their bytes, replicated leaves their full size — the weight half
    of the per-member HBM budget check."""
    import jax
    import numpy as np

    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        nbytes = int(np.asarray(leaf).nbytes)
        if path[-1].key in COL | ROW:
            nbytes //= shard
        total += nbytes
    return total


def check_member_budget(params, shard: int, pool_bytes: int,
                        budget: int) -> int:
    """Enforce the per-member HBM budget: weights slice + this member's
    pool slice must fit in ``budget`` bytes. Returns the per-member
    total; raises ValueError when it does not fit — the "refused at
    shard=1, serves at shard=2" gate ``make shard-smoke`` pins."""
    per_member = member_weight_bytes(params, shard) + pool_bytes // shard
    if budget and per_member > budget:
        raise ValueError(
            f"model needs {per_member} bytes per member at shard={shard} "
            f"(weights {member_weight_bytes(params, shard)} + pool "
            f"{pool_bytes // shard}), over the {budget}-byte member HBM "
            f"budget — shard wider")
    return per_member


def wrap_forward(shard: int, body, cache_arg: int):
    """shard_map-wrap a ``(params, *rest) -> (out, cache)`` forward body
    over the ``tp`` mesh: params get the Megatron specs, the cache (at
    ``rest[cache_arg]``) the KV-head pool spec, every other operand and
    the non-cache output replicate. ``body`` must run the MEMBER-LOCAL
    view (:func:`oim_tpu.models.generate.shard_config` cfg,
    ``axis="tp"``). Built at jit trace time — ``param_specs`` reads only
    tree paths, so tracers are fine."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = tp_mesh(shard)
    pool = pool_specs()

    def wrapped(params, *rest):
        specs: list = [P()] * len(rest)
        specs[cache_arg] = pool
        f = shard_map(
            body, mesh=mesh,
            in_specs=(param_specs(params), *specs),
            out_specs=(P(), pool), check_vma=False)
        return f(params, *rest)

    return wrapped


# -- ICI allreduce probe ----------------------------------------------------

@functools.lru_cache(maxsize=8)
def _probe_program(shard: int):
    """A compiled one-psum shard_map program: the smallest unit whose
    wall time IS one ICI allreduce (the per-layer collectives inside
    the fused decode step cannot be host-timed individually)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from oim_tpu.parallel import collectives
    from jax import shard_map

    mesh = tp_mesh(shard)
    prog = jax.jit(shard_map(
        lambda x: collectives.psum(x, "tp"), mesh=mesh,
        in_specs=(P(),), out_specs=P(), check_vma=False))
    import jax.numpy as jnp

    x = jnp.zeros((256,), jnp.float32)
    prog(x).block_until_ready()  # compile outside the timed window
    return prog, x


def time_allreduce(shard: int) -> float:
    """Seconds for one 1 KiB allreduce over the ``tp`` mesh — observed
    into ``oim_serve_ici_allreduce_seconds`` by the engine's step
    wrapper so the decode path's ICI health is on /metrics."""
    prog, x = _probe_program(shard)
    t0 = time.perf_counter()
    prog(x).block_until_ready()
    return time.perf_counter() - t0


# -- member leases ----------------------------------------------------------

def member_key(serve_id: str, rank: int) -> str:
    """``serve/<id>.member.<k>`` — one path component (dots, not
    slashes), so it rides the same ``serve`` prefix the router polls,
    while the missing ``endpoint`` keeps ``Replica.parse`` skipping it
    (member rows are liveness beacons, never routing targets)."""
    from oim_tpu.serve.registration import serve_key

    return serve_key(f"{serve_id}.member.{rank}")


class _MemberPublisher(RegistryRowPublisher):
    """One member's TTL lease row. Value is tiny and value-stable, so
    the default batched-Heartbeat renewal applies (unlike the serve row,
    which re-publishes its load snapshot every beat)."""

    THREAD_NAME = "oim-shard-member"

    def __init__(self, serve_id: str, rank: int, shard: int,
                 registry_address: str, **kwargs):
        super().__init__(member_key(serve_id, rank), registry_address,
                         **kwargs)
        self.rank = rank
        self.shard = shard

    def snapshot(self) -> dict:
        return {"member": self.rank, "shard": self.shard, "state": "ready"}


class ShardMembers:
    """The member-lease side of one sharded replica: N TTL-leased
    ``serve/<id>.member.<k>`` rows plus the liveness poll the engine's
    readiness folds in.

    In a real deployment each member PROCESS runs its own publisher for
    its own rank; in-process (bench, chaos sim) one ShardMembers drives
    all N rows, and :meth:`stop_member` is the SIGKILL lever — the
    row's heartbeats stop mid-lease, nothing deregisters, and the lapse
    is what flips the replica not-ready.
    """

    def __init__(self, serve_id: str, shard: int, registry_address: str,
                 *, interval: float = 10.0, tls: TLSConfig | None = None,
                 pool: channelpool.ChannelPool | None = None):
        self.serve_id = serve_id
        self.shard = shard
        self.registry_address = registry_address
        self.interval = interval
        self.tls = tls
        self._pool = pool if pool is not None else channelpool.shared()
        self._members: dict[int, _MemberPublisher] = {}
        self._lock = threading.Lock()
        self._last_counts = {"ready": shard, "stale": 0, "total": shard}

    def _new_publisher(self, rank: int) -> _MemberPublisher:
        return _MemberPublisher(
            self.serve_id, rank, self.shard, self.registry_address,
            interval=self.interval, tls=self.tls, pool=self._pool)

    def start(self) -> "ShardMembers":
        for rank in range(self.shard):
            m = self._new_publisher(rank)
            m.beat_once()  # deterministic first registration
            m.start()
            self._members[rank] = m
        return self

    def stop(self, deregister: bool = True) -> None:
        for m in self._members.values():
            m.stop(deregister=deregister)
        self._members.clear()

    # -- fault/heal levers (the chaos rung's handles) ----------------------

    def stop_member(self, rank: int) -> None:
        """SIGKILL semantics for member ``rank``: heartbeats stop
        mid-lease and the row is NOT deleted — it outlives the corpse
        until the TTL lapses, exactly like a killed replica's serve
        row."""
        self._members.pop(rank).stop(deregister=False)

    def restart_member(self, rank: int) -> None:
        """The member process rebooted (and re-staged its weight slice
        — a stage-cache hit): a fresh publisher re-takes the lease."""
        m = self._new_publisher(rank)
        m.beat_once()
        m.start()
        self._members[rank] = m

    # -- liveness poll ------------------------------------------------------

    def member_counts(self) -> dict:
        """``{"ready": live, "stale": lapsed, "total": shard}`` from one
        lease-filtered + one include_stale GetValues under this
        replica's member prefix. On a registry error the LAST known
        counts are returned (a flapping control-plane read must not
        flap the replica's readiness; the lease itself is the
        authority and the next poll re-reads it)."""
        prefix = f"{REGISTRY_SERVE}/{self.serve_id}.member."
        try:
            stub = RegistryStub(self._pool.get(
                self.registry_address.split(",")[0], self.tls,
                "component.registry"))
            live = [v for v in stub.GetValues(
                pb.GetValuesRequest(path=REGISTRY_SERVE),
                timeout=10.0).values if v.path.startswith(prefix)]
            everything = [v for v in stub.GetValues(
                pb.GetValuesRequest(path=REGISTRY_SERVE, include_stale=True),
                timeout=10.0).values if v.path.startswith(prefix)]
        except grpc.RpcError as err:
            from_context().warning(
                "member liveness poll failed", serve=self.serve_id,
                error=err.code().name)
            return dict(self._last_counts)
        counts = {"ready": len(live),
                  "stale": max(len(everything) - len(live), 0),
                  "total": self.shard}
        with self._lock:
            self._last_counts = counts
        return dict(counts)
