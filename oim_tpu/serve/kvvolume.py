"""Prefix KV chains as content-addressed volumes: the fleet tier.

A prefix chain's K/V is a pure function of its token chain — which
makes it CONTENT: the same pack discipline that ships weights
(serve/weights.py) serializes a chain's page blocks into one
deterministic self-describing blob (magic + JSON manifest + raw
K/V bytes), published through the ordinary feeder/controller path as a
raw uint8 volume whose id is derived from the chain's deepest hash.
From there the PR 4/5 machinery is the fleet fan-out:

* the HOLDER replica exports a hot chain once (one D2H snapshot via
  the engine's command queue, one publish);
* a PEER that misses the prefix locally ``ReadVolume``s the finished
  pages over the direct data path and H2D-stages them into its own
  pool — adoption costs one window read, not a prefill forward;
* ``PrestageVolume`` fan-out becomes prefix WARMING for freshly
  booted or autoscaled replicas (exactly the weights pattern).

Byte identity survives because every hop is a bit-exact copy and the
volume id binds the bytes to the chain: the manifest records the chain
hashes and a model-geometry fingerprint, a fetch validates both, and
ANY failure — missing volume, holder death mid-stream, fingerprint or
chain mismatch, truncated blob — returns a miss/error so the engine
falls back to plain local recompute, never a misaligned resume.
"""

from __future__ import annotations

import json
import os
import struct
import tempfile
from typing import Sequence

import numpy as np

from oim_tpu.common import metrics as M
from oim_tpu.common.logging import from_context
from oim_tpu.serve.weights import _dtype_name, _leaf_dtype

_MAGIC = b"OIMK0002"  # 0002: one shape a pool leaf ("shapes"), not k/v

# Volume-id prefix for exported chains: the id is a pure function of
# the chain (deepest hash names all of it — chain hashes are
# cumulative), so every replica that exports the same prefix publishes
# the SAME id and the controller's content addressing dedups the bytes.
VOLUME_PREFIX = "kvchain"


def config_fingerprint(cfg, page_tokens: int) -> dict:
    """The geometry a KV block's bytes depend on. Two engines whose
    fingerprints match hold interchangeable pages; a mismatch (other
    model, other page size) makes a fetched blob unusable and the
    unpack refuses it."""
    latent = bool(getattr(cfg, "kv_lora_rank", 0))
    fp = {
        "n_layers": int(cfg.n_layers),
        # The cache's kind and, per leaf, what a position keeps: a latent
        # volume must never be adopted by a GQA replica, nor the reverse,
        # even where the byte counts happen to agree.
        "cache": "latent" if latent else "gqa",
        "leaves": {name: [int(d) for d in tail]
                   for name, tail in sorted(cfg.cache_leaves.items())},
        "dtype": _dtype_name(np.dtype(cfg.dtype)),
        "page_tokens": int(page_tokens),
    }
    if not latent:
        fp.update(n_kv_heads=int(cfg.n_kv_heads), head_dim=int(cfg.head_dim))
    return fp


def chain_volume_id(hashes: Sequence[str]) -> str:
    """The content address of a chain's volume: hashes are cumulative
    (hash i commits to every token before it), so the deepest hash
    names the whole chain."""
    if not hashes:
        raise ValueError("empty chain has no volume id")
    return f"{VOLUME_PREFIX}-{hashes[-1]}"


def pack_chain(hashes: Sequence[str], blocks, block: int,
               fingerprint: dict) -> bytes:
    """Serialize a chain's blocks — ``blocks[i]`` is the tuple of host
    arrays for ``hashes[i]``, one a leaf of the pool (``kvtier.page_kv``:
    (k, v) for GQA, (kv,) for a latent pool) — into one self-describing
    blob: magic + uint64 header length + sorted-keys JSON manifest + raw
    bytes per block in chain order, leaf after leaf. Deterministic for a given chain, so
    identical prefixes pack to identical bytes on every replica and
    content-address to one stage-cache entry."""
    if len(blocks) != len(hashes):
        raise ValueError(
            f"pack needs one block per hash: {len(hashes)} hashes, "
            f"{len(blocks)} blocks")
    if not hashes:
        raise ValueError("refusing to pack an empty chain")
    first = [np.ascontiguousarray(a) for a in blocks[0]]
    shapes = [a.shape for a in first]
    block_bytes = int(sum(a.nbytes for a in first))
    header = json.dumps({
        "chain": list(hashes),
        "block": int(block),
        "fingerprint": fingerprint,
        "shapes": [list(shape) for shape in shapes],
        "dtype": _dtype_name(first[0].dtype),
        "block_bytes": block_bytes,
        "total_bytes": block_bytes * len(blocks),
    }, sort_keys=True).encode()
    out = bytearray()
    out += _MAGIC
    out += struct.pack("<Q", len(header))
    out += header
    for leaves in blocks:
        leaves = [np.ascontiguousarray(a) for a in leaves]
        if [a.shape for a in leaves] != shapes:
            raise ValueError("ragged chain blocks cannot pack")
        for a in leaves:
            # memoryview, not the array: bytearray += ndarray is
            # elementwise add, not concatenation (weights.py discipline).
            out += memoryview(a).cast("B")
    return bytes(out)


def unpack_chain(buf, fingerprint: dict | None = None):
    """Rebuild (hashes, blocks, block_tokens) from packed bytes or a
    uint8 numpy view of them. Raises ``ValueError`` on ANY defect —
    bad magic, truncation, geometry mismatch against ``fingerprint`` —
    because a partial chain must never be resumed misaligned; the
    caller treats the error as a fetch failure and recomputes."""
    data = np.frombuffer(buf, dtype=np.uint8) if isinstance(
        buf, (bytes, bytearray, memoryview)) else np.asarray(buf)
    if data.dtype != np.uint8:
        data = data.view(np.uint8)
    data = data.reshape(-1)
    if data[:len(_MAGIC)].tobytes() != _MAGIC:
        raise ValueError("not a packed oim KV-chain blob (bad magic)")
    (hlen,) = struct.unpack(
        "<Q", data[len(_MAGIC):len(_MAGIC) + 8].tobytes())
    body = len(_MAGIC) + 8
    header = json.loads(data[body:body + hlen].tobytes())
    if fingerprint is not None and header["fingerprint"] != fingerprint:
        raise ValueError(
            f"KV-chain fingerprint mismatch: blob packed for "
            f"{header['fingerprint']}, engine expects {fingerprint}")
    base = body + hlen
    if len(data) - base < header["total_bytes"]:
        raise ValueError(
            f"truncated KV-chain blob: {len(data) - base} payload "
            f"bytes, manifest claims {header['total_bytes']}")
    dtype = _leaf_dtype(header["dtype"])
    shapes = [tuple(shape) for shape in header["shapes"]]
    blocks = []
    off = base
    for _ in header["chain"]:
        leaves = []
        for shape in shapes:
            nbytes = int(np.prod(shape)) * dtype.itemsize
            leaves.append(data[off:off + nbytes].view(dtype).reshape(shape))
            off += nbytes
        blocks.append(tuple(leaves))
    return list(header["chain"]), blocks, int(header["block"])


def chain_request(volume_id: str, path: str, total_bytes: int):
    """The MapVolumeRequest publishing a packed chain file as a raw
    uint8 volume (the weights_request shape, so publish and prestage
    content-key identically on every replica)."""
    from oim_tpu.spec import pb

    return pb.MapVolumeRequest(
        volume_id=volume_id,
        spec=pb.ArraySpec(shape=[total_bytes], dtype="uint8"),
        file=pb.FileParams(path=path, format="raw"),
    )


def export_chain(engine, feeder, hashes: Sequence[str],
                 timeout: float = 60.0) -> str | None:
    """Export one cached chain from ``engine`` as a content-addressed
    volume through ``feeder``: snapshot (D2H on the engine thread, via
    its command queue), pack, publish. Returns the volume id, or None
    when the chain is no longer fully cached (a best-effort export
    never races retirement into a partial blob)."""
    hashes = list(hashes)
    blocks = engine.snapshot_chain(hashes, timeout=timeout)
    if not blocks:
        return None
    fingerprint = config_fingerprint(engine.cfg, engine.page_tokens)
    blob = pack_chain(hashes, blocks, engine.prefix_block, fingerprint)
    volume_id = chain_volume_id(hashes)
    fd, path = tempfile.mkstemp(prefix="oim-kvchain-", suffix=".bin")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
        pub = feeder.publish(
            chain_request(volume_id, path, len(blob)), timeout=timeout)
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass
    M.KVTIER_EXPORTS.inc()
    note = getattr(engine, "note_exported", None)
    if callable(note):
        note(hashes[-1], volume_id)
    from oim_tpu.common import events

    events.emit(events.KV_CHAIN_EXPORTED, volume=volume_id,
                blocks=len(hashes), bytes=int(pub.bytes))
    from_context().info("exported KV chain volume", volume=volume_id,
                        blocks=len(hashes), bytes=int(pub.bytes))
    return volume_id


class PeerPrefixFetcher:
    """The engine's ``kv_fetch`` callback: resolve which exported
    volume covers the request's chain, read it over the feeder's
    direct data path, validate, and hand back the adoptable blocks.

    ``known`` is an optional callable returning the deepest hashes
    known exported fleet-wide (from the heartbeat ``prefix_volumes``
    advertisement); without it, local mode probes the attached
    controller directly (get_volume misses are free) and remote mode
    probes only the full chain (blind depth scans would each pay a
    failed RPC).

    Contract with the engine: return the consecutive blocks extending
    the local match (possibly []), or None after a fetch that STARTED
    and failed — the engine emits the fallback event for None and
    recomputes either way, so a broken peer can cost latency but never
    correctness.
    """

    def __init__(self, feeder, fingerprint: dict, known=None,
                 timeout: float = 10.0):
        self.feeder = feeder
        self.fingerprint = fingerprint
        self.known = known
        self.timeout = timeout

    def _candidate_depths(self, chain: list[str], m: int) -> list[int]:
        depths = list(range(len(chain), m, -1))
        if self.known is not None:
            try:
                known = set(self.known())
            except Exception:  # noqa: BLE001 - advisory source only
                known = set()
            return [j for j in depths if chain[j - 1] in known]
        if self.feeder.controller is not None:
            return depths  # local probes are a dict lookup
        return depths[:1]  # remote: only the full chain, no blind scan

    def _read(self, volume_id: str):
        if self.feeder.controller is not None:
            volume = self.feeder.controller.get_volume(volume_id)
            if volume is None:
                return None
            return np.asarray(volume.array)
        raw, _, _ = self.feeder.fetch_window(
            volume_id, 0, 0, timeout=self.timeout)
        return raw

    def __call__(self, chain, m: int):
        chain = list(chain)
        try:
            for j in self._candidate_depths(chain, m):
                volume_id = chain_volume_id(chain[:j])
                raw = self._read(volume_id)
                if raw is None:
                    continue
                hashes, blocks, _ = unpack_chain(raw, self.fingerprint)
                if hashes != chain[:j]:
                    raise ValueError(
                        f"volume {volume_id} does not hold the chain "
                        f"it is addressed by")
                M.SERVE_PREFIX_PEER_FETCHES.labels(outcome="hit").inc()
                return [(chain[i], blocks[i]) for i in range(m, j)]
        except Exception as err:  # noqa: BLE001 - any defect => recompute
            M.SERVE_PREFIX_PEER_FETCHES.labels(outcome="error").inc()
            from_context().warning(
                "peer prefix fetch failed; recomputing locally",
                error=repr(err))
            return None
        M.SERVE_PREFIX_PEER_FETCHES.labels(outcome="miss").inc()
        return []
