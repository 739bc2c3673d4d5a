"""Checkpoint params as ONE content-addressed volume: the serving tier's
weight-distribution path.

A params pytree is packed into a single self-describing blob (JSON leaf
manifest + concatenated leaf bytes), written to disk ONCE, and published
through the ordinary feeder/controller path as a raw uint8 volume. From
there the PR 4/5 machinery does the fan-out for free:

* the FIRST serving replica's publish stages the blob from source (one
  disk scan, content-addressed into the controller's stage cache);
* every OTHER replica is warmed with ``PrestageVolume`` — its later
  ``MapVolume`` of the identical content is an O(1) cache hit with ZERO
  source re-reads (provable from oim_stage_cache_hits_total);
* a replica restores the params tree from the staged bytes (zero-copy
  views in local mode; one direct-path window read in remote mode).

Publish once, prestage N, boot N replicas from cache — the same shape as
warm-standby failover, applied to model weights.
"""

from __future__ import annotations

import json
import re
import struct
from typing import Any

import numpy as np

from oim_tpu.common.logging import from_context

_MAGIC = b"OIMW0001"


def _leaf_dtype(name: str) -> np.dtype:
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def _dtype_name(dtype) -> str:
    name = np.dtype(dtype).name
    if name == "void16":  # numpy's view of a raw bfloat16 buffer
        name = "bfloat16"
    return name


def _pack_parts(params: Any):
    """(preamble bytes, [leaf byte views]) of the packed form: magic +
    uint64 header length + JSON manifest (tree paths, dtypes, shapes,
    offsets), then the raw leaf bytes in manifest order."""
    import jax

    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    manifest = []
    blobs = []
    offset = 0
    for path, leaf in leaves_with_paths:
        arr = np.ascontiguousarray(np.asarray(leaf))
        manifest.append({
            "path": jax.tree_util.keystr(path),
            "dtype": _dtype_name(arr.dtype),
            "shape": list(arr.shape),
            "offset": offset,
            "bytes": int(arr.nbytes),
        })
        # A uint8 view, not memoryview(arr): the buffer protocol has no
        # format for bfloat16 (the dtype every real-width leaf has).
        blobs.append(arr.reshape(-1).view(np.uint8))
        offset += arr.nbytes
    header = json.dumps({
        "leaves": manifest,
        "treedef": str(treedef),
        "total_bytes": offset,
    }, sort_keys=True).encode()
    return _MAGIC + struct.pack("<Q", len(header)) + header, blobs


def pack_params(params: Any) -> bytes:
    """Serialize a params pytree to bytes. Deterministic for a given
    tree, so identical checkpoints pack to identical bytes and
    content-address to one stage-cache entry."""
    preamble, blobs = _pack_parts(params)
    return preamble + b"".join(memoryview(raw) for raw in blobs)


def unpack_params(buf) -> dict:
    """Rebuild the params tree from packed bytes (or a uint8 numpy view
    of them — leaves come back as ZERO-COPY views into ``buf`` when it is
    an array, so a staged volume restores without duplicating host RAM).
    The tree is returned as nested dicts/lists keyed by the recorded tree
    paths — structurally identical to the packed pytree for the
    dict/list trees the model family uses."""
    data = np.frombuffer(buf, dtype=np.uint8) if isinstance(
        buf, (bytes, bytearray, memoryview)) else np.asarray(buf)
    if data.dtype != np.uint8:
        data = data.view(np.uint8)
    data = data.reshape(-1)
    if data[:len(_MAGIC)].tobytes() != _MAGIC:
        raise ValueError("not a packed oim weights blob (bad magic)")
    (hlen,) = struct.unpack("<Q", data[len(_MAGIC):len(_MAGIC) + 8].tobytes())
    body = len(_MAGIC) + 8
    header = json.loads(data[body:body + hlen].tobytes())
    base = body + hlen
    tree: dict = {}
    for leaf in header["leaves"]:
        raw = data[base + leaf["offset"]:base + leaf["offset"] + leaf["bytes"]]
        arr = raw.view(_leaf_dtype(leaf["dtype"])).reshape(leaf["shape"])
        _insert(tree, leaf["path"], arr)
    return tree


# Leaves a checkpoint may carry and serving never reads, by the name of any
# key on their path: the multi-token-prediction module of the DeepSeek-V3
# family (``num_nextn_predict_layers``). The main model's forward is whole
# without it, and serve/spec.py drafts from a separate model only.
IGNORED_KEYS = ("mtp", "nextn")


def _insert(tree: dict, keystr: str, leaf) -> None:
    """Place a leaf at a jax.tree_util.keystr path like
    "['layers']['wq']" — dict keys only (the llama param tree). A leaf
    under an ``IGNORED_KEYS`` name is dropped."""
    keys = re.findall(r"\['([^']+)'\]", keystr)
    if "".join(f"['{k}']" for k in keys) != keystr or not keys:
        raise ValueError(f"unsupported tree path {keystr!r}")
    if any(k.startswith(IGNORED_KEYS) for k in keys):
        return
    node = tree
    for k in keys[:-1]:
        node = node.setdefault(k, {})
    node[keys[-1]] = leaf


def rope_split_half(params: dict, cfg) -> dict:
    """A latent-attention tree whose rope dims are in the PUBLISHED
    interleaved order (``rope_interleave: true``: pairs (2i, 2i+1)), in
    the order the program rotates (split-half): the ``qk_rope_head_dim``
    output columns of every head of ``wq_b`` and of ``wkv_a`` permuted by
    ``ops.rope.interleaved_to_split_half``. Query and key are permuted
    alike, so every score is unchanged (tests/test_latent.py)."""
    from oim_tpu.ops.rope import interleaved_to_split_half

    m = cfg.latent
    perm = np.asarray(interleaved_to_split_half(m.rope))
    head = np.concatenate([np.arange(m.nope), m.nope + perm])
    q_cols = (np.arange(m.heads)[:, None] * (m.nope + m.rope)
              + head[None, :]).reshape(-1)
    kv_cols = np.concatenate([np.arange(m.rank), m.rank + perm])

    def group(layers):
        return {**layers, "wq_b": layers["wq_b"][..., q_cols],
                "wkv_a": layers["wkv_a"][..., kv_cols]}

    from oim_tpu.models.llama import LAYER_GROUPS

    return {k: group(v) if k in LAYER_GROUPS else v
            for k, v in params.items()}


def save_packed(params: Any, path: str) -> int:
    """Pack ``params`` to ``path``; returns the byte size. The file is
    the volume SOURCE — publish it with :func:`publish_weights`."""
    preamble, blobs = _pack_parts(params)
    with open(path, "wb") as f:
        # Leaf by leaf: an 8B-class tree is never built a second (or
        # third) time in host RAM just to reach the disk.
        f.write(preamble)
        for raw in blobs:
            f.write(memoryview(raw))
    return len(preamble) + sum(raw.nbytes for raw in blobs)


def weights_request(volume_id: str, path: str, total_bytes: int):
    """The MapVolumeRequest publishing a packed weights file as a raw
    uint8 volume (shared by publish and prestage so the content key —
    request params + source fingerprint — is identical on every
    replica)."""
    from oim_tpu.spec import pb

    return pb.MapVolumeRequest(
        volume_id=volume_id,
        spec=pb.ArraySpec(shape=[total_bytes], dtype="uint8"),
        file=pb.FileParams(path=path, format="raw"),
    )


def publish_weights(feeder, volume_id: str, path: str,
                    timeout: float = 300.0):
    """Publish a packed weights file through ``feeder`` (local or
    remote); returns the PublishedVolume."""
    import os

    request = weights_request(volume_id, path, os.path.getsize(path))
    pub = feeder.publish(request, timeout=timeout)
    from oim_tpu.data import staging

    # read_path: which engine read the source in THIS process (native /
    # io_uring / readinto; "none" = staged remotely or a cache hit).
    from_context().info(
        "published weights volume", volume=volume_id, bytes=pub.bytes,
        read_path=staging.read_path())
    return pub


# What the most recent restore_weights() call in this process staged —
# the sharded-restore accounting tests and bench read (bytes_staged at
# rank k is the member's HBM weight footprint: split leaves contribute
# 1/shard of their bytes, replicated leaves their full size).
LAST_RESTORE: dict = {}


def _shard_axis(keystr: str, ndim: int) -> int | None:
    """The Megatron split axis for one manifest leaf (None =
    replicated): COL leaves slice their last dim (output features /
    heads — a contiguous slice keeps each query head with its own GQA
    KV head), ROW leaves dim 1 (input features, after the stacked
    layer dim). The sets live in serve/shard.py so the restore and the
    engine's shard_map specs can never disagree about which leaf
    splits which way."""
    from oim_tpu.serve.shard import COL, ROW

    name = re.findall(r"\['([^']+)'\]", keystr)[-1]
    if name in COL:
        return ndim - 1
    if name in ROW:
        return 1
    return None


def _unpack_shard(data: np.ndarray, shard: int, rank: int) -> dict:
    """Rank ``rank``'s member-local params tree from packed bytes: each
    split leaf is materialized as ONLY its 1/shard slice (one compact
    copy out of the staged volume), replicated leaves stay zero-copy
    views. Every rank reads the SAME byte-identical manifest — the
    slice geometry is derived, never negotiated."""
    if data.dtype != np.uint8:
        data = data.view(np.uint8)
    data = data.reshape(-1)
    if data[:len(_MAGIC)].tobytes() != _MAGIC:
        raise ValueError("not a packed oim weights blob (bad magic)")
    (hlen,) = struct.unpack("<Q", data[len(_MAGIC):len(_MAGIC) + 8].tobytes())
    body = len(_MAGIC) + 8
    header = json.loads(data[body:body + hlen].tobytes())
    base = body + hlen
    tree: dict = {}
    staged = 0
    for leaf in header["leaves"]:
        raw = data[base + leaf["offset"]:base + leaf["offset"] + leaf["bytes"]]
        arr = raw.view(_leaf_dtype(leaf["dtype"])).reshape(leaf["shape"])
        axis = _shard_axis(leaf["path"], arr.ndim)
        if axis is not None:
            n = arr.shape[axis]
            if n % shard:
                raise ValueError(
                    f"leaf {leaf['path']} dim {axis} ({n}) does not "
                    f"divide by shard={shard}")
            width = n // shard
            idx = [slice(None)] * arr.ndim
            idx[axis] = slice(rank * width, (rank + 1) * width)
            arr = np.ascontiguousarray(arr[tuple(idx)])
        staged += arr.nbytes
        _insert(tree, leaf["path"], arr)
    LAST_RESTORE.clear()
    LAST_RESTORE.update(
        shard=shard, rank=rank, bytes_staged=staged,
        total_bytes=int(header["total_bytes"]))
    return tree


def restore_weights(feeder, volume_id: str, timeout: float = 300.0, *,
                    shard: int = 1, rank: int = 0) -> dict:
    """The params tree from a published weights volume: zero-copy views
    of the resident array in local mode, one whole-volume window read
    (direct path when resolvable) in remote mode.

    ``shard > 1`` is the sharded restore: member ``rank`` of an N-way
    tensor-parallel replica gets its MEMBER-LOCAL tree — split leaves
    sliced to this rank's heads/features, replicated leaves whole — out
    of the same published volume every other member reads (one publish,
    one content-addressed manifest, N partial restores; reassembling
    all ranks along the split axes reproduces the full tree
    byte-identically)."""
    if not 0 <= rank < max(shard, 1):
        raise ValueError(f"rank {rank} outside shard={shard}")
    if feeder.controller is not None:
        volume = feeder.controller.get_volume(volume_id)
        if volume is None:
            raise ValueError(f"no volume {volume_id!r} on the controller")
        data = np.asarray(volume.array)
    else:
        raw, _, _ = feeder.fetch_window(volume_id, 0, 0, timeout=timeout)
        data = np.frombuffer(raw, dtype=np.uint8)
    if shard < 2:
        tree = unpack_params(data)
        LAST_RESTORE.clear()
        LAST_RESTORE.update(
            shard=1, rank=0, bytes_staged=int(data.nbytes),
            total_bytes=int(data.nbytes))
        return tree
    return _unpack_shard(data, shard, rank)
