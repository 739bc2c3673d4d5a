"""Host-RAM tier for the prefix KV store: demote instead of drop.

The prefix store (serve/prefixcache.py) holds chains only as HBM page
references, so eviction pressure — LRU overflow or the pool-pressure
valve — used to DESTROY a chain's K/V outright, and the next request
for that prefix paid a full prefill. This module adds the middle rung
of the tier lattice:

    hbm (PagePool page, zero-copy shareable)
      |  demote: D2H copy on eviction of a store-only page
      v
    host (numpy block in this LRU, bounded by --kv-host-bytes)
      |  promote: H2D re-stage into a freshly allocated page on a hit
      v
    volume (serve/kvvolume.py: content-addressed blob on a controller)

A page lives in exactly ONE tier: demotion captures the bytes before
the HBM page frees, promotion pops the host entry after the bytes land
back on device (move semantics — the census sums tiers without double
counting). Byte identity is free: K/V at a position is a pure function
of the token chain, and both transitions are bit-exact copies, so a
promoted block holds exactly what a fresh prefill would recompute.

Threading: ``HostTier`` itself is lock-protected, but the D2H/H2D
helpers touch the engine's device pool, whose buffers are DONATED to
the jitted step programs — they must only run on the engine thread
(the engine calls them from its admission/retirement paths; external
snapshots go through the engine's command queue).

Visibility: oim_kvtier_host_{pages,bytes} gauges,
oim_kvtier_{demotions,promotions}_total counters.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict

import numpy as np

from oim_tpu.common import metrics as M


class _HostBlock:
    """One demoted block: what ``page_tokens`` positions of one chain hash
    keep in the cache, as one host numpy array [L, page_tokens, ...] a
    leaf of the pool, in the order of the pool's sorted leaf names ((k, v)
    for GQA, (kv,) for a latent pool)."""

    __slots__ = ("key", "leaves", "nbytes")

    def __init__(self, key: str, leaves: tuple):
        self.key = key
        self.leaves = leaves
        self.nbytes = int(sum(a.nbytes for a in leaves))


class HostTier:
    """Thread-safe LRU of demoted prefix blocks, bounded by
    ``capacity_bytes`` of host RAM. ``capacity_bytes=0`` disables the
    tier (puts are dropped) — the ``--kv-host-bytes 0`` off switch."""

    def __init__(self, capacity_bytes: int, track_metrics: bool = True):
        self.capacity_bytes = max(0, int(capacity_bytes))
        self.track_metrics = track_metrics
        self._blocks: OrderedDict[str, _HostBlock] = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.demotions = 0
        self.promotions = 0
        if track_metrics:
            M.KVTIER_HOST_PAGES.set(0)
            M.KVTIER_HOST_BYTES.set(0)

    def put(self, key: str, *leaves: np.ndarray) -> bool:
        """Admit one demoted block (MRU), LRU-evicting to fit. False
        when the tier is disabled or the block alone exceeds the
        budget (the chain is simply dropped, as pre-tier eviction
        always did)."""
        block = _HostBlock(key, leaves)
        with self._lock:
            if block.nbytes > self.capacity_bytes:
                return False
            old = self._blocks.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            while (self._bytes + block.nbytes > self.capacity_bytes
                   and self._blocks):
                _, victim = self._blocks.popitem(last=False)
                self._bytes -= victim.nbytes
            self._blocks[key] = block
            self._bytes += block.nbytes
            self.demotions += 1
            self._update_locked()
        if self.track_metrics:
            M.KVTIER_DEMOTIONS.inc()
        return True

    def get(self, key: str) -> tuple | None:
        """The block's leaves, MRU-touched; None when absent."""
        with self._lock:
            block = self._blocks.get(key)
            if block is None:
                return None
            self._blocks.move_to_end(key)
            return block.leaves

    def pop(self, key: str, promoted: bool = True) -> bool:
        """Remove a block — the promotion's second half (the bytes are
        back on device; move semantics keep a block in one tier).
        Returns whether the key was present."""
        with self._lock:
            block = self._blocks.pop(key, None)
            if block is None:
                return False
            self._bytes -= block.nbytes
            if promoted:
                self.promotions += 1
            self._update_locked()
        if promoted and self.track_metrics:
            M.KVTIER_PROMOTIONS.inc()
        return True

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return key in self._blocks

    def __len__(self) -> int:
        with self._lock:
            return len(self._blocks)

    def hot(self, n: int) -> list[str]:
        """The ``n`` most-recently-used keys, hottest first — the host
        half of the replica's tier advertisement."""
        with self._lock:
            keys = list(self._blocks.keys())
        return keys[::-1][:n]

    def evict_all(self) -> int:
        """Drop every block NOW (drain/census). Returns blocks dropped."""
        with self._lock:
            n = len(self._blocks)
            self._blocks.clear()
            self._bytes = 0
            self._update_locked()
        return n

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._blocks),
                "bytes": self._bytes,
                "capacity_bytes": self.capacity_bytes,
                "demotions": self.demotions,
                "promotions": self.promotions,
            }

    def _update_locked(self) -> None:
        if self.track_metrics:
            M.KVTIER_HOST_PAGES.set(len(self._blocks))
            M.KVTIER_HOST_BYTES.set(self._bytes)


# -- device <-> host block movement (engine-thread only) -----------------

def page_kv(cache: dict, page: int) -> tuple:
    """D2H: one physical page as host arrays [L, page_tokens, ...], one a
    leaf of the pool in sorted-name order ((k, v) for GQA, (kv,) for a
    latent pool). Reads the engine's device pool, so engine-thread only
    (the buffers are donated to the step programs between the engine's
    own dispatches)."""
    return tuple(np.asarray(cache[name][:, page]) for name in sorted(cache))


@functools.lru_cache(maxsize=64)
def _stage_program(geometry: tuple):
    """H2D re-stage of one page or of N (``pages`` a scalar or [N]),
    jitted once per pool geometry and shared across engines (the
    _target_programs discipline). The pool is DONATED so writing a page
    never copies the whole pool — the promotion's device cost is the
    pages' H2D plus an aliased update."""
    import jax

    def stage(pool, pages, blocks):
        return {name: pool[name].at[:, pages].set(block)
                for name, block in zip(sorted(pool), blocks)}

    del geometry  # cache key only: geometry selects the HLO
    return jax.jit(stage, donate_argnums=(0,))


def _geometry(cache: dict) -> tuple:
    return tuple((name, tuple(a.shape), str(a.dtype))
                 for name, a in sorted(cache.items()))


def stage_page(cache: dict, page: int, *leaves: np.ndarray) -> dict:
    """H2D: write one block's leaves (``page_kv`` order) into physical
    ``page`` of the device pool, returning the NEW pool dict (the old
    buffers are donated, matching the engine's cache-threading
    discipline). Engine-thread only."""
    import jax.numpy as jnp

    return _stage_program(_geometry(cache))(
        cache, jnp.int32(page), tuple(jnp.asarray(a) for a in leaves))


def stage_pages(cache: dict, pages: list, blocks: list) -> dict:
    """H2D: write N blocks (each a tuple of leaves in ``page_kv`` order)
    into N pool pages in ONE jitted scatter, returning the NEW pool dict.
    A peer-fetch adoption stages whole chains at once; per-page dispatch
    overhead would eat a good slice of the prefill it is there to save.
    Compiled per (chain length, pool geometry) — adoption lengths repeat.
    Engine-thread only."""
    import jax.numpy as jnp

    if len(pages) == 1:
        return stage_page(cache, pages[0], *blocks[0])
    # Stack along axis 1: a leaf is [L, page, tok, ...], so the scatter
    # operand is [L, N, tok, ...].
    return _stage_program(_geometry(cache))(
        cache, jnp.asarray(np.asarray(pages, np.int32)),
        tuple(jnp.asarray(np.stack(leaf, axis=1)) for leaf in zip(*blocks)))
