"""Latent attention (MLA, the DeepSeek-V2/V3 family) over a latent cache.

What a position keeps is ONE vector for all heads: ``[c_kv | k_r]``, the
normed low-rank latent (``rank`` wide) and the rotated rope dims
(``rope`` wide) that every head shares (zero-padded to whole lanes:
``Dims.width``). ``wkv_b`` [rank, H * (nope + v)]
expands a latent into each head's key part ``k_n`` and value ``v``.

Two forms of the same sum, both plain ``jax.numpy`` with an online
softmax over BLOCKS of keys, so that no [H, T, S] array ever exists:

- EXPANDED (``full_attention``, and ``paged_attention`` for T > 1: the
  full-sequence forward and every prefill chunk): a block's latents are
  expanded to per-head ``k = [k_n | k_r]`` and ``v``; 2 * (192 + 128)
  operations a head, query and key.
- ABSORBED (``paged_attention`` for T == 1: the decode step): the query is
  taken into the latent space instead, ``q_l = q_n W_k^T`` (rank wide a
  head), scored against the latent itself, ``o_l = softmax(s) c_kv``, and
  expanded once at the end, ``o = o_l W_v``. The cache is read once, ``width``
  wide, for all heads: a decode step is bound by those bytes.

The paged forms read pool[layer] [n_pages, page, rank + rope] through the
page tables, a block of pages at a time, up to the furthest live position
only (a traced loop bound), in place in the carried pool: a row whose
first table entry is the scratch page 0 is idle and reads nothing.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from oim_tpu.ops.attention import NEG_INF, _log_dispatch

# Key positions an iteration of the online softmax holds. Scores of a
# 2048-token prefill chunk are [H, 2048, KEY_BLOCK] f32: 134 MB at 32 heads.
KEY_BLOCK = 512
LANES = 128


@dataclasses.dataclass(frozen=True)
class Dims:
    heads: int
    rank: int    # kv_lora_rank: the normed latent
    nope: int    # qk_nope_head_dim
    rope: int    # qk_rope_head_dim, shared by all heads
    v: int       # v_head_dim

    @property
    def scale(self) -> float:
        return (self.nope + self.rope) ** -0.5

    @property
    def width(self) -> int:
        """What a position's cache entry holds: ``[c_kv | k_r | 0...]``,
        rank + rope padded with zeros to whole 128-lane tiles (576 ->
        640). Unpadded, the TPU compiler gives the pool a compact entry
        layout with the PAGE index minor-most and relayouts the whole
        pool on the way in and out of every program (two copies of 1.7 GB
        a decode step at JoyAI-LLM-Flash's sizes, read off the compiled
        programs: tests/test_chip_compile.py holds them to none)."""
        return -(-(self.rank + self.rope) // LANES) * LANES

    def entry(self, c_kv, k_r):
        """The cache entry of normed latents [..., rank] and rotated rope
        dims [..., rope]."""
        pad = jnp.zeros(c_kv.shape[:-1] + (self.width - self.rank - self.rope,),
                        c_kv.dtype)
        return jnp.concatenate([c_kv, k_r.astype(c_kv.dtype), pad], axis=-1)


def _split_wkv_b(wkv_b, d: Dims):
    w = wkv_b.reshape(d.rank, d.heads, d.nope + d.v)
    return w[..., :d.nope], w[..., d.nope:]  # [rank, H, nope], [rank, H, v]


def expand(latent, wkv_b, d: Dims):
    """latent [..., rank + rope] -> (k [..., H, nope + rope], v [..., H, v])."""
    lead = latent.shape[:-1]
    kv = (latent[..., :d.rank] @ wkv_b).reshape(lead + (d.heads, d.nope + d.v))
    k_r = jnp.broadcast_to(latent[..., None, d.rank:d.rank + d.rope],
                           lead + (d.heads, d.rope))
    return jnp.concatenate([kv[..., :d.nope], k_r], axis=-1), kv[..., d.nope:]


def _fold(n_blocks, block_of, stat_shape, out_shape):
    """The online softmax over key blocks 0..n_blocks-1 (static or traced).
    ``block_of(i)`` -> (scores f32 [*stat_shape, KB], valid bool
    broadcastable to it, ``weigh``), ``weigh(p)`` being the block's sum of
    p-weighted values, f32 [*out_shape]: a block's keys are fetched once
    for both. Returns softmax(scores) @ values, f32."""

    def body(i, carry):
        m, l, acc = carry
        s, valid, weigh = block_of(i)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # A block may hold no valid key of a row (a row shorter than the
        # batch's longest): exp(NEG_INF - NEG_INF) must not count.
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + weigh(p)
        return m_new, l, acc

    init = (jnp.full(stat_shape, NEG_INF, jnp.float32),
            jnp.zeros(stat_shape, jnp.float32),
            jnp.zeros(out_shape, jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, body, init)
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _expanded(q, block_latent, n_blocks, q_pos, wkv_b, d: Dims, block: int):
    """q [B, T, H, nope + rope] at absolute positions ``q_pos`` [B, T] over
    keys at positions 0..n_blocks * block - 1; ``block_latent(i)`` -> that
    block's cache entries [B, block, width]. -> [B, T, H, v]."""
    B, T, H, _ = q.shape

    def block_of(i):
        k, v = expand(block_latent(i), wkv_b, d)  # [B, KB, H, dq], [.., v]
        s = jnp.einsum("bthd,bshd->bhts", q, k,
                       preferred_element_type=jnp.float32) * d.scale
        k_pos = i * block + jnp.arange(block)
        return (s, q_pos[:, None, :, None] >= k_pos[None, None, None, :],
                lambda p: jnp.einsum("bhts,bshd->bhtd", p.astype(v.dtype), v,
                                     preferred_element_type=jnp.float32))

    out = _fold(n_blocks, block_of, (B, H, T), (B, H, T, d.v))
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # [B, T, H, v]


def full_attention(q, latent, wkv_b, d: Dims, pos=0):
    """Causal attention of q [B, T, H, dq] at positions pos + t over the
    cache entries [B, S, width] at positions 0..S-1 (the sequence itself,
    or a dense cache holding it): expanded form, static block count."""
    B, T = q.shape[:2]
    S = latent.shape[1]
    block = min(KEY_BLOCK, S)
    pad = -S % block
    if pad:  # masked: their positions lie above every query's
        latent = jnp.pad(latent, ((0, 0), (0, pad), (0, 0)))
    q_pos = jnp.broadcast_to(jnp.asarray(pos), (B,))[:, None] + jnp.arange(T)
    return _expanded(
        q, lambda i: lax.dynamic_slice_in_dim(latent, i * block, block, 1),
        (S + pad) // block, q_pos, wkv_b, d, block)


def _paged_blocks(pool, layer, tables):
    """(positions a key block, blocks a table, ``block_latent``): a block is
    whole pages and a table whole blocks; ``block_latent(i)`` gathers block
    i of every row through its table, [B, block, width] (ONE gather indexed
    by (layer, page): no slice of the pool)."""
    B, nb = tables.shape
    page, width = pool.shape[2:]
    pages = max(KEY_BLOCK // page, 1)
    while nb % pages:
        pages //= 2

    def block_latent(i):
        ids = lax.dynamic_slice_in_dim(tables, i * pages, pages, axis=1)
        return pool[layer, ids].reshape(B, pages * page, width)

    return pages * page, nb // pages, block_latent


def _absorbed(q, pool, layer, tables, pos, wkv_b, d: Dims):
    """One query a row: q [B, H, nope + rope] at ``pos`` [B] -> [B, H, v]."""
    B, H, _ = q.shape
    block, n, block_latent = _paged_blocks(pool, layer, tables)
    w_k, w_v = _split_wkv_b(wkv_b, d)
    # The query in the cache's own layout [q_l | q_r | 0]: one product
    # against the entry scores the nope and the rope part together.
    q_l = jnp.einsum("bhn,rhn->bhr", q[..., :d.nope], w_k,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    qq = d.entry(q_l, q[..., d.nope:])  # [B, H, width]
    live = tables[:, 0] != 0
    length = jnp.where(live, jnp.minimum(pos + 1, n * block), 0)

    def block_of(i):
        latent = block_latent(i)
        s = jnp.einsum("bhc,bsc->bhs", qq, latent,
                       preferred_element_type=jnp.float32) * d.scale
        k_pos = i * block + jnp.arange(block)
        c = latent[..., :d.rank]
        return (s, k_pos[None, None, :] < length[:, None, None],
                lambda p: jnp.einsum("bhs,bsr->bhr", p.astype(c.dtype), c,
                                     preferred_element_type=jnp.float32))

    o_l = _fold((jnp.max(length) + block - 1) // block, block_of,
                (B, H), (B, H, d.rank))
    return jnp.einsum("bhr,rhv->bhv", o_l.astype(q.dtype), w_v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def kernel_name(t: int) -> str:
    """The word ``paged_attention`` logs for a program of ``t`` query
    positions a row, and the engine shows in its stats."""
    return "jnp_latent_absorbed" if t == 1 else "jnp_latent_expanded"


def paged_attention(q, pool, layer, tables, pos, wkv_b, d: Dims):
    """q [B, T, H, nope + rope] at positions pos + t (``pos`` scalar or
    [B]) over pool[layer] through ``tables`` [B, n_blocks] -> [B, T, H, v].
    ``pool`` is the whole carried array [L, n_pages, page, width],
    this call's latents already scattered into it."""
    B, T = q.shape[:2]
    _log_dispatch(kernel_name(T), q, pool)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    if T == 1:
        with jax.named_scope("mla_decode"):
            return _absorbed(q[:, 0], pool, layer, tables, pos_b, wkv_b,
                             d)[:, None]
    block, n, block_latent = _paged_blocks(pool, layer, tables)
    q_pos = pos_b[:, None] + jnp.arange(T)
    with jax.named_scope("mla_prefill"):
        n_blocks = jnp.minimum((jnp.max(q_pos) + block) // block, n)
        return _expanded(q, block_latent, n_blocks, q_pos, wkv_b, d, block)
