"""Latent attention (MLA, the DeepSeek-V2/V3 family) over a latent cache.

What a position keeps is ONE vector for all heads: ``[c_kv | k_r]``, the
normed low-rank latent (``rank`` wide) and the rotated rope dims
(``rope`` wide) that every head shares (zero-padded to whole lanes:
``Dims.width``). ``wkv_b`` [rank, H * (nope + v)]
expands a latent into each head's key part ``k_n`` and value ``v``.

Two forms of the same sum, an online softmax over BLOCKS of keys, so that
no [H, T, S] array ever exists:

- EXPANDED (``full_attention``, and ``paged_attention`` for T > 1: the
  full-sequence forward, every prefill chunk and ``verify_step``): a
  block's latents are expanded to per-head ``k = [k_n | k_r]`` and ``v``;
  2 * (192 + 128) operations a head, query and key. Plain ``jax.numpy``.
- ABSORBED (``paged_attention`` for T == 1: the decode step): the query is
  taken into the latent space instead, ``q_l = q_n W_k^T`` (rank wide a
  head), scored against the latent itself, ``o_l = softmax(s) c_kv``, and
  expanded once at the end, ``o = o_l W_v``. The cache is read once, ``width``
  wide, for all heads: a decode step is bound by those bytes.

The paged forms read pool[layer] [n_pages, page, width] through the page
tables, in place in the carried pool: a row whose first table entry is the
scratch page 0 is idle and reads nothing. Which program takes which path
(``_latent_plan``, by shapes and backend alone; ``kernel_name`` says it):

- ``pallas_latent``: the absorbed sum of a decode step on a TPU. A Pallas
  kernel walks each LIVE row's table up to the row's own position and
  copies those pages, [page, width] slabs, into a double-buffered VMEM
  block: one copy a page serves the scores (the whole entry) and the
  values (its first ``rank`` columns). The call stands where a key block
  stands in the reference's loop, under the same carry. The sibling of
  ``ops/paged_attention.py _paged_kernel`` (GQA pools), sharing no body
  with it: one buffer for both sides, no head axis in the cache.
- ``jnp_latent_absorbed``: the same sum in ``jax.numpy``, a block of
  pages of EVERY row gathered at a time up to the furthest live position;
  the kernel's reference, and what a CPU runs.
- ``jnp_latent_expanded``: every program of T > 1, on any backend.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from oim_tpu.ops.attention import NEG_INF, _log_dispatch

# Key positions an iteration of the online softmax holds. Scores of a
# 2048-token prefill chunk are [H, 2048, KEY_BLOCK] f32: 134 MB at 32 heads.
KEY_BLOCK = 512
LANES = 128
# Positions a block of the decode kernel holds (a page copy is 20 KB, so a
# block's fixed cost is spread over many of them). On a v5e at 8 live rows of
# 32, 93 441 positions, 5 layers, page 16, blocks of 128 / 256 / 512 / 1024 /
# 2048 positions read the live entries at 213 / 277 / 334 / 362 / 377 GB/s
# (PERF.md section 6, PR 29); 1024 keeps the two buffers at 2.6 MB of VMEM
# beside q and o, which grow with the heads.
BLOCK_TOKENS = 1024
# What the TPU compiler gives a kernel's scoped allocations unless asked for
# more, and the room a call leaves beside what it counts itself.
VMEM_SCOPE = 16 << 20
VMEM_ROOM = 4 << 20


@dataclasses.dataclass(frozen=True)
class Dims:
    heads: int
    rank: int    # kv_lora_rank: the normed latent
    nope: int    # qk_nope_head_dim
    rope: int    # qk_rope_head_dim, shared by all heads
    v: int       # v_head_dim
    # YaRN's attention temperature (``use_mla_scaling_factor``: 0.1 ln(factor)
    # + 1 where the rotary tables are stretched): the softmax scale takes its
    # square, as a query and a key each scaled by it would.
    mscale: float = 1.0

    @property
    def scale(self) -> float:
        return (self.nope + self.rope) ** -0.5 * self.mscale ** 2

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


def _gathered_sum(qq, pool, layer, tables, pos, d: Dims):
    """The reference of the absorbed sum: softmax(qq . entry * scale) c_kv
    of qq [B, H, width] at ``pos`` [B] -> [B, H, rank], a gathered block of
    every row at a time."""
    B, H, _ = qq.shape
    block, n, block_latent = _paged_blocks(pool, layer, tables)
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

    return _fold((jnp.max(length) + block - 1) // block, block_of,
                 (B, H), (B, H, d.rank)).astype(qq.dtype)


# ---------------------------------------------------------------- pallas ----


def _latent_kernel(layer_ref, tables_ref, len_ref, next_ref,
                   q_ref, kv_hbm, o_ref, buf, sems, *,
                   scale, pages, page, rank, n_blocks):
    """Every live row in turn, each row's blocks in turn; the next block
    (of this row, or the first of the next live row) is in flight while
    this one is folded into the row's online softmax. A block is ``pages``
    table entries; of those only the pages at or under the row's position
    are fetched, one copy a page for scores and values both."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, _ = q_ref.shape
    R = pages * page  # positions (cache entries) of a block
    layer = layer_ref[0]

    def for_live_pages(b, i, slot, act):
        """``act`` on the copy of each page of block i of row b that holds
        a position at or under the row's: the same descriptors start a
        copy and wait for it."""
        n_live = jnp.clip(pl.cdiv(len_ref[b] - i * R, page), 0, pages)

        def page_body(j, _):
            pid = tables_ref[b * n_blocks + i * pages + j]
            dst = pl.ds(pl.multiple_of(j * page, page), page)
            act(pltpu.make_async_copy(
                kv_hbm.at[layer, pid], buf.at[slot, dst], sems.at[slot]))

        lax.fori_loop(0, n_live, page_body, None)

    def start(b, i, slot):
        for_live_pages(b, i, slot, lambda copy: copy.start())

    def wait(b, i, slot):
        for_live_pages(b, i, slot, lambda copy: copy.wait())

    start(next_ref[B], 0, 0)  # the first live row (row B: none)

    col = lax.broadcasted_iota(jnp.int32, (H, R), 1)
    entry_row = lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    stat_lane = lax.broadcasted_iota(jnp.int32, (H, LANES), 1)

    def row_body(b, slot):
        n = pl.cdiv(len_ref[b], R)  # idle: no block, zeros out
        q = q_ref[b]  # [H, width]: the query in the entry's layout

        def block_body(i, carry):
            m_prev, l_prev, acc, slot = carry

            # What to fetch while this block is computed: the row's next
            # block, or the next live row's first (row B: none, length 0).
            more = i + 1 < n
            start(jnp.where(more, b, next_ref[b]),
                  jnp.where(more, i + 1, 0), 1 - slot)

            wait(b, i, slot)
            n_rows = len_ref[b] - i * R  # entries at or under the position

            # Entries past the position hold stale bytes (or whatever VMEM
            # held where no page was fetched): zero them where they lie,
            # so that 0 x NaN cannot reach the value sum. Only a row's
            # last block has any, so the full blocks pay nothing for it.
            @pl.when(n_rows < R)
            def _():
                kv = buf[slot]
                buf[slot] = jnp.where(entry_row < n_rows, kv,
                                      jnp.zeros_like(kv))

            kv = buf[slot]  # [R, width]
            s = lax.dot_general(
                q, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [H, R]
            valid = col < n_rows
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + lax.dot_general(
                p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc, 1 - slot

        m, l, acc, slot = lax.fori_loop(0, n, block_body, (
            jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, rank), jnp.float32), slot))
        # The row's part of the softmax, not yet divided: [acc | m, l, 0...].
        o_ref[b, :, pl.ds(0, rank)] = acc
        o_ref[b, :, pl.ds(rank, LANES)] = jnp.where(
            stat_lane == 0, m, jnp.where(stat_lane == 1, l, 0.0))
        return slot

    lax.fori_loop(0, B, row_body, 0)


def _latent_decode(qq, pool, layer, tables, pos, d: Dims, pages: int,
                   interpret: bool = False, span: int | None = None):
    """The absorbed sum through the kernel: qq [B, H, width] at ``pos``
    [B] over pool[layer] through ``tables`` [B, nb] -> [B, H, rank]. An
    idle row reads nothing and gets zeros. A kernel call walks ``span``
    table entries of every row (all ``nb`` unless a test says fewer) and
    the calls' parts are merged as the reference merges its key blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, width = qq.shape
    page = pool.shape[2]
    nb = tables.shape[1]
    span = nb if span is None else span
    assert nb % span == 0 and span % pages == 0, (nb, span, pages)
    live = tables[:, 0] != 0
    # Positions a row attends; 0 for an idle row.
    length = jnp.where(live, jnp.minimum(pos + 1, nb * page), 0)

    kernel = functools.partial(
        _latent_kernel, scale=d.scale, pages=pages, page=page, rank=d.rank,
        n_blocks=span)
    # Every row's query and part stand in VMEM for the one grid step, beside
    # the two page buffers: 3.9 MB at 32 rows of 32 heads, 18 MB at 64 rows
    # of 64, over the compiler's 16 MB scope of a v5e's 128. Only a call
    # that needs more than the scope asks for it.
    need = (B * H * width * qq.dtype.itemsize + B * H * (d.rank + LANES) * 4
            + 2 * pages * page * width * pool.dtype.itemsize)
    params = (pltpu.CompilerParams(vmem_limit_bytes=need + VMEM_ROOM)
              if need > VMEM_SCOPE - VMEM_ROOM else None)
    walk = pl.pallas_call(
        kernel,
        compiler_params=params,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((B, H, width), lambda *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((B, H, d.rank + LANES),
                                   lambda *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * page, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, d.rank + LANES), jnp.float32),
        interpret=interpret,
    )
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def merge(i, carry):
        """Span i of every row's table through the kernel, into the
        online softmax's carry as ``_fold`` takes a key block."""
        m, l, acc = carry
        # What is left of each row from this span on, at most the span;
        # the live rows' chain as ``paged_attention._paged_decode`` has
        # it: written again here and not shared, so that no line of the
        # GQA cells' decode program moves with this kernel. "Row B"
        # stands for "no row" wherever the kernel looks one ahead.
        todo = jnp.clip(length - i * span * page, 0, span * page)
        idx = jnp.where(todo > 0, jnp.arange(B, dtype=jnp.int32), B)
        first_from = lax.cummin(idx, reverse=True)
        # next_row[b]: the first such row after b (B: none); [B]: the first.
        next_row = jnp.concatenate(
            [first_from[1:], jnp.full((1,), B, jnp.int32), first_from[:1]])
        with jax.named_scope("mla_decode"):  # the call's name in a trace
            part = walk(
                layer,
                lax.dynamic_slice_in_dim(tables, i * span, span, axis=1)
                .reshape(-1).astype(jnp.int32),
                jnp.append(todo, 0).astype(jnp.int32), next_row, qq, pool)
        m_part, l_part = part[..., d.rank], part[..., d.rank + 1]
        m_new = jnp.maximum(m, m_part)
        old, new = jnp.exp(m - m_new), jnp.exp(m_part - m_new)
        return (m_new, l * old + l_part * new,
                acc * old[..., None] + part[..., :d.rank] * new[..., None])

    # The reference's loop and carry (``_fold``: s32, m, l, acc) over spans
    # of the table, as many as the furthest live row reaches: at a span of
    # the whole table one pass, none when no row is live. The decode
    # attention of both forms is thus the SAME operation of the program, a
    # ``while`` with this carry, and the benchmark's latent_attn_roofline
    # reads either by it.
    _, l, acc = lax.fori_loop(
        0, (jnp.max(length) + span * page - 1) // (span * page), merge,
        (jnp.full((B, H), NEG_INF, jnp.float32),
         jnp.zeros((B, H), jnp.float32),
         jnp.zeros((B, H, d.rank), jnp.float32)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qq.dtype)


def _absorbed(q, pool, layer, tables, pos, wkv_b, d: Dims, pages):
    """One query a row: q [B, H, nope + rope] at ``pos`` [B] -> [B, H, v];
    the sum over the cache through the kernel (``pages`` a block) or, with
    ``pages`` None, through the gathered blocks."""
    w_k, w_v = _split_wkv_b(wkv_b, d)
    # The query in the cache's own layout [q_l | q_r | 0]: one product
    # against the entry scores the nope and the rope part together.
    q_l = jnp.einsum("bhn,rhn->bhr", q[..., :d.nope], w_k,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    qq = d.entry(q_l, q[..., d.nope:])  # [B, H, width]
    if pages is None:
        o_l = _gathered_sum(qq, pool, layer, tables, pos, d)
    else:
        o_l = _latent_decode(qq, pool, layer, tables, pos, d, pages)
    return jnp.einsum("bhr,rhv->bhv", o_l, w_v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _latent_plan(q, pool, tables, d: Dims) -> int | None:
    """Pages a kernel block when the Pallas kernel applies to these shapes
    (arrays or ShapeDtypeStructs) on this backend, else None — THE dispatch
    rule of the decode step. It reads shapes and the backend only."""
    page, width = pool.shape[2:]
    nb = tables.shape[1]
    # A page [page, width] must be whole sublane tiles of the cache's
    # dtype (16 rows of bf16, 8 of f32), the entry and its value part
    # whole lanes.
    tile = 32 // jnp.dtype(pool.dtype).itemsize
    if (jax.default_backend() != "tpu" or q.shape[1] != 1 or width % LANES
            or d.rank % LANES or page % tile):
        return None
    pages = max(BLOCK_TOKENS // page, 1)
    while nb % pages:  # a block never runs past the table
        pages //= 2
    return pages


def kernel_name(q, pool, tables, d: Dims) -> str:
    """The word ``paged_attention`` logs for these shapes, and the engine
    shows in its stats: which implementation a program takes."""
    if q.shape[1] != 1:
        return "jnp_latent_expanded"
    return ("jnp_latent_absorbed" if _latent_plan(q, pool, tables, d) is None
            else "pallas_latent")


def paged_attention(q, pool, layer, tables, pos, wkv_b, d: Dims):
    """q [B, T, H, nope + rope] at positions pos + t (``pos`` scalar or
    [B]) over pool[layer] through ``tables`` [B, n_blocks] -> [B, T, H, v].
    ``pool`` is the whole carried array [L, n_pages, page, width],
    this call's latents already scattered into it. Dispatch: absorbed for a
    decode step (the Pallas kernel on a TPU), expanded otherwise; one log
    line per trace says which."""
    B, T = q.shape[:2]
    pages = _latent_plan(q, pool, tables, d)
    _log_dispatch(kernel_name(q, pool, tables, d), q, pool,
                  pages_per_block=pages)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    if T == 1:
        with jax.named_scope("mla_decode"):
            return _absorbed(q[:, 0], pool, layer, tables, pos_b, wkv_b,
                             d, pages)[:, None]
    block, n, block_latent = _paged_blocks(pool, layer, tables)
    q_pos = pos_b[:, None] + jnp.arange(T)
    with jax.named_scope("mla_prefill"):
        n_blocks = jnp.minimum((jnp.max(q_pos) + block) // block, n)
        return _expanded(q, block_latent, n_blocks, q_pos, wkv_b, d, block)
