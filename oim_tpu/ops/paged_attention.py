"""Attention over the serving engine's page pool: a Pallas decode kernel
that reads live pages where they lie (TPU) + the gather reference
(everywhere).

The pool here is the GQA one, {"k","v"} [L, n_pages, page_tokens, kv_heads,
head_dim] (models/generate.py ``init_page_pool``; a latent-attention model's
one-leaf pool has its own decode kernel and rule in ``ops/latent_attention.py``);
logical position s of row b lives at pool[l, tables[b, s // page], s % page].
Both paths take the WHOLE pool and the layer index, never a per-layer slice:
a slice of a carried buffer is a copy of it.

- ``gather_attention``: materialize each row's logical [S] cache through
  its table, then ``cache_attention`` — the same function the solo dense
  path runs, which is what keeps the serving programs byte-identical to
  solo ``generate()`` on this path. Any T; what tier-1 (CPU) runs.
- ``_paged_decode``: one query token a row. One page of one layer is a
  contiguous [page * kv_heads, head_dim] slab of HBM; the kernel walks
  each row's table up to its position only, DMAs those slabs into a
  double-buffered VMEM block and folds the block into an online softmax.
  No [B, S, kvh, hd] gather and no [B, kvh, g, 1, S] scores exist.

``paged_attention`` picks between them by shapes and backend alone
(``_paged_plan``), as ``attention._flash_plan`` does for the flash kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from oim_tpu.ops.attention import NEG_INF, _log_dispatch

# K/V positions a kernel block holds: enough rows to amortize a block's
# fixed cost, few enough that a row's ragged last block wastes little. On
# a v5e at 21 rows, 18 071 positions, 14 layers, page 16, blocks of 64 /
# 128 / 256 / 512 positions read the live pages at 360 / 553 / 613 / 584
# GB/s with the page loop unrolled, 601 at 256 as it is now (PERF.md
# section 6, PR 27).
BLOCK_TOKENS = 256


def cache_attention(q, ck, cv, pos):
    """q [B,T,H,hd] over the full cache [B,S,kvh,hd], masked to positions
    <= pos+t (unwritten cache slots mask out with everything else).
    ``pos`` is a scalar (every row at the same depth — prefill/solo
    decode) or a [B] vector (the serving batch, where mid-flight
    admission puts every slot at its own depth).

    GQA rides a grouped einsum against the kv-head cache directly — no
    head-expanded copy of the cache, no f32 materialization of K (the
    einsum accumulates in f32 from bf16 operands, the same numerics as the
    training path's mha_reference)."""
    B, T, H, hd = q.shape
    S, kvh = ck.shape[1], ck.shape[2]
    qg = q.reshape(B, T, kvh, H // kvh, hd)
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, ck, preferred_element_type=jnp.float32
    ) * (hd ** -0.5)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    mask = (pos_b[:, None] + jnp.arange(T))[:, :, None] \
        >= jnp.arange(S)[None, None, :]  # [B,T,S]
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # Probs drop to the cache dtype (what the flash kernels do) so the V
    # side also avoids an f32 copy of the cache; accumulation stays f32.
    out = jnp.einsum(
        "bkgts,bskd->btkgd", probs.astype(cv.dtype), cv,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, T, H, hd).astype(q.dtype)


def gather_attention(q, pk, pv, layer, tables, pos):
    """The reference: gather-by-page-table each row's logical [S] view of
    pool[layer] (ONE gather indexed by (layer, table): no slice of the
    pool), then ``cache_attention``."""
    B = q.shape[0]
    _, _, page, kvh, hd = pk.shape
    S = tables.shape[1] * page
    ck = pk[layer, tables].reshape(B, S, kvh, hd)
    cv = pv[layer, tables].reshape(B, S, kvh, hd)
    return cache_attention(q, ck, cv, pos)


# ---------------------------------------------------------------- pallas ----


def _paged_kernel(layer_ref, tables_ref, len_ref, next_ref,
                  q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, *,
                  scale, pages, rows, kvh, n_blocks):
    """Every live row in turn, each row's blocks in turn; the next block
    (of this row, or the first of the next live row) is in flight while
    this one is computed. A block is ``pages`` table entries; of those
    only the pages at or under the row's position are fetched."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, _ = q_ref.shape
    R = pages * rows  # K/V rows of a block: (token, kv head), head minor
    block_tokens = R // kvh
    page_tokens = rows // kvh
    layer = layer_ref[0]

    def for_live_pages(b, i, slot, act):
        """``act`` on the K and V copy of each page of block i of row b
        that holds a position at or under the row's: the same descriptors
        start a copy and wait for it."""
        n_live = jnp.clip(
            pl.cdiv(len_ref[b] - i * block_tokens, page_tokens), 0, pages)

        def page_body(j, _):
            pid = tables_ref[b * n_blocks + i * pages + j]
            dst = pl.ds(pl.multiple_of(j * rows, rows), rows)
            act(pltpu.make_async_copy(
                k_hbm.at[layer, pid], kbuf.at[slot, dst], sems.at[0, slot]))
            act(pltpu.make_async_copy(
                v_hbm.at[layer, pid], vbuf.at[slot, dst], sems.at[1, slot]))

        lax.fori_loop(0, n_live, page_body, None)

    def start(b, i, slot):
        for_live_pages(b, i, slot, lambda copy: copy.start())

    def wait(b, i, slot):
        for_live_pages(b, i, slot, lambda copy: copy.wait())

    start(next_ref[B], 0, 0)  # the first live row (row B: none)

    # Column c of a block is (token c // kvh, kv head c % kvh); query head
    # r reads kv head r // g. The other heads' columns are computed and
    # masked: one [H, d] x [d, R] matmul instead of kvh strided ones.
    col = lax.broadcasted_iota(jnp.int32, (H, R), 1)
    row = lax.broadcasted_iota(jnp.int32, (H, R), 0)
    own_head = lax.rem(col, kvh) == lax.div(row, H // kvh)
    v_row = lax.broadcasted_iota(jnp.int32, (R, 1), 0)

    def row_body(b, slot):
        n = pl.cdiv(len_ref[b], block_tokens)  # idle: no block, zeros out
        q = q_ref[b]  # [H, d]

        def block_body(i, carry):
            m_prev, l_prev, acc, slot = carry

            # What to fetch while this block is computed: the row's next
            # block, or the next live row's first (row B: none, length 0).
            more = i + 1 < n
            start(jnp.where(more, b, next_ref[b]),
                  jnp.where(more, i + 1, 0), 1 - slot)

            wait(b, i, slot)
            # Rows of this block at or under the row's position.
            n_rows = (len_ref[b] - i * block_tokens) * kvh
            k = kbuf[slot]
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [H, R]
            valid = jnp.logical_and(own_head, col < n_rows)
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            # Rows past the position hold stale bytes (or whatever VMEM
            # held where no page was fetched): zero them, so that 0 x NaN
            # cannot reach the sum. Only a row's last block has any.
            v = vbuf[slot]
            v = jnp.where(v_row < n_rows, v, jnp.zeros_like(v))
            acc = acc * corr + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc, 1 - slot

        _, l, acc, slot = lax.fori_loop(0, n, block_body, (
            jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros(q.shape, jnp.float32), slot))
        o_ref[b] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return slot

    lax.fori_loop(0, B, row_body, 0)


def _paged_decode(q, pk, pv, layer, tables, pos, pages: int,
                  interpret: bool = False):
    """q [B, H, hd] at positions ``pos`` [B] over pool[layer] through
    ``tables`` [B, nb] -> [B, H, hd]. A row whose first table entry is the
    scratch page 0 is idle (the engine maps a live row's first block
    before it decodes): it reads nothing and gets zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    L, n_pages, page, kvh, _ = pk.shape
    nb = tables.shape[1]
    rows = page * kvh
    live = tables[:, 0] != 0
    # Positions a row attends; 0 for an idle row and for "row B", which
    # stands for "no row" wherever the kernel looks one ahead.
    length = jnp.where(live, jnp.minimum(pos + 1, nb * page), 0)
    length = jnp.append(length, 0).astype(jnp.int32)
    # next_row[b]: the first live row after b (B: none); [B]: the first.
    idx = jnp.where(live, jnp.arange(B, dtype=jnp.int32), B)
    first_from = lax.cummin(idx, reverse=True)
    next_row = jnp.concatenate(
        [first_from[1:], jnp.full((1,), B, jnp.int32), first_from[:1]])

    kernel = functools.partial(
        _paged_kernel, scale=hd ** -0.5, pages=pages, rows=rows, kvh=kvh,
        n_blocks=nb)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((B, H, hd), lambda *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((B, H, hd), lambda *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * rows, hd), pk.dtype),
                pltpu.VMEM((2, pages * rows, hd), pv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), length, next_row,
      q, pk.reshape(L, n_pages, rows, hd), pv.reshape(L, n_pages, rows, hd))


def _paged_plan(q, pk, tables) -> int | None:
    """Pages a kernel block when the Pallas decode kernel applies to these
    shapes (arrays or ShapeDtypeStructs) on this backend, else None — THE
    dispatch rule. It reads shapes and the backend only."""
    T, H, hd = q.shape[1:]
    page, kvh = pk.shape[2], pk.shape[3]
    nb = tables.shape[1]
    # One page viewed [page * kvh, hd] must be whole sublane tiles of the
    # cache's dtype (16 rows of bf16, 8 of f32).
    tile = 32 // jnp.dtype(pk.dtype).itemsize
    if (jax.default_backend() != "tpu" or T != 1 or hd % 128 or H % kvh
            or (page * kvh) % tile):
        return None
    pages = max(BLOCK_TOKENS // page, 1)
    while nb % pages:  # a block never runs past the table
        pages //= 2
    return pages


def kernel_name(q, pk, tables) -> str:
    """The word ``paged_attention`` logs for these shapes, and the engine
    shows in its stats: which implementation a program takes."""
    return ("jnp_gather" if _paged_plan(q, pk, tables) is None
            else "pallas_paged")


def paged_attention(q, pk, pv, layer, tables, pos):
    """q [B,T,H,hd] at positions pos+t (``pos`` scalar or [B]) over
    pool[layer] through ``tables`` [B, n_blocks] -> [B,T,H,hd]. Dispatch:
    the Pallas kernel for a decode step on TPU, the gather reference
    otherwise; one log line per trace says which."""
    pages = _paged_plan(q, pk, tables)
    _log_dispatch(kernel_name(q, pk, tables), q, pk, pages_per_block=pages)
    if pages is None:
        return gather_attention(q, pk, pv, layer, tables, pos)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), q.shape[:1])
    return _paged_decode(q[:, 0], pk, pv, layer, tables, pos_b,
                         pages)[:, None]
