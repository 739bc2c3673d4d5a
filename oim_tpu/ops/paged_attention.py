"""Attention over the serving engine's page pool: two Pallas kernels that
read live pages where they lie (TPU), a decode step's and a prompt
slice's, + the gather reference (everywhere).

The pool here is the GQA one, {"k","v"} [L, n_pages, page_tokens, kv_heads,
head_dim] (models/generate.py ``init_page_pool``; a latent-attention model's
one-leaf pool has its own decode kernel and rule in ``ops/latent_attention.py``);
logical position s of row b lives at pool[l, tables[b, s // page], s % page].
Every path takes the WHOLE pool and the layer index, never a per-layer
slice: a slice of a carried buffer is a copy of it.

- ``gather_attention``: materialize each row's logical [S] cache through
  its table, then ``cache_attention`` — the same function the solo dense
  path runs, which is what keeps the serving programs byte-identical to
  solo ``generate()`` on this path. Any T; what tier-1 (CPU) runs.
- ``_paged_decode``: one query token a row. One page of one layer is a
  contiguous [page * kv_heads, head_dim] slab of HBM; the kernel walks
  each row's table up to its position only, DMAs those slabs into a
  double-buffered VMEM block and folds the block into an online softmax.
  No [B, S, kvh, hd] gather and no [B, kvh, g, 1, S] scores exist.
- ``_paged_prefill``: T query rows a row, in blocks of query positions.
  The same page walk, up to the query block's last REAL position and no
  further (causal work only; a block of nothing but pad rows reads
  nothing); a kv head's rows of a key block are taken apart from the
  others' in VMEM and multiplied against that head's g query heads' rows
  alone. No gathered table, no re-layout of the pool, no [.., T, S]
  scores outside VMEM.

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


def _page_walk(tables_ref, layer, rows, k_hbm, v_hbm, kbuf, vbuf, sems):
    """The page walk both kernels have: -> ``walk(entry, n_live, slot, act)``,
    which does ``act`` on the K and V copy of each of the first ``n_live``
    pages of a key block (page j's table entry is ``tables_ref[entry(j)]``)
    into block ``slot`` of the two buffers; the same descriptors start a
    copy and wait for it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def walk(entry, n_live, slot, act):
        def page_body(j, _):
            pid = tables_ref[entry(j)]
            dst = pl.ds(pl.multiple_of(j * rows, rows), rows)
            act(pltpu.make_async_copy(
                k_hbm.at[layer, pid], kbuf.at[slot, dst], sems.at[0, slot]))
            act(pltpu.make_async_copy(
                v_hbm.at[layer, pid], vbuf.at[slot, dst], sems.at[1, slot]))

        lax.fori_loop(0, n_live, page_body, None)

    return walk


def _paged_kernel(layer_ref, tables_ref, len_ref, next_ref,
                  q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, *,
                  scale, pages, rows, kvh, n_blocks):
    """Every live row in turn, each row's blocks in turn; the next block
    (of this row, or the first of the next live row) is in flight while
    this one is computed. A block is ``pages`` table entries; of those
    only the pages at or under the row's position are fetched."""
    from jax.experimental import pallas as pl

    B, H, _ = q_ref.shape
    R = pages * rows  # K/V rows of a block: (token, kv head), head minor
    block_tokens = R // kvh
    page_tokens = rows // kvh
    walk = _page_walk(tables_ref, layer_ref[0], rows, k_hbm, v_hbm,
                      kbuf, vbuf, sems)

    def for_live_pages(b, i, slot, act):
        """``act`` on the copies of each page of block i of row b that
        holds a position at or under the row's."""
        n_live = jnp.clip(
            pl.cdiv(len_ref[b] - i * block_tokens, page_tokens), 0, pages)
        walk(lambda j: b * n_blocks + i * pages + j, n_live, slot, act)

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


# ---------------------------------------------------- pallas: prefill ----

# Query rows of one kv head's product (g * block_q) and of a block over all
# heads (H * block_q, what the statistics and the accumulator take of VMEM),
# and K/V positions a key block of the prefill kernel: a query block of 512
# positions at 32 heads over 8, 256 at 64 over 8, 128 at 32 over 2. On a
# v5e, ms a layer for one 1024-row slice at depth 0 / 2048 / 7168 (bf16,
# page 16, scripts/paged_prefill_on_chip.py; PERF.md section 6, PR 43),
# query block x key block:
#
#   32 heads over 2 (gather 4.82 at any depth)    64 heads over 8 (gather 9.52)
#   128 x 128  0.44 / 1.54 / 4.28                 0.88 / 3.39 / 9.64
#   128 x 256  0.35 / 1.06 / 2.84                 0.80 / 2.75 / 7.62
#   128 x 512  0.29 / 0.71 / 1.76                 0.59 / 1.59 / 4.11
#   256 x 256  0.36 / 1.06 / 2.86                 0.61 / 1.98 / 5.38
#   256 x 512  0.27 / 0.64 / 1.55                 0.48 / 1.28 / 3.26
#   512 x 512  0.31 / 0.63 / 1.46                 (16 384 rows x 2: not tried)
#
# 32 heads over 8: a 4096-row bucket from 0 reads 2.35 at 512 x 512, 2.85 at
# 256 x 512, 4.72 at 256 x 256 (gather 9.12); a 512-row one at depth 2048
# 0.31 / 0.40 / 0.66 (gather 1.22); a 32-row one 0.05-0.07 (gather 0.08-0.09:
# small buckets lose nothing on the kernel, so the rule has no lower bound
# but a sublane tile). A block's fixed cost is its statistics ([rows, 1]
# float32: a sublane a row) and the accumulator's rescale, both by the query
# rows and not by the keys: a key block of 512 halves their share, and the
# larger query block reads the keys fewer times. A product of 8192 rows (512
# positions at 16 heads a kv head) gains 2-6 % on one of 2048 and takes five
# times as long to compile (17 s a kernel here against 3.5, six kernels a
# program): a replica's start pays that, so the product stops at 2048 rows.
# A key block of 1024 reads 0.26 / 0.54 where 128 x 512 reads 0.29 / 0.71
# (depth 0 / 2048; 512 x 1024 at 32 heads over 8: 0.25 / 0.50 against 0.26 /
# 0.66): not taken yet, it was read alone and not in a cell (PERF.md
# section 7).
PREFILL_PRODUCT_ROWS = 2048
PREFILL_HEAD_ROWS = 16384
PREFILL_BLOCK_TOKENS = 512
# VMEM the compiler grants a kernel unasked (a v5e has 128 MB), and what a
# call adds to its own count of its blocks for the compiler's temporaries
# (the f32 scores and probabilities of one head's product).
VMEM_SCOPE = 16 << 20
VMEM_ROOM = 12 << 20


def _head_rows(buf, slot, h, kvh: int, n: int):
    """Kv head ``h``'s ``n`` rows of block ``slot`` of a K/V buffer whose
    rows are (token, kv head), head minor: every kvh-th row from row h. A
    strided sublane read where a row is a 32-bit sublane; a 16-bit buffer
    packs rows 2s and 2s + 1 into the halves of sublane s (the even row
    low), so there a head is one half of every (kvh / 2)-th word, widened
    to float32 in place (a bfloat16 IS the high half of its float32) and
    rounded back with nothing lost."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if kvh == 1:
        return buf[slot]
    if buf.dtype.itemsize == 4:
        return buf[slot, pl.ds(h, n, stride=kvh), :]
    words = buf.bitcast(jnp.uint32)  # [2, R / 2, hd]
    w = (words[slot] if kvh == 2
         else words[slot, pl.ds(h // 2, n, stride=kvh // 2), :])
    w = jnp.where(h % 2 == 1, w & jnp.uint32(0xFFFF0000), w << 16)
    return pltpu.bitcast(w, jnp.float32).astype(buf.dtype)


def _prefill_kernel(layer_ref, tables_ref, pos_ref, len_ref,
                    q_ref, k_hbm, v_hbm, o_ref,
                    kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *,
                    scale, pages, rows, kvh, n_blocks, block_q):
    """One block of ``block_q`` query positions of one row a grid step,
    every head of it: the row's key blocks in turn up to the block's last
    real position, the next in flight while this one is computed, each kv
    head's rows of a block against its g query heads' rows."""
    from jax.experimental import pallas as pl

    b, i = pl.program_id(0), pl.program_id(1)
    n_q = q_ref.shape[1]  # g * block_q query rows a kv head, (head, token)
    R = pages * rows  # K/V rows of a block: (token, kv head), head minor
    block_tokens = R // kvh
    page_tokens = rows // kvh
    walk = _page_walk(tables_ref, layer_ref[0], rows, k_hbm, v_hbm,
                      kbuf, vbuf, sems)
    p0 = pos_ref[b] + i * block_q  # the block's first position
    n_real = jnp.clip(len_ref[b] - i * block_q, 0, block_q)
    # Positions the block's rows may read: under its last real row's, and
    # inside the table.
    n_keys = jnp.minimum(p0 + n_real, n_blocks * page_tokens)

    def for_live_pages(j, slot, act):
        """``act`` on the copies of each page of key block j that holds a
        position under ``n_keys``."""
        n_live = jnp.clip(
            pl.cdiv(n_keys - j * block_tokens, page_tokens), 0, pages)
        walk(lambda e: b * n_blocks + j * pages + e, n_live, slot, act)

    @pl.when(n_real == 0)  # nothing but pad rows: no page read, zeros out
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_real > 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        n_kb = pl.cdiv(n_keys, block_tokens)
        for_live_pages(0, 0, lambda copy: copy.start())

        # Row r of a head's query rows is token r % block_q. A pad row
        # reads what the last real row reads: finite, and discarded.
        q_tok = lax.rem(
            lax.broadcasted_iota(jnp.int32, (n_q, 1), 0), block_q)
        q_last = jnp.minimum(p0 + q_tok, n_keys - 1)
        k_col = lax.broadcasted_iota(jnp.int32, (1, block_tokens), 1)
        k_row = lax.broadcasted_iota(jnp.int32, (block_tokens, 1), 0)

        def fold(j, masked: bool):
            slot = lax.rem(j, 2)

            @pl.when(j + 1 < n_kb)
            def _():
                for_live_pages(j + 1, 1 - slot, lambda copy: copy.start())

            for_live_pages(j, slot, lambda copy: copy.wait())
            first = j * block_tokens  # the block's first position

            def head(h, _):
                k = _head_rows(kbuf, slot, h, kvh, block_tokens)
                v = _head_rows(vbuf, slot, h, kvh, block_tokens)
                s = lax.dot_general(
                    q_ref[h], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # [n_q, bt]
                if masked:
                    s = jnp.where(first + k_col <= q_last, s, NEG_INF)
                    # Positions past the last real row's hold stale bytes
                    # (or whatever VMEM held where no page was fetched):
                    # zero them, so that 0 x NaN cannot reach the sum.
                    v = jnp.where(first + k_row < n_keys, v,
                                  jnp.zeros_like(v))
                m_prev = m_ref[h]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True))
                # Position 0 is under every row's: m_new is finite from
                # the first block on, and a masked score's weight exact 0.
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * corr + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_ref[h] = acc_ref[h] * corr + lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new

            # A loop, not kvh copies of the body: the compiler unrolls a
            # product of thousands of rows as it is, and a program's
            # compile is part of a replica's start.
            lax.fori_loop(0, kvh, head, None)

        # Key blocks wholly at or under the block's first position need no
        # mask; those that straddle the diagonal or the last real row's
        # position do.
        n_whole = jnp.minimum((p0 + 1) // block_tokens, n_kb)
        lax.fori_loop(0, n_whole, lambda j, _: fold(j, False), None)
        lax.fori_loop(n_whole, n_kb, lambda j, _: fold(j, True), None)
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "pages", "interpret"))
def _paged_prefill(q, pk, pv, layer, tables, pos, n_tokens, block_q: int,
                   pages: int, interpret: bool = False):
    """q [B, T, H, hd] at positions ``pos`` [B] + t over pool[layer]
    through ``tables`` [B, nb] -> [B, T, H, hd]; row b's first
    ``n_tokens`` [B] query rows are real, a query block of nothing but the
    others reads nothing and gets zeros. Jitted, so that a program whose
    layers are not one scan (a hybrid's six attention layers) traces and
    lowers the kernel once and not a layer: a replica's start pays every
    trace, compile cache or not (2 s a bucket here, 3-4 on the chip's
    host, PR 43)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, hd = q.shape
    L, n_pages, page, kvh, _ = pk.shape
    nb = tables.shape[1]
    rows, g, n_q = page * kvh, H // kvh, T // block_q
    # A block's query rows stand head-major, [kvh, g * block_q, hd]: one
    # product a kv head, no row of another head's in it.
    qt = q.reshape(B, n_q, block_q, kvh, g, hd).transpose(0, 1, 3, 4, 2, 5)
    qt = qt.reshape(B, n_q, kvh, g * block_q, hd)
    block = pl.BlockSpec((None, None, kvh, g * block_q, hd),
                         lambda b, i, *_: (b, i, 0, 0, 0))
    kernel = functools.partial(
        _prefill_kernel, scale=hd ** -0.5, pages=pages, rows=rows, kvh=kvh,
        n_blocks=nb, block_q=block_q)
    # Query and output blocks twice (the pipeline's), the three statistics
    # ([.., 1] float32 takes whole lanes), the two K/V blocks twice.
    need = (H * block_q * (4 * hd * q.dtype.itemsize + 4 * (hd + 256))
            + 4 * pages * rows * hd * pk.dtype.itemsize)
    out = pl.pallas_call(
        kernel,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(need + VMEM_ROOM, VMEM_SCOPE)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, n_q),
            in_specs=[
                block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, pages * rows, hd), pk.dtype),
                pltpu.VMEM((2, pages * rows, hd), pv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kvh, g * block_q, 1), jnp.float32),
                pltpu.VMEM((kvh, g * block_q, 1), jnp.float32),
                pltpu.VMEM((kvh, g * block_q, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      n_tokens.astype(jnp.int32),
      qt, pk.reshape(L, n_pages, rows, hd), pv.reshape(L, n_pages, rows, hd))
    out = out.reshape(B, n_q, kvh, g, block_q, hd)
    return out.transpose(0, 1, 4, 2, 3, 5).reshape(B, T, H, hd)


# ------------------------------------------------------------- dispatch ----


def _paged_plan(q, pk, tables) -> tuple[int, int] | None:
    """(query positions a block, pages a key block) when a Pallas kernel
    applies to these shapes (arrays or ShapeDtypeStructs) on this backend,
    else None — THE dispatch rule. It reads shapes and the backend only.
    One query position a block is the decode kernel."""
    T, H, hd = q.shape[1:]
    page, kvh = pk.shape[2], pk.shape[3]
    nb = tables.shape[1]
    # One page viewed [page * kvh, hd] must be whole sublane tiles of the
    # cache's dtype (16 rows of bf16, 8 of f32).
    itemsize = jnp.dtype(pk.dtype).itemsize
    tile = 32 // itemsize
    if (jax.default_backend() != "tpu" or hd % 128 or H % kvh
            or (page * kvh) % tile):
        return None
    if T == 1:
        block_q, block_tokens = 1, BLOCK_TOKENS
    else:
        most = min(PREFILL_PRODUCT_ROWS // (H // kvh), PREFILL_HEAD_ROWS // H,
                   T)
        # The largest power of two under the bounds: a group of 7 heads
        # takes 256 positions, not 292 that no bucket is a multiple of.
        block_q, block_tokens = (1 << most.bit_length() - 1 if most else 0,
                                 PREFILL_BLOCK_TOKENS)
        # Whole query blocks, a head's rows of one whole sublane tiles of
        # q; a 16-bit pool's heads come apart by the halves of a word.
        if (not block_q or T % block_q
                or block_q % (32 // jnp.dtype(q.dtype).itemsize)
                or (itemsize == 2 and kvh > 1 and kvh % 2)):
            return None
    pages = max(block_tokens // page, 1)
    while nb % pages:  # a block never runs past the table
        pages //= 2
    return block_q, pages


def kernel_name(q, pk, tables) -> str:
    """The word ``paged_attention`` logs for these shapes, and the engine
    shows in its stats: which implementation a program takes."""
    plan = _paged_plan(q, pk, tables)
    if plan is None:
        return "jnp_gather"
    return "pallas_paged" if plan[0] == 1 else "pallas_paged_prefill"


def paged_attention(q, pk, pv, layer, tables, pos, n_tokens=None):
    """q [B,T,H,hd] at positions pos+t (``pos`` scalar or [B]) over
    pool[layer] through ``tables`` [B, n_blocks] -> [B,T,H,hd]; of a row's
    T query rows the first ``n_tokens`` (scalar or [B]; None: all) are
    real, the others' results are the caller's to discard. Dispatch: a
    Pallas kernel on a TPU (the decode kernel for T == 1, the prefill
    kernel for whole query blocks), the gather reference otherwise; one
    log line per trace says which."""
    plan = _paged_plan(q, pk, tables)
    block_q, pages = plan or (None, None)
    _log_dispatch(kernel_name(q, pk, tables), q, pk, pages_per_block=pages,
                  block_q=block_q)
    if plan is None:
        return gather_attention(q, pk, pv, layer, tables, pos)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), q.shape[:1])
    if block_q == 1:
        return _paged_decode(q[:, 0], pk, pv, layer, tables, pos_b,
                             pages)[:, None]
    n_b = jnp.broadcast_to(
        jnp.asarray(q.shape[1] if n_tokens is None else n_tokens),
        q.shape[:1])
    return _paged_prefill(q, pk, pv, layer, tables, pos_b, n_b, block_q,
                          pages)
