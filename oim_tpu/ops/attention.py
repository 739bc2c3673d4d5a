"""Attention: pallas flash kernels (TPU) + jnp reference (everywhere).

The flash forward streams K/V blocks through VMEM with an online softmax so
the [T, T] score matrix never materializes in HBM — the standard TPU
blockwise pattern: sequential innermost grid dimension carries the
accumulator in VMEM scratch across K blocks. It additionally emits the
per-row logsumexp, which the backward consumes.

The backward is also blockwise pallas (no [T, T] materialization): scores
are recomputed per block from Q/K and the saved logsumexp, then two kernels
accumulate the three gradients — dKV walks q-blocks sequentially per
k-block, dQ walks k-blocks sequentially per q-block — each carrying its
f32 accumulator in VMEM scratch. Long-context training still routes through
ring attention (oim_tpu/parallel/ring.py), which calls these kernels on the
per-chip sequence slice.

Shapes: [batch, seq, heads, head_dim] ("BTHD"). GQA: kv heads may divide q
heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from oim_tpu.common.logging import from_context

NEG_INF = -1e30


def _expand_gqa(q, k, v):
    """Repeat K/V heads when num_q_heads > num_kv_heads."""
    hq, hkv = q.shape[2], k.shape[2]
    if hq == hkv:
        return k, v
    if hq % hkv:
        raise ValueError(f"q heads {hq} not divisible by kv heads {hkv}")
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    return k, v


def ref_attention_lse(q, k, v, causal: bool = True, scale: float | None = None):
    """GQA-native jnp attention returning ``(out f32, lse [B,Tq,H] f32)``.

    The merge interface for ring attention (oim_tpu/parallel/ring.py): two
    blocks' normalized outputs combine exactly via their logsumexps. K/V are
    consumed at kv-head width — queries are grouped, K/V never repeat.
    """
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not divisible by kv heads {hkv}")
    group = h // hkv
    if scale is None:
        scale = d ** -0.5
    qg = q.reshape(b, tq, hkv, group, d)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale  # [B, hkv, group, Tq, Tk]
    if causal:
        q_pos = (tk - tq) + jnp.arange(tq)
        mask = q_pos[:, None] >= jnp.arange(tk)[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    lse = m + jnp.log(l)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p / l[..., None], v.astype(jnp.float32)
    ).reshape(b, tq, h, d)
    return out, lse.transpose(0, 3, 1, 2).reshape(b, tq, h)


def mha_reference(q, k, v, causal: bool = True, scale: float | None = None):
    """Plain jnp attention; the numerical ground truth for the kernels."""
    k, v = _expand_gqa(q, k, v)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        # Bottom-right aligned (flash-attention convention): with tq < tk the
        # queries are the LAST tq positions, so a decode step (tq=1) attends
        # to the whole cache.
        tq, tk = q.shape[1], k.shape[1]
        q_pos = (tk - tq) + jnp.arange(tq)
        mask = q_pos[:, None] >= jnp.arange(tk)[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------- pallas ----


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, scale, causal, block_q, block_k, q_offset):
    """One (q-block, k-block) cell; innermost grid dim walks k blocks
    sequentially so the VMEM scratch (acc/m/l) carries across them."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # q_offset = tk - tq bottom-right-aligns the causal mask (decode: the
    # queries are the last tq positions of the key sequence).
    q_start = qi * block_q + q_offset
    k_start = kj * block_k

    def _compute():
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[:, 0]  # [block_q]
        block_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, block_max)
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
        m_ref[:, 0] = m_new
        acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Blocks strictly above the diagonal contribute nothing: skip them
        # (predicated out, the TPU grid still visits the cell).
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)
        # lse rides a [bh, tq, 1] array: a (block_q, 1) tile keeps the TPU
        # (8, 128)-divisibility rule happy where (1, block_q) would not.
        lse_ref[0] = (m_ref[:, 0] + jnp.log(l))[:, None]


def _kv_row_map(h: int, hkv: int):
    """Grid row (b*h + q_head) -> K/V row (b*hkv + q_head // group): GQA is
    an index-map concern, not a data-movement one — the kv-head shard is
    READ by every q head of its group and never materialized per-q-head."""
    group = h // hkv
    return lambda bh: (bh // h) * hkv + (bh % h) // group


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    """Returns (out [B,T,H,D], lse [B*H, Tq] f32). K/V may carry fewer
    (GQA) heads than q; they are consumed in place via the index map."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not divisible by kv heads {hkv}")
    # Kernel works in [B*H, T, D] layout: heads become grid rows and every
    # block is a clean (T_block, d) tile for the MXU.
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, tk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, tk, d)
    kv_row = _kv_row_map(h, hkv)

    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if tq % block_q or tk % block_k:
        raise ValueError(f"seq lens ({tq},{tk}) not divisible by blocks ({block_q},{block_k})")
    grid = (b * h, tq // block_q, tk // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, q_offset=tk - tq,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, kj: (kv_row(bh), kj, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, kj: (kv_row(bh), kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3), lse


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *,
                          scale, causal, block_q, block_k, q_offset):
    """One (k-block, q-block) cell; innermost grid dim walks q blocks
    sequentially so dk/dv accumulate in VMEM across them."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q + q_offset
    k_start = kj * block_k

    def _compute():
        q = q_ref[0]    # [block_q, d]
        k = k_ref[0]    # [block_k, d]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)  # [block_q, d]
        lse = lse_ref[0][:, 0]      # [block_q]
        delta = delta_ref[0][:, 0]  # [block_q]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        # exp(s - lse) is the already-normalized softmax row.
        p = jnp.exp(s - lse[:, None])
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        # dV += P^T dO
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dP = dO V^T;  dS = P * (dP - delta) * scale
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        # dK += dS^T Q
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *,
                         scale, causal, block_q, block_k, q_offset):
    """One (q-block, k-block) cell; innermost grid dim walks k blocks
    sequentially so dq accumulates in VMEM across them."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = qi * block_q + q_offset
    k_start = kj * block_k

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        p = jnp.exp(s - lse[:, None])
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        # dQ += dS K
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                    interpret, g_lse=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, tk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, tk, d)
    kv_row = _kv_row_map(h, hkv)
    dot = g.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    ot = out.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    # delta_i = rowsum(dO_i * O_i): the softmax-normalization term of dS.
    delta = jnp.sum(
        dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1, keepdims=True
    )
    if g_lse is not None:
        # lse is also a primal output (flash_attention_lse): d lse_i/d s_ij
        # = p_ij, so the lse cotangent adds g_lse_i * p_ij to dS — folded
        # into delta since dS = P * (dP - delta + g_lse).
        delta = delta - g_lse.astype(jnp.float32)

    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    q_offset = tk - tq

    in_specs_kmajor = [
        pl.BlockSpec((1, block_q, d), lambda bh, kj, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, kj, qi: (kv_row(bh), kj, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, kj, qi: (kv_row(bh), kj, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh, kj, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, kj, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, kj, qi: (bh, qi, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, q_offset=q_offset,
        ),
        grid=(b * h, tk // block_k, tq // block_q),
        in_specs=in_specs_kmajor,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, kj, qi: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kj, qi: (bh, kj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)
    if group > 1:
        # dk/dv came out PER Q HEAD (each grid row writes only its own
        # block — no cross-row write races); the kv-head gradient is the
        # sum over its group, the vjp of the implicit GQA broadcast.
        dk = dk.reshape(b, hkv, group, tk, d).sum(axis=2).reshape(
            b * hkv, tk, d)
        dv = dv.reshape(b, hkv, group, tk, d).sum(axis=2).reshape(
            b * hkv, tk, d)

    in_specs_qmajor = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, kj: (kv_row(bh), kj, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, kj: (kv_row(bh), kj, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, q_offset=q_offset,
        ),
        grid=(b * h, tq // block_q, tk // block_k),
        in_specs=in_specs_qmajor,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    unflat = lambda x, hh, t: x.reshape(b, hh, t, d).transpose(0, 2, 1, 3)
    return unflat(dq, h, tq), unflat(dk, hkv, tk), unflat(dv, hkv, tk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q, k, v,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """Pallas flash attention. GQA-native: kv heads may divide q heads (the
    kv shard is routed to its query group by the block index map — never
    expanded in HBM). Seq lengths must be divisible by the block sizes.

    GQA memory caveat: the FORWARD never expands K/V; the backward's dK/dV
    transiently come out per-q-head ([B*H, Tk, D]) before the group sum
    (each grid row writes only its own block — no cross-row write races),
    so peak bwd memory scales with q heads. Accumulating the group sum
    inside the kernel grid would remove this at the cost of racing writes
    or an extra sequential grid dim."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash_backward(
        q, k, v, out, lse, g, causal, scale, block_q, block_k, interpret
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _lse_bth(lse, b, h, tq):
    """[B*H, Tq, 1] kernel layout -> [B, Tq, H]."""
    return lse.reshape(b, h, tq).transpose(0, 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_lse(
    q, k, v,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """Flash attention that also returns the per-row logsumexp [B, Tq, H].

    Both outputs are differentiable: the lse cotangent is folded into the
    backward kernels' delta term. This is the TPU block primitive for ring
    attention — per-ring-step (out, lse) pairs merge exactly downstream.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret)
    b, tq, h, _ = q.shape
    return out, _lse_bth(lse, b, h, tq)


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret)
    b, tq, h, _ = q.shape
    return (out, _lse_bth(lse, b, h, tq)), (q, k, v, out, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, tq, h, _ = q.shape
    g_lse_flat = g_lse.transpose(0, 2, 1).reshape(b * h, tq, 1)
    return _flash_backward(
        q, k, v, out, lse, g_out, causal, scale, block_q, block_k, interpret,
        g_lse=g_lse_flat,
    )


flash_attention_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _flash_plan(q, k) -> tuple[int, int] | None:
    """(block_q, block_k) when the pallas kernels apply to these shapes on
    this backend, else None — THE dispatch rule, shared by every entry
    point so they cannot drift apart."""
    tq, tk, d = q.shape[1], k.shape[1], q.shape[-1]
    if (jax.default_backend() != "tpu" or tq % 128 or tk % 128 or d % 128
            or q.shape[2] % k.shape[2]):
        _log_dispatch("jnp_reference", q, k)
        return None

    def pick(t):
        # Largest measured-good block the length divides: the r3 sweep on
        # v5e (BASELINE.md, the July 2026 rig) ranked 1024 > 512 >> 256
        # at seq 2048 (0.6974 / 0.6916 / 0.6161 MFU).
        for b in (1024, 512, 128):
            if t % b == 0:
                return b
        return 128

    plan = pick(tq), pick(tk)
    _log_dispatch("pallas_flash", q, k, block_q=plan[0], block_k=plan[1])
    return plan


def _log_dispatch(kernel: str, q, k, **fields) -> None:
    """One line per TRACE (the plan runs under jit, so never per step):
    which implementation a program took is otherwise invisible — a shape
    that silently misses the kernel costs the whole attention speed."""
    from_context().info(
        "attention dispatch", kernel=kernel, backend=jax.default_backend(),
        q=tuple(q.shape), k=tuple(k.shape), **fields)


def attention_with_lse(q, k, v, causal: bool = True, scale: float | None = None):
    """Dispatching block attention returning ``(out f32, lse [B,Tq,H] f32)``.

    Pallas flash on TPU when block-aligned (GQA-native via the kv-row index
    map), GQA-native jnp reference otherwise. The (out, lse) pair is the
    mergeable unit ring attention accumulates across ring steps.
    """
    plan = _flash_plan(q, k)
    if plan is not None:
        out, lse = flash_attention_lse(q, k, v, causal, scale, *plan)
        return out.astype(jnp.float32), lse
    return ref_attention_lse(q, k, v, causal, scale)


def attention(q, k, v, causal: bool = True, scale: float | None = None):
    """Dispatch: pallas flash on TPU when block-aligned, reference otherwise."""
    plan = _flash_plan(q, k)
    if plan is not None:
        return flash_attention(q, k, v, causal, scale, *plan)
    return mha_reference(q, k, v, causal, scale)
