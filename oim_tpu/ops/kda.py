"""The KDA mixer (Kimi-style delta attention: a gated delta rule with one
decay a CHANNEL) in plain ``jax.numpy``: the one-token recurrent update a
decode step runs, and the chunked scan a prompt slice runs, which takes the
recurrent state and the conv window in and gives them out. Both compute the
same recurrence (tests/test_kda.py holds them to each other and to the
sequential definition of benchmarks/reference/solar_open2_like.py):

    [q | k | v] = x W_qkv                               each H x d wide
    q, k, v <- silu(sum_j w_j * [q|k|v]_{t-(K-1)+j})    depthwise, causal
    q_h <- q_h / |q_h| * d^-1/2,  k_h <- k_h / |k_h|    a head
    g_t    = -exp(A_log_h) * softplus(W_f2 (W_f1 x_t) + dt_bias)   [H, d]
    beta_t = 2 * sigmoid(x_t W_beta)                    [H]; 1 * without
                                                        negative eigenvalues
    S_t    = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t    = S_t^T q_t                                  S: [H, d, d], float32
    out    = [RMSNorm_head(o_t) * sigmoid(W_g2 (W_g1 x_t) + b_g)] W_out

What a SLOT keeps between calls is ``S`` ([H, d, d] float32) and the last
K - 1 inputs of the conv ([K - 1, 3 H d], the model's dtype, kept FLAT in
the pool as ops/ssm.py says why): a fixed size whatever the position.

The scan is chunked and EXACT. With ``u_t = beta_t (v_t - S_{t-1}^T (a_t *
k_t))`` (a_t = exp(g_t)) the recurrence is ``S_t = Diag(a_t) S_{t-1} + k_t
u_t^T``: linear in S given u. Inside a chunk of C positions from a state S_0,
with G_t the cumulative log-decay up to t,

    A_tj = sum_c k_tc k_jc exp(G_tc - G_jc)   j < t
    B_tj = sum_c q_tc k_jc exp(G_tc - G_jc)   j <= t
    (I + Diag(beta) A) U = Diag(beta) (V - (exp(G) * K) S_0)
    O   = (exp(G) * Q) S_0 + B U
    S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) * K)^T U

The unit lower-triangular system is solved by forward substitution for its
inverse T, once for every chunk at a time, so that ``U = T beta V - (T beta
exp(G) K) S_0`` leaves only three products with S in the sequential loop
over chunks. Every exponent above is a DIFFERENCE of cumulative log-decays
taken in the direction time runs, so it is never positive: a channel whose
decay over a chunk passes float32's range underflows to the zero it is,
where the factored form ``exp(G_t) exp(-G_j)`` overflows. Nothing is
clamped and no term is dropped. All of it runs in float32 at the highest
matmul precision (a TPU multiplies float32 in bfloat16 passes unless told):
the chunk's products are a few GFLOP a slice, the state is held in float32,
and the triangular solve amplifies what rounding the products leave.

A kernel is a later change's; the named scopes ``kda_scan`` and
``kda_step`` mark what it would replace.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

NAME = "kda"  # the kind, in the engine's accounting of state bytes
SCOPES = ("kda_step", "kda_scan")
QK_EPS = 1e-6  # under the root of a head's squared norm

_mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)


@dataclasses.dataclass(frozen=True)
class Dims:
    heads: int                # H  (linear_attn_config.num_heads)
    head_dim: int             # d  (linear_attn_config.head_dim: keys, values)
    conv: int = 4             # K  (linear_attn_config.short_conv_kernel_size)
    # C, the positions a chunk of ``scan``: the program's own size (no
    # published key, no option). A 1024-token slice of the 4-layer cell
    # alone on a v5e took 48.1 / 53.6 / 61.2 ms at 16 / 32 / 64 (PERF.md
    # section 6, PR 37): the pairwise decays grow with the chunk, the
    # sequential loop over chunks shrinks with it.
    chunk: int = 16
    neg_eigval: bool = True   # beta in (0, 2)  (kda_allow_neg_eigval)

    # The state pool's leaves of this kind (``slot_leaves``).
    state_leaf = "kda"
    window_leaf = "kda_conv"

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return 3 * self.inner

    @property
    def rank(self) -> int:
        """Inner width of the two low-rank pairs (decay gate, output gate)."""
        return self.head_dim

    @property
    def window(self) -> tuple:
        return (self.conv - 1, self.conv_dim)

    def slot_leaves(self, dtype) -> dict:
        """What a slot holds in one layer, as the pool keeps it: {leaf:
        (shape, dtype)}, the matrix state and the window (flat)."""
        return {self.state_leaf: ((self.heads, self.head_dim, self.head_dim),
                                  jnp.float32),
                self.window_leaf: (((self.conv - 1) * self.conv_dim,), dtype)}


def n_params(dim: int, d: Dims) -> int:
    """Parameters of one mixer at model width ``dim`` (without the block's
    norm): the four projections, the two low-rank pairs and the beta
    projection, then the conv, the output gate's bias, dt_bias, A_log and
    the head norm's weight."""
    return (dim * (4 * d.inner + 2 * d.rank + d.heads)
            + d.conv * d.conv_dim + 2 * d.rank * d.inner + 2 * d.inner
            + d.heads + d.head_dim)


def init(rng, dim: int, d: Dims, dtype, n_layers: int,
         dt_min: float = 1e-3, dt_max: float = 0.1, dt_floor: float = 1e-4):
    """Stacked mixer leaves [L, ...]: ``dt_bias`` the inverse softplus of a
    log-uniform step in [dt_min, dt_max] a channel, ``A_log`` the log of a
    uniform [1, 16] a head (as ops/ssm.py); matrices at the fan-in of their
    contraction."""
    ks = jax.random.split(rng, 10)
    L, r, inner = n_layers, d.rank, d.inner
    step = jnp.exp(jax.random.uniform(ks[2], (L, inner))
                   * (jnp.log(dt_max) - jnp.log(dt_min)) + jnp.log(dt_min))
    step = jnp.maximum(step, dt_floor)

    def dense(key, shape):
        return (jax.random.normal(key, (L,) + shape)
                * shape[0] ** -0.5).astype(dtype)

    return {
        "w_qkv": dense(ks[0], (dim, d.conv_dim)),
        "conv_w": dense(ks[1], (d.conv, d.conv_dim)),
        "w_f1": dense(ks[4], (dim, r)),
        "w_f2": dense(ks[5], (r, inner)),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(jnp.float32),
        "A_log": jnp.log(jax.random.uniform(
            ks[3], (L, d.heads), minval=1.0, maxval=16.0)).astype(jnp.float32),
        "w_beta": dense(ks[6], (dim, d.heads)),
        "w_g1": dense(ks[7], (dim, r)),
        "w_g2": dense(ks[8], (r, inner)),
        "g_bias": jnp.zeros((L, inner), jnp.float32),
        "o_norm": jnp.ones((L, d.head_dim), jnp.float32),
        "w_out": dense(ks[9], (inner, dim)),
    }


def _gates(layer, x, d: Dims):
    """x [..., D] -> (log-decay g [..., H, d] <= 0, beta [..., H], the
    output gate before its sigmoid [..., H, d]), float32."""
    lead = x.shape[:-1]
    f = ((x @ layer["w_f1"]) @ layer["w_f2"]).astype(jnp.float32)
    g = (-jnp.exp(layer["A_log"])[:, None]
         * jax.nn.softplus(f + layer["dt_bias"]).reshape(
             lead + (d.heads, d.head_dim)))
    beta = jax.nn.sigmoid((x @ layer["w_beta"]).astype(jnp.float32))
    gate = (((x @ layer["w_g1"]) @ layer["w_g2"]).astype(jnp.float32)
            + layer["g_bias"]).reshape(lead + (d.heads, d.head_dim))
    return g, beta * 2.0 if d.neg_eigval else beta, gate


def _heads(mixed, d: Dims):
    """The conv's output [..., 3 H d] float32 -> q, k, v [..., H, d], q and
    k at unit length a head, q scaled by d^-1/2."""
    q, k, v = (a.reshape(a.shape[:-1] + (d.heads, d.head_dim))
               for a in jnp.split(mixed, 3, axis=-1))
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + QK_EPS)
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + QK_EPS)
    return q * d.head_dim ** -0.5, k, v


def _gated_out(o, gate, layer, d: Dims, eps: float, dtype):
    """RMSNorm over each head of ``o`` [..., H, d], the gate, then W_out."""
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * layer["o_norm"].astype(jnp.float32) * jax.nn.sigmoid(gate)
    return o.reshape(o.shape[:-2] + (d.inner,)).astype(dtype) @ layer["w_out"]


def step(layer, x, state, conv, d: Dims, eps: float):
    """One token a row: x [B, D], state [B, H, d, d] float32, conv
    [B, K - 1, 3 H d] -> (out [B, D], state, conv)."""
    with jax.named_scope(SCOPES[0]):
        window = jnp.concatenate(
            [conv, (x @ layer["w_qkv"])[:, None]], axis=1)      # [B, K, 3Hd]
        q, k, v = _heads(jax.nn.silu(jnp.sum(
            window.astype(jnp.float32)
            * layer["conv_w"].astype(jnp.float32), axis=1)), d)
        g, beta, gate = _gates(layer, x, d)
        # Sums over a state's rows as multiply-and-add, not as a product the
        # TPU would round to bfloat16; each reads the state where it lies.
        decayed = state * jnp.exp(g)[..., None]
        u = beta[..., None] * (v - jnp.sum(k[..., None] * decayed, axis=-2))
        state = decayed + k[..., None] * u[..., None, :]
        o = jnp.sum(q[..., None] * state, axis=-2)
        return (_gated_out(o, gate, layer, d, eps, x.dtype), state,
                window[:, 1:])


def _unit_lower_inverse(m):
    """(I + m)^-1 for m [..., C, C] strictly lower triangular, by forward
    substitution a row at a time: row t = e_t - m[t] @ (rows before t)."""
    C = m.shape[-1]
    eye = jnp.eye(C, dtype=m.dtype)

    def row(t, inv):
        m_t = lax.dynamic_index_in_dim(m, t, axis=-2, keepdims=False)
        new = eye[t] - jnp.sum(m_t[..., None] * inv, axis=-2)
        return lax.dynamic_update_index_in_dim(inv, new, t, axis=-2)

    return lax.fori_loop(1, C, row, jnp.broadcast_to(eye, m.shape))


def scan(layer, x, state, conv, n_tokens, d: Dims, eps: float):
    """A slice of T positions a row, the first ``n_tokens`` real: x
    [B, T, D], state [B, H, d, d] float32, conv [B, K - 1, 3 H d] -> (out
    [B, T, D], state, conv). Positions at or past ``n_tokens`` leave state
    and window as the last real token left them (their decay is 1 and
    their beta 0, and the window handed out ends at the last real token);
    their outputs are whatever falls out and are the caller's to drop."""
    with jax.named_scope(SCOPES[1]):
        B, T, _ = x.shape
        H, dk, K = d.heads, d.head_dim, d.conv
        seq = jnp.concatenate([conv, x @ layer["w_qkv"]], axis=1)
        w = layer["conv_w"].astype(jnp.float32)
        q, k, v = _heads(jax.nn.silu(sum(
            seq[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))), d)
        new_conv = lax.dynamic_slice_in_dim(seq, n_tokens, K - 1, axis=1)
        g, beta, gate = _gates(layer, x, d)
        real = (jnp.arange(T) < n_tokens)[None, :, None]
        g = jnp.where(real[..., None], g, 0.0)
        beta = jnp.where(real, beta, 0.0)

        C = min(d.chunk, T)
        pad = -T % C
        n = (T + pad) // C

        def chunks(a):  # [B, T, H, ...] -> [B, n, H, C, ...]
            if pad:  # a padded position is one more that moves nothing
                a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            a = a.reshape((B, n, C) + a.shape[2:])
            return jnp.moveaxis(a, 2, 3)

        q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
        beta = chunks(beta)                                   # [B, n, H, C]
        cum = jnp.cumsum(g, axis=3)                           # [B, n, H, C, d]
        # exp(G_t - G_j) for j <= t, a channel: never above 1.
        at = jnp.arange(C)
        back = (at[:, None] >= at[None, :])[..., None]
        decay = jnp.exp(jnp.where(
            back, cum[..., :, None, :] - cum[..., None, :, :], -jnp.inf))
        kd = k[..., None, :, :] * decay                       # k_j exp(.)
        a = jnp.where(at[:, None] > at[None, :],
                      jnp.sum(k[..., :, None, :] * kd, axis=-1), 0.0)
        b = jnp.sum(q[..., :, None, :] * kd, axis=-1)         # [.., C, C]
        inv = _unit_lower_inverse(beta[..., None] * a)
        wv = _mm("bnhtj,bnhjv->bnhtv", inv, beta[..., None] * v)
        wk = _mm("bnhtj,bnhjk->bnhtk", inv,
                 beta[..., None] * jnp.exp(cum) * k)
        to_end = jnp.exp(cum[..., -1:, :] - cum)              # [B, n, H, C, d]

        def carry(s, inp):
            wv, wk, qd, b, kend, whole = inp
            u = wv - _mm("bhtk,bhkv->bhtv", wk, s)
            o = _mm("bhtk,bhkv->bhtv", qd, s) + _mm("bhtj,bhjv->bhtv", b, u)
            s = whole[..., None] * s + _mm("bhtk,bhtv->bhkv", kend, u)
            return s, o

        state, o = lax.scan(carry, state, tuple(
            jnp.moveaxis(t, 1, 0) for t in (
                wv, wk, q * jnp.exp(cum), b, k * to_end,
                jnp.exp(cum[..., -1, :]))))
        o = jnp.moveaxis(o, 0, 1)                             # [B, n, H, C, d]
        o = jnp.moveaxis(o, 2, 3).reshape(B, n * C, H, dk)[:, :T]
        return _gated_out(o, gate, layer, d, eps, x.dtype), state, new_conv
