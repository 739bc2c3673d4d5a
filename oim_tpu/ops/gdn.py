"""The GatedDeltaNet mixer (a gated delta rule with one decay a HEAD, fewer
key heads than value heads) in plain ``jax.numpy``: the one-token recurrent
update a decode step runs, and the chunked scan a prompt slice runs, which
takes the recurrent state and the conv window in and gives them out. Both
compute the same recurrence (tests/test_gdn.py holds them to each other and
to the sequential definition of benchmarks/reference/gigachat3_5_like.py):

    [q | k | v] = x W_qkv              Hk x dk | Hk x dk | Hv x dv wide
    q, k, v <- silu(sum_j w_j * [q|k|v]_{t-(K-1)+j})    depthwise, causal
    q_h <- q_h / |q_h| * dk^-1/2,  k_h <- k_h / |k_h|   a key head
    value head j reads key head j // (Hv / Hk)
    g_t    = -exp(A_log) * softplus(x_t W_a + dt_bias)  [Hv], one a head
    beta_t = sigmoid(x_t W_b)                           [Hv]
    S_t    = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
    o_t    = S_t^T q_t                        S: [Hv, dk, dv], float32
    out    = [Norm_head(o_t) * scale * sigmoid(x_t W_z)] W_out

What a SLOT keeps between calls is ``S`` and the last K - 1 inputs of the
conv ([K - 1, 2 Hk dk + Hv dv], the model's dtype, kept FLAT in the pool as
ops/ssm.py says why): a fixed size whatever the position.

The algebra is ops/kda.py's with the decay a scalar of the head, and that is
what it is cheaper by. Inside a chunk of C positions from a state S_0, with
G_t the head's cumulative log-decay up to t,

    A_tj = (k_t . k_j) exp(G_t - G_j)   j < t
    B_tj = (q_t . k_j) exp(G_t - G_j)   j <= t
    (I + Diag(beta) A) U = Diag(beta) (V - exp(G) * (K S_0))
    O   = exp(G) * (Q S_0) + B U
    S_C = exp(G_C) S_0 + (exp(G_C - G) * K)^T U

``K K^T`` and ``Q K^T`` are ONE [C, C] product a KEY head and the decay an
element-wise factor a value head: no exponent stands inside a contraction,
where the per-channel form builds [C, C, d] and sums it (hence a chunk of 64
here for its 16). Every exponent is a difference of cumulative log-decays in
the direction time runs, never positive. All of it in float32 at the highest
matmul precision, as ops/kda.py says why.

The one-token update reads the state twice and writes it once: ``S^T k`` and
``S^T q`` in one pass, then ``S <- a S + k u^T``; the read-out of the NEW
state is ``a S^T q + (q . k) u``, which needs no third pass.

A kernel is a later change's. The mixer's own scopes, ``gdn_step`` and
``gdn_scan``, stand INSIDE the delta-rule family's (``kda_step``,
``kda_scan``): the benchmark's vocabulary of scopes charges an operation to
the innermost name it knows, this mixer's roofline reads its own.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from oim_tpu.ops.kda import QK_EPS, _mm, _unit_lower_inverse

NAME = "gdn"  # the kind, in the engine's accounting of state bytes
SCOPES = ("kda_step/gdn_step", "kda_scan/gdn_scan")


@dataclasses.dataclass(frozen=True)
class Dims:
    k_heads: int              # Hk (linear_num_key_heads): queries and keys
    v_heads: int              # Hv (linear_num_value_heads): values, state
    k_dim: int                # dk (linear_key_head_dim)
    v_dim: int                # dv (linear_value_head_dim)
    conv: int = 4             # K  (linear_conv_kernel_dim)
    gate_scale: float = 1.0   # the output gate's (linear_sigmoid_gate_scale)
    gated_norm: bool = False  # the head norm's weight through 2 sigmoid(.)
    # C, the positions a chunk of ``scan``: the program's own size (no
    # published key, no option), the published algorithm's.
    chunk: int = 64

    # The state pool's leaves of this kind (``slot_leaves``).
    state_leaf = "gdn"
    window_leaf = "gdn_conv"

    @property
    def key_dim(self) -> int:
        return self.k_heads * self.k_dim

    @property
    def value_dim(self) -> int:
        return self.v_heads * self.v_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def window(self) -> tuple:
        return (self.conv - 1, self.conv_dim)

    def slot_leaves(self, dtype) -> dict:
        """What a slot holds in one layer, as the pool keeps it: {leaf:
        (shape, dtype)}, the matrix state and the window (flat)."""
        return {self.state_leaf: ((self.v_heads, self.k_dim, self.v_dim),
                                  jnp.float32),
                self.window_leaf: (((self.conv - 1) * self.conv_dim,), dtype)}


def n_params(dim: int, d: Dims) -> int:
    """Parameters of one mixer at model width ``dim`` (without the block's
    norms): W_qkv, the two head-wide gates, the output gate and W_out, then
    the conv, dt_bias, A_log and the head norm's weight."""
    return (dim * (d.conv_dim + 2 * d.v_heads + 2 * d.value_dim)
            + d.conv * d.conv_dim + 2 * d.v_heads + d.v_dim)


def init(rng, dim: int, d: Dims, dtype, n_layers: int,
         dt_min: float = 1e-3, dt_max: float = 0.1, dt_floor: float = 1e-4):
    """Stacked mixer leaves [L, ...]: ``dt_bias`` the inverse softplus of a
    log-uniform step in [dt_min, dt_max] and ``A_log`` the log of a uniform
    [1, 16], one a value head (as ops/kda.py); matrices at the fan-in of
    their contraction; the head norm's weight at what multiplies by 1."""
    ks = jax.random.split(rng, 8)
    L = n_layers
    step = jnp.exp(jax.random.uniform(ks[2], (L, d.v_heads))
                   * (jnp.log(dt_max) - jnp.log(dt_min)) + jnp.log(dt_min))
    step = jnp.maximum(step, dt_floor)

    def dense(key, shape):
        return (jax.random.normal(key, (L,) + shape)
                * shape[0] ** -0.5).astype(dtype)

    return {
        "w_qkv": dense(ks[0], (dim, d.conv_dim)),
        "conv_w": dense(ks[1], (d.conv, d.conv_dim)),
        "w_a": dense(ks[4], (dim, d.v_heads)),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(jnp.float32),
        "A_log": jnp.log(jax.random.uniform(
            ks[3], (L, d.v_heads), minval=1.0, maxval=16.0)
        ).astype(jnp.float32),
        "w_b": dense(ks[5], (dim, d.v_heads)),
        "w_z": dense(ks[6], (dim, d.value_dim)),
        "o_norm": (jnp.zeros if d.gated_norm else jnp.ones)(
            (L, d.v_dim), jnp.float32),
        "w_out": dense(ks[7], (d.value_dim, dim)),
    }


def _gates(layer, x, d: Dims):
    """x [..., D] -> (log-decay g [..., Hv] <= 0, beta [..., Hv] in (0, 1),
    the output gate before its sigmoid [..., Hv, dv]), float32."""
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        (x @ layer["w_a"]).astype(jnp.float32) + layer["dt_bias"])
    beta = jax.nn.sigmoid((x @ layer["w_b"]).astype(jnp.float32))
    gate = (x @ layer["w_z"]).astype(jnp.float32).reshape(
        x.shape[:-1] + (d.v_heads, d.v_dim))
    return g, beta, gate


def _heads(mixed, d: Dims):
    """The conv's output [..., 2 Hk dk + Hv dv] float32 -> q, k [..., Hk, dk]
    at unit length a head, q scaled by dk^-1/2, and v [..., Hv, dv]."""
    lead = mixed.shape[:-1]
    q, k, v = jnp.split(mixed, (d.key_dim, 2 * d.key_dim), axis=-1)
    q = q.reshape(lead + (d.k_heads, d.k_dim))
    k = k.reshape(lead + (d.k_heads, d.k_dim))
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + QK_EPS)
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + QK_EPS)
    return q * d.k_dim ** -0.5, k, v.reshape(lead + (d.v_heads, d.v_dim))


def _gated_out(o, gate, layer, d: Dims, eps: float, dtype):
    """The norm over each head of ``o`` [..., Hv, dv], the gate, W_out."""
    weight = layer["o_norm"].astype(jnp.float32)
    if d.gated_norm:
        weight = 2.0 * jax.nn.sigmoid(weight)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * weight * (d.gate_scale * jax.nn.sigmoid(gate))
    return (o.reshape(o.shape[:-2] + (d.value_dim,)).astype(dtype)
            @ layer["w_out"])


def step(layer, x, state, conv, d: Dims, eps: float):
    """One token a row: x [B, D], state [B, Hv, dk, dv] float32, conv
    [B, K - 1, conv_dim] -> (out [B, D], state, conv)."""
    with jax.named_scope(SCOPES[0]):
        window = jnp.concatenate(
            [conv, (x @ layer["w_qkv"])[:, None]], axis=1)      # [B, K, .]
        q, k, v = _heads(jax.nn.silu(jnp.sum(
            window.astype(jnp.float32)
            * layer["conv_w"].astype(jnp.float32), axis=1)), d)
        per = d.v_heads // d.k_heads
        q, k = jnp.repeat(q, per, axis=1), jnp.repeat(k, per, axis=1)
        g, beta, gate = _gates(layer, x, d)
        a = jnp.exp(g)[..., None]                                # [B, Hv, 1]
        # Sums over a state's rows as multiply-and-add, not as a product the
        # TPU would round to bfloat16; both read the state in one pass.
        sk = jnp.sum(k[..., None] * state, axis=-2)              # S^T k
        sq = jnp.sum(q[..., None] * state, axis=-2)              # S^T q
        u = beta[..., None] * (v - a * sk)
        o = a * sq + jnp.sum(q * k, axis=-1, keepdims=True) * u
        state = a[..., None] * state + k[..., None] * u[..., None, :]
        return (_gated_out(o, gate, layer, d, eps, x.dtype), state,
                window[:, 1:])


def scan(layer, x, state, conv, n_tokens, d: Dims, eps: float):
    """A slice of T positions a row, the first ``n_tokens`` real: x
    [B, T, D], state [B, Hv, dk, dv] float32, conv [B, K - 1, conv_dim] ->
    (out [B, T, D], state, conv). Positions at or past ``n_tokens`` leave
    state and window as the last real token left them (their decay is 1 and
    their beta 0, and the window handed out ends at the last real token);
    their outputs are whatever falls out and are the caller's to drop."""
    with jax.named_scope(SCOPES[1]):
        B, T, _ = x.shape
        K, per = d.conv, d.v_heads // d.k_heads
        seq = jnp.concatenate([conv, x @ layer["w_qkv"]], axis=1)
        w = layer["conv_w"].astype(jnp.float32)
        q, k, v = _heads(jax.nn.silu(sum(
            seq[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))), d)
        new_conv = lax.dynamic_slice_in_dim(seq, n_tokens, K - 1, axis=1)
        g, beta, gate = _gates(layer, x, d)
        real = (jnp.arange(T) < n_tokens)[None, :, None]
        g = jnp.where(real, g, 0.0)
        beta = jnp.where(real, beta, 0.0)

        C = min(d.chunk, T)
        pad = -T % C
        n = (T + pad) // C

        def chunks(a):  # [B, T, H, ...] -> [B, n, H, C, ...]
            if pad:  # a padded position is one more that moves nothing
                a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            a = a.reshape((B, n, C) + a.shape[2:])
            return jnp.moveaxis(a, 2, 3)

        q, k, v = chunks(q), chunks(k), chunks(v)
        g, beta = chunks(g), chunks(beta)[..., None]          # [B, n, Hv, C]
        cum = jnp.cumsum(g, axis=-1)
        # exp(G_t - G_j) for j <= t, a value head: never above 1.
        at = jnp.arange(C)
        decay = jnp.exp(jnp.where(
            at[:, None] >= at[None, :],
            cum[..., :, None] - cum[..., None, :], -jnp.inf))  # [.., C, C]

        def heads(m):  # a key head's [B, n, Hk, ...] for its value heads
            return jnp.repeat(m, per, axis=2)

        a = jnp.where(at[:, None] > at[None, :],
                      heads(_mm("bnhtk,bnhjk->bnhtj", k, k)) * decay, 0.0)
        b = heads(_mm("bnhtk,bnhjk->bnhtj", q, k)) * decay
        q, k = heads(q), heads(k)                             # [B, n, Hv, C, dk]
        grown = jnp.exp(cum)[..., None]                       # exp(G_t)
        inv = _unit_lower_inverse(beta * a)
        wv = _mm("bnhtj,bnhjv->bnhtv", inv, beta * v)
        wk = _mm("bnhtj,bnhjk->bnhtk", inv, beta * grown * k)
        to_end = jnp.exp(cum[..., -1:] - cum)[..., None]      # exp(G_C - G_t)

        def carry(s, inp):
            wv, wk, qd, b, kend, whole = inp
            u = wv - _mm("bhtk,bhkv->bhtv", wk, s)
            o = _mm("bhtk,bhkv->bhtv", qd, s) + _mm("bhtj,bhjv->bhtv", b, u)
            s = whole[..., None, None] * s + _mm("bhtk,bhtv->bhkv", kend, u)
            return s, o

        state, o = lax.scan(carry, state, tuple(
            jnp.moveaxis(t, 1, 0) for t in (
                wv, wk, q * grown, b, k * to_end, jnp.exp(cum[..., -1]))))
        o = jnp.moveaxis(o, 0, 1)                             # [B, n, Hv, C, dv]
        o = jnp.moveaxis(o, 2, 3).reshape(B, n * C, d.v_heads, d.v_dim)[:, :T]
        return _gated_out(o, gate, layer, d, eps, x.dtype), state, new_conv
