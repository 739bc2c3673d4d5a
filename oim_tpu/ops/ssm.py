"""The Mamba-2 mixer (state-space duality) in plain ``jax.numpy``: the
one-token recurrent update a decode step runs, and the chunked scan a
prompt slice runs, which takes the recurrent state and the conv window in
and gives them out. Both compute the same recurrence (tests/test_hybrid.py
holds them to each other and to the sequential definition):

    [z | xBC | dt] = x W_in
    xBC_t  <- silu(b + sum_j w_j * xBC_{t-(K-1)+j})     depthwise, causal
    x [H, P], B [G, N], C [G, N] = split(xBC)           head h uses group h // (H/G)
    dt_t   = softplus(dt_t + dt_bias),  A = -exp(A_log)
    h_t    = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t  h: [H, P, N], float32
    y_t    = h_t C_t + D x_t
    out    = RMSNorm_groups(y * silu(z)) W_out

What a SLOT keeps between calls is ``h`` ([H, P, N] float32) and the last
K - 1 inputs of the conv ([K - 1, conv_dim], the model's dtype): a fixed
size whatever the position, unlike a page pool. The stacked state of a
model is {"ssm": [L, slots, H, P, N], "conv": [L, slots, (K - 1) *
conv_dim]} (models/generate.py ``init_state_pool``; a slot's window is
kept FLAT: K - 1 = 3 rows as the second-minor dim of a bf16 array are
padded to a 16-row tile, and a prefill that cut one slot's rows out of a
[.., K - 1, slots, conv_dim] pool had the compiler re-lay the whole pool
with those 3 rows minor, 1.6 GB for 39 MB).

The scan is the chunked algorithm: inside a chunk of ``Dims.chunk``
positions the outputs are a masked matrix product (as attention without a
softmax), the state moves chunk to chunk through a short sequential loop.
A kernel is a later change's; the named scopes ``ssm_scan`` and
``ssm_step`` mark what it would replace.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

NAME = "mamba"  # the kind, in the engine's accounting of state bytes
SCOPES = ("ssm_step", "ssm_scan")


@dataclasses.dataclass(frozen=True)
class Dims:
    heads: int       # H  (mamba_num_heads)
    head_dim: int    # P  (mamba_head_dim)
    groups: int      # G  (n_groups)
    state: int       # N  (ssm_state_size)
    conv: int = 4    # K  (conv_kernel)
    chunk: int = 128  # (chunk_size)

    # The state pool's leaves of this kind (``slot_leaves``).
    state_leaf = "ssm"
    window_leaf = "conv"

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.groups * self.state

    @property
    def proj_dim(self) -> int:
        """Columns of ``w_in``: z | xBC | dt."""
        return self.inner + self.conv_dim + self.heads

    @property
    def window(self) -> tuple:
        return (self.conv - 1, self.conv_dim)

    def slot_leaves(self, dtype) -> dict:
        """What a slot holds in one layer, as the pool keeps it: {leaf:
        (shape, dtype)}, the matrix state and the window (flat)."""
        return {self.state_leaf: ((self.heads, self.head_dim, self.state),
                                  jnp.float32),
                self.window_leaf: (((self.conv - 1) * self.conv_dim,), dtype)}


def init(rng, dim: int, d: Dims, dtype, n_layers: int,
         dt_min: float = 1e-3, dt_max: float = 0.1, dt_floor: float = 1e-4):
    """Stacked mixer leaves [L, ...], drawn as the family initialises them
    where that decides the numerics: ``dt_bias`` the inverse softplus of a
    log-uniform step in [dt_min, dt_max], ``A_log`` the log of a uniform
    [1, 16], ``D`` one; matrices at the fan-in of their contraction."""
    ks = jax.random.split(rng, 5)
    L = n_layers
    step = jnp.exp(jax.random.uniform(ks[2], (L, d.heads))
                   * (jnp.log(dt_max) - jnp.log(dt_min)) + jnp.log(dt_min))
    step = jnp.maximum(step, dt_floor)
    return {
        "w_in": (jax.random.normal(ks[0], (L, dim, d.proj_dim))
                 * dim ** -0.5).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (L, d.conv, d.conv_dim))
                   * d.conv ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((L, d.conv_dim), dtype),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(jnp.float32),
        "A_log": jnp.log(jax.random.uniform(
            ks[3], (L, d.heads), minval=1.0, maxval=16.0)).astype(jnp.float32),
        "D": jnp.ones((L, d.heads), jnp.float32),
        "gate_norm": jnp.ones((L, d.inner), jnp.float32),
        "w_out": (jax.random.normal(ks[4], (L, d.inner, dim))
                  * d.inner ** -0.5).astype(dtype),
    }


def _split_proj(p, d: Dims):
    return (p[..., :d.inner], p[..., d.inner:d.inner + d.conv_dim],
            p[..., d.inner + d.conv_dim:])


def _split_conv(c, d: Dims):
    """conv output [..., conv_dim] -> x [..., G, H/G, P], B, C [..., G, N]."""
    lead = c.shape[:-1]
    gn = d.groups * d.state
    x = c[..., :d.inner].reshape(lead + (d.groups, d.heads // d.groups,
                                         d.head_dim))
    b = c[..., d.inner:d.inner + gn].reshape(lead + (d.groups, d.state))
    cc = c[..., d.inner + gn:].reshape(lead + (d.groups, d.state))
    return x, b, cc


def _by_group(v, d: Dims):
    """A per-head vector [..., H] as [..., G, H/G]."""
    return v.reshape(v.shape[:-1] + (d.groups, d.heads // d.groups))


def _gated_out(y, z, layer, d: Dims, eps: float):
    """RMSNorm over each of the G groups of ``y * silu(z)``, then W_out."""
    g = (y * jax.nn.silu(z.astype(jnp.float32)))
    g = g.reshape(g.shape[:-1] + (d.groups, d.inner // d.groups))
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    g = g.reshape(z.shape) * layer["gate_norm"].astype(jnp.float32)
    return g.astype(z.dtype) @ layer["w_out"]


def step(layer, x, ssm, conv, d: Dims, eps: float):
    """One token a row: x [B, D], ssm [B, H, P, N] float32, conv
    [B, K - 1, conv_dim] -> (out [B, D], ssm, conv)."""
    with jax.named_scope(SCOPES[0]):
        B = x.shape[0]
        z, xbc, dt = _split_proj(x @ layer["w_in"], d)
        window = jnp.concatenate([conv, xbc[:, None]], axis=1)  # [B, K, C]
        mixed = jax.nn.silu(
            jnp.sum(window.astype(jnp.float32)
                    * layer["conv_w"].astype(jnp.float32), axis=1)
            + layer["conv_b"].astype(jnp.float32))
        xs, bm, cm = _split_conv(mixed, d)  # float32
        dt = _by_group(jax.nn.softplus(
            dt.astype(jnp.float32) + layer["dt_bias"]), d)    # [B, G, R]
        decay = jnp.exp(dt * _by_group(-jnp.exp(layer["A_log"]), d))
        h = ssm.reshape((B, d.groups, d.heads // d.groups,
                         d.head_dim, d.state))
        h = (h * decay[..., None, None]
             + (dt[..., None] * xs)[..., None] * bm[:, :, None, None, :])
        y = (jnp.sum(h * cm[:, :, None, None, :], axis=-1)
             + _by_group(layer["D"], d)[..., None] * xs)
        out = _gated_out(y.reshape(B, d.inner), z, layer, d, eps)
        return out, h.reshape(ssm.shape), window[:, 1:]


def scan(layer, x, ssm, conv, n_tokens, d: Dims, eps: float):
    """A slice of T positions a row, the first ``n_tokens`` real: x
    [B, T, D], ssm [B, H, P, N] float32, conv [B, K - 1, conv_dim] -> (out
    [B, T, D], ssm, conv). Positions at or past ``n_tokens`` leave state
    and window as the last real token left them (their step is 0, and the
    window handed out ends at the last real token); their outputs are
    whatever falls out and are the caller's to drop."""
    with jax.named_scope(SCOPES[1]):
        B, T, _ = x.shape
        G, R, P, N = d.groups, d.heads // d.groups, d.head_dim, d.state
        K = d.conv
        mm = x.dtype  # matmul operands; sums in float32
        z, xbc, dt = _split_proj(x @ layer["w_in"], d)
        seq = jnp.concatenate([conv, xbc], axis=1)
        w = layer["conv_w"].astype(jnp.float32)
        mixed = layer["conv_b"].astype(jnp.float32) + sum(
            seq[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))
        # The window after the last real token: inputs n - (K-1) .. n - 1,
        # which sit K - 1 further along in ``seq``.
        new_conv = lax.dynamic_slice_in_dim(seq, n_tokens, K - 1, axis=1)
        xs, bm, cm = _split_conv(jax.nn.silu(mixed), d)
        real = (jnp.arange(T) < n_tokens)[None, :, None]
        dt = jnp.where(real, jax.nn.softplus(
            dt.astype(jnp.float32) + layer["dt_bias"]), 0.0)  # [B, T, H]

        Q = min(d.chunk, T)
        pad = -T % Q
        if pad:  # whole chunks; a padded position is one more of step 0
            dt, xs, bm, cm = (jnp.pad(a, ((0, 0), (0, pad))
                                      + ((0, 0),) * (a.ndim - 2))
                              for a in (dt, xs, bm, cm))
        c = (T + pad) // Q
        dt = _by_group(dt, d).reshape(B, c, Q, G, R)
        xs = xs.reshape(B, c, Q, G, R, P)
        bm, cm = bm.reshape(B, c, Q, G, N), cm.reshape(B, c, Q, G, N)
        cum = jnp.cumsum(dt * _by_group(-jnp.exp(layer["A_log"]), d), axis=2)
        xd = (xs * dt[..., None]).astype(mm)                   # dt_s x_s

        # Inside a chunk: y_q = sum_{s <= q} exp(cum_q - cum_s) (C_q . B_s)
        # dt_s x_s, a masked [Q, Q] product a head.
        cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cm.astype(mm), bm.astype(mm),
                        preferred_element_type=jnp.float32)
        gap = cum[:, :, :, None] - cum[:, :, None, :]   # [B, c, q, s, G, R]
        causal = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])
        weight = jnp.exp(jnp.where(causal[:, :, None, None], gap, -jnp.inf))
        m = jnp.moveaxis(cb, 2, 4)[..., None] * weight   # [B, c, q, s, G, R]
        y = jnp.einsum("bcqsgr,bcsgrp->bcqgrp", m.astype(mm), xd,
                       preferred_element_type=jnp.float32)

        # What each chunk adds to the state by its end, and the state each
        # chunk starts from (sequential over the c chunks).
        to_end = jnp.exp(cum[:, :, -1:] - cum)                # [B, c, Q, G, R]
        added = jnp.einsum(
            "bcsgrp,bcsgn->bcgrpn", (xd * to_end[..., None]).astype(mm),
            bm.astype(mm), preferred_element_type=jnp.float32)
        whole = jnp.exp(cum[:, :, -1])                        # [B, c, G, R]

        def carry(h, inp):
            decay, add = inp
            return h * decay[..., None, None] + add, h

        h, start = lax.scan(
            carry, ssm.reshape(B, G, R, P, N),
            (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
        start = jnp.moveaxis(start, 0, 1)                # [B, c, G, R, P, N]
        y = y + jnp.einsum(
            "bcqgn,bcgrpn->bcqgrp", cm.astype(mm), start.astype(mm),
            preferred_element_type=jnp.float32) * jnp.exp(cum)[..., None]
        y = y + _by_group(layer["D"], d)[..., None] * xs
        y = y.reshape(B, c * Q, d.inner)[:, :T]
        return _gated_out(y, z, layer, d, eps), h.reshape(ssm.shape), new_conv
