"""Compressed convolutional attention (CCA), the mixing that stands before
the softmax: queries and keys are made in a compressed latent (H query heads
and Hkv key heads of d, far under the model's width), mixed over the
SEQUENCE by two causal convolutions of two taps, joined by a q-k mean,
normalised with a learned temperature on the keys and rotated in half of a
head; values are read half from this position and half from the one before.
With ``h_t`` the normed input (``h_{-1}`` = ``p_{-1}`` = ``u_{-1}`` = 0):

    p_t = [q~_t | k~_t] = h_t [W_q | W_k]                (H + Hkv) d wide
    u_t[j] = w0[0, j] p_{t-1}[j] + w0[1, j] p_t[j] + b0[j]        depthwise
    z_t[g] = u_{t-1}[g] W1[0, g] + u_t[g] W1[1, g] + b1[g]   a head g a group
    q_t[i] = z_t[i] + (q~_t[i] + k~_t[i // G]) / 2           G = H / Hkv
    k_t[j] = z_t[H + j] + (mean_{i // G = j} q~_t[i] + k~_t[j]) / 2
    q^ = q d^1/2 / sqrt(|q|^2 + eps),  k^ = tau_j k d^1/2 / sqrt(|k|^2 + eps)
    q^, k^ rotated in their first ``rope_dim`` dims (ops/rope.py)
    v_t = [h_t W_v1 | h_{t-1} W_v2]     the first half of the key-value heads
                              holds this position's values, the second half
                              those of the position before

After it K and V of a position are final: a GQA page pool and its kernels
(ops/paged_attention.py) serve them at H query and Hkv key-value heads, the
softmax scale ``d^-1/2``. What a SLOT keeps beside its pages is a TAIL, a
fixed size whatever the position: ``[p_t | u_t | h_t W_v2]`` of its last
position, float32, kept flat. ``step`` is the one-token form a decode step
runs from a slot's tail, ``scan`` the form a prompt slice runs from the tail
the slice before it left (zeros at position 0); both are ``mix``, whose
shifts read the tail where the slice has no position before.

The elementwise part (convolution, mean, norm, rotation) runs in float32;
the projections and the grouped convolution are products in the model's
type with float32 sums.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from oim_tpu.ops.rope import apply_rope

NAME = "cca"  # the kind, in the engine's accounting of state bytes
# The mixing stands under ONE name inside the block's ``blk_qkv`` in both
# programs (the module tells a step's from a slice's); (step, scan) as the
# recurrent kinds' modules name theirs.
SCOPE = "blk_qkv/cca_mix"
SCOPES = (SCOPE, SCOPE)
TAPS = 2  # both convolutions' kernel (cca_time0 = cca_time1 = 2)


@dataclasses.dataclass(frozen=True)
class Dims:
    heads: int      # H: query heads
    kv_heads: int   # Hkv: key heads; the values' two halves
    head_dim: int   # d (how many of them rotate is the rope tables' width)

    # The state pool's leaf of this kind (``slot_leaves``).
    tail_leaf = "cca_tail"

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def latent(self) -> int:
        """Width of the packed pre-conv ``[q~ | k~]``."""
        return self.q_dim + self.kv_dim

    @property
    def shifted(self) -> int:
        """Width of the values read from the position before."""
        return self.kv_dim // 2

    @property
    def tail(self) -> int:
        return 2 * self.latent + self.shifted

    def slot_leaves(self, dtype) -> dict:
        """What a slot holds in one layer, as the pool keeps it: {leaf:
        (shape, dtype)}, the tail, float32 whatever the model's type."""
        del dtype
        return {self.tail_leaf: ((self.tail,), jnp.float32)}


def n_params(dim: int, d: Dims) -> int:
    """Parameters of one CCA sublayer at model width ``dim`` (without the
    block's norm and scales): W_q | W_k, W_v, W_o, the two convolutions with
    their biases and the keys' temperature."""
    groups = d.heads + d.kv_heads
    return (dim * (d.latent + d.kv_dim + d.q_dim)
            + (TAPS + 1) * d.latent
            + TAPS * groups * d.head_dim * d.head_dim + d.latent
            + d.kv_heads)


def init(rng, dim: int, d: Dims, dtype, n_layers: int):
    """Stacked leaves [L, ...]: matrices and both convolutions at the fan-in
    of their contraction, biases 0, the temperature 1."""
    ks = jax.random.split(rng, 5)
    L, groups = n_layers, d.heads + d.kv_heads

    def dense(key, shape, fan):
        return (jax.random.normal(key, (L,) + shape) * fan ** -0.5
                ).astype(dtype)

    return {
        "w_qk": dense(ks[0], (dim, d.latent), dim),
        "w_v": dense(ks[1], (dim, d.kv_dim), dim),
        "wo": dense(ks[2], (d.q_dim, dim), d.q_dim),
        "conv0_w": jax.random.normal(ks[4], (L, TAPS, d.latent), jnp.float32)
        * TAPS ** -0.5,
        "conv0_b": jnp.zeros((L, d.latent), jnp.float32),
        "conv1_w": dense(ks[3], (TAPS, groups, d.head_dim, d.head_dim),
                         TAPS * d.head_dim),
        "conv1_b": jnp.zeros((L, d.latent), jnp.float32),
        "tau": jnp.ones((L, d.kv_heads), jnp.float32),
    }


def split_tail(tail, d: Dims):
    """(p, u, shifted-value projection) of a tail [..., d.tail]."""
    return (tail[..., :d.latent], tail[..., d.latent:2 * d.latent],
            tail[..., 2 * d.latent:])


def _shift(x, first):
    """x [B, T, W] one position later: row 0 is ``first`` [B, W]."""
    return jnp.concatenate([first[:, None].astype(x.dtype), x[:, :-1]], axis=1)


def _unit(x, eps: float):
    """x d^1/2 / sqrt(|x|^2 + eps) over the last axis."""
    scale = lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)
    return x * (x.shape[-1] ** 0.5 * scale)


def mix(layer, h, tail, d: Dims, cos, sin, positions, n_tokens=None,
        eps: float = 1e-5):
    """h [B, T, D] (normed) at ``positions`` [B, T], from ``tail`` [B,
    d.tail] float32 -> (q [B, T, H, d], k and v [B, T, Hkv, d] in ``h``'s
    type, the tail after position ``n_tokens`` - 1 (the last without it))."""
    B, T, _ = h.shape
    H, Hkv, hd = d.heads, d.kv_heads, d.head_dim
    f32 = jnp.float32
    tail_p, tail_u, tail_s = split_tail(tail, d)
    p = jnp.matmul(h, layer["w_qk"], preferred_element_type=f32)
    w0 = layer["conv0_w"]
    u = w0[0] * _shift(p, tail_p) + w0[1] * p + layer["conv0_b"]
    w1 = layer["conv1_w"]

    def grouped(x, w):  # a head's channels through that head's matrix
        return jnp.einsum(
            "btgc,gce->btge", x.reshape(B, T, H + Hkv, hd).astype(w.dtype), w,
            preferred_element_type=f32)

    z = (grouped(_shift(u, tail_u), w1[0]) + grouped(u, w1[1])
         + layer["conv1_b"].reshape(H + Hkv, hd))
    q0 = p[..., :d.q_dim].reshape(B, T, Hkv, H // Hkv, hd)
    k0 = p[..., d.q_dim:].reshape(B, T, Hkv, hd)
    q = z[:, :, :H] + (0.5 * (q0 + k0[:, :, :, None])).reshape(B, T, H, hd)
    k = z[:, :, H:] + 0.5 * (jnp.mean(q0, axis=3) + k0)
    q = _unit(q, eps)
    k = _unit(k, eps) * layer["tau"].astype(f32)[:, None]
    q = apply_rope(q, cos, sin, positions).astype(h.dtype)
    k = apply_rope(k, cos, sin, positions).astype(h.dtype)
    s = jnp.matmul(h, layer["w_v"], preferred_element_type=f32)
    now, later = s[..., :d.shifted], s[..., d.shifted:]
    v = jnp.concatenate([now, _shift(later, tail_s)], axis=-1)
    v = v.reshape(B, T, Hkv, hd).astype(h.dtype)

    def last(x):  # the last real position's row
        if n_tokens is None:
            return x[:, -1]
        return lax.dynamic_slice_in_dim(x, n_tokens - 1, 1, axis=1)[:, 0]

    tail = jnp.concatenate([last(p), last(u), last(later)], axis=-1)
    return q, k, v, tail.astype(f32)


def step(layer, h, tail, d: Dims, cos, sin, positions, eps: float = 1e-5):
    """One token a row: h [B, D] at ``positions`` [B] from each row's tail
    -> (q [B, 1, H, d], k, v [B, 1, Hkv, d], tail [B, d.tail])."""
    return mix(layer, h[:, None], tail, d, cos, sin, positions[:, None],
               None, eps)


def scan(layer, h, tail, n_tokens, d: Dims, cos, sin, positions,
         eps: float = 1e-5):
    """A slice: h [B, T, D] at ``positions`` [B, T], its first ``n_tokens``
    real, resumed from ``tail`` (zeros at position 0) -> (q, k, v, the tail
    after the last real position)."""
    return mix(layer, h, tail, d, cos, sin, positions, n_tokens, eps)
