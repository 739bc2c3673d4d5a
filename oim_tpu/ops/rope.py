"""Rotary position embeddings (RoPE).

Frequencies are computed once per model (host-side, float32) and indexed by
position inside jit; the rotation itself is elementwise and fuses into the
QK projection's epilogue.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0):
    """cos/sin tables [max_seq, head_dim//2], float32."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin, positions=None):
    """Rotate [B, T, H, D] by position; positions defaults to arange(T).

    Pair convention: (x[..., :D/2], x[..., D/2:]) — the "split-half" layout,
    matching the frequencies above.
    """
    if positions is None:
        cos_t = cos[: x.shape[1]]
        sin_t = sin[: x.shape[1]]
    else:
        cos_t = cos[positions]
        sin_t = sin[positions]
    # [T, D/2] (or [B, T, D/2] with explicit positions) -> broadcast over heads.
    cos_t = jnp.expand_dims(cos_t, axis=-2)
    sin_t = jnp.expand_dims(sin_t, axis=-2)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos_t - xf2 * sin_t
    out2 = xf2 * cos_t + xf1 * sin_t
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def interleaved_to_split_half(n: int):
    """Column order that turns an interleaved rope layout of ``n`` dims
    into the split-half one: [0, 2, 4, ..., 1, 3, 5, ...]. A projection
    whose rope OUTPUT columns are permuted this way (on the query and on
    the key alike) may be rotated split-half by ``apply_rope``: the pairs
    and their frequencies are the same, and the dot product of a rotated
    query with a rotated key does not depend on the order of the dims."""
    return jnp.concatenate([jnp.arange(0, n, 2), jnp.arange(1, n, 2)])
