"""Rotary position embeddings (RoPE).

Frequencies are computed once per model (host-side, float32) and indexed by
position inside jit; the rotation itself is elementwise and fuses into the
QK projection's epilogue.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_ramp(head_dim: int, theta: float, original_max: int,
              beta_fast: float, beta_slow: float):
    """YaRN's blend a pair i of ``head_dim`` / 2, float32 in [0, 1]: 0 where
    the pair turns ``beta_fast`` times or more over ``original_max``
    positions (kept as trained), 1 where it turns ``beta_slow`` times or
    fewer (interpolated), a ramp between the two pairs' whole indices."""
    def pair(turns: float) -> float:
        return (head_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low, high = math.floor(pair(beta_fast)), math.ceil(pair(beta_slow))
    at = jnp.arange(head_dim // 2, dtype=jnp.float32)
    return jnp.clip((at - low) / max(high - low, 1e-3), 0.0, 1.0)


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0,
                     yarn: tuple = ()):
    """cos/sin tables [max_seq, head_dim//2], float32. ``yarn`` = (factor,
    original_max_position_embeddings, beta_fast, beta_slow) divides the slow
    pairs' frequencies by ``factor`` (``yarn_ramp``); the tables are not
    scaled (the family's ``mscale`` over ``mscale_all_dim`` is 1: what YaRN
    adds to the attention's temperature is the softmax scale's, see
    ops/latent_attention.py ``Dims.mscale``)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if yarn:
        factor, original_max, beta_fast, beta_slow = yarn
        ramp = yarn_ramp(head_dim, theta, original_max, beta_fast, beta_slow)
        inv_freq = ramp * inv_freq / factor + (1.0 - ramp) * inv_freq
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin, positions=None):
    """Rotate [B, T, H, D] by position; positions defaults to arange(T).

    Pair convention: (x[..., :D/2], x[..., D/2:]) — the "split-half" layout,
    matching the frequencies above. Tables of a rotary width under the
    head's (``partial_rotary_factor``: cos/sin [.., R/2] with R < D) rotate
    the first R dims in split-half pairs (i, i + R/2) and pass the rest.
    """
    width = 2 * cos.shape[-1]
    if width < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :width], cos, sin, positions), x[..., width:]],
            axis=-1)
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
