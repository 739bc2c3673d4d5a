"""Normalization ops.

Plain jnp on purpose: XLA fuses the reduce + scale chain into the adjacent
matmuls on TPU; a pallas kernel here would only pin layouts. Reductions run
in float32 regardless of activation dtype (bf16 accumulation loses ~3 digits
over a 4k-wide embed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def rmsnorm(x, weight, eps: float = 1e-6):
    """RMSNorm (Llama-family). weight shape: x.shape[-1]."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def gated_rmsnorm(x, weight, eps: float = 1e-6):
    """The zero-centred gated norm: RMSNorm whose weight passes a sigmoid,
    ``x * rsqrt(mean(x^2) + eps) * 2 sigmoid(w)``: 1 at ``w`` = 0, between
    0 and 2 whatever ``w``. The tensors and the cost of an RMSNorm."""
    return rmsnorm(x, 2.0 * jax.nn.sigmoid(weight.astype(jnp.float32)), eps)


def layernorm(x, weight, bias, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    normed = (xf - mean) * lax.rsqrt(var + eps)
    out = normed * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)
