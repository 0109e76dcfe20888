"""Normalization ops.

RMSNorm computes in float32 regardless of input dtype (bf16 accumulation
loses enough precision to move loss curves), then casts back — the
standard TPU recipe: the cast pair fuses into the surrounding XLA graph.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps: float = 1e-5):
    """RMSNorm: x * w / sqrt(mean(x^2) + eps), f32 accumulation."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    """LayerNorm with a bias: (x - mean) / sqrt(var + eps) * w + b, f32
    accumulation."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                             + eps)
    return (out * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)
