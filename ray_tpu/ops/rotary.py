"""Rotary position embeddings (RoPE), Llama-3 style.

Frequencies are precomputed once per model (host side) and passed in as a
(seq, head_dim/2) cos/sin table so the per-step work is one fused
elementwise multiply on the VPU.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``
    (1 where the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, scaling: dict):
    """YaRN's frequencies (Peng et al. 2023, as DeepSeek-V2 publishes
    them): dimension pairs that turn often within the original context
    keep their frequency (extrapolation), pairs that turn less than once
    are slowed by ``factor`` (interpolation), a linear ramp between,
    from the pair that makes ``beta_fast`` turns over
    ``original_max_position_embeddings`` to the one that makes
    ``beta_slow``."""
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])
    extra = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    inter = extra / factor

    def correction_dim(turns: float) -> float:
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    mask = 1.0 - ramp            # 1: keep the frequency, 0: slow it
    return inter * (1.0 - mask) + extra * mask


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 500000.0,
                     dtype=jnp.float32, scaling=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables of shape (max_seq, head_dim // 2). ``scaling``: a
    published ``rope_scaling`` of type "yarn" (a dict, or its items):
    ``yarn_inv_freq``'s frequencies, the tables times ``mscale(factor,
    mscale) / mscale(factor, mscale_all_dim)``. None: plain ``theta``."""
    scaling = dict(scaling or {})
    if scaling:
        kind = scaling.get("type", scaling.get("rope_type"))
        if kind != "yarn":
            raise ValueError(f"rope scaling {kind!r}: only 'yarn' is known")
        inv_freq = yarn_inv_freq(head_dim, theta, scaling)
        amplitude = (yarn_mscale(scaling["factor"],
                                 scaling.get("mscale", 1.0))
                     / yarn_mscale(scaling["factor"],
                                   scaling.get("mscale_all_dim", 0.0)))
    else:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                               dtype=jnp.float32)
                                    / head_dim))
        amplitude = 1.0
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return ((jnp.cos(freqs) * amplitude).astype(dtype),
            (jnp.sin(freqs) * amplitude).astype(dtype))


def apply_rotary(x, cos, sin, positions=None):
    """Rotate half-split pairs (x[..., :d/2], x[..., d/2:]) by the
    position angle — the GPT-NeoX / HF-Llama layout. Checkpoints stored
    in Meta's interleaved even/odd layout must be permuted at load time
    (handled by the model's checkpoint import, not here).

    x: (..., seq, heads, head_dim). cos/sin: (max_seq, head_dim//2) or
    already gathered (..., seq, head_dim//2) when ``positions`` is given
    (decode path with per-sequence offsets).
    """
    if positions is not None:
        cos = jnp.take(cos, positions, axis=0)
        sin = jnp.take(sin, positions, axis=0)
    else:
        seq = x.shape[-3]
        cos, sin = cos[:seq], sin[:seq]
    # broadcast over heads: (..., seq, 1, head_dim//2)
    cos = jnp.expand_dims(cos, axis=-2)
    sin = jnp.expand_dims(sin, axis=-2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
