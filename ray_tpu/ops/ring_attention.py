"""Ring attention: exact attention over a sequence-parallel mesh axis.

Each device holds a contiguous sequence shard of q/k/v. K/V shards rotate
around the ring via ``ppermute`` (single-hop ICI neighbours) while every
device accumulates FlashAttention online-softmax statistics for its local
queries — so per-device memory stays O(seq/ring) and the compute/comm
overlap is XLA's to schedule.

Net-new vs the reference, which has no sequence/context parallelism at
all (SURVEY §5.7: repo-wide grep for ring_attention/sequence_parallel
finds nothing). Used inside ``shard_map`` with the "sp" mesh axis.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.lax import axis_size

from .attention import NEG_INF


def ring_attention(q, k, v, *, axis: str = "sp", causal: bool = True,
                   scale: Optional[float] = None, kv_block: int = 512):
    """Attention where q/k/v are sequence-sharded along mesh ``axis``.

    Must be called inside shard_map/pjit with ``axis`` a real mesh axis.
    q: (B, Sq_local, Hq, D); k/v: (B, Skv_local, Hkv, D). Returns the
    local output shard (B, Sq_local, Hq, D). Exact (not approximate):
    equivalent to full attention over the concatenated sequence.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    ring = axis_size(axis)
    rank = jax.lax.axis_index(axis)
    scale_ = scale if scale is not None else d ** -0.5

    # Local query positions in the global sequence.
    q_pos = rank * sq + jnp.arange(sq)

    def one_chunk(kc, vc, src_rank):
        """(m, l, acc) contributions of one rotating kv chunk."""
        qf = q.astype(jnp.float32) * scale_
        kf = kc.astype(jnp.float32)
        vf = vc.astype(jnp.float32)
        n_rep = hq // kc.shape[2]
        if n_rep > 1:
            kf = jnp.repeat(kf, n_rep, axis=2)
            vf = jnp.repeat(vf, n_rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
        if causal:
            k_pos = src_rank * skv + jnp.arange(skv)
            mask = k_pos[None, :] <= q_pos[:, None]
            logits = jnp.where(mask[None, None], logits, NEG_INF)
        m = logits.max(axis=-1)
        p = jnp.exp(logits - m[..., None])
        # Zero fully-masked rows (exp(NEG_INF - NEG_INF) == 1 otherwise).
        p = jnp.where(logits > NEG_INF * 0.5, p, 0.0)
        l = p.sum(axis=-1)
        acc = jnp.einsum("bhqk,bkhd->bhqd", p, vf)
        return m, l, acc

    def merge(carry, chunk_stats):
        m, l, acc = carry
        cm, cl, cacc = chunk_stats
        m_new = jnp.maximum(m, cm)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(cm - m_new)
        l = l * c_old + cl * c_new
        acc = acc * c_old[..., None] + cacc * c_new[..., None]
        return m_new, l, acc

    def step(carry, _):
        m, l, acc, kc, vc, src = carry
        # Rotate first (iterations 1..ring-1); the local chunk's stats are
        # folded in by the prologue below, so the last useless rotation of
        # a rotate-after-compute loop never happens.
        perm = [(i, (i + 1) % ring) for i in range(ring)]
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        src = (src - 1) % ring
        m, l, acc = merge((m, l, acc), one_chunk(kc, vc, src))
        return (m, l, acc, kc, vc, src), None

    m0, l0, acc0 = one_chunk(k, v, rank)  # prologue: local chunk
    carry = (m0, l0, acc0, k, v, rank)
    (m, l, acc, _, _, _), _ = jax.lax.scan(step, carry, None, length=ring - 1)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)
