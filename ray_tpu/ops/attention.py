"""Attention kernels: naive, blockwise (FlashAttention-style online
softmax in pure jax), and a Pallas TPU flash-attention forward kernel.

Layouts: q (B, Sq, Hq, D); k/v (B, Skv, Hkv, D). GQA when Hkv < Hq.

Dispatch policy (``attention``):
  * TPU → Pallas flash kernels for BOTH directions: forward (MXU-tiled,
    VMEM online-softmax accumulation, LSE saved) and backward (dq + dkv
    kernels rebuilding softmax from the LSE — ~4x the throughput of a
    blockwise-recompute VJP).
  * everywhere else (CPU tests, unaligned shapes) → blockwise jax
    implementation; XLA fuses it well and autodiff gives a
    memory-efficient backward when wrapped in jax.checkpoint.

The forward kernel's grid is (batch*heads, query blocks, key chunks). A
grid step holds a chunk of its head's keys and values in VMEM, the whole
head wherever ``_pick_chunk`` finds room for it (every served bucket),
and walks the chunk's key blocks itself, ascending, from the first block
its query block can see (0, or the window's far edge) to its diagonal
block: blocks above the diagonal, behind the window, or under a query
block that holds no token are no step of anything. Only the blocks that
can hold a hidden pair (the diagonal's, the one on a window's edge)
build a mask; the others run a body without one. ``flash_forward_steps``
counts the steps walked and the blocks computed. The backward kernels
keep a grid of single blocks.

The reference has no attention of its own (tensors are torch's problem —
SURVEY §2.3/§5.7); these kernels are net-new TPU substrate.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

try:  # pallas TPU backend is importable even on CPU-only processes
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

NEG_INF = -1e30
# the window flash kernel's name in a device trace
WINDOW_KERNEL = "flash_window_fwd"
# the flash kernel's name where a head's values are not as wide as its
# queries and keys (latent attention's expanded form: 192 and 128)
LATENT_KERNEL = "flash_mla_fwd"


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def naive_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    window: Optional[int] = None):
    """Reference O(S^2)-memory attention (correctness oracle for tests).
    ``window``: a query sees its ``window`` newest keys, its own among
    them (needs ``causal``)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(sq)[:, None] + (skv - sq)
        ki = jnp.arange(skv)[None, :]
        seen = ki <= qi
        if window is not None:
            seen &= qi - ki < window
        logits = jnp.where(seen, logits, NEG_INF)
    # Masked softmax with all-masked rows producing zeros (not uniform).
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = jnp.where(logits > NEG_INF * 0.5, p, 0.0)
    denom = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / denom, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Blockwise attention: lax.scan over kv chunks with online softmax.
# Differentiable; O(S * block) memory per step.
# ---------------------------------------------------------------------------


def blockwise_attention(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        kv_block: int = 512,
                        window: Optional[int] = None):
    """FlashAttention recurrence in jax: scan kv blocks, track (m, l, acc).
    ``window`` (with ``causal``): a query sees its ``window`` newest
    keys, its own among them. Every key block is scanned for all the
    queries at once, so the window is a mask here and skips nothing:
    this is the CPU's path."""
    if window is not None and not causal:
        raise ValueError("a window needs causal attention")
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    n_rep = hq // hkv
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)

    kv_block = min(kv_block, skv)
    pad = (-skv) % kv_block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = (skv + pad) // kv_block

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32).reshape(b, n_blocks, kv_block, hq, d)
    vf = v.astype(jnp.float32).reshape(b, n_blocks, kv_block, hq, -1)
    # scan over blocks: move block axis to front
    kf = jnp.moveaxis(kf, 1, 0)
    vf = jnp.moveaxis(vf, 1, 0)

    q_pos = jnp.arange(sq)[:, None] + (skv - sq)

    def step(carry, blk):
        m, l, acc, j = carry
        kb, vb = blk
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)
        k_pos = j * kv_block + jnp.arange(kv_block)[None, :]
        mask = k_pos < skv  # padding mask, shape (1, kv_block)
        if causal:
            mask = mask & (k_pos <= q_pos)  # (sq, kv_block)
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        # Keep fully-masked rows at zero weight: exp(NEG_INF - NEG_INF)
        # would otherwise be 1 and attend uniformly (incl. padding).
        p = jnp.where(logits > NEG_INF * 0.5, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        return (m_new, l_new, acc_new, j + 1), None

    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    acc0 = jnp.zeros((b, hq, sq, v.shape[-1]), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, acc0, 0), (kf, vf))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU flash-attention forward: grid (batch*heads_q, query blocks,
# key chunks), the key blocks of a chunk walked inside the kernel (module
# docstring).
# ---------------------------------------------------------------------------

# VMEM the kernel had before it held a chunk: Mosaic's default scoped
# limit, which holds the query and output blocks, the accumulators and
# the (block_q, block_k) float32 intermediates
_VMEM_BASE_BYTES = 16 * 2**20
# ... and what a chunk's keys and values may take on top, with both of
# the pipeline's buffers counted: a quarter of the 128 MiB a v5e core has
KV_VMEM_BYTES = 32 * 2**20
# A query block goes through a key block in bands of this many rows, every
# band's scores first, then every band's softmax, then every band's
# values: rows do not meet, so the bits are those of the whole block, and
# the scheduler can put one band's softmax (vector unit) beside another's
# product (matrix unit). On a v5e, 16,384 rows x 128 heads of 192/128:
# 146 ms in one band of 512, 130 in two, 96 in four (PERF.md, PR 40).
_BAND_ROWS = 128


def _pick_chunk(skv: int, block_k: int, d: int, dv: int,
                itemsize: int) -> tuple[int, int]:
    """``(key blocks a grid step holds, the VMEM limit the kernel asks
    for)``.

    A row of keys takes ``d`` lanes rounded up to 128 and a row of values
    ``dv`` lanes likewise, ``itemsize`` bytes each, and the pipeline
    keeps two buffers of each operand. The chunk is the largest divisor
    of the head's ``skv // block_k`` key blocks whose two buffers fit in
    ``KV_VMEM_BYTES``: 16,384 bf16 keys of a latent head (192 -> 256
    lanes, values 128) are 16,384 x 384 x 2 B = 12.6 MB, 25.2 MB in two
    buffers, so the whole head is resident and fetched once a head;
    32,768 of them are two chunks of 16,384. A chunk of ONE block is the
    kernel as it was before it walked its own key blocks. The limit is
    ``_VMEM_BASE_BYTES`` plus the chunk's two buffers (Mosaic's default
    of 16 MiB would refuse the first example)."""
    nk = skv // block_k
    block = 2 * block_k * (-(-d // 128) + -(-dv // 128)) * 128 * itemsize
    chunk = max(c for c in range(1, nk + 1)
                if nk % c == 0 and (c == 1 or c * block <= KV_VMEM_BYTES))
    return chunk, _VMEM_BASE_BYTES + chunk * block


def _key_block_bounds(i, live, *, block_q, block_k, seq_q, seq_k, causal,
                      window, xp=jnp):
    """The key blocks query block ``i`` walks, as four block indices
    ``first <= full_lo <= full_hi <= end``: it computes ``[first, end)``
    in ascending order, and of those ``[full_lo, full_hi)`` hold no
    hidden pair and need no mask (every key at or under every query of
    the block, and inside the newest query's window). ``live`` False (a
    query block behind its row's end) empties all of them. ``xp`` is
    ``jnp`` inside the kernel and its index maps and ``numpy`` for
    plain integers (``flash_forward_steps``): one arithmetic for both."""
    nk = seq_k // block_k
    q_first = i * block_q + (seq_k - seq_q)   # the block's oldest query
    q_last = q_first + block_q - 1
    if causal:
        # blocks that start at or under the newest query ...
        end = xp.minimum((xp.maximum(q_last + 1, 0) + block_k - 1)
                         // block_k, nk)
        # ... and those that end at or under the oldest
        full_hi = xp.minimum(xp.maximum(q_first + 1, 0) // block_k, end)
    else:
        end = full_hi = nk
    if window is None:
        first = full_lo = 0
    else:
        # the first block with a key in the oldest query's window, and
        # the first whose every key is in the newest query's
        first = xp.maximum(q_first - window + 1, 0) // block_k
        full_lo = xp.maximum(q_last - window + block_k, 0) // block_k
    if live is not True:
        end = xp.where(live, end, 0)
    full_hi = xp.minimum(full_hi, end)
    full_lo = xp.minimum(full_lo, full_hi)
    first = xp.minimum(first, full_lo)
    return first, full_lo, full_hi, end


class FlashForwardSteps(NamedTuple):
    """What one head of the forward kernel walks: grid ``steps``, key
    ``blocks`` computed, and how many of those build a ``masked``."""
    steps: int
    blocks: int
    masked: int


def flash_forward_steps(sq: int, skv: int, block_q: int, block_k: int,
                        chunk: int, *, causal: bool = True,
                        window: Optional[int] = None,
                        length: Optional[int] = None) -> FlashForwardSteps:
    """The grid steps a head of ``flash_attention_tpu`` walks and the key
    blocks it computes, by the kernel's own arithmetic
    (``_key_block_bounds``). ``chunk`` is ``_pick_chunk``'s: a head walks
    ``(sq / block_q) x (skv / block_k / chunk)`` steps whatever it
    computes (the kernel builds its grid from this), and computes, for
    each query block that starts under ``length`` (None: the bucket),
    the key blocks from its window's far edge to its diagonal. 16,384
    rows in blocks of 512: 32 steps and 528 blocks, 32 of them masked,
    where a grid of single blocks walked 1,024."""
    nq, nk = sq // block_q, skv // block_k
    blocks = masked = 0
    for i in range(nq):
        live = length is None or i * block_q < length
        first, full_lo, full_hi, end = _key_block_bounds(
            i, bool(live), block_q=block_q, block_k=block_k, seq_q=sq,
            seq_k=skv, causal=causal, window=window, xp=np)
        blocks += int(end - first)
        masked += int(end - first) - int(full_hi - full_lo)
    return FlashForwardSteps(nq * (nk // chunk), blocks, masked)


def _flash_fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *,
                      scale, causal, block_q, block_k, seq_q, seq_k,
                      heads, chunk, window=None):
    # len_ref: the rows' lengths, prefetched as scalars, or None (bound
    # by the caller: the kernel then has no such operand)
    i = pl.program_id(1)
    c = pl.program_id(2)

    last = c == pl.num_programs(2) - 1
    # a q block that starts at or behind its row's end walks nothing and
    # is written as what an empty walk leaves: zeros (acc 0 over l
    # 1e-30) and a finite LSE
    live = True if len_ref is None else (
        i * block_q < len_ref[pl.program_id(0) // heads])
    if live is not True:
        @pl.when(last & ~live)
        def _():
            o_ref[0] = jnp.zeros_like(o_ref[0])
            lse_ref[0, 0] = jnp.full_like(
                lse_ref[0, 0], NEG_INF + math.log(1e-30))

    @pl.when((c == 0) & live)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    first, full_lo, full_hi, end = _key_block_bounds(
        i, live, block_q=block_q, block_k=block_k, seq_q=seq_q,
        seq_k=seq_k, causal=causal, window=window)
    q_off = seq_k - seq_q  # causal alignment for self-attn with cache

    sub = min(_BAND_ROWS, block_q)       # rows of a band

    def walk(lo, hi, masked):
        """The chunk's share of key blocks ``[lo, hi)``, ascending."""
        if masked:
            # row - column of a (sub, block_k) tile
            distance = (
                jax.lax.broadcasted_iota(jnp.int32, (sub, block_k), 0)
                - jax.lax.broadcasted_iota(jnp.int32, (sub, block_k), 1))

        def block(j, _):
            # matmuls run in the INPUT dtype (bf16 on the MXU at full
            # rate) with f32 accumulation — an f32 upcast before the dot
            # would halve MXU throughput on the kernel's dominant FLOPs
            rows = pl.ds(pl.multiple_of((j - c * chunk) * block_k, block_k),
                         block_k)
            k = k_ref[0, rows, :]                        # (bk, d)
            v = v_ref[0, rows, :]                        # (bk, dv)
            parts = [pl.ds(r * sub, sub) for r in range(block_q // sub)]
            logits = []
            for r, part in enumerate(parts):
                lg = jax.lax.dot_general(
                    q_ref[0, part, :], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (sub, bk)
                seen = None
                if masked:
                    # a query's distance to a key: the pair is seen from
                    # 0 (the key is the query's own) to under the window
                    ahead = (i * block_q + r * sub + q_off - j * block_k
                             ) + distance
                    seen = ahead >= 0
                    if window is not None:
                        seen &= ahead < window
                    lg = jnp.where(seen, lg, NEG_INF)
                logits.append((lg, seen))
            weights = []
            for part, (lg, seen) in zip(parts, logits):
                # the row statistics stay (rows, 1): a column broadcasts
                # over lanes as it lies, a 1-D vector is laid out anew
                m_prev = m_ref[part, :]
                m_new = jnp.maximum(m_prev, lg.max(axis=-1, keepdims=True))
                p = jnp.exp(lg - m_new)
                if masked:
                    # a row that has seen no key yet has m_new NEG_INF
                    # and exp(0) for every hidden one
                    p = jnp.where(seen, p, 0.0)
                corr = jnp.exp(m_prev - m_new)
                l_ref[part, :] = (l_ref[part, :] * corr
                                  + p.sum(axis=-1, keepdims=True))
                m_ref[part, :] = m_new
                weights.append((p.astype(v.dtype), corr))
            for part, (p, corr) in zip(parts, weights):
                pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                acc_ref[part, :] = acc_ref[part, :] * corr + pv

        jax.lax.fori_loop(jnp.maximum(lo, c * chunk),
                          jnp.minimum(hi, (c + 1) * chunk), block, None)

    if window is not None:
        walk(first, full_lo, True)       # the window's edge
    walk(full_lo, full_hi, False)        # no pair hidden: no mask built
    if causal:
        walk(full_hi, end, True)         # the diagonal

    @pl.when(last & live)
    def _():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # log-sum-exp per query row: the backward kernels rebuild softmax
        # probabilities as exp(s - lse) without the online max recurrence
        lse_ref[0, 0] = (m_ref[:] + jnp.log(l))[:, 0]


def _pick_block(seq: int, target: int) -> Optional[int]:
    """Largest lane-aligned (multiple-of-128) block <= target dividing seq.

    Returns None when no such block exists (e.g. seq=100): Mosaic needs
    lane/sublane-aligned tiles, so the dispatcher must fall back to the
    blockwise jax path rather than hand Pallas an illegal block.
    """
    for b in range(min(target, seq), 127, -128):
        if seq % b == 0 and b % 128 == 0:
            return b
    return None


def flash_attention_tpu(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False,
                        return_lse: bool = False,
                        window: Optional[int] = None,
                        lengths=None):
    """Pallas flash-attention forward (TPU). No autodiff — use
    ``attention`` for a differentiable entry point.

    The grid is ``(batch*heads, query blocks, key chunks)``. A grid step
    holds a chunk of its head's keys and values in VMEM, as many whole
    key blocks as ``_pick_chunk`` finds room for (the whole head for
    every served bucket: fetched once a head, and once for all the query
    heads of a GQA group), and runs a ``fori_loop`` over the chunk's key
    blocks from the first one its query block can see to its diagonal
    block, in ascending order. The blocks strictly under the diagonal and
    strictly inside the window run a body with no mask; the diagonal
    block (and the block on a window's edge) builds the mask. A mask that
    hides nothing is the identity, so the result is the same to the bit
    whatever the chunk. Within a key block the query block's rows go in
    bands of ``_BAND_ROWS`` (scores of every band, then softmax, then
    values), which changes the schedule and not a bit.
    ``flash_forward_steps`` counts the steps and blocks, and the grid is
    built from it.

    ``window`` (with ``causal``): a query sees its ``window`` newest
    keys, its own among them. Key blocks wholly behind the window are
    outside the loop like those above the diagonal, and a chunk none of
    whose blocks is walked is not fetched either (the index map holds at
    the nearest chunk that is). ``None`` is the causal kernel, unnamed;
    the window kernel is named ``WINDOW_KERNEL`` in a trace.

    Values may be narrower or wider than queries and keys (latent
    attention's expanded heads score 192 wide and return 128): the
    output has the values' width, and that kernel is ``LATENT_KERNEL``.

    ``lengths`` (int32 ``[B]``): the first ``lengths[b]`` query rows of
    row ``b`` are tokens, the rest right padding. It reaches the same
    kernel, under the same name, as a prefetched scalar operand. A query
    block that starts at or behind its row's end has an empty loop, is
    written as zeros (a finite LSE), and fetches nothing: the index maps
    hold at the blocks the row's last live query block left. Every other
    query block walks exactly the blocks it walks without ``lengths``,
    so a token's output is the same to the bit; only padding rows that
    share a block with tokens are still computed. ``None`` lowers to the
    kernel with no such operand (training's forward).

    ``interpret=True`` runs the kernel in the Pallas interpreter (works on
    CPU) so the kernel body is testable without TPU hardware."""
    b, sq, hq, d = q.shape
    skv, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    scale = scale if scale is not None else d ** -0.5
    n_rep = hq // hkv
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(skv, block_k)
    if block_q is None or block_k is None:
        raise ValueError(
            f"no lane-aligned block divides seq lengths ({sq}, {skv})")

    # (B, S, H, D) -> (B*H, S, D); kv head index = q head index // n_rep.
    qt = jnp.moveaxis(q, 2, 1).reshape(b * hq, sq, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * hkv, skv, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * hkv, skv, dv)

    if window is not None and not causal:
        raise ValueError("a window needs causal attention")

    chunk, vmem_bytes = _pick_chunk(skv, block_k, d, dv, k.dtype.itemsize)
    nq = sq // block_q
    steps = flash_forward_steps(sq, skv, block_q, block_k, chunk,
                                causal=causal, window=window).steps
    grid = (b * hq, nq, steps // nq)
    shape = dict(block_q=block_q, block_k=block_k, seq_q=sq, seq_k=skv,
                 causal=causal, window=window)

    # the index maps take the prefetched lengths last, where there are any
    def last_live(bh, lens):
        """The row's last query block that holds a token."""
        return jnp.maximum(lens[bh // hq] - 1, 0) // block_q

    def q_index(bh, i, c, *lens):
        if lens:
            i = jnp.minimum(i, last_live(bh, *lens))
        return (bh, i, 0)

    def kv_index(bh, i, c, *lens):
        hb = bh // hq  # batch
        h = bh % hq
        if grid[2] > 1:
            if lens:
                # behind the row's end: the chunk its last live step left
                last = last_live(bh, *lens)
                c = jnp.where(i > last, grid[2] - 1, c)
                i = jnp.minimum(i, last)
            # a chunk that is not walked is not fetched: hold at the
            # nearest one that is
            first, _, _, end = _key_block_bounds(i, True, **shape)
            c = jnp.clip(c, first // chunk,
                         jnp.maximum(end - 1, first) // chunk)
        return (hb * hkv + h // n_rep, c, 0)

    named = {}
    if window is not None:
        named = {"name": WINDOW_KERNEL}
    elif dv != d:
        named = {"name": LATENT_KERNEL}
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, heads=hq,
                               chunk=chunk, **shape)
    spec = dict(
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, chunk * block_k, d), kv_index),
            pl.BlockSpec((1, chunk * block_k, dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, i, c, *_: (bh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, c, *_: (bh, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ])
    if lengths is None:
        kernel, operands = functools.partial(kernel, None), (qt, kt, vt)
    else:
        spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **spec))
        operands = (lengths.astype(jnp.int32), qt, kt, vt)
    out, lse = pl.pallas_call(
        kernel,
        **named,
        **spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * hq, 1, sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
    )(*operands)
    out = jnp.moveaxis(out.reshape(b, hq, sq, dv), 1, 2)
    if return_lse:
        return out, lse.reshape(b, hq, sq)
    return out


# ---------------------------------------------------------------------------
# Pallas TPU flash-attention backward: two kernels sharing the saved LSE
# (softmax is rebuilt as exp(s - lse), no online recurrence).
#   dQ kernel: grid (bh, q_blocks, kv_blocks), kv innermost, dq accumulated
#              in VMEM scratch across the kv loop.
#   dKV kernel: grid (bh, kv_blocks, q_blocks), q innermost, dk/dv
#               accumulated in scratch across the q loop.
# GQA: gradients come out at q-head granularity and are summed over each
# kv-head's group afterwards.
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                         dq_ref, dq_acc, *,
                         scale, causal, block_q, block_k, seq_q, seq_k):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_off = seq_k - seq_q
    run = True
    if causal:
        run = (j * block_k) <= (i * block_q + block_q - 1 + q_off)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + q_off
            ki = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(ki <= qi, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jax.lax.dot_general(do, v,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec_ref[0, 0][:, None])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == nj - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *,
                          scale, causal, block_q, block_k, seq_q, seq_k):
    j = pl.program_id(1)   # kv block
    i = pl.program_id(2)   # q block (innermost)
    ni = pl.num_programs(2)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_off = seq_k - seq_q
    run = True
    if causal:
        # q block entirely above this kv block's diagonal → contributes 0
        run = (i * block_q + block_q - 1 + q_off) >= (j * block_k)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + q_off
            ki = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(ki <= qi, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])           # (bq, bk)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)
        dp = jax.lax.dot_general(do, v,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec_ref[0, 0][:, None])           # (bq, bk)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bk, d)

    @pl.when(i == ni - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def flash_attention_tpu_bwd(q, k, v, out, lse, do, *,
                            causal: bool = True,
                            scale: Optional[float] = None,
                            block_q: int = 512, block_k: int = 512,
                            interpret: bool = False):
    """Flash backward: (dq, dk, dv) from saved output + LSE."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    n_rep = hq // hkv
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(skv, block_k)
    if block_q is None or block_k is None:
        raise ValueError("no lane-aligned block for flash backward")

    qt = jnp.moveaxis(q, 2, 1).reshape(b * hq, sq, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * hkv, skv, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * hkv, skv, d)
    dot = jnp.moveaxis(do, 2, 1).reshape(b * hq, sq, d)
    lset = lse.reshape(b * hq, 1, sq)
    # D_i = rowsum(dO * O): the softmax-jacobian correction vector
    dvec = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)                               # (b, sq, hq)
    dvec = jnp.moveaxis(dvec, 2, 1).reshape(b * hq, 1, sq)

    def kv_index(bh, i, j):
        hb = bh // hq
        h = bh % hq
        return (hb * hkv + h // n_rep, j, 0)

    def kv_index_jfirst(bh, j, i):
        hb = bh // hq
        h = bh % hq
        return (hb * hkv + h // n_rep, j, 0)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_q=sq, seq_k=skv)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * hq, sq // block_q, skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lset, dvec)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_q=sq, seq_k=skv)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * hq, skv // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_k, d), kv_index_jfirst),
            pl.BlockSpec((1, block_k, d), kv_index_jfirst),
            pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, j, i: (bh, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda bh, j, i: (bh, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b * hq, skv, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(kt, vt, qt, dot, lset, dvec)

    dq = jnp.moveaxis(dq.reshape(b, hq, sq, d), 1, 2)
    # GQA: fold each kv head's q-head group gradients together
    dk = dk.reshape(b, hkv, n_rep, skv, d).sum(axis=2)
    dv = dv.reshape(b, hkv, n_rep, skv, d).sum(axis=2)
    dk = jnp.moveaxis(dk, 1, 2).astype(k.dtype)
    dv = jnp.moveaxis(dv, 1, 2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Dispatcher with custom_vjp: pallas forward, pallas backward (blockwise
# fallback off-TPU / for unaligned shapes).
# ---------------------------------------------------------------------------


def _on_tpu(x) -> bool:
    """True when ``x`` lives on (or will be committed to) a TPU device."""
    try:
        devs = getattr(x, "devices", None)
        if callable(devs):
            ds = devs()
            if ds:
                return all(d.platform == "tpu" for d in ds)
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover — tracers without devices
        return jax.default_backend() == "tpu"


def attention_path(sq: int, skv: int, head_dim: int, on_tpu: bool,
                   value_dim: Optional[int] = None) -> str:
    """The implementation ``attention`` picks for these shapes:
    ``"pallas"`` (the flash kernels) or ``"blockwise"`` (plain jax).
    The kernels need a TPU, lane-aligned blocks and a head they can
    tile: queries and keys a multiple of 128 wide, or, where the values
    have a width of their own (``value_dim``: latent attention's 192 and
    128), queries and keys a multiple of 64 and values of 128 (the
    forward kernel takes the two widths; compiled for a v5e in
    tests/test_tpu_compile.py). Any other head goes to ``blockwise``:
    ``LLMEngine`` logs the path its programs take at start.
    The multi-device model path asks here too, with the platform of its
    mesh (models/llama.py). What a program was really compiled to is
    read from its text (``tpu_custom_call``), not from this predicate."""
    if value_dim in (None, head_dim):
        head_ok = head_dim % 128 == 0
    else:
        head_ok = head_dim % 64 == 0 and value_dim % 128 == 0
    if (on_tpu and head_ok
            and _pick_block(sq, 512) is not None
            and _pick_block(skv, 512) is not None):
        return "pallas"
    return "blockwise"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention_tpu(q, k, v, causal, scale):
    return flash_attention_tpu(q, k, v, causal=causal, scale=scale)


def _attn_fwd(q, k, v, causal, scale):
    out, lse = _named(*flash_attention_tpu(
        q, k, v, causal=causal, scale=scale, return_lse=True))
    return out, (q, k, v, out, lse)


def _attn_bwd(causal, scale, res, g):
    q, k, v, out, lse = res
    return flash_attention_tpu_bwd(q, k, v, out, lse, g,
                                   causal=causal, scale=scale)


_attention_tpu.defvjp(_attn_fwd, _attn_bwd)


def attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
              use_pallas: Optional[bool] = None,
              window: Optional[int] = None, lengths=None):
    """Differentiable attention with TPU pallas fast path. ``window``: a
    query sees its ``window`` newest keys (see ``flash_attention_tpu``);
    the window kernel is forward only, which is all serving asks of it
    (the blockwise path differentiates either way). ``lengths`` (int32
    ``[B]``, right padding behind them): the kernel skips the query
    blocks that hold no token and writes them as zeros, forward only
    too; the blockwise path computes every row, as it did. A token's row
    is the same with and without it, what a padding row holds is not."""
    if use_pallas is None:
        use_pallas = attention_path(q.shape[1], k.shape[1], q.shape[-1],
                                    _on_tpu(q), v.shape[-1]) == "pallas"
    if not use_pallas:
        return blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)
    if v.shape[-1] != q.shape[-1] or window is not None or lengths is not None:
        # the forward kernel only (serving): values of their own width,
        # a window, rows that end before the bucket does
        return flash_attention_tpu(q, k, v, causal=causal, scale=scale,
                                   window=window, lengths=lengths)
    return _attention_tpu(q, k, v, causal, scale)


# Below every call into a kernel, import and all: a Mosaic kernel's
# serialized module carries the lines of its call sites, ``attention``'s
# among them, and a line that moves above one is a cold first run of every
# program that holds the kernel.
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"


def _named(out, lse):
    """The flash forward's two results under the names a
    ``jax.checkpoint`` policy can keep them by (models/llama.py
    ``REMAT_POLICIES``); outside a checkpoint a name is the identity. One
    kernel gives both, so a policy that keeps one of the two names still
    runs it again."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)


# ---------------------------------------------------------------------------
# Differential attention (arXiv:2410.05258) as grouped-query attention.
# The heads are paired by parity: pair p scores q[2p] against k[2g] and
# q[2p + 1] against k[2g + 1] (g = p // R, R pairs a pair of KV heads),
# and both softmaxes weigh ONE value row of twice the width, v[2g] beside
# v[2g + 1]. Two neighbouring K (or V) heads side by side are one row of
# 2 hd, which is how they lie in memory already; a query padded with
# zeros on the other head's half scores its own half of that row alone.
# So every attention this repo has (the flash forwards, a window, the
# paged decode's one softmax over segments) serves a differential layer
# as a GQA layer of h heads over kvh / 2 rows of 2 hd: the cache holds
# what any GQA layer's holds, and the price is the zeros' half of the
# score product.
# ---------------------------------------------------------------------------


def differential_rows(x):
    """k or v [..., kvh, hd] -> [..., kvh / 2, 2 hd]: neighbouring heads
    side by side (no element moves)."""
    return x.reshape(*x.shape[:-2], x.shape[-2] // 2, 2 * x.shape[-1])


def differential_queries(q, kv_heads: int):
    """q [..., h, hd] -> [..., h, 2 hd], in the order grouped-query
    attention over ``kv_heads / 2`` rows wants them: for a row g its R
    first queries (even heads, zeros behind them), then its R second
    queries (odd heads, zeros before them)."""
    h, hd = q.shape[-2:]
    G = kv_heads // 2
    q = q.reshape(*q.shape[:-2], G, h // kv_heads, 2, hd)
    zero = jnp.zeros_like(q[..., 0, :])
    both = jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                      jnp.concatenate([zero, q[..., 1, :]], -1)], -3)
    return both.reshape(*both.shape[:-4], h, 2 * hd)


def differential_lambda(lp, lam0: float):
    """exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_0, float32 scalar."""
    f = lambda name: lp[name].astype(jnp.float32)  # noqa: E731
    return (jnp.exp(jnp.sum(f("lam_q1") * f("lam_k1")))
            - jnp.exp(jnp.sum(f("lam_q2") * f("lam_k2"))) + lam0)


def differential_combine(o, lam, kv_heads: int):
    """What attention returned for ``differential_queries``' heads [...,
    h, 2 hd] -> the pairs' differences float32 [..., h / 2, 2 hd]:
    first minus ``lam`` times second."""
    h = o.shape[-2]
    o = o.astype(jnp.float32).reshape(
        *o.shape[:-2], kv_heads // 2, 2, h // kv_heads, o.shape[-1])
    diff = o[..., 0, :, :] - lam * o[..., 1, :, :]
    return diff.reshape(*diff.shape[:-3], h // 2, diff.shape[-1])
