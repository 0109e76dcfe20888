"""Paged-attention decode kernel (Pallas/TPU).

The serving engine's decode attends over a paged KV cache: each
sequence's context lives in non-contiguous pages indexed by a block
table (ray_tpu/llm/cache.py). The XLA fallback gathers the pages into a
contiguous [B, S, kvh, hd] copy per burst (`jnp.take`) — at long
contexts that copy dominates HBM traffic. This kernel instead streams
pages straight from the cache pool guided by a scalar-prefetched block
table (the grid's page dimension DMAs exactly the pages each sequence
owns), with flash-style online softmax — no materialized gather.

Reference analog: the vLLM paged-attention CUDA kernels behind
ray.llm's vllm_engine (SURVEY §2.4) — rebuilt Pallas-native, since the
reference delegates all device work to vLLM.

Layout contract (matches llm/cache.py):
  cache_k/cache_v (one layer): [P, page, kvh, hd]
  block_tables:                [B, max_pages] int32 (page 0 = dump page)
  q:                           [B, kvh, rep, hd]   (rep = heads per kv head)
  new_k/new_v:                 [B, K, kvh, hd]     burst scratch (in-VMEM tail)
  ctx_len:                     [B] int32           valid OLD positions
  new_len:                     [B] int32           valid NEW (burst) positions

Grid: (B, kvh, n_pages + 1). Page steps accumulate (m, l, acc) in VMEM
scratch; the final step folds in the burst tail and writes the
normalized output. Masking: page p covers absolute positions
[p*page_size, ...); rows >= ctx_len[b] are masked; the dump page
(table entry 0 for unused slots) masks out naturally the same way.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(ctx_len_ref, new_len_ref, bt_ref,  # scalar prefetch
            q_ref, k_page_ref, v_page_ref, new_k_ref, new_v_ref,
            o_ref,
            m_ref, l_ref, acc_ref,
            *, page_size: int, n_pages: int, scale: float, kvh: int):
    """Grid (B, n_pages + 1); blocks carry whole pages [page, kvh, hd]
    (TPU tiling: a block's trailing dims must equal the array's or tile
    by (8, 128) — the head dim therefore stays INSIDE the block and the
    kernel unrolls over the static kvh). Scratch rows are the kvh*rep
    flattened query heads."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    rep = q_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def online_update(g, k, v, pos_mask):
        """One flash block for kv head g: k/v [S, hd] f32, mask [S]."""
        rows = slice(g * rep, (g + 1) * rep)
        q = q_ref[0, g].astype(jnp.float32) * scale   # [rep, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [rep, S]
        s = jnp.where(pos_mask[None, :], s, _NEG_INF)
        m_prev = m_ref[rows]                          # [rep, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # masked entries must contribute EXACTLY zero: when a whole
        # block is masked, m_new == _NEG_INF and exp(s - m_new) would be
        # exp(0) = 1 per masked entry, poisoning l and acc
        p_blk = jnp.where(pos_mask[None, :],
                          jnp.exp(s - m_new), 0.0)    # [rep, S]
        l_ref[rows] = l_ref[rows] * alpha + p_blk.sum(-1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
            p_blk, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [rep, hd]
        m_ref[rows] = m_new

    @pl.when(p < n_pages)
    def _page_step():
        base = p * page_size
        pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)[:, 0]
        mask = pos < ctx_len_ref[b]
        for g in range(kvh):  # static unroll over kv heads
            k = k_page_ref[0, :, g].astype(jnp.float32)   # [page, hd]
            v = v_page_ref[0, :, g].astype(jnp.float32)
            online_update(g, k, v, mask)

    @pl.when(p == n_pages)
    def _tail_and_write():
        kk = new_k_ref.shape[1]
        pos = jax.lax.broadcasted_iota(jnp.int32, (kk, 1), 0)[:, 0]
        mask = pos < new_len_ref[b]
        for g in range(kvh):
            k = new_k_ref[0, :, g].astype(jnp.float32)    # [K, hd]
            v = new_v_ref[0, :, g].astype(jnp.float32)
            online_update(g, k, v, mask)
        l = jnp.maximum(l_ref[...], 1e-20)
        out = (acc_ref[...] / l)                      # [kvh*rep, hd]
        o_ref[0] = out.reshape(kvh, rep, out.shape[-1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_decode_attention(q, cache_k, cache_v, new_k, new_v,
                           block_tables, ctx_len, new_len, *,
                           page_size: int,
                           interpret: Optional[bool] = None):
    """Decode attention over paged KV + an in-flight burst tail.

    q [B, kvh, rep, hd]; cache_k/cache_v [P, page, kvh, hd];
    new_k/new_v [B, K, kvh, hd]; block_tables [B, n_pages] int32;
    ctx_len/new_len [B] int32. Returns o [B, kvh, rep, hd] (q dtype).

    ``interpret=None`` runs the kernel body in the Pallas interpreter on
    the CPU backend only (how the CPU tests run it); an explicit value is
    obeyed, so a compile for a described chip gets the real kernel.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, kvh, rep, hd = q.shape
    n_pages = block_tables.shape[1]
    K = new_k.shape[1]
    grid = (B, n_pages + 1)

    def q_map(b, p, ctx, nl, bt):
        return (b, 0, 0, 0)

    def page_map(b, p, ctx, nl, bt):
        # last (tail) step re-reads an arbitrary valid page; masked out
        return (bt[b, jnp.minimum(p, n_pages - 1)], 0, 0, 0)

    def new_map(b, p, ctx, nl, bt):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, kvh, rep, hd), q_map),
            pl.BlockSpec((1, page_size, kvh, hd), page_map),
            pl.BlockSpec((1, page_size, kvh, hd), page_map),
            pl.BlockSpec((1, K, kvh, hd), new_map),
            pl.BlockSpec((1, K, kvh, hd), new_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, rep, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((kvh * rep, 1), jnp.float32),   # m
            pltpu.VMEM((kvh * rep, 1), jnp.float32),   # l
            pltpu.VMEM((kvh * rep, hd), jnp.float32),  # acc
        ],
    )
    kernel = functools.partial(
        _kernel, page_size=page_size, n_pages=n_pages,
        scale=float(hd) ** -0.5, kvh=kvh)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, rep, hd), q.dtype),
        interpret=interpret,
        # the batch dim is parallel; the page dim carries the softmax
        # state and must run sequentially
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(ctx_len, new_len, block_tables, q, cache_k, cache_v, new_k, new_v)


def paged_decode_attention_reference(q, cache_k, cache_v, new_k, new_v,
                                     block_tables, ctx_len, new_len):
    """Naive oracle: gather pages, mask, softmax (the XLA-path shape)."""
    B, kvh, rep, hd = q.shape
    page = cache_k.shape[1]
    Sold = block_tables.shape[1] * page
    ok = jnp.take(cache_k, block_tables, axis=0).reshape(B, Sold, kvh, hd)
    ov = jnp.take(cache_v, block_tables, axis=0).reshape(B, Sold, kvh, hd)
    scale = hd ** -0.5
    s_old = jnp.einsum("bgrd,bsgd->bgrs", q.astype(jnp.float32),
                       ok.astype(jnp.float32)) * scale
    s_new = jnp.einsum("bgrd,bkgd->bgrk", q.astype(jnp.float32),
                       new_k.astype(jnp.float32)) * scale
    old_mask = jnp.arange(Sold)[None, :] < ctx_len[:, None]
    new_mask = jnp.arange(new_k.shape[1])[None, :] < new_len[:, None]
    s_old = jnp.where(old_mask[:, None, None, :], s_old, _NEG_INF)
    s_new = jnp.where(new_mask[:, None, None, :], s_new, _NEG_INF)
    s = jnp.concatenate([s_old, s_new], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    o = (jnp.einsum("bgrs,bsgd->bgrd", p[..., :Sold],
                    ov.astype(jnp.float32))
         + jnp.einsum("bgrk,bkgd->bgrd", p[..., Sold:],
                      new_v.astype(jnp.float32)))
    return o.astype(q.dtype)
