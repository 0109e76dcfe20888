"""Latent attention (MLA, DeepSeek-V2) over the ONE row a token keeps.

A latent layer's cache row is ``[c_kv ; k_r]``: the compressed keys and
values (``rank`` wide, 512) and the rotary key every head shares (64),
576 values, kept in a slot a whole number of lanes wide (640, the rest
zero: ``LlamaConfig.latent_row``, which says why).
Two forms of the same attention read it:

  * expanded (whole-prompt prefill): ``k_nope`` and ``v`` of every head
    are multiplied out of ``c_kv`` (``expand``) and the flash kernel
    scores 192 wide and returns 128 (``ops/attention.py``);
  * absorbed (everything that reads the cache): ``W_UK`` moves onto the
    query (``absorb_query``: a head's query becomes ``rank`` + rope wide)
    so that the scores are one product with the rows as they are stored,
    the probabilities weigh the rows' first ``rank`` columns, and ``W_UV``
    is applied to that once a query (``expand_output``). No key or value
    of a head is ever written, and no absorbed copy of a weight is kept:
    an int8 ``W_UK``'s per-column scales multiply the query first.

``decode_attention`` is the absorbed form for one query a slot over the
slot's OWN pages in the pool. A position costs 128 heads x (576 + 512)
x 2 operations against 1,152 bytes of values read, the v5e's own ratio,
so what a step reads and multiplies has to follow each slot's context:
on a TPU a Pallas kernel (``DECODE_KERNEL`` in a trace) walks the
slot's block table with the heads as the rows of each product and stops
at the slot's length; elsewhere the table's rectangle is gathered and
masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .quant import is_quantized, weight_einsum

NEG_INF = -1e30
# the decode kernel's name in a compiled program and a device trace
DECODE_KERNEL = "rt_mla_decode"
# pages a grid step of the kernel reads (512 keys at a page of 64)
_STEP_PAGES = 8


def expand(rows, w_uk, w_uv, n_heads: int, rope: int):
    """Cache rows [B, S, >= rank + rope] -> (k [B, S, h, nope + rope],
    v [B, S, h, v_dim]): every head's keys and values."""
    rank = w_uv["q"].shape[0] if is_quantized(w_uv) else w_uv.shape[0]
    c_kv, k_r = rows[..., :rank], rows[..., rank:rank + rope]
    k_nope = weight_einsum("bsc,chk->bshk", c_kv, w_uk)
    v = weight_einsum("bsc,chk->bshk", c_kv, w_uv)
    k_r = jnp.broadcast_to(k_r[:, :, None, :],
                           (*k_r.shape[:2], n_heads, k_r.shape[-1]))
    return jnp.concatenate([k_nope, k_r.astype(k_nope.dtype)], -1), v


def absorb_query(q, w_uk, width: int):
    """q [..., h, nope + rope] -> [..., h, width]: ``q_nope W_UK^T``
    beside the rotary part (then zeros up to the cache row's width), to
    score cache rows as they are stored."""
    nope = (w_uk["q"] if is_quantized(w_uk) else w_uk).shape[-1]
    q_nope, q_r = q[..., :nope], q[..., nope:]
    if is_quantized(w_uk):
        # the scale of a nope column multiplies the query before the
        # product: the stored int8 matrix is read as it is
        q_nope = (q_nope.astype(jnp.float32) * w_uk["s"]).astype(q.dtype)
        w_uk = w_uk["q"].astype(q.dtype)
    q_lat = jnp.einsum("...hk,chk->...hc", q_nope, w_uk)
    pad = jnp.zeros((*q.shape[:-1], width - q_lat.shape[-1] - q_r.shape[-1]),
                    q.dtype)
    return jnp.concatenate([q_lat, q_r, pad], -1)


def expand_output(o_lat, w_uv):
    """o_lat [..., h, rank] (the probabilities' sum over ``c_kv``) ->
    [..., h, v_dim]."""
    return weight_einsum("...hc,chk->...hk", o_lat, w_uv)


def attend_rows(ql, scale: float, rank: int, *segments):
    """Absorbed attention in plain jax over rows that lie in segments,
    one softmax over all. ql [B, S, h, row]; a segment: (rows
    [B, T, row], mask broadcastable to [B, S, T]). Returns
    o_lat float32 [B, S, h, rank]."""
    s = jnp.concatenate([
        jnp.where(mask[:, :, None, :], jnp.einsum(
            "bshc,btc->bsht", ql, rows,
            preferred_element_type=jnp.float32) * scale, -jnp.inf)
        for rows, mask in segments], axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(segments[0][0].dtype)
    outs, at = [], 0
    for rows, _ in segments:
        end = at + rows.shape[1]
        outs.append(jnp.einsum("bsht,btc->bshc", p[..., at:end],
                               rows[..., :rank],
                               preferred_element_type=jnp.float32))
        at = end
    return sum(outs[1:], outs[0])


def _decode_kernel(layer, tables, lengths, q_ref, *refs, scale, rank,
                   page, pages):
    from jax.experimental import pallas as pl

    page_refs, (o_ref, lse_ref, acc, m_ref, l_ref) = refs[:pages], \
        refs[pages:]
    b, j = pl.program_id(0), pl.program_id(1)
    keys = pages * page

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lengths[b]

    @pl.when(j * keys < length)
    def _():
        rows = jnp.concatenate([r[...] for r in page_refs], axis=0)
        s = jax.lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (h, keys)
        at = j * keys + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(at < length, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(at < length, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
        m_ref[:, 0] = m_new
        acc[...] = acc[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[...] = acc[...] / l[:, None]
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(l)


def _step_pages(n_pages: int) -> int:
    pages = _STEP_PAGES
    while n_pages % pages:
        pages //= 2
    return pages


def decode_attention_tpu(ql, pool, layer, tables, lengths, *, scale: float,
                         rank: int, interpret: bool = False):
    """The kernel: grid (slot, step); a step reads ``_STEP_PAGES`` of
    the slot's pages straight from the pool (the page's index comes from
    the block table, prefetched as scalars; the pool is handed over once
    a page of the step) and keeps an online softmax over them in VMEM.
    A step past the slot's length computes nothing and asks for the
    block it already has, so nothing is fetched for it either."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, h, width = ql.shape
    page = pool.shape[2]
    pages = _step_pages(tables.shape[1])

    def page_index(g):
        def index(b, j, layer, tables, lengths):
            last = jnp.maximum(lengths[b] - 1, 0) // page
            return (layer[0], tables[b, jnp.minimum(j * pages + g, last)],
                    0, 0)
        return index

    o, lse = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, rank=rank,
                          page=page, pages=pages),
        out_shape=[jax.ShapeDtypeStruct((B, h, rank), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, h), jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((None, h, width),
                                   lambda b, j, *_: (b, 0, 0))]
            + [pl.BlockSpec((None, None, page, width), page_index(g))
               for g in range(pages)],
            out_specs=[
                pl.BlockSpec((None, h, rank), lambda b, j, *_: (b, 0, 0)),
                pl.BlockSpec((1, 1, h), lambda b, j, *_: (b, 0, 0))],
            grid=(B, tables.shape[1] // pages),
            scratch_shapes=[pltpu.VMEM((h, rank), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=DECODE_KERNEL,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tables, lengths, ql,
      *([pool] * pages))
    return o, lse[:, 0]


def decode_attention_xla(ql, pool, layer, tables, lengths, *, scale: float,
                         rank: int):
    """The same in plain jax (the CPU's path, and the kernel's oracle):
    the table's rectangle of the layer's pages gathered, every slot
    scoring its own row under its length."""
    B, n = tables.shape
    page = pool.shape[2]
    rows = jnp.take(jax.lax.dynamic_index_in_dim(pool, layer, 0, False),
                    tables, axis=0).reshape(B, n * page, -1)
    s = jnp.einsum("bhc,btc->bht", ql, rows,
                   preferred_element_type=jnp.float32) * scale
    seen = (jnp.arange(n * page)[None, :] < lengths[:, None])[:, None, :]
    s = jnp.where(seen, s, NEG_INF)
    m = s.max(-1)
    p = jnp.where(seen, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.maximum(p.sum(-1), 1e-30)
    o = jnp.einsum("bht,btc->bhc", p.astype(rows.dtype), rows[..., :rank],
                   preferred_element_type=jnp.float32)
    return o / l[..., None], m + jnp.log(l)


def decode_attention(ql, pool, layer, tables, lengths, *, scale: float,
                     rank: int):
    """One absorbed query a slot over the slot's own cached rows.

    ql [B, h, row]; pool [L, P, page, row], the whole
    stack, and ``layer`` (a traced index) picks its layer in the page's
    address: no layer is sliced out; tables int32 [B, n], a slot's pages
    in order (unused entries 0: the reserved page, never scored);
    lengths int32 [B], the positions a slot has cached (0: nothing, and
    the slot's output is 0 with a log-sum-exp of about -1e30).

    Returns (o_lat float32 [B, h, rank], the softmax's output over the
    cached rows alone; lse float32 [B, h], its log-sum-exp, by which the
    caller joins it with rows the cache does not hold yet)."""
    def tpu(ql, pool, layer, tables, lengths):
        return decode_attention_tpu(ql, pool, layer, tables, lengths,
                                    scale=scale, rank=rank)

    def xla(ql, pool, layer, tables, lengths):
        return decode_attention_xla(ql, pool, layer, tables, lengths,
                                    scale=scale, rank=rank)

    return jax.lax.platform_dependent(ql, pool, layer, tables, lengths,
                                      tpu=tpu, default=xla)


def join_new_rows(o_old, lse_old, ql, rows, mask, *, scale: float,
                  rank: int):
    """One softmax over (what ``decode_attention`` saw; ``rows``
    [B, K, row] the cache does not hold yet, under ``mask``
    [B or 1, K]). Returns o_lat float32 [B, h, rank]."""
    s = jnp.einsum("bhc,bkc->bhk", ql, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m = jnp.maximum(lse_old, s.max(-1))
    w_old = jnp.exp(lse_old - m)
    p = jnp.where(mask[:, None, :], jnp.exp(s - m[..., None]), 0.0)
    new = jnp.einsum("bhk,bkc->bhc", p, rows[..., :rank].astype(jnp.float32))
    return ((w_old[..., None] * o_old + new)
            / jnp.maximum(w_old + p.sum(-1), 1e-30)[..., None])
