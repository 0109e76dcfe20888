"""State layers (``LlamaConfig.linear_heads``: a ``layer_pattern`` that
lists every layer, of the kinds "linear" and "block_nope", each with a
stack of weights of its own: ``params["linear_layers"]``,
``params["layers"]``; ``runner._layers`` runs such a pattern a layer at a
time, each with its weights and its state by its place IN ITS KIND).

A linear layer keeps no key: its memory is a float32 state a slot
(``ops/linear_attention.py``), a pool ``KVCache.s`` [linear layers, slots,
heads, hd, hd] that every program takes as the donated keyword
``cache_s`` and returns last: ``prefill`` runs the chunked form from
zeros and puts the end state at the slot's place (``slots``),
``prefill_chunk`` carries the slot's state through the pool from chunk to
chunk, ``decode_burst`` keeps the pool in the step loop's carry and every
step updates the live slots' states in place. A block layer's K and V
pools are page MATRICES [layers, pages, page x kv_heads, hd]
(``_pair_rows``: a row's "position" is ``position * kv_heads + head``, so
the loops of slices serve them as they are), and beside them
``KVCache.c`` (``cache_c``) holds the float32 sum of the keys of every
``block_stride`` positions, of which a compressed key is the mean of two
neighbours: a query below ``block_dense_len`` attends over every visible
key (the flash forward), one above over the tokens of the blocks it
chooses (``sparse.block_attend``); a decode step scores the sums of its
slot's pages (gathered once a burst) and the burst's own keys, lists the
pages chosen, a row a (slot, KV head), and reads those pages and no other
where they lie (``sparse.block_decode_attention``). There is no
``verify_step``: a rejected window would have to roll a state back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...models.llama import linear
from ...ops import attention, linear_attention, rms_norm
from ...ops import sparse_attention as sparse
from .. import runner
from ..cache import KVCache
from . import Burst
from .latent import (LOWEST_BUCKET, _write_latent_pages,  # noqa: F401
                     _write_slices, one_prompt)
from .paged import _write_rows

OWN_PAGES = True
# slots whose state was zeroed at an admission (the engine moves it);
# bytes of state the bursts' steps read and wrote (every decoding slot's,
# once each a step); queries, prefilled and decoded, below
# ``block_dense_len`` (every visible key attended); for the rest the
# blocks a query could see and scored and the blocks it attended over (a
# block layer and KV head); the pages of K (and as many of V) the bursts'
# steps read (a block layer and KV head)
SLOT_RESET = "state_slots_reset"
COUNTERS = (SLOT_RESET, "state_bytes_step", "dense_queries",
            "scored_blocks", "chosen_blocks", "block_decode_pages")


def count(cfg, counters, page_size, start, end, decode) -> None:
    """The queries at positions [start, end) of one sequence: a query at
    t below ``block_dense_len`` attends over every visible key; another
    sees t // block + 1 blocks and attends over at most ``block_topk``,
    in every block layer and KV head. A burst's step reads and writes
    the slot's state."""
    each = cfg.n_kv_layers * cfg.n_kv_heads
    dense = max(0, min(end, cfg.block_dense_len) - start)
    counters["dense_queries"] += dense
    seen = np.arange(start + dense, end) // cfg.block_size + 1
    counters["scored_blocks"] += each * int(seen.sum())
    counters["chosen_blocks"] += each * int(
        np.minimum(seen, cfg.block_topk).sum())
    if decode:
        # the pages that hold the slot's cached positions: all of them
        # below dense_len, then at most block_topk
        cached = -(-start // cfg.block_size)
        counters["block_decode_pages"] += each * (
            dense * cached + (end - start - dense) * min(
                cached, cfg.block_topk))
        counters["state_bytes_step"] += (
            2 * (end - start) * cfg.state_bytes_per_slot)


def attention_paths(cfg, prefill: str, on_tpu: bool):
    return {
        "prefill": f"linear layers: "
        f"{'pallas rt_linear_prefill' if on_tpu else 'xla'} (chunked "
        f"form); block layers: {prefill} below block_dense_len, then "
        f"{'pallas rt_block_score, rt_sparse_select, flash_block_sparse_fwd' if on_tpu else 'xla'}"
        " (the chosen blocks of the prompt's rows)",
        "prefill_chunk": "the same, over the gathered pages; the "
        "state carried through its pool",
        "verify_step": "refused (a state cannot be rolled back)",
        "decode_burst": (
            "pallas rt_linear_decode (each live slot's state, in "
            "place), rt_block_score, rt_sparse_attend_decode (the "
            "chosen pages where they lie)" if on_tpu else "xla")}


def refuses(cfg):
    """What a state a slot beside the pages cannot do yet (ROADMAP M3)."""
    return f"state layers (linear_heads={cfg.linear_heads})", {
        "enable_prefix_caching":
            "a cached page says nothing of a linear layer's state at its "
            "end (snapshots of the state at page boundaries: ROADMAP M3)",
        "lora_rank":
            "adapters ride ONE scan over layers of one stack, and the "
            "linear and block layers have a stack each",
        "speculation":
            "verify_step would have to roll a slot's state back behind a "
            "rejected window, and the state keeps no token apart",
        "kv_transfer":
            "a KV payload is a K and a V stack of pages for all layers, "
            "and the linear layers' memory is a state a slot that no page "
            "holds"}


def init_pools(cfg, num_pages, page_size: int, dtype, slots: int) -> KVCache:
    if page_size != cfg.block_size:
        raise ValueError(
            f"page_size={page_size} with block_size={cfg.block_size}: "
            f"a block that is chosen is a page that is read, so they "
            f"are equal")
    if not isinstance(num_pages, int) or slots < 1:
        raise ValueError("a configuration with linear layers has one "
                         "layer group of pages, and a state a slot")
    L, hd = cfg.n_kv_layers, cfg.head_dim
    # a page's (position, KV head) rows as ONE matrix, the form
    # ``rt_sparse_attend_decode`` multiplies: with 2 KV heads a
    # [.., page, 2, hd] pool is tiled (2, 128) and its reshape to
    # rows is a copy of the whole pool in every layer of every step
    # (read from a compile for a v5e, PR 46)
    shape = (L, num_pages, page_size * cfg.n_kv_heads, hd)
    return KVCache(
        jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
        c=jnp.zeros((L, num_pages, page_size // cfg.block_stride,
                     cfg.n_kv_heads, hd), jnp.float32),
        s=jnp.zeros((cfg.n_linear_layers, slots, cfg.linear_heads,
                     hd, hd), jnp.float32))


def _pair_rows(rows):
    """K or V rows [..., S, kvh, hd] as the rows of the page matrices:
    [..., S * kvh, hd], a (position, KV head) pair a row,
    position-major. Such a row's "position" is ``position * kvh + head``
    and a page holds ``page_size * kvh`` of them, so the loops of slices
    serve them as they are."""
    return rows.reshape(*rows.shape[:-3], -1, rows.shape[-1])


def _pair_positions(positions, valid, kvh: int):
    """(positions, valid) [B, S] of tokens -> those of their (position,
    KV head) rows [B, S * kvh] (``_pair_rows``)."""
    at = positions[..., None] * kvh + jnp.arange(kvh)
    ok = jnp.broadcast_to(jnp.broadcast_to(valid, positions.shape)[..., None],
                          at.shape)
    return at.reshape(*positions.shape[:-1], -1), ok.reshape(
        *positions.shape[:-1], -1)


def _add_to_sums(pool, rows, table, positions, written, stride: int):
    """A burst's keys added to the sums of the strides they fall in.
    pool float32 [L, P, per, kvh, hd] (``KVCache.c``); rows [L, B, K,
    kvh, hd], the burst's keys, row r of slot b at position
    ``positions[b] + r``; written bool [B, K]. A stride's sum is of the
    positions that are cached: one that begins at or behind the slot's
    old length starts from nothing, whatever the page held before."""
    K, per = written.shape[1], pool.shape[2]
    touched = (K - 1) // stride + 2
    m = (positions // stride)[:, None] + jnp.arange(touched)[None, :]
    at = positions[:, None] + jnp.arange(K)[None, :]
    mine = ((at // stride)[:, :, None] == m[:, None, :]) & written[..., None]
    add = jnp.einsum("bkn,lbkgd->lbngd", mine.astype(jnp.float32),
                     rows.astype(jnp.float32), precision="highest")
    page = jnp.take_along_axis(
        table, jnp.clip(m // per, 0, table.shape[1] - 1), axis=1)
    old = jnp.where((m * stride < positions[:, None])[None, ..., None, None],
                    pool[:, page, m % per], 0.0)
    return _write_rows((pool,), (old + add,), table, m, mine.any(1))[0]


def heads(h, lp, lr, state, *, cfg, kind, attend, **how):
    """``state``: a linear layer's place among the linear layers; a
    block layer's slice of the program's state."""
    q, k, v = runner._heads(h, lp, lr, cfg=cfg, kind=kind, **how)
    # the closure opens rt.attn.linear, or rt.attn.block.score,
    # rt.attn.select and rt.attn.sparse
    o, kept = attend(kind, q, k, v, state)
    if linear(kind):
        # the output norm over all heads
        o = rms_norm(o.reshape(*o.shape[:2], -1), lp["o_norm"],
                     cfg.norm_eps).reshape(o.shape)
    return runner._gated(o, h, lp), kept


def _by_kind(of_linear, of_block):
    """``attend(kind, q, k, v, state)``: the layer's kind decides, once."""
    return lambda kind, *a: (of_linear if linear(kind) else of_block)(*a)


def prefill(cfg, cache, block_tables, prompt_lens, slots, pos_grid, valid):
    one_prompt(pos_grid.shape[0])
    S, sizes = pos_grid.shape[1], cfg.block_sizes
    # the state pool, as the linear layers leave it one after the other
    box = {"s": cache.s}

    def of_linear(q, k, v, place):
        # from a zero state, whatever the slot held: the state behind the
        # prompt's last token goes to the slot's place in the pool
        o, end = linear_attention.prefill(
            q, k, v, cfg.linear_decay, prompt_lens, scale=cfg.softmax_scale)
        box["s"] = jax.lax.dynamic_update_slice(
            box["s"], end[None], (place, slots[0], 0, 0, 0))
        return o, None

    def of_block(q, k, v, _):
        # queries below dense_len (all of them in a bucket that short)
        # attend over every visible key: the flash forward; the rest
        # over the blocks they choose
        sums = sparse.stride_sums(k, valid, sizes.stride)
        D = sizes.dense_len
        o = attention(q[:, :D], k[:, :D], v[:, :D], causal=True,
                      lengths=jnp.minimum(prompt_lens, D))
        if S > D:
            o = jnp.concatenate([o, sparse.block_attend(
                q[0, D:], k[0], v[0], sums[0],
                jnp.where(valid[0, D:], pos_grid[0, D:], -1), sizes,
                scale=cfg.softmax_scale)[None].astype(o.dtype)], 1)
        return o, (k.astype(cache.k.dtype), v.astype(cache.v.dtype), sums)

    def write(rows):
        # the ONE prompt's K and V rows a page at a time, and the
        # strides' sums likewise (a page of them is ``per`` rows)
        k, v, sums = rows[0]
        cache_c, = _write_latent_pages(
            cache.c, sums, block_tables, -(-prompt_lens // sizes.stride))
        (cache_k,), (cache_v,) = (_write_latent_pages(
            c, _pair_rows(r), block_tables, prompt_lens * cfg.n_kv_heads)
            for c, r in ((cache.k, k), (cache.v, v)))
        return KVCache(cache_k, cache_v, None, cache_c, box["s"])

    return _by_kind(of_linear, of_block), write


def prefill_chunk(cfg, cache, block_tables, start_pos, chunk_len, slots,
                  pos_grid, valid):
    box, sizes = {"s": cache.s}, cfg.block_sizes
    # a page's rows are (position, KV head) pairs
    page_size = cache.k.shape[2] // cfg.n_kv_heads
    B, Spast = pos_grid.shape[0], block_tables.shape[1] * page_size

    def of_linear(q, k, v, place):
        # the slot's state (zeroed when the request was admitted)
        # carried from chunk to chunk through the pool
        at = (place, slots[0], 0, 0, 0)
        o, end = linear_attention.prefill(
            q, k, v, cfg.linear_decay, chunk_len.reshape(1),
            jax.lax.dynamic_slice(
                box["s"], at, (1, 1, *box["s"].shape[2:]))[0],
            scale=cfg.softmax_scale)
        box["s"] = jax.lax.dynamic_update_slice(box["s"], end[None], at)
        return o, None

    def of_block(q, k, v, pools):
        # the chunk's rows into the pages, then the span through the
        # pages, the chunk's own rows among it; the strides' sums of the
        # whole span from its keys, and back into their pool, which
        # rides the layer scan as a third pool of the block layers
        at, ok = _pair_positions(pos_grid, valid, cfg.n_kv_heads)
        pk, pv = (_write_slices(pool, _pair_rows(new), block_tables, at,
                                ok, 1)
                  for pool, new in zip(pools[:2], (k, v)))
        sk, sv = (runner._take_span(pool, block_tables).reshape(
            B, Spast, *k.shape[2:]) for pool in (pk, pv))
        cached = jnp.arange(Spast)[None, :] < start_pos + chunk_len
        sums = sparse.stride_sums(sk, cached, sizes.stride)
        strides = jnp.arange(Spast // sizes.stride)[None, :]
        pc, = _write_rows(
            pools[2:], (sums,), block_tables, strides,
            strides * sizes.stride < start_pos + chunk_len)
        o = sparse.block_attend(
            q[0], sk[0], sv[0], sums[0],
            jnp.where(valid[0], pos_grid[0], -1), sizes,
            scale=cfg.softmax_scale)[None]
        return o, (pk, pv, pc)

    def done(pools):
        pk, pv, pc = pools[0]
        return KVCache(pk, pv, None, pc, box["s"])

    return (((cache.k, cache.v, cache.c),), _by_kind(of_linear, of_block),
            done)


def verify_step(cfg, *_):
    raise ValueError(
        "verify_step is not written for linear layers: a window that "
        "is rejected would have to roll a slot's state back, and the "
        "state keeps no token apart (LLMEngine refuses speculation "
        "with them)")


def decode_burst(cfg, cache, block_tables, gather, positions, active,
                 K: int) -> Burst:
    """``gather``: as a latent burst's."""
    B, sizes = positions.shape[0], cfg.block_sizes
    # no K or V is copied; the strides' sums of each slot's own pages
    # are, ONCE a burst for all block layers (a sixteenth of K): [L, B,
    # n * per, kvh, hd]
    span = block_tables if gather is None else gather
    L, P = cache.c.shape[:2]
    sums = jnp.take(
        cache.c.reshape(L * P, *cache.c.shape[2:]),
        jnp.arange(L)[:, None, None] * P + span[None],
        axis=0).reshape(L, B, -1, *cache.c.shape[3:])
    # a stride that holds no cached position holds what the page held
    # before: nothing of this sequence
    held = (jnp.arange(sums.shape[2])[None, :] * sizes.stride
            < positions[:, None])
    old = ((jnp.arange(L, dtype=jnp.int32), jnp.where(
        held[None, ..., None, None], sums, 0.0)),)
    scratch = (tuple(jnp.zeros((L, B, K, cfg.n_kv_heads, cfg.head_dim),
                               c.dtype) for c in (cache.k, cache.v)),)
    # the slots that decode, in the order the state kernel walks them:
    # the same for every layer and step
    live_slots = linear_attention.live_order(active)
    box = {}

    def step(i, new_mask, carry):
        box["s"] = carry
        def of_linear(q, k, v, place):
            # every live slot's state of the layer advanced by the
            # step's token, in place in the pool
            o, box["s"] = linear_attention.decode_step(
                q[:, 0], k[:, 0], v[:, 0], box["s"], place, active,
                cfg.linear_decay, scale=cfg.softmax_scale,
                order=live_slots)
            return o[:, None], None

        def of_block(q, k, v, state):
            # the slot's pages chosen by the scores of its strides' sums
            # and the burst's own keys; the chosen pages' K and V read
            # where they lie, the burst's rows (always of the newest
            # blocks, always chosen) joined from scratch
            layer, sums, nk, nv = state
            nk, nv = (jax.lax.dynamic_update_slice_in_dim(
                rows, new.astype(rows.dtype), i, 1)
                for rows, new in ((nk, k), (nv, v)))
            listed = sparse.block_decode_pages(
                q[:, 0], sums, nk, i + 1, span, positions, sizes,
                scale=cfg.softmax_scale)
            o, lse = sparse.block_decode_attention(
                q[:, 0], cache.k, cache.v, layer, *listed,
                kvh=cfg.n_kv_heads, scale=cfg.softmax_scale)
            o = sparse.join_new_rows(
                o, lse, q[:, 0], nk, nv,
                jnp.broadcast_to(new_mask, nk.shape[:2]),
                scale=cfg.softmax_scale)
            return o[:, None], (nk, nv)

        return _by_kind(of_linear, of_block), lambda: box["s"]

    def write(scratch, cache_s, p_grid, written):
        at, ok = _pair_positions(p_grid, written, cfg.n_kv_heads)
        cache_k, cache_v = (
            _write_slices(c, _pair_rows(r), block_tables, at, ok, 1)
            for c, r in zip((cache.k, cache.v), scratch[0]))
        return KVCache(cache_k, cache_v, None, _add_to_sums(
            cache.c, scratch[0][0], block_tables, positions, written,
            sizes.stride), cache_s)

    return Burst(old, scratch, cache.s, step, write)
