"""An indexer beside attention (``LlamaConfig.sparse_top_k``;
``ops/sparse_attention.py``). Beside its K and V rows a token keeps the
indexer's ONE key a layer (64 values, in a slot of ``cfg.indexer_row`` =
128 for ``latent_row``'s reason): ``KVCache.i`` (``cache_i``) is a third
pool [layers, pages, page_size, indexer_row] that shares the page ids of
K and V: the same allocator, the same tables, a page's rows written,
shared and released together. One layer group.

``heads`` also projects the indexer's queries, its one key a token and a
weight a head (``_index``) and hands them to ``attend``: a query attends
over the ``sparse_top_k`` visible keys the indexer scores highest.
``prefill`` scores, chooses and multiplies a tile of queries at a time
over the prompt's own rows (a bucket of at most ``sparse_top_k`` keys
takes the dense path, whose result it is); ``prefill_chunk`` and
``verify_step`` over the gathered span (and the chunk's own rows);
``decode_burst`` copies no K or V: every step gathers the indexer's rows
of each slot's own pages (a sixteenth of its K and V), scores them and
the burst's own and chooses (``sparse.decode_chosen``), then attends over
each slot's own K and V pages where they lie, under that choice
(``sparse.decode_attention``, a Pallas kernel on a TPU: it reads every
page up to the slot's length, which costs less than finding the chosen
rows did: 0.12 to 0.18 ms a layer against 0.81 to 0.91, PR 44), joined
with the burst's own chosen rows by the log-sum-exp. Whole prompts and
bursts are written by ``latent``'s loops of slices, not by the scatter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops import attention
from ...ops import sparse_attention as sparse
from ...ops.quant import weight_einsum
from .. import runner
from ..cache import KVCache
from . import Burst
from .latent import (LOWEST_BUCKET, _write_latent,  # noqa: F401  (a fact)
                     _write_latent_pages, _write_slices, one_prompt)
from .paged import _write_rows

OWN_PAGES = True
# summed over queries (prefilled and decoded tokens), from their
# positions: the keys a query could see and its indexer scored, and the
# keys it attended over (at most sparse_top_k of them), and the columns
# its choice counted over a pass (``sparse.counted_keys``: the kernel's
# chunks, against the bucket's row); the pages of K (and as many of V)
# the bursts' steps walked: every decoding slot's cached pages, once a
# step run
COUNTERS = ("scored_keys", "attended_keys", "counted_keys",
            "sparse_decode_pages")


def count(cfg, counters, page_size, start, end, decode) -> None:
    """The queries at positions [start, end) of one sequence: the query
    at position t sees t + 1 keys and attends over at most
    ``sparse_top_k``; a burst's walk the slot's cached pages each."""
    k = cfg.sparse_top_k

    def upto(n: int) -> int:        # 1 + 2 + .. + n
        return n * (n + 1) // 2

    lo, hi = min(start, k), min(end, k)
    counters["scored_keys"] += upto(end) - upto(start)
    counters["attended_keys"] += (
        upto(hi) - upto(lo) + k * ((end - start) - (hi - lo)))
    counters["counted_keys"] += sparse.counted_keys(start, end, k, decode)
    if decode:
        counters["sparse_decode_pages"] += (end - start) * -(
            -start // page_size)


def attention_paths(cfg, prefill: str, on_tpu: bool):
    chosen = ("pallas rt_sparse_index, rt_sparse_select, "
              "flash_sparse_fwd (the indexer's choice, over the %s)"
              if on_tpu else "xla (the indexer's choice, over the %s)")
    return {"prefill": prefill + " up to sparse_top_k keys, then "
            + chosen % "prompt's rows",
            "prefill_chunk": chosen % "gathered pages",
            "verify_step": chosen % "gathered pages",
            "decode_burst": (
                "pallas rt_sparse_index_decode, rt_sparse_select_"
                "decode (each slot's own indexer rows), rt_sparse_"
                "attend_decode" if on_tpu else "xla") + " (each "
            "slot's own K and V pages where they lie, under the "
            "choice)"}


def refuses(cfg):
    """What a third pool cannot do yet (ROADMAP M7)."""
    return f"an indexer (sparse_top_k={cfg.sparse_top_k})", {
        "enable_prefix_caching":
            "a cached page's indexer rows are shared with it by page id, "
            "but no test runs a resumed prompt through the selection yet",
        "lora_rank":
            "adapters are deltas on wq and wv, the indexer chooses keys "
            "from projections of its own, and no test runs both",
        "speculation":
            "verify_step selects over the pages (llm/kinds/indexed.py), "
            "but the drafter mirrors a K and a V pool and no test runs a "
            "speculative round through the third pool",
        "kv_transfer":
            "a KV payload is a K and a V stack of pages, and a page here "
            "has a third row a token, the indexer's key"}


def init_pools(cfg, num_pages, page_size: int, dtype, slots: int) -> KVCache:
    L = cfg.n_layers
    shape = (L, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((L, num_pages, page_size, cfg.indexer_row),
                             dtype))


def _write(pools, rows, block_tables, positions, valid):
    """One layer's rows inside a layer scan (``prefill_chunk``,
    ``verify_step``), where the scatter's window is one layer's: K and V
    by the scatter, the indexer's pool (one row a position, as a latent
    configuration's) by the loop of slices."""
    return _write_rows(pools[:2], rows[:2], block_tables, positions,
                       valid) + _write_latent(
                           pools[2], rows[2], block_tables, positions, valid)


def _index(h, lp, cfg, positions):
    """A layer's indexer on the normalised input h [B, S, d]: (qI [B, S,
    J, ``cfg.indexer_row``], rotated, then zeros; w float32 [B, S, J];
    the row the third pool keeps [B, S, ``cfg.indexer_row``]: the one
    key a token has, LayerNorm'd and rotated, then zeros). Its rotary
    embedding turns the whole of ``indexer_dim`` at the model's theta."""
    di = cfg.indexer_dim
    with jax.named_scope("rt.attn.index"):
        # float32 out of the products and through the norm and the
        # rotation, rounded ONCE: a score that is off by a rounding swaps
        # keys across the top_k-th place
        qi, ki, w = (weight_einsum(eq, h, lp[name],
                                   preferred_element_type=jnp.float32)
                     for eq, name in (("bsd,djk->bsjk", "wi_q"),
                                      ("bsd,dk->bsk", "wi_k"),
                                      ("bsd,dj->bsj", "wi_w")))
        ki = ki - ki.mean(-1, keepdims=True)
        ki = (ki * jax.lax.rsqrt(jnp.square(ki).mean(-1, keepdims=True)
                                 + cfg.norm_eps)
              * lp["wi_k_norm"].astype(jnp.float32)
              + lp["wi_k_bias"].astype(jnp.float32))
        at = jnp.arange(h.shape[1])[None] if positions is None else positions
        angle = at[..., None].astype(jnp.float32) * cfg.rope_theta ** (
            -jnp.arange(0, di, 2, dtype=jnp.float32) / di)
        cos, sin = jnp.cos(angle), jnp.sin(angle)          # [B, S, di / 2]

        def turned(x, cos, sin):
            x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1).astype(h.dtype)

        # both as wide as the pool's slot (zeros behind ``di``): a query
        # scores the rows as they are stored, whole lanes, nothing sliced
        pad = ((0, cfg.indexer_row - di),)
        qi = jnp.pad(turned(qi, cos[..., None, :], sin[..., None, :]),
                     ((0, 0),) * 3 + pad)
        ki = jnp.pad(turned(ki, cos, sin), ((0, 0),) * 2 + pad)
        return qi, w, ki


def heads(h, lp, lr, state, *, cfg, positions, attend, **how):
    q, k, v = runner._heads(h, lp, lr, cfg=cfg, positions=positions, **how)
    # the closure opens rt.attn.select and rt.attn.sparse
    return attend(q, k, v, state, _index(h, lp, cfg, positions))


def _pools(cache: KVCache):
    return (cache.k, cache.v, cache.i)


def prefill(cfg, cache, block_tables, prompt_lens, slots, pos_grid, valid):
    one_prompt(pos_grid.shape[0])
    S = pos_grid.shape[1]

    def attend(q, k, v, _, index):
        # at most top_k keys in the bucket: every visible key is chosen,
        # the dense path; else the indexer's choice a query
        qi, w, ki = index
        if S <= cfg.sparse_top_k:
            o = attention(q, k, v, causal=True, lengths=prompt_lens)
        else:
            o = sparse.attend(
                q, k, v, qi, w, ki, jnp.where(valid, pos_grid + 1, 0),
                top_k=cfg.sparse_top_k, scale=cfg.softmax_scale)
        return o, tuple(r.astype(c.dtype)
                        for r, c in zip((k, v, ki), _pools(cache)))

    def write(rows):
        # the ONE prompt's rows a page at a time into all three pools
        return KVCache(*sum((_write_latent_pages(
            c, r, block_tables, prompt_lens)
            for c, r in zip(_pools(cache), rows[0])), ()))

    return attend, write


def prefill_chunk(cfg, cache, block_tables, start_pos, chunk_len, slots,
                  pos_grid, valid):
    C = pos_grid.shape[1]
    Spast = block_tables.shape[1] * cache.k.shape[2]

    def attend(q, k, v, pools, index):
        # the chunk's queries score the cached rows below its start and
        # the chunk's own rows up to themselves
        qi, w, ki = index
        pools = _write(pools, (k, v, ki), block_tables, pos_grid, valid)
        keys = [jnp.concatenate([runner._take_span(pool, block_tables),
                                 new.astype(pool.dtype)], 1)
                for pool, new in zip(pools, (k, v, ki))]
        o = sparse.attend(
            q, keys[0], keys[1], qi, w, keys[2],
            jnp.where(valid, start_pos, 0),
            jnp.where(valid, jnp.arange(C)[None, :] + 1, 0), Spast,
            top_k=cfg.sparse_top_k, scale=cfg.softmax_scale)
        return o, pools

    return (_pools(cache),), attend, lambda pools: KVCache(*pools[0])


def verify_step(cfg, cache, block_tables, positions, qpos, valid):
    def attend(q, k, v, pools, index):
        # the window's own rows are scored through the pages too
        qi, w, ki = index
        pools = _write(pools, (k, v, ki), block_tables, positions, valid)
        pk, pv, pi = (runner._take_span(pool, block_tables)
                      for pool in pools)
        o = sparse.attend(
            q, pk, pv, qi, w, pi, jnp.where(valid, qpos + 1, 0),
            top_k=cfg.sparse_top_k, scale=cfg.softmax_scale)
        return o, pools

    return (_pools(cache),), attend, lambda pools: KVCache(*pools[0])


def decode_burst(cfg, cache, block_tables, gather, positions, active,
                 K: int) -> Burst:
    """``gather``: as a latent burst's."""
    pools = _pools(cache)
    span = block_tables if gather is None else gather
    # no copy: a layer's state is its index into the pools
    old = ((jnp.arange(cfg.n_layers, dtype=jnp.int32),),)
    scratch = (tuple(jnp.zeros((c.shape[0], positions.shape[0], K,
                                *c.shape[3:]), c.dtype) for c in pools),)

    def step(i, new_mask, _):
        def attend(q, k, v, state, index):
            # the slot's indexer rows and the burst's own, scored and
            # chosen from; the slot's K and V pages read where they lie
            # under the choice, the burst's rows joined from scratch:
            # one softmax
            layer, nk, nv, ni = state
            qi, w, ki = index
            nk, nv, ni = (jax.lax.dynamic_update_slice_in_dim(
                rows, new.astype(rows.dtype), i, 1)
                for rows, new in ((nk, k), (nv, v), (ni, ki)))
            chosen, own = sparse.decode_chosen(
                qi[:, 0], w[:, 0], pools[2], layer, span, positions,
                ni, i + 1, top_k=cfg.sparse_top_k)
            o, lse = sparse.decode_attention(
                q[:, 0], *pools[:2], layer, span, positions, chosen,
                scale=cfg.softmax_scale)
            o = sparse.join_new_rows(o, lse, q[:, 0], nk, nv, own,
                                     scale=cfg.softmax_scale)
            return o[:, None], (nk, nv, ni)

        return attend, lambda: None

    def write(scratch, _, p_grid, written):
        # K and V by slices too: ``latent._write_slices`` says why
        return KVCache(*(_write_slices(c, r, block_tables, p_grid, written,
                                       c.ndim - 3)
                         for c, r in zip(pools, scratch[0])))

    return Burst(old, scratch, None, step, write)
