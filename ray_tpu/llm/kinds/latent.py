"""Latent attention (``LlamaConfig.latent``): a token keeps ONE row a
layer, the compressed keys and values and the rotary key all heads share
(512 + 64 values, in a slot of ``cfg.latent_row`` = 640, which
models/llama.py explains), not a key and a value a head. ``KVCache.k`` is
the one pool [layers, pages, page_size, latent_row] and ``KVCache.v`` is
None: no V pool is allocated, copied or written, the values are the first
512 columns of the same row. One layer group.

``heads`` projects queries through their low-rank bottleneck and the row
(``_latent``) and hands ``attend(q, row, (W_UK, W_UV), state)``, which
chooses the form (``ops/mla.py``): ``prefill`` expands every head's keys
and values from the rows and runs the flash kernel; ``prefill_chunk`` and
``verify_step`` score the gathered rows in the absorbed form;
``decode_burst`` copies nothing: each step's absorbed queries go to
``mla.decode_attention``, which walks each slot's own pages in the pool
(a Pallas kernel on a TPU), and are joined with the burst's own rows by
their log-sum-exp.

The writers here are loops of slices, which an indexer's and a state
kind's pools take too. A scatter would not do: XLA's scatter on a TPU
wants the two fastest dimensions of the pool inside the window it writes,
and with one row a position the second fastest is the position itself, so
it turns the whole pool into another layout and back (two copies of
1.5 GB a burst, read from the compiled text).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops import apply_rotary, attention, mla, rms_norm
from ...ops.quant import weight_einsum
from .. import runner
from ..cache import KVCache
from . import Burst

OWN_PAGES = True
# a burst's table span: the kernel's grid covers the span whatever the
# slots hold, a step past a slot's length costs a third of a
# microsecond, and every bucket is a program to load before the replica
# is ready
LOWEST_BUCKET = 32
COUNTERS = ()


def count(cfg, counters, page_size, start, end, decode) -> None:
    """Nothing: every visible key is attended."""


def attention_paths(cfg, prefill: str, on_tpu: bool):
    gathered = "xla (absorbed, over the gathered rows)"
    return {"prefill": prefill + " (expanded)",
            "prefill_chunk": gathered, "verify_step": gathered,
            "decode_burst": "pallas rt_mla_decode (absorbed, each "
            "slot's own pages)" if on_tpu else gathered}


def refuses(cfg):
    return "latent attention", {
        "lora_rank":
            "adapters are deltas on wq and wv, and a latent layer has "
            "neither (its queries pass a low-rank bottleneck and its "
            "values are expanded from the cached row)",
        "speculation":
            "the drafter mirrors a K and a V pool, and a latent cache has "
            "one pool of rows",
        "kv_transfer":
            "a KV payload is a K and a V stack of pages, and a latent "
            "cache is one pool of rows with no V"}


def init_pools(cfg, num_pages, page_size: int, dtype, slots: int) -> KVCache:
    if not isinstance(num_pages, int):
        raise ValueError("a latent configuration has one layer group")
    return KVCache(jnp.zeros((cfg.n_layers, num_pages, page_size,
                              cfg.latent_row), dtype), None)


def one_prompt(B: int) -> None:
    if B != 1:
        raise ValueError("a latent configuration's prefill, and one's "
                         "with an indexer or with state layers, writes ONE "
                         "prompt's rows a page at a time "
                         "(_write_latent_pages), as the "
                         f"engine asks: B == 1, not {B}")


def _write_slices(pool, rows, block_tables, positions, valid, tail: int):
    """Rows into their pages, a row at a time. pool [..., P, page,
    *row]; rows [..., B, S, *row] with the pool's leading dimensions;
    block_tables, positions, valid as ``paged._write_rows``. ``tail``:
    the dimensions a position's row has (1: a latent row, an indexer's
    key, a (position, KV head) pair of a state kind's page matrix; 2:
    the heads and their width, for the K and V pools of a configuration
    with an indexer, whose burst is written here too: a window of all
    layers x 4 heads x 128 made the scatter turn each 4 GB pool
    layers-inward and back, read from a compile for a v5e). A paged
    burst keeps the scatter: the accepted serve cells' ``tpot_p95_ms``
    was measured with it, and whether this loop would serve them as well
    has not been measured (ROADMAP D20). A row that is not a token is
    written nowhere: its place is page 0's first row, its value what is
    there already. Returns the pool."""
    page_size, *row = pool.shape[-tail - 1:]
    lead = pool.shape[:-tail - 2]
    page = jnp.take_along_axis(block_tables, positions // page_size, axis=1)
    ok = jnp.broadcast_to(valid, positions.shape).reshape(-1)
    fp = jnp.where(ok, page.reshape(-1), 0)
    fo = jnp.where(ok, (positions % page_size).reshape(-1), 0)
    flat = rows.reshape(-1, fp.size, *row).astype(pool.dtype)
    whole = pool.reshape(-1, *pool.shape[-tail - 2:])   # the layers in front
    zeros = (0,) * tail

    def write(i, whole):
        # one row of one layer: a slice over the layers as well would
        # make XLA turn the pool layers-inward for the loop, and back
        layer, t = i // fp.size, i % fp.size
        at = (layer, fp[t], fo[t], *zeros)
        new = jax.lax.dynamic_slice(flat, (layer, t, *zeros), (1, 1, *row))
        old = jax.lax.dynamic_slice(whole, at, (1, 1, 1, *row))
        return jax.lax.dynamic_update_slice(
            whole, jnp.where(ok[t], new[:, None], old), at)

    whole = jax.lax.fori_loop(0, whole.shape[0] * fp.size, write, whole)
    return whole.reshape(*lead, *pool.shape[-tail - 2:])


def _write_latent_pages(pool, rows, table, prompt_lens):
    """A whole prompt's rows into its pages, a PAGE at a time: pool [L,
    P, page, row]; rows [L, 1, S, row], position 0 first; table [1,
    max_pages]; prompt_lens [1]. The rows behind the prompt's end on its
    last page are written too (their positions are masked until a decode
    step writes them); a page wholly behind it is written nowhere (page
    0 keeps what it holds). A position's row may have dimensions of its
    own (a K or V pool's [kvh, hd], for a configuration with an
    indexer). Returns the 1-tuple of the pool."""
    L, _, page_size, *row = pool.shape
    S = rows.shape[2]
    pad = (-S) % page_size
    zeros = (0,) * len(row)
    pages = jnp.pad(rows[:, 0], ((0, 0), (0, pad)) + ((0, 0),) * len(row)
                    ).reshape(L, -1, page_size, *row).astype(pool.dtype)

    def write(j, pool):
        ok = j * page_size < prompt_lens[0]
        at = (0, jnp.where(ok, table[0, j], 0), 0, *zeros)
        new = jax.lax.dynamic_slice_in_dim(pages, j, 1, 1)
        old = jax.lax.dynamic_slice(pool, at, new.shape)
        return jax.lax.dynamic_update_slice(
            pool, jnp.where(ok, new, old), at)

    return (jax.lax.fori_loop(0, pages.shape[1], write, pool),)


def _write_latent(pool, rows, block_tables, positions, valid):
    """``_write_slices`` of one row a position; the 1-tuple of the pool."""
    return (_write_slices(pool, rows, block_tables, positions, valid, 1),)


def _latent(h, lp, cfg, cos, sin, positions):
    """A latent layer's projections of the normalised input h [B, S, d]:
    (q [B, S, heads, nope + rope], the rotary part rotated; the row the
    cache keeps [B, S, ``cfg.latent_row``]: the normalised compressed
    keys and values, the rotated rotary key all heads share, zeros)."""
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_dim
    c_q = rms_norm(weight_einsum("bsd,dr->bsr", h, lp["wq_a"]),
                   lp["q_a_norm"], cfg.norm_eps)
    q = weight_einsum("bsr,rn->bsn", c_q, lp["wq_b"])
    q = q.reshape(*q.shape[:2], cfg.n_heads, cfg.head_dim)
    q = jnp.concatenate([
        q[..., :cfg.qk_nope_dim],
        apply_rotary(q[..., cfg.qk_nope_dim:], cos, sin,
                     positions=positions)], -1)
    kv = weight_einsum("bsd,dr->bsr", h, lp["wkv_a"])
    c_kv = rms_norm(kv[..., :rank], lp["kv_a_norm"], cfg.norm_eps)
    k_r = apply_rotary(kv[..., None, rank:], cos, sin,
                       positions=positions)[..., 0, :]
    pad = jnp.zeros((*kv.shape[:-1], cfg.latent_row - rank - rope), kv.dtype)
    return q, jnp.concatenate([c_kv, k_r, pad], -1)


def heads(h, lp, lr, state, *, cfg, cos, sin, positions, attend, **_):
    q, row = _latent(h, lp, cfg, cos, sin, positions)
    return attend(q, row, (lp["w_uk"], lp["w_uv"]), state)


def prefill(cfg, cache, block_tables, prompt_lens, slots, pos_grid, valid):
    one_prompt(pos_grid.shape[0])

    def attend(q, row, w, _):
        # the expanded form: every head's keys and values multiplied out
        # of the rows, which alone leave the layer scan
        with jax.named_scope("rt.attn.mla.prefill"):
            keys, values = mla.expand(row, *w, cfg.n_heads, cfg.qk_rope_dim)
            o = attention(q, keys, values, causal=True,
                          scale=cfg.softmax_scale, lengths=prompt_lens)
        return o, (row.astype(cache.k.dtype),)

    def write(rows):
        return KVCache(*_write_latent_pages(cache.k, rows[0][0],
                                            block_tables, prompt_lens), None)

    return attend, write


def _over_pages(cfg, cache, table, positions, written, past, own=None):
    """``prefill_chunk``'s and ``verify_step``'s half, where the pool
    rides the layer scan: write the rows into the layer's pages, gather
    the table's span, and attend in the absorbed form over (the span
    under ``past``; with ``own``, the rows themselves under it)."""
    def attend(q, row, w, pools):
        with jax.named_scope("rt.attn.mla.decode"):
            pools = _write_latent(pools[0], row, table, positions, written)
            segments = [(runner._take_span(pools[0], table), past)]
            if own is not None:
                segments.append((row.astype(pools[0].dtype), own))
            o = mla.attend_rows(
                mla.absorb_query(q, w[0], cfg.latent_row),
                cfg.softmax_scale, cfg.kv_lora_rank, *segments)
            return mla.expand_output(o.astype(q.dtype), w[1]), pools

    return ((cache.k,),), attend, lambda pools: KVCache(pools[0][0], None)


def prefill_chunk(cfg, cache, block_tables, start_pos, chunk_len, slots,
                  pos_grid, valid):
    past, own = runner._chunk_masks(
        block_tables.shape[1] * cache.k.shape[2], start_pos, valid)
    return _over_pages(cfg, cache, block_tables, pos_grid, valid, past, own)


def verify_step(cfg, cache, block_tables, positions, qpos, valid):
    Sall = block_tables.shape[1] * cache.k.shape[2]
    seen = jnp.arange(Sall)[None, None, :] <= qpos[:, :, None]
    return _over_pages(cfg, cache, block_tables, qpos, valid, seen)


def decode_burst(cfg, cache, block_tables, gather, positions, active,
                 K: int) -> Burst:
    """``gather``: int32 [B, n], the block tables cut to the pages that
    can hold old context (None: the whole of ``block_tables``): a step
    reads each slot's own pages of it straight from the pool, up to the
    slot's own length, so it costs what the slot's context costs."""
    pool = cache.k
    span = block_tables if gather is None else gather
    # no copy: a layer's state is its index into the pool
    old = ((jnp.arange(cfg.n_layers, dtype=jnp.int32),),)
    scratch = ((jnp.zeros((pool.shape[0], positions.shape[0], K,
                           *pool.shape[3:]), pool.dtype),),)

    def step(i, new_mask, _):
        def attend(q, row, w, state):
            # absorbed: the slot's cached rows where they lie, then the
            # burst's own rows up to this step, one softmax
            layer, rows = state
            rows = jax.lax.dynamic_update_slice_in_dim(
                rows, row.astype(rows.dtype), i, 1)
            with jax.named_scope("rt.attn.mla.decode"):
                ql = mla.absorb_query(q[:, 0], w[0], cfg.latent_row)
                seen = dict(scale=cfg.softmax_scale, rank=cfg.kv_lora_rank)
                o, lse = mla.decode_attention(
                    ql, pool, layer, span, positions, **seen)
                o = mla.join_new_rows(o, lse, ql, rows, new_mask, **seen)
                o = mla.expand_output(o.astype(q.dtype), w[1])
            return o[:, None], (rows,)

        return attend, lambda: None

    def write(scratch, _, p_grid, written):
        return KVCache(*_write_latent(pool, scratch[0][0], block_tables,
                                      p_grid, written), None)

    return Burst(old, scratch, None, step, write)
