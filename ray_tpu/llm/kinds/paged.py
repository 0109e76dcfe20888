"""K and V rows in pages: a pair of arrays [layers, pages, page_size,
kv_heads, head_dim] a layer group.

Layer groups. Layers whose keys live equally long share a pool, an
allocator and a table (``LlamaConfig.kv_groups``: the layers that see the
whole sequence; the layers that see a window). One kind of layer is one
group, whose pools, tables and page lists the programs take and return
bare; with two they are tuples, one a group, each pool with its group's
layers in front and its own number of pages. The tables are indexed by a
position's page all the same, and a window group's sequence gives its
oldest pages back as they leave the window (``SequenceTable.
release_front``): their entries go back to 0, and a row whose entry is 0
is written nowhere (``_held``), so the window group's pool is sized by
what can be live at once (``cache.window_group_pages``). ``attend`` is
told the layer's window and finds its group's tables and masks by it: a
window layer's mask has a lower bound (key position > query position -
window).

One scatter, one convention: ``_write_rows`` writes every row of this
kind. A row that is not a token (bucket padding, a chunk's tail, a
window's -1 positions, an inactive slot) carries the out-of-range page
index ``num_pages`` and ``mode="drop"`` writes nothing for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...models.llama import windowed
from ...ops import attention
from .. import runner
from ..cache import KVCache
from . import Burst

OWN_PAGES = False
# below it a list is a few hundred KB a layer and a finer bucket buys
# nothing. A plain burst always lists flat: alone on the chip at fixed
# shapes ONE list of the live pages costs what a rectangle of as many
# pages (a row a slot at the longest's span) costs, or less (Mistral-7B,
# 16 slots: 103.4 against 103.8 ms a burst at 128 pages, 122.4 against
# 122.5 at 256; OLMoE, 8 slots: 50.4 against 53.2 and 65.5 against 70.9;
# PERF.md, PR 32), and the live pages are never more than that rectangle's
LOWEST_BUCKET = 16
COUNTERS = ()


def count(cfg, counters, page_size, start, end, decode) -> None:
    """Nothing: every visible key is attended."""


def attention_paths(cfg, prefill: str, on_tpu: bool):
    listed = "xla (over the gathered pages)"
    return {"prefill": prefill, "prefill_chunk": listed,
            "verify_step": listed, "decode_burst": listed}


def refuses(cfg):
    """What several layer groups cannot do yet (ROADMAP M4); one group
    refuses nothing."""
    n = len(cfg.kv_groups)
    if n == 1:
        return "one layer group", {}
    return f"{n} layer groups (full and window layers side by side)", {
        "enable_prefix_caching":
            "a cached prompt page would have to be shared in every group, "
            "and a window group gives its pages back",
        "lora_rank":
            "adapters ride a scan over layers, not over periods of a layer "
            "pattern, and no test runs both",
        "speculation":
            "the drafter mirrors ONE page pool and one block table",
        "kv_transfer":
            "a KV payload is one stack of pages for all layers, and a "
            "window group holds only the pages inside its window"}


def init_pools(cfg, num_pages, page_size: int, dtype, slots: int) -> KVCache:
    def pools(layers: int, pages: int):
        return jnp.zeros((layers, pages, page_size, cfg.n_kv_heads,
                          cfg.head_dim), dtype)

    if isinstance(num_pages, int):
        if len(cfg.kv_groups) > 1:
            raise ValueError(f"{len(cfg.kv_groups)} layer groups need a "
                             f"number of pages each")
        return KVCache(pools(cfg.n_layers, num_pages),
                       pools(cfg.n_layers, num_pages))
    sizes = [(cfg.group_layers(g), n) for g, n in enumerate(num_pages)]
    return KVCache(tuple(pools(*s) for s in sizes),
                   tuple(pools(*s) for s in sizes))


def _groups(x):
    """Pools, block tables or page lists, one a layer group: given bare,
    the one group's."""
    return x if isinstance(x, tuple) else (x,)


def _pools(cache: KVCache):
    """The (K, V) pair of each layer group."""
    return tuple(zip(_groups(cache.k), _groups(cache.v)))


def _ungrouped(pools, like) -> KVCache:
    """The groups' (K, V) pairs as the cache, in the form ``like`` came
    in: tuples a group, or the one group's bare arrays."""
    cache_k, cache_v = zip(*pools)
    if not isinstance(like, tuple):
        cache_k, cache_v = cache_k[0], cache_v[0]
    return KVCache(cache_k, cache_v)


def _write_rows(pools, rows, block_tables, positions, valid):
    """THE scatter: K and V rows into their pages (in place when the
    pools are donated).

    pools: the (K, V) pair, each [..., P, page, kvh, hd]; rows: the
    (K, V) pair, each [..., B, S, kvh, hd] with the pools' leading
    dimensions (none: one layer's pools; L: all layers at once);
    block_tables: [B, max_pages]; positions: [B, S] absolute; valid:
    broadcastable to [B, S]. A row that is not a token is given the
    out-of-range page P and dropped: it changes no page.
    """
    n_pages, page_size = pools[0].shape[-4:-2]
    page = jnp.take_along_axis(block_tables, positions // page_size, axis=1)
    fp = jnp.where(valid, page, n_pages).reshape(-1)           # [B*S]
    fo = (positions % page_size).reshape(-1)
    return tuple(
        pool.at[..., fp, fo, :, :].set(
            r.reshape(*r.shape[:-4], -1, *r.shape[-2:]).astype(pool.dtype),
            mode="drop")
        for pool, r in zip(pools, rows))


def _held(table, positions, valid, page_size: int):
    """``valid`` without the rows whose table entry is the reserved page
    0: a page a window group's sequence gave back (or never asked for)
    is written nowhere. table [B, n]; positions, valid [B, S]."""
    page = jnp.take_along_axis(
        table, jnp.clip(positions // page_size, 0, table.shape[1] - 1),
        axis=1)
    return valid & (page > 0)


def heads(h, lp, lr, state, *, cfg, kind, attend, **how):
    q, k, v = runner._heads(h, lp, lr, cfg=cfg, kind=kind, **how)
    with jax.named_scope("rt.attn.window" if windowed(kind)
                         else "rt.attn.full"):
        o, kept = attend(q, k, v, state,
                         cfg.window if windowed(kind) else None)
    return (runner._gated(o, h, lp) if cfg.attn_output_gate else o), kept


def prefill(cfg, cache, block_tables, prompt_lens, slots, pos_grid, valid,
            scale=None, windows=None):
    """``windows``: the windows of the groups given here, where they are
    not all of ``cfg.kv_groups`` (a kind that keeps a group its own way
    hands the others); ``prefill_chunk`` and ``decode_burst`` likewise."""
    pools, tables = _pools(cache), _groups(block_tables)
    windows = windows or cfg.kv_groups
    S = pos_grid.shape[1]
    page_size = pools[0][0].shape[2]     # read by the window groups alone
    # a window layer hands out the rows that can still be inside the
    # window at the prompt's end, not the bucket's: ``kept_rows`` of
    # them, from ``kept_from`` [B] on (the engine holds pages from the
    # one that position prompt_len - window + 1 lies on)
    kept_rows = {w: min(S, -(-w // page_size) * page_size + page_size)
                 for w in windows if w is not None}
    kept_from = {w: jnp.clip(
        jnp.maximum(prompt_lens - w + 1, 0) // page_size * page_size,
        0, S - n) for w, n in kept_rows.items()}

    def attend(q, k, v, _, window):
        # right padding is safe under the causal mask (a real position
        # only attends to earlier, real, positions) and, told where the
        # prompt ends, costs the kernel only the rest of the prompt's
        # last block: the blocks behind it come back as zeros
        o = attention(q, k, v, causal=True, window=window,
                      lengths=prompt_lens, scale=scale)
        if window is not None and kept_rows[window] < S:
            k, v = (jax.vmap(lambda rows, at: jax.lax.dynamic_slice_in_dim(
                rows, at, kept_rows[window], 0))(rows, kept_from[window])
                for rows in (k, v))
        return o, (k.astype(pools[0][0].dtype), v.astype(pools[0][1].dtype))

    def write(rows):
        written = []
        for window, pool, table, kept in zip(windows, pools, tables, rows):
            at, ok = pos_grid, valid
            if window is not None:
                at = kept_from[window][:, None] + jnp.arange(
                    kept_rows[window])
                ok = _held(table, at, at < prompt_lens[:, None], page_size)
            written.append(_write_rows(pool, kept, table, at, ok))
        return _ungrouped(written, block_tables)

    return attend, write


def _span(cache, block_tables) -> int:
    """Positions the (first group's) table spans."""
    return _groups(block_tables)[0].shape[1] * _groups(cache.k)[0].shape[2]


def _over_pages(cfg, cache, block_tables, at, qpos, valid, seen, own=None,
                scale=None, windows=None):
    """``prefill_chunk``'s and ``verify_step``'s half: write the rows (at
    ``at``) into the layer's pages, gather the table's span, and attend
    over (the span under ``seen``; with ``own``, the rows themselves
    under it), a window layer's queries (at ``qpos``) inside the window."""
    pools = _pools(cache)
    tables = dict(zip(windows or cfg.kv_groups, _groups(block_tables)))
    page_size, S = pools[0][0].shape[2], qpos.shape[1]
    span = _span(cache, block_tables)

    def attend(q, k, v, pools, window):
        table, past, mine, rows = tables[window], seen, own, valid
        if window is not None:
            rows = _held(table, qpos, valid, page_size)
            past = past & (jnp.arange(span)[None, None, :]
                           > qpos[:, :, None] - window)
            if own is not None:
                mine = own & (jnp.arange(S)[None, :, None]
                              - jnp.arange(S)[None, None, :] < window)
        pools = _write_rows(pools, (k, v), table, at, rows)
        pk, pv = (runner._take_span(pool, table) for pool in pools)
        return runner._attend(q, (pk, pv, past), *(
            () if own is None else ((k, v, mine),)), scale=scale), pools

    return pools, attend, lambda pools: _ungrouped(pools, block_tables)


def prefill_chunk(cfg, cache, block_tables, start_pos, chunk_len, slots,
                  pos_grid, valid, scale=None, windows=None):
    return _over_pages(cfg, cache, block_tables, pos_grid, pos_grid, valid,
                       *runner._chunk_masks(_span(cache, block_tables),
                                            start_pos, valid), scale=scale,
                       windows=windows)


def verify_step(cfg, cache, block_tables, positions, qpos, valid):
    # unused table slots are 0 (the reserved page) but sit past the row's
    # provisioned span, so their key positions exceed every query's
    seen = (jnp.arange(_span(cache, block_tables))[None, None, :]
            <= qpos[:, :, None])
    return _over_pages(cfg, cache, block_tables, positions, qpos, valid,
                       seen)


def decode_burst(cfg, cache, block_tables, gather, positions, active,
                 K: int, scale=None, windows=None) -> Burst:
    """``gather``: int32 [3, T] a group, ONE flat list of the LIVE pages,
    those that hold old context of decoding slots: each one's (page,
    owner slot, first position); a page two slots share is listed once
    for each, an entry that lists nothing has owner -1 (and page 0).
    Every slot scores every listed key and keeps its own, so cache
    traffic follows the live context. None: the rectangle of the whole
    of ``block_tables``, row b slot b's pages and a slot scoring only
    its row: the worst case. A window group's list holds only the pages
    still inside the window. Either way a slot's keys are those it owns
    at positions below its own: one softmax over them and the burst's
    rows. A pair (first int32 [B], pages int32 [B, n]) in a group's
    place: a row a slot, the slot's pages that hold old context its
    layers still see and the first of their positions (unused entries
    page 0); a slot scores its own row alone (``kinds.ROWS``: a window
    group's row is short, and a list of all of them costs slots x more
    scores than it holds)."""
    B = positions.shape[0]
    pools, tables = _pools(cache), _groups(block_tables)
    gathers = (None,) * len(pools) if gather is None else _groups(gather)
    page_size = pools[0][0].shape[2]
    # old context copied ONCE a burst (read-only during it), and who may
    # score it: [L, B, n * page, kvh, hd] for the table's rectangle, or
    # [L, kvh, T * page, hd] for one flat list; the burst's own rows are
    # [L, B, K, kvh, hd]; all of it a layer group
    old, old_mask, key_pos = [], {}, {}
    for window, pool, table, listed in zip(windows or cfg.kv_groups, pools,
                                           tables, gathers):
        if listed is None or isinstance(listed, tuple):
            first, pages = (0, table) if listed is None else (
                listed[0][:, None], listed[1])
            at = first + jnp.arange(pages.shape[1] * page_size)[None, :]
            mask = at < positions[:, None]                     # [B, S]
        else:
            pages, owner, first = listed
            at = (first[:, None] + jnp.arange(page_size)).reshape(-1)[None]
            mask = ((jnp.repeat(owner, page_size)[None, :]
                     == jnp.arange(B)[:, None])
                    & (at < positions[:, None]))               # [B, S]
        old.append(tuple(runner._gather_span(c, pages) for c in pool))
        old_mask[window], key_pos[window] = mask, at
    scratch = tuple(tuple(
        jnp.zeros((c.shape[0], B, K, *c.shape[3:]), c.dtype) for c in pool)
        for pool in pools)

    def step(i, new_mask, _):
        def attend(q, k, v, state, window):
            ok, ov, nk, nv = state
            nk = jax.lax.dynamic_update_slice_in_dim(
                nk, k.astype(nk.dtype), i, 1)
            nv = jax.lax.dynamic_update_slice_in_dim(
                nv, v.astype(nv.dtype), i, 1)
            seen, own = old_mask[window], new_mask
            if window is not None:
                # step i's query sits at positions + i
                seen = seen & (key_pos[window]
                               > (positions + i - window)[:, None])
                own = own & (i - jnp.arange(K)[None, :] < window)
            # one query a slot: attend without the length-1 axis
            o = runner._attend(q[:, 0], (ok, ov, seen), (nk, nv, own),
                               scale=scale)
            return o[:, None], (nk, nv)

        return attend, lambda: None

    def write(scratch, _, p_grid, written):
        # one scatter of the whole burst into the paged cache
        return _ungrouped(
            [_write_rows(pool, rows, table, p_grid, written)
             for pool, rows, table in zip(pools, scratch, tables)],
            block_tables)

    return Burst(tuple(old), scratch, None, step, write)
