"""A decoder-hybrid-decoder stack (``LlamaConfig.scan_state``; SambaY,
arXiv:2507.06607: ``models/sambay.py`` has the weights, ``runner.
_hybrid_layers`` the three traced bodies): five kinds of layer, three
things a sequence leaves behind.

  * A "scan" layer (``ops/selective_scan.py``) keeps no key: its memory
    is a float32 state of ``scan_state`` numbers a channel a SLOT and the
    convolution's last ``scan_conv - 1`` inputs, ONE pool ``KVCache.s``
    [scan layers, slots, scan_state + scan_conv - 1, E / 128, 128] that
    every program takes as the donated keyword ``cache_s`` and returns
    last. ``prefill`` starts from zeros and leaves the end state and the
    tail at the slot's place (``slots``); ``prefill_chunk`` hands every
    scan layer the slot's rows and puts back what they leave (the engine
    zeroes a slot at admission); ``decode_burst`` carries the pool from
    layer to layer and step to step, and the kernel updates the live
    slots' states where they lie.
  * The "window_diff" layers and the ONE "full_diff" layer keep K and V
    in pages, two layer groups as ``kinds/paged.py`` has them (the full
    group first, ONE layer; the window group's pages given back behind
    the window). The window group's every table, list, scatter and mask
    is that module's. The FULL group's pages a burst reads where they
    lie, a slot its own through its table (``ops/sparse_attention.py
    decode_attention``, ``OWN_PAGES``): eight layers read them at every
    step, and a flat list of them makes every slot score every listed
    key. So its pool keeps a page as ONE matrix of (position, row) rows,
    the form the kernel multiplies, as ``kinds/state.py`` keeps its
    pages and for its reason, and is written by ``kinds/latent.py``'s
    loops of slices.
    Differential attention pairs the heads; two neighbouring K (or V)
    heads are one row of twice the width, so a page holds ``n_kv_heads /
    2`` rows of ``2 head_dim`` a position: what a GQA layer's holds
    (``ops/attention.py``, "Differential attention").
  * A "gmu" layer keeps nothing: it gates the last scan layer's output
    of the SAME row. A "cross_diff" layer keeps nothing either: it has a
    query of its own and reads the full layer's pages, so ONE layer's
    pages serve 1 + Q layers.

A prefill samples one row, and above the full layer's keys a row's
output depends on its own stream, its own ``m`` and the full layer's K
and V at earlier rows: ``prefill`` and ``prefill_chunk`` run the full
layer's attention and feed-forward and the whole cross-decoder for the
LAST row alone (``runner._block``'s ``last``; the stack asks the
program's ``attend("last")`` which row that is, None in a burst), an
equality and not an approximation. There is no ``verify_step``: a rejected window would have
to roll a slot's state back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops import rms_norm, selective_scan, sparse_attention
from ...ops.attention import (attention_path, differential_combine,
                              differential_lambda, differential_queries,
                              differential_rows)
from ...ops.quant import weight_einsum
from .. import runner
from ..cache import KVCache
from . import ROWS, Burst, paged
from .latent import _write_latent_pages, _write_slices, one_prompt
from .state import _pair_positions, _pair_rows

# a group: the full layer's pages through each slot's table; the window
# group's a row a slot (at most 9 pages of 8 layers: a slot scores its own
# and the row has ONE width, so the group adds no program shape)
OWN_PAGES = (True, ROWS)
# a replica loads a decode program a table span of the full group: 3 at
# 13,312 positions. The span sizes the table and the mask handed to the
# kernel, which walks a slot's own pages and no more
LOWEST_BUCKET = 64
# slots whose state was zeroed at an admission (the engine moves it);
# bytes of state and tail the bursts' steps read and wrote (every decoding
# slot's, once each a step); rows the cross-decoder ran in prefills (one a
# dispatch: a whole prompt's, or a chunk's); pages of the full layer's
# pool the bursts' steps read, times the layers that read them
SLOT_RESET = "scan_slots_reset"
COUNTERS = (SLOT_RESET, "scan_state_bytes_step", "cross_prefill_rows",
            "shared_kv_pages_step")


def count(cfg, counters, page_size, start, end, decode) -> None:
    """The queries at positions [start, end) of one sequence: a prefill
    dispatch runs the cross-decoder for ONE row; a burst's step reads and
    writes the slot's state and reads the pages that hold the slot's old
    context once a layer that attends over them."""
    if not decode:
        counters["cross_prefill_rows"] += 1
        return
    steps = end - start
    counters["scan_state_bytes_step"] += 2 * steps * cfg.state_bytes_per_slot
    counters["shared_kv_pages_step"] += (
        steps * -(-start // page_size) * (1 + cfg.hybrid_periods[1]))


def attention_paths(cfg, prefill: str, on_tpu: bool):
    # the engine asks for heads of ``head_dim``; the kernel sees rows of
    # twice that (any lane-aligned bucket tiles alike)
    prefill = attention_path(4096, 4096, 2 * cfg.head_dim, on_tpu)
    scan = "pallas rt_scan_prefill" if on_tpu else "xla (the recurrence)"
    return {
        "prefill": f"scan layers: {scan}; window layers: {prefill} "
        "(differential heads as GQA rows of twice the width); the full "
        "layer and the cross-decoder: xla, the last row alone",
        "prefill_chunk": "the same, over the gathered pages; the state "
        "and the tail carried through their pool",
        "verify_step": "refused (a state cannot be rolled back)",
        "decode_burst": (
            "pallas rt_scan_decode (each live slot's state, in place)"
            if on_tpu else "xla") + "; the full layer and the cross "
        "layers: " + ("pallas rt_sparse_attend_decode" if on_tpu else "xla")
        + " (a slot's own pages where they lie, the burst's rows joined); "
        "window layers: xla over the gathered rows, a slot's each"}


def refuses(cfg):
    """What a state a slot beside two page groups cannot do yet
    (ROADMAP M3, M4)."""
    return f"scan layers (scan_state={cfg.scan_state})", {
        "enable_prefix_caching":
            "a cached page says nothing of a scan layer's state at its "
            "end (snapshots of the state at page boundaries: ROADMAP M3)",
        "lora_rank":
            "adapters ride ONE scan over layers of one stack, and the "
            "five kinds of layer have four stacks",
        "speculation":
            "verify_step would have to roll a slot's state back behind a "
            "rejected window, and the state keeps no token apart",
        "kv_transfer":
            "a KV payload is a K and a V stack of pages for all layers, "
            "and a scan layer's memory is a state a slot that no page "
            "holds"}


def _sizes(cfg):
    """(N, taps - 1, E, E / 128)."""
    E = cfg.scan_channels
    return cfg.scan_state, cfg.scan_conv - 1, E, E // selective_scan.LANES


def init_pools(cfg, num_pages, page_size: int, dtype, slots: int) -> KVCache:
    if isinstance(num_pages, int) or len(num_pages) != 2 or slots < 1:
        raise ValueError("a configuration with scan layers has two layer "
                         "groups of pages (full, window), a number of "
                         "pages each, and a state a slot")
    N, T, _, R = _sizes(cfg)

    rows, width = cfg.n_kv_heads // 2, 2 * cfg.head_dim

    def pools():
        # the full layer's page ONE matrix of its (position, row) rows:
        # a [.., page, 10, 128] pool's reshape to them is a copy of the
        # whole pool (read from a compile for a v5e)
        return (jnp.zeros((cfg.group_layers(0), num_pages[0],
                           page_size * rows, width), dtype),
                jnp.zeros((cfg.group_layers(1), num_pages[1], page_size,
                           rows, width), dtype))

    return KVCache(
        pools(), pools(),
        s=jnp.zeros((cfg.hybrid_periods[0] + 1, slots, N + T, R,
                     selective_scan.LANES), jnp.float32))


def _packed(cfg, state, tail):
    """A scan layer's (state [B, N, E], tail [B, taps - 1, E]) as the
    pool's rows of one slot: [B, N + taps - 1, E / 128, 128]."""
    return jnp.concatenate([state, tail], 1).reshape(
        state.shape[0], -1, _sizes(cfg)[3], selective_scan.LANES)


def _unpacked(cfg, rows):
    N, _, E, _ = _sizes(cfg)
    rows = rows.reshape(*rows.shape[:2], E)
    return rows[:, :N], rows[:, N:]


def _scan_rows(cfg, scan, lengths, rows):
    """A scan layer over the rows of a prefill. ``scan``: what ``heads``
    hands ``attend``; ``rows``: the slot's rows of the pool before them
    (None: a sequence's start). Returns (y float32 [B, S, E], the rows
    behind the last token)."""
    u_raw, conv_w, conv_b, project, a, d = scan
    N, T, E, _ = _sizes(cfg)
    B = u_raw.shape[0]
    state, tail = (None, jnp.zeros((B, T, E), jnp.float32)) \
        if rows is None else _unpacked(cfg, rows)
    out, tail = selective_scan.causal_conv(u_raw, tail, conv_w, conv_b,
                                           lengths)
    u = jax.nn.silu(out)
    y, state = selective_scan.prefill(u, *project(u), a, d, lengths, state)
    return y, _packed(cfg, state, tail)


def heads(h, lp, lr, state, *, cfg, kind, attend, last=None, **_):
    """A layer's mixer on the normalised input, by its kind. ``state``
    and what is kept are the kind's: a scan layer is handed (its place
    among the scan layers, the slot's rows or None, the pool the layers
    carry or None) and keeps (its output before the gate, the rows it
    leaves, the pool); the full layer keeps (its K and V, what the cross
    layers read); a memory unit is handed ``m``; a cross layer what the
    full layer left."""
    B, S, _ = h.shape
    N, _, E, R = _sizes(cfg)
    f32 = jnp.float32
    if kind == "gmu":
        gate = weight_einsum("bsd,de->bse", h, lp["wg"],
                             preferred_element_type=f32)
        o = state * jax.nn.silu(gate)
        return o.reshape(B, S, R, selective_scan.LANES), None
    if kind == "scan":
        uz = weight_einsum("bsd,de->bse", h, lp["w_in"])
        u_raw, z = uz[..., :E], uz[..., E:]

        def project(u):
            """(dt, B, C) float32 of the convolved input."""
            rbc = weight_einsum("bse,er->bsr", u.astype(h.dtype), lp["w_x"],
                                preferred_element_type=f32)
            r, Bm, Cm = jnp.split(rbc, [cfg.scan_dt_rank,
                                        cfg.scan_dt_rank + N], -1)
            dt = weight_einsum("bsr,re->bse", r.astype(h.dtype), lp["w_dt"],
                               preferred_element_type=f32)
            return jax.nn.softplus(dt + lp["b_dt"].astype(f32)), Bm, Cm

        with jax.named_scope("rt.attn.scan"):
            y, kept = attend(kind, (
                u_raw, lp["conv_w"], lp["conv_b"], project,
                -jnp.exp(lp["a_log"].astype(f32)),
                lp["d_skip"].astype(f32)), state)
        o = y * jax.nn.silu(z.astype(f32))
        return o.reshape(B, S, R, selective_scan.LANES), (y, *kept)
    # differential attention: the query's side, the keys' (a cross layer
    # has none), the two softmaxes as one GQA attention, the difference
    hq = h if last is None else runner._rows_at(h, last)
    q = weight_einsum("bsd,dhk->bshk", hq, lp["wq"]) + lp["bq"]
    q = differential_queries(q, cfg.n_kv_heads)
    k = v = None
    if kind != "cross_diff":
        k, v = (differential_rows(
            weight_einsum("bsd,dhk->bshk", h, lp[w]) + lp[b])
            for w, b in (("wk", "bk"), ("wv", "bv")))
    with jax.named_scope("rt.attn.window" if kind == "window_diff"
                         else "rt.attn.full"):
        o, kept = attend(kind, q, k, v, state)
    lam0 = lp["lam0"]
    o = differential_combine(o, differential_lambda(lp, lam0),
                             cfg.n_kv_heads)
    return rms_norm(o, lp["sub_norm"], cfg.norm_eps) * (1.0 - lam0), kept


def _one_row(q, *segments, scale):
    """The one query row a sequence has, over ``segments``."""
    if q.shape[1] != 1:
        raise ValueError("the full layer's attention and the cross layers "
                         f"run one row a sequence, not {q.shape[1]}")
    return runner._attend(q[:, 0], *segments, scale=scale)[:, None]


def prefill(cfg, cache, block_tables, prompt_lens, slots, pos_grid, valid):
    one_prompt(pos_grid.shape[0])
    scale = cfg.softmax_scale
    windowed, write_pages = paged.prefill(
        cfg, KVCache(cache.k[1:], cache.v[1:]), block_tables[1:],
        prompt_lens, slots, pos_grid, valid, scale=scale,
        windows=cfg.kv_groups[1:])
    dtype = cache.k[0].dtype

    def attend(kind, *a):
        if kind == "last":
            return jnp.maximum(prompt_lens - 1, 0)
        if kind == "scan":
            # from zeros, whatever the slot held
            y, rows = _scan_rows(cfg, a[0], prompt_lens, None)
            return y, (rows, None)
        q, k, v, state = a
        if kind == "window_diff":
            return windowed(q, k, v, None, cfg.window)
        if kind == "cross_diff":
            return _one_row(q, (*state, valid), scale=scale), None
        # the full layer: the last row's query over the prompt's rows
        return _one_row(q, (k, v, valid), scale=scale), (
            (k.astype(dtype), v.astype(dtype)), (k, v))

    def write(rows):
        full, window, (left, _) = rows
        pages = write_pages((window,))
        # the ONE prompt's rows of the full layer a page at a time
        (fk,), (fv,) = (_write_latent_pages(
            pool, _pair_rows(new), block_tables[0],
            prompt_lens * (cfg.n_kv_heads // 2))
            for pool, new in zip((cache.k[0], cache.v[0]), full))
        # its end state and tail of every scan layer to the slot's place
        return KVCache((fk, *pages.k), (fv, *pages.v),
                       s=jax.lax.dynamic_update_slice(
                           cache.s, left, (0, slots[0], 0, 0, 0)))

    return attend, write


def prefill_chunk(cfg, cache, block_tables, start_pos, chunk_len, slots,
                  pos_grid, valid):
    scale = cfg.softmax_scale
    pools, windowed, done_pages = paged.prefill_chunk(
        cfg, KVCache(cache.k[1:], cache.v[1:]), block_tables[1:], start_pos,
        chunk_len, slots, pos_grid, valid, scale=scale,
        windows=cfg.kv_groups[1:])
    table, B = block_tables[0], pos_grid.shape[0]
    span = table.shape[1] * cache.k[1].shape[2]
    # the chunk's rows lie in the pages when the last row's query reads
    # them: everything up to that row
    seen = jnp.arange(span)[None, :] < start_pos + chunk_len
    lens = chunk_len.reshape(1)
    # the slot's rows of every scan layer (zeroed when the request was
    # admitted), carried from chunk to chunk through the pool
    handed = jax.lax.dynamic_slice(
        cache.s, (0, slots[0], 0, 0, 0),
        (cache.s.shape[0], 1, *cache.s.shape[2:]))

    def attend(kind, *a):
        if kind == "last":
            return jnp.broadcast_to(jnp.maximum(chunk_len - 1, 0), (B,))
        if kind == "scan":
            y, rows = _scan_rows(cfg, a[0], lens, a[1][1])
            return y, (rows, None)
        q, k, v, state = a
        if kind == "window_diff":
            return windowed(q, k, v, state, cfg.window)
        if kind == "cross_diff":
            return _one_row(q, state, scale=scale), None
        # the chunk's (position, row) rows into the pages, then the span
        # through them
        at, ok = _pair_positions(pos_grid, valid, k.shape[2])
        pages = tuple(
            _write_slices(pool, _pair_rows(new), table, at, ok, 1)
            for pool, new in zip(state, (k, v)))
        sk, sv = (runner._take_span(pool, table).reshape(
            B, span, *k.shape[2:]) for pool in pages)
        return _one_row(q, (sk, sv, seen), scale=scale), (
            pages, (sk, sv, seen))

    def done(kept):
        (fk, fv), window, (left, _) = kept
        pages = done_pages((window,))
        return KVCache((fk, *pages.k), (fv, *pages.v),
                       s=jax.lax.dynamic_update_slice(
                           cache.s, left, (0, slots[0], 0, 0, 0)))

    return ((cache.k[0], cache.v[0]), *pools, (handed, None)), attend, done


def verify_step(cfg, *_):
    raise ValueError(
        "verify_step is not written for scan layers: a window that is "
        "rejected would have to roll a slot's state back, and the state "
        "keeps no token apart (LLMEngine refuses speculation with them)")


def decode_burst(cfg, cache, block_tables, gather, positions, active,
                 K: int) -> Burst:
    """``gather``: (the full group's block table cut to the bucket of
    the longest decoding slot's pages, int32 [B, n]; the window group's
    rows, a slot's each, as a paged burst's). None: the whole tables."""
    scale = cfg.softmax_scale
    span, listed = (block_tables[0], None) if gather is None else (
        gather[0], gather[1:])
    pages = paged.decode_burst(
        cfg, KVCache(cache.k[1:], cache.v[1:]), block_tables[1:], listed,
        positions, active, K, scale=scale, windows=cfg.kv_groups[1:])
    N, T, E, R = _sizes(cfg)
    B = positions.shape[0]
    full = (cache.k[0], cache.v[0])
    # the full layer's old context is copied nowhere: a slot's query
    # walks the slot's own pages (none where it does not decode), every
    # cached key of them, and the burst's rows are joined from scratch
    cached = jnp.where(active, positions, 0)
    pairs = cfg.n_kv_heads // 2
    every = jnp.ones((B, span.shape[1] * full[0].shape[2] // pairs),
                     jnp.int8)
    # the slots that decode, in the order the state kernel walks them:
    # the same for every layer and step
    order = selective_scan.live_order(active)
    one = jnp.ones((B,), jnp.int32)

    def step(i, new_mask, _):
        over_pages, _ = pages.step(i, new_mask, None)

        def over_full(q, rows):
            o, lse = sparse_attention.decode_attention(
                q[:, 0], *full, 0, span, cached, every, scale=scale,
                kvh=pairs)
            return sparse_attention.join_new_rows(
                o, lse, q[:, 0], *rows,
                jnp.broadcast_to(new_mask, rows[0].shape[:2]),
                scale=scale)[:, None]

        def attend(kind, *a):
            if kind == "last":
                return None
            if kind == "scan":
                (u_raw, conv_w, conv_b, project, a_, d), (
                    place, _, pool) = a
                at = (place, 0, N, 0, 0)
                tail = jax.lax.dynamic_slice(
                    pool, at, (1, B, T, R, selective_scan.LANES)
                ).reshape(B, T, E)
                out, new = selective_scan.causal_conv(
                    u_raw, tail, conv_w, conv_b, one)
                pool = jax.lax.dynamic_update_slice(
                    pool, jnp.where(active[:, None, None], new, tail
                                    ).reshape(1, B, T, R, -1), at)
                u = jax.nn.silu(out)
                dt, Bm, Cm = project(u)
                # every live slot's state of the layer advanced by the
                # step's token, in place in the pool
                y, pool = selective_scan.decode_step(
                    u[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], a_, d, pool,
                    place, active, order)
                return y[:, None], (None, pool)
            q, k, v, state = a
            if kind == "window_diff":
                return over_pages(q, k, v, state, cfg.window)
            if kind == "cross_diff":
                return over_full(q, state), None
            # the full layer: its rows of the burst, which the cross
            # layers read beside the pages
            rows = tuple(jax.lax.dynamic_update_slice_in_dim(
                r, new.astype(r.dtype), i, 1)
                for r, new in zip(state, (k, v)))
            return over_full(q, rows), (rows, rows)

        return attend, lambda: None

    def write(scratch, _, p_grid, written):
        at, ok = _pair_positions(p_grid, written, pairs)
        fk, fv = (_write_slices(pool, _pair_rows(new), block_tables[0], at,
                                ok, 1)
                  for pool, new in zip(full, scratch[0]))
        out = pages.write(scratch[1:2], None, p_grid, written)
        return KVCache((fk, *out.k), (fv, *out.v), s=scratch[2][1])

    rows = tuple(jnp.zeros((1, B, K, pairs, c.shape[-1]), c.dtype)
                 for c in full)
    return Burst(((), *pages.old, ()),
                 (rows, *pages.scratch, (None, cache.s)), None, step, write)
