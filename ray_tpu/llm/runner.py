"""Model runner: the serving decoder layer, written once, and the four
jitted programs that run it over a paged KV cache.

One block. ``_block`` is the decoder layer: norm, the three projections
(plus a slot's LoRA deltas where adapter rows are given), QK-norm,
rotary, attention, ``wo``, norm, the feed-forward (dense SwiGLU or the
dropless routed experts); ``_layers`` makes the one ``lax.scan`` of it
over the stacked layers. A program supplies ``attend(q, k, v, state)``,
how the layer reaches the cache, and nothing else reaches it:
  * ``prefill`` (whole prompts; ``prefill_sample`` fuses the sampler)
    attends over the prompt's own k and v, reads no page, and hands the
    rows out of the scan; one scatter writes all layers' rows at the end.
  * ``prefill_chunk`` (one chunk of a long prompt) writes the chunk's
    rows into the layer's pages, gathers the table's span, and attends
    over (the span below the chunk's start; the chunk's own rows).
  * ``verify_step`` (a speculative window) writes the window's rows,
    gathers the span, and attends over it under key position <= query
    position: the window sees its own keys through the pages.
  * ``decode_burst`` (up to n fused decode+sample steps, the number an
    operand) copies the pages that hold old context once a burst for all
    layers: one flat list of the live pages that every slot scores under
    its own mask, or the rectangle of a row a slot; step i puts its row
    into a burst scratch and attends over (the copy; scratch up to i);
    one scatter writes the scratch at the end.
A scan's stacked output is not aliased to its stacked input, so a pool
that rides a layer scan is rewritten whole: ``prefill`` and
``decode_burst`` keep it out, ``prefill_chunk`` and ``verify_step``
still take it through (ROADMAP S10: a change to their two closures).

One scatter, one convention. ``_write_rows`` is the only place a page is
written (a latent configuration's rows: ``_write_latent``, the same
convention as a loop of slices, which says why). A row that is not a
token (bucket padding, a chunk's tail, a window's -1 positions, an
inactive slot) carries the out-of-range page index ``num_pages`` and
``mode="drop"`` writes nothing for it. Page 0
stays reserved: block tables and page lists are padded with 0 and the
gathers read it under a mask; nothing writes to it. A burst's gather,
``_gather_span``, copies the listed pages of all layers once, straight
into the layer-major array the layer scan slices (no transposition,
select or fill behind it); inside a layer scan one layer's pool is
gathered by ``_take_span``, a plain ``jnp.take``, which is the faster
there (both measured alone on the chip: PERF.md, PR 32).

Layer groups. A configuration whose layers differ (``LlamaConfig.
layer_pattern``: with or without the rotary embedding, the whole sequence
or a window of it) still has the one ``_block``: ``_layers`` scans over
PERIODS of the pattern and the body runs the period's layers in turn,
each with its kind. Layers whose keys live equally long share a page
pool (``llm/cache.py``), so a program takes its pools, its block tables
and a burst's page lists one a group (a tuple; one given bare is the one
group's, and a one-group configuration lowers to what it always did);
``attend`` is told the layer's window and finds its group's state by it.
A window layer's mask has a lower bound (key position > query position -
window); a window group's row whose table entry is the reserved page 0
(a page that left the window and was given back) is written nowhere.

Latent attention (``LlamaConfig.latent``). The block's
attention half projects queries through their low-rank bottleneck and
ONE row a token for the cache (``_latent``): the compressed keys and
values and the rotary key all heads share. There is one pool of such
rows and no V pool (``cache_v`` is None everywhere). ``attend(q, row,
(W_UK, W_UV), state, None)`` chooses the form (``ops/mla.py``): whole-
prompt ``prefill`` expands every head's keys and values from the rows
and runs the flash kernel; ``prefill_chunk`` and ``verify_step`` score
the gathered rows in the absorbed form; ``decode_burst`` copies nothing:
each step's absorbed queries go to ``mla.decode_attention``, which walks
each slot's own pages in the pool (a Pallas kernel on a TPU), and are
joined with the burst's own rows by their log-sum-exp.

An indexer (``LlamaConfig.sparse_top_k``; ``ops/sparse_attention.py``).
The block's attention half also projects the indexer's queries, its one
key a token and a weight a head (``_index``), and ``attend`` is handed
them (``index=``): a query attends over the ``sparse_top_k`` visible
keys the indexer scores highest. The indexer's keys live in a THIRD pool
beside K and V (``cache_i``: every program takes it as a keyword, donates
it and returns it behind its expert counts), written where K and V are
written and addressed by the same tables. ``prefill`` scores, chooses
and multiplies a tile of queries at a time over the prompt's own rows
(a bucket of at most ``sparse_top_k`` keys takes the dense path, whose
result it is); ``prefill_chunk`` and ``verify_step`` over the gathered
span (and the chunk's own rows); ``decode_burst`` copies no K or V:
every step gathers the indexer's rows of each slot's own pages, scores
them and the burst's own, chooses, and attends over each slot's own K
and V pages where they lie in the pools, under that choice
(``sparse.decode_attention``, a Pallas kernel on a TPU: it reads every
page up to the slot's length, which costs less than finding the chosen
rows did: 0.12 to 0.18 ms a layer against 0.81 to 0.91 for the counting
and the two gathers, PR 44). Whole prompts and bursts are written into
the three pools by loops of slices (``_write_latent_pages``,
``_write_slices``), not by the scatter, which says why.

State layers (``LlamaConfig.linear_heads``: a ``layer_pattern`` that
lists every layer, of the kinds "linear" and "block_nope", each with a
stack of weights of its own: ``params["linear_layers"]``,
``params["layers"]``). ``_layers`` runs such a pattern a layer at a
time, each with its weights and its state by its place IN ITS KIND, and
``attend`` is told the kind (``linear=``, ``block=``). A linear layer
keeps no key: its memory is a float32 state a slot
(``ops/linear_attention.py``), a pool ``cache_s`` [linear layers, slots,
heads, hd, hd] that every program takes as a donated keyword and
returns last: ``prefill`` runs the chunked form from zeros and puts the
end state at the slot's place (``slots``), ``prefill_chunk`` carries the
slot's state through the pool from chunk to chunk, ``decode_burst``
keeps the pool in the step loop's carry and every step updates the live
slots' states in place. A block layer's K and V pools are page MATRICES
[layers, pages, page x kv_heads, hd] (``_pair_rows``: a row's
"position" is ``position * kv_heads + head``, so the writers serve them
as they are), and beside them ``cache_c`` holds the float32 sum of the
keys of every ``block_stride`` positions, of which a compressed key is
the mean of two neighbours: a query below ``block_dense_len`` attends
over every visible key (the flash forward), one above over the tokens
of the blocks it chooses (``sparse.block_attend``); a decode step scores
the sums of its slot's pages (gathered once a burst) and the burst's own
keys, lists the pages chosen, a row a (slot, KV head), and reads those
pages and no other where they lie (``sparse.block_decode_attention``).
``verify_step`` refuses the kinds by name: a rejected window would have
to roll a state back.

Leading dense layers (``LlamaConfig.n_dense_layers``) are their own
stack ``params["dense_layers"]``: ``_layers`` scans them first, with
the same block and the dense feed-forward, then the expert layers; the
cache's layers are in that order.

Static shapes throughout: prefill pads a prompt to a power-of-2 bucket
or a rung between two (``prefill_bucket``; one executable a bucket),
decode runs the whole slot batch every step with inactive slots masked
over a page list padded to a power-of-2 bucket (one executable a bucket,
whatever the burst's width), and the cache buffers are donated, so the
scatters update pages in place.
Reference analog: the vLLM
paged-attention CUDA kernels behind ray.llm's vllm_engine (SURVEY §2.4),
rebuilt natively since the reference delegates all device work to vLLM.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..models.llama import LlamaConfig, linear, qk_norm, rotated, windowed
from ..ops import apply_rotary, attention, mla, rms_norm
from ..ops import linear_attention
from ..ops import sparse_attention as sparse
from ..ops.moe import router_logits
from ..ops.quant import embed_lookup, is_quantized, weight_einsum
from .lora import lora_delta
from .sampling import sample_from_logits


_EXPERT_STACKS = ("w_gate", "w_up", "w_down")
# donated: the scatters run in place
_POOLS = ("cache_k", "cache_v", "cache_i", "cache_c", "cache_s")


def _split_layers(layers, cfg: LlamaConfig):
    """(what a scan over layers slices, what it must not). An expert
    config's expert matrices stay whole stacks that the routed layer
    addresses by the layer's index: a scan that sliced them would copy a
    layer's experts (0.4 GB int8 at OLMoE's widths) in every iteration,
    where a decode step needs a few of them."""
    if not cfg.n_experts:
        return layers, None
    sliced = {k: v for k, v in layers.items() if k not in _EXPERT_STACKS}
    sliced["layer"] = jnp.arange(cfg.n_moe_layers, dtype=jnp.int32)
    return sliced, {k: layers[k] for k in _EXPERT_STACKS}


def _groups(x):
    """Pools, block tables or page lists, one a layer group: given bare,
    the one group's."""
    return x if isinstance(x, tuple) else (x,)


def _pools(cache_k, cache_v, cache_i=None):
    """The pools of each layer group: the (K, V) pair; where there is no
    V pool (``cache_v`` None: a latent configuration), the one pool of
    rows alone; with an indexer (``cache_i``), its pool third."""
    if cache_v is None:
        return tuple((k,) for k in _groups(cache_k))
    if cache_i is not None:
        return tuple(zip(_groups(cache_k), _groups(cache_v),
                         _groups(cache_i)))
    return tuple(zip(_groups(cache_k), _groups(cache_v)))


def _ungrouped(pools, like):
    """(cache_k, cache_v, the rest) out of the groups' pools, in the
    form ``like`` came in: tuples a group, or the one group's bare
    arrays; ``cache_v`` None where the groups have one pool each; the
    rest: ``(cache_i,)`` where they have three, else ``()``: what a
    program returns behind its expert counts."""
    cache_k, *more = zip(*pools)
    if not isinstance(like, tuple):
        cache_k, more = cache_k[0], [m[0] for m in more]
    return cache_k, more[0] if more else None, tuple(more[1:])



def _mlp(h, lp, cfg: LlamaConfig, valid=None, experts=None, logits=None):
    """Serving MLP: dense SwiGLU, or for expert configs the one dropless
    routed layer (``ops.moe.moe_mlp_routed``): no capacity, so a
    sequence's answer does not change with its batch, and rows that are
    not tokens (``valid`` [B, S] False: bucket padding, inactive slots)
    are given to no expert; ``lp`` and ``experts`` are ``_split_layers``'
    two halves; ``logits``: the router's, where ``_block`` computed them
    before attention (``cfg.router_input``); ``experts`` None: a dense
    layer (a dense config's, or a leading dense layer). Returns (out,
    counts): the layer's (expert rows, experts touched, rows routed to
    experts that are not here) int32 [3], None for a dense layer."""
    if experts is not None:
        from ..ops.moe import moe_mlp_routed

        shared = (lp["ws_gate"], lp["ws_up"], lp["ws_down"]) \
            if cfg.n_shared_experts else None
        return moe_mlp_routed(
            h, lp["router"], experts["w_gate"], experts["w_up"],
            experts["w_down"], top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, valid=valid,
            layer=lp["layer"], logits=logits, activation=cfg.expert_act,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            scale=cfg.routed_scale, held=cfg.experts_held, shared=shared)
    g = weight_einsum("bsd,dm->bsm", h, lp["w_gate"])
    u = weight_einsum("bsd,dm->bsm", h, lp["w_up"])
    return weight_einsum("bsm,md->bsd", jax.nn.silu(g) * u,
                         lp["w_down"]), None


def _embed(params, tokens, cfg: LlamaConfig):
    """The tokens' rows of the table, times ``cfg.embed_scale``."""
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    if cfg.embed_scale != 1.0:
        x = (x.astype(jnp.float32) * cfg.embed_scale).astype(x.dtype)
    return x


def _total(counts):
    """Per-layer (or per-step) expert counts stacked by a scan -> their
    sum; None stays None (a dense config counts nothing)."""
    return None if counts is None else counts.sum(0)


def _head(x, params, cfg: LlamaConfig):
    """Hidden [..., d] -> final norm -> f32 logits [..., vocab], raw or
    int8 lm_head. bf16 operands on the MXU with f32 accumulation either
    way."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.logit_divisor != 1.0:
        x = (x.astype(jnp.float32) / cfg.logit_divisor).astype(x.dtype)
    lm = params["lm_head"]
    if not is_quantized(lm):
        lm = lm.astype(cfg.dtype)
    return weight_einsum("...d,dv->...v", x.astype(cfg.dtype), lm,
                         preferred_element_type=jnp.float32)


def _pick(logits, greedy, seed, temperature, top_k, top_p):
    """``greedy=True`` (every request temperature==0) compiles an
    argmax-only epilogue: bit-identical results for greedy requests, and
    a program without the top_k/sort/categorical sampler. Whether the
    fork pays is not measured (ROADMAP R6 prices it)."""
    if greedy:
        return jnp.argmax(logits, axis=-1)
    return sample_from_logits(logits, seed, temperature, top_k, top_p)


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


def _write(pools, rows, block_tables, positions, valid):
    """``_write_rows`` for the pools of a layer group with an indexer
    inside a layer scan (``prefill_chunk``, ``verify_step``), where the
    scatter's window is one layer's: K and V by the scatter, the
    indexer's pool (one row a position, as a latent configuration's) by
    ``_write_latent``."""
    return _write_rows(pools[:2], rows[:2], block_tables, positions,
                       valid) + _write_latent(
                           pools[2], rows[2], block_tables, positions, valid)


def _write_latent(pool, rows, block_tables, positions, valid):
    """``_write_rows`` for the ONE pool of a latent configuration, a row
    at a time. pool [..., P, page, row]; rows [..., B, S, row] with the
    pool's leading dimensions; the rest as ``_write_rows``. A scatter
    would not do: XLA's scatter on a TPU wants the two fastest
    dimensions of the pool inside the window it writes, and with one row
    a position the second fastest is the position itself, so it turns
    the whole pool into another layout and back (two copies of 1.5 GB a
    burst, read from the compiled text). A loop of one row's slice of
    one layer updated in place has no such wish. A row that is not a
    token is written nowhere: its place is page 0's first row, its value
    what is there already. Returns the 1-tuple of the pool, as
    ``_write_rows``."""
    return (_write_slices(pool, rows, block_tables, positions, valid, 1),)


def _write_slices(pool, rows, block_tables, positions, valid, tail: int):
    """``_write_latent``'s loop. ``tail``: the dimensions a position's
    row has (1: a latent row, an indexer's key; 2: the heads and their
    width, for the K and V pools of a configuration with an indexer,
    whose burst is written here too: a window of all layers x 4 heads x
    128 made the scatter turn each 4 GB pool layers-inward and back,
    read from a compile for a v5e). Every other configuration's burst
    keeps ``_write_rows``: the accepted serve cells' ``tpot_p95_ms``
    was measured with the scatter, and whether this loop would serve
    them as well has not been measured (PERF.md section 7). Returns the
    pool."""
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
    """A whole prompt's latent rows into its pages, a PAGE at a time:
    pool [L, P, page, row]; rows [L, 1, S, row], position 0 first; table
    [1, max_pages]; prompt_lens [1]. The rows behind the prompt's end on
    its last page are written too (their positions are masked until a
    decode step writes them); a page wholly behind it is written
    nowhere (page 0 keeps what it holds). A position's row may have
    dimensions of its own (a K or V pool's [kvh, hd], for a
    configuration with an indexer)."""
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


def _pair_rows(rows):
    """K or V rows [..., S, kvh, hd] as the rows of the page matrices a
    configuration with state layers keeps (llm/cache.py): [..., S * kvh,
    hd], a (position, KV head) pair a row, position-major. Such a row's
    "position" is ``position * kvh + head`` and a page holds ``page_size
    * kvh`` of them, so the writers below serve them as they are."""
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
    pool float32 [L, P, per, kvh, hd] (llm/cache.py ``KVCache.c``); rows
    [L, B, K, kvh, hd], the burst's keys, row r of slot b at position
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


def _take_span(pool, block_tables):
    """The pages a table lists, side by side, of ONE layer's pool (inside
    a layer scan): pool [P, page, kvh, hd] -> [B, max_pages * page, kvh,
    hd]. A table's unused slots are 0 and read page 0: the caller masks
    by position. With no layer axis in front ``jnp.take`` is one gather;
    ``_gather_span``'s loop in its place ran ``verify_step`` 5 to 42%
    and ``prefill_chunk`` 3 to 7% slower (PERF.md, PR 32, call 9)."""
    B, n = block_tables.shape
    return jnp.take(pool, block_tables, axis=0).reshape(
        B, n * pool.shape[1], *pool.shape[2:])


def _gather_span(pool, pages):
    """The listed pages of every layer side by side, copied ONCE and
    straight to where the burst's layer scan slices them. pool [..., P,
    page, kvh, hd], the layers in front; pages int32 [G, T], a row a
    query row -> [..., G, T * page, kvh, hd]; or int32 [T], ONE list for
    every query row -> [..., kvh, T * page, hd], heads first, as the
    shared product wants its keys (``_attend``). A loop of
    one page's slice and its update in place: no transposition behind it
    (``jnp.take`` gathers page-major and a whole second copy turns it
    layer-major), no select (``dynamic_slice`` clamps, and a page index
    is never out of range) and no fill (the loop writes every row). A
    list's unused entries are 0 and read page 0: the caller masks by
    position or owner."""
    axis = pool.ndim - 4
    lead, (page, kvh, hd) = pool.shape[:axis], pool.shape[-3:]
    flat = pages.reshape(-1)
    shared = pages.ndim == 1

    def copy(t, out):
        rows = jax.lax.dynamic_slice_in_dim(pool, flat[t], 1, axis)
        if shared:
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.swapaxes(rows.reshape(*lead, page, kvh, hd),
                                  -2, -3), t * page, axis + 1)
        return jax.lax.dynamic_update_slice_in_dim(out, rows, t, axis)

    out = jax.lax.fori_loop(0, flat.size, copy, jax.lax.empty(
        (*lead, kvh, flat.size * page, hd) if shared
        else (*lead, flat.size, page, kvh, hd), pool.dtype))
    return out if shared else out.reshape(
        *lead, pages.shape[0], pages.shape[1] * page, kvh, hd)


def _attend(q, *segments):
    """Grouped-query attention over keys that lie in segments (a cached
    span; rows the cache does not hold yet), one softmax over all.

    q: [B, ..., heads, hd], with or without a query axis; a segment:
    (keys [B, S, kvh, hd], values, mask broadcastable to [B, ..., S]).
    Keys [kvh, S, hd], with no row axis, are ONE list that every query
    row scores, each under its own mask. The operands go to the MXU in
    their own dtype with f32 accumulation. Returns f32, in q's shape.
    """
    hd = q.shape[-1]
    kvh = segments[0][0].shape[-2 if segments[0][0].ndim == 4 else 0]
    qg = q.reshape(*q.shape[:-2], kvh, q.shape[-2] // kvh, hd)

    def product(lhs, rows, spec):
        # spec: the axis summed over, the axis kept. ``rows`` with no row
        # axis is the one shared list, and goes first: XLA's CPU backend
        # runs a bf16 product into f32 only in that order of operands
        if rows.ndim == 3:
            return jnp.einsum(f"gsd,b...gr{spec[0]}->b...gr{spec[1]}",
                              rows, lhs, preferred_element_type=jnp.float32)
        return jnp.einsum(f"b...gr{spec[0]},bsgd->b...gr{spec[1]}", lhs,
                          rows, preferred_element_type=jnp.float32)

    s = jnp.concatenate([
        jnp.where(mask[..., None, None, :],
                  product(qg, keys, "ds") * hd ** -0.5, -jnp.inf)
        for keys, _, mask in segments], axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(segments[0][0].dtype)
    outs, at = [], 0
    for _, values, _ in segments:
        end = at + values.shape[-3 if values.ndim == 4 else -2]
        outs.append(product(p[..., at:end], values, "sd"))
        at = end
    return sum(outs[1:], outs[0]).reshape(q.shape)


def _latent(h, lp, cfg: LlamaConfig, cos, sin, positions):
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


def _index(h, lp, cfg: LlamaConfig, positions):
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


def _heads(h, lp, lr, state, *, cfg: LlamaConfig, kind, cos, sin, positions,
           attend, lora_scale):
    """A layer of heads' attention half on the normalised input h: the
    three projections (plus a slot's LoRA deltas), QK-norm, rotary, with
    an indexer its three (``_index``), ``attend`` under the layer's
    span. Returns (o, kept)."""
    q = weight_einsum("bsd,dhk->bshk", h, lp["wq"])
    k = weight_einsum("bsd,dhk->bshk", h, lp["wk"])
    v = weight_einsum("bsd,dhk->bshk", h, lp["wv"])
    if lr:
        q = q + lora_delta(h, lr["a_q"], lr["b_q"], lora_scale,
                           cfg.n_heads, cfg.head_dim)
        v = v + lora_delta(h, lr["a_v"], lr["b_v"], lora_scale,
                           cfg.n_kv_heads, cfg.head_dim)
    q, k = qk_norm(q, k, lp, cfg)
    if rotated(kind):
        q = apply_rotary(q, cos, sin, positions=positions)
        k = apply_rotary(k, cos, sin, positions=positions)
    if cfg.own_weights:
        if linear(kind):
            # the closure opens rt.attn.linear; ``state`` is the layer's
            # place among the linear layers. The output norm over all heads
            o, kept = attend(q, k, v, state, None, linear=True)
            o = rms_norm(o.reshape(*o.shape[:2], -1), lp["o_norm"],
                         cfg.norm_eps).reshape(o.shape)
        else:
            # rt.attn.block.score, rt.attn.select and rt.attn.sparse
            o, kept = attend(q, k, v, state, None, block=True)
        gate = weight_einsum("bsd,dhk->bshk", h, lp["wg"],
                             preferred_element_type=jnp.float32)
        return o * jax.nn.sigmoid(gate), kept
    if cfg.sparse_top_k:
        # the closure opens rt.attn.select and rt.attn.sparse
        return attend(q, k, v, state, None,
                      index=_index(h, lp, cfg, positions))
    with jax.named_scope("rt.attn.window" if windowed(kind)
                         else "rt.attn.full"):
        return attend(q, k, v, state,
                      cfg.window if windowed(kind) else None)


def _attend_latent_pages(q, row, w, pools, table, positions, written, cfg,
                         past, own=None):
    """A latent layer's ``attend`` where the pool rides the layer scan
    (``prefill_chunk``, ``verify_step``): write the rows into the
    layer's pages, gather the table's span, and attend in the absorbed
    form over (the span under ``past``; with ``own``, the rows
    themselves under it). Returns (o [B, S, heads, v], the 1-tuple of
    the layer's pool)."""
    with jax.named_scope("rt.attn.mla.decode"):
        pools = _write_latent(pools[0], row, table, positions, written)
        segments = [(_take_span(pools[0], table), past)]
        if own is not None:
            segments.append((row.astype(pools[0].dtype), own))
        o = mla.attend_rows(mla.absorb_query(q, w[0], cfg.latent_row),
                            cfg.softmax_scale, cfg.kv_lora_rank, *segments)
        return mla.expand_output(o.astype(q.dtype), w[1]), pools


def _block(x, inputs, *, cfg: LlamaConfig, kind, cos, sin, positions, valid,
           attend, experts, lora_scale):
    """The decoder layer, once, as the body of a scan over layers.

    x: [B, S, d]; inputs: (the layer's weights, the layer's slice of the
    program's own state, the layer's per-slot adapter rows: low-rank
    deltas on wq/wv, llm/lora.py, empty = base model); ``kind``: the
    layer's (``LlamaConfig.layer_pattern``: rotated or not, windowed or
    not); positions: [B, S] rotary positions, None = 0..S-1; valid:
    [B, S], the rows that are tokens; ``attend(q, k, v, state, window)
    -> (o [B, S, heads, hd], kept)``, ``window`` the layer's or None; a
    latent layer hands it ``(q, the row to keep, (W_UK, W_UV), state,
    None)``. ``experts`` None: the layer's feed-forward is dense.
    Returns (x, (kept, expert counts: see ``_mlp``)).
    """
    lp, state, lr = inputs
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    # a router that reads the attention's input: its logits are known a
    # whole attention before the experts need them
    logits = router_logits(h, lp["router"]) if (
        experts is not None and cfg.router_input == "attention") else None
    if cfg.latent:
        q, row = _latent(h, lp, cfg, cos, sin, positions)
        # the program's closure opens the span: rt.attn.mla.prefill (the
        # expanded form) or rt.attn.mla.decode (the absorbed one)
        o, kept = attend(q, row, (lp["w_uk"], lp["w_uv"]), state, None)
    else:
        o, kept = _heads(h, lp, lr, state, cfg=cfg, kind=kind, cos=cos,
                         sin=sin, positions=positions, attend=attend,
                         lora_scale=lora_scale)
    def added(a):
        # what a layer's two halves add to the stream, times the scale
        if cfg.residual_scale == 1.0:
            return a
        return (a.astype(jnp.float32) * cfg.residual_scale).astype(x.dtype)

    x = x + added(weight_einsum("bshk,hkd->bsd", o.astype(x.dtype),
                                lp["wo"]))
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    m, counts = _mlp(h, lp, cfg, valid, experts, logits)
    return x + added(m), (kept, counts)


def _layers(params, cfg: LlamaConfig, cos, sin, lora=None):
    """-> ``run(x, state, attend, *, positions, valid)``: ONE
    ``lax.scan`` of ``_block`` over the stacked layers, returning (x,
    what ``attend`` kept, a tuple with [L_g, ...] a layer group, expert
    counts over the layers). ``state``: the program's own per-layer
    state, a tuple with one pytree a layer group (leading dimension L_g,
    the group's layers), or None. ``lora``: per-slot batched adapters
    from ``LoRAPool.select(ids)``, empty/None = base model. What every
    call shares is made here once: a burst calls ``run`` at every step.

    A configuration with a layer pattern of ``n`` kinds is scanned a
    period at a time: the body runs the period's ``n`` layers in turn,
    each with its kind, its weights and its place in its group's state,
    all taken by the layer's index."""
    layers, experts = _split_layers(params["layers"], cfg)
    n_dense, dense = cfg.n_dense_layers, params.get("dense_layers")
    # adapters ride the layer scan as xs: [B, L, ...] -> [L, B, ...]
    lora_xs = {} if not lora else {
        k2: jnp.swapaxes(v2, 0, 1) for k2, v2 in lora.items()
        if k2 != "scale"}
    kinds = cfg.layer_kinds
    if lora_xs and (len(kinds) > 1 or cfg.latent or n_dense):
        # LLMEngine refuses lora_rank with these; adapters ride the one
        # scan over layers and add to wq and wv
        raise ValueError("adapters are not supported with a layer "
                         "pattern, latent attention or leading dense "
                         "layers")
    places = [cfg.layer_group(j) for j in range(len(kinds))]
    n_groups = len(cfg.kv_groups)

    per_group = [sum(g == h for g, _ in places) for h in range(n_groups)]

    def at(tree, i):
        """Layer ``i`` (a traced index) of a stacked tree: the slice a
        scan over layers would hand its body, taken where it is used."""
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), tree)

    def run(x, state, attend, *, positions, valid):
        block = partial(_block, cfg=cfg, cos=cos, sin=sin,
                        positions=positions, valid=valid, attend=attend,
                        experts=experts,
                        lora_scale=lora["scale"] if lora else None)
        if cfg.own_weights:
            # kinds with weights of their own: the layers one after the
            # other, each with its weights and its state by its place IN
            # ITS KIND. A linear layer's state is the program's (a pool a
            # slot, reached through ``attend``), which is told the place
            kept, place = [], {True: 0, False: 0}
            for kind in kinds:
                lin = linear(kind)
                i = place[lin]
                place[lin] += 1
                lp = jax.tree.map(lambda a: a[i], params[
                    "linear_layers" if lin else "layers"])
                mine = i if lin or state is None else jax.tree.map(
                    lambda a: a[i], state[0])
                x, (rows, _) = block(x, (lp, mine, {}), kind=kind)
                if not lin:
                    kept.append(rows)
            return x, (jax.tree.map(lambda *a: jnp.stack(a), *kept),), None
        if len(kinds) == 1:
            mine = None if state is None else state[0]
            if n_dense:
                # the leading dense layers first: the same block, the
                # dense feed-forward, the cache's first layers
                x, (first, _) = jax.lax.scan(
                    partial(block, kind=kinds[0], experts=None), x,
                    (dense, None if mine is None else jax.tree.map(
                        lambda a: a[:n_dense], mine), {}))
                if mine is not None:
                    mine = jax.tree.map(lambda a: a[n_dense:], mine)
            x, (kept, counts) = jax.lax.scan(
                partial(block, kind=kinds[0]), x, (layers, mine, lora_xs))
            if n_dense:
                kept = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], 0), first, kept)
            return x, (kept,), _total(counts)

        def period(x, p):
            # every layer takes its own slices by its index: handed the
            # period's slices as one array, the layers would each copy
            # theirs out of it (a burst's keys, read twice a step)
            kept, counts = [[] for _ in range(n_groups)], []
            for j, (kind, (g, place)) in enumerate(zip(kinds, places)):
                layer = p * len(kinds) + j
                x, (rows, n) = block(
                    x, (at(layers, layer), None if state is None else at(
                        state[g], p * per_group[g] + place), {}), kind=kind)
                kept[g].append(rows)
                counts.append(n)
            return x, (tuple(jax.tree.map(lambda *a: jnp.stack(a), *rows)
                             for rows in kept),
                       None if counts[0] is None else sum(counts))

        x, (kept, counts) = jax.lax.scan(
            period, x, jnp.arange(cfg.n_layers // len(kinds)))
        return x, tuple(jax.tree.map(
            lambda a: a.reshape(-1, *a.shape[2:]), rows)
            for rows in kept), _total(counts)

    return run


def _held(table, positions, valid, page_size: int):
    """``valid`` without the rows whose table entry is the reserved page
    0: a page a window group's sequence gave back (or never asked for)
    is written nowhere. table [B, n]; positions, valid [B, S]."""
    page = jnp.take_along_axis(
        table, jnp.clip(positions // page_size, 0, table.shape[1] - 1),
        axis=1)
    return valid & (page > 0)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=_POOLS)
def prefill(params, cache_k, cache_v, tokens, prompt_lens, block_tables,
            cos, sin, lora=None, cache_i=None, cache_c=None, cache_s=None,
            slots=None, *, cfg: LlamaConfig):
    """Process full prompts, fill their pages, return last-token logits.

    tokens: [B, S] right-padded; prompt_lens: [B]; block_tables: [B, Pmax];
    ``lora``: see ``_layers``; the pools and tables one a layer group
    (``_groups``). The layers hand their K and V rows out of
    the scan ([L, B, S, kvh, hd], in the cache's dtype); padding rows
    (position >= prompt_len) are dropped. Attention is told the prompts'
    lengths: on the TPU the flash kernel leaves out the query blocks
    that hold no token (``flash_attention_tpu``), everything else in a
    layer still runs the bucket's rows.

    Returns (logits [B, vocab], cache_k, cache_v, expert counts: see
    ``_mlp``; None for a dense config), and behind them ``cache_i``
    where the configuration has an indexer (every program does).
    """
    B, S = tokens.shape
    if (cfg.latent or cfg.sparse_top_k or cfg.own_weights) and B != 1:
        raise ValueError("a latent configuration's prefill, and one's "
                         "with an indexer or with state layers, writes ONE "
                         "prompt's rows a page at a time "
                         "(_write_latent_pages), as the "
                         f"engine asks: B == 1, not {B}")
    pools = _pools(cache_k, cache_v, cache_i)
    # the state pool, as the linear layers leave it one after the other
    box = {"s": cache_s}
    sizes = cfg.block_sizes
    tables = _groups(block_tables)
    page_size = pools[0][0].shape[2]     # read by the window groups alone
    x = _embed(params, tokens, cfg)
    pos_grid = jnp.arange(S)[None, :].repeat(B, 0)
    valid = pos_grid < prompt_lens[:, None]                    # [B, S]
    # a window layer hands out the rows that can still be inside the
    # window at the prompt's end, not the bucket's: ``kept_rows`` of
    # them, from ``kept_from`` [B] on (the engine holds pages from the
    # one that position prompt_len - window + 1 lies on)
    kept_rows = {w: min(S, -(-w // page_size) * page_size + page_size)
                 for w in cfg.kv_groups if w is not None}
    kept_from = {w: jnp.clip(
        jnp.maximum(prompt_lens - w + 1, 0) // page_size * page_size,
        0, S - n) for w, n in kept_rows.items()}

    def attend(q, k, v, place, window, index=None, linear=False,
               block=False):
        if linear:
            # from a zero state, whatever the slot held: the state behind
            # the prompt's last token goes to the slot's place in the pool
            o, end = linear_attention.prefill(
                q, k, v, cfg.linear_decay, prompt_lens,
                scale=cfg.softmax_scale)
            box["s"] = jax.lax.dynamic_update_slice(
                box["s"], end[None], (place, slots[0], 0, 0, 0))
            return o, None
        if block:
            # queries below dense_len (all of them in a bucket that
            # short) attend over every visible key: the flash forward;
            # the rest over the blocks they choose
            sums = sparse.stride_sums(k, valid, sizes.stride)
            D = sizes.dense_len
            o = attention(q[:, :D], k[:, :D], v[:, :D], causal=True,
                          lengths=jnp.minimum(prompt_lens, D))
            if S > D:
                o = jnp.concatenate([o, sparse.block_attend(
                    q[0, D:], k[0], v[0], sums[0],
                    jnp.where(valid[0, D:], pos_grid[0, D:], -1), sizes,
                    scale=cfg.softmax_scale)[None].astype(o.dtype)], 1)
            return o, (k.astype(pools[0][0].dtype),
                       v.astype(pools[0][1].dtype), sums)
        if index is not None:
            # at most top_k keys in the bucket: every visible key is
            # chosen, the dense path; else the indexer's choice a query
            qi, w, ki = index
            if S <= cfg.sparse_top_k:
                o = attention(q, k, v, causal=True, lengths=prompt_lens)
            else:
                o = sparse.attend(
                    q, k, v, qi, w, ki,
                    jnp.where(valid, pos_grid + 1, 0),
                    top_k=cfg.sparse_top_k, scale=cfg.softmax_scale)
            return o, tuple(r.astype(c.dtype)
                            for r, c in zip((k, v, ki), pools[0]))
        if cfg.latent:
            # the expanded form: every head's keys and values multiplied
            # out of the rows, which alone leave the layer scan
            with jax.named_scope("rt.attn.mla.prefill"):
                keys, values = mla.expand(k, *v, cfg.n_heads,
                                          cfg.qk_rope_dim)
                o = attention(q, keys, values, causal=True,
                              scale=cfg.softmax_scale, lengths=prompt_lens)
            return o, (k.astype(pools[0][0].dtype),)
        # right padding is safe under the causal mask (a real position
        # only attends to earlier, real, positions) and, told where the
        # prompt ends, costs the kernel only the rest of the prompt's
        # last block: the blocks behind it come back as zeros
        o = attention(q, k, v, causal=True, window=window,
                      lengths=prompt_lens)
        if window is not None and kept_rows[window] < S:
            k, v = (jax.vmap(lambda rows, at: jax.lax.dynamic_slice_in_dim(
                rows, at, kept_rows[window], 0))(rows, kept_from[window])
                for rows in (k, v))
        return o, (k.astype(pools[0][0].dtype), v.astype(pools[0][1].dtype))

    x, rows, counts = _layers(params, cfg, cos, sin, lora)(
        x, None, attend, positions=None, valid=valid)
    written = []
    for window, pool, table, kept in zip(cfg.kv_groups, pools, tables,
                                         rows):
        if cfg.own_weights:
            # the ONE prompt's K and V rows a page at a time, and the
            # strides' sums likewise (a page of them is ``per`` rows)
            cache_c, = _write_latent_pages(
                cache_c, kept[2], table, -(-prompt_lens // sizes.stride))
            written.append(sum((_write_latent_pages(
                c, _pair_rows(r), table, prompt_lens * cfg.n_kv_heads)
                for c, r in zip(pool, kept[:2])), ()))
            continue
        if cfg.latent:
            written.append(
                _write_latent_pages(pool[0], kept[0], table, prompt_lens))
            continue
        if window is None:
            if len(pool) == 2:
                written.append(_write_rows(pool, kept, table, pos_grid,
                                           valid))
            else:
                # with an indexer the ONE prompt's rows go a page at a
                # time into all three pools (``_write_slices`` says why)
                written.append(sum((_write_latent_pages(
                    c, r, table, prompt_lens)
                    for c, r in zip(pool, kept)), ()))
            continue
        at = kept_from[window][:, None] + jnp.arange(kept_rows[window])
        written.append(_write_rows(
            pool, kept, table, at,
            _held(table, at, at < prompt_lens[:, None], page_size)))
    cache_k, cache_v, rest = _ungrouped(written, block_tables)
    x_last = jnp.take_along_axis(
        x, jnp.maximum(prompt_lens - 1, 0)[:, None, None], axis=1)[:, 0]
    if cfg.own_weights:
        rest = (cache_c, box["s"])
    return (_head(x_last, params, cfg), cache_k, cache_v, counts, *rest)


def page_bucket(pages: int, most: int, floor: int = 16) -> int:
    """Power-of-2 bucket of a list of pages (a table's span, a burst's
    flat list), capped at the most there can be: one compiled decode
    program a bucket."""
    b = floor
    while b < pages:
        b *= 2
    return min(b, most)


# Rows between two powers of two that a whole prompt may be padded to.
# Every layer's projections, norms and expert products run the bucket's
# rows, not the prompt's tokens, so a rung takes a quarter off the prefill
# of every prompt it catches; it costs a program more (one the engine
# loads before it is ready: ``LLMEngine.load_prefill_programs``), so a
# rung is added where a cell's prompts crowd: 12,288 is a whole number of
# every tile the prefill kernels use (24 x 512). The next rung is one
# entry here.
PREFILL_RUNGS = (12288,)


def prefill_bucket(seq_len: int, max_seq: int, floor: int = 16,
                   rungs=PREFILL_RUNGS) -> int:
    """The rows a whole prompt of ``seq_len`` tokens is padded to, one
    compiled prefill a bucket: the next power of two, or a rung of
    ``rungs`` that holds the prompt and lies below it; never above
    ``max_seq``."""
    return min([page_bucket(seq_len, max_seq, floor)]
               + [r for r in rungs if seq_len <= r])


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=_POOLS)
def prefill_chunk(params, cache_k, cache_v, tokens, start_pos, chunk_len,
                  block_tables, cos, sin, cache_i=None, cache_c=None,
                  cache_s=None, slots=None, *, cfg: LlamaConfig):
    """One CHUNK of a long prompt (vLLM's chunked prefill, rebuilt for
    static shapes): tokens [1, C] are positions [start_pos,
    start_pos+chunk_len), attending causally within the chunk AND over
    the pages written by earlier chunks. One compiled executable per (C,
    table-span) pair serves prompts of every length, and decode bursts
    for other requests interleave between chunks.

    Returns (logits [1, vocab] of the chunk's LAST VALID token,
    cache_k, cache_v, expert counts as ``prefill``).
    """
    B, C = tokens.shape
    # the sums of strides ride as a third pool of the block layers' group
    pools = _pools(cache_k, cache_v, cache_c if cfg.own_weights
                   else cache_i)
    box = {"s": cache_s}
    sizes = cfg.block_sizes
    tables = dict(zip(cfg.kv_groups, _groups(block_tables)))
    page_size = pools[0][0].shape[2]
    if cfg.own_weights:         # a page's rows are (position, KV head) pairs
        page_size //= cfg.n_kv_heads
    Spast = _groups(block_tables)[0].shape[1] * page_size
    x = _embed(params, tokens, cfg)
    pos_grid = start_pos + jnp.arange(C)[None, :]          # [1, C]
    valid = jnp.arange(C)[None, :] < chunk_len
    # past pages hold positions < start_pos (written by earlier chunks)
    past_mask = jnp.arange(Spast)[None, None, :] < start_pos
    chunk_mask = (jnp.arange(C)[None, :, None]
                  >= jnp.arange(C)[None, None, :]) & valid[:, None, :]

    def attend(q, k, v, pools, window, index=None, linear=False,
               block=False):
        if linear:
            # the slot's state (zeroed when the request was admitted)
            # carried from chunk to chunk through the pool
            at = (pools, slots[0], 0, 0, 0)
            o, end = linear_attention.prefill(
                q, k, v, cfg.linear_decay, chunk_len.reshape(1),
                jax.lax.dynamic_slice(
                    box["s"], at, (1, 1, *box["s"].shape[2:]))[0],
                scale=cfg.softmax_scale)
            box["s"] = jax.lax.dynamic_update_slice(box["s"], end[None], at)
            return o, None
        table, past, own, rows = tables[window], past_mask, chunk_mask, valid
        if block:
            # the chunk's rows into the pages, then the span through the
            # pages, the chunk's own rows among it; the strides' sums of
            # the whole span from its keys, and back into their pool
            at, ok = _pair_positions(pos_grid, rows, cfg.n_kv_heads)
            pk, pv = (_write_slices(pool, _pair_rows(new), table, at, ok, 1)
                      for pool, new in zip(pools[:2], (k, v)))
            sk, sv = (_take_span(pool, table).reshape(B, Spast, *k.shape[2:])
                      for pool in (pk, pv))
            cached = jnp.arange(Spast)[None, :] < start_pos + chunk_len
            sums = sparse.stride_sums(sk, cached, sizes.stride)
            strides = jnp.arange(Spast // sizes.stride)[None, :]
            pc, = _write_rows(
                pools[2:], (sums,), table, strides,
                strides * sizes.stride < start_pos + chunk_len)
            o = sparse.block_attend(
                q[0], sk[0], sv[0], sums[0],
                jnp.where(valid[0], pos_grid[0], -1), sizes,
                scale=cfg.softmax_scale)[None]
            return o, (pk, pv, pc)
        if cfg.latent:
            return _attend_latent_pages(q, k, v, pools, table, pos_grid,
                                        rows, cfg, past, own)
        if index is not None:
            # the chunk's queries score the cached rows below its start
            # and the chunk's own rows up to themselves
            qi, w, ki = index
            pools = _write(pools, (k, v, ki), table, pos_grid, rows)
            keys = [jnp.concatenate([_take_span(pool, table),
                                     new.astype(pool.dtype)], 1)
                    for pool, new in zip(pools, (k, v, ki))]
            o = sparse.attend(
                q, keys[0], keys[1], qi, w, keys[2],
                jnp.where(valid, start_pos, 0),
                jnp.where(valid, jnp.arange(C)[None, :] + 1, 0), Spast,
                top_k=cfg.sparse_top_k, scale=cfg.softmax_scale)
            return o, pools
        if window is not None:
            rows = _held(table, pos_grid, valid, page_size)
            past = past & (jnp.arange(Spast)[None, None, :]
                           > pos_grid[:, :, None] - window)
            own = own & (jnp.arange(C)[None, :, None]
                         - jnp.arange(C)[None, None, :] < window)
        pools = _write_rows(pools, (k, v), table, pos_grid, rows)
        pk, pv = (_take_span(pool, table) for pool in pools)
        return _attend(q, (pk, pv, past), (k, v, own)), pools

    x, pools, counts = _layers(params, cfg, cos, sin)(
        x, pools, attend, positions=pos_grid, valid=valid)
    cache_k, cache_v, rest = _ungrouped(pools, block_tables)
    idx = jnp.broadcast_to(jnp.maximum(chunk_len - 1, 0).reshape(1, 1, 1),
                           (B, 1, 1))
    x_last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
    if cfg.own_weights:
        rest = (*rest, box["s"])
    return (_head(x_last, params, cfg), cache_k, cache_v, counts, *rest)


@partial(jax.jit, static_argnames=("cfg", "greedy"), donate_argnames=_POOLS)
def verify_step(params, cache_k, cache_v, tokens, positions, block_tables,
                cos, sin, seed, temperature, top_k, top_p, cache_i=None,
                cache_c=None, cache_s=None, *, cfg: LlamaConfig,
                greedy: bool = False):
    """Batched multi-token verification forward (speculative decoding,
    Leviathan et al. ICML'23 — PAPERS.md): score a whole k-token draft
    window in ONE dispatch, like a short prefill over the paged cache.

    tokens: [B, S] window tokens (row = [last_emitted, d_1 .. d_k]);
    positions: [B, S] absolute per-token positions, -1 = padding (rows
    with shorter windows, undrafted slots), written nowhere. Every valid
    window token's KV is WRITTEN first, then attention gathers the
    pages, masked by key_pos <= query_pos: the window's own keys are
    visible through the pages, and stale rows from a previous rejected
    window sit at positions > query_pos and never score.

    Returns (argmax tokens [B, S] — index j predicts the token AFTER
    window position j, sampled position-0 token [B] for rows that
    aren't greedy, cache_k, cache_v, expert counts as ``prefill``).
    """
    if cfg.own_weights:
        raise ValueError(
            "verify_step is not written for linear layers: a window that "
            "is rejected would have to roll a slot's state back, and the "
            "state keeps no token apart (LLMEngine refuses speculation "
            "with them)")
    pools = _pools(cache_k, cache_v, cache_i)
    tables = dict(zip(cfg.kv_groups, _groups(block_tables)))
    page_size = pools[0][0].shape[2]
    Sall = _groups(block_tables)[0].shape[1] * page_size
    x = _embed(params, tokens, cfg)
    valid = positions >= 0
    qpos = jnp.maximum(positions, 0)                       # [B, S]
    # unused table slots are 0 (the reserved page) but sit past the row's
    # provisioned span, so their key positions exceed every query's
    kmask = (jnp.arange(Sall)[None, None, :]
             <= qpos[:, :, None])                          # [B, S, Sall]

    def attend(q, k, v, pools, window, index=None):
        table, seen, rows = tables[window], kmask, valid
        if cfg.latent:
            return _attend_latent_pages(q, k, v, pools, table, qpos, rows,
                                        cfg, seen)
        if index is not None:
            # the window's own rows are scored through the pages too
            qi, w, ki = index
            pools = _write(pools, (k, v, ki), table, positions, rows)
            pk, pv, pi = (_take_span(pool, table) for pool in pools)
            o = sparse.attend(
                q, pk, pv, qi, w, pi,
                jnp.where(valid, qpos + 1, 0),
                top_k=cfg.sparse_top_k, scale=cfg.softmax_scale)
            return o, pools
        if window is not None:
            rows = _held(table, qpos, valid, page_size)
            seen = seen & (jnp.arange(Sall)[None, None, :]
                           > qpos[:, :, None] - window)
        pools = _write_rows(pools, (k, v), table, positions, rows)
        pk, pv = (_take_span(pool, table) for pool in pools)
        return _attend(q, (pk, pv, seen)), pools

    x, pools, counts = _layers(params, cfg, cos, sin)(
        x, pools, attend, positions=qpos, valid=valid)
    cache_k, cache_v, rest = _ungrouped(pools, block_tables)
    logits = _head(x, params, cfg)
    tgt = jnp.argmax(logits, axis=-1)                      # [B, S]
    samp0 = tgt[:, 0] if greedy else sample_from_logits(
        logits[:, 0], seed, temperature, top_k, top_p)
    return (tgt, samp0, cache_k, cache_v, counts, *rest)


@jax.jit
def sample_logits(logits, seed, temperature, top_k, top_p):
    """Standalone sampler dispatch (the chunked-prefill tail — the
    whole-prompt path fuses sampling into prefill_sample instead)."""
    return sample_from_logits(logits, seed, temperature, top_k, top_p)


# --- fused step functions: model + sampler in ONE dispatch, which saves a
# host round trip per token (what that is worth: not measured) ---

@partial(jax.jit, static_argnames=("cfg", "greedy"), donate_argnames=_POOLS)
def prefill_sample(params, cache_k, cache_v, tokens, prompt_lens,
                   block_tables, cos, sin, seed, temperature, top_k, top_p,
                   lora=None, cache_i=None, cache_c=None, cache_s=None,
                   slots=None, *, cfg: LlamaConfig, greedy: bool = False):
    """``prefill`` with the sampler behind it (``greedy``: see ``_pick``)."""
    logits, *rest = prefill.__wrapped__(
        params, cache_k, cache_v, tokens, prompt_lens, block_tables,
        cos, sin, lora, cache_i, cache_c, cache_s, slots, cfg=cfg)
    return (_pick(logits, greedy, seed, temperature, top_k, top_p), *rest)


@partial(jax.jit, donate_argnames=_POOLS,
         static_argnames=("cfg", "n_steps", "paged_kernel", "greedy"))
def decode_burst(params, cache_k, cache_v, tokens, positions, block_tables,
                 active, cos, sin, seed, temperature, top_k, top_p,
                 lora=None, gather=None, steps=None, cache_i=None,
                 cache_c=None, cache_s=None, *, cfg: LlamaConfig,
                 n_steps: int, paged_kernel: bool = None,
                 greedy: bool = False):
    """Up to n_steps fused decode+sample steps, sampled tokens fed back
    ON-DEVICE (multi-step scheduling, vLLM's --num-scheduler-steps
    analog). One host round trip yields a burst of tokens per slot; the
    best depth is not measured (ROADMAP R6).

    The big cache never rides the step loop's carry (that would copy it
    every step): the old context is copied ONCE a burst, the burst's rows
    accumulate in a [L, B, n_steps] scratch and scatter once at the end,
    rows of inactive slots and of steps not run dropped.

    The pools, ``block_tables`` and ``gather`` are one a layer group
    (``_groups``): a window group's list holds only the pages still
    inside the window, so a burst neither copies nor reads a key that
    has left it, and its masks keep a step's query to its window.

    ``gather``: int32 [3, T], ONE flat list of the LIVE pages, those that
    hold old context of decoding slots: each one's (page, owner slot,
    first position); a page two slots share is listed once for each, an
    entry that lists nothing has owner -1 (and page 0). Every slot scores
    every listed key and keeps its own, so cache traffic follows the live
    context. None: the rectangle of the whole of ``block_tables``, row b
    slot b's pages and a slot scoring only its row: the worst case.
    Either way a slot's keys are those it owns at positions below its
    own: one softmax over them and the burst's rows.

    A latent configuration copies nothing: ``gather`` is int32 [B, n],
    the block tables cut to the pages that can hold old context (None:
    the whole of ``block_tables``), and every step's attention reads
    each slot's own pages of it straight from the pool, up to the slot's
    own length (``ops/mla.py`` ``decode_attention``), so a step's
    attention costs what the slot's context costs.

    A configuration with an indexer copies no K or V either, and takes
    the same ``gather``: every step gathers the INDEXER's rows of each
    slot's own pages (a sixteenth of its K and V), scores them and the
    burst's own and chooses (``ops/sparse_attention.py``
    ``decode_chosen``), then reads each slot's own K and V pages straight
    from the pools up to the slot's length and attends under the choice
    (``decode_attention``), joined with the burst's own chosen rows by
    the log-sum-exp: the pages' bytes (1.2 ms a step at the cell's
    spans) cost less than counting the chosen rows out and gathering
    them did (13 to 15 ms a step; PR 44).

    ``steps``: int32 scalar, the steps to run (<= n_steps, which is only
    the capacity: scratch rows and the returned [n_steps, B]); None runs
    them all. A width is an operand, not a program.

    Returns (tokens [n_steps, B], rows past ``steps`` 0; cache_k,
    cache_v; expert counts over all steps and layers as ``prefill``).
    The host must have pre-provisioned pages for positions ..
    positions+steps-1 in ``block_tables`` (the full-width table: only
    ``_write_rows`` reads it).
    """
    if paged_kernel:
        # the keyword stays only because benchmarks/aot_fit.py:73 passes
        # paged_kernel=False; it goes when a benchmark PR drops it there
        raise ValueError("decode_burst has one attention path: the paged "
                         "kernel was deleted (PR 31)")
    B, K = tokens.shape[0], n_steps
    pools = _pools(cache_k, cache_v, cache_i)
    tables = _groups(block_tables)
    gathers = (None,) * len(pools) if gather is None else _groups(gather)
    page_size = pools[0][0].shape[2]
    # old context copied ONCE a burst (read-only during it), and who may
    # score it: [L, B, n * page, kvh, hd] for the table's rectangle, or
    # [L, kvh, T * page, hd] for one flat list; the burst's own rows are
    # [L, B, K, kvh, hd]; all of it a layer group
    old, old_mask, key_pos = [], {}, {}
    sizes = cfg.block_sizes
    for window, pool, table, listed in zip(cfg.kv_groups, pools, tables,
                                           gathers):
        if cfg.own_weights:
            # no K or V is copied; the strides' sums of each slot's own
            # pages are, ONCE a burst for all block layers (a sixteenth
            # of K): [L, B, n * per, kvh, hd]
            span = table if listed is None else listed
            L, P = cache_c.shape[:2]
            sums = jnp.take(
                cache_c.reshape(L * P, *cache_c.shape[2:]),
                jnp.arange(L)[:, None, None] * P + span[None],
                axis=0).reshape(L, B, -1, *cache_c.shape[3:])
            # a stride that holds no cached position holds what the page
            # held before: nothing of this sequence
            held = (jnp.arange(sums.shape[2])[None, :] * sizes.stride
                    < positions[:, None])
            old.append((jnp.arange(L, dtype=jnp.int32), jnp.where(
                held[None, ..., None, None], sums, 0.0)))
            continue
        if cfg.latent or cfg.sparse_top_k:
            # no copy: a layer's state is its index into the pool
            old.append((jnp.arange(cfg.n_layers, dtype=jnp.int32),))
            span = table if listed is None else listed
            continue
        if listed is None:
            pages = table
            at = jnp.arange(pages.shape[1] * page_size)[None, :]
            mask = at < positions[:, None]                     # [B, S]
        else:
            pages, owner, first = listed
            at = (first[:, None] + jnp.arange(page_size)).reshape(-1)[None]
            mask = ((jnp.repeat(owner, page_size)[None, :]
                     == jnp.arange(B)[:, None])
                    & (at < positions[:, None]))               # [B, S]
        old.append(tuple(_gather_span(c, pages) for c in pool))
        old_mask[window], key_pos[window] = mask, at
    scratch = tuple(tuple(
        jnp.zeros((c.shape[0], B, K, *((cfg.n_kv_heads, cfg.head_dim)
                                       if cfg.own_weights else c.shape[3:])),
                  c.dtype) for c in pool)
        for pool in pools)
    layers = _layers(params, cfg, cos, sin, lora)
    n_run = K if steps is None else steps

    box = {}
    # the slots that decode, in the order the state kernel walks them:
    # the same for every layer and step
    live_slots = linear_attention.live_order(active) if cfg.own_weights \
        else None

    def step(i, carry):
        toks, scratch, out, total, box["s"] = carry
        x = _embed(params, toks, cfg)[:, None, :]
        new_mask = jnp.arange(K)[None, :] <= i                 # [1, K]

        def attend(q, k, v, state, window, index=None, linear=False,
                   block=False):
            if linear:
                # every live slot's state of the layer advanced by the
                # step's token, in place in the pool (``state``: the
                # layer's place in it)
                o, box["s"] = linear_attention.decode_step(
                    q[:, 0], k[:, 0], v[:, 0], box["s"], state, active,
                    cfg.linear_decay, scale=cfg.softmax_scale,
                    order=live_slots)
                return o[:, None], None
            if block:
                # the slot's pages chosen by the scores of its strides'
                # sums and the burst's own keys; the chosen pages' K and
                # V read where they lie, the burst's rows (always of the
                # newest blocks, always chosen) joined from scratch
                layer, sums, nk, nv = state
                nk, nv = (jax.lax.dynamic_update_slice_in_dim(
                    rows, new.astype(rows.dtype), i, 1)
                    for rows, new in ((nk, k), (nv, v)))
                listed = sparse.block_decode_pages(
                    q[:, 0], sums, nk, i + 1, span, positions, sizes,
                    scale=cfg.softmax_scale)
                o, lse = sparse.block_decode_attention(
                    q[:, 0], *pools[0][:2], layer, *listed,
                    kvh=cfg.n_kv_heads, scale=cfg.softmax_scale)
                o = sparse.join_new_rows(
                    o, lse, q[:, 0], nk, nv,
                    jnp.broadcast_to(new_mask, nk.shape[:2]),
                    scale=cfg.softmax_scale)
                return o[:, None], (nk, nv)
            if index is not None:
                # the slot's indexer rows and the burst's own, scored and
                # chosen from; the slot's K and V pages read where they
                # lie under the choice, the burst's rows joined from
                # scratch: one softmax
                layer, nk, nv, ni = state
                qi, w, ki = index
                nk, nv, ni = (jax.lax.dynamic_update_slice_in_dim(
                    rows, new.astype(rows.dtype), i, 1)
                    for rows, new in ((nk, k), (nv, v), (ni, ki)))
                chosen, own = sparse.decode_chosen(
                    qi[:, 0], w[:, 0], pools[0][2], layer, span, positions,
                    ni, i + 1, top_k=cfg.sparse_top_k)
                o, lse = sparse.decode_attention(
                    q[:, 0], *pools[0][:2], layer, span, positions, chosen,
                    scale=cfg.softmax_scale)
                o = sparse.join_new_rows(o, lse, q[:, 0], nk, nv, own,
                                         scale=cfg.softmax_scale)
                return o[:, None], (nk, nv, ni)
            if cfg.latent:
                # absorbed: the slot's cached rows where they lie, then
                # the burst's own rows up to this step, one softmax
                layer, rows = state
                rows = jax.lax.dynamic_update_slice_in_dim(
                    rows, k.astype(rows.dtype), i, 1)
                with jax.named_scope("rt.attn.mla.decode"):
                    ql = mla.absorb_query(q[:, 0], v[0], cfg.latent_row)
                    seen = dict(scale=cfg.softmax_scale,
                                rank=cfg.kv_lora_rank)
                    o, lse = mla.decode_attention(
                        ql, pools[0][0], layer, span, positions, **seen)
                    o = mla.join_new_rows(o, lse, ql, rows, new_mask,
                                          **seen)
                    o = mla.expand_output(o.astype(q.dtype), v[1])
                return o[:, None], (rows,)
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
            o = _attend(q[:, 0], (ok, ov, seen), (nk, nv, own))
            return o[:, None], (nk, nv)

        x, scratch, counts = layers(
            x, tuple(o + s for o, s in zip(old, scratch)), attend,
            positions=(positions + i)[:, None], valid=active[:, None])
        newt = _pick(_head(x[:, 0], params, cfg), greedy, seed + i,
                     temperature, top_k, top_p)
        newt = jnp.where(active, newt, toks).astype(out.dtype)
        out = jax.lax.dynamic_update_slice_in_dim(out, newt[None], i, 0)
        return (newt, scratch, out,
                None if counts is None else total + counts, box["s"])

    _, scratch, out, counts, cache_s = jax.lax.fori_loop(
        0, n_run, step,
        (tokens, scratch, jnp.zeros((K, B), tokens.dtype),
         jnp.zeros(3, jnp.int32) if cfg.n_experts else None, cache_s))
    # one scatter of the whole burst into the paged cache
    p_grid = positions[:, None] + jnp.arange(K)[None, :]       # [B, K]
    written = active[:, None] & (jnp.arange(K)[None, :] < n_run)

    def burst_rows(pool, rows, table):
        if cfg.own_weights:
            at, ok = _pair_positions(p_grid, written, cfg.n_kv_heads)
            return tuple(_write_slices(c, _pair_rows(r), table, at, ok, 1)
                         for c, r in zip(pool, rows))
        if cfg.latent:
            return _write_latent(pool[0], rows[0], table, p_grid, written)
        if cfg.sparse_top_k:
            # K and V by slices too: ``_write_slices`` says why
            return tuple(_write_slices(c, r, table, p_grid, written,
                                       c.ndim - 3)
                         for c, r in zip(pool, rows))
        return _write_rows(pool, rows, table, p_grid, written)

    cache_k, cache_v, rest = _ungrouped(
        [burst_rows(*group) for group in zip(pools, scratch, tables)],
        block_tables)
    if cfg.own_weights:
        rest = (_add_to_sums(cache_c, scratch[0][0], tables[0], positions,
                             written, sizes.stride), cache_s)
    return (out, cache_k, cache_v, counts, *rest)
