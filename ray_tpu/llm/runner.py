"""Model runner: the serving decoder layer, written once, and the four
jitted programs that run it over a cache of any kind (``llm/kinds``).

One block. ``_block`` is the decoder layer: norm, the attention half,
``wo``, norm, the feed-forward (dense SwiGLU or the dropless routed
experts); ``_layers`` makes the one ``lax.scan`` of it over the stacked
layers. The attention half is the cache kind's (``kinds.of(cfg).heads``),
because what a layer projects is what its cache keeps. A program is what
all kinds share (embedding, positions, the rows that are tokens,
``_layers``, head, sampler, a burst's step loop) and ONE call into the
kind, which hands back ``attend`` and what writes the cache behind it;
nothing in this file asks the configuration which kind it has:
  * ``prefill`` (whole prompts; ``prefill_sample`` fuses the sampler)
    attends over the prompt's own rows, reads no page, and hands what
    the layers keep out of the scan; the kind writes it at the end.
  * ``prefill_chunk`` (one chunk of a long prompt) writes the chunk's
    rows into the layer's pages, gathers the table's span, and attends
    over (the span below the chunk's start; the chunk's own rows).
  * ``verify_step`` (a speculative window) writes the window's rows,
    gathers the span, and attends over it under key position <= query
    position: the window sees its own keys through the pages.
  * ``decode_burst`` (up to n fused decode+sample steps, the number an
    operand): the kind copies or indexes the old context once a burst
    for all layers; step i puts its row into a burst scratch and attends
    over (the old context; scratch up to i); the kind writes the scratch
    at the end.
A scan's stacked output is not aliased to its stacked input, so a pool
that rides a layer scan is rewritten whole: ``prefill`` and
``decode_burst`` keep it out, ``prefill_chunk`` and ``verify_step``
still take it through (ROADMAP S10).

The pools come and go as the five parameters ``cache_k, cache_v, cache_i,
cache_c, cache_s`` (``cache.KVCache``'s fields; None where the kind has
no such pool) and are donated, so the writes update pages in place; a
program returns ``cache_k, cache_v``, its expert counts, and behind them
the others that are not None (``_rest``). Page 0 stays reserved: block
tables and page lists are padded with 0 and the gathers read it under a
mask; nothing writes to it. A burst's gather, ``_gather_span``, copies
the listed pages of all layers once, straight into the layer-major array
the layer scan slices; inside a layer scan one layer's pool is gathered
by ``_take_span``, a plain ``jnp.take``, which is the faster there (both
measured alone on the chip: PERF.md, PR 32).

A layer pattern (``LlamaConfig.layer_pattern``) still has the one
``_block``: ``_layers`` scans over PERIODS of the pattern and the body
runs the period's layers in turn, each with its kind string, which
``heads`` is told. Leading dense layers (``n_dense_layers``) are their
own stack ``params["dense_layers"]``: ``_layers`` scans them first, with
the dense feed-forward; the cache's layers are in that order. Under a
pattern they sit INSIDE it: the periods that hold one run before the
scan, layer by layer, each layer at its place in its group's pool, and
the scan runs the periods behind them.

Static shapes throughout: prefill pads a prompt to a power-of-2 bucket
or a rung between two (``prefill_bucket``; one executable a bucket),
decode runs the whole slot batch every step with inactive slots masked
over a page list padded to a power-of-2 bucket (one executable a bucket,
whatever the burst's width).
Reference analog: the vLLM
paged-attention CUDA kernels behind ray.llm's vllm_engine (SURVEY §2.4),
rebuilt natively since the reference delegates all device work to vLLM.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..models.llama import LlamaConfig, linear, qk_norm, rotated
from ..ops import apply_rotary, layer_norm, rms_norm
from ..ops.moe import router_logits
from ..ops.quant import embed_lookup, is_quantized, weight_einsum
from . import kinds
from .cache import KVCache
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


def _rest(cache: KVCache):
    """The pools a program returns behind its expert counts: the
    cache's other fields that are not None, in their order."""
    return tuple(p for p in (cache.i, cache.c, cache.s) if p is not None)


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
            scale=cfg.routed_scale, held=cfg.experts_held, shared=shared,
            score=cfg.router_score,
            bias=lp["expert_bias"] if cfg.router_bias else None)
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
    x = _norm(x, params, "final_norm", cfg)
    if cfg.logit_divisor != 1.0:
        x = (x.astype(jnp.float32) / cfg.logit_divisor).astype(x.dtype)
    if "lm_head" not in params:
        # a tied head: the table's rows are the head's columns
        return weight_einsum("...d,vd->...v", x.astype(cfg.dtype),
                             params["embed"],
                             preferred_element_type=jnp.float32)
    lm = params["lm_head"]
    if not is_quantized(lm):
        lm = lm.astype(cfg.dtype)
    return weight_einsum("...d,dv->...v", x.astype(cfg.dtype), lm,
                         preferred_element_type=jnp.float32)


def _norm(x, lp, name: str, cfg: LlamaConfig):
    """The norm ``name`` of ``lp``: RMSNorm, or where ``lp`` has a bias
    beside the weight (``name`` + "_bias") LayerNorm with it."""
    if name + "_bias" in lp:
        return layer_norm(x, lp[name], lp[name + "_bias"], cfg.norm_eps)
    return rms_norm(x, lp[name], cfg.norm_eps)


def _pick(logits, greedy, seed, temperature, top_k, top_p):
    """``greedy=True`` (every request temperature==0) compiles an
    argmax-only epilogue: bit-identical results for greedy requests, and
    a program without the top_k/sort/categorical sampler. Whether the
    fork pays is not measured (ROADMAP R6 prices it)."""
    if greedy:
        return jnp.argmax(logits, axis=-1)
    return sample_from_logits(logits, seed, temperature, top_k, top_p)


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


def _attend(q, *segments, scale=None):
    """Grouped-query attention over keys that lie in segments (a cached
    span; rows the cache does not hold yet), one softmax over all.
    ``scale``: the softmax's, where it is not ``hd ** -0.5``.

    q: [B, ..., heads, hd], with or without a query axis; a segment:
    (keys [B, S, kvh, hd], values, mask broadcastable to [B, ..., S]).
    Keys [kvh, S, hd], with no row axis, are ONE list that every query
    row scores, each under its own mask. The operands go to the MXU in
    their own dtype with f32 accumulation. Returns f32, in q's shape.
    """
    hd = q.shape[-1]
    scale = hd ** -0.5 if scale is None else scale
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
                  product(qg, keys, "ds") * scale, -jnp.inf)
        for keys, _, mask in segments], axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(segments[0][0].dtype)
    outs, at = [], 0
    for _, values, _ in segments:
        end = at + values.shape[-3 if values.ndim == 4 else -2]
        outs.append(product(p[..., at:end], values, "sd"))
        at = end
    return sum(outs[1:], outs[0]).reshape(q.shape)


def _heads(h, lp, lr, *, cfg: LlamaConfig, kind, cos, sin, positions,
           lora_scale):
    """A layer of heads' projections of the normalised input h, for the
    kinds whose layers have q, k and v (``kinds/*.heads``): the three
    products (plus a slot's LoRA deltas), QK-norm, rotary."""
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
    return q, k, v


def _gated(o, h, lp):
    """Attention's output times sigmoid(W_g h), before ``wo``
    (``cfg.attn_output_gate``): float32, in o's shape."""
    gate = weight_einsum("bsd,dhk->bshk", h, lp["wg"],
                         preferred_element_type=jnp.float32)
    return o * jax.nn.sigmoid(gate)


def _chunk_masks(span: int, start_pos, valid):
    """A chunk's two masks: (the cached span's positions below the
    chunk's start [1, 1, span]: earlier chunks wrote them; the chunk's
    own rows up to each query [1, C, C]). valid: [1, C]."""
    C = valid.shape[1]
    past = jnp.arange(span)[None, None, :] < start_pos
    own = (jnp.arange(C)[None, :, None]
           >= jnp.arange(C)[None, None, :]) & valid[:, None, :]
    return past, own


def _block(x, inputs, *, cfg: LlamaConfig, kind, cos, sin, positions, valid,
           attend, experts, lora_scale, last=None):
    """The decoder layer, once, as the body of a scan over layers.

    x: [B, S, d]; inputs: (the layer's weights, the layer's slice of the
    program's own state, the layer's per-slot adapter rows: low-rank
    deltas on wq/wv, llm/lora.py, empty = base model); ``kind``: the
    layer's (``LlamaConfig.layer_pattern``); positions: [B, S] rotary
    positions, None = 0..S-1; valid: [B, S], the rows that are tokens;
    ``attend``: the program's, from the configuration's cache kind, whose
    ``heads`` is the layer's attention half and calls it (``llm/kinds``).
    ``experts`` None: the layer's feed-forward is dense. ``last`` int32
    [B]: the layer's ``heads`` sees every row (what it keeps is every
    row's) and answers for row ``last`` alone, and from there on the
    stream is that row, [B, 1, d].
    Returns (x, (kept, expert counts: see ``_mlp``)).
    """
    lp, state, lr = inputs
    h = _norm(x, lp, "attn_norm", cfg)
    # a router that reads the attention's input: its logits are known a
    # whole attention before the experts need them
    logits = router_logits(h, lp["router"]) if (
        experts is not None and cfg.router_input == "attention") else None
    o, kept = kinds.of(cfg).heads(
        h, lp, lr, state, cfg=cfg, kind=kind, cos=cos, sin=sin,
        positions=positions, attend=attend, lora_scale=lora_scale,
        **({} if last is None else {"last": last}))
    if last is not None:
        x, valid = _rows_at(x, last), None

    def added(a):
        # what a layer's two halves add to the stream, times the scale
        if cfg.residual_scale == 1.0:
            return a
        return (a.astype(jnp.float32) * cfg.residual_scale).astype(x.dtype)

    def post(a, name):
        # sandwich norms (``cfg.post_norms``): a norm of its own on what
        # the half adds; a layer without the weight adds it as it is
        return rms_norm(a, lp[name], cfg.norm_eps) if name in lp else a

    out = weight_einsum("bshk,hkd->bsd", o.astype(x.dtype), lp["wo"])
    if "bo" in lp:
        out = out + lp["bo"]
    x = x + added(post(out, "post_attn_norm"))
    h = _norm(x, lp, "mlp_norm", cfg)
    m, counts = _mlp(h, lp, cfg, valid, experts, logits)
    return x + added(post(m, "post_mlp_norm")), (kept, counts)


def _sampled(x, at):
    """The row a prefill samples: x [B, S, d], at int32 [B, 1, 1] ->
    [B, d]. A stream of ONE row is that row: a stack that needs its
    upper layers for the sampled row alone hands back no other
    (``_hybrid_layers``)."""
    if x.shape[1] == 1:
        return x[:, 0]
    return jnp.take_along_axis(x, at, axis=1)[:, 0]


def _rows_at(x, at):
    """Row ``at[b]`` of every sequence: x [B, S, ...] -> [B, 1, ...]."""
    return jnp.take_along_axis(
        x, at.reshape(-1, 1, *(1,) * (x.ndim - 2)), axis=1)


def _hybrid_layers(params, cfg: LlamaConfig, block, x, state, last=None):
    """``_layers``' ``run`` for a decoder-hybrid-decoder stack
    (``cfg.scan_state``; ``kinds/scan.py``): five kinds of layer and
    THREE traced bodies, a ``lax.scan`` over the P periods of (scan,
    window attention), the pair (scan, full attention) by itself, and a
    ``lax.scan`` over the Q periods of (memory unit, cross-attention).
    Two things pass from a layer to LATER layers within the step: the
    last scan layer's output before its gate (``m``, which every memory
    unit reads at its own row) and what the full layer's attention
    leaves for the cross layers, which have no key of their own.

    ``state``: (the full group's, the window group's, (what a scan layer
    is handed by its place, what the scan layers carry from one to the
    next)), any of them None; what ``run`` returns as kept has that
    form. ``block``: ``_block`` with the program's arguments. ``last``:
    from the full layer's attention on the stream is every sequence's
    row ``last`` alone, and the layers below it leave what they keep for
    every row."""
    P, Q = cfg.hybrid_periods
    attn = params["layers"]

    def at(tree, i):
        """Layer ``i`` of a stacked tree, taken where it is used: a scan
        that sliced the first P of P + 1 layers would copy them."""
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), tree)

    full, window, (handed, carried) = state or (None, None, (None, None))

    def period(carry, xs):
        x, carried = carry
        win, i = xs
        x, ((_, left, carried), _) = block(
            x, (at(params["scan_layers"], i),
                (i, None if handed is None else at(handed, i), carried), {}),
            kind="scan")
        x, (kept, _) = block(x, (at(attn, i), win, {}), kind="window_diff")
        return (x, carried), (left, kept)

    (x, carried), (left, kept_w) = jax.lax.scan(
        period, (x, carried), (window, jnp.arange(P)))
    x, ((m, left_p, carried), _) = block(
        x, (at(params["scan_layers"], P),
            (P, None if handed is None else at(handed, P), carried), {}),
        kind="scan")
    x, ((kept_f, shared), _) = block(
        x, (at(attn, P), None if full is None else jax.tree.map(
            lambda a: a[0], full), {}), kind="full_diff", last=last)
    if last is not None:
        m = _rows_at(m, last)

    def cross_period(x, xs):
        gp, cp = xs
        x, _ = block(x, (gp, m, {}), kind="gmu")
        x, _ = block(x, (cp, shared, {}), kind="cross_diff")
        return x, None

    x, _ = jax.lax.scan(cross_period, x, (params["gmu_layers"],
                                          params["cross_layers"]))
    if left is not None:
        left = jnp.concatenate([left, left_p[None]], 0)
    return x, (jax.tree.map(lambda a: a[None], kept_f), kept_w,
               (left, carried)), None


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
    pattern = cfg.layer_kinds
    if lora_xs and (len(pattern) > 1 or cfg.latent or n_dense):
        # LLMEngine refuses lora_rank with these; adapters ride the one
        # scan over layers and add to wq and wv
        raise ValueError("adapters are not supported with a layer "
                         "pattern, latent attention or leading dense "
                         "layers")
    places = [cfg.layer_group(j) for j in range(len(pattern))]
    n_groups = len(cfg.kv_groups)

    per_group = [sum(g == h for g, _ in places) for h in range(n_groups)]

    def at(tree, i):
        """Layer ``i`` (a traced index) of a stacked tree: the slice a
        scan over layers would hand its body, taken where it is used."""
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), tree)

    if cfg.scan_state:
        if lora_xs:
            raise ValueError("adapters are not supported with scan layers")

        def run(x, state, attend, *, positions, valid):
            # the kind's ``attend`` knows the row a prefill samples
            return _hybrid_layers(params, cfg, partial(
                _block, cfg=cfg, cos=cos, sin=sin, positions=positions,
                valid=valid, attend=attend, experts=None, lora_scale=None),
                x, state, attend("last"))

        return run

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
            for kind in pattern:
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
        if len(pattern) == 1:
            mine = None if state is None else state[0]
            if n_dense:
                # the leading dense layers first: the same block, the
                # dense feed-forward, the cache's first layers
                x, (first, _) = jax.lax.scan(
                    partial(block, kind=pattern[0], experts=None), x,
                    (dense, None if mine is None else jax.tree.map(
                        lambda a: a[:n_dense], mine), {}))
                if mine is not None:
                    mine = jax.tree.map(lambda a: a[n_dense:], mine)
            x, (kept, counts) = jax.lax.scan(
                partial(block, kind=pattern[0]), x, (layers, mine, lora_xs))
            if n_dense:
                kept = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], 0), first, kept)
            return x, (kept,), _total(counts)

        def period(x, p, leading=False):
            # every layer takes its own slices by its index: handed the
            # period's slices as one array, the layers would each copy
            # theirs out of it (a burst's keys, read twice a step).
            # ``leading``: ``p`` is a number, not the scan's index, and
            # the period's first layers may be dense ones
            kept, counts = [[] for _ in range(n_groups)], []
            for j, (kind, (g, place)) in enumerate(zip(pattern, places)):
                is_dense = leading and p * len(pattern) + j < n_dense
                # the expert layers' stack begins behind the dense ones
                stack, first = (dense, 0) if is_dense else (layers, n_dense)
                x, (rows, n) = block(
                    x, (at(stack, p * len(pattern) + (j - first)),
                        None if state is None else at(
                            state[g], p * per_group[g] + place), {}),
                    kind=kind, **({"experts": None} if is_dense else {}))
                kept[g].append(rows)
                if n is not None:
                    counts.append(n)
            return x, (tuple(jax.tree.map(lambda *a: jnp.stack(a), *rows)
                             for rows in kept),
                       sum(counts) if counts else None)

        # the periods that hold a dense layer, then the scan of the rest
        n_lead = -(-n_dense // len(pattern))
        lead = []
        for p in range(n_lead):
            x, out = period(x, p, leading=True)
            lead.append(out)
        x, (kept, counts) = jax.lax.scan(
            period, x, jnp.arange(n_lead, cfg.n_layers // len(pattern)))
        kept = tuple(jax.tree.map(
            lambda a: a.reshape(-1, *a.shape[2:]), rows) for rows in kept)
        counts = _total(counts)
        if lead:
            kept = tuple(jax.tree.map(
                lambda *a: jnp.concatenate(a, 0), *rows)
                for rows in zip(*(k for k, _ in lead), kept))
            counts = sum((n for _, n in lead if n is not None), counts)
        return x, kept, counts

    return run


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=_POOLS)
def prefill(params, cache_k, cache_v, tokens, prompt_lens, block_tables,
            cos, sin, lora=None, cache_i=None, cache_c=None, cache_s=None,
            slots=None, *, cfg: LlamaConfig):
    """Process full prompts, fill their pages, return last-token logits.

    tokens: [B, S] right-padded; prompt_lens: [B]; block_tables: [B, Pmax];
    ``lora``: see ``_layers``; the pools and tables as the kind keeps
    them. The layers hand what they keep out of the scan (a paged kind:
    K and V rows [L, B, S, kvh, hd], in the cache's dtype) and the kind
    writes it behind the scan; padding rows (position >= prompt_len) are
    dropped. Attention is told the prompts' lengths: on the TPU the
    flash kernel leaves out the query blocks that hold no token
    (``flash_attention_tpu``), everything else in a layer still runs the
    bucket's rows.

    Returns (logits [B, vocab], cache_k, cache_v, expert counts: see
    ``_mlp``; None for a dense config), and behind them the kind's other
    pools (``_rest``), as every program does.
    """
    B, S = tokens.shape
    x = _embed(params, tokens, cfg)
    pos_grid = jnp.arange(S)[None, :].repeat(B, 0)
    valid = pos_grid < prompt_lens[:, None]                    # [B, S]
    attend, write = kinds.of(cfg).prefill(
        cfg, KVCache(cache_k, cache_v, cache_i, cache_c, cache_s),
        block_tables, prompt_lens, slots, pos_grid, valid)
    x, rows, counts = _layers(params, cfg, cos, sin, lora)(
        x, None, attend, positions=None, valid=valid)
    cache = write(rows)
    x_last = _sampled(x, jnp.maximum(prompt_lens - 1, 0)[:, None, None])
    return (_head(x_last, params, cfg), cache.k, cache.v, counts,
            *_rest(cache))


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
# of every prompt it catches and costs a program more (the engine loads
# it before it is ready: ``LLMEngine.load_prefill_programs``). Both are
# whole tiles of every prefill kernel (24 and 48 x 512): 12,288 catches
# 21 of ``longfile-steady``'s 27 prompts and 6 of ``longctx-steady``'s
# 32, 24,576 the two longest of each cycle, which are their TTFT tails.
PREFILL_RUNGS = (12288, 24576)


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
    x = _embed(params, tokens, cfg)
    pos_grid = start_pos + jnp.arange(C)[None, :]          # [1, C]
    valid = jnp.arange(C)[None, :] < chunk_len
    pools, attend, done = kinds.of(cfg).prefill_chunk(
        cfg, KVCache(cache_k, cache_v, cache_i, cache_c, cache_s),
        block_tables, start_pos, chunk_len, slots, pos_grid, valid)
    x, pools, counts = _layers(params, cfg, cos, sin)(
        x, pools, attend, positions=pos_grid, valid=valid)
    cache = done(pools)
    idx = jnp.broadcast_to(jnp.maximum(chunk_len - 1, 0).reshape(1, 1, 1),
                           (B, 1, 1))
    return (_head(_sampled(x, idx), params, cfg), cache.k, cache.v, counts,
            *_rest(cache))


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
    window sit at positions > query_pos and never score. A kind whose
    memory cannot be rolled back behind a rejected window has no such
    program and says so by name.

    Returns (argmax tokens [B, S] — index j predicts the token AFTER
    window position j, sampled position-0 token [B] for rows that
    aren't greedy, cache_k, cache_v, expert counts as ``prefill``).
    """
    x = _embed(params, tokens, cfg)
    valid = positions >= 0
    qpos = jnp.maximum(positions, 0)                       # [B, S]
    pools, attend, done = kinds.of(cfg).verify_step(
        cfg, KVCache(cache_k, cache_v, cache_i, cache_c, cache_s),
        block_tables, positions, qpos, valid)
    x, pools, counts = _layers(params, cfg, cos, sin)(
        x, pools, attend, positions=qpos, valid=valid)
    cache = done(pools)
    logits = _head(x, params, cfg)
    tgt = jnp.argmax(logits, axis=-1)                      # [B, S]
    samp0 = tgt[:, 0] if greedy else sample_from_logits(
        logits[:, 0], seed, temperature, top_k, top_p)
    return (tgt, samp0, cache.k, cache.v, counts, *_rest(cache))


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
    every step): what the kind needs of the old context is copied or
    indexed ONCE a burst (``kinds/*.decode_burst``, which also says what
    ``gather`` lists for the kind), the burst's rows accumulate in a
    [L, B, n_steps] scratch and are written once at the end, rows of
    inactive slots and of steps not run dropped.

    ``steps``: int32 scalar, the steps to run (<= n_steps, which is only
    the capacity: scratch rows and the returned [n_steps, B]); None runs
    them all. A width is an operand, not a program.

    Returns (tokens [n_steps, B], rows past ``steps`` 0; cache_k,
    cache_v; expert counts over all steps and layers as ``prefill``).
    The host must have pre-provisioned pages for positions ..
    positions+steps-1 in ``block_tables`` (the full-width table: only
    the burst's write reads it).
    """
    if paged_kernel:
        # the keyword stays only because benchmarks/aot_fit.py:73 passes
        # paged_kernel=False; it goes when a benchmark PR drops it there
        raise ValueError("decode_burst has one attention path: the paged "
                         "kernel was deleted (PR 31)")
    B, K = tokens.shape[0], n_steps
    burst = kinds.of(cfg).decode_burst(
        cfg, KVCache(cache_k, cache_v, cache_i, cache_c, cache_s),
        block_tables, gather, positions, active, K)
    layers = _layers(params, cfg, cos, sin, lora)
    n_run = K if steps is None else steps

    def step(i, carry):
        toks, scratch, out, total, more = carry
        x = _embed(params, toks, cfg)[:, None, :]
        new_mask = jnp.arange(K)[None, :] <= i                 # [1, K]
        attend, carried = burst.step(i, new_mask, more)
        x, scratch, counts = layers(
            x, tuple(o + s for o, s in zip(burst.old, scratch)), attend,
            positions=(positions + i)[:, None], valid=active[:, None])
        newt = _pick(_head(x[:, 0], params, cfg), greedy, seed + i,
                     temperature, top_k, top_p)
        newt = jnp.where(active, newt, toks).astype(out.dtype)
        out = jax.lax.dynamic_update_slice_in_dim(out, newt[None], i, 0)
        return (newt, scratch, out,
                None if counts is None else total + counts, carried())

    _, scratch, out, counts, more = jax.lax.fori_loop(
        0, n_run, step,
        (tokens, burst.scratch, jnp.zeros((K, B), tokens.dtype),
         jnp.zeros(3, jnp.int32) if cfg.n_experts else None, burst.carry))
    # the whole burst into the cache at once: rows of inactive slots and
    # of steps not run are written nowhere
    p_grid = positions[:, None] + jnp.arange(K)[None, :]       # [B, K]
    written = active[:, None] & (jnp.arange(K)[None, :] < n_run)
    cache = burst.write(scratch, more, p_grid, written)
    return (out, cache.k, cache.v, counts, *_rest(cache))
