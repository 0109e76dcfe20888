"""Model runner: jitted prefill + single-token decode over a paged KV
cache, for the Llama family.

TPU-first shape discipline (everything static under jit):
  * prefill pads the prompt to a power-of-2 bucket — one compiled
    executable per bucket, reused across requests;
  * decode runs the WHOLE slot batch [max_seqs] every step, inactive
    slots masked (their writes land on dump page 0) — one executable for
    the life of the engine;
  * cache buffers are donated, so XLA updates pages in place (no
    O(cache) copy per step) — and, HBM discipline, the big cache stays
    out of the scans that would copy it: a scan's stacked output is not
    aliased to its stacked input, so a pool that rides one is rewritten
    whole. ``prefill`` (whole prompts) and ``decode_burst`` hand their
    new K/V rows out of their scans and scatter them into the donated
    pools once at the end; ``prefill_chunk`` and ``verify_step`` still
    take the pool through their layer scans (they read back the pages
    they write, layer by layer: ROADMAP S10).

The decode attention gathers pages with jnp.take (XLA fuses the gather
into the attention when it can); a Pallas in-place kernel is the upgrade
path once shapes are pinned. Reference analog: the vLLM paged-attention
CUDA kernels behind ray.llm's vllm_engine (SURVEY §2.4) — rebuilt here
natively since the reference delegates all device work to vLLM.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.llama import LlamaConfig, qk_norm
from ..ops import apply_rotary, attention, rms_norm, rope_frequencies
from ..ops.quant import embed_lookup, is_quantized, weight_einsum
from .cache import KVCache


_EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def _split_layers(layers, cfg: LlamaConfig):
    """(what a scan over layers slices, what it must not). An expert
    config's expert matrices stay whole stacks that the routed layer
    addresses by the layer's index: a scan that sliced them would copy a
    layer's experts (0.4 GB int8 at OLMoE's widths) in every iteration,
    where a decode step needs a few of them."""
    if not cfg.n_experts:
        return layers, None
    sliced = {k: v for k, v in layers.items() if k not in _EXPERT_STACKS}
    sliced["layer"] = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    return sliced, {k: layers[k] for k in _EXPERT_STACKS}


def _mlp(h, lp, cfg: LlamaConfig, valid=None, experts=None):
    """Serving MLP: dense SwiGLU, or for expert configs the one dropless
    routed layer (``ops.moe.moe_mlp_routed``): no capacity, so a
    sequence's answer does not change with its batch, and rows that are
    not tokens (``valid`` [B, S] False: bucket padding, inactive slots)
    are given to no expert; ``lp`` and ``experts`` are ``_split_layers``'
    two halves. Returns (out, counts): the layer's
    (expert rows, experts touched) int32 [2], None for a dense config."""
    if cfg.n_experts:
        from ..ops.moe import moe_mlp_routed

        return moe_mlp_routed(
            h, lp["router"], experts["w_gate"], experts["w_up"],
            experts["w_down"], top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, valid=valid,
            layer=lp["layer"])
    g = weight_einsum("bsd,dm->bsm", h, lp["w_gate"])
    u = weight_einsum("bsd,dm->bsm", h, lp["w_up"])
    return weight_einsum("bsm,md->bsd", jax.nn.silu(g) * u,
                         lp["w_down"]), None


def _total(counts):
    """Per-layer (or per-step) expert counts stacked by a scan -> their
    sum; None stays None (a dense config counts nothing)."""
    return None if counts is None else counts.sum(0)


def _lm_logits(x_last, params, cfg: LlamaConfig):
    """Final-norm'd hidden -> f32 logits, raw or int8 lm_head. bf16
    operands on the MXU with f32 accumulation either way."""
    lm = params["lm_head"]
    if not is_quantized(lm):
        lm = lm.astype(cfg.dtype)
    return weight_einsum("bd,dv->bv", x_last.astype(cfg.dtype), lm,
                         preferred_element_type=jnp.float32)


def _write_pages(cache_layer, new, block_tables, positions, page_size):
    """Scatter per-token K or V rows into their pages.

    cache_layer: [P, page, kvh, hd]; new: [B, S, kvh, hd];
    block_tables: [B, max_pages]; positions: [B, S] absolute positions
    (negative = padding -> routed to dump page 0).
    """
    B, S = new.shape[:2]
    page_idx = jnp.take_along_axis(
        block_tables, jnp.maximum(positions, 0) // page_size, axis=1)
    valid = positions >= 0
    page_idx = jnp.where(valid, page_idx, 0)           # dump page
    offset = jnp.where(valid, positions % page_size, 0)
    flat_pages = page_idx.reshape(-1)                  # [B*S]
    flat_off = offset.reshape(-1)
    flat_new = new.reshape(B * S, *new.shape[2:])
    return cache_layer.at[flat_pages, flat_off].set(
        flat_new.astype(cache_layer.dtype), mode="drop")


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache_k",
                                                             "cache_v"))
def prefill(params, cache_k, cache_v, tokens, prompt_lens, block_tables,
            cos, sin, lora=None, *, cfg: LlamaConfig):
    """Process full prompts, fill their pages, return last-token logits.

    tokens: [B, S] right-padded; prompt_lens: [B]; block_tables: [B, Pmax].
    ``lora``: per-slot batched adapters from LoRAPool.select(ids) —
    low-rank deltas on wq/wv (llm/lora.py), empty/None = base model.

    HBM discipline (as ``decode_burst``): the big cache never rides the
    layer scan. A scan's stacked output is not aliased to its stacked
    input, so a pool passed through as xs/ys is read and rewritten whole
    (every page of every layer, a second pool among the temporaries) to
    write one prompt's rows. Attention here runs on the prompt's own
    k and v and never reads a page, so each layer only hands its K and V
    rows out of the scan ([L, B, S, kvh, hd], in the cache's dtype) and
    they scatter into the donated pools ONCE at the end, in place.
    Padding rows (position >= prompt_len) carry an out-of-range page
    index and are dropped: they change no page, the dump page neither.

    Returns (logits [B, vocab], cache_k, cache_v, expert counts: see
    ``_mlp``; None for a dense config).
    """
    from .lora import lora_delta

    B, S = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    pos_grid = jnp.arange(S)[None, :].repeat(B, 0)
    valid = pos_grid < prompt_lens[:, None]                    # [B, S]
    # adapters ride the layer scan as xs: [B, L, ...] -> [L, B, ...]
    lora_xs = {} if not lora else {
        k2: jnp.swapaxes(v2, 0, 1) for k2, v2 in lora.items()
        if k2 != "scale"}

    def layer(x, inputs):
        lp, lr = inputs
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = weight_einsum("bsd,dhk->bshk", h, lp["wq"])
        k = weight_einsum("bsd,dhk->bshk", h, lp["wk"])
        v = weight_einsum("bsd,dhk->bshk", h, lp["wv"])
        if lr:
            q = q + lora_delta(h, lr["a_q"], lr["b_q"], lora["scale"],
                               cfg.n_heads, cfg.head_dim)
            v = v + lora_delta(h, lr["a_v"], lr["b_v"], lora["scale"],
                               cfg.n_kv_heads, cfg.head_dim)
        q, k = qk_norm(q, k, lp, cfg)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
        # right padding is safe under the causal mask: a real position
        # only attends to earlier (real) positions
        o = attention(q, k, v, causal=True)
        x = x + weight_einsum("bshk,hkd->bsd", o, lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        m, n = _mlp(h, lp, cfg, valid, experts)
        return x + m, (k.astype(cache_k.dtype), v.astype(cache_v.dtype), n)

    layers, experts = _split_layers(params["layers"], cfg)
    x, (rows_k, rows_v, counts) = jax.lax.scan(layer, x, (layers, lora_xs))

    # one scatter of all layers' rows into the paged cache (donated ->
    # in-place); page index num_pages is out of range, so mode="drop"
    # writes nothing for a padding row
    n_pages, page_size = cache_k.shape[1:3]
    page_idx = jnp.take_along_axis(block_tables, pos_grid // page_size,
                                   axis=1)
    fp = jnp.where(valid, page_idx, n_pages).reshape(-1)       # [B*S]
    fo = (pos_grid % page_size).reshape(-1)

    def put(cache, rows):                      # rows: [L, B, S, kvh, hd]
        return cache.at[:, fp, fo].set(
            rows.reshape(rows.shape[0], B * S, *rows.shape[3:]),
            mode="drop")

    cache_k, cache_v = put(cache_k, rows_k), put(cache_v, rows_v)
    x_last = jnp.take_along_axis(
        x, jnp.maximum(prompt_lens - 1, 0)[:, None, None], axis=1)[:, 0]
    x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    logits = _lm_logits(x_last, params, cfg)
    return logits, cache_k, cache_v, _total(counts)


def prefill_bucket(seq_len: int, max_seq: int, floor: int = 16) -> int:
    """Power-of-2 padding bucket — one compiled prefill per bucket."""
    b = floor
    while b < seq_len:
        b *= 2
    return min(b, max_seq)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache_k",
                                                             "cache_v"))
def prefill_chunk(params, cache_k, cache_v, tokens, start_pos, chunk_len,
                  block_tables, cos, sin, *, cfg: LlamaConfig):
    """One CHUNK of a long prompt (vLLM's chunked prefill, rebuilt for
    static shapes): tokens [1, C] are positions
    [start_pos, start_pos+chunk_len), attending causally within the
    chunk AND over the pages written by earlier chunks. One compiled
    executable per (C, table-span) pair serves prompts of every length —
    and decode bursts for other requests interleave between chunks, so a
    long prompt no longer stalls running streams for its whole prefill.

    Returns (logits [1, vocab] of the chunk's LAST VALID token,
    cache_k, cache_v, expert counts as ``prefill``).
    """
    B, C = tokens.shape
    page_size = cache_k.shape[2]
    Spast = block_tables.shape[1] * page_size
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    pos_grid = start_pos + jnp.arange(C)[None, :]          # [1, C]
    valid = jnp.arange(C)[None, :] < chunk_len
    write_pos = jnp.where(valid, pos_grid, -1)
    # past pages hold positions < start_pos (written by earlier chunks)
    past_mask = jnp.arange(Spast)[None, :] < start_pos     # [1, Spast]
    chunk_mask = (jnp.arange(C)[None, :, None]
                  >= jnp.arange(C)[None, None, :]) & valid[:, None, :]

    def layer(x, inputs):
        lp, ck, cv = inputs
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = weight_einsum("bsd,dhk->bshk", h, lp["wq"])
        k = weight_einsum("bsd,dhk->bshk", h, lp["wk"])
        v = weight_einsum("bsd,dhk->bshk", h, lp["wv"])
        q, k = qk_norm(q, k, lp, cfg)
        q = apply_rotary(q, cos, sin, positions=pos_grid)
        k = apply_rotary(k, cos, sin, positions=pos_grid)
        ck = _write_pages(ck, k, block_tables, write_pos, page_size)
        cv = _write_pages(cv, v, block_tables, write_pos, page_size)
        pk = jnp.take(ck, block_tables, axis=0).reshape(
            B, Spast, *k.shape[2:])
        pv = jnp.take(cv, block_tables, axis=0).reshape(
            B, Spast, *v.shape[2:])
        kvh, hd = cfg.n_kv_heads, cfg.head_dim
        rep = cfg.n_heads // kvh
        qg = q.reshape(B, C, kvh, rep, hd)
        scale = hd ** -0.5
        s_past = jnp.einsum("bcgrd,bsgd->bcgrs", qg, pk,
                            preferred_element_type=jnp.float32)
        s_self = jnp.einsum("bcgrd,btgd->bcgrt", qg, k,
                            preferred_element_type=jnp.float32)
        s_past = jnp.where(past_mask[:, None, None, None, :],
                           s_past * scale, -jnp.inf)
        s_self = jnp.where(chunk_mask[:, :, None, None, :],
                           s_self * scale, -jnp.inf)
        p = jax.nn.softmax(
            jnp.concatenate([s_past, s_self], axis=-1), axis=-1
        ).astype(pk.dtype)
        o = (jnp.einsum("bcgrs,bsgd->bcgrd", p[..., :Spast], pv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bcgrt,btgd->bcgrd", p[..., Spast:], v,
                          preferred_element_type=jnp.float32))
        o = o.reshape(B, C, cfg.n_heads, hd).astype(x.dtype)
        x = x + weight_einsum("bshk,hkd->bsd", o, lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        m, n = _mlp(h, lp, cfg, valid, experts)
        return x + m, (ck, cv, n)

    layers, experts = _split_layers(params["layers"], cfg)
    x, (cache_k, cache_v, counts) = jax.lax.scan(
        layer, x, (layers, cache_k, cache_v))
    idx = jnp.broadcast_to(jnp.maximum(chunk_len - 1, 0).reshape(1, 1, 1),
                           (B, 1, 1))
    x_last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
    x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    logits = _lm_logits(x_last, params, cfg)
    return logits, cache_k, cache_v, _total(counts)


@partial(jax.jit, static_argnames=("cfg", "greedy"),
         donate_argnames=("cache_k", "cache_v"))
def verify_step(params, cache_k, cache_v, tokens, positions, block_tables,
                cos, sin, seed, temperature, top_k, top_p, *,
                cfg: LlamaConfig, greedy: bool = False):
    """Batched multi-token verification forward (speculative decoding,
    Leviathan et al. ICML'23 — PAPERS.md): score a whole k-token draft
    window in ONE dispatch, like a short prefill over the paged cache.

    tokens: [B, S] window tokens (row = [last_emitted, d_1 .. d_k]);
    positions: [B, S] absolute per-token positions, -1 = padding (rows
    with shorter windows, undrafted slots) — padded writes land on dump
    page 0. Every valid window token's KV is WRITTEN first, then
    attention gathers the pages, masked by key_pos <= query_pos: the
    window's own keys are visible through the pages (write-then-gather,
    same discipline as prefill_chunk), stale rows from a previous
    rejected window sit at positions > query_pos and never score.

    Returns (argmax tokens [B, S] — index j predicts the token AFTER
    window position j, sampled position-0 token [B] for rows that
    aren't greedy, cache_k, cache_v, expert counts as ``prefill``).
    """
    from .sampling import sample_from_logits

    B, S = tokens.shape
    page_size = cache_k.shape[2]
    Sall = block_tables.shape[1] * page_size
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    rep = cfg.n_heads // kvh
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    qpos = jnp.maximum(positions, 0)                       # [B, S]
    # unused table slots are 0 (dump page) but sit past the row's
    # provisioned span, so their key positions exceed every query's
    kmask = (jnp.arange(Sall)[None, None, :]
             <= qpos[:, :, None])                          # [B, S, Sall]

    def layer(x, inputs):
        lp, ck, cv = inputs
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = weight_einsum("bsd,dhk->bshk", h, lp["wq"])
        k = weight_einsum("bsd,dhk->bshk", h, lp["wk"])
        v = weight_einsum("bsd,dhk->bshk", h, lp["wv"])
        q, k = qk_norm(q, k, lp, cfg)
        q = apply_rotary(q, cos, sin, positions=qpos)
        k = apply_rotary(k, cos, sin, positions=qpos)
        ck = _write_pages(ck, k, block_tables, positions, page_size)
        cv = _write_pages(cv, v, block_tables, positions, page_size)
        pk = jnp.take(ck, block_tables, axis=0).reshape(B, Sall, kvh, hd)
        pv = jnp.take(cv, block_tables, axis=0).reshape(B, Sall, kvh, hd)
        qg = q.reshape(B, S, kvh, rep, hd)
        s = jnp.einsum("bsgrd,btgd->bsgrt", qg, pk,
                       preferred_element_type=jnp.float32)
        s = jnp.where(kmask[:, :, None, None, :], s * (hd ** -0.5),
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(pk.dtype)
        o = jnp.einsum("bsgrt,btgd->bsgrd", p, pv,
                       preferred_element_type=jnp.float32)
        o = o.reshape(B, S, cfg.n_heads, hd).astype(x.dtype)
        x = x + weight_einsum("bshk,hkd->bsd", o, lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        m, n = _mlp(h, lp, cfg, positions >= 0, experts)
        return x + m, (ck, cv, n)

    layers, experts = _split_layers(params["layers"], cfg)
    x, (cache_k, cache_v, counts) = jax.lax.scan(
        layer, x, (layers, cache_k, cache_v))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    lm = params["lm_head"]
    if not is_quantized(lm):
        lm = lm.astype(cfg.dtype)
    logits = weight_einsum("bsd,dv->bsv", x.astype(cfg.dtype), lm,
                           preferred_element_type=jnp.float32)
    tgt = jnp.argmax(logits, axis=-1)                      # [B, S]
    if greedy:
        samp0 = tgt[:, 0]
    else:
        samp0 = sample_from_logits(logits[:, 0], seed, temperature,
                                   top_k, top_p)
    return tgt, samp0, cache_k, cache_v, _total(counts)


@jax.jit
def sample_logits(logits, seed, temperature, top_k, top_p):
    """Standalone sampler dispatch (the chunked-prefill tail — the
    whole-prompt path fuses sampling into prefill_sample instead)."""
    from .sampling import sample_from_logits

    return sample_from_logits(logits, seed, temperature, top_k, top_p)


# --- fused step functions: model + sampler in ONE dispatch ------------------
# Every dispatch pays a host round trip; fusing sampling into the step
# saves one per token (not re-measured on a locally attached chip yet).

@partial(jax.jit, static_argnames=("cfg", "greedy"),
         donate_argnames=("cache_k", "cache_v"))
def prefill_sample(params, cache_k, cache_v, tokens, prompt_lens,
                   block_tables, cos, sin, seed, temperature, top_k,
                   top_p, lora=None, *, cfg: LlamaConfig,
                   greedy: bool = False):
    """``greedy=True`` (every request temperature==0) compiles an
    argmax-only epilogue — bit-identical results for greedy requests,
    and a materially simpler program than the top_k/sort/categorical
    sampler fused behind multi-GiB weight args. Whether the fork still
    pays on a locally attached chip is not measured yet (ROADMAP D5)."""
    from .sampling import sample_from_logits

    logits, cache_k, cache_v, counts = prefill.__wrapped__(
        params, cache_k, cache_v, tokens, prompt_lens, block_tables,
        cos, sin, lora, cfg=cfg)
    if greedy:
        toks = jnp.argmax(logits, axis=-1)
    else:
        toks = sample_from_logits(logits, seed, temperature, top_k,
                                  top_p)
    return toks, cache_k, cache_v, counts


@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "paged_kernel", "greedy"),
         donate_argnames=("cache_k", "cache_v"))
def decode_burst(params, cache_k, cache_v, tokens, positions,
                 block_tables, active, cos, sin, seed, temperature,
                 top_k, top_p, lora=None, *, cfg: LlamaConfig,
                 n_steps: int, paged_kernel: bool = None,
                 greedy: bool = False):
    """n_steps fused decode+sample steps, sampled tokens fed back
    ON-DEVICE (multi-step scheduling, vLLM's --num-scheduler-steps
    analog). One host round trip yields n_steps tokens per slot, which
    hides per-step dispatch overhead; the best depth on a locally
    attached chip is not measured yet (ROADMAP D5).

    HBM discipline: the big cache never rides the step-scan carry (that
    would copy it every step). The burst's new KV rows accumulate in a
    [L, B, K] scratch; attention runs over (pages gathered once per
    burst) + (scratch, causally masked per step); the scratch scatters
    into the paged cache ONCE at the end. ``block_tables`` may be a
    narrowed slice of the full table — the engine buckets it to the
    longest active context, so KV read traffic scales with real context,
    not max_seq_len.

    Returns (tokens [n_steps, B], cache_k, cache_v, expert counts over
    all steps and layers as ``prefill``). The host must have
    pre-provisioned pages for positions .. positions+n_steps-1.
    """
    from .sampling import sample_from_logits

    from .._private.config import global_config
    from .lora import lora_delta

    # static jit arg (None -> config default) so flag flips retrace
    use_paged_kernel = (global_config().llm_paged_kernel
                        if paged_kernel is None else paged_kernel)
    B = tokens.shape[0]
    K = n_steps
    L = cfg.n_layers
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    rep = cfg.n_heads // cfg.n_kv_heads
    page_size = cache_k.shape[2]
    Sold = block_tables.shape[1] * page_size
    if use_paged_kernel:
        # pages stream straight through the Pallas kernel per layer —
        # no materialized [L, B, Sold] gather copy in HBM
        old_k = old_v = jnp.zeros((L, 0), cache_k.dtype)
    else:
        # old context gathered ONCE per burst (read-only during burst)
        old_k = jnp.take(cache_k, block_tables, axis=1).reshape(
            L, B, Sold, kvh, hd)
        old_v = jnp.take(cache_v, block_tables, axis=1).reshape(
            L, B, Sold, kvh, hd)
    scratch_k = jnp.zeros((L, B, K, kvh, hd), cache_k.dtype)
    scratch_v = jnp.zeros((L, B, K, kvh, hd), cache_v.dtype)
    lora_xs = {} if not lora else {
        k2: jnp.swapaxes(v2, 0, 1) for k2, v2 in lora.items()
        if k2 != "scale"}
    old_mask = jnp.arange(Sold)[None, :] < positions[:, None]  # [B, Sold]
    layers, experts = _split_layers(params["layers"], cfg)

    def step(carry, i):
        toks, sk, sv = carry
        pos_i = positions + i
        x = embed_lookup(params["embed"], toks, cfg.dtype)[:, None, :]
        new_mask = jnp.arange(K)[None, :] <= i                 # [1, K]

        def attend_gathered(qg, ok, ov, nk, nv):
            # bf16 operands straight onto the MXU, f32 accumulation
            s_old = jnp.einsum("bgrd,bsgd->bgrs", qg, ok,
                               preferred_element_type=jnp.float32)
            s_new = jnp.einsum("bgrd,bkgd->bgrk", qg, nk,
                               preferred_element_type=jnp.float32)
            scale = hd ** -0.5
            s_old = jnp.where(old_mask[:, None, None, :], s_old * scale,
                              -jnp.inf)
            s_new = jnp.where(new_mask[None, None, :, :], s_new * scale,
                              -jnp.inf)
            s_all = jnp.concatenate([s_old, s_new], axis=-1)
            p_all = jax.nn.softmax(s_all, axis=-1).astype(ok.dtype)
            return (jnp.einsum("bgrs,bsgd->bgrd", p_all[..., :Sold], ov,
                               preferred_element_type=jnp.float32)
                    + jnp.einsum("bgrk,bkgd->bgrd", p_all[..., Sold:], nv,
                                 preferred_element_type=jnp.float32))

        def attend_paged(qg, ck_l, cv_l, nk, nv):
            from ..ops.paged_attention import paged_decode_attention

            return paged_decode_attention(
                qg, ck_l, cv_l, nk, nv, block_tables, positions,
                jnp.full((B,), i + 1, jnp.int32),
                page_size=page_size).astype(jnp.float32)

        def layer(x, inputs):
            lp, ok, ov, nk, nv, lr = inputs
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            q = weight_einsum("bsd,dhk->bshk", h, lp["wq"])
            k = weight_einsum("bsd,dhk->bshk", h, lp["wk"])
            v = weight_einsum("bsd,dhk->bshk", h, lp["wv"])
            if lr:
                q = q + lora_delta(h, lr["a_q"], lr["b_q"],
                                   lora["scale"], cfg.n_heads, hd)
                v = v + lora_delta(h, lr["a_v"], lr["b_v"],
                                   lora["scale"], kvh, hd)
            q, k = qk_norm(q, k, lp, cfg)
            q = apply_rotary(q, cos, sin, positions=pos_i[:, None])[:, 0]
            k = apply_rotary(k, cos, sin, positions=pos_i[:, None])[:, 0]
            nk = jax.lax.dynamic_update_index_in_dim(
                nk, k.astype(nk.dtype), i, 1)
            nv = jax.lax.dynamic_update_index_in_dim(
                nv, v[:, 0].astype(nv.dtype), i, 1)
            qg = q.reshape(B, kvh, rep, hd)
            if use_paged_kernel:
                o = attend_paged(qg, ok, ov, nk, nv)
            else:
                o = attend_gathered(qg, ok, ov, nk, nv)
            o = o.reshape(B, 1, cfg.n_heads, hd).astype(x.dtype)
            x = x + weight_einsum("bshk,hkd->bsd", o, lp["wo"])
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            m, n = _mlp(h, lp, cfg, active[:, None], experts)
            return x + m, (nk, nv, n)

        if use_paged_kernel:
            # UNROLLED layers: a lax.scan over the cache would dynamic-
            # slice the whole [L, P, ...] page pool per (step, layer) —
            # measured 2.6x slower than the gather path. Static slices
            # in an unrolled loop let XLA alias into the donated pool.
            sks, svs, ns = [], [], []
            for li in range(L):
                lp_l = jax.tree.map(lambda a: a[li], layers)
                lr_l = {k2: v2[li] for k2, v2 in lora_xs.items()}
                x, (nk_l, nv_l, n_l) = layer(
                    x, (lp_l, cache_k[li], cache_v[li], sk[li], sv[li],
                        lr_l))
                sks.append(nk_l)
                svs.append(nv_l)
                ns.append(n_l)
            sk = jnp.stack(sks)
            sv = jnp.stack(svs)
            counts = jnp.stack(ns) if cfg.n_experts else None
        else:
            x, (sk, sv, counts) = jax.lax.scan(
                layer, x, (layers, old_k, old_v, sk, sv, lora_xs))
        h = rms_norm(x[:, 0], params["final_norm"], cfg.norm_eps)
        logits = _lm_logits(h, params, cfg)
        if greedy:   # see prefill_sample: argmax-only epilogue
            newt = jnp.argmax(logits, axis=-1)
        else:
            newt = sample_from_logits(logits, seed + i, temperature,
                                      top_k, top_p)
        newt = jnp.where(active, newt, toks)
        return (newt, sk, sv), (newt, _total(counts))

    (_, scratch_k, scratch_v), (out, counts) = jax.lax.scan(
        step, (tokens, scratch_k, scratch_v), jnp.arange(K))

    # one scatter of the whole burst into the paged cache (donated ->
    # in-place); inactive slots land on dump page 0
    p_grid = positions[:, None] + jnp.arange(K)[None, :]       # [B, K]
    page_idx = jnp.take_along_axis(block_tables, p_grid // page_size,
                                   axis=1)
    valid = active[:, None]
    page_idx = jnp.where(valid, page_idx, 0)
    offset = jnp.where(valid, p_grid % page_size, 0)
    fp, fo = page_idx.reshape(-1), offset.reshape(-1)          # [B*K]
    cache_k = cache_k.at[:, fp, fo].set(
        scratch_k.reshape(L, B * K, kvh, hd), mode="drop")
    cache_v = cache_v.at[:, fp, fo].set(
        scratch_v.reshape(L, B * K, kvh, hd), mode="drop")
    return out, cache_k, cache_v, _total(counts)
