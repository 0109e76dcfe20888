"""The family of DeepSeek-V2 (deepseek-ai; ``model_type`` ``deepseek_v2``):
latent attention, a leading dense layer, and expert layers with a
group-limited router, shared experts and a scaling factor. The layer,
from the catalog row's ``config`` (l counts from 0; ``h = rmsnorm(x)``,
eps 1e-6):

    c_q = rmsnorm(h W_DQ)                       1536 (q_lora_rank)
    q   = c_q W_UQ  -> 128 heads x (128 nope + 64 rope); the rope part
          rotated
    [c_kv ; k_r] = h W_DKV                      512 + 64
    c_kv = rmsnorm(c_kv);  k_r rotated, ONE for all heads
    [k_nope ; v] = c_kv W_UKV -> 128 heads x (128 + 128)
    k   = [k_nope ; k_r]
    x   = x + softmax(q k^T s) v W_O            causal, softmax in float32
          s = 192^-0.5 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
          (0.707, 40: m = 1.2608, s = 0.114721)
    rotary: YaRN over the 64 rope dimensions: inv_freq = inter (1 - mask)
          + extra mask, extra = theta^(-2i/64), inter = extra / factor,
          mask = 1 - ramp(low, high), low and high the correction range
          of beta_fast and beta_slow at original_max_position_embeddings;
          cos and sin times mscale(factor, mscale) / mscale(factor,
          mscale_all_dim) = 1
    g   = rmsnorm(x)
    layer l < first_k_dense_replace:  x = x + SwiGLU_12288(g)
    else:  p = softmax(g W_r) over n_routed_experts (160), float32;
          the experts are n_group (8) groups of neighbours, a group's
          score is its largest p, the topk_group (3) best groups stay,
          every other p is set to 0; the 6 largest of what is left are
          chosen with weights p x routed_scaling_factor (16;
          norm_topk_prob false);
          x = x + sum_i w_i E_i(g) + S(g),  E_i SwiGLU of 1536,
          S one SwiGLU of n_shared_experts x 1536 = 3072
    logits = rmsnorm(x) W_head                  untied

Departures, each under ``assumed`` in the configuration file: the
checkpoint stores the rope columns interleaved and the published code
permutes them to halves before ``rotate_half``; with seeded weights that
is a permutation of columns, and this reference rotates halves.
``W_UKV`` arrives as its two column halves ``w_uk`` and ``w_uv``.

The share. The configuration gives this chip's part of a layer that
``share.chips`` chips hold together: ``n_routed_experts`` of the
``share.routed_experts`` experts, from ``share.first_expert`` on, and a
slice of the vocabulary. The router keeps all its outputs and chooses
among all experts; what the experts that are not here would add is left
out, here as in the program, and that partial result goes on to the
next layer. The shared experts are on every chip.

The program serves it through ``LLMServer`` with ``LlamaConfig(
kv_lora_rank=..., ...)``: one pool of latent rows in the paged cache,
expanded heads through the flash kernel in prefill, the absorbed form
over each slot's own pages in decode (``ops/mla.py``), and the routed
layer of ``ops/moe.py`` told which experts it holds. This file is what
the harness knows of it. Importing it imports no jax.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os

from benchmarks.families.llama_dense import (  # noqa: F401
    server_class, training)


def _refuse_a_program_without_latent_attention() -> None:
    """A tree older than latent attention would fail in the replica's
    constructor (``LlamaConfig`` has no such fields), for which
    ``serve_cell`` waits 25 minutes. Look at the source (no import of
    the program, no jax) for the ONE name ``program_config`` cannot do
    without, the ``LlamaConfig`` field that says which experts are held,
    and stop the run before the runtime starts."""
    spec = importlib.util.find_spec("ray_tpu")
    for root in (spec.submodule_search_locations or []) if spec else []:
        full = os.path.join(root, "models", "llama.py")
        if os.path.isfile(full):
            with open(full) as f:
                if "experts_held" in f.read():
                    return
    raise ValueError(
        "the family deepseek_v2 needs a program with latent attention and "
        "a routed layer that is told which experts it holds, and this "
        "tree's ray_tpu/models/llama.py has no LlamaConfig.experts_held: "
        "it cannot serve DeepSeek-V2")


_refuse_a_program_without_latent_attention()

# every key of the catalog row's ``config``, and ``share`` (see above)
CONFIG_KEYS = frozenset((
    "attention_bias", "first_k_dense_replace", "hidden_act", "hidden_size",
    "intermediate_size", "kv_lora_rank", "max_position_embeddings",
    "model_type", "moe_intermediate_size", "moe_layer_freq", "n_group",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "rms_norm_eps", "rope_scaling", "rope_theta",
    "routed_scaling_factor", "scoring_func", "seq_aux",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
    "vocab_size", "share"))

# Factors on the seeded weights' 1/sqrt(fan_in) scale (``LLMServer``'s
# ``seed_gains``; ``served_params`` gives the reference the same), after
# ``families/smallthinker.py``, which says at length why a seeded network
# at plain fan-in scale cannot be held to its reference tightly (here:
# worst probe margin 0.84 on both seeds tried at fan-in scale). The
# embedding at unit variance (x 70: sqrt(5120) = 71.6), so that a layer
# adds a fraction of the stream as a trained one does; queries that pick
# keys (``wq_b`` x 2: scores of deviation about 3 after the scale, so
# that a wrong softmax scale or a wrong rotary table moves what
# attention returns) but not so sharply that bf16's last bit decides
# which key wins (x 3 read 0.21 to 0.63 on three seeds); attention's
# output at a half (``wo``: at 1 the stream is attention's sum and its
# bf16 noise with it; at a quarter the change reads 0.00 to 0.02 and a
# plain rotary table 0.15 to 0.23, no longer told apart from it); the
# routed, shared and dense down projections at an eighth (the routed
# weights are p x 16, about 0.5 an expert: at fan-in scale the experts'
# sum would be the stream; at a quarter the change reads up to 0.22).
# ``w_down`` names the dense layer's down projection too. Every reading:
# PERF.md section 6, PR 35.
SEED_GAINS = {"embed": 70.0, "wq_b": 2.0, "wo": 0.5, "w_down": 1.0 / 8,
              "ws_down": 1.0 / 8}

# The reference check's limit, in deviations of a position's reference
# logits (``harness/families.chosen_token_margins``): how far below the
# reference's first choice a token the engine chose may lie. Readings on
# the chip under ``SEED_GAINS`` (my chip runs, PR 35; PERF.md section 6
# has every number): the worst margin of a seed's 2 x 16 probe tokens
# read 0.000 to 0.182 over 22 seeds (two of them over 0.11; mean of a
# seed's tokens at most 0.009), and of 64 greedy tokens after prompts of
# 64 to 12,000 tokens, alone and batched, 0.029 to 0.109. The same
# tokens against a reference that is wrong on purpose, worst margin: the
# layers' int8 weights rounded to int4, the nearest precision below the
# one stated, 0.84 to 1.47 on the probes of 8 seeds and 1.26 and 0.69 at
# 64 and 12,000 tokens; the softmax scale without YaRN's m^2 1.04 to
# 1.99; no shared expert 0.47 to 1.51. The limit stands 1.65 times over
# the largest sound reading of 22 seeds and 2.8 times under the smallest
# int4 reading on the probes (2.3 times under the smallest at any
# length): between its two readings with room on both sides, nearer the
# sound one, since the controls that read lowest decide what it can see.
# What it does NOT catch on every seed, each read on the same tokens:
# plain rotary frequencies for YaRN's (0.23 to 0.55 on the probes: over
# on most seeds; 2.01 after 12,000 tokens: YaRN leaves the fast pairs,
# which tell neighbours apart, as they are), the 6 largest of all 160
# experts for the group-limited choice (0.20 to 0.43: over on some
# seeds; 1.5 of a token's 6 experts are here and each is an eighth of a
# layer), and the router's product in bfloat16 (0.01 to 0.19, the
# change's own readings, as in the smallthinker family: never). Their
# MEAN margins do tell the first two apart (below), and the harness's
# ``correct`` judges the worst margin only; tests/test_mla.py and
# tests/test_moe_routed.py hold each to a plain forward at 1e-4 in
# float32.
MARGIN_LIMIT = 0.3
# ``check_long_context_latent.py`` holds the MEAN of an answer's margins
# to this as well: a lower precision or a wrong table moves every token
# a little, a swapped expert few tokens far. The change's mean read at
# most 0.005 over 64 tokens at any length; plain rotary 0.049 and 0.66,
# ungrouped 0.025 and 0.032, int4 0.34 and 0.16 there. The harness's
# ``correct`` judges the worst only.
MEAN_MARGIN_LIMIT = 0.01


def _require(config: dict) -> None:
    """The published settings this family's block is written for."""
    wanted = {"attention_bias": False, "hidden_act": "silu",
              "moe_layer_freq": 1, "norm_topk_prob": False,
              "scoring_func": "softmax", "tie_word_embeddings": False,
              "topk_method": "group_limited_greedy"}
    wrong = {k: config.get(k) for k, v in wanted.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"the family deepseek_v2 is written for {wanted}; "
                         f"this configuration has {wrong}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has a key and a value a query "
                         "head: num_key_value_heads = num_attention_heads")
    if (config["rope_scaling"] or {}).get("type") != "yarn":
        raise ValueError("the family deepseek_v2 is written for YaRN "
                         "rope_scaling")


def share_of(config: dict) -> dict:
    """The chip's share: ``chips`` that hold a layer together, the
    ``routed_experts`` the router chooses among, the ``first_expert``
    held here (``n_routed_experts`` of them), the published
    ``vocab_size``. A file without the key holds everything."""
    share = dict(config.get("share") or {})
    share.setdefault("chips", 1)
    share.setdefault("routed_experts", int(config["n_routed_experts"]))
    share.setdefault("first_expert", 0)
    share.setdefault("vocab_size", int(config["vocab_size"]))
    return share


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(config: dict, mscale_squared: bool = True) -> float:
    """``(nope + rope)^-0.5 x m^2``, m YaRN's ``mscale_all_dim`` term."""
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    scaling = config["rope_scaling"]
    m = yarn_mscale(float(scaling["factor"]),
                    float(scaling.get("mscale_all_dim", 0.0)))
    return width ** -0.5 * (m * m if mscale_squared else 1.0)


# 1. the program's configuration
def program_config(config: dict):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    _require(config)
    rehearsal = bool(config.get("rehearsal"))
    share = share_of(config)
    return LlamaConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        mlp_dim=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.float32 if rehearsal else jnp.bfloat16,
        remat=not rehearsal,
        n_experts=int(share["routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_dim=int(config["qk_nope_head_dim"]),
        qk_rope_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        attn_scale=softmax_scale(config),
        rope_scaling=tuple(sorted(config["rope_scaling"].items())),
        n_dense_layers=int(config["first_k_dense_replace"]),
        dense_mlp_dim=int(config["intermediate_size"]),
        n_shared_experts=int(config["n_shared_experts"]),
        n_group=int(config["n_group"]), topk_group=int(config["topk_group"]),
        routed_scale=float(config["routed_scaling_factor"]),
        experts_held=(int(share["first_expert"]),
                      int(config["n_routed_experts"])))


# 2. how the replica is made: ``LLMServer``, given the configuration itself
def server_arguments(config: dict, seed: int):
    return (program_config(config),), dict(
        init="random", seed=seed, quantize=config.get("quantize"),
        engine_config=dict(config["engine"]), seed_gains=dict(SEED_GAINS))


def served_params(key, config: dict):
    cfg = program_config(config)
    if config.get("quantize") == "int8":
        from ray_tpu.ops.quant import init_params_quantized

        return init_params_quantized(key, cfg, SEED_GAINS)
    from ray_tpu.models import init_params

    return init_params(key, cfg, SEED_GAINS)


# 3. the plain reference, written from the lines above; nothing of the
# program is imported. ``reference.py``'s helpers are the benchmark's own.
# Attention is one masked softmax over all the keys, taken a group of
# heads and a block of queries at a time so that a prompt of 12,000
# tokens fits beside the replica's weights: no running maximum, no
# kernel, no cache, no absorbed product.
QUERY_BLOCK, HEAD_GROUP = 256, 16


def yarn_inverse_frequencies(width: int, theta: float, scaling: dict):
    import jax.numpy as jnp

    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])
    extra = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    inter = extra / factor

    def correction(turns: float) -> float:
        return (width * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(scaling["beta_slow"]))), width - 1)
    ramp = jnp.clip((jnp.arange(width // 2, dtype=jnp.float32) - low)
                    / (0.001 if high == low else high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def _rotate(x, inv_freq, amplitude: float):
    """x: [batch, seq, heads, width]; pairs are (i, i + width/2)."""
    import jax.numpy as jnp

    seq, half = x.shape[1], x.shape[3] // 2
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angle) * amplitude)[None, :, None, :]
    sin = (jnp.sin(angle) * amplitude)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.cache
def _layer():
    """Made on first use, in the chip's holder: importing a family
    imports no jax."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import _rms_norm
    from benchmarks.harness.reference import _f32 as stored

    @functools.partial(jax.jit, static_argnames=(
        "dense", "scale", "theta", "scaling", "eps", "top_k", "n_group",
        "topk_group", "routed_scale", "first", "router_dtype", "int4",
        "shared", "nope"))
    def layer(x, lp, *, dense, scale, theta, scaling, eps, top_k, n_group,
              topk_group, routed_scale, first, router_dtype, int4, shared,
              nope):
        def _f32(w, contract=()):
            if int4 and isinstance(w, dict):
                # the control: the stored int8 values rounded to 4 bits
                w = {"q": jnp.round(w["q"].astype(jnp.float32) / 16) * 16,
                     "s": w["s"]}
            return stored(w, contract)

        def heads_of(w, at):
            """Heads ``at`` .. ``at + HEAD_GROUP`` of a stored matrix
            whose head axis is 1: [in, heads, out]."""
            return _f32(jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(
                    a, at, group, a.ndim - 2), w), (0,))

        scaling_ = dict(scaling) if scaling else None
        h = _rms_norm(x, _f32(lp["attn_norm"]), eps)
        c_q = _rms_norm(jnp.einsum("bsd,dr->bsr", h, _f32(lp["wq_a"], (0,))),
                        _f32(lp["q_a_norm"]), eps)
        kv = jnp.einsum("bsd,dr->bsr", h, _f32(lp["wkv_a"], (0,)))
        rank = lp["kv_a_norm"].shape[0]
        n_heads = lp["wo"]["q"].shape[0] if isinstance(lp["wo"], dict) \
            else lp["wo"].shape[0]
        # the program keeps W_UQ with its heads flattened
        wq_b = jax.tree.map(
            lambda a: a.reshape(*a.shape[:-1], n_heads, -1), lp["wq_b"])
        rope = (wq_b["q"] if isinstance(wq_b, dict)
                else wq_b).shape[-1] - nope
        c_kv = _rms_norm(kv[..., :rank], _f32(lp["kv_a_norm"]), eps)
        if scaling_:
            inv_freq = yarn_inverse_frequencies(rope, theta, scaling_)
            amplitude = (yarn_mscale(float(scaling_["factor"]),
                                     float(scaling_.get("mscale", 1.0)))
                         / yarn_mscale(float(scaling_["factor"]), float(
                             scaling_.get("mscale_all_dim", 0.0))))
        else:       # the control: plain rotary frequencies
            inv_freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32)
                                 / rope)
            amplitude = 1.0
        k_r = _rotate(kv[:, :, None, rank:rank + rope], inv_freq, amplitude)
        b, seq, _ = x.shape
        group = math.gcd(n_heads, HEAD_GROUP)
        key_at = jnp.arange(seq)
        pad = (-seq) % QUERY_BLOCK

        def head_group(at):
            q = jnp.einsum("bsr,rhk->bshk", c_q, heads_of(wq_b, at))
            q = jnp.concatenate([q[..., :nope], _rotate(
                q[..., nope:], inv_freq, amplitude)], -1)
            k_nope = jnp.einsum("bsc,chk->bshk", c_kv,
                                heads_of(lp["w_uk"], at))
            v = jnp.einsum("bsc,chk->bshk", c_kv, heads_of(lp["w_uv"], at))
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_r, (*k_nope.shape[:3], rope))], -1)
            blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
                b, -1, QUERY_BLOCK, *q.shape[2:]).swapaxes(0, 1)

            def one_block(first_q, qb):
                at_q = first_q + jnp.arange(QUERY_BLOCK)
                seen = key_at[None, :] <= at_q[:, None]
                scores = jnp.einsum("bqhk,bshk->bhqs", qb, k) * scale
                scores = jnp.where(seen[None, None], scores, -jnp.inf)
                return jnp.einsum("bhqs,bshk->bqhk",
                                  jax.nn.softmax(scores, -1), v)

            attended = jax.lax.map(
                lambda a: one_block(*a),
                (jnp.arange(blocks.shape[0]) * QUERY_BLOCK, blocks))
            attended = attended.swapaxes(0, 1).reshape(
                b, -1, *q.shape[2:3], v.shape[-1])[:, :seq]
            wo = _f32(jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(
                    a, at, group, 0) if a.ndim == 3 else a, lp["wo"]),
                (0, 1))
            return jnp.einsum("bshk,hkd->bsd", attended, wo)

        x = x + jax.lax.map(
            head_group, jnp.arange(0, n_heads, group)).sum(0)

        g = _rms_norm(x, _f32(lp["mlp_norm"]), eps)

        def swiglu(gate, up, down):
            return jnp.einsum(
                "bsm,md->bsd",
                jax.nn.silu(jnp.einsum("bsd,dm->bsm", g, gate))
                * jnp.einsum("bsd,dm->bsm", g, up), down)

        if dense:
            return x + swiglu(_f32(lp["w_gate"], (0,)), _f32(lp["w_up"], (0,)),
                              _f32(lp["w_down"], (0,)))
        probs = jax.nn.softmax(jnp.einsum(
            "bsd,de->bse", g.astype(router_dtype),
            _f32(lp["router"]).astype(router_dtype)).astype(jnp.float32), -1)
        experts = probs.shape[-1]
        if n_group > 1:
            best = probs.reshape(b, seq, n_group, -1).max(-1)
            _, groups = jax.lax.top_k(best, topk_group)
            kept = jax.nn.one_hot(groups, n_group, dtype=probs.dtype).sum(-2)
            probs = probs * jnp.repeat(kept, experts // n_group, axis=-1)
        chosen_p, chosen = jax.lax.top_k(probs, top_k)
        weight = jnp.einsum("bsk,bske->bse", chosen_p * routed_scale,
                            jax.nn.one_hot(chosen, experts,
                                           dtype=probs.dtype))
        held = (lp["w_gate"]["q"] if isinstance(lp["w_gate"], dict)
                else lp["w_gate"]).shape[0]

        def one_expert(out, e):
            # every expert that is HERE, plainly, on every token; the
            # stored (int8) weights multiplied out in float32 by this
            # expert's scales. An expert that is elsewhere adds nothing
            w = [_f32(jax.tree.map(lambda a: a[e], lp[name]), (0,))
                 for name in ("w_gate", "w_up", "w_down")]
            return out + jnp.take(weight, first + e, axis=-1)[..., None] \
                * swiglu(*w), None

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                              jnp.arange(held))
        if shared:
            out = out + swiglu(_f32(lp["ws_gate"], (0,)),
                               _f32(lp["ws_up"], (0,)),
                               _f32(lp["ws_down"], (0,)))
        return x + out

    return layer


def _forward(params, tokens, config: dict, *, last=None, int4=False,
             router_dtype=None, mscale_squared=True, yarn=True,
             grouped=True, shared=True):
    """The forward pass. ``last``: logits of the last ``last`` positions
    only (a 12,000-token prompt's logits over the vocabulary are 1.2
    GB). The other keywords are for the controls that show a limit
    bites (the layers' int8 weights rounded to 4 bits, the router's
    product in bfloat16, the softmax scale without YaRN's m^2, plain
    rotary frequencies for YaRN's, the 6 largest of all 160 for the
    group-limited choice, no shared expert); the harness calls it
    without them."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import _embed, _head

    _require(config)
    eps = float(config["rms_norm_eps"])
    n_dense = int(config["first_k_dense_replace"])
    share = share_of(config)
    common = dict(
        scale=softmax_scale(config, mscale_squared),
        theta=float(config["rope_theta"]),
        scaling=tuple(sorted(config["rope_scaling"].items()))
        if yarn else None,
        eps=eps, top_k=int(config["num_experts_per_tok"]),
        n_group=int(config["n_group"]) if grouped else 1,
        topk_group=int(config["topk_group"]),
        routed_scale=float(config["routed_scaling_factor"]),
        first=int(share["first_expert"]),
        router_dtype=router_dtype or jnp.float32, int4=int4,
        shared=shared, nope=int(config["qk_nope_head_dim"]))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], tokens)
        for i in range(int(config["num_hidden_layers"])):
            stack, at = (params["dense_layers"], i) if i < n_dense else \
                (params["layers"], i - n_dense)
            lp = jax.tree.map(lambda a: a[at], stack)
            x = _layer()(x, lp, dense=i < n_dense, **common)
        if last is not None:
            x = x[:, -last:]
        return _head(x, params["final_norm"], params["lm_head"], eps=eps)


def forward_logits(params, tokens, config: dict, **control):
    """tokens [batch, seq] int32 -> float32 logits [batch, seq, vocab]
    (``last=n``: of the last n positions)."""
    return _forward(params, tokens, config, **control)


def next_token_loss(params, tokens, config: dict, z_loss: float = 0.0):
    """Next-token cross-entropy with the logits' z-loss. No cell trains
    this family (the program's training forward refuses latent
    attention), so no load-balancing term is assumed."""
    import jax
    import jax.numpy as jnp

    logits = _forward(params, tokens, config)[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(logz - target + z_loss * logz * logz)


# 4. the counts, of the share that is HERE: what the chip holds and what
# a token is multiplied with on it
def _attention_params(c: dict) -> int:
    """W_DQ, W_UQ, W_DKV, W_UKV, W_O."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * (nope + rope)
            + d * (c["kv_lora_rank"] + rope)
            + c["kv_lora_rank"] * h * (nope + v) + h * v * d)


def _expert_params(c: dict) -> int:
    """One routed expert: gate, up and down of ``moe_intermediate_size``."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _shared_params(c: dict) -> int:
    return c["n_shared_experts"] * _expert_params(c)


def _dense_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _router_params(c: dict) -> int:
    return c["hidden_size"] * share_of(c)["routed_experts"]


def dense_layers(c: dict) -> int:
    return int(c["first_k_dense_replace"])


def expert_layers(c: dict) -> int:
    return int(c["num_hidden_layers"]) - dense_layers(c)


def experts_held(c: dict) -> int:
    return int(c["n_routed_experts"])


def held_experts_per_token(c: dict) -> float:
    """The routed experts a token is multiplied with HERE, on average
    under uniform routing: 6 x 40 / 160 = 1.5."""
    return (c["num_experts_per_tok"] * experts_held(c)
            / share_of(c)["routed_experts"])


def held_params(c: dict) -> int:
    """Every parameter this chip holds, with its slice of the embedding
    table and the head (norms left out: 0.1 M)."""
    layer = (_attention_params(c) + _shared_params(c) + _router_params(c)
             + experts_held(c) * _expert_params(c))
    return (dense_layers(c) * (_attention_params(c) + _dense_params(c))
            + expert_layers(c) * layer
            + 2 * c["hidden_size"] * c["vocab_size"])


def matmul_params(c: dict) -> float:
    """Parameters a token is multiplied with on this chip."""
    layer = (_attention_params(c) + _shared_params(c) + _router_params(c)
             + held_experts_per_token(c) * _expert_params(c))
    return (dense_layers(c) * (_attention_params(c) + _dense_params(c))
            + expert_layers(c) * layer + c["hidden_size"] * c["vocab_size"])


def attended_pairs(n: float) -> float:
    return n * (n + 1) / 2.0


def _pair_flops(c: dict) -> float:
    """Operations one (query, key) pair costs in the expanded form, all
    heads: 2 a product x (192 for the score + 128 for the value)."""
    return 2.0 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def latent_attention_flops(c: dict, prompt_tokens: float) -> float:
    """Operations the attention of one prompt's prefill needs, expanded:
    the causal pairs, in every layer. What the prefill flash kernel is
    measured against (a bucket's padding and the blocks on the diagonal
    computed whole are work the kernel does and the count leaves out)."""
    return (c["num_hidden_layers"] * _pair_flops(c)
            * attended_pairs(float(prompt_tokens)))


def prefill_flops(c: dict, prompt_tokens: float) -> float:
    """Operations the prefill of one prompt needs on this chip: every
    prompt token through every layer's attention projections (the
    expansion of keys and values from the latent among them), the dense
    feed-forward or the router, the shared experts and the 1.5 routed
    experts that are here (2 x the parameters), attention's causal pairs
    at 192 + 128, and the head for the one position that is sampled."""
    n = float(prompt_tokens)
    per_token = matmul_params(c) - c["hidden_size"] * c["vocab_size"]
    return (2.0 * n * per_token + latent_attention_flops(c, n)
            + 2.0 * c["hidden_size"] * c["vocab_size"])


def train_flops_per_token(c: dict, seq: int) -> float:
    """6 x the parameters a token is multiplied with here, plus
    attention's pairs forward and backward. No cell trains this family."""
    return (6.0 * matmul_params(c) + 3.0 * c["num_hidden_layers"]
            * _pair_flops(c) * attended_pairs(seq) / seq)


def latent_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Bytes of the values one cached position holds, all layers: ONE
    row of kv_lora_rank + qk_rope_head_dim a layer (the slot's padding
    to whole lanes holds nothing an algorithm needs)."""
    return (c["num_hidden_layers"] * (c["kv_lora_rank"]
                                      + c["qk_rope_head_dim"])
            * bytes_per_value)


def latent_decode_cost(c: dict, live_context_tokens: float):
    """(bytes, operations) of ONE decode step's attention over the
    cached rows, absorbed, all layers: every live position's row read
    once, and scored by 128 heads 576 wide and weighed 512 wide."""
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    flops = (c["num_hidden_layers"] * live_context_tokens
             * c["num_attention_heads"] * 2.0 * (2 * rank + rope))
    return live_context_tokens * latent_bytes_per_token(c), flops


def experts_touched(c: dict, active_rows: float) -> float:
    """The expected number of distinct experts HERE that a layer's ``n``
    rows choose, each row taking 6 of 160 uniformly: an expert is chosen
    by a row with probability 6 / 160."""
    k, total = c["num_experts_per_tok"], share_of(c)["routed_experts"]
    return experts_held(c) * (1.0 - (1.0 - k / total) ** active_rows)


def _scales(c: dict, experts: float) -> float:
    """Bytes of float32 per-output-channel scales of an expert layer's
    int8 matrices (attention, shared experts, ``experts`` routed)."""
    d, h, m = (c["hidden_size"], c["num_attention_heads"],
               c["moe_intermediate_size"])
    attention = (c["q_lora_rank"] + h * (c["qk_nope_head_dim"]
                                         + c["qk_rope_head_dim"])
                 + c["kv_lora_rank"] + c["qk_rope_head_dim"]
                 + h * (c["qk_nope_head_dim"] + c["v_head_dim"]) + d)
    return 4.0 * (attention + (experts + c["n_shared_experts"])
                  * (2 * m + d))


def routed_decode_step_bytes(c: dict, active_rows: float,
                             live_context_tokens: float,
                             weight_bytes: int = 1) -> float:
    """Bytes one decode step of ``active_rows`` sequences needs from HBM
    on this chip: the dense layer, attention's matrices, the shared
    experts and the head once, the float32 router, the norms, the held
    experts the rows chose (``experts_touched``, not all 40) with their
    scales, and the latent rows of the live positions once (not a key
    and a value a head)."""
    d = c["hidden_size"]
    touched = experts_touched(c, active_rows)
    matrices = (dense_layers(c) * (_attention_params(c) + _dense_params(c))
                + expert_layers(c) * (_attention_params(c)
                                      + _shared_params(c)
                                      + touched * _expert_params(c))
                + d * c["vocab_size"])
    scales = 0.0
    if weight_bytes == 1:
        scales = expert_layers(c) * _scales(c, touched) + 4 * c["vocab_size"]
    router = 4 * expert_layers(c) * _router_params(c)
    norms = 2 * (c["num_hidden_layers"] * 2 * d + d)
    return (matrices * weight_bytes + scales + router + norms
            + live_context_tokens * latent_bytes_per_token(c))


def decode_step_bytes(c: dict, live_context_tokens: float,
                      weight_bytes: int = 1) -> float:
    """What ``decode_burst_roofline`` divides by: every matrix the chip
    holds once, all 40 held experts, and the live rows. A step of a few
    rows reads far fewer experts, so that reader is not declared for
    this family's cell; ``expert_decode_roofline`` reads
    ``routed_decode_step_bytes``."""
    return ((held_params(c) - c["hidden_size"] * c["vocab_size"])
            * weight_bytes
            + 4 * expert_layers(c) * _router_params(c)
            + live_context_tokens * latent_bytes_per_token(c))
