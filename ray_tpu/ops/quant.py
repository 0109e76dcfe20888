"""Weight-only int8 quantization (w8a16) for serving.

Why this exists: Llama-3-8B's bf16 parameters are 16.1 GB — more than
one 16 GB v5e holds — so the BASELINE 7B-class model cannot touch a
single chip at full precision. Per-output-channel symmetric int8 halves
weight bytes (8B → 8.0 GB) and the model fits with room for the paged
KV cache. The reference only reaches quantized serving by passing
engine kwargs through to vLLM (ref: python/ray/llm/_internal/serve/
deployments/llm/vllm/vllm_models.py:59 `engine_kwargs`); this framework
owns its engine, so the path is native.

Design (TPU-first):
  * a quantized weight is a pytree leaf-dict ``{"q": int8[w.shape],
    "s": f32[output-dims]}`` — scales are indexed by the NON-contracted
    (output) dims, so ``einsum(x, q) * s`` is bit-exact with
    dequantize-then-matmul while the per-channel multiply stays a cheap
    elementwise epilogue XLA fuses into the matmul consumer;
  * decode is weight-bandwidth-bound: HBM reads the int8 bytes and the
    int8→bf16 convert fuses into the dot's operand load, so effective
    weight bandwidth doubles — int8 is a *throughput* feature on top of
    the capacity one;
  * stacked layer weights carry their "layers" axis in BOTH q and s, so
    ``lax.scan`` / per-layer tree slicing works on quantized trees
    unchanged;
  * activations stay bf16 (w8a16). Full-int8 MXU matmuls (w8a8 with
    dynamic activation scales) are the upgrade path, not the default:
    decode batch=B matmuls are too skinny for int8 MXU gains to beat
    the requantize overhead on v5e.

Quantization math: symmetric per-output-channel. ``s = amax_over_
contracted_dims(|w|) / 127``; ``q = round(w / s)``. Embeddings are
quantized per-row (each vocab entry its own scale) since lookup is a
gather, not a matmul.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "quantize_weight", "dequantize_weight", "weight_einsum",
    "embed_lookup", "quantize_params", "init_params_quantized",
    "is_quantized",
]


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def quantize_weight(w, contract_axes: Sequence[int]) -> Dict[str, Any]:
    """Symmetric per-output-channel int8. ``contract_axes``: the axes a
    matmul will contract (reduced out of the scale). Works on numpy
    arrays (host-side checkpoint load) and jax arrays alike."""
    xp = np if isinstance(w, np.ndarray) else jnp
    wf = xp.asarray(w, dtype=xp.float32)
    amax = xp.max(xp.abs(wf), axis=tuple(contract_axes))
    s = xp.maximum(amax, 1e-8) / 127.0
    s_b = xp.expand_dims(s, tuple(contract_axes))
    q = xp.clip(xp.round(wf / s_b), -127, 127).astype(xp.int8)
    return {"q": q, "s": s.astype(xp.float32)}


def dequantize_weight(w: Dict[str, Any], contract_axes: Sequence[int],
                      dtype=jnp.bfloat16):
    xp = np if isinstance(w["q"], np.ndarray) else jnp
    s_b = xp.expand_dims(w["s"], tuple(contract_axes))
    return (w["q"].astype(xp.float32) * s_b).astype(dtype)


def weight_einsum(eq: str, x, w, *, preferred_element_type=None):
    """``jnp.einsum(eq, x, w)`` that transparently handles quantized
    ``w``. The scale multiplies the OUTPUT (exact for per-output-channel
    scales, since scales are constant along contracted dims); the
    multiply runs in f32 and the result returns in the dtype the
    unquantized einsum would have produced.

    Requirement on ``eq``: every output dim that belongs to ``w`` is a
    trailing suffix of the output spec in the same order as in ``s``
    (true for all y = x @ W projection forms: "...d,dhk->...hk" etc.).
    """
    if not is_quantized(w):
        return jnp.einsum(eq, x, w,
                          preferred_element_type=preferred_element_type)
    out = jnp.einsum(eq, x, w["q"].astype(x.dtype),
                     preferred_element_type=preferred_element_type)
    scaled = out.astype(jnp.float32) * w["s"]
    target = out.dtype if preferred_element_type is None \
        else preferred_element_type
    return scaled.astype(target)


def embed_lookup(embed, tokens, dtype=None):
    """Embedding-table row gather for raw or per-row-quantized tables."""
    if not is_quantized(embed):
        x = jnp.take(embed, tokens, axis=0)
        return x if dtype is None else x.astype(dtype)
    rows = jnp.take(embed["q"], tokens, axis=0).astype(jnp.float32)
    scale = jnp.take(embed["s"], tokens, axis=0)
    x = rows * scale[..., None]
    return x.astype(dtype or jnp.bfloat16)


# Contract-axis map for the stacked Llama layer tree (leading axis is
# "layers", never contracted). Matches models/llama.py init_params.
_LLAMA_LAYER_CONTRACT = {
    "wq": (1,),      # (L, d, h, hd)   contract d
    "wk": (1,),
    "wv": (1,),
    "wo": (1, 2),    # (L, h, hd, d)   contract h, hd
    "w_gate": (1,),  # (L, d, m)       contract d
    "w_up": (1,),
    "w_down": (1,),  # (L, m, d)       contract m
    # latent attention (models/llama.py, ``cfg.latent``)
    "wq_a": (1,),    # (L, d, rq)      contract d
    "wq_b": (1,),    # (L, rq, h * hd) contract rq
    "wkv_a": (1,),   # (L, d, rkv + rope)
    "w_uk": (1,),    # (L, rkv, h, nope): per (head, column) scales; the
    "w_uv": (1,),    # absorbed form scales the query by w_uk's instead
    # shared experts: a dense SwiGLU beside the routed ones
    "ws_gate": (1,),
    "ws_up": (1,),
    "ws_down": (1,),
    # the indexer (models/llama.py, ``cfg.sparse_top_k``)
    "wi_q": (1,),    # (L, d, J, di)   contract d
    "wi_k": (1,),    # (L, d, di)
    "wi_w": (1,),    # (L, d, J)
    # attention's output gate (``cfg.attn_output_gate``)
    "wg": (1,),      # (L, d, h, hd)   contract d
}
# expert configs: the feed-forward has an "expert" axis after "layers",
# never contracted either: per-expert per-output-channel scales (L, E, n),
# which the routed layer applies by each row's expert (ops/moe.py)
_EXPERT_LAYER_CONTRACT = {
    "w_gate": (2,),  # (L, E, d, m)    contract d
    "w_up": (2,),
    "w_down": (2,),  # (L, E, m, d)    contract m
}


def quantize_params(params: Dict, cfg=None) -> Dict:
    """Quantize a dense-Llama param tree for serving: all projection
    matrices + embedding (per-row) + lm_head go int8; norms (and an
    expert config's float32 router: its ties decide everything after
    it) stay as-is (tiny, precision-sensitive). Expert matrices get
    per-expert per-output-channel scales."""
    def stack(layers):
        layers = dict(layers)
        contract = dict(_LLAMA_LAYER_CONTRACT)
        if "router" in layers:
            contract.update(_EXPERT_LAYER_CONTRACT)
        for name, axes in contract.items():
            if name in layers:
                layers[name] = quantize_weight(layers[name], axes)
        return layers

    out = {
        "embed": quantize_weight(params["embed"], (1,)),   # per-row
        "layers": stack(params["layers"]),
        "final_norm": params["final_norm"],
        "lm_head": quantize_weight(params["lm_head"], (0,)),
    }
    # stacks beside ``layers``: leading dense layers; the linear layers
    # of a pattern whose kinds have weights of their own
    for name in ("dense_layers", "linear_layers"):
        if name in params:
            out[name] = stack(params[name])
    return out


def init_params_quantized(key, cfg, gains=None) -> Dict:
    """Random int8 params DIRECTLY on device — the benchmarking path
    for configs whose bf16 init cannot exist on one chip (8B: 16.1 GB
    bf16 vs 8.0 GB int8). ``jax.random.bits`` emits uint8 natively so
    no 4x int32 intermediate is ever allocated; values are bitcast to
    int8 and scales chosen so dequantized weights look like the
    1/sqrt(fan_in) init (uniform int8 has RMS ≈ 74, so
    s = fan_in**-0.5 / 74 gives unit-variance-scaled projections).

    ``gains``: a matrix's name ("embed", "wq", "w_down", ...) -> a factor
    on its seeded scale (``models.llama.init_params`` takes the same);
    None: every matrix at 1/sqrt(fan_in).

    The whole init is ONE jitted program: eagerly it would dispatch and
    load ~50 single-op executables."""
    return _init_params_quantized_jit(
        key, cfg, tuple(sorted((gains or {}).items())))


@partial(jax.jit, static_argnums=(1, 2))
def _init_params_quantized_jit(key, cfg, gains=()) -> Dict:
    d, hd = cfg.dim, cfg.head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    # what a configuration of the older kinds draws from these 16 keys
    # stays what it was; latent attention, shared experts and leading
    # dense layers need more and draw from a second set
    plain = not (cfg.latent or cfg.n_shared_experts or cfg.n_dense_layers
                 or cfg.sparse_top_k or cfg.own_weights or cfg.router_bias
                 or cfg.post_norms)
    ks = iter(jax.random.split(key, 16) if plain else jax.random.split(
        jax.random.fold_in(key, 1), 64))
    gains = dict(gains)
    used = set()

    def qrand(shape, fan_in, out_dims: Tuple[int, ...], spread=False,
              name=None):
        used.add(name)
        bits = jax.random.bits(next(ks), shape, jnp.uint8)
        q = jax.lax.bitcast_convert_type(bits, jnp.int8)
        s_shape = tuple(shape[i] for i in out_dims)
        s = jnp.full(s_shape, gains.get(name, 1.0) * (fan_in ** -0.5) / 74.0,
                     jnp.float32)
        if spread:
            # scales that differ by expert and channel (x 0.5 to 1.5):
            # a product scaled by another expert's scales shows
            s = s * jax.random.uniform(next(ks), s_shape, jnp.float32,
                                       0.5, 1.5)
        return {"q": q, "s": s}

    def gain(L, width, name=None):
        # learned gains scattered about 1 (about ``gains[name]``, for a
        # norm that has a name there), so that a norm over the wrong
        # width or with the wrong weight shows against a reference
        used.add(name)
        return (gains.get(name, 1.0) * (1.0 + 0.25 * jax.random.normal(
            next(ks), (L, width), jnp.float32))).astype(jnp.bfloat16)

    def attention_leaves(L):
        if not cfg.latent:
            return {
                "wq": qrand((L, d, h, hd), d, (0, 2, 3), name="wq"),
                "wk": qrand((L, d, hkv, hd), d, (0, 2, 3), name="wk"),
                "wv": qrand((L, d, hkv, hd), d, (0, 2, 3), name="wv"),
                "wo": qrand((L, h, hd, d), h * hd, (0, 3), name="wo"),
            }
        rq, rkv, v = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.v_head_dim
        return {
            "wq_a": qrand((L, d, rq), d, (0, 2), name="wq_a"),
            "q_a_norm": gain(L, rq),
            "wq_b": qrand((L, rq, h * hd), rq, (0, 2), name="wq_b"),
            "wkv_a": qrand((L, d, cfg.latent_dim), d, (0, 2), name="wkv_a"),
            "kv_a_norm": gain(L, rkv),
            # scales that differ by head and column: an absorbed product
            # that forgot w_uk's scales shows
            "w_uk": qrand((L, rkv, h, cfg.qk_nope_dim), rkv, (0, 2, 3),
                          True, "w_uk"),
            "w_uv": qrand((L, rkv, h, v), rkv, (0, 2, 3), True, "w_uv"),
            "wo": qrand((L, h, v, d), h * v, (0, 3), name="wo"),
        }

    def dense_mlp(L, m):
        return dict(
            w_gate=qrand((L, d, m), d, (0, 2), name="w_gate"),
            w_up=qrand((L, d, m), d, (0, 2), name="w_up"),
            w_down=qrand((L, m, d), m, (0, 2), name="w_down"))

    # made in this order: each qrand takes the next key, and a dense
    # config's weights for a seed are what they always were
    embed = qrand((cfg.vocab, d), d, (0,), name="embed")
    L, m = cfg.n_moe_layers, cfg.mlp_dim
    if cfg.own_weights:
        L = cfg.n_kv_layers          # ``layers``: the layers with pages
    layers = {
        "attn_norm": jnp.ones((L, d), jnp.bfloat16),
        **attention_leaves(L),
        "mlp_norm": jnp.ones((L, d), jnp.bfloat16),
    }
    if cfg.n_experts:
        E, held = cfg.n_experts, cfg.n_experts_held
        # the router stays float32, as init_params makes it; all E
        # outputs, whatever part of the experts is held here
        layers["router"] = jax.random.normal(
            next(ks), (L, d, E), jnp.float32) * (d ** -0.5)
        layers.update(
            w_gate=qrand((L, held, d, m), d, (0, 1, 3), True, "w_gate"),
            w_up=qrand((L, held, d, m), d, (0, 1, 3), True, "w_up"),
            w_down=qrand((L, held, m, d), m, (0, 1, 3), True, "w_down"))
        if cfg.n_shared_experts:
            ms = cfg.n_shared_experts * m
            layers.update(
                ws_gate=qrand((L, d, ms), d, (0, 2), name="ws_gate"),
                ws_up=qrand((L, d, ms), d, (0, 2), name="ws_up"),
                ws_down=qrand((L, ms, d), ms, (0, 2), name="ws_down"))
    else:
        layers.update(dense_mlp(L, m))
    def qk_norms(n):
        by_head = cfg.qk_norm_by_head
        return dict(q_norm=gain(n, hd if by_head else h * hd, "q_norm"),
                    k_norm=gain(n, hd if by_head else hkv * hd, "k_norm"))

    if cfg.qk_norm:
        layers.update(qk_norms(L))
    if cfg.sparse_top_k:
        # the indexer's three projections, int8 like their neighbours,
        # and its key's LayerNorm: gains about 1, biases about 0
        J, di = cfg.indexer_heads, cfg.indexer_dim
        layers.update(
            wi_q=qrand((L, d, J, di), d, (0, 2, 3), name="wi_q"),
            wi_k=qrand((L, d, di), d, (0, 2), name="wi_k"),
            wi_w=qrand((L, d, J), d, (0, 2), name="wi_w"),
            wi_k_norm=gain(L, di),
            wi_k_bias=(gain(L, di) - 1.0).astype(jnp.bfloat16))
    if cfg.attn_output_gate:
        layers["wg"] = qrand((L, d, h, hd), d, (0, 2, 3), name="wg")

    def post_norms(n):
        return dict(post_attn_norm=gain(n, d, "post_attn_norm"),
                    post_mlp_norm=gain(n, d, "post_mlp_norm"))

    if cfg.post_norms:
        layers.update(post_norms(L))
    if cfg.router_bias:
        # float32 and never quantized, as the router; seeded NON-zero
        # (``models.llama.init_params``)
        from ..models.llama import EXPERT_BIAS_SCALE

        used.add("expert_bias")
        layers["expert_bias"] = (
            gains.get("expert_bias", 1.0) * EXPERT_BIAS_SCALE
            * jax.random.normal(next(ks), (L, cfg.n_experts), jnp.float32))
    params = {
        "embed": embed,
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.bfloat16),
        "lm_head": qrand((d, cfg.vocab), d, (1,), name="lm_head"),
    }
    if cfg.own_weights:
        # the linear layers' stack (``models.llama.init_params``)
        n, lh = cfg.n_linear_layers, cfg.linear_heads
        params["linear_layers"] = {
            "attn_norm": jnp.ones((n, d), jnp.bfloat16),
            "wq": qrand((n, d, lh, hd), d, (0, 2, 3), name="wq"),
            "wk": qrand((n, d, lh, hd), d, (0, 2, 3), name="wk"),
            "wv": qrand((n, d, lh, hd), d, (0, 2, 3), name="wv"),
            "wo": qrand((n, lh, hd, d), lh * hd, (0, 3), name="wo"),
            "wg": qrand((n, d, lh, hd), d, (0, 2, 3), name="wg"),
            "q_norm": gain(n, hd, "q_norm"),
            "k_norm": gain(n, hd, "k_norm"),
            "o_norm": gain(n, lh * hd, "o_norm"),
            "mlp_norm": jnp.ones((n, d), jnp.bfloat16),
            **dense_mlp(n, m),
        }
    if cfg.n_dense_layers:
        n = cfg.n_dense_layers
        params["dense_layers"] = {
            "attn_norm": jnp.ones((n, d), jnp.bfloat16),
            **attention_leaves(n),
            "mlp_norm": jnp.ones((n, d), jnp.bfloat16),
            **dense_mlp(n, cfg.dense_mlp_dim),
        }
        # what a layer's attention half has beside its projections
        if cfg.qk_norm:
            params["dense_layers"].update(qk_norms(n))
        if cfg.attn_output_gate:
            params["dense_layers"]["wg"] = qrand((n, d, h, hd), d,
                                                 (0, 2, 3), name="wg")
        if cfg.post_norms:
            params["dense_layers"].update(post_norms(n))
    unknown = set(gains) - used
    if unknown:
        raise ValueError(f"gains for matrices that are not seeded: "
                         f"{sorted(unknown)}")
    return params
