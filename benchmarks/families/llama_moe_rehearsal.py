"""A second family at toy size, to show that the seam carries an expert
layer: the program's *present* one (``LlamaConfig(n_experts, top_k)``,
``ops/moe.py``): a softmax router in float32 over all experts, the top k
of them a token, their weights renormalised to sum to 1, SwiGLU experts,
no token dropped. It is a rehearsal (``tiny-chat-moe``, on the CPU) and
never a cell of ``BENCHMARK.json``; it is no published model's family
(OLMoE's router, for one, does not renormalise), and a ``model_config``
PR brings its own.

What differs from ``llama_dense``: the program's configuration gets the
expert counts, the plain reference has its own layer, and the counts tell
the experts a replica *holds* (all ``E``) from those a token is
multiplied with (``k``).
"""

from __future__ import annotations

import functools

from benchmarks.families import llama_dense
from benchmarks.families.llama_dense import (  # noqa: F401
    server_class, training)

CONFIG_KEYS = llama_dense.CONFIG_KEYS | {
    "num_experts", "num_experts_per_tok", "norm_topk_prob",
    "router_aux_loss_coef"}

# No ``MARGIN_LIMIT`` or ``LOSS_TOLERANCE`` of its own: the rehearsal runs
# in float32 on both sides, so the engine and the reference differ by
# float32 rounding alone and the worst margin reads 0 unless a router's
# k-th and (k+1)-th probabilities tie to the last bit. A family served in
# bf16 has to read its margins over a dozen seeds and state its limit: one
# flipped expert moves a token's output by a whole expert's share, which
# the dense argument (a few hundredths of a deviation from bf16
# activations) does not cover.


# 1. the program's configuration
def program_config(config: dict):
    import dataclasses

    if not config["norm_topk_prob"]:
        raise ValueError("the program's expert layer renormalises the "
                         "chosen experts' weights (ops/moe.py); a router "
                         "that does not is another family")
    experts, chosen = config["num_experts"], config["num_experts_per_tok"]
    return dataclasses.replace(
        llama_dense.program_config(config), n_experts=experts, top_k=chosen,
        aux_loss_coef=float(config["router_aux_loss_coef"]),
        # an expert's buffer takes every token of the batch: the training
        # path drops none, as the reference drops none
        capacity_factor=experts / chosen)


# 2. how the replica is made: ``LLMServer`` again, with this configuration
def server_arguments(config: dict, seed: int):
    from ray_tpu.models.llama import LLAMA_CONFIGS

    LLAMA_CONFIGS[config["name"]] = program_config(config)
    return (config["name"],), dict(
        init="random", seed=seed, quantize=config.get("quantize"),
        engine_config=dict(config["engine"]))


def served_params(key, config: dict):
    if config.get("quantize"):
        raise ValueError("the program serves no quantized experts "
                         "(ops/quant.py)")
    return training()[0](key, program_config(config))


# 3. the plain reference: the dense one's attention, and an expert layer
# written from the description above, not from ``ops/moe.py``
@functools.cache
def _layer():
    """Made on first use, in the chip's holder: importing a family
    imports no jax."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import (_CONTRACT, _f32, _rms_norm,
                                              _rotate)

    attention = ("wq", "wk", "wv", "wo")

    @functools.partial(jax.jit, static_argnames=(
        "n_heads", "n_kv_heads", "top_k", "theta", "eps"))
    def layer(x, lp, *, n_heads, n_kv_heads, top_k, theta, eps):
        w = {name: _f32(lp[name], _CONTRACT[name]) for name in attention}
        h = _rms_norm(x, _f32(lp["attn_norm"]), eps)
        q = _rotate(jnp.einsum("bsd,dhk->bshk", h, w["wq"]), theta)
        k = _rotate(jnp.einsum("bsd,dhk->bshk", h, w["wk"]), theta)
        v = jnp.einsum("bsd,dhk->bshk", h, w["wv"])
        rep = n_heads // n_kv_heads
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * q.shape[-1] ** -0.5
        seq = x.shape[1]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        attended = jnp.einsum("bhqs,bshk->bqhk",
                              jax.nn.softmax(scores, -1), v)
        x = x + jnp.einsum("bshk,hkd->bsd", attended, w["wo"])

        h = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
        probs = jax.nn.softmax(
            jnp.einsum("bsd,de->bse", h, _f32(lp["router"])), -1)
        experts = probs.shape[-1]
        kth = jax.lax.top_k(probs, top_k)[0][..., -1:]
        chosen = probs >= kth                          # [b, s, E]
        weight = jnp.where(chosen, probs, 0.0)
        weight = weight / weight.sum(-1, keepdims=True)
        out = jnp.zeros_like(x)
        for e in range(experts):                       # every expert, plainly
            gate = jnp.einsum("bsd,dm->bsm", h, _f32(lp["w_gate"][e]))
            up = jnp.einsum("bsd,dm->bsm", h, _f32(lp["w_up"][e]))
            out = out + weight[..., e:e + 1] * jnp.einsum(
                "bsm,md->bsd", jax.nn.silu(gate) * up,
                _f32(lp["w_down"][e]))
        # the load-balancing term the program's loss adds (Switch): the
        # share of picks an expert gets x its mean probability, x E
        picked = chosen.astype(jnp.float32).mean((0, 1)) / top_k
        balance = experts * jnp.sum(picked * probs.mean((0, 1)))
        return x + out, balance

    return layer


def _forward(params, tokens, config: dict):
    import jax

    from benchmarks.harness.reference import _embed, _head

    eps = float(config["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        x, balance = _embed(params["embed"], tokens), 0.0
        for i in range(int(config["num_hidden_layers"])):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, b = _layer()(
                x, lp, n_heads=int(config["num_attention_heads"]),
                n_kv_heads=int(config["num_key_value_heads"]),
                top_k=int(config["num_experts_per_tok"]),
                theta=float(config["rope_theta"]), eps=eps)
            balance = balance + b
        return _head(x, params["final_norm"], params["lm_head"],
                     eps=eps), balance


def forward_logits(params, tokens, config: dict):
    """tokens [batch, seq] int32 -> float32 logits [batch, seq, vocab]."""
    return _forward(params, tokens, config)[0]


def next_token_loss(params, tokens, config: dict, z_loss: float = 0.0):
    """The dense reference's loss on these logits, plus the layers'
    load-balancing terms x ``router_aux_loss_coef``."""
    import jax
    import jax.numpy as jnp

    logits, balance = _forward(params, tokens, config)
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return (jnp.mean(logz - target + z_loss * logz * logz)
            + float(config["router_aux_loss_coef"]) * balance)


# 4. the counts: E experts held, k used
def _attention_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def _expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def held_params(c: dict) -> int:
    """Every parameter a replica holds, with the embedding table."""
    d = c["hidden_size"]
    layer = (_attention_params(c) + d * c["num_experts"]
             + c["num_experts"] * _expert_params(c) + 2 * d)
    return c["num_hidden_layers"] * layer + 2 * d * c["vocab_size"] + d


def matmul_params(c: dict) -> int:
    """Parameters a token is multiplied with: attention, the router, its
    ``k`` experts, in every layer, and the output head."""
    d = c["hidden_size"]
    layer = (_attention_params(c) + d * c["num_experts"]
             + c["num_experts_per_tok"] * _expert_params(c))
    return c["num_hidden_layers"] * layer + d * c["vocab_size"]


def train_flops_per_token(c: dict, seq: int) -> float:
    """As the dense count: 6 x the parameters a token is multiplied with
    (``k`` experts, not ``E``: the dispatch that brings a token to its
    experts is no arithmetic the algorithm needs) plus causal attention."""
    width = c["num_attention_heads"] * c["head_dim"]
    return 6.0 * matmul_params(c) + 6 * c["num_hidden_layers"] * width * seq


def decode_step_bytes(c: dict, live_context_tokens: float,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode step needs from HBM: every matrix the replica
    holds once (a batch of a few sequences x ``k`` picks touches every
    one of a few experts; a family with many experts has to count those
    a step's tokens chose, or its roofline share reads too high), the
    float32 router, the norms in bf16, and the live keys and values."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    if weight_bytes != 2:
        raise ValueError("the program serves no quantized experts")
    matrices = layers * (_attention_params(c)
                         + c["num_experts"] * _expert_params(c)) \
        + d * c["vocab_size"]
    router = 4 * layers * d * c["num_experts"]
    norms = 2 * (2 * layers * d + d)
    return (matrices * weight_bytes + router + norms
            + live_context_tokens * llama_dense.kv_bytes_per_token(c))
