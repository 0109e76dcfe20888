"""The family of OLMoE-1B-7B (allenai; ``model_type`` ``olmoe``): a
Llama-shaped decoder whose feed-forward is 64 SwiGLU experts of which a
token takes 8, with no shared expert, whose router's probabilities are
used as they are (``norm_topk_prob`` false), and whose projected queries
and keys are RMS-normalised over their whole width before the rotary
embedding. The layer, from the OLMoE paper and the ``olmoe`` model code:

    h = rmsnorm(x); q = rmsnorm_q(h Wq); k = rmsnorm_k(h Wk); v = h Wv
        (one learned weight over the whole projected width, applied
        before the split into heads and the rotary embedding)
    x = x + softmax_causal(rot(q) rot(k)^T / sqrt(hd)) v Wo
    h = rmsnorm(x); p = softmax(h Wr) in float32 over all experts
    x = x + sum over the 8 largest p_e of
            p_e * Wdown_e(silu(Wgate_e h) * Wup_e h)      (p_e as it is)

The program serves it through ``LLMServer`` with ``LlamaConfig(n_experts,
top_k, norm_topk_prob=False, qk_norm=True)`` and the dropless routed
layer of ``ops/moe.py``. This file is what the harness knows of it: the
program's configuration, the replica, the plain reference, the counts,
what is trained. Importing it imports no jax.
"""

from __future__ import annotations

import functools
import importlib.util
import os

from benchmarks.families import llama_dense
from benchmarks.families.llama_dense import (  # noqa: F401
    kv_bytes_per_token, server_class, training)


def _refuse_a_program_without_the_routed_layer() -> None:
    """A tree older than the dropless layer would serve this family
    through all 64 experts a token, renormalised and without QK-norm, or
    fail in the replica's constructor, for which ``serve_cell`` waits 25
    minutes. Look at the source (no import of the program, no jax) and
    stop the run before the runtime starts."""
    # the top-level package's spec: asking for ray_tpu.ops.moe's would
    # import ray_tpu.ops, and with it jax
    spec = importlib.util.find_spec("ray_tpu")
    source = ""
    for root in (spec.submodule_search_locations or []) if spec else []:
        path = os.path.join(root, "ops", "moe.py")
        if os.path.isfile(path):
            with open(path) as f:
                source = f.read()
    if "def moe_mlp_routed(" not in source:
        raise ValueError(
            "the family olmoe needs the program's dropless routed expert "
            "layer (ray_tpu/ops/moe.py moe_mlp_routed), and this tree has "
            "none: it cannot serve OLMoE")


_refuse_a_program_without_the_routed_layer()

# every key of the catalog row's ``config`` (the published config.json
# without the keys that say nothing of the shape), and ``head_dim``,
# which OLMoE's config.json does not state (assumed: hidden / heads)
CONFIG_KEYS = llama_dense.CONFIG_KEYS | {
    "attention_bias", "clip_qkv", "hidden_act", "model_type",
    "norm_topk_prob", "num_experts", "num_experts_per_tok", "rope_scaling",
    "tie_word_embeddings"}

# Assumed, from the OLMoE paper (its load-balancing loss, coefficient
# 0.01); the catalog row has no key for it. Only ``next_token_loss`` and
# ``program_config`` use it. The paper's router z-loss (0.001) is in
# neither yet: the program's loss has none, so the reference's loss has
# none either (the PR that adds the training cell brings both together).
BALANCE_COEFFICIENT = 0.01

# The reference check's limit, in deviations of a position's reference
# logits (``harness/families.chosen_token_margins``): how far below the
# reference's first choice a token the engine chose may lie. The dense
# limit, 0.15, is argued from bf16 activations alone. An expert layer has
# a second way to differ: where a token's 8th and 9th router
# probabilities lie within the bf16 noise of the hidden state, engine and
# reference take different eighth experts, which swaps one term of eight
# under the smallest chosen probability in one layer of 16. Readings on
# the chip (PERF.md section 6, PR 28; 16 seeds x 32 distinct probe
# tokens, and one 1,536-token prompt): the worst margin a seed read was
# 0.0282, ten seeds read 0.0; the engine's token differs from the
# reference's first choice at 3.6% of positions, by 0.02 at most of the
# time. The limit is three times the largest reading. The same probes
# against a reference that is wrong on purpose, over 12 seeds, worst
# margin a seed: weights rounded to int4 (the nearest precision below)
# 0.25 to 1.39, renormalised router weights 0.39 to 1.42: every seed
# over the limit; no QK-norm 0.09 to 0.45 in 13 seeds of 14 and 0.0 in
# one; 7 experts for 8 0.0 to 0.13, over the limit in 6 seeds of 14:
# dropping a token's smallest expert (2% of its expert output) moves 32
# greedy tokens less than a check of this size can always see, at any
# limit above the engine's own noise. A dropped layer, a wrong page or
# position read about 4.
MARGIN_LIMIT = 0.08


def _require(config: dict) -> None:
    """The published settings this family's block is written for."""
    wanted = {"attention_bias": False, "clip_qkv": None,
              "hidden_act": "silu", "model_type": "olmoe",
              "rope_scaling": None, "tie_word_embeddings": False}
    wrong = {k: config.get(k) for k, v in wanted.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"the family olmoe is written for {wanted}; this "
                         f"configuration has {wrong}")


# 1. the program's configuration
def program_config(config: dict):
    import dataclasses

    _require(config)
    return dataclasses.replace(
        llama_dense.program_config(config),
        n_experts=int(config["num_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        norm_topk_prob=bool(config["norm_topk_prob"]), qk_norm=True,
        aux_loss_coef=BALANCE_COEFFICIENT,
        # training's buffers take every token: nothing dropped there either
        capacity_factor=config["num_experts"]
        / config["num_experts_per_tok"])


# 2. how the replica is made: ``LLMServer`` again, given the configuration
# itself (nothing is written into ``LLAMA_CONFIGS``)
def server_arguments(config: dict, seed: int):
    return (program_config(config),), dict(
        init="random", seed=seed, quantize=config.get("quantize"),
        engine_config=dict(config["engine"]))


def served_params(key, config: dict):
    cfg = program_config(config)
    if config.get("quantize") == "int8":
        from ray_tpu.ops.quant import init_params_quantized

        return init_params_quantized(key, cfg)
    return training()[0](key, cfg)


# 3. the plain reference, written from the lines above; nothing of the
# program is imported. ``reference.py``'s helpers are the benchmark's own
# (float32 widening of a stored weight, RMSNorm, the rotary embedding in
# the half-split layout, embedding and head).
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
        "n_heads", "n_kv_heads", "top_k", "renormalise", "qk_norm", "theta",
        "eps"))
    def layer(x, lp, *, n_heads, n_kv_heads, top_k, renormalise, qk_norm,
              theta, eps):
        w = {name: _f32(lp[name], _CONTRACT[name]) for name in attention}
        h = _rms_norm(x, _f32(lp["attn_norm"]), eps)
        q = jnp.einsum("bsd,dhk->bshk", h, w["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, w["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, w["wv"])
        if qk_norm:
            # over the whole projected width, all heads together
            b, s = x.shape[:2]
            q = _rms_norm(q.reshape(b, s, -1), _f32(lp["q_norm"]),
                          eps).reshape(q.shape)
            k = _rms_norm(k.reshape(b, s, -1), _f32(lp["k_norm"]),
                          eps).reshape(k.shape)
        q, k = _rotate(q, theta), _rotate(k, theta)
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
        chosen = jax.nn.one_hot(jax.lax.top_k(probs, top_k)[1], experts,
                                dtype=probs.dtype).sum(-2)    # [b, s, E]
        weight = probs * chosen            # the probabilities as they are
        if renormalise:
            weight = weight / weight.sum(-1, keepdims=True)

        def one_expert(out, e):
            # every expert, plainly, on every token; the stored (int8)
            # weights multiplied out in float32 by this expert's scales
            gate = jnp.einsum("bsd,dm->bsm", h, _f32(
                jax.tree.map(lambda a: a[e], lp["w_gate"]), (0,)))
            up = jnp.einsum("bsd,dm->bsm", h, _f32(
                jax.tree.map(lambda a: a[e], lp["w_up"]), (0,)))
            down = jnp.einsum("bsm,md->bsd", jax.nn.silu(gate) * up, _f32(
                jax.tree.map(lambda a: a[e], lp["w_down"]), (0,)))
            share = jnp.take(weight, e, axis=-1)[..., None]
            return out + share * down, None

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                              jnp.arange(experts))
        # the load-balancing term (Switch; the paper's L_LB): the share of
        # picks an expert gets x its mean probability, x E
        picked = chosen.mean((0, 1)) / top_k
        balance = experts * jnp.sum(picked * probs.mean((0, 1)))
        return x + out, balance

    return layer


def _forward(params, tokens, config: dict, *, top_k=None, renormalise=None,
             qk_norm=True):
    """The forward pass. The keywords are for the controls that show the
    limit bites (7 experts for 8, renormalised weights, no QK-norm); the
    harness calls it without them."""
    import jax

    from benchmarks.harness.reference import _embed, _head

    _require(config)
    eps = float(config["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        x, balance = _embed(params["embed"], tokens), 0.0
        for i in range(int(config["num_hidden_layers"])):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, b = _layer()(
                x, lp, n_heads=int(config["num_attention_heads"]),
                n_kv_heads=int(config["num_key_value_heads"]),
                top_k=int(top_k or config["num_experts_per_tok"]),
                renormalise=bool(config["norm_topk_prob"]
                                 if renormalise is None else renormalise),
                qk_norm=qk_norm, theta=float(config["rope_theta"]),
                eps=eps)
            balance = balance + b
        return _head(x, params["final_norm"], params["lm_head"],
                     eps=eps), balance


def forward_logits(params, tokens, config: dict, **control):
    """tokens [batch, seq] int32 -> float32 logits [batch, seq, vocab]."""
    return _forward(params, tokens, config, **control)[0]


def next_token_loss(params, tokens, config: dict, z_loss: float = 0.0):
    """Next-token cross-entropy with the logits' z-loss the program's
    ``lm_loss`` adds, plus the layers' load-balancing terms x
    ``BALANCE_COEFFICIENT`` (assumed, see above)."""
    import jax
    import jax.numpy as jnp

    logits, balance = _forward(params, tokens, config)
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return (jnp.mean(logz - target + z_loss * logz * logz)
            + BALANCE_COEFFICIENT * balance)


# 4. the counts: 64 experts held, 8 a token is multiplied with
def experts_held(c: dict) -> int:
    return int(c["num_experts"])


def experts_per_token(c: dict) -> int:
    return int(c["num_experts_per_tok"])


def _attention_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def _expert_params(c: dict) -> int:
    """One expert: gate, up and down of width ``intermediate_size``."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _small_params(c: dict) -> int:
    """A layer's norms: two over the hidden width, and the QK-norm's two
    over the projected widths."""
    return (2 * c["hidden_size"] + (c["num_attention_heads"]
                                    + c["num_key_value_heads"])
            * c["head_dim"])


def held_params(c: dict) -> int:
    """Every parameter a replica holds, with the embedding table."""
    d = c["hidden_size"]
    layer = (_attention_params(c) + d * experts_held(c)
             + experts_held(c) * _expert_params(c) + _small_params(c))
    return c["num_hidden_layers"] * layer + 2 * d * c["vocab_size"] + d


def matmul_params(c: dict) -> int:
    """Parameters a token is multiplied with: attention, the router, its
    8 experts, in every layer, and the output head."""
    d = c["hidden_size"]
    layer = (_attention_params(c) + d * experts_held(c)
             + experts_per_token(c) * _expert_params(c))
    return c["num_hidden_layers"] * layer + d * c["vocab_size"]


def train_flops_per_token(c: dict, seq: int) -> float:
    """As the dense count: 6 x the parameters a token is multiplied with
    (8 experts, not 64) plus causal attention."""
    width = c["num_attention_heads"] * c["head_dim"]
    return 6.0 * matmul_params(c) + 6 * c["num_hidden_layers"] * width * seq


def prefill_flops(c: dict, prompt_tokens: float) -> float:
    """Operations the prefill of one prompt needs: every prompt token
    through every layer's attention projections, router and its 8
    experts (2 x the parameters), causal attention's scores and values
    (2 products x 2 operations x n^2 / 2 pairs x the attention width a
    layer), and the output head for the one position that is sampled.
    Sorting rows by expert and the padding of a bucket are no operations
    the algorithm needs."""
    d, n = c["hidden_size"], float(prompt_tokens)
    layer = (_attention_params(c) + d * experts_held(c)
             + experts_per_token(c) * _expert_params(c))
    width = c["num_attention_heads"] * c["head_dim"]
    return (c["num_hidden_layers"] * (2.0 * n * layer + 2.0 * n * n * width)
            + 2.0 * d * c["vocab_size"])


def experts_touched(c: dict, active_rows: float) -> float:
    """The expected number of distinct experts a layer's ``n`` rows
    choose, each taking ``k`` of ``E``: ``E (1 - (1 - k/E)^n)``. Routing
    that is uniform and independent touches the most experts ``n`` rows
    can on average, so a step that reads fewer reads under 100% of this
    and none can read over it for that reason."""
    e, k = experts_held(c), experts_per_token(c)
    return e * (1.0 - (1.0 - k / e) ** active_rows)


def _scales_per_layer(c: dict, experts: float) -> float:
    """float32 per-output-channel scales a layer's int8 matrices have."""
    hd, m, d = c["head_dim"], c["intermediate_size"], c["hidden_size"]
    attention = (c["num_attention_heads"] + 2 * c["num_key_value_heads"]) \
        * hd + d
    return 4 * (attention + experts * (2 * m + d))


def routed_decode_step_bytes(c: dict, active_rows: float,
                             live_context_tokens: float,
                             weight_bytes: int = 1) -> float:
    """Bytes one decode step of ``active_rows`` sequences needs from HBM:
    attention's matrices and the output head once, the float32 router,
    the norms in bf16, the experts the rows chose (``experts_touched``,
    not all 64) with their scales, and the live keys and values."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    touched = experts_touched(c, active_rows)
    matrices = layers * (_attention_params(c)
                         + touched * _expert_params(c)) + d * c["vocab_size"]
    scales = 0.0
    if weight_bytes == 1:
        scales = layers * _scales_per_layer(c, touched) \
            + 4 * c["vocab_size"]
    router = 4 * layers * d * experts_held(c)
    norms = 2 * (layers * _small_params(c) + d)
    return (matrices * weight_bytes + scales + router + norms
            + live_context_tokens * kv_bytes_per_token(c))


def decode_step_bytes(c: dict, live_context_tokens: float,
                      weight_bytes: int = 1) -> float:
    """What ``decode_burst_roofline`` divides by: every matrix the
    replica holds once, all 64 experts. A step of a few rows reads far
    fewer, so that reader is not declared for this family's cell;
    ``expert_decode_roofline`` reads ``routed_decode_step_bytes``."""
    return routed_decode_step_bytes(
        c, float("inf"), live_context_tokens, weight_bytes)
