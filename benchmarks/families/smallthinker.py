"""The family of SmallThinker-21BA3B (PowerInfer; ``model_name``
``smallthinker_21b_instruct``): a decoder whose layers come in two kinds
side by side, whose router sits before attention, and whose experts are
gated by ReLU. The layer, from the catalog row's ``config`` and
``described_as`` and the public model code (l counts from 0;
``rope_layout[l]`` and ``sliding_window_layout[l]`` are both
``[0, 1, 1, 1]`` repeated):

    h  = rmsnorm_attn(x)                                    eps 1e-6
    r  = h W_r       float32, 64 logits: the router reads the ATTENTION's
                     input, not the feed-forward's
    q  = h Wq [28 x 128]   k = h Wk [4 x 128]   v = h Wv [4 x 128]
                     no bias, no QK-norm; 28 x 128 = 3584, not hidden 2560
    layout 1:  q, k = rot(q), rot(k), theta 1.5e6; key j scores for query
               i where 0 <= i - j < 4096
    layout 0:  no rotary embedding at all (NoPE); key j scores for query i
               where j <= i
    x  = x + softmax(q k^T / sqrt(128)) v Wo
    g  = rmsnorm_mlp(x)
    top 6 of r;  p = softmax over those 6 logits  (= the full softmax
               renormalised over the 6: norm_topk_prob true)
    x  = x + sum_e p_e Wdown_e(relu(Wgate_e g) * Wup_e g)
               64 experts of width 768, no shared expert, no dense layer
    logits = rmsnorm(x) W_head           untied, vocabulary 151936

Two points are inferences (the configuration file states them under
``assumed``): that the router reads the NORMALISED attention input
(``described_as`` says only "router placed before attention"; the public
model code computes the router's product on the output of the input norm
and hands it to the expert block after attention), and that a window
layer's query sees 4096 keys counting its own (``i - j <
sliding_window_size``). Departure: the "secondary experts" of
``described_as`` have no key in ``config`` and are left out.

The program serves it through ``LLMServer`` with ``LlamaConfig(head_size,
layer_pattern, window, router_input="attention", expert_act="relu")``,
two layer groups in the paged cache (``llm/cache.py``) and the dropless
routed layer of ``ops/moe.py``. This file is what the harness knows of
it. Importing it imports no jax.
"""

from __future__ import annotations

import functools
import importlib.util
import os

from benchmarks.families.llama_dense import (  # noqa: F401
    server_class, training)


def _refuse_a_program_without_layer_groups() -> None:
    """A tree older than the layer pattern would serve this family with
    every layer full and rotated, the router on the feed-forward's input
    and silu experts, under one pool that cannot hold the cell's slots,
    or fail in the replica's constructor, for which ``serve_cell`` waits
    25 minutes. Look at the source (no import of the program, no jax)
    and stop the run before the runtime starts."""
    spec = importlib.util.find_spec("ray_tpu")
    found = {"models/llama.py": "layer_pattern",
             "llm/cache.py": "window_group_pages",
             "ops/moe.py": "def router_logits("}
    missing = []
    for path, mark in found.items():
        source = ""
        for root in (spec.submodule_search_locations or []) if spec else []:
            full = os.path.join(root, *path.split("/"))
            if os.path.isfile(full):
                with open(full) as f:
                    source = f.read()
        if mark not in source:
            missing.append(f"ray_tpu/{path} ({mark.strip('( ')})")
    if missing:
        raise ValueError(
            "the family smallthinker needs a program with layer patterns, "
            "layer groups in the page cache and a router that can read "
            "before attention, and this tree lacks " + ", ".join(missing)
            + ": it cannot serve SmallThinker")


_refuse_a_program_without_layer_groups()

# every key of the catalog row's ``config``
CONFIG_KEYS = frozenset((
    "head_dim", "hidden_size", "max_position_embeddings", "model_name",
    "moe_ffn_hidden_size", "moe_num_active_primary_experts",
    "moe_num_primary_experts", "moe_primary_router_apply_softmax",
    "norm_topk_prob", "num_attention_heads", "num_hidden_layers",
    "num_key_value_heads", "rms_norm_eps", "rope_layout", "rope_scaling",
    "rope_theta", "sliding_window_layout", "sliding_window_size",
    "tie_word_embeddings", "vocab_size"))

# Assumed (the catalog row has no key for it): the load-balancing term's
# coefficient, as ``families/olmoe.py``. Only ``next_token_loss`` and
# ``program_config`` use it; no cell trains this family.
BALANCE_COEFFICIENT = 0.01

# Factors on the seeded weights' 1/sqrt(fan_in) scale (``LLMServer``'s
# ``seed_gains``; ``served_params`` gives the reference the same), so that
# the seeded network can be held to its reference as tightly as a lower
# precision demands. At fan-in scale alone an embedding row is a fiftieth
# of a layer's output, attention averages its keys (scores of deviation
# 1) and the renormalised experts' output is the largest term of every
# layer: the stream is the experts' sum, one swapped sixth expert (a 6th
# and 7th router logit within bf16's noise: about one token-layer in
# eight) trades a third of a layer's expert output and re-routes every
# layer above it, greedy decoding falls into cycles of 3 to 9 tokens, and
# the worst margin of 32 tokens read 0.13 to 0.86 with the layers'
# weights in int4 at 0.57 to 1.21: no limit told them apart (PERF.md
# section 6, PR 33's first session). With the embedding at unit variance
# (x 50, sqrt(2560) = 50.6), queries that pick keys (x 3: scores of
# deviation 3) and the experts' down projections at a sixteenth, a layer
# adds a fraction of the stream as a trained one does, 9 to 16 of 16
# greedy tokens are distinct, the same swap moves a logit by hundredths
# of its deviation, and what attention gets wrong shows. Tried on the
# CPU at this depth and these widths (one seed, worst margin of the two
# probes, sound / int4 / silu for relu): fan-in scale 0.53 / 1.49 / 0.96;
# experts at 1/16 alone: one token repeated, every reading 0; with the
# embedding x 50: 0.008 / 0.44 / 0.02 (7 and 5 distinct tokens); with
# queries x 3 too, experts at 1/16, 1/8, 1/4: 0.023 / 1.55 / 0.11, 0.127 /
# 1.63 / 0.22, 0.153 / 1.70 / 0.26. The price: the experts are a thirtieth
# of a layer's output, so silu for relu reads 0.0 to 0.12 on the chip
# beside the change's own 0.03 to 0.07 and is NOT told apart by the
# probes (tests/test_llm_groups.py holds the activation, the early router
# and every kind of layer to this reference at 1e-4 in float32).
SEED_GAINS = {"embed": 50.0, "wq": 3.0, "w_down": 1.0 / 16}

# The reference check's limit, in deviations of a position's reference
# logits (``harness/families.chosen_token_margins``): how far below the
# reference's first choice a token the engine chose may lie. Readings on
# the chip under ``SEED_GAINS`` (PERF.md section 6, PR 33, second
# session): the worst margin of a seed's 2 x 16 probe tokens read 0.0 to
# 0.087 over 52 seeds (mean at most 0.0063; 81 to 100% of tokens the
# reference's first choice), and of 32 greedy tokens after prompts of 48
# to 12,032 tokens, alone and batched, 0.004 to 0.126 in 13 readings
# (mean 0.0002 to 0.0051). The same tokens against a reference that is
# wrong on purpose, worst margin: the layers' int8 weights rounded to
# int4, the nearest precision below the one stated, 0.91 to 2.17 on the
# probes of every one of the 52 seeds and 0.97 to 1.47 at 64 and 4,160
# tokens on two seeds (means 0.23 to 0.62); rotary on the NoPE layers
# 1.75 to 3.44; every layer full 1.06 to 1.42 at 6,144 and 8,170 tokens
# and 2.11, 2.50 at 12,032 (means 0.40 to 0.81). The limit stands 3.4
# times over the probes' largest reading, 2.4 times over the largest at
# any length and 3.0 times under the smallest int4 reading. What it does not catch, each
# read on the same tokens: the router's product in bfloat16 (0.021 to
# 0.068, the change's own readings: the engine feeds its float32 router a
# bf16 hidden state, so a bf16 product on the reference's float32 one adds
# what is already there), and silu for relu (0.0 to 0.12, above).
MARGIN_LIMIT = 0.3
# ``check_long_context.py`` holds the MEAN of an answer's 32 margins to
# this as well: a lower precision or a wrong mask moves every token a
# little, a swapped expert few tokens far. The change read at most
# 0.0063, the controls above 0.23 to 1.32: six times over the one and
# six under the other. The harness's ``correct`` judges the worst only.
MEAN_MARGIN_LIMIT = 0.04


def _require(config: dict) -> None:
    """The published settings this family's block is written for."""
    wanted = {"moe_primary_router_apply_softmax": True,
              "norm_topk_prob": True, "rope_scaling": None,
              "tie_word_embeddings": False}
    wrong = {k: config.get(k) for k, v in wanted.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"the family smallthinker is written for {wanted}; "
                         f"this configuration has {wrong}")


def layer_kinds(config: dict) -> list:
    """(rotated, windowed) of each layer that is run: the published
    layouts hold all 52 layers', the first ``num_hidden_layers`` count."""
    n = int(config["num_hidden_layers"])
    rope, window = config["rope_layout"], config["sliding_window_layout"]
    if len(rope) < n or len(window) < n:
        raise ValueError(f"the layouts state {len(rope)} and {len(window)} "
                         f"layers, fewer than num_hidden_layers={n}")
    return [(bool(r), bool(w)) for r, w in zip(rope[:n], window[:n])]


def _period(kinds: list) -> list:
    """The shortest run of kinds that, repeated, is ``kinds``."""
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return kinds[:n]
    return kinds


# 1. the program's configuration
def program_config(config: dict):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    _require(config)
    names = {(True, False): "full", (False, False): "full_nope",
             (True, True): "window", (False, True): "window_nope"}
    rehearsal = bool(config.get("rehearsal"))
    experts = int(config["moe_num_primary_experts"])
    top_k = int(config["moe_num_active_primary_experts"])
    return LlamaConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"],
        mlp_dim=config["moe_ffn_hidden_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.float32 if rehearsal else jnp.bfloat16,
        remat=not rehearsal, n_experts=experts, top_k=top_k,
        norm_topk_prob=bool(config["norm_topk_prob"]),
        aux_loss_coef=BALANCE_COEFFICIENT,
        capacity_factor=experts / top_k,
        layer_pattern=tuple(names[k] for k in _period(layer_kinds(config))),
        window=int(config["sliding_window_size"]),
        router_input="attention", expert_act="relu")


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
# Attention is one masked softmax over all the keys, taken a block of
# queries at a time so that a prompt of 12,000 tokens fits beside the
# replica's weights: no running maximum, no kernel, no cache.
QUERY_BLOCK = 256


@functools.cache
def _layer():
    """Made on first use, in the chip's holder: importing a family
    imports no jax."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import _CONTRACT, _rms_norm, _rotate
    from benchmarks.harness.reference import _f32 as stored

    attention = ("wq", "wk", "wv", "wo")

    @functools.partial(jax.jit, static_argnames=(
        "n_kv_heads", "top_k", "rotate", "window", "theta", "eps",
        "router_dtype", "act", "int4"))
    def layer(x, lp, *, n_kv_heads, top_k, rotate, window, theta, eps,
              router_dtype, act, int4):
        def _f32(w, contract=()):
            if int4 and isinstance(w, dict):
                # the control: the stored int8 values rounded to 4 bits
                w = {"q": jnp.round(w["q"].astype(jnp.float32) / 16) * 16,
                     "s": w["s"]}
            return stored(w, contract)

        w = {name: _f32(lp[name], _CONTRACT[name]) for name in attention}
        h = _rms_norm(x, _f32(lp["attn_norm"]), eps)
        # the router reads the attention's normalised input
        router = jnp.einsum("bsd,de->bse", h.astype(router_dtype),
                            _f32(lp["router"]).astype(router_dtype)
                            ).astype(jnp.float32)
        q = jnp.einsum("bsd,dhk->bshk", h, w["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, w["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, w["wv"])
        if rotate:
            q, k = _rotate(q, theta), _rotate(k, theta)
        b, seq, heads, hd = q.shape
        q = q.reshape(b, seq, n_kv_heads, heads // n_kv_heads, hd)
        pad = (-seq) % QUERY_BLOCK
        blocks = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3).reshape(
            b, -1, QUERY_BLOCK, *q.shape[2:]).swapaxes(0, 1)
        key_at = jnp.arange(seq)

        def one_block(first, qb):
            at = first + jnp.arange(QUERY_BLOCK)
            seen = key_at[None, :] <= at[:, None]
            if window is not None:
                seen &= at[:, None] - key_at[None, :] < window
            scores = jnp.einsum("bqgrk,bsgk->bgrqs", qb, k) * hd ** -0.5
            scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
            return jnp.einsum("bgrqs,bsgk->bqgrk",
                              jax.nn.softmax(scores, -1), v)

        attended = jax.lax.map(
            lambda a: one_block(*a),
            (jnp.arange(blocks.shape[0]) * QUERY_BLOCK, blocks))
        attended = attended.swapaxes(0, 1).reshape(
            b, -1, heads, hd)[:, :seq]
        x = x + jnp.einsum("bshk,hkd->bsd", attended, w["wo"])

        g = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
        experts = router.shape[-1]
        best, chosen = jax.lax.top_k(router, top_k)
        share = jax.nn.softmax(best, -1)       # over the 6 chosen logits
        weight = jnp.einsum("bsk,bske->bse", share, jax.nn.one_hot(
            chosen, experts, dtype=share.dtype))

        def one_expert(out, e):
            # every expert, plainly, on every token; the stored (int8)
            # weights multiplied out in float32 by this expert's scales
            gate = jnp.einsum("bsd,dm->bsm", g, _f32(
                jax.tree.map(lambda a: a[e], lp["w_gate"]), (0,)))
            up = jnp.einsum("bsd,dm->bsm", g, _f32(
                jax.tree.map(lambda a: a[e], lp["w_up"]), (0,)))
            hidden = (jax.nn.relu(gate) if act == "relu"
                      else jax.nn.silu(gate)) * up
            down = jnp.einsum("bsm,md->bsd", hidden, _f32(
                jax.tree.map(lambda a: a[e], lp["w_down"]), (0,)))
            return out + jnp.take(weight, e, axis=-1)[..., None] * down, None

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                              jnp.arange(experts))
        # the load-balancing term (Switch): the share of picks an expert
        # gets x its mean probability over all experts, x E
        probs = jax.nn.softmax(router, -1)
        picked = (weight > 0).astype(probs.dtype).mean((0, 1)) / top_k
        balance = experts * jnp.sum(picked * probs.mean((0, 1)))
        return x + out, balance

    return layer


def _forward(params, tokens, config: dict, *, last=None, all_full=False,
             rotate_all=False, router_dtype=None, act="relu", int4=False):
    """The forward pass. ``last``: logits of the last ``last`` positions
    only (a 12,000-token prompt's logits over the whole vocabulary are
    7 GB). The other keywords are for the controls that show a limit
    bites (every layer full, rotary on the NoPE layers too, the router's
    product in bfloat16, silu for relu, the layers' int8 weights rounded
    to 4 bits); the harness calls it without them."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import _embed, _head

    _require(config)
    eps = float(config["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        x, balance = _embed(params["embed"], tokens), 0.0
        for i, (rotated, windowed) in enumerate(layer_kinds(config)):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, b = _layer()(
                x, lp, n_kv_heads=int(config["num_key_value_heads"]),
                top_k=int(config["moe_num_active_primary_experts"]),
                rotate=rotated or rotate_all,
                window=int(config["sliding_window_size"])
                if windowed and not all_full else None,
                theta=float(config["rope_theta"]), eps=eps,
                router_dtype=router_dtype or jnp.float32, act=act,
                int4=int4)
            balance = balance + b
        if last is not None:
            x = x[:, -last:]
        return _head(x, params["final_norm"], params["lm_head"],
                     eps=eps), balance


def forward_logits(params, tokens, config: dict, **control):
    """tokens [batch, seq] int32 -> float32 logits [batch, seq, vocab]
    (``last=n``: of the last n positions)."""
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


# 4. the counts: 64 experts held, 6 a token is multiplied with; 1 layer
# in 4 sees the whole sequence, 3 in 4 a window of 4096
def experts_held(c: dict) -> int:
    return int(c["moe_num_primary_experts"])


def experts_per_token(c: dict) -> int:
    return int(c["moe_num_active_primary_experts"])


def window_layers(c: dict) -> int:
    return sum(windowed for _rotated, windowed in layer_kinds(c))


def _attention_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def _expert_params(c: dict) -> int:
    """One expert: gate, up and down of width ``moe_ffn_hidden_size``."""
    return 3 * c["hidden_size"] * c["moe_ffn_hidden_size"]


def held_params(c: dict) -> int:
    """Every parameter a replica holds, with the embedding table."""
    d = c["hidden_size"]
    layer = (_attention_params(c) + d * experts_held(c)
             + experts_held(c) * _expert_params(c) + 2 * d)
    return c["num_hidden_layers"] * layer + 2 * d * c["vocab_size"] + d


def matmul_params(c: dict) -> int:
    """Parameters a token is multiplied with: attention, the router, its
    6 experts, in every layer, and the output head."""
    d = c["hidden_size"]
    layer = (_attention_params(c) + d * experts_held(c)
             + experts_per_token(c) * _expert_params(c))
    return c["num_hidden_layers"] * layer + d * c["vocab_size"]


def attended_pairs(n: float, window=None) -> float:
    """(query, key) pairs a prompt of ``n`` tokens scores in one layer:
    query i sees i + 1 keys, or its window's where that is fewer."""
    if window is None or n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + (n - window) * float(window)


def window_attention_flops(c: dict, prompt_tokens: float) -> float:
    """Operations the window layers' attention of one prompt needs: 2
    products x 2 operations x the pairs inside the window x the
    attention width, in every window layer. What the window flash kernel
    is measured against."""
    width = c["num_attention_heads"] * c["head_dim"]
    return (window_layers(c) * 4.0 * width * attended_pairs(
        float(prompt_tokens), int(c["sliding_window_size"])))


def train_flops_per_token(c: dict, seq: int) -> float:
    """6 x the parameters a token is multiplied with, plus attention's
    pairs (forward and backward: x 3) a token on average."""
    width = c["num_attention_heads"] * c["head_dim"]
    full = c["num_hidden_layers"] - window_layers(c)
    pairs = (full * attended_pairs(seq) + window_layers(c) * attended_pairs(
        seq, int(c["sliding_window_size"]))) / seq
    return 6.0 * matmul_params(c) + 12.0 * width * pairs


def prefill_flops(c: dict, prompt_tokens: float) -> float:
    """Operations the prefill of one prompt needs: every prompt token
    through every layer's attention projections, router and its 6
    experts (2 x the parameters), attention's scores and values (2
    products x 2 operations x the pairs a layer scores x the attention
    width: all the causal pairs in a full layer, those inside the window
    in a window layer, NOT causal's), and the output head for the one
    position that is sampled. Sorting rows by expert and the padding of a
    bucket are no operations the algorithm needs."""
    d, n = c["hidden_size"], float(prompt_tokens)
    layer = (_attention_params(c) + d * experts_held(c)
             + experts_per_token(c) * _expert_params(c))
    width = c["num_attention_heads"] * c["head_dim"]
    full = c["num_hidden_layers"] - window_layers(c)
    return (c["num_hidden_layers"] * 2.0 * n * layer
            + full * 4.0 * width * attended_pairs(n)
            + window_attention_flops(c, n)
            + 2.0 * d * c["vocab_size"])


def experts_touched(c: dict, active_rows: float) -> float:
    """The expected number of distinct experts a layer's ``n`` rows
    choose, each taking ``k`` of ``E``: ``E (1 - (1 - k/E)^n)`` (as
    ``families/olmoe.py``: uniform independent routing touches the most
    experts ``n`` rows can on average)."""
    e, k = experts_held(c), experts_per_token(c)
    return e * (1.0 - (1.0 - k / e) ** active_rows)


def _layer_kv_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """Bytes of keys and values one cached position holds in ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Bytes of keys and values one position holds while every layer
    still caches it (a window layer drops it 4096 positions later)."""
    return c["num_hidden_layers"] * _layer_kv_bytes(c, bytes_per_value)


def _scales_per_layer(c: dict, experts: float) -> float:
    """float32 per-output-channel scales a layer's int8 matrices have."""
    hd, m, d = c["head_dim"], c["moe_ffn_hidden_size"], c["hidden_size"]
    attention = (c["num_attention_heads"] + 2 * c["num_key_value_heads"]) \
        * hd + d
    return 4 * (attention + experts * (2 * m + d))


def routed_decode_step_bytes(c: dict, active_rows: float,
                             live_context_tokens: float,
                             weight_bytes: int = 1) -> float:
    """Bytes one decode step of ``active_rows`` sequences needs from HBM:
    attention's matrices and the output head once, the float32 router,
    the norms in bf16, the experts the rows chose (``experts_touched``,
    not all 64) with their scales, and the live keys and values: all of
    a sequence's in a full layer, the newest ``sliding_window_size`` of
    them in a window layer.

    The rounds give the live positions summed over the active rows, so a
    row's length is taken as the mean, ``live / active``, and the window
    clips that mean. Where lengths are mixed (one row of 12,000 and
    seven of 500) the mean is under the window while the long row's keys
    beyond it are not read: clipping the mean then counts bytes that no
    step needs, so the share reads HIGH by that much and never low."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    touched = experts_touched(c, active_rows)
    matrices = layers * (_attention_params(c)
                         + touched * _expert_params(c)) + d * c["vocab_size"]
    scales = 0.0
    if weight_bytes == 1:
        scales = layers * _scales_per_layer(c, touched) \
            + 4 * c["vocab_size"]
    router = 4 * layers * d * experts_held(c)
    norms = 2 * (layers * 2 * d + d)
    full = layers - window_layers(c)
    mean = live_context_tokens / active_rows if active_rows else 0.0
    seen = active_rows * min(mean, float(c["sliding_window_size"]))
    keys = _layer_kv_bytes(c) * (full * live_context_tokens
                                 + window_layers(c) * seen)
    return matrices * weight_bytes + scales + router + norms + keys


def decode_step_bytes(c: dict, live_context_tokens: float,
                      weight_bytes: int = 1) -> float:
    """What ``decode_burst_roofline`` divides by: every matrix the
    replica holds once, all 64 experts, and every live key in every
    layer. A step of a few rows reads far fewer experts and a window
    layer far fewer keys, so that reader is not declared for this
    family's cell; ``expert_decode_roofline`` reads
    ``routed_decode_step_bytes``."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    matrices = layers * (_attention_params(c) + experts_held(c)
                         * _expert_params(c)) + d * c["vocab_size"]
    scales = 0.0
    if weight_bytes == 1:
        scales = layers * _scales_per_layer(c, experts_held(c)) \
            + 4 * c["vocab_size"]
    return (matrices * weight_bytes + scales
            + 4 * layers * d * experts_held(c) + 2 * (layers * 2 * d + d)
            + live_context_tokens * kv_bytes_per_token(c))
