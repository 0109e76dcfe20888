"""The family of Trinity-Large (arcee-ai; ``model_type`` ``afmoe``): a
decoder whose layers come in two kinds side by side (three with a
sliding window, then one full layer with no rotary embedding), whose
first layers have a dense feed-forward INSIDE that pattern, whose router
scores by sigmoid and chooses with a bias that the weights never see,
and whose two halves are normed before AND after (sandwich norms). The
layer, from the catalog row's ``config`` and ``described_as`` and the
family's public model code (l counts from 0; ``n(.)`` an RMSNorm with
its own learned vector, eps 1e-5):

    x0   = E[token] * sqrt(3072)                              mup_enabled
    a    = n_in(x)
    q, k, v = a Wq, a Wk, a Wv        48 / 8 / 8 heads of 128
    g    = a Wg                       48 x 128: attention's output gate
    q, k = n_q(q), n_k(k)             a head, over its 128
    q, k = rot(q), rot(k), theta 1e4  on a sliding layer only; a full
                                      layer has no rotary embedding
    o    = softmax(q k^T / sqrt(128), mask) v
           mask: j <= i, and on a sliding layer 0 <= i - j < 4096
    x    = x + n_post_attn((o * sigmoid(g)) Wo)
    m    = n_pre_mlp(x)
    layer l < num_dense_layers:  f = Wdown(silu(Wgate m) * Wup m), 12288
    else:  s = sigmoid(m Wr)          float32, 256 wide
           chosen = top4(s + b)       b: the layer's expert_bias; the
                                      CHOICE sees it
           w = s[chosen] / (sum s[chosen] + 1e-20) * 2.448
                                      the WEIGHTS do not (route_norm,
                                      route_scale)
           f = shared(m) + sum_i w_i expert_chosen_i(m)
                                      each a SwiGLU of width 3072
    x    = x + n_post_mlp(f)
    logits = n_final(x) W_head        untied

Every reading that is not a key of ``config`` is under ``assumed`` in the
configuration file: the window counts the query's own key (``i - j <
sliding_window``), ``mup_enabled`` is the embedding's scale alone,
``load_balance_coeff`` and the "SMEBU" update of the bias are
training-time only, "depth-scaled" is how the sandwich norms are
initialised.

The share. The configuration gives this chip's part of a layer that
``share.chips`` chips hold together: ``num_experts`` of the
``share.routed_experts`` experts, from ``share.first_expert`` on, and a
slice of the vocabulary. The router keeps all its outputs and its bias
and chooses among all experts; what the experts that are not here would
add is left out, here as in the program, and that partial result (under
its post norm) goes on to the next layer. Attention, the norms, the
router and the shared expert are on every chip.

The program serves it through ``LLMServer`` with ``LlamaConfig(
layer_pattern=("window", "window", "window", "full_nope"),
n_dense_layers=..., router_score="sigmoid", router_bias=True,
post_norms=True, attn_output_gate=True, qk_norm_by_head=True,
embed_scale=..., routed_scale=..., experts_held=...)``: two layer groups
in the paged cache, the dense layers at their places in the first
period, and the routed layer of ``ops/moe.py``. This file is what the
harness knows of it. Importing it imports no jax.
"""

from __future__ import annotations

import functools
import importlib.util
import os

from benchmarks.families.llama_dense import (  # noqa: F401
    server_class, training)


def _refuse_a_program_without_the_block() -> None:
    """A tree older than this family's block has no ``LlamaConfig``
    fields for it and would fail in the replica's constructor, for which
    ``serve_cell`` waits 25 minutes. Look at the source (no import of
    the program, no jax) for the fields ``program_config`` cannot do
    without, and stop the run before the runtime starts."""
    spec = importlib.util.find_spec("ray_tpu")
    source = ""
    for root in (spec.submodule_search_locations or []) if spec else []:
        full = os.path.join(root, "models", "llama.py")
        if os.path.isfile(full):
            with open(full) as f:
                source = f.read()
    missing = [name for name in ("router_score", "router_bias", "post_norms")
               if name not in source]
    if missing:
        raise ValueError(
            "the family afmoe needs a program with a sigmoid router, a "
            "selection bias, sandwich norms and dense layers inside a "
            "layer pattern, and this tree's ray_tpu/models/llama.py has no "
            f"LlamaConfig.{', '.join(missing)}: it cannot serve Trinity")


_refuse_a_program_without_the_block()

# every key of the catalog row's ``config``, and ``share`` (see above)
CONFIG_KEYS = frozenset((
    "global_attn_every_n_layers", "head_dim", "hidden_act", "hidden_size",
    "intermediate_size", "layer_types", "load_balance_coeff",
    "max_position_embeddings", "model_type", "moe_intermediate_size",
    "mup_enabled", "n_group", "num_attention_heads", "num_dense_layers",
    "num_expert_groups", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "num_limited_groups",
    "num_shared_experts", "rms_norm_eps", "rope_scaling", "rope_theta",
    "route_norm", "route_scale", "score_func", "sliding_window",
    "tie_word_embeddings", "topk_group", "use_grouped_mm", "vocab_size",
    "share"))

# Factors on the seeded weights (``LLMServer``'s ``seed_gains``;
# ``served_params`` gives the reference the same), after
# ``families/smallthinker.py``, which says at length why a seeded network
# at plain scale cannot be held to its reference tightly. This block
# leaves fewer to set: the embedding is at unit variance by the model's
# own ``mup`` scale (a row at 1/sqrt(3072) x sqrt(3072)); the QK-norm
# erases ``wq``'s and ``wk``'s scale and the post norms erase ``wo``'s
# and the scale of a feed-forward's sum, so what a half ADDS to the
# stream is its post norm's learned vector, and how sharply a query picks
# keys is the QK-norms'. The post norms at a quarter (scattered about
# it): a half adds a quarter of an embedding row's size, the stream after
# 16 halves is 1.4 rows, as a trained layer adds a fraction of the stream
# (the family initialises them "depth-scaled"). The queries' norm x 2:
# scores of deviation 2, a query attends to a hundredth of its keys, so a
# wrong mask, a rotary embedding where there is none or a dropped window
# moves what attention returns. The ROUTED experts' down projections at a
# QUARTER (``w_down``; the shared expert's ``ws_down`` at 1, and the
# dense layer's ``w_down`` is alone under its post norm, where its scale
# is erased): under the post norm only the routed experts' size beside
# the shared expert's matters, and it is set between two faults. At 1 a
# held expert (weight about 0.6) is half of what the feed-forward adds,
# and one swapped fourth expert (a 4th and 5th biased score within
# bf16's noise: the engine feeds its float32 router a bf16 hidden state;
# about one token in eight meets one in a layer here) moves the stream
# by 9%, MORE than int4 weights move it (4%): on the chip the sound
# answers read worst margins of 0.0 to 0.55 and int4 0.55 to 0.92, and no
# limit told them apart (PERF.md section 6, PR 52, call 1). At an eighth
# the sound answers read 0.000 to 0.030 and a WRONG ROUTER passed with
# them: softmax for sigmoid 0.028 to 0.274 an answer, the bias left out
# of the choice 0.000 to 0.126 (call 7: 30 answers of 32 tokens, 3
# seeds; the review of this PR: no comparison on the chip could fail the
# router). The scan of call 7, sound worst margin over 960 tokens a gain:
# 1/8 0.030, 1/4 0.047, 1/3 0.170, 1/2 0.144 (a sound token read over
# 0.6 x the gain in none of 3,840 and over 0.5 x in one); at a quarter
# the answers' worst margins are the table under ``MARGIN_LIMIT``.
# ``expert_bias`` x 1: the program's own seeded size
# (``models.llama.EXPERT_BIAS_SCALE``, 0.02 of a sigmoid score: larger,
# and a few experts would take most rows of every seed; the 4th and 5th
# of 256 scores lie about 0.02 apart), so the bias ADDED TO THE WEIGHTS
# moves a weight by 1.5% and reads what the sound answers read at every
# gain up to a half (0.000 to 0.042 beside 0.000 to 0.047 at a quarter):
# tests/test_llm_trinity.py holds it, in float32, and nothing on the chip.
SEED_GAINS = {"q_norm": 2.0, "post_attn_norm": 0.25, "post_mlp_norm": 0.25,
              "w_down": 0.25}

# The reference check's limit, in deviations of a position's reference
# logits (``harness/families.chosen_token_margins``): how far below the
# reference's first choice a token the engine chose may lie. Readings on
# the chip under ``SEED_GAINS`` (my chip runs, PR 52, calls 7 and 8;
# PERF.md section 6 has every number). Call 7, a bare engine, 3 seeds,
# 24 answers of 32 greedy tokens after 64 tokens and 6 after 992, the
# worst margin of an ANSWER (what a cell's two probes of 16 tokens are),
# smallest / median / largest:
#   sound                                   0.000 / 0.009 / 0.047
#   the router's product in bfloat16        0.000 / 0.005 / 0.035
#   the bias added to the weights           0.000 / 0.006 / 0.042
#   the bias left out of the choice         0.027 / 0.112 / 0.246
#   softmax for sigmoid                     0.033 / 0.219 / 0.401
#   the layers' int8 weights rounded to int4, the nearest precision
#   below the one stated                    0.313 / 0.413 / 0.770
# Call 8, the check on the final tree at its default seed, eight sound
# answers after 48 to 16,000 tokens: 0.000 to 0.036 and one of 0.107 (one
# token of the answer after 96 tokens: the largest sound reading at this
# gain, 0.43 x the gain; the scan's largest over all gains was 0.51 x);
# int4 0.382 and 0.409. The limit stands 1.9 times over the largest sound
# reading of 38 answers and 1.6 times under the smallest int4 reading of
# 26: between its two readings with room on both sides. A run of the cell
# is refused by ONE token: a sound token over 0.8 x the gain was not seen
# in 5,000, and the tail's slope (a factor of 0.4 for every 0.1 x the
# gain) puts one at one run in 2,000. Rotary on the full layers, every
# layer full and the post norms left out read 0.52 to 4.0 (call 8).
MARGIN_LIMIT = 0.2
# ``check_long_context_afmoe.py`` holds the MEAN of an answer's margins
# to this as well: a lower precision, a wrong mask or a wrong router
# moves many tokens a little, a swapped expert few tokens far. Call 7's
# answers, the mean of an answer's 32 margins, smallest / median /
# largest: sound 0.0000 / 0.0003 / 0.0019; the bias left out of the
# choice 0.0015 / 0.0073 / 0.0217 (18 of 30 answers over one of the two
# limits, so that the check's six answers miss it once in 250); softmax
# for sigmoid 0.0012 / 0.0221 / 0.0487 (29 of 30); int4 0.0223 / 0.0600
# / 0.0867 (24 of 24). Call 8's sound answers: 0.0000 to 0.0011 and the
# one of 0.0034 (its token of 0.107); int4 0.054 and 0.077; the bias left
# out over a limit on 2 of 6 answers, softmax on 6 of 6. The limit stands
# 1.5 times over the largest sound mean and 4.4 times under the smallest
# int4 mean. The harness's ``correct`` judges the worst only.
MEAN_MARGIN_LIMIT = 0.005


def _require(config: dict) -> None:
    """The published settings this family's block is written for."""
    wanted = {"hidden_act": "silu", "score_func": "sigmoid",
              "route_norm": True, "mup_enabled": True, "n_group": 1,
              "topk_group": 1, "num_expert_groups": 1,
              "num_limited_groups": 1, "rope_scaling": None,
              "tie_word_embeddings": False}
    wrong = {k: config.get(k) for k, v in wanted.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"the family afmoe is written for {wanted}; "
                         f"this configuration has {wrong}")


def share_of(config: dict) -> dict:
    """The chip's share: ``chips`` that hold a layer together, the
    ``routed_experts`` the router chooses among, the ``first_expert``
    held here (``num_experts`` of them), the published ``vocab_size``.
    A file without the key holds everything."""
    share = dict(config.get("share") or {})
    share.setdefault("chips", 1)
    share.setdefault("routed_experts", int(config["num_experts"]))
    share.setdefault("first_expert", 0)
    share.setdefault("vocab_size", int(config["vocab_size"]))
    return share


def layer_kinds(config: dict) -> list:
    """True for a sliding layer (window, rotary), False for a full one
    (no rotary embedding), of each layer that is run: ``layer_types``
    holds all the published layers', the first ``num_hidden_layers``
    count."""
    n = int(config["num_hidden_layers"])
    kinds = config["layer_types"]
    names = {"sliding_attention": True, "full_attention": False}
    if len(kinds) < n or set(kinds) - set(names):
        raise ValueError(f"layer_types states {len(kinds)} layers of kinds "
                         f"{sorted(set(kinds))}; want at least {n} of "
                         f"{sorted(names)}")
    return [names[k] for k in kinds[:n]]


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
    rehearsal = bool(config.get("rehearsal"))
    share = share_of(config)
    return LlamaConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"],
        mlp_dim=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.float32 if rehearsal else jnp.bfloat16,
        remat=not rehearsal,
        n_experts=int(share["routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        norm_topk_prob=bool(config["route_norm"]),
        routed_scale=float(config["route_scale"]),
        router_score=config["score_func"], router_bias=True,
        n_dense_layers=int(config["num_dense_layers"]),
        dense_mlp_dim=int(config["intermediate_size"]),
        n_shared_experts=int(config["num_shared_experts"]),
        experts_held=(int(share["first_expert"]),
                      int(config["num_experts"])),
        layer_pattern=tuple("window" if sliding else "full_nope"
                            for sliding in _period(layer_kinds(config))),
        window=int(config["sliding_window"]),
        qk_norm=True, qk_norm_by_head=True, attn_output_gate=True,
        post_norms=True, embed_scale=float(config["hidden_size"]) ** 0.5)


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
# queries at a time so that a prompt of 16,000 tokens fits beside the
# replica's weights: no running maximum, no kernel, no cache. Every held
# expert is computed on every token and weighed by the router's choice.
QUERY_BLOCK = 128


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
        "dense", "n_kv_heads", "top_k", "rotate", "window", "theta", "eps",
        "route_scale", "first", "score", "bias_in_weights",
        "bias_in_choice", "post_norms", "router_dtype", "int4"))
    def layer(x, lp, *, dense, n_kv_heads, top_k, rotate, window, theta,
              eps, route_scale, first, score, bias_in_weights,
              bias_in_choice, post_norms, router_dtype, int4):
        def _f32(w, contract=()):
            if int4 and isinstance(w, dict):
                # the control: the stored int8 values rounded to 4 bits
                w = {"q": jnp.round(w["q"].astype(jnp.float32) / 16) * 16,
                     "s": w["s"]}
            return stored(w, contract)

        def post(added, name):
            # the control leaves the norm out: the half adds what it gave
            return _rms_norm(added, _f32(lp[name]), eps) if post_norms \
                else added

        w = {name: _f32(lp[name], _CONTRACT[name]) for name in attention}
        a = _rms_norm(x, _f32(lp["attn_norm"]), eps)
        q = jnp.einsum("bsd,dhk->bshk", a, w["wq"])
        k = jnp.einsum("bsd,dhk->bshk", a, w["wk"])
        v = jnp.einsum("bsd,dhk->bshk", a, w["wv"])
        gate = jnp.einsum("bsd,dhk->bshk", a, _f32(lp["wg"], (0,)))
        # a head over its own width, one learned vector for all heads
        q = _rms_norm(q, _f32(lp["q_norm"]), eps)
        k = _rms_norm(k, _f32(lp["k_norm"]), eps)
        if rotate:
            q, k = _rotate(q, theta), _rotate(k, theta)
        b, seq, heads, hd = q.shape
        q = q.reshape(b, seq, n_kv_heads, heads // n_kv_heads, hd)
        pad = (-seq) % QUERY_BLOCK
        blocks = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3).reshape(
            b, -1, QUERY_BLOCK, *q.shape[2:]).swapaxes(0, 1)
        key_at = jnp.arange(seq)

        def one_block(first_q, qb):
            at = first_q + jnp.arange(QUERY_BLOCK)
            seen = key_at[None, :] <= at[:, None]
            if window is not None:
                seen &= at[:, None] - key_at[None, :] < window
            scores = jnp.einsum("bqgrk,bsgk->bgrqs", qb, k) * hd ** -0.5
            scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
            return jnp.einsum("bgrqs,bsgk->bqgrk",
                              jax.nn.softmax(scores, -1), v)

        attended = jax.lax.map(
            lambda args: one_block(*args),
            (jnp.arange(blocks.shape[0]) * QUERY_BLOCK, blocks))
        attended = attended.swapaxes(0, 1).reshape(
            b, -1, heads, hd)[:, :seq]
        x = x + post(jnp.einsum(
            "bshk,hkd->bsd", attended * jax.nn.sigmoid(gate), w["wo"]),
            "post_attn_norm")

        m = _rms_norm(x, _f32(lp["mlp_norm"]), eps)

        def swiglu(gate, up, down):
            return jnp.einsum(
                "bsm,md->bsd",
                jax.nn.silu(jnp.einsum("bsd,dm->bsm", m, gate))
                * jnp.einsum("bsd,dm->bsm", m, up), down)

        if dense:
            return x + post(swiglu(
                _f32(lp["w_gate"], (0,)), _f32(lp["w_up"], (0,)),
                _f32(lp["w_down"], (0,))), "post_mlp_norm")
        logits = jnp.einsum("bsd,de->bse", m.astype(router_dtype),
                            _f32(lp["router"]).astype(router_dtype)
                            ).astype(jnp.float32)
        s = jax.nn.sigmoid(logits) if score == "sigmoid" \
            else jax.nn.softmax(logits, -1)
        experts = s.shape[-1]
        biased = s + lp["expert_bias"].astype(jnp.float32)
        _, chosen = jax.lax.top_k(biased if bias_in_choice else s, top_k)
        picked = jnp.take_along_axis(biased if bias_in_weights else s,
                                     chosen, axis=-1)
        share = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
            * route_scale
        weight = jnp.einsum("bsk,bske->bse", share, jax.nn.one_hot(
            chosen, experts, dtype=share.dtype))
        held = (lp["w_gate"]["q"] if isinstance(lp["w_gate"], dict)
                else lp["w_gate"]).shape[0]

        def one_expert(out, e):
            # every expert that is HERE, plainly, on every token; the
            # stored (int8) weights multiplied out in float32 by this
            # expert's scales. An expert that is elsewhere adds nothing
            mats = [_f32(jax.tree.map(lambda t: t[e], lp[name]), (0,))
                    for name in ("w_gate", "w_up", "w_down")]
            return out + jnp.take(weight, first + e, axis=-1)[..., None] \
                * swiglu(*mats), None

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                              jnp.arange(held))
        out = out + swiglu(_f32(lp["ws_gate"], (0,)), _f32(lp["ws_up"], (0,)),
                           _f32(lp["ws_down"], (0,)))
        return x + post(out, "post_mlp_norm")

    return layer


def _forward(params, tokens, config: dict, *, last=None, all_full=False,
             rotate_all=False, bias_in_weights=False, bias_in_choice=True,
             score=None, post_norms=True, router_dtype=None, int4=False):
    """The forward pass. ``last``: logits of the last ``last`` positions
    only. The other keywords are for the controls that show a limit
    bites (every layer full, rotary on the full layers too, the bias
    added to the weights, the bias left out of the choice, softmax for
    sigmoid, the post norms left out,
    the router's product in bfloat16, the layers' int8 weights rounded
    to 4 bits); the harness calls it without them."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import _embed, _head

    _require(config)
    eps = float(config["rms_norm_eps"])
    n_dense = int(config["num_dense_layers"])
    share = share_of(config)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], tokens) \
            * float(config["hidden_size"]) ** 0.5
        for i, sliding in enumerate(layer_kinds(config)):
            stack, at = (params["dense_layers"], i) if i < n_dense else \
                (params["layers"], i - n_dense)
            lp = jax.tree.map(lambda t: t[at], stack)
            x = _layer()(
                x, lp, dense=i < n_dense,
                n_kv_heads=int(config["num_key_value_heads"]),
                top_k=int(config["num_experts_per_tok"]),
                rotate=sliding or rotate_all,
                window=int(config["sliding_window"])
                if sliding and not all_full else None,
                theta=float(config["rope_theta"]), eps=eps,
                route_scale=float(config["route_scale"]),
                first=int(share["first_expert"]),
                score=score or config["score_func"],
                bias_in_weights=bias_in_weights,
                bias_in_choice=bias_in_choice, post_norms=post_norms,
                router_dtype=router_dtype or jnp.float32, int4=int4)
        if last is not None:
            x = x[:, -last:]
        return _head(x, params["final_norm"], params["lm_head"], eps=eps)


def forward_logits(params, tokens, config: dict, **control):
    """tokens [batch, seq] int32 -> float32 logits [batch, seq, vocab]
    (``last=n``: of the last n positions)."""
    return _forward(params, tokens, config, **control)


def next_token_loss(params, tokens, config: dict, z_loss: float = 0.0):
    """Next-token cross-entropy with the logits' z-loss. No cell trains
    this family (the program's training forward refuses its block), and
    ``load_balance_coeff`` is training-time only: no balance term."""
    import jax
    import jax.numpy as jnp

    logits = _forward(params, tokens, config)[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(logz - target + z_loss * logz * logz)


# 4. the counts, of the share that is HERE: what the chip holds and what
# a token is multiplied with on it. The routed part is the EXPECTED rows:
# a token's 4 experts of 256 are here with probability 32 / 256 each
# (uniform routing, which the seeded router and a bias of 0.02 give to
# within a few percent an expert), 0.5 experts a token on average
def window_layers(c: dict) -> int:
    return sum(layer_kinds(c))


def dense_layers(c: dict) -> int:
    return int(c["num_dense_layers"])


def expert_layers(c: dict) -> int:
    return int(c["num_hidden_layers"]) - dense_layers(c)


def experts_held(c: dict) -> int:
    return int(c["num_experts"])


def held_experts_per_token(c: dict) -> float:
    """The routed experts a token is multiplied with HERE, on average
    under uniform routing: 4 x 32 / 256 = 0.5."""
    return (c["num_experts_per_tok"] * experts_held(c)
            / share_of(c)["routed_experts"])


def _attention_params(c: dict) -> int:
    """Wq, Wk, Wv, Wg, Wo."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (3 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def _expert_params(c: dict) -> int:
    """One routed expert: gate, up and down of ``moe_intermediate_size``."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _shared_params(c: dict) -> int:
    return c["num_shared_experts"] * _expert_params(c)


def _dense_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _router_params(c: dict) -> int:
    return c["hidden_size"] * share_of(c)["routed_experts"]


def _small_params(c: dict) -> int:
    """A layer's norms: four over the stream, two a head."""
    return 4 * c["hidden_size"] + 2 * c["head_dim"]


def held_params(c: dict) -> int:
    """Every parameter this chip holds, with its slice of the embedding
    table and the head, the norms and the routers' biases."""
    layer = (_attention_params(c) + _shared_params(c) + _router_params(c)
             + share_of(c)["routed_experts"]
             + experts_held(c) * _expert_params(c) + _small_params(c))
    return (dense_layers(c) * (_attention_params(c) + _dense_params(c)
                               + _small_params(c))
            + expert_layers(c) * layer
            + 2 * c["hidden_size"] * c["vocab_size"] + c["hidden_size"])


def matmul_params(c: dict) -> float:
    """Parameters a token is multiplied with on this chip."""
    layer = (_attention_params(c) + _shared_params(c) + _router_params(c)
             + held_experts_per_token(c) * _expert_params(c))
    return (dense_layers(c) * (_attention_params(c) + _dense_params(c))
            + expert_layers(c) * layer + c["hidden_size"] * c["vocab_size"])


def attended_pairs(n: float, window=None) -> float:
    """(query, key) pairs a prompt of ``n`` tokens scores in one layer:
    query i sees i + 1 keys, or its window's where that is fewer."""
    if window is None or n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + (n - window) * float(window)


def window_attention_flops(c: dict, prompt_tokens: float) -> float:
    """Operations the sliding layers' attention of one prompt needs: 2
    products x 2 operations x the pairs inside the window x the
    attention width, in every sliding layer. What the window flash
    kernel is measured against."""
    width = c["num_attention_heads"] * c["head_dim"]
    return (window_layers(c) * 4.0 * width * attended_pairs(
        float(prompt_tokens), int(c["sliding_window"])))


def prefill_flops(c: dict, prompt_tokens: float) -> float:
    """Operations the prefill of one prompt needs on this chip: every
    prompt token through every layer's attention projections and gate,
    the dense feed-forward or the router, the shared expert and the 0.5
    routed experts that are here ON AVERAGE (2 x the parameters),
    attention's scores and values (all the causal pairs in a full layer,
    those inside the window in a sliding layer), and the head for the
    one position that is sampled. Sorting rows by expert and the padding
    of a bucket are no operations the algorithm needs."""
    n = float(prompt_tokens)
    per_token = matmul_params(c) - c["hidden_size"] * c["vocab_size"]
    width = c["num_attention_heads"] * c["head_dim"]
    full = c["num_hidden_layers"] - window_layers(c)
    return (2.0 * n * per_token + full * 4.0 * width * attended_pairs(n)
            + window_attention_flops(c, n)
            + 2.0 * c["hidden_size"] * c["vocab_size"])


def train_flops_per_token(c: dict, seq: int) -> float:
    """6 x the parameters a token is multiplied with here, plus
    attention's pairs forward and backward. No cell trains this family."""
    width = c["num_attention_heads"] * c["head_dim"]
    full = c["num_hidden_layers"] - window_layers(c)
    pairs = (full * attended_pairs(seq) + window_layers(c) * attended_pairs(
        seq, int(c["sliding_window"]))) / seq
    return 6.0 * matmul_params(c) + 12.0 * width * pairs


def experts_touched(c: dict, active_rows: float) -> float:
    """The expected number of distinct experts HERE that a layer's ``n``
    rows choose, each row taking 4 of 256 uniformly: an expert is chosen
    by a row with probability 4 / 256."""
    k, total = c["num_experts_per_tok"], share_of(c)["routed_experts"]
    return experts_held(c) * (1.0 - (1.0 - k / total) ** active_rows)


def _layer_kv_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """Bytes of keys and values one cached position holds in ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Bytes of keys and values one position holds while every layer
    still caches it (a sliding layer drops it 4096 positions later)."""
    return c["num_hidden_layers"] * _layer_kv_bytes(c, bytes_per_value)


def _attention_scales(c: dict) -> int:
    """float32 per-output-channel scales of a layer's int8 attention
    matrices (Wq, Wk, Wv, Wg by head and column, Wo by column)."""
    return 4 * ((2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"])
                * c["head_dim"] + c["hidden_size"])


def _mlp_scales(c: dict, width: int, count: float) -> float:
    return 4.0 * count * (2 * width + c["hidden_size"])


def routed_decode_step_bytes(c: dict, active_rows: float,
                             live_context_tokens: float,
                             weight_bytes: int = 1) -> float:
    """Bytes one decode step of ``active_rows`` sequences needs from HBM
    on this chip: the dense layers, attention's matrices, the shared
    expert and the head once, the float32 routers and biases, the norms,
    the held experts the rows chose (``experts_touched``: the EXPECTED
    number, 4 of 32 for 8 rows, not all 32) with their scales, and the
    live keys and values: all of a sequence's in a full layer, the newest
    ``sliding_window`` of them in a sliding layer (the mean length
    clipped by the window, as ``families/smallthinker.py`` says: where
    lengths are mixed the share reads high by that, never low)."""
    d = c["hidden_size"]
    touched = experts_touched(c, active_rows)
    matrices = (dense_layers(c) * (_attention_params(c) + _dense_params(c))
                + expert_layers(c) * (_attention_params(c)
                                      + _shared_params(c)
                                      + touched * _expert_params(c))
                + d * c["vocab_size"])
    scales = 0.0
    if weight_bytes == 1:
        scales = (c["num_hidden_layers"] * _attention_scales(c)
                  + dense_layers(c) * _mlp_scales(
                      c, c["intermediate_size"], 1)
                  + expert_layers(c) * _mlp_scales(
                      c, c["moe_intermediate_size"],
                      touched + c["num_shared_experts"])
                  + 4 * c["vocab_size"])
    router = 4 * expert_layers(c) * (_router_params(c)
                                     + share_of(c)["routed_experts"])
    norms = 2 * (c["num_hidden_layers"] * _small_params(c) + d)
    full = c["num_hidden_layers"] - window_layers(c)
    mean = live_context_tokens / active_rows if active_rows else 0.0
    seen = active_rows * min(mean, float(c["sliding_window"]))
    keys = _layer_kv_bytes(c) * (full * live_context_tokens
                                 + window_layers(c) * seen)
    return matrices * weight_bytes + scales + router + norms + keys


def decode_step_bytes(c: dict, live_context_tokens: float,
                      weight_bytes: int = 1) -> float:
    """What ``decode_burst_roofline`` divides by: every matrix the chip
    holds once, all 32 held experts, and every live key in every layer.
    A step of a few rows reads far fewer experts and a sliding layer far
    fewer keys, so that reader is not declared for this family's cell;
    ``expert_decode_roofline`` reads ``routed_decode_step_bytes``."""
    small = (c["num_hidden_layers"] * _small_params(c) + c["hidden_size"]
             + expert_layers(c) * share_of(c)["routed_experts"])
    matrices = (held_params(c) - c["hidden_size"] * c["vocab_size"] - small
                - expert_layers(c) * _router_params(c))
    return (matrices * weight_bytes
            + 4 * expert_layers(c) * (_router_params(c)
                                      + share_of(c)["routed_experts"])
            + 2 * (c["num_hidden_layers"] * _small_params(c)
                   + c["hidden_size"])
            + live_context_tokens * kv_bytes_per_token(c))
