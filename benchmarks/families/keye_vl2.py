"""The family of Keye-VL-2.0's language model (Kwai-Keye; ``model_type``
``KeyeVL2``): a Qwen3-MoE block (GQA with a QK-norm a head, 128 experts
of which 8 a token, renormalised, no shared expert, no dense layer) with
a DeepSeek-V3.2-style indexer beside every attention layer. The layer,
from the catalog row's ``config`` (``h = rmsnorm(x)``, eps 1e-6; s <= t
are positions):

    q_t = W_q h_t (32 x 128), k_t = W_k h_t (4 x 128), v_t = W_v h_t;
          rmsnorm of q and k over each head's 128, one learned
          128-vector each; rotary (halves, theta 1e7) on q and k
    qI_t = W_Iq h_t (16 x 64); kI_t = layernorm(W_Ik h_t) (ONE 64-vector
          a token, shared by the 16 heads); w_t = W_Iw h_t (16);
          rotary on qI and kI over all 64
    I(t, s) = sum_j w_t[j] relu(qI_t[j] . kI_s)           float32
    S_t = the 2,048 positions s <= t with the largest I(t, s), ties to
          the earlier position; all of them where t < 2,048; one set a
          query token, shared by its 32 heads
    x   = x + W_o concat_h softmax_{s in S_t}(q_t[h] . k_s[g(h)]
          / sqrt(128)) v_s[g(h)]
    g   = rmsnorm(x); p = softmax(g W_r) over 128, float32; the 8
          largest, divided by their sum;
    x   = x + sum_e p_e W_down,e (silu(W_gate,e g) * W_up,e g)
    logits = rmsnorm(x) W_head                              untied

What the row has no key for is under ``assumed`` in the configuration
file: the QK-norm a head, the LayerNorm on kI and rotary over the whole
of qI and kI, selection a token (``q_chunk_size`` and ``kv_chunk_size``
read as the tiles in which scores are computed), no scale on w (a
positive constant cannot change a top-k), bf16 where the published
indexer uses fp8. The vision tower has no key in the row: the cell
serves token ids, where the three components of a position are equal and
``mrope_section`` reduces to plain rotary.

The share, as in ``families/deepseek_v2.py``: the configuration gives
this chip's part of a layer that ``share.chips`` chips hold together:
``num_experts`` of the ``share.routed_experts`` experts, from
``share.first_expert`` on, and a slice of the vocabulary. The router
keeps all its outputs, chooses among all experts and renormalises over
the 8 chosen BEFORE the cut; what the experts that are not here would
add is left out, here as in the program.

The program serves it through ``LLMServer`` with ``LlamaConfig(
indexer_heads=..., indexer_dim=..., sparse_top_k=...)``: a third page
pool for the indexer's keys and ``ops/sparse_attention.py``. This file
is what the harness knows of it. Importing it imports no jax.
"""

from __future__ import annotations

import functools
import importlib.util
import os

from benchmarks.families.llama_dense import (  # noqa: F401
    server_class, training)


def _refuse_a_program_without_an_indexer() -> None:
    """A tree older than the indexer would fail in the replica's
    constructor (``LlamaConfig`` has no such field), for which
    ``serve_cell`` waits 25 minutes. Look at the source (no import of the
    program, no jax) for the ONE name ``program_config`` cannot do
    without and stop the run before the runtime starts."""
    spec = importlib.util.find_spec("ray_tpu")
    for root in (spec.submodule_search_locations or []) if spec else []:
        full = os.path.join(root, "models", "llama.py")
        if os.path.isfile(full):
            with open(full) as f:
                if "sparse_top_k" in f.read():
                    return
    raise ValueError(
        "the family keye_vl2 needs a program with an indexer beside its "
        "attention layers, and this tree's ray_tpu/models/llama.py has no "
        "LlamaConfig.sparse_top_k: it cannot serve Keye-VL-2.0")


_refuse_a_program_without_an_indexer()

# every key of the catalog row's ``config``, and ``share`` (see above)
CONFIG_KEYS = frozenset((
    "attention_bias", "decoder_sparse_step", "head_dim", "hidden_act",
    "hidden_size", "intermediate_size", "max_position_embeddings",
    "max_window_layers", "mlp_only_layers", "model_type",
    "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "num_local_experts", "rms_norm_eps",
    "rope_scaling", "rope_theta", "sa_config", "sliding_window",
    "tie_word_embeddings", "use_sliding_window", "vocab_size", "share"))

# Factors on the seeded weights' 1/sqrt(fan_in) scale (``LLMServer``'s
# ``seed_gains``; ``served_params`` gives the reference the same), after
# ``families/deepseek_v2.py`` and ``smallthinker.py``, which say at
# length why a seeded network at plain fan-in scale cannot be held to
# its reference tightly. The embedding at unit variance (x 45:
# sqrt(2048) = 45.3), so that a layer adds a fraction of the stream as a
# trained one does. The queries' norm weight x 2 (``q_norm``: with a
# QK-norm the projections' own scale is normalised away, so the
# sharpness of the softmax is the norm's gain): scores of deviation
# about 2, so that WHICH keys a query attends over moves what attention
# returns. At deviation 1 the softmax over thousands of seeded keys is
# near enough uniform that what a query reads is the mean of the values
# it sees, the same for every query: with attention's output then
# raised to matter (``wo`` x 2 to 8 were tried) every position decoded
# the same token and every control read 0.000. Attention's output at a
# fifth and the experts' down projection at a quarter: a query whose
# sharp softmax rests on a key at the 2,048th place loses or gains that
# key when the indexer's bfloat16 scores round the other way than the
# reference's float32 ones, so the change's OWN margins grow with what a
# layer adds to the stream (``wo`` 0.5, ``w_down`` 1: worst 0.22 to
# 0.67, mean 0.016 to 0.095 over 64 to 32,000 tokens, every control
# saturated at 0.8 to 1.9; ``wo`` 0.2, ``w_down`` 1: worst 0.10 to 0.32,
# mean 0.005 to 0.017, int4 0.57 to 0.91; ``wo`` 0.2, ``w_down`` 0.25:
# worst 0.000 to 0.137, mean 0.0003 to 0.0047, int4 0.155 to 0.22), and
# fall faster than the controls' do. Every reading: PERF.md section 6,
# PR 43.
SEED_GAINS = {"embed": 45.0, "wo": 0.2, "w_down": 0.25, "q_norm": 2.0}

# The reference check's limits, in deviations of a position's reference
# logits (``harness/families.chosen_token_margins``): how far below the
# reference's first choice a token the engine chose may lie. TWO checks,
# each with limits of its own, set from its own readings on the chip
# under ``SEED_GAINS`` (my chip runs, PR 43; PERF.md section 6 has every
# number).
#
# ``MARGIN_LIMIT`` is what the harness's ``correct`` judges: a cell's two
# probes of 64 + 16 tokens, which never select. Sound, 13 seeds (nine
# runs of the cell and four checks): worst margin 0.000 to 0.047. The
# layers' int8 weights rounded to int4, the nearest precision below the
# one stated, at the same length: 0.155, 0.168, 0.157 (three seeds).
# 0.09 stands between with about twice the room on either side.
#
# ``LONG_MARGIN_LIMIT`` (worst) and ``MEAN_MARGIN_LIMIT`` (the mean over
# an answer) are ``check_long_context_sparse.py``'s: 64 greedy tokens
# after prompts of 64 to 32,000 tokens, alone and seven together. Sound:
# worst 0.000 to 0.137 (nine of eleven answers under 0.025; 0.122 after
# 32,000 tokens and 0.137 after 2,100 in the batch), mean 0.0003 to
# 0.0047. The same tokens against a reference that is wrong on purpose,
# worst and mean at 12,000 and 32,000 tokens: every visible key attended
# 0.187 / 0.018 and 0.310 / 0.026; the 1,024 best keys 0.431 / 0.039 and
# 0.208 / 0.022; the score without its ReLU 0.277 / 0.014 and 0.356 /
# 0.029; the router's 8 not renormalised 0.261 / 0.023 and 0.442 /
# 0.039; int4 0.220 / 0.005 and 0.187 / 0.011: each over one of the two
# limits at both lengths. The room there is NARROW on both sides (0.137
# against 0.187 for the worst; 0.0047 against 0.011 for the mean): the
# worst margin of a sound answer past 2,048 keys is made by the rare
# query whose sharpest key sits at the 2,048th place of the indexer's
# ranking, which a control that changes every query's keys a little does
# not exceed by much. REQUIRED by the issue and NOT caught, read on the
# same tokens: one QK-norm over all heads for one a head (0.088 / 0.003
# and 0.111 / 0.006, under both limits: seeded heads have nearly the same
# norm, so the whole-width norm is the head's times a constant near one;
# tests/test_llm_sparse.py holds it at 2e-4 in float32). Read and not
# required: the indexer's products in bfloat16 (0.026 and 0.082: the
# change's own precision).
MARGIN_LIMIT = 0.09
LONG_MARGIN_LIMIT = 0.15
MEAN_MARGIN_LIMIT = 0.01


def _require(config: dict) -> None:
    """The published settings this family's block is written for."""
    wanted = {"attention_bias": False, "decoder_sparse_step": 1,
              "hidden_act": "silu", "mlp_only_layers": [],
              "norm_topk_prob": True, "sliding_window": None,
              "tie_word_embeddings": False, "use_sliding_window": False}
    wrong = {k: config.get(k) for k, v in wanted.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"the family keye_vl2 is written for {wanted}; "
                         f"this configuration has {wrong}")
    if config["sa_config"]["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has ONE key a token: "
                         "sa_config.indexer_num_kv_heads = 1")
    if config["num_experts"] != config["num_local_experts"]:
        raise ValueError("num_experts and num_local_experts both say how "
                         "many experts are HERE: they are equal")
    if (config["rope_scaling"] or {}).get("rope_type", "default") != "default":
        raise ValueError("the family keye_vl2 is written for plain rotary "
                         "frequencies (token ids: the three components of "
                         "a position are equal)")


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


# 1. the program's configuration
def program_config(config: dict):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    _require(config)
    rehearsal = bool(config.get("rehearsal"))
    share, sa = share_of(config), config["sa_config"]
    return LlamaConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_size=int(config["head_dim"]),
        mlp_dim=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.float32 if rehearsal else jnp.bfloat16,
        remat=not rehearsal,
        n_experts=int(share["routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        qk_norm=True, qk_norm_by_head=True,
        indexer_heads=int(sa["indexer_num_heads"]),
        indexer_dim=int(sa["indexer_head_dim"]),
        sparse_top_k=int(sa["topk"]),
        experts_held=(int(share["first_expert"]),
                      int(config["num_experts"])))


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
# Attention is one masked softmax over all the keys, the mask from
# ``lax.top_k`` of the indexer's scores, a block of queries at a time
# (its scores against every key, its mask, then a group of heads at a
# time) so that 32k tokens fit beside the replica's weights: no running
# maximum, no kernel, no cache, no threshold.
QUERY_BLOCK = 128


@functools.cache
def _layer():
    """Made on first use, in the chip's holder: importing a family
    imports no jax."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import _f32 as stored
    from benchmarks.harness.reference import _rms_norm, _rotate

    @functools.partial(jax.jit, static_argnames=(
        "n_kv_heads", "theta", "eps", "top_k", "first", "keys", "dense",
        "relu", "whole_norm", "renormalise", "int4", "index_dtype"))
    def layer(x, lp, *, n_kv_heads, theta, eps, top_k, first, keys, dense,
              relu, whole_norm, renormalise, int4, index_dtype):
        def _f32(w, contract=()):
            if int4 and isinstance(w, dict):
                # the control: the stored int8 values rounded to 4 bits
                w = {"q": jnp.round(w["q"].astype(jnp.float32) / 16) * 16,
                     "s": w["s"]}
            return stored(w, contract)

        b, seq, _ = x.shape
        h = _rms_norm(x, _f32(lp["attn_norm"]), eps)
        q = jnp.einsum("bsd,dhk->bshk", h, _f32(lp["wq"], (0,)))
        k = jnp.einsum("bsd,dhk->bshk", h, _f32(lp["wk"], (0,)))
        v = jnp.einsum("bsd,dhk->bshk", h, _f32(lp["wv"], (0,)))
        if whole_norm:
            # the control: one norm over all heads together (OLMoE's)
            def normed(a, weight):
                flat = a.reshape(b, seq, -1)
                return _rms_norm(flat, jnp.tile(weight, a.shape[2]),
                                 eps).reshape(a.shape)
        else:
            def normed(a, weight):
                return _rms_norm(a, weight, eps)
        q = _rotate(normed(q, _f32(lp["q_norm"])), theta)
        k = _rotate(normed(k, _f32(lp["k_norm"])), theta)
        # the indexer
        qi = jnp.einsum("bsd,djk->bsjk", h, _f32(lp["wi_q"], (0,)))
        ki = jnp.einsum("bsd,dk->bsk", h, _f32(lp["wi_k"], (0,)))
        w = jnp.einsum("bsd,dj->bsj", h, _f32(lp["wi_w"], (0,)))
        ki = ki - ki.mean(-1, keepdims=True)
        ki = (ki * jax.lax.rsqrt((ki * ki).mean(-1, keepdims=True) + eps)
              * _f32(lp["wi_k_norm"]) + _f32(lp["wi_k_bias"]))
        qi = _rotate(qi, theta)
        ki = _rotate(ki[:, :, None, :], theta)[:, :, 0]
        # the control: the indexer's products in a lower precision
        qi, ki = qi.astype(index_dtype), ki.astype(index_dtype)

        n_heads = q.shape[2]
        group = n_heads // n_kv_heads
        key_at = jnp.arange(seq)
        pad = (-seq) % QUERY_BLOCK

        def blocks(a):
            return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
                           ).reshape(b, -1, QUERY_BLOCK, *a.shape[2:]
                                     ).swapaxes(0, 1)

        def one_block(first_q, qb, qib, wb):
            at_q = first_q + jnp.arange(QUERY_BLOCK)
            seen = key_at[None, :] <= at_q[:, None]              # [Q, S]
            scores = jnp.einsum("bqjd,bsd->bqjs", qib, ki,
                                preferred_element_type=jnp.float32)
            if relu:
                scores = jax.nn.relu(scores)
            scores = (scores * wb[..., None]).sum(2)             # [b, Q, S]
            scores = jnp.where(seen[None], scores, -jnp.inf)
            if dense or seq <= keys:
                chosen = jnp.broadcast_to(seen[None], scores.shape)
            else:
                _, idx = jax.lax.top_k(scores, keys)
                chosen = jnp.zeros(scores.shape, bool).at[
                    jnp.arange(b)[:, None, None],
                    jnp.arange(QUERY_BLOCK)[None, :, None], idx].set(True)
                chosen = chosen & seen[None]

            def kv_head(g):
                qg = jax.lax.dynamic_slice_in_dim(qb, g * group, group, 2)
                kg = jax.lax.dynamic_index_in_dim(k, g, 2, False)
                vg = jax.lax.dynamic_index_in_dim(v, g, 2, False)
                s = jnp.einsum("bqhk,bsk->bhqs", qg, kg) \
                    * qg.shape[-1] ** -0.5
                s = jnp.where(chosen[:, None], s, -jnp.inf)
                return jnp.einsum("bhqs,bsk->bqhk",
                                  jax.nn.softmax(s, -1), vg)

            out = jax.lax.map(kv_head, jnp.arange(n_kv_heads))
            # [kvh, b, Q, group, hd] -> [b, Q, heads, hd]
            return jnp.moveaxis(out, 0, 2).reshape(
                b, QUERY_BLOCK, n_heads, -1)

        n_blocks = (seq + pad) // QUERY_BLOCK
        attended = jax.lax.map(
            lambda a: one_block(*a),
            (jnp.arange(n_blocks) * QUERY_BLOCK, blocks(q), blocks(qi),
             blocks(w)))
        attended = attended.swapaxes(0, 1).reshape(
            b, -1, n_heads, q.shape[-1])[:, :seq]
        x = x + jnp.einsum("bshk,hkd->bsd", attended,
                           _f32(lp["wo"], (0, 1)))

        g = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
        probs = jax.nn.softmax(
            jnp.einsum("bsd,de->bse", g, _f32(lp["router"])), -1)
        experts = probs.shape[-1]
        chosen_p, chosen_e = jax.lax.top_k(probs, top_k)
        if renormalise:
            chosen_p = chosen_p / chosen_p.sum(-1, keepdims=True)
        weight = jnp.einsum("bsk,bske->bse", chosen_p,
                            jax.nn.one_hot(chosen_e, experts,
                                           dtype=probs.dtype))
        held = (lp["w_gate"]["q"] if isinstance(lp["w_gate"], dict)
                else lp["w_gate"]).shape[0]

        def one_expert(out, e):
            # every expert that is HERE, plainly, on every token; an
            # expert that is elsewhere adds nothing
            gate, up, down = (
                _f32(jax.tree.map(lambda a: a[e], lp[name]), (0,))
                for name in ("w_gate", "w_up", "w_down"))
            y = jnp.einsum(
                "bsm,md->bsd",
                jax.nn.silu(jnp.einsum("bsd,dm->bsm", g, gate))
                * jnp.einsum("bsd,dm->bsm", g, up), down)
            return out + jnp.take(weight, first + e, axis=-1)[..., None] \
                * y, None

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                              jnp.arange(held))
        return x + out

    return layer


def _forward(params, tokens, config: dict, *, last=None, dense=False,
             topk=None, relu=True, whole_norm=False, renormalise=True,
             int4=False, index_dtype=None):
    """The forward pass. ``last``: logits of the last ``last`` positions
    only (32k positions' logits over the vocabulary are 4.9 GB). The
    other keywords are for the controls that show a limit bites (attend
    over every key, the 1,024 best keys for the 2,048, the score without
    its ReLU, one QK-norm over all heads, the router's 8 not
    renormalised, the layers' int8 weights rounded to 4 bits, the
    indexer's products in bfloat16); the harness calls it without them."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import _embed, _head

    _require(config)
    eps = float(config["rms_norm_eps"])
    common = dict(
        n_kv_heads=int(config["num_key_value_heads"]),
        theta=float(config["rope_theta"]), eps=eps,
        top_k=int(config["num_experts_per_tok"]),
        first=int(share_of(config)["first_expert"]),
        keys=int(topk or config["sa_config"]["topk"]), dense=dense,
        relu=relu, whole_norm=whole_norm, renormalise=renormalise,
        int4=int4, index_dtype=jnp.dtype(index_dtype or "float32"))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], tokens)
        for i in range(int(config["num_hidden_layers"])):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x = _layer()(x, lp, **common)
        if last is not None:
            x = x[:, -last:]
        return _head(x, params["final_norm"], params["lm_head"], eps=eps)


def forward_logits(params, tokens, config: dict, **control):
    """tokens [batch, seq] int32 -> float32 logits [batch, seq, vocab]
    (``last=n``: of the last n positions)."""
    return _forward(params, tokens, config, **control)


def next_token_loss(params, tokens, config: dict, z_loss: float = 0.0):
    """Next-token cross-entropy with the logits' z-loss. No cell trains
    this family (the program's training forward refuses the indexer), so
    no load-balancing term is assumed."""
    import jax
    import jax.numpy as jnp

    logits = _forward(params, tokens, config)[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(logz - target + z_loss * logz * logz)


# 4. the counts, of the share that is HERE: what the chip holds and what
# a token is multiplied with on it
def _attention_params(c: dict) -> int:
    """W_q, W_k, W_v, W_o."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def _indexer_params(c: dict) -> int:
    """W_Iq, W_Ik, W_Iw."""
    sa = c["sa_config"]
    return c["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def _expert_params(c: dict) -> int:
    """One expert: gate, up and down of ``moe_intermediate_size``."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _router_params(c: dict) -> int:
    return c["hidden_size"] * share_of(c)["routed_experts"]


def experts_held(c: dict) -> int:
    return int(c["num_experts"])


def held_experts_per_token(c: dict) -> float:
    """The experts a token is multiplied with HERE, on average under
    uniform routing: 8 x 32 / 128 = 2."""
    return (c["num_experts_per_tok"] * experts_held(c)
            / share_of(c)["routed_experts"])


def held_params(c: dict) -> int:
    """Every parameter this chip holds, with its slice of the embedding
    table and the head (norms left out: 0.1 M)."""
    layer = (_attention_params(c) + _indexer_params(c) + _router_params(c)
             + experts_held(c) * _expert_params(c))
    return (c["num_hidden_layers"] * layer
            + 2 * c["hidden_size"] * c["vocab_size"])


def matmul_params(c: dict) -> float:
    """Parameters a token is multiplied with on this chip."""
    layer = (_attention_params(c) + _indexer_params(c) + _router_params(c)
             + held_experts_per_token(c) * _expert_params(c))
    return (c["num_hidden_layers"] * layer
            + c["hidden_size"] * c["vocab_size"])


def _pairs(n: float, most: float):
    """(visible, attended) (query, key) pairs of ``n`` causal queries:
    query t sees t + 1 keys and attends over at most ``most``."""
    visible = n * (n + 1) / 2.0
    full = min(n, most)
    return visible, full * (full + 1) / 2.0 + (n - full) * most


def sparse_attention_flops(c: dict, prompt_tokens: float) -> float:
    """Operations the sparse attention of one prompt's prefill needs by
    the equations, whatever implements them, in every layer: a score of
    every visible (query, key) pair (16 heads x 64 x 2, and the weighted
    sum), and a head's score and value (2 x 128 x 2 x 32 heads) of every
    CHOSEN pair. A prefill that computes every visible pair and masks
    does more than this and reads LOW against it; the choice itself (no
    product) counts nothing."""
    sa = c["sa_config"]
    visible, attended = _pairs(float(prompt_tokens), sa["topk"])
    scores = 2.0 * sa["indexer_num_heads"] * (sa["indexer_head_dim"] + 1)
    heads = 4.0 * c["num_attention_heads"] * c["head_dim"]
    return c["num_hidden_layers"] * (visible * scores + attended * heads)


def prefill_flops(c: dict, prompt_tokens: float) -> float:
    """Operations the prefill of one prompt needs on this chip: every
    prompt token through every layer's projections (the indexer's among
    them), the router and the 2 experts that are here (2 x the
    parameters), the sparse attention, and the head for the one position
    that is sampled."""
    n = float(prompt_tokens)
    per_token = matmul_params(c) - c["hidden_size"] * c["vocab_size"]
    return (2.0 * n * per_token + sparse_attention_flops(c, n)
            + 2.0 * c["hidden_size"] * c["vocab_size"])


def train_flops_per_token(c: dict, seq: int) -> float:
    """6 x the parameters a token is multiplied with here, plus the
    sparse attention forward and backward. No cell trains this family."""
    return (6.0 * matmul_params(c)
            + 3.0 * sparse_attention_flops(c, seq) / seq)


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Bytes one cached position holds, all layers: a key and a value a
    KV head and the indexer's one key (the slot's padding to whole lanes
    holds nothing an algorithm needs)."""
    return c["num_hidden_layers"] * bytes_per_value * (
        2 * c["num_key_value_heads"] * c["head_dim"]
        + c["sa_config"]["indexer_head_dim"])


def indexer_decode_bytes(c: dict, live_context_tokens: float,
                         bytes_per_value: int = 2) -> float:
    """Bytes ONE decode step's scoring needs from the cache, all layers:
    the indexer's key of every live position once. What
    ``sparse_decode_roofline`` holds the scoring and choosing kernels
    to."""
    return (c["num_hidden_layers"] * bytes_per_value * live_context_tokens
            * c["sa_config"]["indexer_head_dim"])


def sparse_decode_bytes(c: dict, active_rows: float,
                        live_context_tokens: float,
                        bytes_per_value: int = 2) -> float:
    """Bytes ONE decode step's sparse attention needs from the cache, all
    layers: the indexer's key of every live position once
    (``indexer_decode_bytes``), and the K and V rows of the positions
    chosen (at most ``topk`` a sequence: of a sequence's share of the
    live positions, as if they were of equal length, which over-counts
    none)."""
    sa = c["sa_config"]
    rows = max(float(active_rows), 1.0)
    chosen = rows * min(live_context_tokens / rows, sa["topk"])
    return (indexer_decode_bytes(c, live_context_tokens, bytes_per_value)
            + c["num_hidden_layers"] * bytes_per_value * chosen * 2
            * c["num_key_value_heads"] * c["head_dim"])


def experts_touched(c: dict, active_rows: float) -> float:
    """The expected number of distinct experts HERE that a layer's ``n``
    rows choose, each row taking 8 of 128 uniformly."""
    k, total = c["num_experts_per_tok"], share_of(c)["routed_experts"]
    return experts_held(c) * (1.0 - (1.0 - k / total) ** active_rows)


def _scales(c: dict, experts: float) -> float:
    """Bytes of float32 per-output-channel scales of a layer's int8
    matrices (attention, the indexer, ``experts`` experts)."""
    d, hd, m = c["hidden_size"], c["head_dim"], c["moe_intermediate_size"]
    sa = c["sa_config"]
    attention = (c["num_attention_heads"]
                 + 2 * c["num_key_value_heads"]) * hd + d
    indexer = (sa["indexer_num_heads"] * (sa["indexer_head_dim"] + 1)
               + sa["indexer_head_dim"])
    return 4.0 * (attention + indexer + experts * (2 * m + d))


def routed_decode_step_bytes(c: dict, active_rows: float,
                             live_context_tokens: float,
                             weight_bytes: int = 1) -> float:
    """Bytes one decode step of ``active_rows`` sequences needs from HBM
    on this chip: attention's and the indexer's matrices and the head
    once, the float32 router, the norms, the held experts the rows chose
    (``experts_touched``, not all 32) with their scales, the indexer's
    rows of the live positions and the K and V rows CHOSEN, not the
    span (``sparse_decode_bytes``)."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    touched = experts_touched(c, active_rows)
    matrices = layers * (_attention_params(c) + _indexer_params(c)
                         + touched * _expert_params(c)) + d * c["vocab_size"]
    scales = 0.0
    if weight_bytes == 1:
        scales = layers * _scales(c, touched) + 4 * c["vocab_size"]
    router = 4 * layers * _router_params(c)
    norms = 2 * (layers * (2 * d + 2 * c["head_dim"]) + d)
    return (matrices * weight_bytes + scales + router + norms
            + sparse_decode_bytes(c, active_rows, live_context_tokens))


def decode_step_bytes(c: dict, live_context_tokens: float,
                      weight_bytes: int = 1) -> float:
    """What ``decode_burst_roofline`` divides by: every matrix the chip
    holds once, all 32 held experts, the indexer's rows of the live
    positions and the chosen K and V rows of (at most) 8 sequences, not
    the span. A step of a few rows reads far fewer experts, so that
    reader is not declared for this family's cell;
    ``expert_decode_roofline`` reads ``routed_decode_step_bytes``."""
    return ((held_params(c) - c["hidden_size"] * c["vocab_size"])
            * weight_bytes + 4 * c["num_hidden_layers"] * _router_params(c)
            + sparse_decode_bytes(c, 8.0, live_context_tokens))
