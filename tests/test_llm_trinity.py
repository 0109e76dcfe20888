"""Trinity's block on the serving path: leading dense layers INSIDE a
layer pattern, a sigmoid router whose choice sees a bias and whose
weights do not, sandwich norms, attention's output gate and a QK-norm a
head on the paged kind, a shared expert, one chip's share of the experts.

The size keeps the shape of the problem: hidden 48, 4 query heads x 16
(so ``head_dim`` is not ``dim / heads``), 2 key-value heads, 8 layers =
2 periods of (window, window, window, full NoPE), the FIRST layer dense
(a window layer of period 0), window 16, pages of 4, 16 experts of 32 of
which 8 are held (experts 8 to 15), 3 a token, float32. The yardstick is
the plain reference of ``benchmarks/families/afmoe.py`` (float32, one
masked softmax, every held expert on every token, nothing of the
program).
"""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import families                     # noqa: E402
from ray_tpu.llm.cache import init_kv_cache                 # noqa: E402
from ray_tpu.llm.engine import EngineConfig, LLMEngine      # noqa: E402
from ray_tpu.llm.runner import prefill, prefill_chunk       # noqa: E402
from ray_tpu.llm.sampling import SamplingParams             # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params   # noqa: E402
from ray_tpu.ops import moe, rope_frequencies               # noqa: E402
from ray_tpu.ops.quant import init_params_quantized         # noqa: E402

PAGE, WINDOW, BURST = 4, 16, 4

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tiny-rehearsal-trinity.json")) as _f:
    CONFIG = json.load(_f)
FAMILY = families.family_of(CONFIG)
CFG = FAMILY.program_config(CONFIG)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(11), CFG, FAMILY.SEED_GAINS)


def _reference(params, tokens, **control):
    return np.asarray(FAMILY.forward_logits(
        params, jnp.asarray([tokens], jnp.int32), CONFIG, **control))[0]


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, CFG.vocab, n)]


def _engine(params, slots=3, chunk=0, **more):
    return LLMEngine(params, CFG, EngineConfig(
        max_num_seqs=slots, page_size=PAGE, num_pages=1 + slots * 32,
        max_seq_len=128, decode_burst=BURST, prefill_chunk=chunk, **more))


# ------------------------------------------------- (d) the configuration
def test_the_configuration_is_the_shape_of_the_problem(params):
    assert CFG.layer_pattern == ("window", "window", "window", "full_nope")
    assert CFG.kv_groups == (None, WINDOW)
    assert (CFG.group_layers(0), CFG.group_layers(1)) == (2, 6)
    assert (CFG.n_dense_layers, CFG.n_moe_layers) == (1, 7)
    assert (CFG.router_score, CFG.router_bias, CFG.post_norms) == (
        "sigmoid", True, True)
    assert (CFG.n_experts, CFG.experts_held, CFG.top_k) == (16, (8, 8), 3)
    assert CFG.head_dim == 16 != CFG.dim // CFG.n_heads
    # the dense layer has an attention half like every layer's, and the
    # expert layers' stack begins behind it
    dense, layers = params["dense_layers"], params["layers"]
    assert dense["w_gate"].shape == (1, 48, 96)
    assert layers["w_gate"].shape == (7, 8, 48, 32)
    assert layers["router"].shape == (7, 48, 16)
    assert layers["expert_bias"].shape == (7, 16)
    assert layers["expert_bias"].dtype == jnp.float32
    assert float(jnp.abs(layers["expert_bias"]).min()) > 0
    for name, width in (("wg", None), ("q_norm", 16), ("k_norm", 16),
                        ("post_attn_norm", 48), ("post_mlp_norm", 48)):
        assert name in dense and name in layers, name
        if width:
            assert dense[name].shape == (1, width)
            assert layers[name].shape == (7, width)
    assert "expert_bias" not in dense and "router" not in dense


@pytest.mark.parametrize("change, message", [
    (dict(n_experts=0, experts_held=None, router_bias=False,
          n_shared_experts=0), "n_dense_layers"),
    (dict(dense_mlp_dim=0), "n_dense_layers"),
    (dict(n_dense_layers=8), "n_dense_layers"),
    (dict(router_score="tanh"), "router_score"),
    (dict(n_experts=0, experts_held=None, n_dense_layers=0,
          n_shared_experts=0), "router_bias"),
], ids=["dense-without-experts", "dense-without-a-width", "all-dense",
        "unknown-score", "bias-without-experts"])
def test_what_the_configuration_still_refuses(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_the_training_forward_refuses_the_block():
    from ray_tpu.models.llama import forward

    plain = LlamaConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, mlp_dim=24, max_seq=64,
                        dtype=jnp.float32, remat=False, n_experts=4)
    for change in (dict(router_score="sigmoid"), dict(router_bias=True),
                   dict(post_norms=True)):
        cfg = dataclasses.replace(plain, **change)
        with pytest.raises(ValueError, match="llm/runner.py only"):
            forward(init_params(jax.random.PRNGKey(0), cfg),
                    jnp.zeros((1, 8), jnp.int32), cfg)


def test_the_int8_weights_have_the_same_leaves(params):
    seeded = jax.eval_shape(lambda: init_params_quantized(
        jax.random.PRNGKey(0), CFG, FAMILY.SEED_GAINS))
    for stack in ("layers", "dense_layers"):
        assert set(seeded[stack]) == set(params[stack]), stack
    assert seeded["layers"]["expert_bias"].dtype == jnp.float32
    assert seeded["layers"]["router"].dtype == jnp.float32
    assert seeded["layers"]["post_mlp_norm"].shape == (7, 48)
    with pytest.raises(ValueError, match="not seeded"):
        init_params_quantized(jax.random.PRNGKey(0), dataclasses.replace(
            CFG, post_norms=False), FAMILY.SEED_GAINS)


# ---------------------------------- (a) the engine against the reference
def _pages_through_the_chunk_program(engine, state):
    """Logits of the next position, read through the engine's pages as
    they are (both groups, released entries 0): ``prefill_chunk`` of the
    one token the next decode step would take."""
    engine._provision_pages(state, state.ctx_len + 1)
    tables = tuple(jnp.asarray(t.block_tables[state.slot:state.slot + 1])
                   for t in engine.seq_tables)
    tokens = np.zeros((1, 4), np.int32)
    tokens[0, 0] = state.output[-1]
    logits, ck, cv, _ = prefill_chunk(
        engine.params, engine.cache.k, engine.cache.v, jnp.asarray(tokens),
        jnp.int32(state.ctx_len), jnp.int32(1), tables, engine.cos,
        engine.sin, cfg=CFG)
    engine.cache = type(engine.cache)(ck, cv)
    return np.asarray(logits)[0]


@pytest.mark.parametrize("company", ["alone", "with-a-short-and-an-idle"])
def test_prefill_then_decode_through_the_pages_is_the_full_forward(
        params, company):
    """A prompt of 37 tokens (2.3 windows) decodes 50 more, across the
    releases of its window pages: every token is the reference's first
    choice given the tokens before it, and the logits read back through
    the pages (the dense layer's among them, first in the window group's
    pool) are the reference's to 1e-4."""
    engine = _engine(params)
    long_id = engine.add_request(_prompt(37, 1), SamplingParams(
        temperature=0.0, max_tokens=50))
    short_id = None
    if company != "alone":
        short_id = engine.add_request(_prompt(9, 2), SamplingParams(
            temperature=0.0, max_tokens=20))
    state = engine.requests[long_id]
    probed = []
    while not state.finished:
        engine.step()
        if state.slot >= 0 and 33 <= len(state.output) <= 36 \
                and not probed:
            seq = state.prompt + state.output
            got = _pages_through_the_chunk_program(engine, state)
            np.testing.assert_allclose(got, _reference(params, seq)[-1],
                                       atol=1e-4)
            probed.append(len(seq))
    while engine.has_unfinished():
        engine.step()
    assert probed and len(state.output) == 50
    for rid in filter(None, (long_id, short_id)):
        s = engine.requests[rid]
        seq = s.prompt + s.output
        want = _reference(params, seq)[len(s.prompt) - 1:-1].argmax(-1)
        assert s.output == want.tolist()
    groups = engine.stats()["counters"]["groups"]
    assert groups["window"]["released_pages"] >= 8
    assert groups["full"]["released_pages"] == 0


def _tables_for(length, *, whole):
    n = -(-length // PAGE)
    full = np.zeros((1, 32), np.int32)
    full[0, :n] = 1 + np.arange(n)
    first = 0 if whole else max(length - WINDOW + 1, 0) // PAGE
    window = np.zeros((1, 32), np.int32)
    window[0, first:n] = 1 + np.arange(n - first)
    return jnp.asarray(full), jnp.asarray(window)


def _whole_prompt(params, tokens, cfg=CFG):
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :len(tokens)] = tokens
    cache = init_kv_cache(cfg, (40, 40), PAGE)
    logits, _, _, counts = prefill(
        params, cache.k, cache.v, jnp.asarray(padded),
        jnp.asarray([len(tokens)], jnp.int32),
        _tables_for(len(tokens), whole=False), cos, sin, cfg=cfg)
    return np.asarray(logits)[0], np.asarray(counts)


def test_chunks_agree_with_whole_prompt_prefill_and_the_reference(params):
    tokens = _prompt(45, 3)
    cos, sin = rope_frequencies(CFG.head_dim, CFG.max_seq, CFG.rope_theta)
    whole, counts = _whole_prompt(params, tokens)
    np.testing.assert_allclose(whole, _reference(params, tokens)[-1],
                               atol=1e-4)
    # rows given to the experts that are here + rows routed elsewhere =
    # tokens x 3 a token x 7 expert layers: the dense layer counts none
    assert int(counts[0]) + int(counts[2]) == 45 * CFG.top_k * 7
    assert int(counts[2]) > 0 < int(counts[0])
    cache = init_kv_cache(CFG, (40, 40), PAGE)
    ck, cv = cache.k, cache.v
    for start in range(0, 45, 8):
        n = min(8, 45 - start)
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :n] = tokens[start:start + n]
        logits, ck, cv, _ = prefill_chunk(
            params, ck, cv, jnp.asarray(chunk), jnp.int32(start),
            jnp.int32(n), _tables_for(45, whole=True), cos, sin, cfg=CFG)
    np.testing.assert_allclose(np.asarray(logits)[0], whole, atol=1e-4)


@pytest.mark.parametrize("control", [
    dict(all_full=True), dict(rotate_all=True), dict(bias_in_weights=True),
    dict(score="softmax"), dict(post_norms=False), dict(int4=True)],
    ids=lambda c: next(iter(c)))
def test_the_reference_controls_move_the_logits(params, control):
    """Each control of ``check_long_context_afmoe.py`` is another
    function at this size too (int4 on float32 weights is none: it rounds
    stored int8 values)."""
    tokens = _prompt(45, 4)
    sound = _reference(params, tokens)[-1]
    if "int4" in control:
        seeded = init_params_quantized(jax.random.PRNGKey(3), CFG,
                                       FAMILY.SEED_GAINS)
        want = np.asarray(FAMILY.forward_logits(
            seeded, jnp.asarray([tokens], jnp.int32), CONFIG))[0, -1]
        got = np.asarray(FAMILY.forward_logits(
            seeded, jnp.asarray([tokens], jnp.int32), CONFIG, **control))
        assert np.abs(got[0, -1] - want).max() > 1e-2
        return
    wrong = _reference(params, tokens, **control)[-1]
    assert np.abs(wrong - sound).max() > 1e-3
    ours, _ = _whole_prompt(params, tokens)
    np.testing.assert_allclose(ours, sound, atol=1e-4)


@pytest.mark.parametrize("field, other", [
    ("router_score", "softmax"), ("router_bias", False),
    ("attn_output_gate", False), ("embed_scale", 1.0),
    ("layer_pattern", ("window", "window", "window", "full"))],
    ids=["softmax-router", "no-bias", "no-gate", "no-mup-scale",
         "rotary-on-the-full-layer"])
def test_program_settings_that_must_differ(params, field, other):
    tokens = _prompt(45, 5)
    ours, _ = _whole_prompt(params, tokens)
    wrong, _ = _whole_prompt(params, tokens,
                             dataclasses.replace(CFG, **{field: other}))
    assert np.abs(wrong - ours).max() > 1e-3


def test_the_post_norms_are_the_weights_not_a_flag(params):
    """Absent weights mean today's layer: the same block without the two
    vectors adds what each half gave."""
    tokens = _prompt(45, 6)
    ours, _ = _whole_prompt(params, tokens)
    bare = {**params, **{stack: {
        k: v for k, v in params[stack].items() if not k.startswith("post_")}
        for stack in ("layers", "dense_layers")}}
    wrong, _ = _whole_prompt(bare, tokens)
    np.testing.assert_allclose(
        wrong, _reference(params, tokens, post_norms=False)[-1], atol=2e-3,
        rtol=1e-4)
    assert np.abs(wrong - ours).max() > 1e-2


# --------------------------------------------------- (e) expert counters
def test_expert_counters_count_unrolled_and_scanned_periods_alike(params):
    engine = _engine(params)
    prompt = _prompt(21, 7)
    out = engine.generate([prompt], SamplingParams(temperature=0.0,
                                                   max_tokens=9))[0]
    assert len(out) == 9
    counters = engine.stats()["counters"]
    # 21 prompt tokens and 8 decode steps through 7 expert layers (3 in
    # the unrolled first period, 4 in the scanned second), 3 experts each
    assert (counters["expert_rows"] + counters["expert_rows_elsewhere"]
            == (21 + 8) * CFG.top_k * 7)
    assert counters["expert_rows_elsewhere"] > 0 < counters["expert_rows"]


# ---------------------------------------------- (b) the eight shares add up
E, K, D, M = 16, 3, 48, 32


def _layer_weights(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    x = jax.random.normal(ks[0], (2, 11, D), jnp.float32)
    router = jax.random.normal(ks[1], (D, E), jnp.float32) * D ** -0.5
    bias = 0.1 * jax.random.normal(ks[2], (E,), jnp.float32)
    experts = [jax.random.normal(ks[3], (E, D, M)) * D ** -0.5,
               jax.random.normal(ks[4], (E, D, M)) * D ** -0.5,
               jax.random.normal(ks[5], (E, M, D)) * M ** -0.5]
    shared = (jax.random.normal(ks[6], (D, M)) * D ** -0.5,
              jax.random.normal(ks[7], (D, M)) * D ** -0.5,
              jax.random.normal(ks[8], (M, D)) * M ** -0.5)
    return x, router, bias, experts, shared


def _plain_layer(x, router, bias, experts, shared=None, held=(0, E),
                 scale=2.448):
    """The equations, plainly: s = sigmoid(x Wr); chosen = top k of s + b;
    w = s[chosen] / sum s[chosen] x scale; every held expert on every
    token."""
    x = np.asarray(x, np.float64).reshape(-1, D)
    s = 1.0 / (1.0 + np.exp(-(x @ np.asarray(router, np.float64))))
    chosen = np.argsort(-(s + np.asarray(bias, np.float64)), -1,
                        kind="stable")[:, :K]
    picked = np.take_along_axis(s, chosen, -1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale

    def swiglu(gate, up, down):
        g = x @ np.asarray(gate, np.float64)
        return (g / (1.0 + np.exp(-g)) * (x @ np.asarray(up, np.float64))
                ) @ np.asarray(down, np.float64)

    out = np.zeros_like(x)
    for e in range(held[0], held[0] + held[1]):
        weight = (w * (chosen == e)).sum(-1)[:, None]
        out += weight * swiglu(*(np.asarray(m)[e] for m in experts))
    if shared is not None:
        out += swiglu(*shared)
    return out.reshape(2, 11, D)


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of the sixteen; every chip routes
    over all sixteen with the whole bias and computes its own experts'
    part; the shared expert is on every chip. The routed parts of the
    eight shares, and the shared expert counted ONCE, are the uncut
    layer; every (token, expert) row is given to exactly one chip."""
    x, router, bias, experts, shared = _layer_weights(5)
    layer = functools.partial(
        moe.moe_mlp_routed, top_k=K, norm_topk_prob=True, scale=2.448,
        score="sigmoid", bias=bias)
    whole, whole_counts = jax.jit(functools.partial(layer, shared=shared))(
        x, router, *experts)
    np.testing.assert_allclose(
        whole, _plain_layer(x, router, bias, experts, shared), atol=5e-4)
    per, parts, given = E // 8, [], 0
    for chip in range(8):
        held = (chip * per, per)
        part, counts = jax.jit(functools.partial(layer, held=held))(
            x, router, *(w[held[0]:held[0] + per] for w in experts))
        np.testing.assert_allclose(
            part, _plain_layer(x, router, bias, experts, held=held),
            atol=5e-4)
        parts.append(np.asarray(part, np.float64))
        given += int(counts[0])
        assert int(counts[0]) + int(counts[2]) == 2 * 11 * K
    only_shared = _plain_layer(x, router, bias, experts, shared, held=(0, 0))
    np.testing.assert_allclose(sum(parts) + only_shared, whole, atol=1e-3)
    assert given == 2 * 11 * K == int(whole_counts[0])


# ------------------------------------ (c) the bias and the unbiased weights
def test_a_bias_moves_the_choice_and_never_the_weights():
    logits = jax.random.normal(jax.random.PRNGKey(2), (64, E), jnp.float32)
    scores = jax.nn.sigmoid(logits)
    bias = jnp.zeros(E, jnp.float32).at[3].set(0.6).at[9].set(-0.6)
    weight, chosen = moe.route(scores, K, norm_topk_prob=True, scale=2.448,
                               bias=bias)
    plain_w, plain_c = moe.route(scores, K, norm_topk_prob=True, scale=2.448)
    chosen, plain_c = np.asarray(chosen), np.asarray(plain_c)
    # the bias moved choices: expert 3 is taken more often, 9 less
    assert (chosen == 3).sum() > (plain_c == 3).sum()
    assert (chosen == 9).sum() < (plain_c == 9).sum()
    assert (np.sort(chosen, -1) != np.sort(plain_c, -1)).any()
    # chosen by score + bias, in that order
    want = np.argsort(-np.asarray(scores + bias), -1, kind="stable")[:, :K]
    assert (chosen == want).all()
    # and weighed by the UNBIASED scores of what was chosen
    picked = np.take_along_axis(np.asarray(scores), chosen, -1)
    np.testing.assert_allclose(
        weight, picked / picked.sum(-1, keepdims=True) * 2.448, rtol=1e-6)
    biased = np.take_along_axis(np.asarray(scores + bias), chosen, -1)
    assert np.abs(np.asarray(weight) - biased / biased.sum(
        -1, keepdims=True) * 2.448).max() > 1e-2


def test_a_zero_bias_with_softmax_is_todays_route_bit_for_bit():
    probs = jax.nn.softmax(jax.random.normal(
        jax.random.PRNGKey(4), (64, E), jnp.float32), -1)
    for how in (dict(norm_topk_prob=True), dict(norm_topk_prob=False,
                                                scale=4.0),
                dict(n_group=4, topk_group=2)):
        plain = moe.route(probs, K, **how)
        zero = moe.route(probs, K, bias=jnp.zeros(E, jnp.float32), **how)
        for a, b in zip(plain, zero):
            assert (np.asarray(a) == np.asarray(b)).all()
    # and the routed layer with the defaults is what it was without them
    x, router, _, experts, _ = _layer_weights(6)
    old, _ = moe.moe_mlp_routed(x, router, *experts, top_k=K)
    new, _ = moe.moe_mlp_routed(x, router, *experts, top_k=K,
                                score="softmax",
                                bias=jnp.zeros(E, jnp.float32))
    assert (np.asarray(old) == np.asarray(new)).all()
