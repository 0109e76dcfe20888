"""An indexer beside every attention layer on the serving path: a third
page pool for its keys, the 16 best keys a query attended in every
program, 8 experts of which 4 are held under a renormalising router.

The size keeps the shape of the problem: hidden 64, 4 query heads x 16
over 2 key-value heads with a QK-norm a head, an indexer of 4 heads of
16 over ONE key a token, top_k 16 (every prompt below is longer), pages
of 4, float32. The yardstick is the plain reference of
``benchmarks/families/keye_vl2.py`` (float32, ``lax.top_k`` of the
scores, one masked softmax, every held expert on every token, nothing of
the program).
"""

import dataclasses
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
from ray_tpu.llm.runner import prefill                      # noqa: E402
from ray_tpu.llm.sampling import SamplingParams             # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params   # noqa: E402
from ray_tpu.ops import rope_frequencies                    # noqa: E402

PAGE, BURST = 4, 4

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tiny-rehearsal-keye-vl2.json")) as _f:
    CONFIG = json.load(_f)
FAMILY = families.family_of(CONFIG)
CFG = FAMILY.program_config(CONFIG)
# Float32 on both sides: what differs is the order of sums (a flash
# product by blocks, experts sorted by rows) and, for logits of deviation
# 1, reads 1e-6 to 1e-5. The indexer's scores rounded to bfloat16 choose
# other keys and read 1e-2 and more (``test_bfloat16_scores_would_fail``)
LOGIT_TOLERANCE = 2e-4
# a chosen token may lie this far below the reference's largest logit, in
# deviations of the position's logits: the same sums, and a tie between
# two tokens is a tie on both sides
MARGIN_TOLERANCE = 1e-3


@pytest.fixture(scope="module")
def params():
    return FAMILY.served_params(jax.random.PRNGKey(11), CONFIG)


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


def _margins(params, prompt, answer):
    logits = _reference(params, prompt + answer)[len(prompt) - 1:-1]
    chosen = logits[np.arange(len(answer)), answer]
    return (logits.max(-1) - chosen) / logits.std(-1)


def test_the_configuration_is_the_shape_of_the_problem():
    assert (CFG.indexer_heads, CFG.indexer_dim, CFG.sparse_top_k) == (4, 16,
                                                                      16)
    assert CFG.qk_norm and CFG.qk_norm_by_head
    assert (CFG.n_experts, CFG.experts_held) == (8, (2, 4))
    assert CFG.indexer_row == 128 and CFG.kv_groups == (None,)
    with pytest.raises(ValueError, match="all of them"):
        dataclasses.replace(CFG, indexer_dim=0)
    with pytest.raises(ValueError, match="ONE stack of full, rotated GQA layers"):
        dataclasses.replace(CFG, layer_pattern=("full", "window"), window=8,
                            n_layers=4)
    with pytest.raises(ValueError, match="set qk_norm too"):
        LlamaConfig(qk_norm_by_head=True)


def test_a_third_pool_under_the_same_pages(params):
    cache = init_kv_cache(CFG, 33, PAGE)
    assert cache.k.shape == cache.v.shape == (3, 33, PAGE, 2, 16)
    assert cache.i.shape == (3, 33, PAGE, 128)
    engine = _engine(params)
    assert len(engine.allocators) == len(engine.seq_tables) == 1
    assert engine.stats()["counters"]["kv_bytes_per_token"] == (
        3 * (2 * 2 * 16 + 128) * 4)
    assert init_kv_cache(dataclasses.replace(
        CFG, indexer_heads=0, indexer_dim=0, sparse_top_k=0), 33,
        PAGE).i is None


@pytest.mark.parametrize("length", [9, 48, 90])
def test_prefill_is_the_plain_forward(params, length):
    """Whole-prompt prefill's logits (a bucket of at most top_k keys
    takes the dense path, the others select) against the reference's."""
    tokens = _prompt(length, length)
    cos, sin = rope_frequencies(CFG.rope_dim, CFG.max_seq, CFG.rope_theta)
    cache = init_kv_cache(CFG, 33, PAGE)
    bucket = 16 if length <= 16 else 128
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :length] = tokens
    logits, *_ = prefill(
        params, cache.k, cache.v, jnp.asarray(padded),
        jnp.asarray([length], jnp.int32),
        jnp.arange(1, 33, dtype=jnp.int32)[None], cos, sin, None, cache.i,
        cfg=CFG)
    want = _reference(params, tokens)[-1]
    np.testing.assert_allclose(np.asarray(logits)[0], want,
                               atol=LOGIT_TOLERANCE, rtol=0)


def test_bfloat16_scores_would_fail(params):
    """The tolerance bites: the reference with the indexer's products in
    bfloat16, or attending over every key, is another function."""
    tokens = _prompt(90, 90)
    want = _reference(params, tokens)
    for control in (dict(index_dtype="bfloat16"), dict(dense=True),
                    dict(topk=8), dict(relu=False), dict(whole_norm=True),
                    dict(renormalise=False)):
        other = _reference(params, tokens, **control)
        assert np.abs(other - want).max() > 50 * LOGIT_TOLERANCE, control


@pytest.mark.parametrize("chunk", [0, 16])
def test_prefill_then_decode_through_the_three_pools(params, chunk):
    """The engine, whole and chunked prefill: every token it decodes
    (bursts of 4 over the slots' own pages, three sequences together,
    each far past top_k) is the reference's choice, by the reference's
    own logits on the sequence."""
    engine = _engine(params, chunk=chunk)
    prompts = [_prompt(n, n) for n in (48, 61, 90)]
    answers = engine.generate(prompts, SamplingParams(
        temperature=0.0, max_tokens=13))
    for prompt, answer in zip(prompts, answers):
        assert len(answer) == 13
        assert _margins(params, prompt, answer).max() <= MARGIN_TOLERANCE
    counters = engine.stats()["counters"]
    # from the positions alone: a query at position t sees t + 1 keys
    # and attends over at most 16; the last token of an answer is
    # sampled and never a query
    spans = [(len(p), len(p) + 12) for p in prompts]
    assert counters["scored_keys"] == sum(
        n * (n + 1) // 2 for _p, n in spans)
    assert counters["attended_keys"] == sum(
        16 * 17 // 2 + 16 * (n - 16) for _p, n in spans)
    # a step of a burst walks every page that held a key when the burst
    # began: bursts of 4 from the prompt's length on, 12 steps in all
    assert counters["sparse_decode_pages"] == sum(
        4 * -(-(p + first) // PAGE) for p, _n in spans
        for first in (0, 4, 8))


def test_a_sequence_alone_and_in_a_batch_choose_the_same(params):
    prompt = _prompt(70, 3)
    alone = _engine(params).generate([prompt], SamplingParams(
        temperature=0.0, max_tokens=9))[0]
    batched = _engine(params).generate(
        [_prompt(33, 4), prompt, _prompt(50, 5)], SamplingParams(
            temperature=0.0, max_tokens=9))[1]
    assert alone == batched


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips, each holding a quarter of the experts, under the
    router that renormalises over the 8 chosen of ALL experts before the
    cut: their outputs sum to the layer that holds every expert."""
    from ray_tpu.ops.moe import moe_mlp_routed

    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    E, d, m, k = 16, 32, 24, 4
    x = jax.random.normal(ks[0], (2, 10, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, E), jnp.float32)
    gate, up = (jax.random.normal(key, (E, d, m), jnp.float32) * d ** -0.5
                for key in ks[2:4])
    down = jax.random.normal(ks[4], (E, m, d), jnp.float32) * m ** -0.5
    whole, _ = moe_mlp_routed(x, router, gate, up, down, top_k=k,
                              norm_topk_prob=True)
    parts = [moe_mlp_routed(
        x, router, gate[first:first + 4], up[first:first + 4],
        down[first:first + 4], top_k=k, norm_topk_prob=True,
        held=(first, 4))[0] for first in range(0, E, 4)]
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5, rtol=1e-5)
    assert all(np.abs(np.asarray(p)).max() > 0 for p in parts)


@pytest.mark.parametrize("option, asked, why", [
    ("enable_prefix_caching", {"enable_prefix_caching": True},
     "no test runs a resumed prompt through the selection"),
    ("lora_rank", {"lora_rank": 4}, "deltas on wq and wv"),
    ("speculation", {"speculation": {"draft_config": "tiny",
                                     "num_draft_tokens": 2}},
     "the drafter mirrors a K and a V pool"),
])
def test_what_a_third_pool_cannot_do_yet_is_refused_by_name(
        params, option, asked, why):
    with pytest.raises(ValueError) as refused:
        _engine(params, **asked)
    assert f"EngineConfig.{option}" in str(refused.value)
    assert "an indexer" in str(refused.value)
    assert why in str(refused.value)


@pytest.mark.parametrize("what", ["export_kv_request", "snapshot_kv_request",
                                  "inject_request"])
def test_kv_hand_over_with_a_third_pool_is_refused_by_name(params, what):
    engine = _engine(params)
    rid = engine.add_request(_prompt(9, 1), SamplingParams(
        temperature=0.0, max_tokens=4))
    engine.step()
    with pytest.raises(ValueError) as refused:
        if what == "inject_request":
            engine.inject_request({"request_id": "x"})
        else:
            getattr(engine, what)(rid)
    assert what in str(refused.value)
    assert "the indexer's key" in str(refused.value)


def test_two_prompts_in_one_prefill_are_refused_by_name(params):
    """The engine prefills one prompt a program, and the three pools are
    written a page of ONE prompt at a time."""
    cos, sin = rope_frequencies(CFG.rope_dim, CFG.max_seq, CFG.rope_theta)
    cache = init_kv_cache(CFG, 33, PAGE)
    with pytest.raises(ValueError, match="an indexer.*B == 1, not 2"):
        prefill(params, cache.k, cache.v, jnp.zeros((2, 16), jnp.int32),
                jnp.asarray([9, 9], jnp.int32),
                jnp.arange(1, 33, dtype=jnp.int32).reshape(2, 16), cos, sin,
                None, cache.i, cfg=CFG)


def test_the_training_forward_refuses_the_indexer(params):
    from ray_tpu.models.llama import forward

    with pytest.raises(ValueError, match=r"an indexer \(sparse_top_k\)"):
        forward(params, jnp.zeros((1, 8), jnp.int32), CFG)


def test_the_seeded_int8_weights_have_the_indexers_leaves():
    from ray_tpu.ops.quant import (init_params_quantized, is_quantized,
                                   quantize_params)

    seeded = init_params_quantized(jax.random.PRNGKey(0), CFG,
                                   {"q_norm": 2.0})["layers"]
    for name, shape in (("wi_q", (3, 64, 4, 16)), ("wi_k", (3, 64, 16)),
                        ("wi_w", (3, 64, 4))):
        assert is_quantized(seeded[name]) and seeded[name]["q"].shape == shape
    assert seeded["wi_k_norm"].shape == seeded["wi_k_bias"].shape == (3, 16)
    assert seeded["q_norm"].shape == seeded["k_norm"].shape == (3, 16)
    # the norm's gain is about the factor asked for
    assert 1.5 < float(jnp.mean(seeded["q_norm"].astype(jnp.float32))) < 2.5
    rounded = quantize_params(init_params(jax.random.PRNGKey(0), CFG))
    assert all(is_quantized(rounded["layers"][n])
               for n in ("wi_q", "wi_k", "wi_w"))


def test_the_engine_says_which_attention_each_program_takes(params):
    paths = _engine(params).attention_paths()
    assert "the indexer's choice" in paths["prefill"]
    assert "own K and V pages where they lie" in paths["decode_burst"]


@pytest.mark.parametrize("program, spans", [
    ("prefill", ("rt.attn.index", "rt.attn.select", "rt.attn.sparse",
                 "rt.moe.route")),
    ("decode", ("rt.attn.index", "rt.attn.select", "rt.attn.sparse")),
])
def test_the_programs_carry_their_spans(params, program, spans):
    """The scopes are in the programs' own text (what a device trace
    attributes operations to), and the kernels' names are the ones the
    readers repeat."""
    from ray_tpu.llm.runner import decode_burst
    from ray_tpu.ops import sparse_attention as sparse

    engine = _engine(params)
    if program == "prefill":
        text = engine.compile_prefill(40)[1].as_text()
    else:
        B = engine.ecfg.max_num_seqs
        zi, zf = jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32)
        text = decode_burst.lower(
            engine.params, engine.cache.k, engine.cache.v, zi, zi,
            engine._tables(), jnp.zeros(B, bool), engine.cos, engine.sin,
            0, zf, zi, zf, None, engine._bt(32), jnp.int32(1),
            engine.cache.i, cfg=engine.cfg, n_steps=BURST,
            greedy=True).as_text(debug_info=True)
    for span in spans:
        assert span in text, span
    assert (sparse.INDEX_KERNEL, sparse.SELECT_KERNEL, sparse.PREFILL_KERNEL,
            sparse.DECODE) == ("rt_sparse_index", "rt_sparse_select",
                               "flash_sparse_fwd", "_decode")
