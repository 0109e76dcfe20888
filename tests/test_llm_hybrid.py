"""State layers on the serving path (MiniCPM-SALA): linear-attention
layers whose memory is a state a slot beside the paged keys and values
of the sparse layers only, selection by blocks where a page is a block,
weights a layer kind.

The size keeps the shape of the problem: the WHOLE published list of 32
``mixer_types`` (8 sparse and 24 lightning layers) at hidden 64, 4 heads
of 16 (2 key-value heads in a sparse layer), blocks and pages of 8 of
which a query past 32 tokens chooses 4, float32. The yardstick is the
plain reference of ``benchmarks/families/minicpm_sala.py`` (float32, the
recurrence a token at a time, an explicit mask of chosen blocks from
``lax.top_k``, nothing of the program).
"""

import dataclasses
import hashlib
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
from ray_tpu.llm.runner import prefill, verify_step         # noqa: E402
from ray_tpu.llm.sampling import SamplingParams             # noqa: E402
from ray_tpu.models.llama import LlamaConfig                # noqa: E402
from ray_tpu.ops import rope_frequencies                    # noqa: E402

PAGE, BURST = 8, 4


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


CONFIG = _config("tiny-rehearsal-minicpm-sala")
FAMILY = families.family_of(CONFIG)
CFG = FAMILY.program_config(CONFIG)
# Float32 on both sides: what differs is the order of sums (the chunked
# form against the recurrence, a flash product by blocks, a stride's sum
# against a window's mean) through 32 layers, which reads 1e-6 to 1e-5
# on logits of deviation about 0.06 (the logits are divided by 4 here,
# by 16 at the published widths). A block chosen differently would read
# 1e-3 and more
LOGIT_TOLERANCE = 5e-5
# a chosen token may lie this far below the reference's largest logit, in
# deviations of the position's logits
MARGIN_TOLERANCE = 2e-3


@pytest.fixture(scope="module")
def params():
    return FAMILY.served_params(jax.random.PRNGKey(11), CONFIG)


def _reference(params, tokens, **control):
    return np.asarray(FAMILY.forward_logits(
        params, jnp.asarray([tokens], jnp.int32), CONFIG, **control))[0]


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, CFG.vocab, n)]


def _engine(params, slots=3, chunk=0, pages=None, **more):
    return LLMEngine(params, CFG, EngineConfig(
        max_num_seqs=slots, page_size=PAGE,
        num_pages=pages or 1 + slots * 16, max_seq_len=128,
        decode_burst=BURST, prefill_chunk=chunk, **more))


def _margins(params, prompt, answer):
    logits = _reference(params, prompt + answer)[len(prompt) - 1:-1]
    chosen = logits[np.arange(len(answer)), answer]
    return (logits.max(-1) - chosen) / logits.std(-1)


def test_the_configuration_is_the_shape_of_the_problem():
    published = _config("minicpm-sala-int8-12l")["published"]["mixer_types"]
    assert CONFIG["mixer_types"] == published and len(published) == 32
    assert CFG.layer_pattern.count("block_nope") == 8
    assert CFG.layer_pattern.count("linear") == 24
    assert [i for i, k in enumerate(CFG.layer_pattern)
            if k == "block_nope"] == [0, 9, 16, 17, 22, 29, 30, 31]
    assert CFG.kv_groups == (None,) and CFG.group_layers(0) == 8
    assert (CFG.n_linear_layers, CFG.n_kv_layers) == (24, 8)
    assert CFG.state_bytes_per_slot == 24 * 4 * 16 * 16 * 4
    assert CFG.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    np.testing.assert_allclose(
        CFG.linear_decay, [2.0 ** (-8 * (h + 1) / 4) for h in range(4)],
        rtol=1e-6)
    with pytest.raises(ValueError, match="lists every layer"):
        dataclasses.replace(CFG, n_layers=64)
    with pytest.raises(ValueError, match="all of them"):
        dataclasses.replace(CFG, block_topk=0)
    with pytest.raises(ValueError, match="need linear_heads"):
        dataclasses.replace(CFG, linear_heads=0)
    with pytest.raises(ValueError, match="belong to"):
        LlamaConfig(linear_heads=4)


def test_weights_a_layer_kind(params):
    """A stack a kind: the lightning layers' keys and values as wide as
    their queries, the sparse layers' of 2 heads; a gate in both."""
    sparse, linear = params["layers"], params["linear_layers"]
    assert sparse["wk"].shape == (8, 64, 2, 16)
    assert linear["wk"].shape == linear["wq"].shape == (24, 64, 4, 16)
    assert sparse["wg"].shape == (8, 64, 4, 16)
    assert linear["o_norm"].shape == (24, 64)
    assert "o_norm" not in sparse
    assert CFG.n_params() == sum(a.size for a in jax.tree.leaves(params))


def test_the_seeded_int8_weights_have_a_stack_a_kind():
    from ray_tpu.ops.quant import (init_params_quantized, is_quantized,
                                   quantize_params)

    seeded = init_params_quantized(jax.random.PRNGKey(0), CFG,
                                   FAMILY.SEED_GAINS)
    for stack, kv in (("layers", 2), ("linear_layers", 4)):
        assert seeded[stack]["wk"]["q"].shape[2] == kv
        for name in ("wq", "wk", "wv", "wo", "wg", "w_gate", "w_down"):
            assert is_quantized(seeded[stack][name]), (stack, name)
        assert not is_quantized(seeded[stack]["q_norm"])
    made = quantize_params(FAMILY.served_params(jax.random.PRNGKey(0),
                                                CONFIG))
    assert jax.tree.structure(made) == jax.tree.structure(seeded)


def test_a_state_a_slot_beside_the_sparse_layers_pages(params):
    cache = _engine(params, slots=3).cache
    # a page ONE matrix of (position, KV head) rows, the sparse layers only
    assert cache.k.shape == cache.v.shape == (8, 49, PAGE * 2, 16)
    assert cache.c.shape == (8, 49, PAGE // 2, 2, 16)
    assert cache.s.shape == (24, 3, 4, 16, 16)
    assert cache.c.dtype == cache.s.dtype == jnp.float32
    with pytest.raises(ValueError, match="so they are equal"):
        init_kv_cache(CFG, 33, 4, slots=2)


def _prefill_logits(params, tokens):
    n = len(tokens)
    bucket = 16
    while bucket < n:
        bucket *= 2
    cos, sin = rope_frequencies(CFG.rope_dim, CFG.max_seq, CFG.rope_theta)
    cache = init_kv_cache(CFG, 33, PAGE, slots=2)
    row = np.zeros((1, bucket), np.int32)
    row[0, :n] = tokens
    logits, *_ = prefill(
        params, cache.k, cache.v, jnp.asarray(row),
        jnp.asarray([n], jnp.int32),
        jnp.arange(1, 17, dtype=jnp.int32).reshape(1, 16), cos, sin, None,
        None, cache.c, cache.s, jnp.asarray([1], jnp.int32), cfg=CFG)
    return np.asarray(logits[0])


@pytest.mark.parametrize("length", [5, 31, 33, 70, 128])
def test_prefill_is_the_plain_forward(params, length):
    """Below dense_len (32) every key; past it the chosen blocks."""
    tokens = _prompt(length, length)
    np.testing.assert_allclose(
        _prefill_logits(params, tokens), _reference(params, tokens)[-1],
        atol=LOGIT_TOLERANCE, rtol=0)


def test_the_controls_move_the_logits(params):
    """What the long-context check's controls change shows at this size
    too: the limits above are not so wide that anything passes."""
    tokens = _prompt(100, 3)
    sound = _reference(params, tokens)[-1]
    for control in (dict(dense=True), dict(forced=False), dict(decay=False),
                    dict(rotary=False)):
        wrong = _reference(params, tokens, **control)[-1]
        assert np.abs(wrong - sound).max() > 20 * LOGIT_TOLERANCE, control


@pytest.mark.parametrize("chunk", [0, 8])
def test_prefill_then_decode_through_the_cache(params, chunk):
    """Prompts below and past dense_len batched beside each other:
    prefill (whole, or in chunks that carry the state through its pool)
    then bursts through the pages and the state equal the reference's
    one forward pass, token for token."""
    prompts = [_prompt(70, 1), _prompt(20, 2), _prompt(45, 3)]
    engine = _engine(params, chunk=chunk)
    answers = engine.generate(prompts, SamplingParams(
        temperature=0.0, max_tokens=12))
    for prompt, answer in zip(prompts, answers):
        assert len(answer) == 12
        assert _margins(params, prompt, answer).max() < MARGIN_TOLERANCE
    counters = engine.stats()["counters"]
    assert counters["state_slots_reset"] == 3
    assert counters["dense_queries"] > 0 and counters["chosen_blocks"] > 0
    assert counters["scored_blocks"] > counters["chosen_blocks"]
    assert counters["block_decode_pages"] > 0
    assert counters["state_bytes_step"] % CFG.state_bytes_per_slot == 0
    stats = engine.stats()
    assert stats["state_bytes_per_slot"] == CFG.state_bytes_per_slot
    # K and V of 8 layers x 2 heads x 16 in float32, and a stride's sums
    assert stats["kv_bytes_per_token"] == 8 * (2 * 2 * 16 * 4
                                                + 2 * 16 * 4 // 2)


def test_chunked_prefill_is_whole_prompt_prefill(params):
    prompt = _prompt(90, 4)
    sampling = SamplingParams(temperature=0.0, max_tokens=10)
    whole = _engine(params).generate([prompt], sampling)
    chunked = _engine(params, chunk=16).generate([prompt], sampling)
    assert whole == chunked


def test_a_sequence_alone_and_in_a_batch_decode_the_same(params):
    prompts = [_prompt(70, 5), _prompt(50, 6), _prompt(12, 7)]
    sampling = SamplingParams(temperature=0.0, max_tokens=10)
    together = _engine(params).generate(prompts, sampling)
    for prompt, answer in zip(prompts, together):
        assert _engine(params).generate([prompt], sampling) == [answer]


def test_a_reused_slot_starts_from_zero(params):
    """One slot, two requests after each other: the second finds the
    first's state in the pool and must not read it."""
    engine = _engine(params, slots=1)
    sampling = SamplingParams(temperature=0.0, max_tokens=8)
    first = engine.generate([_prompt(60, 8)], sampling)
    assert float(jnp.abs(engine.cache.s).max()) > 0    # left at release
    second = engine.generate([_prompt(40, 9)], sampling)
    assert second == _engine(params, slots=1).generate([_prompt(40, 9)],
                                                       sampling)
    assert first != second
    assert engine.stats()["counters"]["state_slots_reset"] == 2


def test_preemption_and_resumption_give_the_same_tokens(params):
    """A pool too small for both sequences' growth: one is preempted and
    prefills again, prompt and output so far, into a zeroed state."""
    prompts = [_prompt(40, 10), _prompt(44, 11)]
    sampling = SamplingParams(temperature=0.0, max_tokens=30)
    roomy = _engine(params, slots=2).generate(prompts, sampling)
    tight = _engine(params, slots=2, pages=1 + 16)
    assert tight.generate(prompts, sampling) == roomy
    assert tight.stats()["counters"]["preemptions"] > 0


@pytest.mark.parametrize("option,asked,why", [
    ("enable_prefix_caching", dict(enable_prefix_caching=True),
     "says nothing of a linear layer's state"),
    ("lora_rank", dict(lora_rank=4), "a stack each"),
])
def test_what_state_layers_cannot_do_yet_is_refused_by_name(
        params, option, asked, why):
    with pytest.raises(ValueError) as refused:
        _engine(params, **asked)
    assert f"EngineConfig.{option}" in str(refused.value)
    assert "state layers" in str(refused.value)
    assert why in str(refused.value)


def test_speculation_with_state_layers_is_refused_by_name(params):
    with pytest.raises(ValueError) as refused:
        _engine(params, speculation={"draft_config": "tiny",
                                     "num_draft_tokens": 2})
    assert "EngineConfig.speculation" in str(refused.value)
    assert "roll a slot's state back" in str(refused.value)
    cache = init_kv_cache(CFG, 33, PAGE, slots=2)
    cos, sin = rope_frequencies(CFG.rope_dim, CFG.max_seq, CFG.rope_theta)
    z = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="verify_step is not written for "
                       "linear layers"):
        verify_step(params, cache.k, cache.v, z, z, jnp.zeros((2, 16),
                                                              jnp.int32),
                    cos, sin, 0, jnp.ones(2), jnp.zeros(2, jnp.int32),
                    jnp.ones(2), None, cache.c, cache.s, cfg=CFG)


@pytest.mark.parametrize("what", ["export_kv_request", "snapshot_kv_request",
                                  "inject_request"])
def test_kv_hand_over_with_state_layers_is_refused_by_name(params, what):
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
    assert "a state a slot that no page holds" in str(refused.value)


def test_two_prompts_in_one_prefill_are_refused_by_name(params):
    cos, sin = rope_frequencies(CFG.rope_dim, CFG.max_seq, CFG.rope_theta)
    cache = init_kv_cache(CFG, 33, PAGE, slots=2)
    with pytest.raises(ValueError, match="state layers.*B == 1, not 2"):
        prefill(params, cache.k, cache.v, jnp.zeros((2, 16), jnp.int32),
                jnp.asarray([9, 9], jnp.int32),
                jnp.arange(1, 33, dtype=jnp.int32).reshape(2, 16), cos, sin,
                None, None, cache.c, cache.s, jnp.asarray([0], jnp.int32),
                cfg=CFG)


def test_the_training_forward_refuses_the_pattern_by_name(params):
    from ray_tpu.models.llama import forward

    with pytest.raises(ValueError, match="'linear' and 'block_nope' layers "
                       "with weights of their own"):
        forward(params, jnp.zeros((1, 8), jnp.int32), CFG)


def test_the_engine_says_which_attention_each_program_takes(params):
    paths = _engine(params).attention_paths()
    assert "linear layers" in paths["prefill"]
    assert "refused" in paths["verify_step"]


def test_the_programs_carry_their_spans(params):
    """The spans the benchmark's readers and a profile find: in
    whole-prompt prefill past dense_len and in a decode burst."""
    from ray_tpu.llm.runner import decode_burst

    cos, sin = rope_frequencies(CFG.rope_dim, CFG.max_seq, CFG.rope_theta)
    cache = init_kv_cache(CFG, 33, PAGE, slots=2)
    table = jnp.arange(1, 17, dtype=jnp.int32).reshape(1, 16)
    text = prefill.lower(
        params, cache.k, cache.v, jnp.zeros((1, 64), jnp.int32),
        jnp.asarray([60], jnp.int32), table, cos, sin, None, None, cache.c,
        cache.s, jnp.asarray([0], jnp.int32), cfg=CFG).as_text(
            debug_info=True)
    for span in ("rt.attn.linear", "rt.attn.block.score", "rt.attn.select",
                 "rt.attn.sparse"):
        assert span in text, span
    z, f = jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.float32)
    tables = jnp.zeros((2, 16), jnp.int32)
    text = decode_burst.lower(
        params, cache.k, cache.v, z, z, tables, jnp.zeros(2, bool), cos,
        sin, 0, f, z, f, None, tables, jnp.int32(1), None, cache.c, cache.s,
        cfg=CFG, n_steps=BURST, greedy=True).as_text(debug_info=True)
    for span in ("rt.attn.linear", "rt.attn.block.score", "rt.attn.select",
                 "rt.attn.sparse"):
        assert span in text, span


def _calls(jaxpr, found):
    """name -> the bodies of the jitted functions a jaxpr calls."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("pjit", "jit"):
            found.setdefault(eqn.params["name"], []).append(
                id(eqn.params["jaxpr"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _calls(sub, found)
    return found


def test_the_layers_functions_are_traced_once_a_program(params):
    """24 linear layers one after the other call the linear form with
    the same shapes and the 8 sparse ones the selection by blocks: each
    is a jitted function of its own, and a program holds ONE traced body
    of it, lowered to one function, however many layers call it (a
    program is loaded before a replica is ready: tracing the same
    function a layer was most of what that cost)."""
    from ray_tpu.llm.runner import decode_burst

    kinds = CFG.layer_kinds
    n_linear = sum(k == "linear" for k in kinds)
    n_sparse = len(kinds) - n_linear
    assert (n_linear, n_sparse) == (24, 8)
    cos, sin = rope_frequencies(CFG.rope_dim, CFG.max_seq, CFG.rope_theta)
    cache = init_kv_cache(CFG, 33, PAGE, slots=2)
    table = jnp.arange(1, 17, dtype=jnp.int32).reshape(1, 16)
    z, f = jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.float32)
    tables = jnp.zeros((2, 16), jnp.int32)
    programs = {
        "prefill": (prefill.trace(
            params, cache.k, cache.v, jnp.zeros((1, 64), jnp.int32),
            jnp.asarray([60], jnp.int32), table, cos, sin, None, None,
            cache.c, cache.s, jnp.asarray([0], jnp.int32), cfg=CFG),
            {"_prefill": n_linear, "_block_attend": n_sparse}),
        "decode_burst": (decode_burst.trace(
            params, cache.k, cache.v, z, z, tables, jnp.zeros(2, bool), cos,
            sin, 0, f, z, f, None, tables, jnp.int32(1), None, cache.c,
            cache.s, cfg=CFG, n_steps=BURST, greedy=True),
            {"_decode_step": n_linear, "_block_decode_pages": n_sparse,
             "block_decode_attention": n_sparse})}
    for program, (traced, expected) in programs.items():
        found = _calls(traced.jaxpr.jaxpr, {})
        text = traced.lower().as_text()
        for name, layers in expected.items():
            assert len(found[name]) == layers, (program, name)
            assert len(set(found[name])) == 1, (program, name)
            assert text.count(f"func.func private @{name}(") == 1, (
                program, name)
            assert text.count(f"call @{name}(") == layers, (program, name)


# ---------------------------------------------------------------- the others
# The five accepted families' seeded weights and a fixed prompt's prefill
# logits as the tree BEFORE the state layers made them (PR 44's, read
# there with this very function): every default of every new
# ``LlamaConfig`` field is what they run. The int8 weights are made from
# integer random bits and are held to the bit; the logits are float32
# sums, held to the bit where this CPU sums as the one that wrote them
# did and to 1e-6 otherwise.
ACCEPTED = {
    "tiny-rehearsal": ("cc52551062bde37b", "1d02e15c156ff775",
                       [0.19841349124908447, 2.2907376289367676,
                        -0.5409632325172424, 0.33411261439323425]),
    "tiny-rehearsal-olmoe": ("59a10f0a20fe52d8", "00a4a06ed3d6d83a",
                             [-0.6014094352722168, 0.7922561168670654,
                              -1.1306408643722534, 0.2143716663122177]),
    "tiny-rehearsal-smallthinker": (
        "68bec1f400268ac6", "b9d961cf3fc4b9f0",
        [0.23147623240947723, 0.6518259644508362, 0.4937548041343689,
         -0.22297123074531555]),
    "tiny-rehearsal-deepseek-v2": (
        "d48c5a72bb659bfc", "1f66ded0403e0ff7",
        [1.5363813638687134, 0.7097108364105225, 0.6335186958312988,
         -0.6370692849159241]),
    "tiny-rehearsal-keye-vl2": (
        "554276796cc166ca", "d881e9d9750fc43f",
        [1.5644657611846924, 0.7173178195953369, 0.7623541951179504,
         -0.5893853902816772]),
}


def _digest(tree):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_the_accepted_families_are_unchanged(name):
    from ray_tpu.ops.quant import init_params_quantized

    config = _config(name)
    family = families.family_of(config)
    cfg = family.program_config(config)
    weights, logits_digest, first = ACCEPTED[name]
    assert _digest(init_params_quantized(
        jax.random.PRNGKey(7), cfg,
        getattr(family, "SEED_GAINS", None))) == weights
    params = family.served_params(jax.random.PRNGKey(7), config)
    groups = len(cfg.kv_groups)
    cache = init_kv_cache(cfg, 33 if groups == 1 else [33] * groups, 4)
    cos, sin = rope_frequencies(cfg.rope_dim, cfg.max_seq, cfg.rope_theta,
                                scaling=cfg.rope_scaling)
    tokens = jnp.asarray([[(7 * i + 3) % cfg.vocab or 1
                           for i in range(32)]], jnp.int32)
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(1, 8)
    more = {} if cache.i is None else {"cache_i": cache.i}
    logits = prefill(
        params, cache.k, cache.v, tokens, jnp.asarray([29], jnp.int32),
        table if groups == 1 else (table,) * groups, cos, sin, None,
        **more, cfg=cfg)[0]
    if _digest(logits) != logits_digest:
        np.testing.assert_allclose(np.asarray(logits)[0, :4], first,
                                   rtol=1e-6, atol=1e-6)
