"""Layer groups on the serving path: full and window layers side by side
(``LlamaConfig.layer_pattern``), a page pool a group, pages given back
behind the window, the router read before attention, ReGLU experts.

The size keeps the shape of the problem: hidden 48, 4 query heads x 16
(so ``head_dim`` is not ``dim / heads``), 2 key-value heads, 8 layers =
2 periods of (full NoPE, window, window, window), window 16, pages of 4,
8 experts of 32, 3 a token, float32. The yardstick is the plain
reference of ``benchmarks/families/smallthinker.py`` (float32, one
masked softmax, every expert on every token, nothing of the program).
"""

import dataclasses
import json
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import families                     # noqa: E402
from ray_tpu.llm import engine as engine_module             # noqa: E402
from ray_tpu.llm.cache import (init_kv_cache,               # noqa: E402
                               window_group_pages)
from ray_tpu.llm.engine import EngineConfig, LLMEngine      # noqa: E402
from ray_tpu.llm.runner import (prefill, prefill_chunk,     # noqa: E402
                                verify_step)
from ray_tpu.llm.sampling import SamplingParams             # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params   # noqa: E402
from ray_tpu.ops import rope_frequencies                    # noqa: E402
from ray_tpu.ops.attention import (blockwise_attention,     # noqa: E402
                                   flash_attention_tpu, naive_attention)

PAGE, WINDOW, BURST = 4, 16, 4

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tiny-rehearsal-smallthinker.json")) as _f:
    CONFIG = json.load(_f)
FAMILY = families.family_of(CONFIG)
CFG = FAMILY.program_config(CONFIG)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(11), CFG)


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


def test_the_configuration_is_the_shape_of_the_problem():
    assert CFG.head_dim == 16 != CFG.dim // CFG.n_heads
    assert CFG.layer_pattern == ("full_nope", "window", "window", "window")
    assert CFG.kv_groups == (None, WINDOW)
    assert (CFG.group_layers(0), CFG.group_layers(1)) == (2, 6)
    assert [CFG.layer_group(j) for j in range(4)] == [
        (0, 0), (1, 0), (1, 1), (1, 2)]
    assert (CFG.router_input, CFG.expert_act, CFG.top_k) == (
        "attention", "relu", 3)
    # today's configurations are what they were: one group, one kind
    plain = LlamaConfig()
    assert plain.kv_groups == (None,) and plain.layer_kinds == ("full",)
    assert plain.head_dim == 128 and plain.group_layers(0) == 32
    with pytest.raises(ValueError, match="whole number of periods"):
        dataclasses.replace(CFG, n_layers=6)
    with pytest.raises(ValueError, match="window is not set"):
        dataclasses.replace(CFG, window=None)
    with pytest.raises(ValueError, match="unknown kinds"):
        dataclasses.replace(CFG, layer_pattern=("sliding",))


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
    """A prompt of 37 tokens (2.3 windows) decodes 70 more, across a
    dozen releases of its window pages: every token is the reference's
    first choice given the tokens before it, the logits read back
    through the pages half way and at the end are the reference's to
    1e-4, and the sequence never holds more than window / page + 2 pages
    of the window group."""
    engine = _engine(params)
    long_id = engine.add_request(_prompt(37, 1), SamplingParams(
        temperature=0.0, max_tokens=70))
    short_id = None
    if company != "alone":
        short_id = engine.add_request(_prompt(9, 2), SamplingParams(
            temperature=0.0, max_tokens=30))
    state = engine.requests[long_id]
    most, probed = 0, []
    while not state.finished:
        engine.step()
        if state.slot >= 0:
            most = max(most, len(engine.seq_tables[1].pages_of(state.slot)))
        if state.slot >= 0 and len(state.output) in (33, 34, 35, 36, 65,
                                                     66, 67, 68) \
                and len(probed) < 2 and (
                    not probed or len(state.output) > 60):
            seq = state.prompt + state.output
            assert state.ctx_len == len(seq) - 1
            got = _pages_through_the_chunk_program(engine, state)
            want = _reference(params, seq)[-1]
            np.testing.assert_allclose(got, want, atol=1e-4)
            probed.append(len(seq))
    while engine.has_unfinished():
        engine.step()
    assert len(probed) == 2 and len(state.output) == 70
    for rid in filter(None, (long_id, short_id)):
        s = engine.requests[rid]
        seq = s.prompt + s.output
        want = _reference(params, seq)[len(s.prompt) - 1:-1].argmax(-1)
        assert s.output == want.tolist()
    assert most <= WINDOW // PAGE + 2
    groups = engine.stats()["counters"]["groups"]
    assert groups["window"]["released_pages"] >= 12
    assert groups["full"]["released_pages"] == 0
    for name, allocator in zip(engine.group_names, engine.allocators):
        assert allocator.free_pages == allocator.num_pages - 1, name
        assert not allocator._refs


def _tables_for(length, *, whole):
    """Block tables of one sequence of ``length`` tokens, a group: pages
    1.. in order; the window group's only from its first live page on
    (``whole``: every page, as a chunked prefill holds them)."""
    n = -(-length // PAGE)
    full = np.zeros((1, 32), np.int32)
    full[0, :n] = 1 + np.arange(n)
    first = 0 if whole else max(length - WINDOW + 1, 0) // PAGE
    window = np.zeros((1, 32), np.int32)
    window[0, first:n] = 1 + np.arange(n - first)
    return (jnp.asarray(full), jnp.asarray(window)), first


def _fresh_cache():
    return init_kv_cache(CFG, (40, 40), PAGE)


def _whole_prompt(params, tokens, cfg=CFG):
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    tables, _ = _tables_for(len(tokens), whole=False)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :len(tokens)] = tokens
    cache = _fresh_cache()
    logits, ck, cv, _ = prefill(
        params, cache.k, cache.v, jnp.asarray(padded),
        jnp.asarray([len(tokens)], jnp.int32), tables, cos, sin, cfg=cfg)
    return np.asarray(logits)[0], (ck, cv), tables


def test_chunks_and_a_verify_window_agree_with_whole_prompt_prefill(params):
    tokens = _prompt(45, 3)
    cos, sin = rope_frequencies(CFG.head_dim, CFG.max_seq, CFG.rope_theta)
    want = _reference(params, tokens)
    whole, (ck, cv), tables = _whole_prompt(params, tokens)
    np.testing.assert_allclose(whole, want[-1], atol=1e-4)
    # the window group's pool holds nothing before its first live page
    first = max(45 - WINDOW + 1, 0) // PAGE
    assert first == 7 and not np.asarray(tables[1])[0, :first].any()

    # eight-token chunks through the pages, every page held to the end
    tables_all, _ = _tables_for(45, whole=True)
    cache = _fresh_cache()
    ck2, cv2 = cache.k, cache.v
    for start in range(0, 45, 8):
        n = min(8, 45 - start)
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :n] = tokens[start:start + n]
        logits, ck2, cv2, _ = prefill_chunk(
            params, ck2, cv2, jnp.asarray(chunk), jnp.int32(start),
            jnp.int32(n), tables_all, cos, sin, cfg=CFG)
    np.testing.assert_allclose(np.asarray(logits)[0], whole, atol=1e-4)

    # a verify window of 5 over the pages whole-prompt prefill left for
    # the first 40 tokens (its window pages only from the 25th position)
    _, (ck, cv), _ = _whole_prompt(params, tokens[:40])
    tables_v, _ = _tables_for(40, whole=False)
    grown = tuple(np.array(t) for t in tables_v)
    for t in grown:                       # pages for positions 40..44
        t[0, 10:12] = (30, 31)
    window = np.asarray(tokens[40:45], np.int32)[None]
    positions = np.arange(40, 45, dtype=np.int32)[None]
    one = jnp.ones(1, jnp.float32)
    tgt, _, _, _, _ = verify_step(
        params, ck, cv, jnp.asarray(window), jnp.asarray(positions),
        tuple(jnp.asarray(t) for t in grown), cos, sin, 0, one,
        jnp.zeros(1, jnp.int32), one, cfg=CFG, greedy=True)
    assert np.asarray(tgt)[0].tolist() == want[40:45].argmax(-1).tolist()
    assert int(np.asarray(tgt)[0, -1]) == int(whole.argmax())


@pytest.mark.parametrize("field, other", [
    ("router_input", "mlp"), ("expert_act", "silu"),
    ("layer_pattern", ("full_nope", "full", "full", "full")),
    ("layer_pattern", ("full", "window", "window", "window"))],
    ids=["router-after-attention", "silu-experts", "no-window",
         "rotary-everywhere"])
def test_controls_that_must_differ(params, field, other):
    """The same weights under one changed setting give other logits: the
    early router is not a router fed the feed-forward's input, relu is
    not silu, a window is not the whole sequence, NoPE is not rotary."""
    tokens = _prompt(45, 4)
    ours, _, _ = _whole_prompt(params, tokens)
    wrong_cfg = dataclasses.replace(CFG, **{field: other})
    assert wrong_cfg.kv_groups in ((None, WINDOW), (None,))
    if wrong_cfg.kv_groups == (None,):
        cos, sin = rope_frequencies(CFG.head_dim, CFG.max_seq,
                                    CFG.rope_theta)
        padded = np.zeros((1, 64), np.int32)
        padded[0, :45] = tokens
        cache = init_kv_cache(wrong_cfg, 40, PAGE)
        wrong, _, _, _ = prefill(
            params, cache.k, cache.v, jnp.asarray(padded),
            jnp.asarray([45], jnp.int32), _tables_for(45, whole=True)[0][0],
            cos, sin, cfg=wrong_cfg)
        wrong = np.asarray(wrong)[0]
    else:
        wrong, _, _ = _whole_prompt(params, tokens, wrong_cfg)
    assert np.abs(wrong - ours).max() > 1e-2
    np.testing.assert_allclose(ours, _reference(params, tokens)[-1],
                               atol=1e-4)


def test_the_reference_controls_move_the_logits(params):
    tokens = _prompt(45, 5)
    right = _reference(params, tokens)
    for control in (dict(all_full=True), dict(rotate_all=True),
                    dict(act="silu")):
        assert np.abs(_reference(params, tokens, **control)
                      - right).max() > 1e-2, control
    np.testing.assert_allclose(
        _reference(params, tokens, last=3), right[-3:], atol=1e-5)


def test_the_other_two_kinds_through_the_pages():
    """A rotated full layer inside a pattern and a window layer without
    rotary (the two bits are independent; the published layout uses the
    other two combinations): prefill, then decode across releases, picks
    the reference's first choice at every token."""
    config = dict(CONFIG, rope_layout=[1, 0] * 4,
                  sliding_window_layout=[0, 1] * 4)
    cfg = FAMILY.program_config(config)
    assert cfg.layer_pattern == ("full", "window_nope")
    assert cfg.kv_groups == (None, WINDOW)
    params = init_params(jax.random.PRNGKey(12), cfg)
    engine = LLMEngine(params, cfg, EngineConfig(
        max_num_seqs=2, page_size=PAGE, num_pages=65, max_seq_len=128,
        decode_burst=BURST))
    prompt = _prompt(37, 6)
    out = engine.generate([prompt], SamplingParams(
        temperature=0.0, max_tokens=40))[0]
    want = np.asarray(FAMILY.forward_logits(
        params, jnp.asarray([prompt + out], jnp.int32), config))[0]
    assert out == want[len(prompt) - 1:-1].argmax(-1).tolist()
    assert engine.stats()["counters"]["groups"]["window"][
        "released_pages"] >= 5


def test_adapters_with_a_layer_pattern_are_refused_by_the_runner(params):
    from ray_tpu.llm.runner import _layers

    cos, sin = rope_frequencies(CFG.head_dim, CFG.max_seq, CFG.rope_theta)
    lora = {"scale": jnp.ones((1,)), "a_q": jnp.zeros((1, 8, 48, 2))}
    with pytest.raises(ValueError, match="layer pattern"):
        _layers(params, CFG, cos, sin, lora)


@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
def test_seed_gains_scale_the_named_matrices_and_nothing_else(quantized):
    """``gains`` multiply a matrix's seeded scale; without them the
    weights of a seed are what they always were; an unknown name is
    refused."""
    from ray_tpu.ops.quant import init_params_quantized

    key = jax.random.PRNGKey(3)
    make = init_params_quantized if quantized else init_params
    plain, same = make(key, CFG), make(key, CFG, {})
    scaled = make(key, CFG, {"embed": 50.0, "w_down": 0.5})

    def value(w):
        if isinstance(w, dict):      # int8: the values are the same
            assert w["q"].dtype == jnp.int8    # bits, the scales move
            return np.asarray(w["s"], np.float32)
        return np.asarray(w, np.float32)

    if quantized:
        np.testing.assert_array_equal(np.asarray(scaled["embed"]["q"]),
                                      np.asarray(plain["embed"]["q"]))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(same)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(value(scaled["embed"]),
                               50.0 * value(plain["embed"]), rtol=1e-6)
    np.testing.assert_allclose(value(scaled["layers"]["w_down"]),
                               0.5 * value(plain["layers"]["w_down"]),
                               rtol=1e-6)
    for name in ("wq", "wo", "w_up", "router"):
        np.testing.assert_array_equal(value(scaled["layers"][name]),
                                      value(plain["layers"][name]))
    with pytest.raises(ValueError, match="not seeded"):
        make(key, CFG, {"w_sideways": 2.0})


# --- the host's bookkeeping alone: the device programs replaced by
# checks of what they are handed ---

@pytest.fixture
def host_only(monkeypatch):
    """``prefill_sample`` and ``decode_burst`` that compute nothing and
    check their page tables and lists: every key a window layer's query
    may see, and every row about to be written, has a page of its own."""
    seen = {"bursts": 0, "most_window_pages": 0}

    def fake_prefill(params, ck, cv, tokens, lens, tables, *_a, cfg, **_k):
        n = int(lens[0])
        full, window = (np.asarray(t)[0] for t in tables)
        assert (full[:-(-(n + 1) // PAGE)] > 0).all()
        live = np.arange(max(n - WINDOW + 1, 0), n + 1) // PAGE
        assert (window[live] > 0).all(), "a live key's page is missing"
        return jnp.zeros(1, jnp.int32), ck, cv, jnp.zeros(2, jnp.int32)

    def fake_burst(params, ck, cv, tokens, positions, tables, active, cos,
                   sin, seed, temp, top_k, top_p, lora, gather, steps, *,
                   cfg, n_steps, greedy):
        seen["bursts"] += 1
        positions, active = np.asarray(positions), np.asarray(active)
        owners = set()
        for g, (table, listed) in enumerate(zip(tables, gather)):
            table, (pages, owner, first) = np.asarray(table), np.asarray(
                listed)
            for slot in np.flatnonzero(active):
                q, k = int(positions[slot]), int(steps)
                written = np.arange(q, q + k) // PAGE
                assert (table[slot, written] > 0).all()
                oldest = 0 if g == 0 else max(q - WINDOW + 1, 0)
                need = sorted({p // PAGE for p in range(oldest, q)})
                mine = owner == slot
                assert sorted(first[mine] // PAGE) == need, (
                    "the list is not the pages the window holds")
                assert (pages[mine] == table[slot, first[mine] // PAGE]
                        ).all() and (pages[mine] > 0).all()
                if g:
                    seen["most_window_pages"] = max(
                        seen["most_window_pages"],
                        int((table[slot] > 0).sum()))
                owners.update((g, int(p)) for p in table[slot][
                    table[slot] > 0])
            held = table[active][table[active] > 0]
            assert len(held) == len(set(held.tolist())), "a shared page"
        return (jnp.zeros((n_steps, len(active)), jnp.int32), ck, cv,
                jnp.zeros(2, jnp.int32))

    monkeypatch.setattr(engine_module, "prefill_sample", fake_prefill)
    monkeypatch.setattr(engine_module, "decode_burst", fake_burst)
    return seen


@pytest.mark.parametrize("seed", range(6))
def test_no_live_page_is_released_and_every_page_comes_back(
        params, host_only, seed):
    """Random prompt and answer lengths, up to seven windows long, more
    requests than slots: at every prefill and burst the tables hold
    every page a live key or a written row needs (``host_only`` asserts
    it), the window pool of slots x (window + page + burst) is never
    short (no preemption), and when the requests end both pools are
    whole again."""
    rng = random.Random(seed)
    slots = rng.choice((2, 3, 4))
    engine = LLMEngine(params, CFG, EngineConfig(
        max_num_seqs=slots, page_size=PAGE, num_pages=1 + slots * 32,
        max_seq_len=128, decode_burst=BURST))
    assert engine.allocators[1].num_pages == window_group_pages(
        slots, WINDOW, PAGE, BURST) == slots * 6 + 1
    ids = []
    for i in range(3 * slots):
        n = rng.choice((rng.randint(1, 15), rng.randint(16, 100)))
        ids.append(engine.add_request(_prompt(n, seed * 100 + i), (
            SamplingParams(temperature=0.0,
                           max_tokens=rng.randint(1, 127 - n)))))
    rounds = 0
    while engine.has_unfinished():
        engine.step()
        rounds += 1
        assert rounds < 5000
    counters = engine.stats()["counters"]
    assert host_only["bursts"] == counters["rounds"] > 10
    assert counters["preemptions"] == 0
    assert host_only["most_window_pages"] <= WINDOW // PAGE + 2
    assert counters["groups"]["window"]["released_pages"] > 0
    for allocator in engine.allocators:
        assert allocator.free_pages == allocator.num_pages - 1
        assert not allocator._refs


def test_admission_waits_for_both_groups_and_says_which(params, host_only):
    """Two slots, a full pool of 24 pages: the second long prompt does
    not fit beside the first in the FULL group, is put off (counted
    against that group), and is admitted when the first ends."""
    engine = LLMEngine(params, CFG, EngineConfig(
        max_num_seqs=2, page_size=PAGE, num_pages=33, max_seq_len=128,
        decode_burst=BURST))
    a = engine.add_request(_prompt(90, 1), SamplingParams(
        temperature=0.0, max_tokens=20))
    b = engine.add_request(_prompt(60, 2), SamplingParams(
        temperature=0.0, max_tokens=8))
    engine.step()            # a is admitted and prefilled
    engine.step()            # b is tried, and put off
    assert engine.requests[a].slot >= 0 and engine.requests[b].slot < 0
    groups = engine.stats()["counters"]["groups"]
    assert groups["full"]["deferred_admissions"] >= 1
    assert groups["window"]["deferred_admissions"] == 0
    assert 0 < groups["window"]["free_pages"] < 12
    while engine.has_unfinished():
        engine.step()
    assert len(engine.requests[b].output) == 8


def test_stats_and_the_release_span(params):
    from ray_tpu.llm.engine import PHASES
    from ray_tpu.util import tracing

    assert "release" in PHASES
    engine = _engine(params, slots=2)
    engine.add_request(_prompt(30, 6), SamplingParams(
        temperature=0.0, max_tokens=12))
    spans = []
    real = tracing.span

    def recording(name, *a, **k):
        spans.append(name)
        return real(name, *a, **k)

    tracing.span = recording
    try:
        while engine.has_unfinished():
            engine.step()
    finally:
        tracing.span = real
    assert "rt.engine.release" in spans
    stats = engine.stats()
    counters = stats["counters"]
    assert counters["host_s"]["release"] > 0
    assert set(counters["groups"]) == {"full", "window"}
    for group in counters["groups"].values():
        assert set(group) == {"live_pages", "gathered_pages",
                              "released_pages", "deferred_admissions",
                              "free_pages", "total_pages"}
    assert stats["free_pages"] == sum(
        g["free_pages"] for g in counters["groups"].values())
    assert counters["live_pages"] == sum(
        g["live_pages"] for g in counters["groups"].values())
    assert all("/" in key for key in counters["gather_hist"])
    # a copy: a reader cannot reach the engine's own
    counters["groups"]["window"]["released_pages"] = -1
    assert engine.stats()["counters"]["groups"]["window"][
        "released_pages"] > 0


def test_a_loaded_engine_compiles_no_decode_program(params):
    """Three slots of 32 pages, a window of 4 or 5: the full group's
    list takes 16, 32, 64 or 96 pages and the window group's always 15,
    so four programs; after loading them a mixed run compiles none."""
    from ray_tpu.llm.runner import decode_burst

    engine = _engine(params)
    shapes = engine.decode_buckets()
    assert shapes == [(16, 15), (32, 15), (64, 15), (96, 15)]
    assert engine.load_decode_programs() == 4
    size = decode_burst._cache_size()
    for i, n in enumerate([90, 5, 40, 70, 20]):
        engine.add_request(_prompt(n, i), SamplingParams(
            temperature=0.0, max_tokens=20 + i))
    while engine.has_unfinished():
        engine.step()
    assert decode_burst._cache_size() == size
    hist = engine.stats()["counters"]["gather_hist"]
    assert set(hist) <= {f"{f}/{w}" for f, w in shapes} and len(hist) > 1


def test_decode_buckets_pair_what_can_meet():
    """At the cell's sizes (8 slots x 192 pages, window 4096 = 64 pages
    of 64): lists grow together until a window fills, then the full
    group's grows alone; 16 programs, not 8 x 7."""
    cfg = LlamaConfig(vocab=64, dim=16, n_layers=4, n_heads=1,
                      n_kv_heads=1, head_size=16, mlp_dim=16,
                      max_seq=12288, dtype=jnp.float32,
                      layer_pattern=("full", "window"), window=4096)
    engine = LLMEngine.__new__(LLMEngine)      # the arithmetic alone
    engine.cfg, engine.windows = cfg, cfg.kv_groups
    engine.ecfg = EngineConfig(max_num_seqs=8, page_size=64,
                               num_pages=1537, max_seq_len=12288)
    engine.prefix_cache = None
    from ray_tpu.llm.cache import PageAllocator, SequenceTable

    engine.allocators = [PageAllocator(1537, 64), PageAllocator(
        window_group_pages(8, 4096, 64, 8), 64)]
    engine.seq_tables = [SequenceTable(8, 192), SequenceTable(8, 192)]
    engine.seq_table = engine.seq_tables[0]
    assert engine.allocators[1].num_pages == 8 * 66 + 1
    shapes = engine.decode_buckets()
    # one slot of 65 pages has 64 of them inside its window
    assert shapes[:5] == [(16, 16), (32, 32), (64, 64), (128, 64),
                          (128, 128)]
    assert (256, 128) in shapes and (256, 256) in shapes
    assert (1536, 520) in shapes and (1536, 16) not in shapes
    assert len(shapes) == 16
    for full, window in shapes:
        assert window <= full


@pytest.mark.parametrize("option, asked", [
    ("enable_prefix_caching", dict(enable_prefix_caching=True)),
    ("speculation", dict(speculation={"draft_config": "tiny",
                                      "num_draft_tokens": 2})),
    ("lora_rank", dict(lora_rank=4)),
    ("prefill_chunk", dict(prefill_chunk=8, max_num_seqs=1)),
    ("export_kv_request", None), ("snapshot_kv_request", None),
    ("inject_request", None), ("enable_speculation", None),
    ("pool", None)])
def test_what_two_groups_cannot_do_yet_is_refused_by_name(params, option,
                                                          asked):
    if asked is not None:
        config = dict(max_num_seqs=2, page_size=PAGE, num_pages=65,
                      max_seq_len=128, decode_burst=BURST)
        config.update(asked)
        with pytest.raises(ValueError, match=option + ".*layer groups"):
            LLMEngine(params, CFG, EngineConfig(**config))
        return
    engine = _engine(params, slots=1)
    if option == "pool":
        from ray_tpu.llm.serve import LLMServer

        server = LLMServer.__new__(LLMServer)
        server.engine = engine
        for pool in ("prefill", "decode"):
            with pytest.raises(ValueError, match="pool=.*layer groups"):
                server.configure_pool(pool, "llm")
        return
    if option == "enable_speculation":
        with pytest.raises(ValueError, match="speculation.*layer groups"):
            engine.enable_speculation({"draft_config": "tiny",
                                       "num_draft_tokens": 2})
        return
    argument = {"prompt": [1], "ctx_len": 1} \
        if option == "inject_request" else "req-0"
    with pytest.raises(ValueError, match=option + ".*layer groups"):
        getattr(engine, option)(argument)


# --- the window in the attention kernels ---

@pytest.mark.parametrize("window", [1, 100, 128, 129, 300, 512, 4096])
def test_window_attention_kernels_against_the_plain_one(window):
    """The flash kernel (in the Pallas interpreter) and the blockwise
    scan with a window equal plain masked attention: blocks wholly
    behind the window skipped, the block on its edge masked; a window
    the sequence never fills is causal attention."""
    keys = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(keys[0], (1, 512, 4, 128), jnp.float32)
    k = jax.random.normal(keys[1], (1, 512, 2, 128), jnp.float32)
    v = jax.random.normal(keys[2], (1, 512, 2, 128), jnp.float32)
    want = naive_attention(q, k, v, window=window)
    flash = flash_attention_tpu(q, k, v, window=window, block_q=128,
                                block_k=128, interpret=True)
    scan = blockwise_attention(q, k, v, window=window, kv_block=128)
    np.testing.assert_allclose(flash, want, atol=2e-5)
    np.testing.assert_allclose(scan, want, atol=2e-5)
    causal = naive_attention(q, k, v)
    if window >= 512:
        np.testing.assert_allclose(want, causal, atol=1e-6)
    else:
        assert np.abs(np.asarray(want - causal)).max() > 1e-2
    with pytest.raises(ValueError, match="causal"):
        blockwise_attention(q, k, v, causal=False, window=window)


def test_routed_layer_takes_logits_from_elsewhere_and_an_activation():
    from ray_tpu.ops.moe import moe_mlp_routed, router_logits

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (2, 5, 16))
    other = jax.random.normal(keys[1], (2, 5, 16))
    router = jax.random.normal(keys[2], (16, 8))
    gate, up = (jax.random.normal(k, (8, 16, 32)) for k in keys[3:5])
    down = jax.random.normal(keys[5], (8, 32, 16))
    base, _ = moe_mlp_routed(x, router, gate, up, down, top_k=3)
    same, _ = moe_mlp_routed(x, None, gate, up, down, top_k=3,
                             logits=router_logits(x, router))
    np.testing.assert_allclose(base, same, atol=1e-6)
    routed_elsewhere, counts = moe_mlp_routed(
        x, None, gate, up, down, top_k=3,
        logits=router_logits(other, router), activation="relu")
    assert int(counts[0]) == 2 * 5 * 3
    # by hand: the experts the OTHER tensor chose, multiplied with x
    weight, chosen = jax.lax.top_k(jax.nn.softmax(
        router_logits(other, router), -1), 3)
    weight = weight / weight.sum(-1, keepdims=True)
    want = sum(
        weight[..., i, None] * jnp.einsum(
            "bsm,bsmd->bsd",
            jax.nn.relu(jnp.einsum("bsd,bsdm->bsm", x,
                                   gate[chosen[..., i]]))
            * jnp.einsum("bsd,bsdm->bsm", x, up[chosen[..., i]]),
            down[chosen[..., i]]) for i in range(3))
    np.testing.assert_allclose(routed_elsewhere, want, atol=1e-4)
    assert np.abs(np.asarray(routed_elsewhere - base)).max() > 1e-2
