"""LLM engine: paged KV cache correctness, continuous batching, serving
(ref: vLLM's test_paged_attention / engine tests — the coverage the
reference inherits by delegating to vLLM; native here)."""

import asyncio
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm import (
    EngineConfig, LLMEngine, PageAllocator, SamplingParams)
from ray_tpu.models import LLAMA_CONFIGS, forward, init_params

CFG = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _reference_greedy(params, prompt, n_steps):
    """Greedy generation with NO cache: full forward each step."""
    tokens = list(prompt)
    for _ in range(n_steps):
        logits = forward(params, jnp.asarray([tokens], jnp.int32), CFG)
        tokens.append(int(jnp.argmax(logits[0, -1])))
    return tokens[len(prompt):]


# --- allocator unit tests ---

def test_page_allocator_reserves_dump_page():
    alloc = PageAllocator(num_pages=8, page_size=4)
    assert alloc.free_pages == 7  # page 0 reserved
    pages = alloc.allocate(7)
    assert 0 not in pages
    with pytest.raises(MemoryError):
        alloc.allocate(1)
    alloc.free(pages[:3])
    assert alloc.free_pages == 3
    with pytest.raises(ValueError):
        alloc.free([0])


def test_pages_needed_rounding():
    alloc = PageAllocator(num_pages=4, page_size=16)
    assert alloc.pages_needed(1) == 1
    assert alloc.pages_needed(16) == 1
    assert alloc.pages_needed(17) == 2


# --- paged generation vs no-cache oracle ---

@pytest.mark.slow
def test_paged_greedy_matches_full_forward(tiny_params):
    prompt = [5, 17, 99, 3, 42, 7, 1]
    n_gen = 12
    want = _reference_greedy(tiny_params, prompt, n_gen)

    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=128))
    got = engine.generate([prompt],
                          SamplingParams(temperature=0.0,
                                         max_tokens=n_gen))[0]
    assert got == want


@pytest.mark.slow
def test_paged_greedy_batch_and_page_boundaries(tiny_params):
    # prompts of different lengths; page_size 4 forces mid-generation
    # page allocation for every sequence
    prompts = [[5, 17, 99], [3, 42, 7, 1, 88, 23, 11], [2, 9]]
    n_gen = 9
    wants = [_reference_greedy(tiny_params, p, n_gen) for p in prompts]

    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=4, page_size=4, num_pages=64, max_seq_len=64))
    gots = engine.generate(prompts,
                           SamplingParams(temperature=0.0,
                                          max_tokens=n_gen))
    assert gots == wants


@pytest.mark.slow
def test_chunked_prefill_matches_oracle(tiny_params):
    """Chunked prefill (prompt processed in C-token chunks across
    engine steps) generates EXACTLY what whole-prompt prefill does —
    chunk boundaries, page boundaries and the final partial chunk must
    all be attention-exact (vLLM chunked-prefill analog)."""
    prompts = [[5, 17, 99, 3, 42, 7, 1, 88, 23, 11, 2, 9, 31],  # 13 toks
               [4, 8, 15, 16, 23]]
    n_gen = 8
    wants = [_reference_greedy(tiny_params, p, n_gen) for p in prompts]

    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        prefill_chunk=4))  # 13 tokens -> 4 chunks incl. a partial one
    gots = engine.generate(prompts,
                           SamplingParams(temperature=0.0,
                                          max_tokens=n_gen))
    assert gots == wants

    # decode really interleaves between chunks: with one long prompt
    # mid-prefill and one short already decoding, the short one streams
    engine2 = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        prefill_chunk=4, decode_burst=2))  # small bursts: the short
    # stream must still be emitting while the long prompt prefills
    greedy = SamplingParams(temperature=0.0, max_tokens=n_gen)
    r_short = engine2.add_request(prompts[1], greedy)
    engine2.step()                       # 5-token prompt: chunk 1 of 2
    engine2.step()                       # chunk 2 -> fully prefilled
    # prefill complete (ctx_len counts decoded tokens too by now)
    assert engine2.requests[r_short].ctx_len >= len(prompts[1])
    r_long = engine2.add_request(prompts[0], greedy)
    short_tokens_during_long_prefill = 0
    for _ in range(3):                   # 13 toks / chunk 4 -> 4 chunks
        outs = engine2.step()
        short_tokens_during_long_prefill += sum(
            1 for o in outs if o.request_id == r_short)
    assert short_tokens_during_long_prefill > 0
    while engine2.has_unfinished():
        engine2.step()
    assert engine2.requests[r_long].output == wants[0]
    assert engine2.requests[r_short].output == wants[1]

    # shortest-remaining-first: a short prompt admitted BEHIND a long
    # one starts streaming after its own chunk count, not the long one's
    engine3 = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        prefill_chunk=4, decode_burst=2))
    r_long3 = engine3.add_request(prompts[0], greedy)   # 4 chunks
    engine3.step()                                      # long chunk 1
    r_short3 = engine3.add_request(prompts[1], greedy)  # 2 chunks
    first_short = first_long = None
    for i in range(16):
        for o in engine3.step():
            if o.request_id == r_short3 and first_short is None:
                first_short = i
            if o.request_id == r_long3 and first_long is None:
                first_long = i
        if first_short is not None and first_long is not None:
            break
    assert first_short is not None and first_short < first_long
    while engine3.has_unfinished():
        engine3.step()
    assert engine3.requests[r_long3].output == wants[0]
    assert engine3.requests[r_short3].output == wants[1]


def test_continuous_batching_staggered_arrivals(tiny_params):
    """A request added mid-decode joins the running batch and both finish
    with oracle-exact outputs."""
    p1, p2 = [5, 17, 99, 3], [42, 7]
    n_gen = 8
    want1 = _reference_greedy(tiny_params, p1, n_gen)
    want2 = _reference_greedy(tiny_params, p2, n_gen)

    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64))
    r1 = engine.add_request(p1, SamplingParams(temperature=0.0,
                                               max_tokens=n_gen))
    # few steps solo, then the second request arrives
    for _ in range(3):
        engine.step()
    r2 = engine.add_request(p2, SamplingParams(temperature=0.0,
                                               max_tokens=n_gen))
    while engine.has_unfinished():
        engine.step()
    assert engine.requests[r1].output == want1
    assert engine.requests[r2].output == want2


def test_pages_freed_after_finish(tiny_params):
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=32, max_seq_len=32))
    free0 = engine.allocator.free_pages
    engine.generate([[1, 2, 3, 4, 5]],
                    SamplingParams(temperature=0.0, max_tokens=6))
    assert engine.allocator.free_pages == free0


def test_queueing_when_slots_full(tiny_params):
    """3 requests, 2 slots: the third waits, then runs; all finish."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64))
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    outs = engine.generate(prompts, SamplingParams(temperature=0.0,
                                                   max_tokens=5))
    wants = [_reference_greedy(tiny_params, p, 5) for p in prompts]
    assert outs == wants


def test_stop_token_and_max_tokens(tiny_params):
    prompt = [5, 17, 99, 3]
    ref = _reference_greedy(tiny_params, prompt, 10)
    stop_tok = ref[4]  # stop at the 5th generated token
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=1, page_size=4, num_pages=32, max_seq_len=64))
    rid = engine.add_request(prompt, SamplingParams(
        temperature=0.0, max_tokens=10, stop_token_ids=(stop_tok,)))
    while engine.has_unfinished():
        engine.step()
    state = engine.requests[rid]
    assert state.finish_reason == "stop"
    # generation halts at the stop token's FIRST occurrence
    assert state.output == ref[:ref.index(stop_tok) + 1]


def test_sampling_temperature_varies_output(tiny_params):
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=4, page_size=4, num_pages=64, max_seq_len=64))
    prompts = [[5, 17, 99]] * 3
    outs = engine.generate(prompts, SamplingParams(temperature=1.5,
                                                   max_tokens=12))
    # with temperature, three identical prompts should not all agree
    assert not (outs[0] == outs[1] == outs[2])


def test_top_k_one_is_greedy(tiny_params):
    prompt = [5, 17, 99, 3]
    want = _reference_greedy(tiny_params, prompt, 6)
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=1, page_size=4, num_pages=32, max_seq_len=64))
    got = engine.generate([prompt], SamplingParams(
        temperature=0.7, top_k=1, max_tokens=6))[0]
    assert got == want


def test_engine_admission_respects_page_budget(tiny_params):
    """With pages for only one sequence, the second waits until the
    first finishes, then completes correctly."""
    # 6 usable pages x page_size 4 = 24 tokens; each seq needs
    # ceil((10+1)/4)=3 pages + growth, so two can't run comfortably
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=7, max_seq_len=24))
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10],
               [11, 12, 13, 14, 15, 16, 17, 18, 19, 20]]
    outs = engine.generate(prompts, SamplingParams(temperature=0.0,
                                                   max_tokens=4))
    wants = [_reference_greedy(tiny_params, p, 4) for p in prompts]
    assert outs == wants


def test_moe_paged_decode_matches_prefill_path():
    """MoE configs serve with exact (drop-free) routing; the decode/KV
    path must produce the same greedy tokens as re-prefilling the whole
    prefix each step (teacher forcing through the prefill path)."""
    import dataclasses

    moe_cfg = dataclasses.replace(CFG, n_experts=4, top_k=2)
    params = init_params(jax.random.PRNGKey(1), moe_cfg)
    prompt = [5, 17, 99, 3, 42]
    n_gen = 8
    ecfg = dict(max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64)

    engine = LLMEngine(params, moe_cfg, EngineConfig(**ecfg))
    got = engine.generate([prompt], SamplingParams(temperature=0.0,
                                                   max_tokens=n_gen))[0]

    # oracle: every next token comes from a fresh prefill of the prefix
    # (max_tokens=1 finishes right after the prefill sample)
    oracle = LLMEngine(params, moe_cfg, EngineConfig(**ecfg))
    prefix = list(prompt)
    want = []
    for _ in range(n_gen):
        tok = oracle.generate([prefix], SamplingParams(
            temperature=0.0, max_tokens=1))[0][0]
        want.append(tok)
        prefix.append(tok)
    assert got == want


# --- serving ---

def test_llm_server_over_serve_http(tiny_params):
    ray_tpu.init(num_cpus=4)
    try:
        from ray_tpu import serve
        from ray_tpu.llm import build_llm_deployment

        app = build_llm_deployment(
            "tiny", name="llm",
            engine_config={"max_num_seqs": 2, "page_size": 4,
                           "num_pages": 64, "max_seq_len": 64})
        handle = serve.run(app)
        # direct handle call
        out = ray_tpu.get(handle.options(method_name="completions").remote(
            {"prompt_ids": [5, 17, 99, 3], "temperature": 0.0,
             "max_tokens": 5}), timeout=300)
        toks = out["choices"][0]["token_ids"]
        assert len(toks) == 5
        assert out["choices"][0]["finish_reason"] == "length"

        # HTTP: non-streaming + streaming through the proxy
        import json as _json
        import urllib.request

        port = serve.start()
        body = _json.dumps({"prompt_ids": [5, 17, 99, 3],
                            "temperature": 0.0, "max_tokens": 5}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            data = _json.loads(resp.read())
        assert data["result"]["choices"][0]["token_ids"] == toks

        # streaming: SSE-style chunks arrive incrementally
        sbody = _json.dumps({"prompt_ids": [5, 17, 99, 3],
                             "temperature": 0.0, "max_tokens": 5,
                             "stream": True}).encode()
        sreq = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm", data=sbody,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(sreq, timeout=300) as resp:
            raw = resp.read().decode()
        chunks = [_json.loads(line[len("data: "):])
                  for line in raw.strip().split("\n\n")]
        assert [c["token"] for c in chunks] == toks
        assert chunks[-1]["finished"] is True
        serve.shutdown()
    finally:
        ray_tpu.shutdown()


# --- automatic prefix caching ---

def test_prefix_cache_page_keys_chain():
    from ray_tpu.llm.cache import PrefixCache

    a = PrefixCache.page_keys(list(range(40)), 16)   # 2 full pages
    b = PrefixCache.page_keys(list(range(32)), 16)
    assert len(a) == 2 and a[:2] == b[:2]
    c = PrefixCache.page_keys([9] + list(range(1, 40)), 16)
    assert c[0] != a[0] and c[1] != a[1]   # divergence poisons the chain


def test_prefix_caching_reuses_pages_and_matches_uncached(tiny_params):
    """Second request sharing a long prefix must (a) reuse the FIRST
    request's page objects, (b) skip that prefix's prefill compute,
    (c) emit byte-identical greedy tokens to an uncached engine."""
    from ray_tpu.llm.cache import PrefixCache

    prefix = [7, 3, 9, 1] * 6                 # 24 tokens = 6 pages @ 4
    p1 = prefix + [11, 12]
    p2 = prefix + [13, 14, 15]

    plain = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64))
    want1 = plain.generate([p1], SamplingParams(temperature=0.0,
                                                max_tokens=6))[0]
    want2 = plain.generate([p2], SamplingParams(temperature=0.0,
                                                max_tokens=6))[0]

    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        enable_prefix_caching=True, prefill_chunk=8))
    got1 = engine.generate([p1], SamplingParams(temperature=0.0,
                                                max_tokens=6))[0]
    assert got1 == want1
    assert len(engine.prefix_cache) == 6      # p1's full pages published

    rid = engine.add_request(p2, SamplingParams(temperature=0.0,
                                                max_tokens=6))
    outs = []
    while engine.has_unfinished():
        outs.extend(o.token for o in engine.step()
                    if o.request_id == rid)
    assert outs == want2
    state = engine.requests[rid]
    # 6 full prefix pages were served from the cache (cap leaves >=1
    # prompt token to prefill)
    assert state.cached_tokens == 24
    # and the shared pages are refcounted, not copied
    keys = PrefixCache.page_keys(p2, 4)
    shared = [engine.prefix_cache._pages[k] for k in keys[:6]]
    assert len(set(shared)) == 6


def test_prefix_cache_eviction_reclaims_pages(tiny_params):
    """A full cache must not wedge admission: LRU cache-only pages are
    evicted to serve new sequences, and refcounts drain to empty."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=1, page_size=4, num_pages=17, max_seq_len=32,
        enable_prefix_caching=True, prefill_chunk=8))
    for i in range(4):   # distinct prompts fill the cache
        prompt = [(i * 31 + j) % 250 + 1 for j in range(14)]
        engine.generate([prompt], SamplingParams(temperature=0.0,
                                                 max_tokens=4))
    assert len(engine.prefix_cache) > 0
    # a fresh long request still admits (evicts cache pages as needed)
    out = engine.generate([[5] * 20], SamplingParams(
        temperature=0.0, max_tokens=8))[0]
    assert len(out) == 8
    # release everything: after evicting the whole cache the allocator
    # must hold zero refs (no leaked pages)
    engine.prefix_cache.evict(1 << 20)
    assert len(engine.prefix_cache) == 0
    assert not engine.allocator._refs
    assert engine.allocator.free_pages == 16


# --- multi-LoRA serving ---

def test_lora_zero_adapter_is_base_model(tiny_params):
    """Requests without a model_id (zero adapter slot) and a FRESH
    adapter (B=0 init) must both reproduce the base model exactly."""
    prompt = [5, 17, 99, 3, 42, 7, 1]
    base = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64))
    want = base.generate([prompt], SamplingParams(temperature=0.0,
                                                  max_tokens=8))[0]

    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        lora_rank=4))
    engine.add_lora("fresh")        # A random, B zero -> exact no-op
    got_base = engine.generate([prompt], SamplingParams(
        temperature=0.0, max_tokens=8))[0]
    assert got_base == want
    rid = engine.add_request(prompt, SamplingParams(temperature=0.0,
                                                    max_tokens=8),
                             model_id="fresh")
    outs = []
    while engine.has_unfinished():
        outs.extend(o.token for o in engine.step()
                    if o.request_id == rid)
    assert outs == want


def test_lora_adapter_changes_outputs_per_slot(tiny_params):
    """A NON-trivial adapter must change generations, and a mixed batch
    (base + adapter decoding together) must keep each stream equal to
    its single-request run."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.lora import init_lora_adapter

    adapter = init_lora_adapter(jax.random.PRNGKey(3), CFG, 4,
                                dtype=CFG.dtype)
    adapter["b_q"] = jax.random.normal(
        jax.random.PRNGKey(4), adapter["b_q"].shape, jnp.float32
    ).astype(CFG.dtype) * 0.3
    adapter["b_v"] = jax.random.normal(
        jax.random.PRNGKey(5), adapter["b_v"].shape, jnp.float32
    ).astype(CFG.dtype) * 0.3

    prompt_a = [5, 17, 99, 3]
    prompt_b = [7, 7, 2, 11, 13]
    g = SamplingParams(temperature=0.0, max_tokens=8)

    def run(engine_cfg_kwargs, requests):
        engine = LLMEngine(tiny_params, CFG, EngineConfig(
            max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
            lora_rank=4, **engine_cfg_kwargs))
        engine.add_lora("tuned", adapter)
        rids = [engine.add_request(p, g, model_id=m) for p, m in requests]
        out = {r: [] for r in rids}
        while engine.has_unfinished():
            for o in engine.step():
                out[o.request_id].append(o.token)
        return [out[r] for r in rids]

    solo_base = run({}, [(prompt_a, None)])[0]
    solo_tuned = run({}, [(prompt_a, "tuned")])[0]
    assert solo_tuned != solo_base          # the adapter really acts
    mixed = run({}, [(prompt_a, None), (prompt_a, "tuned")])
    assert mixed[0] == solo_base            # per-slot isolation
    assert mixed[1] == solo_tuned
    # unknown adapter rejected at submission
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        lora_rank=4))
    with pytest.raises(KeyError):
        engine.add_request(prompt_b, g, model_id="nope")


def test_lora_pool_lifecycle(tiny_params):
    from ray_tpu.llm.lora import LoRAPool, init_lora_adapter
    import jax

    pool = LoRAPool(CFG, rank=4, max_loras=2)
    a = init_lora_adapter(jax.random.PRNGKey(0), CFG, 4, dtype=CFG.dtype)
    pool.add("x", a)
    pool.add("y", a)
    with pytest.raises(RuntimeError):
        pool.add("z", a)
    pool.remove("x")
    pool.add("z", a)
    assert "z" in pool and "x" not in pool
    with pytest.raises(ValueError):
        LLMEngine(tiny_params, CFG, EngineConfig(
            max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
            lora_rank=4, enable_prefix_caching=True))


# --- where the rows go: one scatter, and rows that are not tokens ---

def _pool_of_other_rows(cfg, n_pages, page, scale=1.0):
    """K and V pools in which every page already holds something, on the
    host: the programs donate their pools, so each call gets a copy
    (``_fresh``)."""
    shape = (cfg.n_layers, n_pages, page, cfg.n_kv_heads, cfg.head_dim)
    return tuple(scale * np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), shape)) for seed in (8, 9))


def _fresh(pools):
    return tuple(jnp.asarray(np.asarray(pool)) for pool in pools)


def _with_qk_norm_gains(params):
    """QK-norm gains away from 1, so that the norm's weight matters."""
    layers = dict(params["layers"])
    for i, name in enumerate(("q_norm", "k_norm")):
        layers[name] = 1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(4 + i), layers[name].shape)
    return dict(params, layers=layers)


def _tuned_lora_rows(cfg, tuned):
    """Per-slot adapter rows (``LoRAPool.select``): slots with ``tuned``
    1 wear an adapter whose b matrices are away from 0, the rest the
    base model."""
    from ray_tpu.llm.lora import LoRAPool, init_lora_adapter

    adapter = init_lora_adapter(jax.random.PRNGKey(3), cfg, 4,
                                dtype=cfg.dtype)
    for i, name in enumerate(("b_q", "b_v")):
        adapter[name] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(4 + i), adapter[name].shape, cfg.dtype)
    pool = LoRAPool(cfg, 4, 2, dtype=cfg.dtype)
    slot = pool.add("tuned", adapter)
    return pool.select([slot if t else 0 for t in tuned])


def _write_pages_to_dump_page(cache_layer, new, block_tables, positions,
                              page_size):
    """The scatter as it was before the runner had one (PR 31), kept for
    the reference below: rows at negative positions go to dump page 0."""
    page_idx = jnp.take_along_axis(
        block_tables, jnp.maximum(positions, 0) // page_size, axis=1)
    valid = positions >= 0
    page_idx = jnp.where(valid, page_idx, 0).reshape(-1)
    offset = jnp.where(valid, positions % page_size, 0).reshape(-1)
    flat = new.reshape(-1, *new.shape[2:]).astype(cache_layer.dtype)
    return cache_layer.at[page_idx, offset].set(flat, mode="drop")


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_layer_by_layer(params, cache_k, cache_v, tokens, prompt_lens,
                            block_tables, cos, sin, lora, cfg):
    """The plain reference for ``runner.prefill``, and the program it
    replaced: the layer written out (it must not call the runner's
    block), every layer taking ITS pool through the scan and writing its
    K and V rows there, padding to dump page 0, which copies the whole
    pool to write one prompt."""
    from ray_tpu.llm import runner
    from ray_tpu.llm.lora import lora_delta
    from ray_tpu.models.llama import qk_norm
    from ray_tpu.ops import apply_rotary, attention, rms_norm
    from ray_tpu.ops.quant import embed_lookup, weight_einsum

    B, S = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    pos = jnp.arange(S)[None, :].repeat(B, 0)
    write_pos = jnp.where(pos < prompt_lens[:, None], pos, -1)
    lora_xs = {} if not lora else {
        k: jnp.swapaxes(v, 0, 1) for k, v in lora.items() if k != "scale"}
    layers, experts = runner._split_layers(params["layers"], cfg)

    def layer(x, inputs):
        lp, ck, cv, lr = inputs
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = weight_einsum("bsd,dhk->bshk", h, lp["wq"])
        k = weight_einsum("bsd,dhk->bshk", h, lp["wk"])
        v = weight_einsum("bsd,dhk->bshk", h, lp["wv"])
        if lr:
            q = q + lora_delta(h, lr["a_q"], lr["b_q"], lora["scale"],
                               cfg.n_heads, cfg.head_dim)
            v = v + lora_delta(h, lr["a_v"], lr["b_v"], lora["scale"],
                               cfg.n_kv_heads, cfg.head_dim)
        q, k = qk_norm(q, k, lp, cfg)
        q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        ck = _write_pages_to_dump_page(ck, k, block_tables, write_pos,
                                       ck.shape[1])
        cv = _write_pages_to_dump_page(cv, v, block_tables, write_pos,
                                       cv.shape[1])
        o = attention(q, k, v, causal=True)
        x = x + weight_einsum("bshk,hkd->bsd", o, lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        return x + runner._mlp(h, lp, cfg, write_pos >= 0, experts)[0], (
            ck, cv)

    x, (cache_k, cache_v) = jax.lax.scan(
        layer, x, (layers, cache_k, cache_v, lora_xs))
    last = jnp.take_along_axis(
        x, (prompt_lens - 1)[:, None, None], axis=1)[:, 0]
    return runner._head(last, params, cfg), cache_k, cache_v


@pytest.mark.parametrize("case", ["dense", "experts-qk-norm", "lora-slot"])
def test_prefill_scatters_its_rows_once_and_touches_no_other_page(
        tiny_params, case):
    """Whole-prompt prefill keeps the page pool out of its layer scan:
    the layers hand their rows out and one scatter writes them. Against
    the layer-by-layer reference, for two right-padded prompts of
    different lengths in a pool that holds other sequences' pages:
    (a) every page but the dump page is bit-equal to the reference's;
    (b) no page outside the two block tables changed, and padding rows
    are dropped outright: the dump page did not change either;
    (c) the logits are the reference's."""
    import dataclasses

    from ray_tpu.llm.runner import prefill
    from ray_tpu.ops import rope_frequencies

    cfg, params, lora = CFG, tiny_params, None
    if case == "experts-qk-norm":
        cfg = dataclasses.replace(CFG, n_experts=8, top_k=2,
                                  norm_topk_prob=False, qk_norm=True)
        params = _with_qk_norm_gains(init_params(jax.random.PRNGKey(3), cfg))
    if case == "lora-slot":
        lora = _tuned_lora_rows(cfg, [1, 0])

    page, n_pages, S = 4, 12, 16
    lens = jnp.asarray([9, 6], jnp.int32)       # 3 pages (1 row in the
    tables = jnp.asarray([[3, 5, 9, 0],         # last), 2 pages (2 rows)
                          [2, 6, 0, 0]], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, S), 0, cfg.vocab)
    before_k, before_v = _pool_of_other_rows(cfg, n_pages, page)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)

    want_logits, want_k, want_v = _prefill_layer_by_layer(
        params, jnp.asarray(before_k), jnp.asarray(before_v), tokens, lens,
        tables, cos, sin, lora, cfg=cfg)
    logits, got_k, got_v, counts = prefill(
        params, jnp.asarray(before_k), jnp.asarray(before_v), tokens, lens,
        tables, cos, sin, lora, cfg=cfg)

    mine = [2, 3, 5, 6, 9]
    others = [p for p in range(1, n_pages) if p not in mine]
    for got, want, before in ((got_k, want_k, before_k),
                              (got_v, want_v, before_v)):
        got, want = np.asarray(got), np.asarray(want)
        assert got.dtype == before.dtype
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])      # (a)
        np.testing.assert_array_equal(got[:, others], before[:, others])
        np.testing.assert_array_equal(got[:, 0], before[:, 0])      # (b)
        # the reference did write: 9 + 6 rows a layer differ from before
        changed = (got != before).any(axis=(-1, -2))      # [L, P, page]
        assert changed[:, mine].sum() == cfg.n_layers * 15
        assert (want[:, 0] != before[:, 0]).any()   # its padding: page 0
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))          # (c)
    assert (counts is None) == (case != "experts-qk-norm")
    if counts is not None:      # real tokens x top_k x layers, no padding
        assert int(counts[0]) == 15 * cfg.top_k * cfg.n_layers


@pytest.mark.parametrize("program",
                         ["prefill_chunk", "verify_step", "decode_burst"])
def test_rows_that_are_not_tokens_change_no_page(tiny_params, program):
    """The three programs that reach the cache during their step share
    the one scatter. In a pool whose every page holds another sequence's
    rows: rows that are not tokens (a chunk's tail, a short window's -1
    positions, an inactive slot) change no page, page 0 included; and
    the pages of real rows hold what whole-prompt ``prefill`` writes for
    the same tokens (which the test above holds to its reference)."""
    from ray_tpu.llm.runner import (decode_burst, prefill, prefill_chunk,
                                    verify_step)
    from ray_tpu.ops import rope_frequencies

    cfg, params, page, n_pages = CFG, tiny_params, 4, 12
    tables = jnp.asarray([[3, 5, 9, 0], [2, 6, 0, 0]], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0, cfg.vocab)
    before = _pool_of_other_rows(cfg, n_pages, page)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    zf, zi = jnp.zeros(2, jnp.float32), jnp.zeros(2, jnp.int32)

    def prefilled(tokens, lens, rows=slice(None)):
        out = prefill(params, *_fresh(before), tokens[rows],
                      jnp.asarray(lens, jnp.int32), tables[rows], cos, sin,
                      cfg=cfg)
        return np.asarray(out[1]), np.asarray(out[2])

    if program == "prefill_chunk":
        # sequence 0 alone: a whole chunk of 8, then 3 tokens and a tail
        # of 5 rows whose positions run past the table's three pages
        base, (ck, cv) = before, _fresh(before)
        for start, n in ((0, 8), (8, 3)):
            _, ck, cv, _ = prefill_chunk(
                params, ck, cv, tokens[:1, start:start + 8],
                jnp.int32(start), jnp.int32(n), tables[:1], cos, sin,
                cfg=cfg)
        want, new_rows = prefilled(tokens, [11], slice(0, 1)), 11
    else:
        base = prefilled(tokens, [9, 6])
        if program == "verify_step":
            # slot 0: a window of 2 and one -1; slot 1: no window at all
            pos = jnp.asarray([[9, 10, -1], [-1, -1, -1]], jnp.int32)
            _, _, ck, cv, _ = verify_step(
                params, *_fresh(base), tokens[:, 9:12], pos, tables, cos,
                sin, 0, zf, zi, zf + 1, cfg=cfg, greedy=True)
        else:
            # slot 0 decodes two tokens; slot 1 is inactive
            out, ck, cv, _ = decode_burst(
                params, *_fresh(base), tokens[:, 9],
                jnp.asarray([9, 6], jnp.int32),
                tables, jnp.asarray([True, False]), cos, sin, 0, zf, zi,
                zf + 1, cfg=cfg, n_steps=2, greedy=True)
            tokens = tokens.at[0, 10].set(out[0, 0])
        want, new_rows = prefilled(tokens, [11, 6]), 2

    mine = [3, 5, 9]
    others = [p for p in range(n_pages) if p not in mine]    # page 0 too
    for got, want, base in zip((ck, cv), want, base):
        got, want, base = (np.asarray(a) for a in (got, want, base))
        np.testing.assert_allclose(got[:, mine], want[:, mine], atol=1e-5)
        np.testing.assert_array_equal(got[:, others], base[:, others])
        changed = (got != base).any(axis=(-1, -2))        # [L, P, page]
        assert changed.sum() == cfg.n_layers * new_rows


@pytest.mark.parametrize("ctx, steps, noise", [
    (1, 3, False), (4, 3, False), (6, 4, False), (6, 4, True)],
    ids=["context-1", "exactly-one-page", "crosses-into-the-last-page",
         "noise-on-page-0-and-unwritten-rows"])
def test_decode_burst_at_the_edges_samples_the_full_forward_tokens(
        tiny_params, ctx, steps, noise):
    """The gather path at the contexts where a page-streaming kernel
    would break: one token of context, a context that ends with its
    page, and a burst that crosses into the table's last page. A table's
    unprovisioned slots are 0, and whatever page 0 and the rows not yet
    written hold must not leak into attention."""
    from ray_tpu.llm.runner import decode_burst, prefill
    from ray_tpu.ops import rope_frequencies

    page, n_pages = 4, 8
    prompt = [5, 17, 99, 3, 42, 7][:ctx]
    want = _reference_greedy(tiny_params, prompt, steps + 1)
    cache = _pool_of_other_rows(CFG, n_pages, page, 1e4 if noise else 1.0)
    # three pages hold ctx + steps <= 12 positions; the fourth slot is
    # unprovisioned
    tables = jnp.asarray([[6, 2, 5, 0]], jnp.int32)
    cos, sin = rope_frequencies(CFG.head_dim, CFG.max_seq, CFG.rope_theta)
    row = jnp.zeros((1, 16), jnp.int32).at[0, :ctx].set(jnp.asarray(prompt))
    params = tiny_params
    logits, ck, cv, _ = prefill(params, *_fresh(cache), row,
                                jnp.asarray([ctx], jnp.int32), tables, cos,
                                sin, cfg=CFG)
    first = jnp.argmax(logits, axis=-1)
    one = jnp.ones(1, jnp.float32)
    out, _, _, _ = decode_burst(
        params, ck, cv, first, jnp.asarray([ctx], jnp.int32), tables,
        jnp.asarray([True]), cos, sin, 0, 0 * one, jnp.zeros(1, jnp.int32),
        one, cfg=CFG, n_steps=steps, greedy=True)
    assert [int(first[0])] + np.asarray(out)[:, 0].tolist() == want


def test_decode_burst_refuses_the_deleted_paged_kernel(tiny_params):
    """``paged_kernel`` is a vestige that benchmarks/aot_fit.py still
    passes as False; asking for the kernel is an error, not a fallback."""
    from ray_tpu.llm.runner import decode_burst

    one = jnp.ones(1, jnp.float32)
    cache = _pool_of_other_rows(CFG, 4, 4)
    with pytest.raises(ValueError, match="paged kernel"):
        decode_burst(tiny_params, *_fresh(cache), jnp.zeros(1, jnp.int32),
                     jnp.ones(1, jnp.int32), jnp.asarray([[1]], jnp.int32),
                     jnp.asarray([True]), None, None, 0, one,
                     jnp.zeros(1, jnp.int32), one, cfg=CFG, n_steps=1,
                     paged_kernel=True)


def test_qk_norm_four_programs_agree_with_the_full_forward_pass():
    """One block serves the four programs: with QK-norm on (gains away
    from 1) each of them agrees with ``models.llama.forward`` on one
    prompt: whole-prompt prefill and two chunks by their logits, a
    verification window and a decode burst by the greedy tokens."""
    import dataclasses

    from ray_tpu.llm.runner import (decode_burst, prefill, prefill_chunk,
                                    verify_step)
    from ray_tpu.ops import rope_frequencies

    cfg = dataclasses.replace(CFG, qk_norm=True)
    params = _with_qk_norm_gains(init_params(jax.random.PRNGKey(3), cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, 16), 1, cfg.vocab)
    want = forward(params, tokens, cfg)[0]                 # [16, vocab]
    greedy = np.asarray(jnp.argmax(want, axis=-1))
    page = 4
    cache = _pool_of_other_rows(cfg, 8, page)
    tables = jnp.asarray([[6, 2, 5, 1]], jnp.int32)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    one, zi = jnp.ones(1, jnp.float32), jnp.zeros(1, jnp.int32)

    logits, ck, cv, _ = prefill(params, *_fresh(cache), tokens,
                                jnp.asarray([10], jnp.int32), tables, cos,
                                sin, cfg=cfg)
    np.testing.assert_allclose(logits[0], want[9], atol=1e-4)
    # positions 10..12 as a window over the prefilled pages
    tgt, _, _, _, _ = verify_step(
        params, ck + 0, cv + 0, tokens[:, 10:13],
        jnp.asarray([[10, 11, 12]], jnp.int32), tables, cos, sin, 0,
        0 * one, zi, one, cfg=cfg, greedy=True)
    assert np.asarray(tgt)[0].tolist() == greedy[10:13].tolist()
    # the same prompt in two chunks of 8, the second with 2 tokens
    chunk_k, chunk_v = _fresh(cache)
    for start, n in ((0, 8), (8, 2)):
        logits, chunk_k, chunk_v, _ = prefill_chunk(
            params, chunk_k, chunk_v, tokens[:, start:start + 8],
            jnp.int32(start), jnp.int32(n), tables, cos, sin, cfg=cfg)
    np.testing.assert_allclose(logits[0], want[9], atol=1e-4)
    # a burst of one step from position 10 predicts position 11's token
    out, _, _, _ = decode_burst(
        params, ck, cv, tokens[:, 10], jnp.asarray([10], jnp.int32), tables,
        jnp.asarray([True]), cos, sin, 0, 0 * one, zi, one, cfg=cfg,
        n_steps=1, greedy=True)
    assert int(out[0, 0]) == int(greedy[10])


# --- the burst's page list: one flat list of the live pages, or the
# rectangle; and the steps to run as an operand ---

def _flat_list(tables, ctx, page, bucket):
    """The flat page list as the engine builds it for slots with ``ctx``
    tokens of old context (0: the slot does not decode)."""
    from ray_tpu.llm.engine import burst_gather

    held = [(b, -(-n // page)) for b, n in enumerate(ctx) if n]
    return jnp.asarray(burst_gather(np.asarray(tables), page, bucket, held))


def _burst_case(case):
    """cfg, params, lora, the slots' contexts, their tables and the flat
    bucket of one case of the two tests below: three slots over pages of
    4 in a pool of 24."""
    import dataclasses

    cfg, lora = CFG, None
    ctx = [9, 6, 11]        # 3 + 2 + 3 pages hold them; 8 more tokens fit
    tables = [[3, 5, 9, 14, 18], [2, 6, 15, 17, 0], [7, 11, 12, 16, 19]]
    flat_bucket = 16
    if case == "dense-bf16":
        cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    if case == "experts-qk-norm":
        cfg = dataclasses.replace(CFG, n_experts=8, top_k=2,
                                  norm_topk_prob=False, qk_norm=True)
    if case == "shared-prefix-page":
        tables[2][0] = 3          # slots 0 and 2 share their first page
    if case == "inactive-slot-between":
        ctx[1], tables[1] = 0, [0] * 5
    if case == "list-fills-its-bucket":
        flat_bucket = 8           # 3 + 2 + 3 live pages: no padding
    params = init_params(jax.random.PRNGKey(3), cfg)
    if cfg.qk_norm:
        params = _with_qk_norm_gains(params)
    if case == "lora-slot":
        lora = _tuned_lora_rows(cfg, [1, 0, 0])
    return cfg, params, lora, ctx, jnp.asarray(tables, jnp.int32), flat_bucket


def _prefilled_burst_inputs(cfg, params, lora, ctx, tables, page=4):
    """Pools in which the slots' contexts are prefilled (every other page
    holds another sequence's rows), and the burst's first tokens."""
    from ray_tpu.llm.runner import prefill
    from ray_tpu.ops import rope_frequencies

    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (3, 16), 1, cfg.vocab)
    tokens = tokens.at[2, :4].set(tokens[0, :4])      # a prefix to share
    before = tuple(a.astype(cfg.dtype) for a in
                   _pool_of_other_rows(cfg, 24, page))
    logits, ck, cv, _ = prefill(
        params, *(jnp.asarray(a, cfg.dtype) for a in before), tokens,
        jnp.asarray(ctx, jnp.int32), tables, cos, sin, lora, cfg=cfg)
    return (cos, sin, jnp.argmax(logits, axis=-1).astype(jnp.int32),
            (np.asarray(ck), np.asarray(cv)))


@pytest.mark.parametrize("case", [
    "dense-float32", "dense-bf16", "experts-qk-norm", "lora-slot",
    "shared-prefix-page", "inactive-slot-between", "list-fills-its-bucket"])
def test_flat_page_list_decodes_what_the_rectangle_decodes(case):
    """``decode_burst`` over ONE flat list of the live pages (each with
    its owner and first position; every slot scores every listed key and
    keeps its own) against the rectangle of every slot at the longest
    span, which is what the table alone (``gather=None``, the call
    ``aot_fit.py`` makes) lowers to: the same greedy tokens; every page
    the burst does not write bit-equal, page 0 and a shared prefix page
    too; the rows it writes within the file's tolerance (a layer's rows
    are the layer below's attention, summed in another order over
    another number of keys), layer 0's bit-equal."""
    from ray_tpu.llm.runner import decode_burst

    cfg, params, lora, ctx, tables, flat_bucket = _burst_case(case)
    cos, sin, first, pools = _prefilled_burst_inputs(cfg, params, lora, ctx,
                                                     tables)
    active = jnp.asarray([n > 0 for n in ctx])
    positions = jnp.asarray(ctx, jnp.int32)
    zf, zi = jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.int32)
    flat = _flat_list(tables, ctx, 4, flat_bucket)
    assert flat.shape == (3, flat_bucket)

    def burst(block_tables, gather):
        out = decode_burst(params, *_fresh(pools), first, positions,
                           block_tables, active, cos, sin, 0, zf, zi,
                           zf + 1, lora, gather, cfg=cfg, n_steps=4,
                           greedy=True)
        return [np.asarray(a, np.float32) for a in out[:3]]

    want, got = burst(tables[:, :4], None), burst(tables, flat)
    live = np.asarray(active)
    np.testing.assert_array_equal(got[0][:, live], want[0][:, live])
    tol = 1e-5 if cfg.dtype == jnp.float32 else 0.05
    for g, w, before in zip(got[1:], want[1:], pools):
        changed = (w != np.asarray(before, np.float32)).any(axis=(-1, -2))
        assert changed.sum() == cfg.n_layers * 4 * int(live.sum())
        np.testing.assert_array_equal(g[~changed], w[~changed])
        np.testing.assert_array_equal(g[0], w[0])
        np.testing.assert_allclose(g, w, atol=tol)


@pytest.mark.parametrize("width", [1, 2, 3, 4, 5, 6, 7, 8])
def test_burst_width_is_an_operand_of_one_program(tiny_params, width):
    """One compiled ``decode_burst`` (capacity 8, a flat list) run at
    ``steps`` = width gives the tokens and the pages of the program
    compiled for that width alone (``n_steps=width``, the table's
    rectangle: the parent's call): rows past ``steps`` are dropped and
    the returned rows past it are 0."""
    from ray_tpu.llm.runner import decode_burst

    cfg, params, lora, ctx, tables, flat_bucket = _burst_case("dense-float32")
    params = tiny_params
    cos, sin, first, pools = _prefilled_burst_inputs(cfg, params, lora, ctx,
                                                     tables)
    active, positions = jnp.ones(3, bool), jnp.asarray(ctx, jnp.int32)
    zf, zi = jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.int32)
    flat = _flat_list(tables, ctx, 4, flat_bucket)

    def run(steps):
        return decode_burst(params, *_fresh(pools), first, positions, tables,
                            active, cos, sin, 0, zf, zi, zf + 1, None, flat,
                            jnp.int32(steps), cfg=cfg, n_steps=8,
                            greedy=True)

    run(9 - width)          # another width: the program is compiled here
    size = decode_burst._cache_size()
    got = run(width)
    assert decode_burst._cache_size() == size
    want = decode_burst(params, *_fresh(pools), first, positions, tables,
                        active, cos, sin, 0, zf, zi, zf + 1, cfg=cfg,
                        n_steps=width, greedy=True)
    np.testing.assert_array_equal(np.asarray(got[0])[:width],
                                  np.asarray(want[0]))
    assert not np.asarray(got[0])[width:].any()
    for g, w, before in zip(got[1:3], want[1:3], pools):
        g, w = np.asarray(g), np.asarray(w)
        changed = (g != before).any(axis=(-1, -2))
        assert changed.sum() == cfg.n_layers * width * 3
        np.testing.assert_allclose(g, w, atol=1e-5)
        np.testing.assert_array_equal(g[0], w[0])
