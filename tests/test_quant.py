"""Int8 weight-only quantization (ops/quant.py): math bounds, einsum
equivalence, quantized-engine parity, HF-load quantization.

Reference analog: the reference's quantized serving is vLLM's
(engine_kwargs pass-through, vllm_models.py:59) and is tested there;
this framework owns the path, so the tests live here. The parity bar:
quantized logits track full-precision logits to int8 error, and the
quantized DECODE path agrees with the quantized PREFILL path exactly
(internal consistency across the two compiled code paths)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.llm.cache import init_kv_cache
from ray_tpu.llm.runner import prefill
from ray_tpu.models import LLAMA_CONFIGS, init_params
from ray_tpu.ops import rope_frequencies
from ray_tpu.ops.quant import (
    dequantize_weight, embed_lookup, init_params_quantized, is_quantized,
    quantize_params, quantize_weight, weight_einsum)

CFG = LLAMA_CONFIGS["tiny"]


def test_quantize_roundtrip_error_bound():
    w = np.random.default_rng(0).normal(size=(32, 48)).astype(np.float32)
    qw = quantize_weight(w, (0,))
    assert qw["q"].dtype == np.int8
    assert qw["s"].shape == (48,)
    deq = np.asarray(dequantize_weight(qw, (0,), np.float32))
    # symmetric rounding: per-element error <= half a quantization step
    assert np.all(np.abs(deq - w) <= qw["s"][None, :] * 0.5 + 1e-7)


def test_quantize_numpy_and_jax_agree():
    w = np.random.default_rng(1).normal(size=(4, 8, 6)).astype(np.float32)
    qn = quantize_weight(w, (1,))
    qj = quantize_weight(jnp.asarray(w), (1,))
    np.testing.assert_array_equal(qn["q"], np.asarray(qj["q"]))
    np.testing.assert_allclose(qn["s"], np.asarray(qj["s"]), rtol=1e-6)
    assert qn["s"].shape == (4, 6)


def test_weight_einsum_matches_dequant_matmul():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 5, 16)), jnp.float32)
    qw = quantize_weight(jnp.asarray(rng.normal(size=(16, 4, 8)),
                                     jnp.float32), (0,))
    got = weight_einsum("bsd,dhk->bshk", x, qw)
    want = jnp.einsum("bsd,dhk->bshk", x,
                      dequantize_weight(qw, (0,), jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # raw weights pass straight through
    w = jnp.asarray(rng.normal(size=(16, 4, 8)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(weight_einsum("bsd,dhk->bshk", x, w)),
        np.asarray(jnp.einsum("bsd,dhk->bshk", x, w)))


def test_embed_lookup_quantized_matches_dequant():
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    q = quantize_weight(table, (1,))          # per-row
    toks = jnp.asarray([[0, 5, 31], [7, 7, 2]], jnp.int32)
    got = embed_lookup(q, toks, jnp.float32)
    want = jnp.take(dequantize_weight(q, (1,), jnp.float32), toks, axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


PROMPT = [5, 17, 99, 3, 42, 7, 1, 2]


def _prefill_logits(params):
    cache = init_kv_cache(CFG, num_pages=8, page_size=4,
                          dtype=jnp.float32)
    cos, sin = rope_frequencies(CFG.head_dim, CFG.max_seq, CFG.rope_theta)
    tokens = jnp.asarray([PROMPT], jnp.int32)
    bt = jnp.asarray([[1, 2]], jnp.int32)
    logits, _, _, _ = prefill(params, cache.k, cache.v, tokens,
                           jnp.asarray([len(PROMPT)], jnp.int32), bt,
                           cos, sin, cfg=CFG)
    return np.asarray(logits[0], np.float64)


def test_quantized_prefill_logits_track_full_precision():
    params = init_params(jax.random.PRNGKey(0), CFG)
    qparams = quantize_params(params, CFG)
    assert is_quantized(qparams["embed"])
    assert is_quantized(qparams["layers"]["wq"])
    assert not is_quantized(qparams["layers"]["attn_norm"])
    full = _prefill_logits(params)
    quant = _prefill_logits(qparams)
    cos = (full @ quant) / (np.linalg.norm(full) * np.linalg.norm(quant))
    assert cos > 0.99, f"cosine {cos}"
    rel = np.linalg.norm(full - quant) / np.linalg.norm(full)
    assert rel < 0.1, f"relative error {rel}"


def test_quantized_decode_matches_quantized_prefill_oracle():
    """The engine's paged decode-burst path vs a no-cache oracle built
    from the quantized prefill path — greedy streams must be identical
    (both run the SAME quantized weights; any divergence is a paging or
    masking bug, not quantization error)."""
    params = quantize_params(init_params(jax.random.PRNGKey(0), CFG), CFG)
    n_gen = 10

    def oracle_next(tokens):
        cache = init_kv_cache(CFG, num_pages=34, page_size=4,
                              dtype=jnp.float32)
        cos, sin = rope_frequencies(CFG.head_dim, CFG.max_seq,
                                    CFG.rope_theta)
        pad = 32
        arr = np.zeros((1, pad), np.int32)
        arr[0, :len(tokens)] = tokens
        bt = jnp.asarray([list(range(1, 9))], jnp.int32)
        logits, _, _, _ = prefill(params, cache.k, cache.v,
                               jnp.asarray(arr),
                               jnp.asarray([len(tokens)], jnp.int32), bt,
                               cos, sin, cfg=CFG)
        return int(jnp.argmax(logits[0]))

    want = []
    toks = list(PROMPT)
    for _ in range(n_gen):
        nxt = oracle_next(toks)
        want.append(nxt)
        toks.append(nxt)

    engine = LLMEngine(params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64))
    got = engine.generate([PROMPT], SamplingParams(
        temperature=0.0, max_tokens=n_gen))[0]
    assert got == want


def test_init_params_quantized_structure_and_engine_smoke():
    cfg = CFG
    params = init_params_quantized(jax.random.PRNGKey(1), cfg)
    assert params["layers"]["wq"]["q"].dtype == jnp.int8
    assert params["layers"]["wq"]["q"].shape == (
        cfg.n_layers, cfg.dim, cfg.n_heads, cfg.head_dim)
    assert params["layers"]["wq"]["s"].shape == (
        cfg.n_layers, cfg.n_heads, cfg.head_dim)
    assert params["lm_head"]["s"].shape == (cfg.vocab,)
    engine = LLMEngine(params, cfg, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=32, max_seq_len=32,
        decode_burst=4))
    out = engine.generate([[1, 2, 3]], SamplingParams(
        temperature=0.0, max_tokens=6))[0]
    assert len(out) == 6
    assert all(0 <= t < cfg.vocab for t in out)


def test_llm_server_builds_seeded_int8_weights():
    """init="random" + quantize="int8" (the only 8B weights a machine
    without a checkpoint has): the server's engine is the engine built
    directly from the same seed."""
    import asyncio

    from ray_tpu.llm import LLMServer

    ecfg = dict(max_num_seqs=2, page_size=4, num_pages=32, max_seq_len=32,
                decode_burst=4)
    server = LLMServer("tiny", init="random", quantize="int8", seed=5,
                       engine_config=ecfg)
    assert is_quantized(server.engine.params["layers"]["wq"])
    out = asyncio.run(server.completions(
        {"prompt_ids": [1, 2, 3], "temperature": 0.0, "max_tokens": 6}))
    direct = LLMEngine(init_params_quantized(jax.random.PRNGKey(5), CFG),
                       CFG, EngineConfig(**ecfg))
    want = direct.generate([[1, 2, 3]], SamplingParams(
        temperature=0.0, max_tokens=6))[0]
    assert out["choices"][0]["token_ids"] == want
    with pytest.raises(ValueError, match="unknown quantize"):
        LLMServer("tiny", init="random", quantize="int4")


def test_moe_quantization_round_trip():
    """Quantized experts: int8 with per-expert per-output-channel scales,
    router left float32; the dequantized expert product lies within
    int8's error of the full-precision one (half a step a weight, summed
    over the contracted width like a random walk)."""
    cfg = dataclasses.replace(CFG, n_experts=4, top_k=2, qk_norm=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    q = quantize_params(params, cfg)["layers"]
    L, E, d, m = cfg.n_layers, 4, cfg.dim, cfg.mlp_dim
    assert q["w_gate"]["q"].shape == (L, E, d, m)
    assert q["w_gate"]["q"].dtype == jnp.int8
    assert q["w_gate"]["s"].shape == (L, E, m)
    assert q["w_down"]["s"].shape == (L, E, d)
    assert q["router"].dtype == jnp.float32 and not is_quantized(q["router"])
    assert not is_quantized(q["q_norm"])
    x = jax.random.normal(jax.random.PRNGKey(1), (8, d), jnp.float32)
    for name in ("w_gate", "w_down"):
        w = params["layers"][name][0, 1].astype(jnp.float32)   # [k, n]
        lhs = x if name == "w_gate" else jax.random.normal(
            jax.random.PRNGKey(2), (8, m), jnp.float32)
        deq = (q[name]["q"][0, 1].astype(jnp.float32)
               * q[name]["s"][0, 1][None, :])
        step = q[name]["s"][0, 1]
        assert jnp.all(jnp.abs(deq - w) <= 0.5 * step[None, :] + 1e-7)
        err = jnp.abs(lhs @ deq - lhs @ w)
        bound = 0.5 * step[None, :] * jnp.linalg.norm(
            lhs, axis=1, keepdims=True) * 4.0
        assert jnp.all(err <= bound)
    # the seeded init makes the same tree on the device
    made = init_params_quantized(jax.random.PRNGKey(0), cfg)["layers"]
    assert set(made) == set(q)
    assert made["w_up"]["q"].shape == (L, E, d, m)
    assert made["w_up"]["s"].shape == (L, E, m)
    assert made["router"].dtype == jnp.float32


def test_hf_load_quantized(tmp_path):
    from ray_tpu.models.hf_interop import (
        load_hf_checkpoint, save_hf_checkpoint)

    params = init_params(jax.random.PRNGKey(4), CFG)
    save_hf_checkpoint(params, CFG, str(tmp_path))
    qparams, qcfg = load_hf_checkpoint(str(tmp_path), quantize="int8")
    assert is_quantized(qparams["layers"]["w_down"])
    assert isinstance(qparams["layers"]["wq"]["q"], jax.Array)
    full = _prefill_logits(params)
    quant = _prefill_logits(qparams)
    rel = np.linalg.norm(full - quant) / np.linalg.norm(full)
    assert rel < 0.1
    with pytest.raises(ValueError):
        load_hf_checkpoint(str(tmp_path), quantize="int4")
