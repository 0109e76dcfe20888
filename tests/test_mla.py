"""Latent attention on the serving path (``LlamaConfig.latent``): one pool of rows and no V pool, the expanded form in prefill
and the absorbed form wherever the cache is read, YaRN's frequencies, a
leading dense layer under ``_layers``, and what the engine refuses with
such a cache, by name.

The size keeps the shape of the problem: hidden 64, 4 heads of 16 + 8
scoring and 16 returned, a query bottleneck of 24 and a latent of 32,
3 layers (one dense, two with 8 experts in 4 groups of which 2, 3 a
token x 4, two shared experts, 4 of the 8 experts held), pages of 4,
float32. The yardstick is a plain forward written here in numpy float64
from the published equations, with nothing of the program or of the
benchmark's family in it.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.cache import init_kv_cache
from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.llm.runner import prefill, prefill_chunk, verify_step
from ray_tpu.llm.sampling import SamplingParams
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.ops import mla, rope_frequencies
from ray_tpu.ops.attention import naive_attention
from ray_tpu.ops.quant import quantize_weight
from ray_tpu.ops.rotary import yarn_mscale

PAGE, BURST = 4, 4
YARN = dict(type="yarn", factor=4, beta_fast=32, beta_slow=1, mscale=0.707,
            mscale_all_dim=0.707, original_max_position_embeddings=32)
M = 0.1 * 0.707 * math.log(4) + 1
CFG = LlamaConfig(
    vocab=128, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, mlp_dim=32,
    max_seq=256, dtype=jnp.float32, remat=False, rope_theta=10000.0,
    norm_eps=1e-6, n_experts=8, top_k=3, norm_topk_prob=False,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=16, attn_scale=24 ** -0.5 * M * M,
    rope_scaling=tuple(sorted(YARN.items())), n_dense_layers=1,
    dense_mlp_dim=96, n_shared_experts=2, n_group=4, topk_group=2,
    routed_scale=4.0, experts_held=(2, 4))


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(5), CFG,
                       {"embed": 8.0, "wq_b": 3.0})


# ----------------------------------------------------------- the yardstick
def _yarn_inv_freq(width, theta, s):
    extra = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    inter = extra / s["factor"]

    def dim(turns):
        return (width * math.log(s["original_max_position_embeddings"]
                                 / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(s["beta_fast"])), 0)
    high = min(math.ceil(dim(s["beta_slow"])), width - 1)
    ramp = np.clip((np.arange(width // 2) - low)
                   / (high - low if high != low else 0.001), 0, 1)
    mask = 1 - ramp
    return inter * (1 - mask) + extra * mask


def _rot(x, inv_freq):
    """x [S, ..., width]: pairs (i, i + width / 2), position = row."""
    half = x.shape[-1] // 2
    angle = np.arange(x.shape[0])[:, None] * inv_freq[None, :]
    angle = angle.reshape(x.shape[0], *(1,) * (x.ndim - 2), half)
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * np.cos(angle) - x2 * np.sin(angle),
                           x2 * np.cos(angle) + x1 * np.sin(angle)], -1)


def _rms(x, w, eps=1e-6):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _softmax(s):
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _silu(x):
    return x / (1 + np.exp(-x))


def _swiglu(g, gate, up, down):
    return (_silu(g @ gate) * (g @ up)) @ down


def _latent_attention(h, lp, cfg):
    S, nope = h.shape[0], cfg.qk_nope_dim
    inv_freq = _yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta,
                              dict(cfg.rope_scaling))
    c_q = _rms(h @ lp["wq_a"], lp["q_a_norm"])
    q = (c_q @ lp["wq_b"]).reshape(S, cfg.n_heads, -1)
    q = np.concatenate([q[..., :nope], _rot(q[..., nope:], inv_freq)], -1)
    kv = h @ lp["wkv_a"]
    c_kv = _rms(kv[:, :cfg.kv_lora_rank], lp["kv_a_norm"])
    k_r = _rot(kv[:, cfg.kv_lora_rank:], inv_freq)
    k = np.concatenate([np.einsum("sc,chk->shk", c_kv, lp["w_uk"]),
                        np.repeat(k_r[:, None], cfg.n_heads, 1)], -1)
    v = np.einsum("sc,chk->shk", c_kv, lp["w_uv"])
    scores = np.einsum("qhk,shk->hqs", q, k) * cfg.softmax_scale
    scores = np.where(np.tril(np.ones((S, S), bool))[None], scores, -np.inf)
    return np.einsum("hqs,shk->qhk", _softmax(scores), v).reshape(
        S, -1) @ lp["wo"].reshape(-1, cfg.dim)


def _gqa_attention(h, lp, cfg):
    S, rep = h.shape[0], cfg.n_heads // cfg.n_kv_heads
    inv_freq = cfg.rope_theta ** (
        -np.arange(0, cfg.head_dim, 2, dtype=np.float64) / cfg.head_dim)
    q = _rot(np.einsum("sd,dhk->shk", h, lp["wq"]), inv_freq)
    k = _rot(np.einsum("sd,dhk->shk", h, lp["wk"]), inv_freq)
    v = np.einsum("sd,dhk->shk", h, lp["wv"])
    k, v = np.repeat(k, rep, 1), np.repeat(v, rep, 1)
    scores = np.einsum("qhk,shk->hqs", q, k) * cfg.head_dim ** -0.5
    scores = np.where(np.tril(np.ones((S, S), bool))[None], scores, -np.inf)
    return np.einsum("hqs,shk->qhk", _softmax(scores), v).reshape(
        S, -1) @ lp["wo"].reshape(-1, cfg.dim)


def _experts(g, lp, cfg, held=None, shared=True):
    """The expert layer by a plain loop over tokens: the group-limited
    choice, the chosen probabilities x the factor, only the experts in
    ``held`` (first, count) computed; the shared experts for every
    token."""
    first, count = held or (0, cfg.n_experts)
    out = np.zeros_like(g)
    for t, row in enumerate(g):
        p = _softmax(row @ lp["router"])
        groups = p.reshape(cfg.n_group, -1)
        best = np.argsort(-groups.max(-1), kind="stable")[:cfg.topk_group]
        kept = np.zeros_like(groups)
        kept[best] = groups[best]
        kept = kept.reshape(-1)
        for e in np.argsort(-kept, kind="stable")[:cfg.top_k]:
            if first <= e < first + count:
                w = [lp[n][e - first] for n in ("w_gate", "w_up", "w_down")]
                out[t] += kept[e] * cfg.routed_scale * _swiglu(row, *w)
    if shared and cfg.n_shared_experts:
        out += _swiglu(g, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out


def plain_forward(params, tokens, cfg):
    """float64 logits [S, vocab] of one sequence."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    x = p["embed"][np.asarray(tokens)]
    for i in range(cfg.n_layers):
        dense = i < cfg.n_dense_layers
        stack, at = (p["dense_layers"], i) if dense else \
            (p["layers"], i - cfg.n_dense_layers)
        lp = jax.tree.map(lambda a: a[at], stack)
        h = _rms(x, lp["attn_norm"])
        x = x + (_latent_attention if cfg.latent else _gqa_attention)(
            h, lp, cfg)
        g = _rms(x, lp["mlp_norm"])
        if dense or not cfg.n_experts:
            x = x + _swiglu(g, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            x = x + _experts(g, lp, cfg, cfg.experts_held)
    return _rms(x, p["final_norm"]) @ p["lm_head"]


def _prompt(n, seed=0, vocab=CFG.vocab):
    return [int(t) for t in np.random.default_rng(seed).integers(1, vocab, n)]


def _engine(params, cfg=CFG, slots=3, chunk=0, **more):
    return LLMEngine(params, cfg, EngineConfig(
        max_num_seqs=slots, page_size=PAGE, num_pages=1 + slots * 32,
        max_seq_len=128, decode_burst=BURST, prefill_chunk=chunk, **more))


# ------------------------------------------------------------------ the ops
def test_yarn_table_and_softmax_scale_are_the_published_formulas():
    cos, sin = rope_frequencies(64, 4096, 10000.0, scaling=dict(
        type="yarn", factor=40, beta_fast=32, beta_slow=1, mscale=0.707,
        mscale_all_dim=0.707, original_max_position_embeddings=4096))
    inv_freq = _yarn_inv_freq(64, 10000.0, dict(
        factor=40, beta_fast=32, beta_slow=1,
        original_max_position_embeddings=4096))
    angle = np.arange(4096)[:, None] * inv_freq[None, :]
    # mscale(40, 0.707) / mscale(40, 0.707) = 1 on the tables
    np.testing.assert_allclose(np.asarray(cos), np.cos(angle), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin), np.sin(angle), atol=2e-3)
    # the fastest pairs keep their frequency, the slowest are slowed 40x
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert inv_freq[0] == plain[0] and inv_freq[-1] == plain[-1] / 40
    m = yarn_mscale(40, 0.707)
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert 192 ** -0.5 * m * m == pytest.approx(0.114721, abs=1e-6)
    assert yarn_mscale(1.0, 0.707) == 1.0
    # without scaling the tables are what they were
    c0, _ = rope_frequencies(64, 16, 10000.0)
    np.testing.assert_allclose(
        np.asarray(c0), np.cos(np.arange(16)[:, None] * plain[None]),
        atol=1e-6)
    with pytest.raises(ValueError, match="only 'yarn'"):
        rope_frequencies(64, 16, 10000.0, scaling={"type": "linear"})


@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
def test_the_absorbed_form_equals_the_expanded_form(quantized):
    """Scores over the cached rows as stored, W_UK moved onto the query
    (an int8 W_UK's scales multiplying the query first), W_UV applied
    once a query: the same attention as every head's keys and values
    multiplied out."""
    rank, rope, nope, vd, h, S = 32, 8, 16, 16, 4, 21
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    rows = jax.random.normal(ks[0], (2, S, 128), jnp.float32).at[
        ..., rank + rope:].set(0.0)
    q = jax.random.normal(ks[1], (2, S, h, nope + rope), jnp.float32)
    w_uk = jax.random.normal(ks[2], (rank, h, nope), jnp.float32) * 0.2
    w_uv = jax.random.normal(ks[3], (rank, h, vd), jnp.float32) * 0.2
    if quantized:
        w_uk, w_uv = (quantize_weight(w, (0,)) for w in (w_uk, w_uv))
    k, v = mla.expand(rows, w_uk, w_uv, h, rope)
    want = naive_attention(q, k, v, causal=True, scale=0.3)
    causal = jnp.tril(jnp.ones((S, S), bool))[None]
    got = mla.expand_output(mla.attend_rows(
        mla.absorb_query(q, w_uk, 128), 0.3, rank, (rows, causal)), w_uv)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # in two segments (cached rows; rows the cache does not hold yet)
    # and as one query a slot joined by the log-sum-exp
    ql = mla.absorb_query(q[:, -1], w_uk, 128)                 # [2, h, row]
    pool = jnp.zeros((1, 8, 4, 128)).at[0, 1:6].set(
        rows[0, :20].reshape(5, 4, 128))
    tables = jnp.asarray([[1, 2, 3, 4, 5, 0, 0, 0]] * 2, jnp.int32)
    o, lse = mla.decode_attention(ql[:1].repeat(2, 0), pool, jnp.int32(0),
                                  tables, jnp.asarray([20, 0], jnp.int32),
                                  scale=0.3, rank=rank)
    joined = mla.join_new_rows(
        o, lse, ql[:1].repeat(2, 0), rows[:1, 20:].repeat(2, 0),
        jnp.ones((1, 1), bool), scale=0.3, rank=rank)
    np.testing.assert_allclose(mla.expand_output(joined[0], w_uv),
                               want[0, -1], atol=2e-5)
    # a slot with nothing cached sees the new row alone
    alone = naive_attention(q[:1, -1:], k[:1, -1:], v[:1, -1:], scale=0.3)
    np.testing.assert_allclose(mla.expand_output(joined[1], w_uv),
                               alone[0, 0], atol=2e-5)


@pytest.mark.parametrize("lengths", [(37, 0, 128), (1, 64, 5)])
def test_the_decode_kernel_walks_each_slots_own_pages(lengths):
    """The Pallas kernel (interpreted: the kernel's body on the CPU)
    against the gathered rectangle: pages in any order, a slot with
    nothing cached, a slot that fills its table, lengths inside a page."""
    L, P, page, W, rank, B, h = 2, 40, 8, 128, 32, 3, 4
    pool = jax.random.normal(jax.random.PRNGKey(0), (L, P, page, W))
    ql = jax.random.normal(jax.random.PRNGKey(1), (B, h, W))
    tables = jnp.asarray(np.random.RandomState(0).randint(1, P, (B, 16)),
                         jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    want = mla.decode_attention_xla(ql, pool, jnp.int32(1), tables, lengths,
                                    scale=0.2, rank=rank)
    got = mla.decode_attention_tpu(ql, pool, jnp.int32(1), tables, lengths,
                                   scale=0.2, rank=rank, interpret=True)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5)
    assert not np.asarray(got[0])[np.asarray(lengths) == 0].any()


def test_one_pool_of_rows_and_no_v_pool():
    cache = init_kv_cache(CFG, 17, PAGE)
    assert cache.v is None
    assert cache.k.shape == (CFG.n_layers, 17, PAGE, CFG.latent_row)
    assert (cache.num_pages, cache.page_size) == (17, PAGE)
    assert (CFG.latent_dim, CFG.latent_row) == (40, 128)
    published = dataclasses.replace(CFG, kv_lora_rank=512, qk_rope_dim=64)
    assert (published.latent_dim, published.latent_row) == (576, 640)
    assert (CFG.head_dim, CFG.value_dim, CFG.rope_dim) == (24, 16, 8)
    # a configuration of heads has its two pools, as it always had
    plain = LlamaConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, mlp_dim=64, max_seq=64)
    both = init_kv_cache(plain, 5, PAGE)
    assert both.k.shape == both.v.shape == (2, 5, PAGE, 2, 8)
    assert CFG.latent and not plain.latent      # by kv_lora_rank alone
    with pytest.raises(ValueError, match="needs q_lora_rank"):
        LlamaConfig(kv_lora_rank=32)
    with pytest.raises(ValueError, match="needs q_lora_rank"):
        LlamaConfig(q_lora_rank=24, qk_nope_dim=16)


# ------------------------------------------------------- through the pages
def _next_logits_through_the_pages(engine, state):
    """Logits of the next position read through the engine's pages as
    they are: ``prefill_chunk`` of the one token the next decode step
    would take."""
    engine._provision_pages(state, state.ctx_len + 1)
    table = jnp.asarray(
        engine.seq_table.block_tables[state.slot:state.slot + 1])
    tokens = np.zeros((1, 4), np.int32)
    tokens[0, 0] = state.output[-1]
    logits, ck, cv, _ = prefill_chunk(
        engine.params, engine.cache.k, engine.cache.v, jnp.asarray(tokens),
        jnp.int32(state.ctx_len), jnp.int32(1), table, engine.cos,
        engine.sin, cfg=engine.cfg)
    assert cv is None
    engine.cache = type(engine.cache)(ck, cv)
    return np.asarray(logits)[0]


@pytest.mark.parametrize("company", ["alone", "with-a-short-one"])
def test_prefill_then_decode_through_the_latent_pages_is_the_plain_forward(
        params, company):
    """A prompt of 37 tokens decodes 30 more through the one pool: every
    token is the plain forward's first choice given the tokens before
    it, and the logits read back through the pages half way and at the
    end are the plain forward's to 1e-4."""
    engine = _engine(params)
    assert engine.cache.v is None
    long_id = engine.add_request(_prompt(37, 1), SamplingParams(
        temperature=0.0, max_tokens=30))
    short_id = None
    if company != "alone":
        short_id = engine.add_request(_prompt(9, 2), SamplingParams(
            temperature=0.0, max_tokens=14))
    state = engine.requests[long_id]
    probed = []
    while not state.finished:
        engine.step()
        if state.slot >= 0 and len(state.output) in (13, 14, 15, 16, 25, 26,
                                                     27, 28) \
                and len(probed) < 2 and (
                    not probed or len(state.output) > 22):
            seq = state.prompt + state.output
            assert state.ctx_len == len(seq) - 1
            got = _next_logits_through_the_pages(engine, state)
            np.testing.assert_allclose(
                got, plain_forward(params, seq, CFG)[-1], atol=1e-4)
            probed.append(len(seq))
    while engine.has_unfinished():
        engine.step()
    assert len(probed) == 2 and len(state.output) == 30
    for rid in filter(None, (long_id, short_id)):
        s = engine.requests[rid]
        seq = s.prompt + s.output
        want = plain_forward(params, seq, CFG)[len(s.prompt) - 1:-1]
        assert s.output == want.argmax(-1).tolist()
    counters = engine.stats()["counters"]
    assert counters["expert_rows_elsewhere"] > 0 < counters["expert_rows"]
    # one row of latent_row float32 values a layer
    assert counters["kv_bytes_per_token"] == 3 * 128 * 4
    assert engine.allocator.free_pages == engine.allocator.num_pages - 1


def _table(length):
    table = np.zeros((1, 32), np.int32)
    n = -(-length // PAGE)
    table[0, :n] = 1 + np.arange(n)
    return jnp.asarray(table)


def _whole_prompt(params, tokens, cfg=CFG):
    cos, sin = rope_frequencies(cfg.rope_dim, cfg.max_seq, cfg.rope_theta,
                                scaling=cfg.rope_scaling)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :len(tokens)] = tokens
    cache = init_kv_cache(cfg, 40, PAGE)
    logits, ck, cv, counts = prefill(
        params, cache.k, cache.v, jnp.asarray(padded),
        jnp.asarray([len(tokens)], jnp.int32), _table(len(tokens)), cos,
        sin, cfg=cfg)
    return np.asarray(logits)[0], (ck, cv), (cos, sin), counts


def test_chunks_and_a_verify_window_agree_with_whole_prompt_prefill(params):
    tokens = _prompt(45, 3)
    want = plain_forward(params, tokens, CFG)
    whole, (ck, cv), (cos, sin), counts = _whole_prompt(params, tokens)
    assert cv is None and counts.shape == (3,)
    np.testing.assert_allclose(whole, want[-1], atol=1e-4)
    # page 0 stays as it was made: nothing writes to it
    assert not np.asarray(ck[:, 0]).any()

    # eight-token chunks through the pages
    cache = init_kv_cache(CFG, 40, PAGE)
    ck2, cv2 = cache.k, cache.v
    for start in range(0, 45, 8):
        n = min(8, 45 - start)
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :n] = tokens[start:start + n]
        logits, ck2, cv2, _ = prefill_chunk(
            params, ck2, cv2, jnp.asarray(chunk), jnp.int32(start),
            jnp.int32(n), _table(45), cos, sin, cfg=CFG)
    np.testing.assert_allclose(np.asarray(logits)[0], whole, atol=1e-4)
    # the chunks left the same rows in the pages as the whole prompt did
    np.testing.assert_allclose(np.asarray(ck2[:, 1:12]).reshape(3, 44, -1),
                               np.asarray(ck[:, 1:12]).reshape(3, 44, -1),
                               atol=1e-5)

    # a verify window of 5 over the pages whole-prompt prefill left for
    # the first 40 tokens
    _, (ck, cv), _, _ = _whole_prompt(params, tokens[:40])
    table = np.array(_table(40))
    table[0, 10:12] = (30, 31)              # pages for positions 40..44
    window = np.asarray(tokens[40:45], np.int32)[None]
    positions = np.arange(40, 45, dtype=np.int32)[None]
    one = jnp.ones(1, jnp.float32)
    tgt, _, _, cv, _ = verify_step(
        params, ck, cv, jnp.asarray(window), jnp.asarray(positions),
        jnp.asarray(table), cos, sin, 0, one, jnp.zeros(1, jnp.int32), one,
        cfg=CFG, greedy=True)
    assert cv is None
    assert np.asarray(tgt)[0].tolist() == want[40:45].argmax(-1).tolist()


def test_a_prefix_cache_hit_over_latent_pages_gives_the_same_tokens(params):
    """The second request shares its first 32 tokens (8 pages) with the
    first: their rows are not computed again, and its answer is what it
    is without the cache."""
    shared = _prompt(32, 7)
    first, second = shared + _prompt(5, 8), shared + _prompt(9, 9)
    sampling = SamplingParams(temperature=0.0, max_tokens=10)
    cached = _engine(params, chunk=8, enable_prefix_caching=True)
    out_first = cached.generate([first], sampling)[0]
    rid = cached.add_request(second, sampling)
    while cached.has_unfinished():
        cached.step()
    state = cached.requests[rid]
    assert state.cached_tokens == 32
    fresh = _engine(params)
    assert fresh.generate([first], sampling)[0] == out_first
    assert fresh.generate([second], sampling)[0] == state.output
    want = plain_forward(params, second + state.output, CFG)[
        len(second) - 1:-1]
    assert state.output == want.argmax(-1).tolist()


@pytest.mark.parametrize("field, other", [
    ("attn_scale", 24 ** -0.5), ("rope_scaling", None),
    ("n_group", 1), ("routed_scale", 1.0), ("n_shared_experts", 0),
    ("experts_held", (0, 4))])
def test_controls_that_must_differ(params, field, other):
    """The yardstick bites: the softmax scale without YaRN's m^2, plain
    rotary for YaRN, ungrouped top-3, an unscaled router, no shared
    expert and another chip's experts each move the program's logits
    away from the plain forward's."""
    tokens = _prompt(45, 3)
    want = plain_forward(params, tokens, CFG)[-1]
    got = _whole_prompt(params, tokens,
                        dataclasses.replace(CFG, **{field: other}))[0]
    assert np.abs(got - want).max() > 1e-2


# ------------------------------------------------ a leading dense layer
@pytest.mark.parametrize("experts", [4, 0], ids=["experts", "refused"])
def test_a_leading_dense_layer_under_layers_with_heads(experts):
    """``n_dense_layers`` is not latent attention's: a configuration of
    GQA heads whose first layer is dense and whose others route runs
    the same ``_layers``, and the cache's first layer is the dense
    one's."""
    kw = dict(vocab=96, dim=32, n_layers=3, n_heads=4, n_kv_heads=2,
              mlp_dim=24, max_seq=128, dtype=jnp.float32, remat=False,
              rope_theta=10000.0, norm_eps=1e-6, n_experts=experts, top_k=2,
              norm_topk_prob=False, n_dense_layers=1, dense_mlp_dim=40)
    if not experts:
        with pytest.raises(ValueError, match="n_dense_layers"):
            LlamaConfig(**kw)
        return
    cfg = LlamaConfig(**kw)
    params = init_params(jax.random.PRNGKey(2), cfg)
    assert params["dense_layers"]["w_gate"].shape == (1, 32, 40)
    assert params["layers"]["w_gate"].shape == (2, 4, 32, 24)
    tokens = _prompt(21, 4, 96)
    engine = _engine(params, cfg)
    out = engine.generate([tokens], SamplingParams(temperature=0.0,
                                                   max_tokens=9))[0]
    want = plain_forward(params, tokens + out, cfg)[len(tokens) - 1:-1]
    assert out == want.argmax(-1).tolist()
    assert engine.cache.k.shape[0] == 3 and engine.cache.v is not None


# ----------------------------------------------------- refused, by name
@pytest.mark.parametrize("option, asked, why", [
    ("lora_rank", {"lora_rank": 4}, "deltas on wq and wv"),
    ("speculation", {"speculation": {"draft_config": "tiny",
                                     "num_draft_tokens": 2}},
     "the drafter mirrors a K and a V pool"),
])
def test_what_a_latent_cache_cannot_do_yet_is_refused_by_name(
        params, option, asked, why):
    with pytest.raises(ValueError) as refused:
        _engine(params, **asked)
    assert f"EngineConfig.{option}" in str(refused.value)
    assert "latent attention" in str(refused.value)
    assert why in str(refused.value)


@pytest.mark.parametrize("what", ["export_kv_request", "snapshot_kv_request",
                                  "inject_request"])
def test_kv_hand_over_of_latent_pages_is_refused_by_name(params, what):
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
    assert "one pool of rows with no V" in str(refused.value)


def test_the_training_forward_and_adapters_refuse_latent_attention(params):
    from ray_tpu.models.llama import forward

    with pytest.raises(ValueError, match="latent attention"):
        forward(params, jnp.zeros((1, 8), jnp.int32), CFG)


def test_whole_prompt_prefill_takes_one_latent_prompt_at_a_time(params):
    """The engine prefills B = 1; the rows go to their pages a page at
    a time (``_write_latent_pages``), which is written for one prompt."""
    cos, sin = rope_frequencies(CFG.rope_dim, CFG.max_seq, CFG.rope_theta,
                                scaling=CFG.rope_scaling)
    cache = init_kv_cache(CFG, 40, PAGE)
    with pytest.raises(ValueError, match="B == 1, not 2"):
        prefill(params, cache.k, cache.v, jnp.zeros((2, 16), jnp.int32),
                jnp.asarray([5, 9], jnp.int32),
                jnp.concatenate([_table(5), _table(9)]), cos, sin, cfg=CFG)


def test_the_engine_says_which_attention_each_program_takes(params, caplog):
    import logging

    with caplog.at_level(logging.INFO, logger="ray_tpu.llm.engine"):
        engine = _engine(params)
    paths = engine.attention_paths()
    assert paths["prefill"] == "blockwise (expanded)"      # the CPU's
    assert "absorbed" in paths["decode_burst"]
    assert any("attention paths" in r.getMessage() for r in caplog.records)
    from ray_tpu.ops.attention import attention_path

    # the latent widths take the flash kernel on a TPU, said here; what
    # a program was compiled to is read in tests/test_tpu_compile.py
    assert attention_path(16384, 16384, 192, True, 128) == "pallas"
    assert attention_path(16384, 16384, 192, True) == "blockwise"
    assert attention_path(16384, 16384, 128, True, 128) == "pallas"
    assert attention_path(16384, 16384, 192, False, 128) == "blockwise"
