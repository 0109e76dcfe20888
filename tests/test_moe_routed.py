"""The serving path's one expert layer (``ops.moe.moe_mlp_routed``):
dropless, blind to rows that are not tokens, int8 experts read as int8;
QK-norm; the engine's expert counters.

The contract (ISSUE 28): a sequence's logits do not depend on what else
is in the batch, padding and inactive slots enter no expert group, and
the layer agrees with the all-experts oracle for a renormalising and an
unnormalised router, with raw and with quantized experts."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.llm.cache import init_kv_cache
from ray_tpu.llm.runner import prefill, prefill_chunk
from ray_tpu.models import LLAMA_CONFIGS, init_params
from ray_tpu.models.llama import qk_norm
from ray_tpu.ops import (apply_rotary, naive_attention, rms_norm,
                         rope_frequencies)
from ray_tpu.ops import moe
from ray_tpu.ops.quant import (dequantize_weight, init_params_quantized,
                               quantize_weight)

B, S, D, M, E, K = 3, 8, 64, 32, 8, 2


def _layer_weights(seed=0, quantized=False):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (B, S, D), jnp.float32)
    router = jax.random.normal(k[1], (D, E), jnp.float32) * D ** -0.5
    raw = [jax.random.normal(k[2], (E, D, M)) * D ** -0.5,
           jax.random.normal(k[3], (E, D, M)) * D ** -0.5,
           jax.random.normal(k[4], (E, M, D)) * M ** -0.5]
    if not quantized:
        return x, router, raw, raw
    served = [quantize_weight(w, (1,)) for w in raw]
    plain = [dequantize_weight(w, (1,), jnp.float32) for w in served]
    return x, router, served, plain


@pytest.mark.parametrize("norm_topk_prob", [True, False])
@pytest.mark.parametrize("quantized", [False, True])
def test_routed_layer_matches_oracle_and_ignores_rows_that_are_no_tokens(
        norm_topk_prob, quantized):
    x, router, served, plain = _layer_weights(0, quantized)
    routed = jax.jit(functools.partial(
        moe.moe_mlp_routed, top_k=K, norm_topk_prob=norm_topk_prob))
    want = jax.jit(functools.partial(
        moe.moe_mlp_oracle, top_k=K, norm_topk_prob=norm_topk_prob))(
            x, router, *plain)
    got, counts = routed(x, router, *served)
    # float32 on both sides: summation order alone differs
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert counts.tolist() == [B * S * K, E, 0]     # none is elsewhere
    # padding at the end of two rows, and one row that is no sequence at
    # all: the real rows' outputs do not move, the others' are zero
    valid = jnp.arange(S)[None, :] < jnp.array([5, S, 0])[:, None]
    masked, counts = routed(x, router, *served, valid=valid)
    np.testing.assert_array_equal(
        np.asarray(masked)[np.asarray(valid)],
        np.asarray(got)[np.asarray(valid)])
    assert not np.asarray(masked)[~np.asarray(valid)].any()
    assert int(counts[0]) == (5 + S) * K          # no row for a non-token


def test_oracle_unnormalised_weights_are_the_router_probabilities():
    x, router, raw, _ = _layer_weights(1)
    one = x[:1, :1]
    probs = jax.nn.softmax(one.reshape(1, D) @ router, -1)[0]
    top = jnp.sort(probs)[-K:].sum()
    unnorm = moe.moe_mlp_oracle(one, router, *raw, top_k=K,
                                norm_topk_prob=False)
    norm = moe.moe_mlp_oracle(one, router, *raw, top_k=K)
    np.testing.assert_allclose(unnorm, norm * top, rtol=1e-5, atol=1e-6)


# widths and rows of the kernel's interpret-mode cases: today's one tile,
# widths that are no powers of two in both orders (whole-width tiles of
# 384 and 640), and SmallThinker's own expert at a decode step's rows
GROUPS = [100, 0, 130, 17, 0, 60, 1, 150]
GMM_CASES = {
    "128x128": (512, 128, 128, GROUPS),
    "384x640": (512, 384, 640, GROUPS),
    "640x384": (512, 640, 384, GROUPS),
    "2560x768-decode": (48, 2560, 768, [3, 0, 1, 20, 0, 7, 1, 9]),
}


# --- group-limited choice, the scaling factor, shared experts, and one
# chip's share of the experts (DeepSeek-V2's expert layer) ---
GROUPS, KEPT, FACTOR = 4, 1, 16.0


def _plain_layer(x, router, weights, *, held=(0, E), shared=None,
                 n_group=GROUPS, topk_group=KEPT, scale=FACTOR, top_k=K):
    """The layer by a plain loop over tokens in float64: a group's score
    is its best expert's probability, every expert outside the best
    ``topk_group`` groups is given 0, the ``top_k`` largest of what is
    left are chosen with weights p x ``scale``; only the experts ``held``
    (first, count) are computed, and ``shared`` once for every token."""
    silu = lambda a: a / (1 + np.exp(-a))                      # noqa: E731
    x = np.asarray(x, np.float64).reshape(-1, D)
    w = [np.asarray(a, np.float64) for a in weights]
    first, count = held
    out = np.zeros_like(x)
    for t, row in enumerate(x):
        z = row @ np.asarray(router, np.float64)
        p = np.exp(z - z.max())
        p /= p.sum()
        groups = p.reshape(n_group, -1)
        best = np.argsort(-groups.max(-1), kind="stable")[:topk_group]
        kept = np.zeros_like(groups)
        kept[best] = groups[best]
        kept = kept.reshape(-1)
        for e in np.argsort(-kept, kind="stable")[:top_k]:
            if first <= e < first + count:
                i = e - first
                out[t] += kept[e] * scale * (
                    (silu(row @ w[0][i]) * (row @ w[1][i])) @ w[2][i])
    if shared is not None:
        g, u, d = (np.asarray(a, np.float64) for a in shared)
        out += (silu(x @ g) * (x @ u)) @ d
    return out.reshape(B, S, D)


def _shared_weights(seed=9, width=2 * M):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (D, width)) * D ** -0.5,
            jax.random.normal(k[1], (D, width)) * D ** -0.5,
            jax.random.normal(k[2], (width, D)) * width ** -0.5)


def test_group_limited_choice_the_factor_and_the_shared_expert():
    x, router, served, _ = _layer_weights(4)
    shared = _shared_weights()
    grouped = functools.partial(
        moe.moe_mlp_routed, top_k=K, norm_topk_prob=False, n_group=GROUPS,
        topk_group=KEPT, scale=FACTOR)
    got, counts = jax.jit(functools.partial(grouped, shared=shared))(
        x, router, *served)
    np.testing.assert_allclose(
        got, _plain_layer(x, router, served, shared=shared), atol=2e-4)
    assert counts.tolist()[::2] == [B * S * K, 0]
    # the group limit changes choices here: ungrouped top-k differs
    plain, _ = jax.jit(functools.partial(
        moe.moe_mlp_routed, top_k=K, norm_topk_prob=False, scale=FACTOR))(
            x, router, *served)
    routed_only, _ = jax.jit(grouped)(x, router, *served)
    assert np.abs(np.asarray(plain) - np.asarray(routed_only)).max() > 1e-2
    # every chosen expert lies in a token's best group
    probs = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", x, router).reshape(-1, E), -1)
    weight, chosen = moe.route(probs, K, norm_topk_prob=False,
                               n_group=GROUPS, topk_group=KEPT,
                               scale=FACTOR)
    best = np.argsort(-np.asarray(probs).reshape(-1, GROUPS, E // GROUPS)
                      .max(-1), -1, kind="stable")[:, :KEPT]
    assert all(c // (E // GROUPS) in best[t]
               for t, row in enumerate(np.asarray(chosen)) for c in row)
    np.testing.assert_allclose(
        weight, FACTOR * np.take_along_axis(np.asarray(probs),
                                            np.asarray(chosen), -1),
        rtol=1e-6)
    # renormalised first, then the factor
    normed, _ = moe.route(probs, K, norm_topk_prob=True, n_group=GROUPS,
                          topk_group=KEPT, scale=FACTOR)
    np.testing.assert_allclose(normed.sum(-1), FACTOR, rtol=1e-5)


@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer(quantized):
    """The share tied to the model. Four chips hold two experts each of
    the eight; every chip routes over all eight and computes its own
    experts' part; the shared expert is on every chip. The routed parts
    of the four shares, and the shared expert counted ONCE, are the
    uncut layer; every (token, expert) row is given to exactly one
    chip's experts."""
    x, router, served, plain = _layer_weights(5, quantized)
    shared = _shared_weights()
    layer = functools.partial(
        moe.moe_mlp_routed, top_k=K, norm_topk_prob=False, n_group=GROUPS,
        topk_group=KEPT, scale=FACTOR)
    whole, whole_counts = jax.jit(functools.partial(layer, shared=shared))(
        x, router, *served)
    np.testing.assert_allclose(
        whole, _plain_layer(x, router, plain, shared=shared), atol=5e-4)
    per = E // 4
    parts, given, elsewhere = [], 0, []
    for chip in range(4):
        held = (chip * per, per)
        mine = [jax.tree.map(lambda a: a[held[0]:held[0] + per], w)
                for w in served]
        part, counts = jax.jit(functools.partial(layer, held=held))(
            x, router, *mine)
        np.testing.assert_allclose(
            part, _plain_layer(x, router, [
                np.asarray(w)[held[0]:held[0] + per] for w in plain],
                held=held), atol=5e-4)
        parts.append(np.asarray(part, np.float64))
        given += int(counts[0])
        elsewhere.append(int(counts[2]))
        assert int(counts[0]) + int(counts[2]) == B * S * K
        assert int(counts[1]) <= per
    only_shared = _plain_layer(x, router, plain, held=(0, 0), shared=shared)
    np.testing.assert_allclose(sum(parts) + only_shared, whole, atol=1e-3)
    assert given == B * S * K == int(whole_counts[0])
    assert sum(elsewhere) == 3 * B * S * K


def test_a_row_routed_elsewhere_adds_nothing_and_is_counted():
    x, router, served, _ = _layer_weights(6)
    held = (2, 3)
    mine = [w[2:5] for w in served]
    layer = jax.jit(functools.partial(
        moe.moe_mlp_routed, top_k=K, norm_topk_prob=False, held=held))
    got, counts = layer(x, router, *mine)
    probs = np.asarray(jax.nn.softmax(
        jnp.einsum("bsd,de->bse", x, router), -1)).reshape(-1, E)
    chosen = np.argsort(-probs, -1, kind="stable")[:, :K]
    here = (chosen >= 2) & (chosen < 5)
    assert counts.tolist() == [int(here.sum()), len(set(chosen[here])),
                               int((~here).sum())]
    # a token whose experts are all elsewhere gets exactly nothing
    nowhere = ~here.any(-1)
    assert nowhere.any()
    assert not np.asarray(got).reshape(-1, D)[nowhere].any()
    # rows that are no tokens are neither given nor counted elsewhere
    valid = jnp.arange(S)[None, :] < jnp.array([5, S, 0])[:, None]
    _, masked = layer(x, router, *mine, valid=valid)
    tokens = np.asarray(valid).reshape(-1)
    assert masked.tolist()[::2] == [int(here[tokens].sum()),
                                    int((~here[tokens]).sum())]



@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("m, k, n, sizes", GMM_CASES.values(),
                         ids=GMM_CASES)
def test_grouped_kernel_in_interpret_mode_matches_plain_jax(
        m, k, n, sizes, quantized):
    """The Pallas kernel's arithmetic and group bookkeeping, on the CPU
    in interpret mode: empty groups, a group inside one row tile, groups
    across tiles, rows behind the last group."""
    groups = len(sizes)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.float32)
    w = jax.random.normal(keys[1], (groups, k, n), jnp.float32)
    scale = None
    if quantized:
        w = quantize_weight(w, (1,))
        w, scale = w["q"], w["s"]
    group_sizes = jnp.asarray(sizes, jnp.int32)
    row_group = jnp.repeat(jnp.arange(groups + 1),
                           jnp.asarray(sizes + [m - sum(sizes)]),
                           total_repeat_length=m)
    # a stack of two layers, the second one asked for
    w = jnp.stack([jnp.zeros_like(w), w])
    scale = None if scale is None else jnp.stack([scale, scale])
    assert moe._pick_tiles(m, k, n) == (min(m, 128), k, n)
    got = moe._gmm_tpu(lhs, w, scale, jnp.int32(1), group_sizes,
                       jnp.float32, interpret=True)
    want = moe._gmm_xla(lhs, w, scale, 1, row_group, group_sizes,
                        jnp.float32)
    total = sum(sizes)
    assert total < m
    np.testing.assert_allclose(got[:total], want[:total],
                               atol=1e-4 * k / 128)


# the tile rule alone: (rows, contracted width, output width) -> tiles
TILE_CASES = {
    # OLMoE's products keep the tiles they ran with before the rule read
    # the shapes (PR 28), so its programs do not change
    "olmoe-gate": ((16384, 2048, 1024), (128, 2048, 1024)),
    "olmoe-down": ((16384, 1024, 2048), (128, 1024, 2048)),
    "olmoe-decode": ((64, 2048, 1024), (64, 2048, 1024)),
    # SmallThinker's 2,560 = 5 x 512 and 768 = 3 x 256: whole widths
    "smallthinker-gate": ((73728, 2560, 768), (128, 2560, 768)),
    "smallthinker-down": ((73728, 768, 2560), (128, 768, 2560)),
    "smallthinker-decode": ((48, 768, 2560), (48, 768, 2560)),
    # a width 128 does not divide, and rows that are no whole tiles or
    # no multiple of 16: lax.ragged_dot
    "unaligned-k": ((512, 200, 1024), None),
    "unaligned-n": ((512, 1024, 96), None),
    "ragged-rows": ((200, 1024, 1024), None),
    "odd-rows": ((24, 1024, 1024), None),
    # over the budget of 2,048 x 1,024 elements: the whole contracted
    # width first, then a divisor of the output width under the budget
    "wide-n": ((512, 4096, 14336), (128, 4096, 512)),
    "wide-n-odd": ((512, 2560, 7680), (128, 2560, 768)),
    "wide-k": ((512, 32768, 1024), (128, 16384, 128)),
    "wide-k-odd": ((512, 3 * 16384, 640), (128, 16384, 128)),
}


@pytest.mark.parametrize("shape, tiles", TILE_CASES.values(),
                         ids=TILE_CASES)
def test_tile_rule_reads_the_shapes(shape, tiles):
    got = moe._pick_tiles(*shape)
    assert got == tiles
    if got is not None:
        m, k, n = shape
        tm, tk, tn = got
        assert m % tm == 0 and k % tk == 0 and n % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        assert tk * tn <= 2048 * 1024


MOE = dataclasses.replace(LLAMA_CONFIGS["tiny"], n_experts=8, top_k=2,
                          norm_topk_prob=False, qk_norm=True)


ENGINE = dict(max_num_seqs=4, page_size=16, num_pages=33, max_seq_len=64,
              decode_burst=4)


@functools.cache
def _moe_params(seed=3):
    # one compiled program, not one a random draw
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), MOE)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    layers = dict(params["layers"])
    # gains away from 1, so that the norm's weight and width matter
    layers["q_norm"] = 1 + 0.3 * jax.random.normal(
        k1, layers["q_norm"].shape)
    layers["k_norm"] = 1 + 0.3 * jax.random.normal(
        k2, layers["k_norm"].shape)
    return dict(params, layers=layers)


@functools.partial(jax.jit, static_argnums=2)
def _plain_block(params, tokens, cfg):
    x = params["embed"][tokens][None]                     # [1, S, d]
    cos, sin = rope_frequencies(cfg.head_dim, x.shape[1], cfg.rope_theta,
                                dtype=jnp.float32)
    chosen = []
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        width = q.shape[-2] * q.shape[-1]
        q = rms_norm(q.reshape(1, -1, width), lp["q_norm"],
                     cfg.norm_eps).reshape(q.shape)
        k = rms_norm(k.reshape(1, -1, k.shape[-2] * k.shape[-1]),
                     lp["k_norm"], cfg.norm_eps).reshape(k.shape)
        q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        o = naive_attention(q, k, v, causal=True)
        x = x + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        probs = jax.nn.softmax(h[0] @ lp["router"], -1)
        chosen.append(jax.lax.top_k(probs, cfg.top_k)[1])
        x = x + moe.moe_mlp_oracle(
            h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x[0] @ params["lm_head"], jnp.stack(chosen)


def _plain_forward(params, tokens, cfg):
    """The block written out plainly in float32, no cache, no kernels:
    logits [S, vocab], and the distinct experts the tokens chose summed
    over layers."""
    logits, chosen = _plain_block(params, tokens, cfg)
    return logits, sum(len(set(layer.ravel().tolist()))
                       for layer in np.asarray(chosen))


def _prefill_logits(params, rows, lens, cfg=MOE, page=16):
    """``prefill`` on right-padded rows, each sequence on pages of its
    own: logits [B, vocab] at each row's last token."""
    rows = jnp.asarray(rows, jnp.int32)
    n, bucket = rows.shape
    pages = bucket // page
    cache = init_kv_cache(cfg, 1 + n * pages, page)
    tables = 1 + jnp.arange(n * pages, dtype=jnp.int32).reshape(n, pages)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    logits, ck, cv, counts = prefill(
        params, cache.k, cache.v, rows, jnp.asarray(lens, jnp.int32),
        tables, cos, sin, cfg=cfg)
    return logits, (ck, cv, tables, cos, sin), counts


def test_qk_norm_helper_is_one_norm_over_the_whole_projected_width():
    lp = jax.tree.map(lambda a: a[0], _moe_params()["layers"])
    q = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(6), (2, 3, 2, 16))
    nq, nk = qk_norm(q, k, lp, MOE)
    flat = q.reshape(2, 3, 64)
    want = flat / jnp.sqrt((flat ** 2).mean(-1, keepdims=True)
                           + MOE.norm_eps) * lp["q_norm"]
    np.testing.assert_allclose(nq.reshape(2, 3, 64), want, atol=1e-5)
    assert nk.shape == k.shape
    # off by default: the dense configurations' arithmetic is untouched
    same_q, same_k = qk_norm(q, k, lp, LLAMA_CONFIGS["tiny"])
    assert same_q is q and same_k is k


def test_a_sequence_alone_and_in_a_full_batch_has_equal_logits():
    """Nothing is dropped and no capacity exists: a sequence's logits are
    the same whatever else is in the batch, and whatever padding its
    bucket has."""
    params = _moe_params()
    rng = np.random.default_rng(0)
    lens = [9, 16, 3, 12]
    rows = np.zeros((4, 16), np.int32)
    for i, n in enumerate(lens):
        rows[i, :n] = rng.integers(1, MOE.vocab, n)
    together, _, counts = _prefill_logits(params, rows, lens)
    assert int(counts[0]) == sum(lens) * MOE.top_k * MOE.n_layers
    for i, n in enumerate(lens):
        alone, _, _ = _prefill_logits(params, rows[i:i + 1], [n])
        # float32; the grouped product sums a row's terms in one order
        # whatever the other rows are, so only the batched attention
        # kernel's blocking can differ: rounding, not a dropped expert
        # (which would move a logit by a whole expert's share, ~1e-1)
        np.testing.assert_allclose(alone[0], together[i], atol=1e-5)
        bucket32 = np.zeros((1, 32), np.int32)
        bucket32[0, :n] = rows[i, :n]
        wider, _, _ = _prefill_logits(params, bucket32, [n])
        np.testing.assert_allclose(wider[0], together[i], atol=1e-5)


def test_engine_logits_match_a_plain_float32_forward_pass():
    """Prefill, then decode through the paged cache, for ``qk_norm`` and
    an unnormalised top-2 of 8: logits, not tokens. Both sides are
    float32 on the CPU and compute the same sums in different orders
    (the engine's attention is blockwise over pages, its experts a
    grouped product), so they differ by rounding through two layers:
    under 1e-3 of logits whose deviation is about 1. A missing QK-norm,
    a renormalised router or a wrong page moves them by tenths."""
    params = _moe_params()
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, MOE.vocab, 14)
    want, _ = _plain_forward(params, jnp.asarray(tokens), MOE)
    row = np.zeros((1, 16), np.int32)
    row[0, :10] = tokens[:10]
    logits, (ck, cv, tables, cos, sin), _ = _prefill_logits(
        params, row, [10])
    np.testing.assert_allclose(logits[0], want[9], atol=1e-3)
    for pos in range(10, 14):       # one token at a time over the cache
        logits, ck, cv, counts = prefill_chunk(
            params, ck, cv, jnp.asarray(tokens[pos:pos + 1][None],
                                        jnp.int32),
            jnp.int32(pos), jnp.int32(1), tables, cos, sin, cfg=MOE)
        np.testing.assert_allclose(logits[0], want[pos], atol=1e-3)
        assert counts.tolist() == [MOE.top_k * MOE.n_layers] * 2 + [0]
    # the control: the same pass with a renormalised router is far off
    off, _ = _plain_forward(params, jnp.asarray(tokens),
                            dataclasses.replace(MOE, norm_topk_prob=True))
    assert float(jnp.abs(off[9] - want[9]).max()) > 0.05


def test_engine_decode_tokens_lie_at_the_plain_forward_pass_maximum():
    """``decode_burst`` returns tokens, not logits: every token the
    engine decodes greedily must be the plain forward pass's first
    choice on prompt + answer so far, to within the rounding above."""
    params = _moe_params()
    prompt = np.random.default_rng(2).integers(1, MOE.vocab, 11).tolist()
    engine = LLMEngine(params, MOE, EngineConfig(**ENGINE))
    answer = engine.generate([prompt], SamplingParams(
        temperature=0.0, max_tokens=9))[0]
    want, _ = _plain_forward(params, jnp.asarray(prompt + answer), MOE)
    for i, token in enumerate(answer):
        at = want[len(prompt) - 1 + i]
        assert float(at.max() - at[token]) < 1e-3


def test_expert_counters_against_a_count_by_hand():
    params = _moe_params()
    prompt = np.random.default_rng(3).integers(1, MOE.vocab, 5).tolist()
    engine = LLMEngine(params, MOE, EngineConfig(**ENGINE))
    assert engine.stats()["counters"]["expert_rows"] == 0
    engine.add_request(prompt, SamplingParams(temperature=0.0,
                                              max_tokens=1))
    engine.step()
    counters = engine.stats()["counters"]
    _, touched = _plain_forward(params, jnp.asarray(prompt), MOE)
    layers, k = MOE.n_layers, MOE.top_k
    # 5 tokens in a bucket of 16: the 11 padding rows reach no expert
    assert counters["expert_rows"] == 5 * k * layers
    assert counters["experts_touched"] == touched
    # decode: one active slot of four, so k rows and k experts a layer
    # a step, whatever the three inactive slots hold
    answer = engine.generate([prompt], SamplingParams(
        temperature=0.0, max_tokens=6))[0]
    after = engine.stats()["counters"]
    steps = after["decode_steps"]
    assert len(answer) == 6 and steps >= 5
    assert (after["expert_rows"] - counters["expert_rows"]
            == (5 + steps) * k * layers)
    assert (after["experts_touched"] - counters["experts_touched"]
            == touched + steps * k * layers)
    # a dense configuration has no such counters
    dense = LLMEngine(init_params(jax.random.PRNGKey(0),
                                  LLAMA_CONFIGS["tiny"]),
                      LLAMA_CONFIGS["tiny"], EngineConfig(**ENGINE))
    assert "expert_rows" not in dense.stats()["counters"]


def test_chunked_prefill_counts_ride_the_final_read_back():
    params = _moe_params()
    prompt = np.random.default_rng(4).integers(1, MOE.vocab, 21).tolist()
    engine = LLMEngine(params, MOE, EngineConfig(prefill_chunk=8, **ENGINE))
    answer = engine.generate([prompt], SamplingParams(
        temperature=0.0, max_tokens=1))[0]
    whole = LLMEngine(params, MOE, EngineConfig(**ENGINE)).generate(
        [prompt], SamplingParams(temperature=0.0, max_tokens=1))[0]
    assert answer == whole
    assert (engine.stats()["counters"]["expert_rows"]
            == 21 * MOE.top_k * MOE.n_layers)
    assert not engine._pending_counts


def test_quantized_expert_engine_agrees_with_its_dequantized_twin():
    """int8 experts through the engine (the seeded init's tree) against
    the same weights multiplied out in float32 and served raw: the
    scales are applied by each row's expert."""
    cfg = dataclasses.replace(MOE, dtype=jnp.float32)
    served = init_params_quantized(jax.random.PRNGKey(7), cfg)

    def wide(w, axes):
        return dequantize_weight(w, axes, jnp.float32)

    layers = dict(served["layers"])
    for name, axes in (("wq", (1,)), ("wk", (1,)), ("wv", (1,)),
                       ("wo", (1, 2)), ("w_gate", (2,)), ("w_up", (2,)),
                       ("w_down", (2,))):
        layers[name] = wide(layers[name], axes)
    layers = {k: v.astype(jnp.float32) for k, v in layers.items()}
    raw = {"embed": wide(served["embed"], (1,)), "layers": layers,
           "final_norm": served["final_norm"].astype(jnp.float32),
           "lm_head": wide(served["lm_head"], (0,))}
    row = np.zeros((2, 16), np.int32)
    rng = np.random.default_rng(5)
    row[0, :12] = rng.integers(1, cfg.vocab, 12)
    row[1, :7] = rng.integers(1, cfg.vocab, 7)
    got, _, _ = _prefill_logits(served, row, [12, 7], cfg)
    want, _, _ = _prefill_logits(raw, row, [12, 7], cfg)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)
    # scales differ by expert: the check would see another expert's
    s = served["layers"]["w_gate"]["s"]
    assert float(jnp.abs(s[0, 0] - s[0, 1]).max()) > 0


def test_llm_server_takes_a_configuration_itself():
    from ray_tpu.llm.serve import LLMServer, build_llm_deployment
    from ray_tpu.models.llama import LLAMA_CONFIGS as registry

    before = dict(registry)
    server = LLMServer(MOE, init="random", seed=1, engine_config=ENGINE)
    assert server.engine.cfg is MOE
    assert registry == before                 # nothing was written there
    assert "8e" in server.model_name
    app = build_llm_deployment(MOE, engine_config=dict(max_num_seqs=2))
    import cloudpickle

    again = cloudpickle.loads(cloudpickle.dumps(MOE))
    assert again == MOE and app is not None
