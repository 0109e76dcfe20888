"""Interpret-mode parity tests for the Pallas paged-attention decode
kernel (ray_tpu/ops/paged_attention.py) against the XLA gather oracle —
the same oracle shape the serving runner's fallback path uses
(llm/runner.py decode_burst)."""

import numpy as np
import pytest

import jax

# the harness environment downgrades default matmul precision; parity
# is judged at full f32 precision
jax.config.update("jax_default_matmul_precision", "highest")

import jax.numpy as jnp

from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                         paged_decode_attention_reference)


def _case(rng, B, kvh, rep, hd, page, n_pages, P, K):
    q = jnp.asarray(rng.standard_normal((B, kvh, rep, hd)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((P, page, kvh, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((P, page, kvh, hd)), jnp.float32)
    nk = jnp.asarray(rng.standard_normal((B, K, kvh, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((B, K, kvh, hd)), jnp.float32)
    bt = jnp.asarray(np.stack(
        [rng.choice(P, size=n_pages, replace=False)
         for _ in range(B)]).astype(np.int32))
    return q, ck, cv, nk, nv, bt


@pytest.mark.parametrize("B,kvh,rep,hd,page,n_pages,P,K", [
    (3, 2, 4, 64, 16, 4, 32, 8),     # GQA, mixed contexts
    (2, 1, 8, 128, 32, 2, 8, 4),     # MQA, big heads
    (4, 4, 1, 64, 16, 8, 64, 16),    # MHA (rep=1), long table
])
def test_paged_kernel_matches_oracle(B, kvh, rep, hd, page, n_pages, P, K):
    rng = np.random.default_rng(B * 1000 + rep)
    q, ck, cv, nk, nv, bt = _case(rng, B, kvh, rep, hd, page, n_pages, P, K)
    ctx = jnp.asarray(rng.integers(0, page * n_pages + 1, B), jnp.int32)
    new_len = jnp.asarray(np.maximum(rng.integers(0, K + 1, B), 1),
                          jnp.int32)
    out = paged_decode_attention(q, ck, cv, nk, nv, bt, ctx, new_len,
                                 page_size=page, interpret=True)
    ref = paged_decode_attention_reference(q, ck, cv, nk, nv, bt, ctx,
                                           new_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_paged_kernel_edge_contexts():
    """Empty context (tail only), full pages, page-boundary lengths."""
    rng = np.random.default_rng(7)
    B, kvh, rep, hd, page, n_pages, P, K = 4, 2, 2, 64, 16, 4, 16, 8
    q, ck, cv, nk, nv, bt = _case(rng, B, kvh, rep, hd, page, n_pages, P, K)
    ctx = jnp.asarray([0, page, page * n_pages, page + 1], jnp.int32)
    new_len = jnp.asarray([K, 1, 0, 3], jnp.int32)
    out = paged_decode_attention(q, ck, cv, nk, nv, bt, ctx, new_len,
                                 page_size=page, interpret=True)
    ref = paged_decode_attention_reference(q, ck, cv, nk, nv, bt, ctx,
                                           new_len)
    valid = np.asarray(ctx) + np.asarray(new_len) > 0
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(ref)[valid],
                               atol=3e-5, rtol=3e-5)


def test_decode_burst_kernel_path_matches_gather_path():
    """End-to-end through the serving runner: decode_burst with the
    Pallas kernel (llm_paged_kernel) samples the same tokens and writes
    the same cache as the XLA gather path."""
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.llm.runner import decode_burst
    from ray_tpu.ops import rope_frequencies

    cfg = LlamaConfig(vocab=128, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, mlp_dim=128, max_seq=128,
                      dtype=jnp.float32, remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cos, sin = rope_frequencies(cfg.head_dim, 128, cfg.rope_theta,
                                dtype=jnp.float32)
    L, P, page = cfg.n_layers, 8, 16
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(0)
    B = 2
    ck0 = rng.standard_normal((L, P, page, kvh, hd)).astype(np.float32) * .1
    cv0 = rng.standard_normal((L, P, page, kvh, hd)).astype(np.float32) * .1
    outs = {}
    for flag in (True, False):
        toks, k2, v2, _ = decode_burst(
            params, jnp.asarray(ck0), jnp.asarray(cv0),
            jnp.asarray([3, 5], jnp.int32), jnp.asarray([20, 7], jnp.int32),
            jnp.asarray([[1, 2], [3, 4]], jnp.int32),
            jnp.asarray([True, True]), cos, sin, 0,
            jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
            jnp.ones(B, jnp.float32), cfg=cfg, n_steps=4,
            paged_kernel=flag)
        outs[flag] = (np.asarray(toks), np.asarray(k2))
    assert np.array_equal(outs[False][0], outs[True][0])
    np.testing.assert_allclose(outs[False][1], outs[True][1], atol=1e-5)


def test_paged_kernel_ignores_dump_page_noise():
    """Unused table slots point at page 0 (the dump page); whatever junk
    lives there must not leak into attention."""
    rng = np.random.default_rng(11)
    B, kvh, rep, hd, page, n_pages, P, K = 2, 2, 2, 64, 16, 4, 16, 4
    q, ck, cv, nk, nv, _ = _case(rng, B, kvh, rep, hd, page, n_pages, P, K)
    ck = ck.at[0].set(1e4)  # poison the dump page
    cv = cv.at[0].set(1e4)
    bt = jnp.asarray([[3, 0, 0, 0], [5, 6, 0, 0]], jnp.int32)
    ctx = jnp.asarray([10, 20], jnp.int32)  # inside the real pages only
    new_len = jnp.asarray([2, 2], jnp.int32)
    out = paged_decode_attention(q, ck, cv, nk, nv, bt, ctx, new_len,
                                 page_size=page, interpret=True)
    ref = paged_decode_attention_reference(q, ck, cv, nk, nv, bt, ctx,
                                           new_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    assert float(jnp.max(jnp.abs(out))) < 100  # poison did not leak
