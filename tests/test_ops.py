"""Kernel correctness vs naive oracles on the CPU mesh (SURVEY §4.4)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import (
    apply_rotary, attention, naive_attention, ring_attention, rms_norm,
    rope_frequencies,
)
from ray_tpu.ops.attention import blockwise_attention
from ray_tpu.parallel import MeshSpec, build_mesh


def _qkv(key, b=2, sq=64, skv=64, hq=4, hkv=2, d=16, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, hkv, d), dtype)
    return q, k, v


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.bfloat16)
    w = jnp.ones((32,), jnp.bfloat16) * 2
    out = rms_norm(x, w)
    assert out.dtype == jnp.bfloat16
    xf = np.asarray(x, np.float32)
    ref = xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-5) * 2
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=2e-2, atol=2e-2)


def test_rotary_norm_preserving():
    cos, sin = rope_frequencies(16, 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 4, 16))
    out = apply_rotary(x, cos, sin)
    # Rotation preserves the norm of each pair.
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # Position 0 is the identity rotation.
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [4, 2, 1])
def test_blockwise_matches_naive(causal, hkv):
    q, k, v = _qkv(jax.random.PRNGKey(2), hkv=hkv)
    ref = naive_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, kv_block=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_blockwise_cross_attention_unpadded():
    q, k, v = _qkv(jax.random.PRNGKey(3), sq=32, skv=80)
    ref = naive_attention(q, k, v, causal=False)
    out = blockwise_attention(q, k, v, causal=False, kv_block=32)  # pad 80->96
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_attention_dispatcher_grad():
    q, k, v = _qkv(jax.random.PRNGKey(4), sq=32, skv=32)

    def loss(q, k, v):
        return attention(q, k, v, causal=True).sum()

    g = jax.grad(loss)(q, k, v)
    gref = jax.grad(lambda q, k, v: naive_attention(q, k, v).sum())(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(cpu_mesh8, causal):
    mesh = build_mesh(MeshSpec(sp=8), cpu_mesh8)
    q, k, v = _qkv(jax.random.PRNGKey(5), b=1, sq=64, skv=64, hq=4, hkv=2)

    def f(q, k, v):
        return ring_attention(q, k, v, axis="sp", causal=causal)

    out = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_differentiable(cpu_mesh8):
    mesh = build_mesh(MeshSpec(sp=4), cpu_mesh8[:4])
    q, k, v = _qkv(jax.random.PRNGKey(6), b=1, sq=32, skv=32, hq=2, hkv=2)

    def loss(q, k, v):
        out = shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis="sp"),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)(q, k, v)
        return (out ** 2).sum()

    g = jax.jit(jax.grad(loss))(q, k, v)
    gref = jax.grad(
        lambda a, b, c: (naive_attention(a, b, c) ** 2).sum())(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=1e-4, atol=1e-4)


def test_fully_masked_rows_zero():
    # Every key is in the future for the earliest queries when skv < sq:
    # those rows must produce zeros, not uniform attention over padding.
    q, k, v = _qkv(jax.random.PRNGKey(7), sq=16, skv=8)
    ref = naive_attention(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, kv_block=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # q rows 0..7 see no keys (offset skv-sq = -8): exact zeros.
    np.testing.assert_array_equal(np.asarray(out[:, :7]), 0.0)


def test_pick_block():
    from ray_tpu.ops.attention import _pick_block
    assert _pick_block(640, 512) == 128
    assert _pick_block(1024, 512) == 512
    assert _pick_block(384, 512) == 384
    # Blocks must be 128-lane aligned for Mosaic; seqs with no aligned
    # divisor must return None so the dispatcher falls back to blockwise.
    assert _pick_block(96, 512) is None
    assert _pick_block(100, 512) is None
    assert _pick_block(24, 512) is None
    assert _pick_block(250, 128) is None


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [4, 2])
def test_pallas_flash_interpret_matches_naive(causal, hkv):
    """Run the Pallas kernel body in interpret mode (works on CPU) against
    the naive oracle — covers the VMEM scratch accumulation and the GQA
    kv_index map without TPU hardware."""
    from ray_tpu.ops.attention import flash_attention_tpu

    q, k, v = _qkv(jax.random.PRNGKey(8), b=2, sq=256, skv=256,
                   hq=4, hkv=hkv, d=128)
    ref = naive_attention(q, k, v, causal=causal)
    out = flash_attention_tpu(q, k, v, causal=causal,
                              block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pallas_flash_interpret_bf16_and_uneven():
    from ray_tpu.ops.attention import flash_attention_tpu

    # bf16 inputs, q shorter than kv (decode-with-cache alignment).
    q, k, v = _qkv(jax.random.PRNGKey(9), b=1, sq=128, skv=256,
                   hq=2, hkv=1, d=128, dtype=jnp.bfloat16)
    ref = naive_attention(q, k, v, causal=True)
    out = flash_attention_tpu(q, k, v, causal=True,
                              block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("lengths", [
    (1,), (200,), (256,), (512,), (130, 384)],
    ids=["one", "inside_a_block", "whole_blocks", "the_bucket", "two_rows"])
@pytest.mark.parametrize("variant", ["causal", "window", "own_value_width"])
def test_pallas_flash_interpret_with_lengths(variant, lengths):
    """Told where each row's tokens end, the kernel leaves out the query
    blocks behind the end: a token's row is the row without ``lengths``
    to the bit (and the plain reference's to its tolerance), a skipped
    block is zeros with a finite LSE, and a NaN behind the end reaches
    no token."""
    from ray_tpu.ops.attention import flash_attention_tpu

    S, block = 512, 128
    d, dv = (192, 128) if variant == "own_value_width" else (128, 128)
    window = 200 if variant == "window" else None
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    B = len(lengths)
    q = jax.random.normal(keys[0], (B, S, 4, d), jnp.float32)
    k = jax.random.normal(keys[1], (B, S, 2, d), jnp.float32)
    v = jax.random.normal(keys[2], (B, S, 2, dv), jnp.float32)
    flash = functools.partial(flash_attention_tpu, window=window,
                              block_q=block, block_k=block, interpret=True)
    lens = jnp.asarray(lengths, jnp.int32)
    out, lse = flash(q, k, v, lengths=lens, return_lse=True)
    assert out.shape == (B, S, 4, dv)
    assert np.isfinite(np.asarray(lse)).all()
    without = flash(q, k, v)
    want = naive_attention(q, k, v, window=window)
    for b, n in enumerate(lengths):
        np.testing.assert_array_equal(out[b, :n], without[b, :n])
        np.testing.assert_allclose(out[b, :n], want[b, :n], atol=2e-5)
        behind = -(-n // block) * block     # the first skipped block
        assert not np.asarray(out[b, behind:]).any()
        if behind < S:
            assert np.abs(np.asarray(without[b, behind:])).max() > 1e-2
    # NaN in every padding row of q and k, and in v's behind the block
    # that holds the end (a padding row of THAT block is multiplied by a
    # weight of 0, with and without lengths: in a prefill it is finite)
    rows = jnp.arange(S)[None, :, None, None]
    pad = rows >= lens[:, None, None, None]
    skipped = rows >= -(-lens // block)[:, None, None, None] * block
    poisoned = flash(jnp.where(pad, jnp.nan, q), jnp.where(pad, jnp.nan, k),
                     jnp.where(skipped, jnp.nan, v), lengths=lens)
    for b, n in enumerate(lengths):
        np.testing.assert_array_equal(poisoned[b, :n], out[b, :n])
    np.testing.assert_array_equal(np.asarray(poisoned)[np.asarray(
        jnp.broadcast_to(skipped, poisoned.shape))], 0.0)


def _unfused(fn, *args):
    """``fn(*args)`` compiled with the CPU compiler's fusion pass off.
    Fused, the interpreter's multiply-adds are contracted one way or
    another with the program's shape, and a chunk of one key block then
    differs from a chunk of four in the last bit; unfused, every
    operation rounds by itself, which is what the kernel's claim of
    equal bits is about (the chip's own bits are compared on the chip:
    PERF.md section 6, PR 40)."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_disable_hlo_passes": "fusion"})(*args)


# (q heads, kv heads, q/k width, v width, causal, window)
CHUNKED = {
    "causal": (2, 2, 128, 128, True, None),
    "gqa": (4, 1, 128, 128, True, None),
    "window": (2, 1, 128, 128, True, 200),
    "window_of_whole_blocks": (2, 1, 128, 128, True, 256),
    "own_value_width": (2, 1, 192, 128, True, None),
    "not_causal": (2, 1, 128, 128, False, None),
}


@pytest.mark.parametrize("lengths", [None, (512, 512), (200, 385), (1, 128)],
                         ids=["untold", "full", "mid_block", "one_token"])
@pytest.mark.parametrize("variant", CHUNKED.values(), ids=CHUNKED)
def test_pallas_flash_bits_do_not_depend_on_the_chunk(
        monkeypatch, variant, lengths):
    """However many key blocks a grid step holds (one: the kernel as it
    was; two; the whole head), a token's output and LSE are the same to
    the bit: the blocks are walked in the same order and the body
    without a mask runs only where a mask would hide nothing."""
    A = importlib.import_module("ray_tpu.ops.attention")
    hq, hkv, d, dv, causal, window = variant
    B, S, block = 2, 512, 128
    keys = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(keys[0], (B, S, hq, d), jnp.float32)
    k = jax.random.normal(keys[1], (B, S, hkv, d), jnp.float32)
    v = jax.random.normal(keys[2], (B, S, hkv, dv), jnp.float32)
    kw = dict(causal=causal, window=window, block_q=block, block_k=block,
              interpret=True, return_lse=True)
    if lengths is None:
        fn, args = (lambda q, k, v: A.flash_attention_tpu(q, k, v, **kw)), ()
    else:
        fn = lambda q, k, v, n: A.flash_attention_tpu(
            q, k, v, lengths=n, **kw)
        args = (jnp.asarray(lengths, jnp.int32),)
    one_block = 2 * block * (-(-d // 128) + 1) * 128 * 4  # two buffers
    got = {}
    for room in (1, 2 * one_block, A.KV_VMEM_BYTES):
        monkeypatch.setattr(A, "KV_VMEM_BYTES", room)
        chunk, limit = A._pick_chunk(S, block, d, dv, 4)
        assert limit == A._VMEM_BASE_BYTES + chunk * one_block
        got[chunk] = _unfused(fn, q, k, v, *args)
    assert sorted(got) == [1, 2, 4]
    want = naive_attention(q, k, v, causal=causal, window=window)
    out, lse = got[1]
    assert np.isfinite(np.asarray(lse)).all()
    for b, n in enumerate(lengths or (S, S)):
        for o, l in (got[2], got[4]):
            np.testing.assert_array_equal(o[b, :n], out[b, :n])
            np.testing.assert_array_equal(l[b, :, :n], lse[b, :, :n])
            behind = -(-n // block) * block
            assert not np.asarray(o[b, behind:]).any()
        np.testing.assert_allclose(out[b, :n], want[b, :n], atol=2e-5)


def _blocks_by_the_mask(sq, skv, bq, bk, causal, window, length):
    """(computed, masked) key blocks of one head, counted from the mask
    itself: a block is computed if one of its pairs is seen (and its
    query block starts under ``length``), masked if one is hidden."""
    qi = np.arange(sq)[:, None] + (skv - sq)
    ki = np.arange(skv)[None, :]
    seen = np.ones((sq, skv), bool)
    if causal:
        seen &= ki <= qi
    if window is not None:
        seen &= qi - ki < window
    tiles = seen.reshape(sq // bq, bq, skv // bk, bk)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    if length is not None:
        some &= (np.arange(sq // bq) * bq < length)[:, None]
    return int(some.sum()), int((some & ~every).sum())


@pytest.mark.parametrize("sq, skv, bq, bk, causal, window, length", [
    # the served buckets' block structure at a quarter of their rows
    (4096, 4096, 128, 128, True, None, None),
    (4096, 4096, 128, 128, True, None, 2663),
    (4096, 4096, 128, 128, True, None, 2268),
    (3072, 3072, 128, 128, True, 1024, None),
    (3072, 3072, 128, 128, True, 1024, 2075),
    (2048, 2048, 512, 512, True, None, None),
    (1024, 1024, 512, 512, True, None, 768),
    (1024, 2048, 256, 512, True, None, None),
    (2048, 1024, 256, 128, True, None, None),
    (2048, 2048, 256, 128, True, 300, 1500),
    (2048, 2048, 128, 256, True, 1, None),
    (1024, 1024, 128, 128, True, 5000, 1),
    (1024, 2048, 256, 256, False, None, None),
], ids=lambda v: str(v))
def test_flash_forward_steps_counts_what_the_mask_says(
        sq, skv, bq, bk, causal, window, length):
    """The blocks the kernel's loop bounds walk are exactly the blocks
    that hold a seen pair, the ones it masks exactly those that hold a
    hidden one too, and the steps are the grid's: query blocks times
    key chunks."""
    from ray_tpu.ops.attention import flash_forward_steps

    nq, nk = sq // bq, skv // bk
    for chunk in (c for c in (1, 2, nk) if nk % c == 0):
        steps, blocks, masked = flash_forward_steps(
            sq, skv, bq, bk, chunk, causal=causal, window=window,
            length=length)
        assert steps == nq * (nk // chunk)
        assert (blocks, masked) == _blocks_by_the_mask(
            sq, skv, bq, bk, causal, window, length)


def test_flash_forward_steps_of_the_served_buckets():
    """The numbers PERF.md states: a latent head at 16,384 rows is
    resident whole (32 grid steps a head where single blocks walked
    1,024) and computes 528 blocks, 32 of them masked."""
    from ray_tpu.ops.attention import (
        _VMEM_BASE_BYTES, _pick_chunk, flash_forward_steps)

    chunk, limit = _pick_chunk(16384, 512, 192, 128, 2)
    assert (chunk, limit) == (32, _VMEM_BASE_BYTES + 16384 * 384 * 2 * 2)
    assert flash_forward_steps(16384, 16384, 512, 512, chunk) == (32, 528, 32)
    assert flash_forward_steps(16384, 16384, 512, 512, 1) == (1024, 528, 32)
    assert flash_forward_steps(16384, 16384, 512, 512, chunk,
                               length=10654) == (32, 231, 21)
    # twice the rows: two chunks of 16,384 keys
    assert _pick_chunk(32768, 512, 192, 128, 2)[0] == 32
    # SmallThinker's bucket: whole, and the window bounds a row's blocks
    chunk, _ = _pick_chunk(12288, 512, 128, 128, 2)
    assert chunk == 24
    assert flash_forward_steps(12288, 12288, 512, 512, chunk,
                               window=4096) == (24, 180, 40)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [4, 2])
def test_pallas_flash_backward_interpret(causal, hkv):
    """dq/dk/dv from the Pallas backward kernels (interpret mode) against
    autodiff through the naive oracle — covers the LSE reconstruction,
    the softmax-jacobian correction, and the GQA gradient fold."""
    from ray_tpu.ops.attention import (
        flash_attention_tpu, flash_attention_tpu_bwd, naive_attention)

    q, k, v = _qkv(jax.random.PRNGKey(10), b=2, sq=256, skv=256,
                   hq=4, hkv=hkv, d=128)

    def ref_loss(q, k, v):
        out = naive_attention(q, k, v, causal=causal)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)

    out, lse = flash_attention_tpu(q, k, v, causal=causal,
                                   block_q=128, block_k=128,
                                   interpret=True, return_lse=True)
    do = 2.0 * out.astype(jnp.float32)  # d/dout of sum(out^2)
    dq, dk, dv = flash_attention_tpu_bwd(
        q, k, v, out, lse, do.astype(q.dtype), causal=causal,
        block_q=128, block_k=128, interpret=True)
    for got, want in ((dq, rq), (dk, rk), (dv, rv)):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
        assert err < 2e-2, err
