"""Selection by blocks (``ops/sparse_attention.py``): the sums of
strides and the compressed keys they make, the pooled block scores, the
forced blocks, ties, ``dense_len`` a query, and the choice against an
explicit ``lax.top_k``; the decode step's list of pages against the
gathered rectangle.

float32; the kernels run in Pallas's interpret mode here.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import sparse_attention as sp

# a block of 64 = 4 strides of 16; 4 blocks a query of which the first
# and the two newest are forced; dense below 256
SIZES = sp.BlockSizes(size=64, topk=4, stride=16, init=1, window=128,
                      dense_len=256)
T, S, H, KVH, HD = 256, 1024, 8, 2, 128
SCALE = HD ** -0.5


@pytest.fixture(scope="module")
def rows():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (T, H, HD), jnp.float32),
            jax.random.normal(ks[1], (S, KVH, HD), jnp.float32),
            jax.random.normal(ks[2], (S, KVH, HD), jnp.float32))


def _compressed(k):
    sums = sp.stride_sums(k, jnp.ones(k.shape[0], bool), SIZES.stride)
    return sums, sp.compressed_keys(sums, SIZES, jnp.float32)


def test_a_compressed_key_is_the_mean_of_32_keys(rows):
    _, k, _ = rows
    _, c = _compressed(k)
    for j in (0, 5, S // 16 - 2):
        np.testing.assert_allclose(c[j], k[16 * j:16 * j + 32].mean(0),
                                   rtol=1e-5, atol=1e-6)


def test_sums_leave_out_rows_that_are_no_tokens(rows):
    _, k, _ = rows
    sums = sp.stride_sums(k, jnp.arange(S) < 40, SIZES.stride)
    np.testing.assert_allclose(sums[2], k[32:40].sum(0), rtol=1e-5)
    assert float(jnp.abs(sums[3:]).max()) == 0.0


def test_whole_windows_at_or_before_the_query_only():
    at = jnp.asarray([-1, 0, 30, 31, 46, 47, 63, 1023])
    assert [int(n) for n in sp.valid_compressed(at, SIZES)] == [
        0, 0, 0, 1, 1, 2, 3, 63]


def _scores_by_hand(q, c, q_pos):
    """One softmax a head over the valid compressed keys, summed over the
    KV head's heads, then the largest over j = 4 b - 1 .. 4 b + 3."""
    n_c, group = c.shape[0], H // KVH
    out = np.zeros((KVH, len(q_pos), S // SIZES.size), np.float32)
    for t, at in enumerate(q_pos):
        n = max((at + 1 - 32) // 16 + 1, 0)
        for g in range(KVH):
            s = np.zeros(n_c)
            for r in range(group):
                a = (q[t, g * group + r] @ c[:n, g].T) * SCALE
                if n:
                    a = np.exp(a - a.max())
                    s[:n] += a / a.sum()
            for b in range(out.shape[2]):
                js = [j for j in range(4 * b - 1, 4 * b + 4) if 0 <= j < n]
                out[g, t, b] = max((s[j] for j in js), default=0.0)
    return out


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_block_scores_are_pooled_softmaxes(rows, form):
    q, k, _ = rows
    _, c = _compressed(k)
    q_pos = np.asarray([40, 300, 511, 777, 1023, 64, 65, 500])
    qq = jnp.swapaxes(q[:8], 0, 1)[None]
    ck = jnp.swapaxes(c, 0, 1)[None]
    n_valid = sp.valid_compressed(jnp.asarray(q_pos), SIZES)[None]
    if form == "kernel":
        s = sp.block_scores_tpu(qq, ck, n_valid, scale=SCALE,
                                interpret=True)
        np.testing.assert_allclose(
            s, sp.block_scores_xla(qq, ck, n_valid, scale=SCALE),
            rtol=1e-5, atol=1e-6)
    got = sp.block_scores(qq, ck, jnp.asarray(q_pos)[None], SIZES,
                          scale=SCALE)[0]
    np.testing.assert_allclose(
        got, _scores_by_hand(np.asarray(q[:8]), np.asarray(c), q_pos),
        rtol=1e-4, atol=1e-6)


def _choice_by_hand(scores, q_pos):
    """The reference's mask: forced blocks at +inf, ``lax.top_k``."""
    kvh, n, n_b = scores.shape
    out = np.zeros((kvh, n, n_b), bool)
    for t, at in enumerate(q_pos):
        if at < 0:
            continue
        own = at // SIZES.size
        visible = np.arange(n_b) <= own
        if at < SIZES.dense_len:
            out[:, t] = visible
            continue
        forced = (np.arange(n_b) < SIZES.init) | (
            np.arange(n_b) > own - SIZES.window // SIZES.size)
        for g in range(kvh):
            ranked = np.where(visible, np.where(forced, np.inf,
                                                scores[g, t]), -np.inf)
            _, idx = jax.lax.top_k(jnp.asarray(ranked), SIZES.topk)
            out[g, t, np.asarray(idx)] = True
            out[g, t] &= visible
    return out


def test_the_choice_is_the_references_mask(rows):
    q, k, _ = rows
    _, c = _compressed(k)
    q_pos = jnp.arange(S - T, S).at[-3:].set(-1).at[:4].set(
        jnp.asarray([0, 100, 255, 256]))
    scores = sp.block_scores(jnp.swapaxes(q, 0, 1)[None],
                             jnp.swapaxes(c, 0, 1)[None], q_pos[None],
                             SIZES, scale=SCALE)
    mask = np.asarray(sp.block_choice(scores, q_pos[None], SIZES)[0]) > 0
    want = _choice_by_hand(np.asarray(scores[0]), np.asarray(q_pos))
    assert (mask == want).all()
    at = np.asarray(q_pos)
    # a row that is no token chooses nothing; a query below dense_len
    # every visible block; one above exactly topk, the forced among them
    assert not mask[:, at < 0].any()
    assert (mask[:, 1].sum(-1) == 100 // 64 + 1).all()
    sparse_rows = at >= SIZES.dense_len
    assert (mask[:, sparse_rows].sum(-1) == SIZES.topk).all()
    for t in np.flatnonzero(sparse_rows):
        own = at[t] // 64
        assert mask[:, t, 0].all() and mask[:, t, own].all() \
            and mask[:, t, own - 1].all()


def test_ties_go_to_the_earlier_block():
    scores = jnp.zeros((1, 1, 2, 16), jnp.float32).at[..., 9].set(1.0)
    q_pos = jnp.asarray([[1023, 1023]])
    mask = np.asarray(sp.block_choice(scores, q_pos, SIZES)[0, 0, 0])
    # forced 0, 14, 15; then the one score above the rest... 9
    assert np.flatnonzero(mask).tolist() == [0, 9, 14, 15]
    mask = np.asarray(sp.block_choice(scores * 0, q_pos, SIZES)[0, 0, 0])
    # all equal: the earliest block that is not forced
    assert np.flatnonzero(mask).tolist() == [0, 1, 14, 15]


def _attend_by_hand(q, k, v, mask_blocks, q_pos):
    group = H // KVH
    out = np.zeros(q.shape, np.float32)
    for t, at in enumerate(q_pos):
        if at < 0:
            continue
        for h in range(H):
            keep = np.repeat(mask_blocks[h // group, t], SIZES.size) & (
                np.arange(S) <= at)
            s = (q[t, h] @ k[:, h // group].T) * SCALE
            s = np.where(keep, s, -np.inf)
            p = np.exp(s - s.max())
            out[t, h] = (p / p.sum()) @ v[:, h // group]
    return out


def test_block_attend_is_attention_under_the_chosen_blocks(rows):
    q, k, v = rows
    sums, c = _compressed(k)
    q_pos = jnp.arange(S - 64, S).at[-2:].set(-1).at[0].set(130)
    got = sp.block_attend(q[:64], k, v, sums, q_pos, SIZES, scale=SCALE)
    scores = sp.block_scores(jnp.swapaxes(q[:64], 0, 1)[None],
                             jnp.swapaxes(c, 0, 1)[None], q_pos[None],
                             SIZES, scale=SCALE)
    mask = np.asarray(sp.block_choice(scores, q_pos[None], SIZES)[0]) > 0
    want = _attend_by_hand(np.asarray(q[:64]), np.asarray(k), np.asarray(v),
                           mask, np.asarray(q_pos))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(got[-2:]).max()) == 0.0


def test_the_flash_forward_leaves_out_dead_key_blocks(rows):
    """The kernel under its block name equals the masked product, and a
    key block of 512 in which a query block chose nothing is not
    computed: its values are NaN here, and a weight of 0 times NaN would
    poison the output if it were."""
    q, k, v = rows
    q_pos = jnp.arange(S - 128, S)
    blocks = jnp.zeros((128, S // 64), jnp.int8).at[:, -3:].set(1)
    mask = (jnp.repeat(blocks, 64, -1)
            * (jnp.arange(S)[None, :] <= q_pos[:, None])).astype(jnp.int8)
    kt, vt = jnp.swapaxes(k, 0, 1)[:1], jnp.swapaxes(v, 0, 1)[:1]
    want = sp.masked_attention_xla(q[:128, :4], kt, vt, mask, scale=SCALE)
    dead = vt.at[:, :512].set(jnp.nan)
    got = sp.masked_attention_tpu(q[:128, :4], kt, dead, mask, q_pos + 1,
                                  scale=SCALE, blocks=True, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # without the blocks' table every key block before the last is computed
    got = sp.masked_attention_tpu(q[:128, :4], kt, dead, mask, q_pos + 1,
                                  scale=SCALE, interpret=True)
    assert bool(jnp.isnan(got).any())


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_decode_steps_pages_are_the_gathered_rectangle(rows, form):
    """A decode step: the chosen pages read where they lie equal one
    softmax over the gathered rectangle of the slot's pages under the
    choice, a KV head's choice for its own heads."""
    q, _, _ = rows
    B, P, n, K = 4, 70, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    pool_k = jax.random.normal(ks[0], (2, P, 64, KVH, HD), jnp.float32)
    pool_v = jax.random.normal(ks[1], (2, P, 64, KVH, HD), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, P))[:B * n].reshape(B, n), jnp.int32)
    lengths = jnp.asarray([700, 0, 1000, 200], jnp.int32)
    new_k = jax.random.normal(ks[2], (B, K, KVH, HD), jnp.float32)
    layer, n_new = 1, 2
    keys = jnp.take(pool_k[layer], tables, axis=0).reshape(B, -1, KVH, HD)
    values = jnp.take(pool_v[layer], tables, axis=0).reshape(B, -1, KVH, HD)
    cached = jnp.arange(n * 64)[None, :] < lengths[:, None]
    old = sp.stride_sums(keys, cached, SIZES.stride)    # zeros where not cached
    qd = q[:B]
    pages, lens, seen = sp.block_decode_pages(
        qd, old, new_k, n_new, tables, lengths, SIZES, scale=SCALE)
    run = sp.block_decode_attention_xla if form == "xla" else (
        lambda *a, **kw: sp.block_decode_attention_tpu(*a, kvh=KVH, **kw,
                                                       interpret=True))
    # the pools as llm/cache.py keeps them for these layers: a page ONE
    # matrix of its (position, KV head) rows
    o, lse = run(qd.reshape(B * KVH, -1, HD),
                 pool_k.reshape(2, P, 64 * KVH, HD),
                 pool_v.reshape(2, P, 64 * KVH, HD),
                 jnp.int32(layer), pages, lens, seen, scale=SCALE)
    o = np.asarray(o).reshape(B, H, HD)
    # by hand: the step's sums (the burst's keys among them), the scores,
    # the reference's choice, attention over the cached tokens of it
    for b in (0, 2, 3):
        at = int(lengths[b]) + n_new - 1
        rows_k = np.concatenate([np.asarray(keys[b, :int(lengths[b])]),
                                 np.asarray(new_k[b, :n_new])])
        sums = sp.stride_sums(jnp.asarray(np.pad(
            rows_k, ((0, n * 64 - len(rows_k)), (0, 0), (0, 0)))),
            jnp.arange(n * 64) < len(rows_k), SIZES.stride)
        c = sp.compressed_keys(sums, SIZES, jnp.float32)
        scores = _scores_by_hand(np.asarray(qd[b:b + 1]), np.asarray(c),
                                 [at])
        mask = _choice_by_hand(scores[:, :, :n], [at])
        group = H // KVH
        for h in range(H):
            keep = np.repeat(mask[h // group, 0], 64) & (
                np.arange(n * 64) < int(lengths[b]))
            s = (np.asarray(qd[b, h]) @ np.asarray(
                keys[b, :, h // group]).T) * SCALE
            s = np.where(keep, s, -np.inf)
            p = np.exp(s - s.max())
            np.testing.assert_allclose(
                o[b, h], (p / p.sum()) @ np.asarray(values[b, :, h // group]),
                rtol=2e-4, atol=2e-5)
    # the idle slot lists nothing
    assert int(lens[2]) == 0 and int(lens[3]) == 0


# ------------------------------------------------- jitted on their own
def _decode_rows(rows):
    """A decode step's operands: 4 slots of 16 pages, one of them idle."""
    q, _, _ = rows
    B, P, n, K = 4, 70, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    pools = tuple(jax.random.normal(key, (2, P, 64 * KVH, HD), jnp.float32)
                  for key in ks[:2])
    tables = jnp.asarray(np.random.default_rng(1).permutation(
        np.arange(1, P))[:B * n].reshape(B, n), jnp.int32)
    lengths = jnp.asarray([700, 0, 1000, 200], jnp.int32)
    keys = jnp.take(pools[0][1], tables, axis=0).reshape(B, -1, KVH, HD)
    old = sp.stride_sums(keys, jnp.arange(n * 64)[None, :] < lengths[:, None],
                         SIZES.stride)
    new_k = jax.random.normal(ks[2], (B, K, KVH, HD), jnp.float32)
    return q[:B], pools, tables, lengths, old, new_k


def _attend_case(rows, sizes):
    q, k, v = rows
    sums, _ = _compressed(k)
    # two tiles of 64 queries, the second without a token: skipped
    q_pos = jnp.arange(S - 128, S).at[64:].set(-1).at[0].set(130)
    return (lambda f, *a: f(*a, sizes, scale=SCALE, tile=64),
            (q[:128], k, v, sums, q_pos))


def _pages_case(rows, sizes):
    q, _, tables, lengths, old, new_k = _decode_rows(rows)
    return (lambda f, *a: f(*a, sizes, scale=SCALE),
            (q, old, new_k, jnp.int32(2), tables, lengths))


def _attention_case(rows, sizes):
    q, pools, tables, lengths, old, new_k = _decode_rows(rows)
    listed = sp.block_decode_pages(q, old, new_k, 2, tables, lengths, sizes,
                                   scale=SCALE)
    return (lambda f, *a: f(*a, kvh=KVH, scale=SCALE),
            (q, *pools, jnp.int32(1), *listed))


CASES = {"block_attend": (sp._block_attend, _attend_case),
         "block_decode_pages": (sp._block_decode_pages, _pages_case),
         "block_decode_attention": (sp.block_decode_attention,
                                    _attention_case)}


@pytest.mark.parametrize("name", CASES)
def test_a_program_that_calls_the_jitted_function_has_its_bodys_bits(
        rows, name):
    """Each of the three is jitted on its own (the block layers of a
    program then share ONE traced body of it). A program that calls it
    computes the same bits as one that holds the undecorated body
    inline, as every program did, on the plain-jax twins the CPU takes.
    (Both sides are a program: run op by op the body's sums are not
    fused and differ in the last bit.)"""
    jitted, case = CASES[name]
    call, operands = case(rows, SIZES)
    got = jax.jit(functools.partial(call, getattr(sp, name)))(*operands)
    want = jax.jit(functools.partial(call, jitted.__wrapped__))(*operands)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        assert a.dtype == b.dtype and bool((a == b).all())


@pytest.mark.parametrize("name", ["block_attend", "block_decode_pages"])
@pytest.mark.parametrize("kind", ["numpy", "jax", "tuple", "tracer"])
def test_sizes_that_are_no_block_sizes_are_refused_by_name(rows, name, kind):
    """The sizes key the jitted function: an array cannot, and a tracer
    would key a new body every call. The public function refuses both
    by name, before the jitted function is asked for anything."""
    jitted, case = CASES[name]

    def call(sizes):
        run, operands = case(rows, sizes)
        return run(getattr(sp, name), *operands)

    before = jitted._cache_size()
    with pytest.raises(TypeError, match=f"{name}: sizes .* BlockSizes"):
        if kind == "tracer":
            jax.jit(call)(jnp.asarray(SIZES))
        else:
            call({"numpy": np.asarray(SIZES), "jax": jnp.asarray(SIZES),
                  "tuple": tuple(SIZES)}[kind])
    assert jitted._cache_size() == before
