"""The indexer's scores, the choice of keys and the product over them
(ops/sparse_attention.py), held to plain float32 formulas at tiny sizes
(top_k 16, 48 to 96 keys, pages of 8), on the CPU; the TPU kernels'
bodies run interpreted beside their plain-jax twins."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import sparse_attention as sparse

J, D, H, KVH, HD, TOP = 4, 16, 4, 2, 16, 16


def _inputs(seed, T, S, batch=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        q=jax.random.normal(ks[0], (batch, T, H, HD), jnp.float32),
        k=jax.random.normal(ks[1], (batch, S, KVH, HD), jnp.float32),
        v=jax.random.normal(ks[2], (batch, S, KVH, HD), jnp.float32),
        qi=jax.random.normal(ks[3], (batch, T, J, D), jnp.float32),
        w=jax.random.normal(ks[4], (batch, T, J), jnp.float32),
        ki=jax.random.normal(ks[5], (batch, S, D), jnp.float32))


def _plain_scores(qi, w, ki):
    """I(t, s) = sum_j w[j] relu(qi[j] . ki_s), in numpy float32."""
    s = np.einsum("btjd,bsd->btjs", np.asarray(qi), np.asarray(ki))
    return (np.maximum(s, 0.0) * np.asarray(w)[..., None]).sum(2)


def _plain_chosen(scores, seen, k):
    """The k seen keys with the largest scores, ties to the earlier one,
    by a stable sort a row."""
    out = np.zeros(scores.shape, bool)
    for r, (row, ok) in enumerate(zip(scores, seen)):
        order = np.argsort(-np.where(ok, row, -np.inf), kind="stable")
        out[r, order[:min(k, int(ok.sum()))]] = True
    return out & seen


def _plain_attention(q, k, v, mask):
    """One softmax a (row, head) over the keys the mask keeps."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    group = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, group, 1), np.repeat(v, group, 1)
    s = np.einsum("thd,shd->hts", q, k) * q.shape[-1] ** -0.5
    s = np.where(mask[None], s, -np.inf)
    with np.errstate(invalid="ignore"):       # a row that keeps no key
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", p, v)


@pytest.mark.parametrize("T, S", [(48, 48), (5, 96), (1, 64)])
def test_scores_equal_the_plain_sum(T, S):
    x = _inputs(0, T, S, batch=2)
    got = sparse.index_scores(x["qi"], x["w"], x["ki"])
    np.testing.assert_allclose(got, _plain_scores(x["qi"], x["w"], x["ki"]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T, S, last", [(48, 256, None), (8, 128, 70),
                                        (3, 128, 128)])
def test_index_kernel_is_its_plain_twin(T, S, last):
    """The kernel's body, interpreted; with ``last`` the blocks no row
    sees are skipped and compared nowhere."""
    x = _inputs(1, T, S, batch=2)
    limit = None if last is None else jnp.full((2, T), last, jnp.int32)
    got = sparse.index_scores_tpu(x["qi"], x["w"], x["ki"], limit,
                                  interpret=True)
    want = sparse.index_scores_xla(x["qi"], x["w"], x["ki"])
    upto = S if last is None else last
    np.testing.assert_allclose(got[..., :upto], want[..., :upto],
                               rtol=1e-5, atol=1e-5)


def _limits(T, S, first=0):
    """Causal: the query at row t sees keys 0 .. first + t."""
    return jnp.minimum(first + jnp.arange(T) + 1, S).astype(jnp.int32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_chosen_set_is_the_plain_one(seed):
    T = S = 96
    scores = jax.random.normal(jax.random.PRNGKey(seed), (T, S), jnp.float32)
    lim = _limits(T, S)
    got = np.asarray(sparse.choose(scores, lim, top_k=TOP)) > 0
    seen = np.arange(S)[None] < np.asarray(lim)[:, None]
    want = _plain_chosen(np.asarray(scores), seen, TOP)
    assert (got == want).all()
    # a row that sees at most top_k keys keeps them all
    assert (got[:TOP] == seen[:TOP]).all()
    assert (got.sum(-1) == np.minimum(np.arange(T) + 1, TOP)).all()


def test_ties_go_to_the_earlier_position():
    """Scores of a few values only (zeros among them, as a ReLU makes):
    far more tie across the 16th place than are needed."""
    rng = np.random.default_rng(0)
    scores = rng.integers(-1, 3, (40, 64)).astype(np.float32)
    scores[3] = 0.0                               # one value throughout
    scores[4, ::2] = -0.0                         # -0.0 ties with 0.0
    lim = jnp.full((40,), 64, jnp.int32).at[5].set(10).at[6].set(0)
    got = np.asarray(sparse.choose(jnp.asarray(scores), lim, top_k=TOP)) > 0
    seen = np.arange(64)[None] < np.asarray(lim)[:, None]
    want = _plain_chosen(np.where(scores == 0, 0.0, scores), seen, TOP)
    assert (got == want).all()
    assert got[3, :TOP].all() and not got[3, TOP:].any()
    assert got[5].sum() == 10 and got[6].sum() == 0


def test_two_segments_are_one_order_of_positions():
    """A chunk's queries: the cached span below the chunk's start, then
    the chunk's own rows up to themselves, behind a gap of padding."""
    T, past, start, S = 24, 40, 64, 64 + 24
    scores = jax.random.normal(jax.random.PRNGKey(3), (T, S), jnp.float32)
    lim_a = jnp.full((T,), past, jnp.int32)
    lim_b = jnp.arange(T, dtype=jnp.int32) + 1
    got = np.asarray(sparse.choose(scores, lim_a, lim_b, start,
                                   top_k=TOP)) > 0
    idx = np.arange(S)[None]
    seen = (idx < past) | ((idx >= start)
                           & (idx < start + np.asarray(lim_b)[:, None]))
    assert (got == _plain_chosen(np.asarray(scores), seen, TOP)).all()


def _mixed_scores(rng, R, S):
    """Whole numbers, so that many tie, with fractions on half of them."""
    return (rng.integers(-3, 4, (R, S)).astype(np.float32)
            + (rng.random((R, S)) < 0.5)
            * rng.standard_normal((R, S)).astype(np.float32))


@pytest.mark.parametrize("start_b", [None, 128])
def test_select_kernel_is_its_plain_twin(start_b):
    rng = np.random.default_rng(1)
    R, S = 40, 256
    scores = jnp.asarray(_mixed_scores(rng, R, S))
    lim = jnp.stack([jnp.asarray(rng.integers(0, 129, R), jnp.int32),
                     jnp.asarray(rng.integers(0, 60, R), jnp.int32)
                     * (start_b is not None)], -1)
    got = sparse.choose_tpu(scores, lim, k=TOP, start_b=start_b,
                            interpret=True)
    want = sparse.choose_xla(scores, lim, k=TOP, start_b=start_b)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert np.asarray(want).sum() > 0


def _ends_inside_a_chunk(rng):
    # rows of three blocks that see 130 to 345 keys of 640: their last
    # chunks of 128 are seen in part, the chunks behind them by nobody
    R, S = 72, 640
    return (_mixed_scores(rng, R, S),
            np.stack([130 + 3 * np.arange(R), np.zeros(R, int)], -1),
            TOP, None, 128)


def _nothing_to_count_beside_a_block_that_counts(rng):
    # the first block's rows all see at most top_k keys (its mask is
    # what they see), the second's see more, the third's none
    R, S = 96, 512
    seen = np.concatenate([np.arange(32) % (TOP + 1), 200 + np.arange(32),
                           np.zeros(32, int)])
    return (_mixed_scores(rng, R, S),
            np.stack([seen, np.zeros(R, int)], -1), TOP, None, 128)


def _a_decode_step(rng):
    # 8 slots at the served sizes: a span of two chunks and the burst's
    # own 128 lanes behind it, 3 of them visible; slots that cache
    # nothing, one key, a chunk, a chunk and one, the whole span
    lengths = np.array([0, 1, 2048, 2049, 4096, 3000, 2047, 100])
    return (rng.standard_normal((8, 4096 + 128)).astype(np.float32),
            np.stack([lengths, np.full(8, 3)], -1), 2048, 4096, 2048)


def _a_short_first_segment(rng):
    # a chunk's queries over a cached span of which under a chunk is
    # seen, and their own rows behind it (the second segment, counted
    # whole): nothing cached, under top_k, a part of the first chunk
    R = 64
    return (_mixed_scores(rng, R, 256 + 128),
            np.stack([np.repeat([0, 5, 100, 256], 16),
                      np.arange(R) % 128 + 1], -1), TOP, 256, 128)


def _ties_across_a_chunks_edge(rng):
    # ten keys above a value that 17 keys share, from column 124 to 140:
    # the 6 taken are columns 124 to 129, either side of the edge at 128;
    # and a row of one value throughout
    R, S = 32, 384
    scores = rng.random((R, S)).astype(np.float32)
    scores[:, 124:141] = 2.0
    scores[:, 300:310] = 3.0 + np.arange(10)
    scores[1] = 0.5
    scores[2, ::2] = -0.0
    scores[2, 1::2] = 0.0
    return (scores, np.stack([np.full(R, 330), np.zeros(R, int)], -1),
            TOP, None, 128)


def _anything_behind_the_last_visible_chunk(rng):
    # the key blocks no row of a tile sees are not written by the scores'
    # kernel: NaN and infinities of both signs there, and in the unseen
    # part of the last visible chunk
    R, S = 64, 640
    scores = _mixed_scores(rng, R, S)
    seen = 140 + np.arange(R)
    for r in range(R):
        scores[r, seen[r]:] = (np.nan, np.inf, -np.inf, -np.nan)[r % 4]
    return scores, np.stack([seen, np.zeros(R, int)], -1), TOP, None, 128


@pytest.mark.parametrize("case", [
    _ends_inside_a_chunk, _nothing_to_count_beside_a_block_that_counts,
    _a_decode_step, _a_short_first_segment, _ties_across_a_chunks_edge,
    _anything_behind_the_last_visible_chunk], ids=lambda f: f.__name__)
def test_select_kernel_counts_over_what_its_rows_see(case):
    """Where the first segment is more than one chunk the kernel counts
    over the chunks some row of a block sees and no other: the same
    mask as the plain choice over the whole row, to the bit."""
    scores, lim, k, start_b, chunk = case(np.random.default_rng(2))
    scores, lim = jnp.asarray(scores), jnp.asarray(lim, jnp.int32)
    got = sparse.choose_tpu(scores, lim, k=k, start_b=start_b, chunk=chunk,
                            interpret=True)
    want = np.asarray(sparse.choose_xla(scores, lim, k=k, start_b=start_b))
    assert (np.asarray(got) == want).all()
    idx = np.arange(scores.shape[1])[None]
    seen = np.asarray(sparse.visible(idx, lim[:, :1], lim[:, 1:], start_b))
    assert (want.sum(-1) == np.minimum(seen.sum(-1), k)).all()
    assert want.sum() > 0


def test_counted_keys_is_the_kernels_rule_by_hand():
    """A prompt of 5,000 tokens and two bursts, at the served top_k: rows
    in blocks of 32, columns in chunks of 2,048, nothing where a block
    sees at most 2,048 keys."""
    import types

    from ray_tpu.llm.kinds import indexed

    cfg = types.SimpleNamespace(sparse_top_k=2048)
    counters = dict.fromkeys(indexed.COUNTERS, 0)
    indexed.count(cfg, counters, 64, 0, 5000, False)
    # blocks 64 to 127 see 2,080 to 4,096 keys: 4,096 columns a row;
    # blocks 128 to 155 and the last block's 8 rows see up to 5,000: 6,144
    assert counters["counted_keys"] == (64 * 32 * 4096 + 28 * 32 * 6144
                                        + 8 * 6144) == 13942784
    # against rows x the bucket's row (10 tiles of 512 x 8,192)
    assert round(counters["counted_keys"] / (5120 * 8192), 3) == 0.332
    # a burst of 8 steps behind it: 5,000 cached rounded up, and the
    # burst's own 128 lanes
    indexed.count(cfg, counters, 64, 5000, 5008, True)
    assert counters["counted_keys"] == 13942784 + 8 * (6144 + 128)
    # a burst across top_k: the steps at positions 2,040 to 2,047 see at
    # most 2,048 keys and count nothing
    before = counters["counted_keys"]
    indexed.count(cfg, counters, 64, 2040, 2056, True)
    assert counters["counted_keys"] - before == 8 * (2048 + 128)


def test_at_most_top_k_visible_keys_is_the_dense_path():
    """With no more keys than top_k nothing is scored or counted and the
    output is causal attention's."""
    from ray_tpu.ops.attention import naive_attention

    x = _inputs(4, TOP, TOP)
    got = sparse.attend(x["q"], x["k"], x["v"], x["qi"], x["w"], x["ki"],
                        _limits(TOP, TOP)[None], top_k=TOP, scale=HD ** -0.5)
    want = naive_attention(x["q"], x["k"], x["v"], causal=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T, tile", [(96, 512), (96, 32)])
def test_attend_is_one_softmax_over_the_chosen_keys(T, tile):
    """Whole and a tile of queries at a time (a tile without a token is
    skipped and comes back as zeros)."""
    x = _inputs(5, T, T)
    length = 70
    lim = jnp.where(jnp.arange(T) < length, jnp.arange(T) + 1, 0)[None]
    got = np.asarray(sparse.attend(
        x["q"], x["k"], x["v"], x["qi"], x["w"], x["ki"],
        lim.astype(jnp.int32), top_k=TOP, scale=HD ** -0.5, tile=tile))[0]
    scores = _plain_scores(x["qi"], x["w"], x["ki"])[0]
    seen = np.arange(T)[None] < np.asarray(lim)[0][:, None]
    mask = _plain_chosen(scores, seen, TOP)
    want = _plain_attention(x["q"][0], x["k"][0], x["v"][0],
                            mask)[:length]
    np.testing.assert_allclose(got[:length], want, rtol=1e-4, atol=1e-5)
    assert not got[length:].any()


def test_prefill_kernel_is_its_plain_twin():
    T, S = 128, 256
    x = _inputs(6, T, S)
    rng = np.random.default_rng(2)
    mask = jnp.asarray(rng.random((T, S)) < 0.2, jnp.int8).at[7].set(0)
    last = jnp.full((T,), 200, jnp.int32)
    mask = mask * (jnp.arange(S)[None] < 200)
    kt, vt = jnp.swapaxes(x["k"][0], 0, 1), jnp.swapaxes(x["v"][0], 0, 1)
    got = sparse.masked_attention_tpu(x["q"][0], kt, vt, mask, last,
                                      scale=HD ** -0.5, interpret=True)
    want = sparse.masked_attention_xla(x["q"][0], kt, vt, mask,
                                       scale=HD ** -0.5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[7]).any()        # a row that keeps no key


def _softmax_over(q, keys, values, scale):
    """One softmax a head over ``keys`` [m, KVH, HD], written out in
    NumPy: (o [H, HD], lse [H]); no key: zeros and -inf."""
    q, keys, values = (np.asarray(a, np.float64) for a in (q, keys, values))
    o, lse = np.zeros(q.shape), np.full(q.shape[0], -np.inf)
    for h in range(q.shape[0] if len(keys) else 0):
        s = keys[:, h // (H // KVH)] @ q[h] * scale
        lse[h] = s.max() + np.log(np.exp(s - s.max()).sum())
        o[h] = np.exp(s - lse[h]) @ values[:, h // (H // KVH)]
    return o, lse


DECODE_PAGE, DECODE_SPAN = 8, 8         # 8 pages of 8 positions a slot


def _random_mask(rng, lengths, ones):
    mask = np.zeros((len(lengths), DECODE_PAGE * DECODE_SPAN), np.int8)
    for b, n in enumerate(lengths):
        mask[b, rng.choice(n, min(ones, n), replace=False)] = 1
    return mask


def _whole_pages_skipped(rng, lengths, ones):
    """Ones in the slot's pages 1 and 4 alone (and never past its end)."""
    mask = np.zeros((len(lengths), DECODE_PAGE * DECODE_SPAN), np.int8)
    mask[:, 8:16] = mask[:, 32:40] = 1
    return mask * (np.arange(mask.shape[1])[None] < np.asarray(
        lengths)[:, None])


@pytest.mark.parametrize("lengths, mask_of, layer, own", [
    ((37, 50, 21), _random_mask, 0, 0),         # ends inside a page
    ((32, 64, 8), _random_mask, 0, 0),          # on a page's edge
    ((45, 0, 0), _random_mask, 0, 0),           # slots that do not decode
    ((10, 64, 5), _random_mask, 0, 0),          # fewer than top_k: all seen
    ((64, 47, 12), _whole_pages_skipped, 0, 0),
    ((37, 50, 21), _random_mask, 1, 0),         # a traced layer other than 0
    ((37, 50, 0), _random_mask, 1, 1),          # joined with the burst's rows
    ((64, 29, 9), _whole_pages_skipped, 0, 3),
])
def test_decode_kernel_is_its_plain_twin_and_a_softmax_written_out(
        monkeypatch, lengths, mask_of, layer, own):
    """The decode product over each slot's own pages where they lie, the
    kernel's body interpreted (two pages a step: four steps a slot),
    against its plain twin and against a softmax written out in NumPy
    over the mask's ones. A table's unused entries are page 0, whose
    rows are random here and must not be read into any sum; with ``own``
    burst rows the join by the log-sum-exp, one of them not chosen."""
    monkeypatch.setattr(sparse, "_STEP_BYTES",
                        2 * DECODE_PAGE * KVH * HD * 4)
    rng = np.random.default_rng(sum(lengths) + layer + own)
    B, span = len(lengths), DECODE_PAGE * DECODE_SPAN
    P = 1 + B * DECODE_SPAN
    pool_k, pool_v = (jnp.asarray(rng.standard_normal(
        (2, P, DECODE_PAGE, KVH, HD)), jnp.float32) for _ in range(2))
    tables = rng.permutation(np.arange(1, P)).reshape(B, -1).astype(np.int32)
    for b, n in enumerate(lengths):
        tables[b, -(-n // DECODE_PAGE):] = 0
    mask = mask_of(rng, lengths, TOP)
    q = jnp.asarray(rng.standard_normal((B, H, HD)), jnp.float32)
    args = (q, pool_k, pool_v, jnp.int32(layer), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(mask))
    scale = HD ** -0.5
    got = jax.jit(functools.partial(
        sparse.decode_attention_tpu, scale=scale, interpret=True))(*args)
    twin = sparse.decode_attention_xla(*args, scale=scale)
    new_k, new_v = (rng.standard_normal((B, 4, KVH, HD)).astype(np.float32)
                    for _ in range(2))
    chosen = np.zeros((B, 4), bool)
    chosen[:, :own] = True
    chosen[:, own // 2] = False                 # one of three is not chosen
    if own:
        got, twin = (sparse.join_new_rows(
            o, lse, q, jnp.asarray(new_k), jnp.asarray(new_v),
            jnp.asarray(chosen), scale=scale) for o, lse in (got, twin))
    for b, n in enumerate(lengths):
        at = np.nonzero(mask[b, :n])[0]
        keys, values = (np.concatenate([
            np.asarray(pool)[layer, tables[b, at // DECODE_PAGE],
                             at % DECODE_PAGE], new[b, chosen[b]]])
            for pool, new in ((pool_k, new_k), (pool_v, new_v)))
        o, lse = _softmax_over(q[b], keys, values, scale)
        if own:
            np.testing.assert_allclose(got[b], o, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(twin[b], o, rtol=1e-5, atol=1e-5)
            continue
        for have in (got, twin):
            np.testing.assert_allclose(have[0][b], o, rtol=1e-5, atol=1e-5)
            if len(at):
                np.testing.assert_allclose(have[1][b], lse, rtol=1e-5)
            else:           # a slot that does not decode
                assert not np.asarray(have[0][b]).any()
                assert (np.asarray(have[1][b]) < -1e29).all()
    assert mask[:, :span].sum() > 0


@pytest.mark.parametrize("lengths", [(37, 50, 21), (64, 0, 8)])
def test_decode_over_pools_kept_as_one_matrix_a_page(monkeypatch, lengths):
    """``kvh=``: the pools handed over as [L, P, page * kvh, hd] already
    (a kind that keeps a page as ONE matrix of its (position, KV head)
    rows) give what the [L, P, page, kvh, hd] pools give, the kernel
    interpreted and its plain twin."""
    monkeypatch.setattr(sparse, "_STEP_BYTES",
                        2 * DECODE_PAGE * KVH * HD * 4)
    rng = np.random.default_rng(sum(lengths))
    B, span = len(lengths), DECODE_PAGE * DECODE_SPAN
    P = 1 + B * DECODE_SPAN
    pool_k, pool_v = (jnp.asarray(rng.standard_normal(
        (2, P, DECODE_PAGE, KVH, HD)), jnp.float32) for _ in range(2))
    tables = rng.permutation(np.arange(1, P)).reshape(B, -1).astype(np.int32)
    rest = (jnp.int32(1), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), jnp.ones((B, span), jnp.int8))
    q = jnp.asarray(rng.standard_normal((B, H, HD)), jnp.float32)
    scale = HD ** -0.5
    want = sparse.decode_attention_xla(q, pool_k, pool_v, *rest, scale=scale)
    flat = tuple(pool.reshape(2, P, DECODE_PAGE * KVH, HD)
                 for pool in (pool_k, pool_v))
    for have in (
            jax.jit(functools.partial(
                sparse.decode_attention_tpu, scale=scale, kvh=KVH,
                interpret=True))(q, *flat, *rest),
            sparse.decode_attention_xla(q, *flat, *rest, scale=scale,
                                        kvh=KVH)):
        for a, b in zip(have, want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def _paged(rows, tables, pages, page):
    """rows [B, S, ...] -> a pool [1, pages, page, ...] under ``tables``."""
    B, S = rows.shape[:2]
    pool = np.zeros((1, pages, page, *rows.shape[2:]), np.float32)
    for b in range(B):
        for s in range(S):
            pool[0, tables[b, s // page], s % page] = rows[b, s]
    return jnp.asarray(pool)


@pytest.mark.parametrize("new_rows", [1, 3])
def test_decode_over_pages_is_prefills_last_row(new_rows):
    """One query a slot over its own pages (listed out of order), the
    last ``new_rows`` keys not yet in the cache but in the burst's
    scratch: the row whole-prompt attention gives that query."""
    page, S, B = 8, 64, 2
    x = _inputs(7, S, S, batch=B)
    lim = jnp.broadcast_to(_limits(S, S)[None], (B, S))
    want = np.asarray(sparse.attend(
        x["q"], x["k"], x["v"], x["qi"], x["w"], x["ki"], lim, top_k=TOP,
        scale=HD ** -0.5))[:, -1]
    cached = S - new_rows
    rng = np.random.default_rng(4)
    tables = rng.permutation(np.arange(1, 1 + B * S // page)).reshape(
        B, -1).astype(np.int32)
    pools = [_paged(np.asarray(a)[:, :cached], tables, 1 + B * S // page,
                    page) for a in (x["k"], x["v"], jnp.pad(
                        x["ki"], ((0, 0), (0, 0), (0, 128 - D))))]
    K = 4
    scratch = [jnp.pad(a[:, cached:], ((0, 0), (0, K - new_rows))
                       + ((0, 0),) * (a.ndim - 2))
               for a in (x["k"], x["v"], jnp.pad(
                   x["ki"], ((0, 0), (0, 0), (0, 128 - D))))]
    lengths = jnp.full((B,), cached, jnp.int32)
    chosen, own = sparse.decode_chosen(
        jnp.pad(x["qi"][:, -1], ((0, 0), (0, 0), (0, 128 - D))),
        x["w"][:, -1], pools[2], jnp.int32(0), jnp.asarray(tables),
        lengths, scratch[2], jnp.int32(new_rows), top_k=TOP)
    assert (np.asarray(chosen).sum(-1) + np.asarray(own).sum(-1)
            == TOP).all()
    o, lse = sparse.decode_attention(
        x["q"][:, -1], pools[0], pools[1], jnp.int32(0),
        jnp.asarray(tables), lengths, chosen, scale=HD ** -0.5)
    got = sparse.join_new_rows(o, lse, x["q"][:, -1], scratch[0],
                               scratch[1], own, scale=HD ** -0.5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# --- the programs: llm/runner.py through the three pools ---
@functools.cache
def _model():
    from ray_tpu.models import init_params
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.ops import rope_frequencies

    cfg = LlamaConfig(
        vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, mlp_dim=96,
        max_seq=256, dtype=jnp.float32, remat=False, head_size=16,
        qk_norm=True, qk_norm_by_head=True, indexer_heads=J, indexer_dim=D,
        sparse_top_k=TOP)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cos, sin = rope_frequencies(cfg.rope_dim, cfg.max_seq, cfg.rope_theta)
    return cfg, params, cos, sin


def _whole_prefill(tokens, length, bucket, page=8):
    from ray_tpu.llm import runner
    from ray_tpu.llm.cache import init_kv_cache

    cfg, params, cos, sin = _model()
    cache = init_kv_cache(cfg, 1 + 128 // page, page)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :length] = tokens[:length]
    table = jnp.arange(1, 1 + 128 // page, dtype=jnp.int32)[None]
    logits, k, v, _counts, i = runner.prefill(
        params, cache.k, cache.v, jnp.asarray(padded),
        jnp.asarray([length], jnp.int32), table, cos, sin, None, cache.i,
        cfg=cfg)
    return np.asarray(logits)[0], (k, v, i), table


TOKENS = np.random.default_rng(5).integers(0, 128, 128).astype(np.int32)


@pytest.mark.parametrize("chunk", [16, 32])
def test_a_chunked_prefill_is_the_whole_one(chunk):
    """The chunks' queries score the cached rows and their own, and the
    three pools hold what the whole prompt's prefill wrote."""
    from ray_tpu.llm import runner
    from ray_tpu.llm.cache import init_kv_cache

    cfg, params, cos, sin = _model()
    length = 90
    want, pools, table = _whole_prefill(TOKENS, length, 128)
    cache = init_kv_cache(cfg, 17, 8)
    k, v, i = cache.k, cache.v, cache.i
    for start in range(0, length, chunk):
        n = min(chunk, length - start)
        tokens = np.zeros((1, chunk), np.int32)
        tokens[0, :n] = TOKENS[start:start + n]
        logits, k, v, _counts, i = runner.prefill_chunk(
            params, k, v, jnp.asarray(tokens), jnp.int32(start),
            jnp.int32(n), table, cos, sin, i, cfg=cfg)
    np.testing.assert_allclose(np.asarray(logits)[0], want, rtol=1e-4,
                               atol=1e-4)
    rows = length // 8            # whole pages of the prompt
    for got, whole in zip((k, v, i), pools):
        np.testing.assert_allclose(got[:, 1:1 + rows], whole[:, 1:1 + rows],
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("length", [60, 61])
def test_a_burst_crossing_a_page_decodes_prefills_tokens(length):
    """Four fused steps from a position whose burst ends on another page:
    every step scores the slot's pages and the rows the burst has written
    so far; the tokens are those a whole prefill of the longer sequence
    would choose, step by step."""
    from ray_tpu.llm import runner

    cfg, params, cos, sin = _model()
    logits, (k, v, i), table = _whole_prefill(TOKENS, length, 64)
    first = int(np.argmax(logits))
    out, k, v, _counts, i = runner.decode_burst(
        params, k, v, jnp.asarray([first], jnp.int32),
        jnp.asarray([length], jnp.int32), table, jnp.asarray([True]), cos,
        sin, 0, jnp.ones(1), jnp.zeros(1, jnp.int32), jnp.ones(1), None,
        table[:, :8], jnp.int32(4), i, cfg=cfg, n_steps=4, greedy=True)
    got = np.asarray(out)[:, 0].tolist()
    sequence = list(TOKENS[:length]) + [first]
    for step in range(4):
        want, _pools, _table = _whole_prefill(
            np.asarray(sequence, np.int32), len(sequence), 128)
        assert got[step] == int(np.argmax(want)), step
        sequence.append(got[step])
    # the burst's rows lie where a prefill of the longer sequence puts them
    _logits, pools, _table = _whole_prefill(
        np.asarray(sequence, np.int32), length + 4, 128)
    for mine, whole in zip((k, v, i), pools):
        np.testing.assert_allclose(
            mine[:, 1:9].reshape(2, 64, -1)[:, :length + 4],
            whole[:, 1:9].reshape(2, 64, -1)[:, :length + 4],
            rtol=1e-4, atol=1e-5)


def test_verify_step_selects_through_the_pages():
    """A speculative window's tokens are written, then scored and chosen
    from through the pages: its predictions are a whole prefill's."""
    from ray_tpu.llm import runner

    cfg, params, cos, sin = _model()
    length, window = 70, 5
    _logits, (k, v, i), table = _whole_prefill(TOKENS, length, 128)
    positions = (length + np.arange(window))[None].astype(np.int32)
    tgt, _s0, *_ = runner.verify_step(
        params, k, v, jnp.asarray(TOKENS[None, length:length + window]),
        jnp.asarray(positions), table, cos, sin, 0, jnp.ones(1),
        jnp.zeros(1, jnp.int32), jnp.ones(1), i, cfg=cfg, greedy=True)
    for j in range(window):
        want, _pools, _table = _whole_prefill(TOKENS, length + j + 1, 128)
        assert int(tgt[0, j]) == int(np.argmax(want)), j
