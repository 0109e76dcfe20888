"""Attention over the keys an indexer chooses (DeepSeek-V3.2's lightning
indexer, as Keye-VL-2.0's ``sa_config`` sizes it).

Beside a layer's heads sits an indexer: ``J`` small query heads ``qI`` of
``d`` (16 of 64), ONE key ``kI`` of ``d`` a token that all of them share,
and a weight a head ``w``. A query at position t gives every visible key
s the score

    I(t, s) = sum_j w_t[j] * relu(qI_t[j] . kI_s)          (float32)

and attends, in all its heads, over the ``top_k`` (2,048) visible keys
with the largest scores (ties to the earlier position; all of them where
fewer are visible): one softmax over the chosen keys, nothing else.

Three steps, each under its own ``jax.named_scope`` and, on a TPU, its
own named Pallas kernel (``harness/trace.py`` keeps those names):

  * ``index_scores`` (``rt.attn.index``; ``rt_sparse_index``): the
    scores of a tile of queries against the keys, a block at a time: the
    16 products of a block and their weighted, rectified sum stay in
    VMEM, so no [T, J, S] array exists, and a block of keys that no query
    of the tile can see is not computed.
  * ``choose`` (``rt.attn.select``; ``rt_sparse_select``): which keys a
    row keeps, as a mask. No sort: the 2,048th largest score of a row is
    found by COUNTING, a bit of its (order-preserving) integer image at a
    time, 32 counts over the row while it sits in VMEM, then, only where
    scores tie across the 2,048th place, the position up to which the
    tied ones are taken. ``lax.top_k`` at k = 2,048 sorts rows of up to
    32k on a TPU; a count is a compare and an add.
  * ``masked_attention`` (``rt.attn.sparse``; ``flash_sparse_fwd``):
    whole-prompt prefill's product over the chosen keys: a flash forward
    over key blocks with the mask's block beside each, every head of a
    query block in one grid step so that the mask is read once. It
    computes every visible (query, key) pair and masks: the operations
    the equations need are a share of that (``sparse_attention_flops``
    in the family counts what is needed, so the roofline reads low).

``attend`` runs the three over a tile of queries at a time (never an
[L, L] array: at 32k a layer's scores are 4 GB in float32), for every
program that has the keys side by side: whole-prompt ``prefill``,
``prefill_chunk`` (the cached span, then the chunk's own rows),
``verify_step``. ``decode_chosen`` is a decode step's: the indexer's
rows of each slot's own pages are gathered (128 bytes a position, not
the 2 KB of its K and V), scored and chosen from, the chosen positions
are counted out of the mask (``chosen_rows``: cumulative counts, no
sort, no scatter) and ONLY those K and V rows are fetched from the pool
(``gather_rows``): a slot's K and V span is never copied.

Where a query sees at most ``top_k`` keys every visible key is chosen
and the result is plain causal attention's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30
INT_MIN = -2 ** 31
# the kernels' names in a compiled program and a device trace; inside
# ``decode_burst`` the first two carry ``DECODE`` behind them
INDEX_KERNEL = "rt_sparse_index"
SELECT_KERNEL = "rt_sparse_select"
PREFILL_KERNEL = "flash_sparse_fwd"
DECODE = "_decode"
# queries ``attend`` takes at a time: their scores are [tile, S] float32
QUERY_TILE = 512
_VMEM = 100 * 1024 * 1024


def _block(n: int, most: int, least: int = 128) -> int:
    """The largest power of two <= ``most`` that divides ``n``; ``n``
    itself where none of at least ``least`` does (a block that is the
    whole dimension is always allowed)."""
    b = most
    while b >= least:
        if n % b == 0:
            return b
        b //= 2
    return n


# ------------------------------------------------------------------ scores
def index_scores_xla(qi, w, ki, last=None):
    del last
    s = jnp.einsum("btjd,bsd->btjs", qi, ki,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * w.astype(jnp.float32)[..., None]).sum(2)


def _index_kernel(need_ref, q_ref, w_ref, k_ref, o_ref, *, heads):
    from jax.experimental import pallas as pl

    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j < need_ref[b, i])
    def _():
        keys = k_ref[...]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[h], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + w_ref[h] * jnp.maximum(s, 0.0)
        o_ref[...] = acc


def index_scores_tpu(qi, w, ki, last=None, *, name=INDEX_KERNEL,
                     interpret=False):
    """The kernel: grid (batch, query block, key block); a step holds a
    block of queries of all ``J`` heads and a block of keys and writes
    the block's scores. ``last``: see ``index_scores``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, J, d = qi.shape
    S = ki.shape[1]
    pad = (-T) % 8
    if pad:     # a product of fewer than 8 rows: rows of zeros beside them
        qi = jnp.pad(qi, ((0, 0), (0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)))
        if last is not None:
            last = jnp.pad(last, ((0, 0), (0, pad)))
    Tp = T + pad
    tq, tk = _block(Tp, 256, 8), _block(S, 2048)
    if last is None:
        need = jnp.full((B, Tp // tq), S // tk, jnp.int32)
    else:
        need = -(-last.reshape(B, Tp // tq, tq).max(-1) // tk)

    def keys_at(b, i, j, need):
        return b, jnp.minimum(j, jnp.maximum(need[b, i] - 1, 0)), 0

    out = pl.pallas_call(
        functools.partial(_index_kernel, heads=J),
        out_shape=jax.ShapeDtypeStruct((B, Tp, S), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((None, J, tq, d), lambda b, i, j, _: (b, 0, i, 0)),
                pl.BlockSpec((None, J, tq, 1), lambda b, i, j, _: (b, 0, i, 0)),
                pl.BlockSpec((None, tk, d), keys_at)],
            out_specs=pl.BlockSpec((None, tq, tk),
                                   lambda b, i, j, _: (b, i, j)),
            grid=(B, Tp // tq, S // tk)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret, name=name,
    )(need.astype(jnp.int32), jnp.swapaxes(qi, 1, 2),
      jnp.swapaxes(w.astype(jnp.float32), 1, 2)[..., None], ki)
    return out[:, :T] if pad else out


def index_scores(qi, w, ki, last=None, *, name=INDEX_KERNEL):
    """qi [B, T, J, d]; w [B, T, J]; ki [B, S, d] -> float32 [B, T, S]:
    ``sum_j w[j] relu(qi[j] . ki)``, the products accumulated in float32.
    ``last`` int32 [B, T]: a row sees none of the keys from ``last`` on;
    on a TPU the blocks of keys that no row of a query block sees are
    not computed and hold anything (``choose`` never reads them). None:
    every key is scored."""
    with jax.named_scope("rt.attn.index"):
        if last is None:
            last = jnp.full(qi.shape[:2], ki.shape[1], jnp.int32)
        return jax.lax.platform_dependent(
            qi, w, ki, last,
            tpu=functools.partial(index_scores_tpu, name=name),
            default=index_scores_xla)


# ------------------------------------------------------------------ choice
def visible(idx, lim_a, lim_b=None, start_b=None):
    """Which key indices ``idx`` a row sees: those below ``lim_a`` and,
    with a second segment, those in [start_b, start_b + lim_b)."""
    seen = idx < lim_a
    if start_b is not None:
        seen = seen | ((idx >= start_b) & (idx < start_b + lim_b))
    return seen


def _sort_key(s):
    """float32 -> int32 whose signed order is the floats' (-0.0 as 0.0)."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(s == 0.0, 0.0, s), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def chosen(s, seen, idx, k: int):
    """Rows' choice, on values (a kernel's block or whole arrays): s
    float32 [R, S]; seen bool [R, S]; idx int32 [R, S], a key's place in
    the row's order of positions -> bool [R, S]: the ``k`` seen keys
    with the largest scores, ties to the smaller ``idx``; every seen key
    where fewer than ``k`` are.

    By counting. The k-th largest key's integer image is built a bit at
    a time from the top: a bit stays set if at least ``k`` keys are >=
    the value so far. Keys above it are taken; of the keys equal to it
    the first ``k - (those above)`` by ``idx``, found by halving on
    ``idx`` (only where more tie than are needed)."""
    key = jnp.where(seen, _sort_key(s), jnp.int32(INT_MIN))
    rows = key.shape[:-1] + (1,)
    low = jnp.int32(INT_MIN)

    def count(m):
        # exact in float32 up to 2**24 keys a row
        return m.astype(jnp.float32).sum(-1, keepdims=True)

    def bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(count(key >= (cand ^ low)) >= k, cand, prefix)

    threshold = jax.lax.fori_loop(0, 32, bit,
                                  jnp.zeros(rows, jnp.int32)) ^ low
    above, at = key > threshold, (key == threshold) & seen
    need = k - count(above)
    steps = max(1, (key.shape[-1] - 1).bit_length())

    def halve(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) >> 1
        enough = count(at & (idx <= mid)) >= need
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    def tied():
        return jax.lax.fori_loop(
            0, steps, halve,
            (jnp.zeros(rows, jnp.int32),
             jnp.full(rows, key.shape[-1] - 1, jnp.int32)))[0]

    upto = jax.lax.cond(jnp.any(count(at) > need), tied,
                        lambda: jnp.full(rows, key.shape[-1], jnp.int32))
    return seen & (above | (at & (idx <= upto)))


def choose_xla(scores, lim, *, k, start_b):
    idx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    seen = visible(idx, lim[:, :1], lim[:, 1:], start_b)
    return chosen(scores, seen, idx, k).astype(jnp.int8)


def _select_kernel(lim_ref, s_ref, m_ref, *, k, start_b):
    s = s_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = visible(idx, lim_ref[:, 0:1], lim_ref[:, 1:2], start_b)
    m_ref[...] = chosen(s, seen, idx, k).astype(jnp.int32).astype(
        m_ref.dtype)


_SELECT_ROWS = 32       # an int8 tile's rows


def choose_tpu(scores, lim, *, k, start_b, name=SELECT_KERNEL,
               interpret=False):
    """The kernel: grid (row blocks of 32); a step holds its rows' whole
    scores in VMEM and counts over them there. At most 8 rows (a decode
    step's slots) are one block of 8 and their mask is int32, a tile of
    which has 8 rows: padded to 32 the step would count over 24 rows
    that are nobody's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, S = scores.shape
    rows, dtype = (8, jnp.int32) if R <= 8 else (_SELECT_ROWS, jnp.int8)
    pad = (-R) % rows
    if pad:
        scores = jnp.pad(scores, ((0, pad), (0, 0)))
        lim = jnp.pad(lim, ((0, pad), (0, 0)))
    mask = pl.pallas_call(
        functools.partial(_select_kernel, k=k, start_b=start_b),
        out_shape=jax.ShapeDtypeStruct(scores.shape, dtype),
        grid=(scores.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, 2), lambda i: (i, 0)),
                  pl.BlockSpec((rows, S), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, S), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM),
        interpret=interpret, name=name,
    )(lim, scores)
    return mask[:R] if pad else mask


def choose(scores, lim_a, lim_b=None, start_b=None, *, top_k: int,
           name=SELECT_KERNEL):
    """scores float32 [..., S]; lim_a (and lim_b) int32 [...]: what a row
    sees (``visible``) -> int8 [..., S], 1 at the keys the row attends
    over. Where S <= top_k every visible key is chosen and nothing is
    counted."""
    with jax.named_scope("rt.attn.select"):
        S = scores.shape[-1]
        flat = scores.reshape(-1, S)
        lim = jnp.stack(
            [lim_a.reshape(-1),
             jnp.zeros(flat.shape[0], jnp.int32) if lim_b is None
             else lim_b.reshape(-1)], -1).astype(jnp.int32)
        if S <= top_k:
            idx = jax.lax.broadcasted_iota(jnp.int32, flat.shape, 1)
            mask = visible(idx, lim[:, :1], lim[:, 1:],
                           start_b).astype(jnp.int8)
        else:
            # int8 from both, whatever the kernel's blocks hold
            mask = jax.lax.platform_dependent(
                flat, lim,
                tpu=lambda flat, lim: choose_tpu(
                    flat, lim, k=top_k, start_b=start_b,
                    name=name).astype(jnp.int8),
                default=functools.partial(choose_xla, k=top_k,
                                          start_b=start_b))
        return mask.reshape(scores.shape)


# ------------------------------------------------- the product over a mask
def masked_attention_xla(q, kt, vt, mask, last=None, *, scale):
    del last
    T, H, hd = q.shape
    kvh = kt.shape[0]
    qg = q.reshape(T, kvh, H // kvh, hd)
    seen = (mask > 0)[None, None]
    s = jnp.einsum("tkgd,ksd->kgts", qg, kt,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(seen, s, NEG_INF)
    p = jnp.where(seen, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    l = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("kgts,ksd->tkgd", (p / l).astype(vt.dtype), vt,
                   preferred_element_type=jnp.float32)
    return o.reshape(T, H, hd).astype(q.dtype)


def _prefill_kernel(need_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, acc,
                    m_ref, l_ref, *, scale, kvh, group):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < need_ref[i])
    def _():
        seen = mask_ref[...].astype(jnp.float32) > 0.0        # [tq, tk]
        for h in range(kvh):
            keys, values = k_ref[h], v_ref[h]
            for r in range(group):
                n = h * group + r
                s = jax.lax.dot_general(
                    q_ref[n], keys, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(seen, s, NEG_INF)
                m_prev = m_ref[n]
                m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
                p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
                corr = jnp.exp(m_prev - m_new)
                l_ref[n] = l_ref[n] * corr + p.sum(-1, keepdims=True)
                acc[n] = acc[n] * corr + jax.lax.dot_general(
                    p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[n] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


_PREFILL_ROWS = 128


def masked_attention_tpu(q, kt, vt, mask, last=None, *, scale,
                         interpret=False):
    """The kernel: grid (query blocks of 128, key blocks of 512); a step
    holds every head's queries of the block, the key block's keys and
    values of every KV head and the mask's block, and keeps an online
    softmax a head. Key blocks from ``last`` on (no row of the query
    block sees them) are neither fetched nor computed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, hd = q.shape
    kvh, S, _ = kt.shape
    tq, tk = _PREFILL_ROWS, _block(S, 512)
    if last is None:
        need = jnp.full((T // tq,), S // tk, jnp.int32)
    else:
        need = -(-last.reshape(T // tq, tq).max(-1) // tk)

    def keys_at(i, j, need):
        return 0, jnp.minimum(j, jnp.maximum(need[i] - 1, 0)), 0

    def mask_at(i, j, need):
        return i, jnp.minimum(j, jnp.maximum(need[i] - 1, 0))

    o = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, kvh=kvh,
                          group=H // kvh),
        out_shape=jax.ShapeDtypeStruct((H, T, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((H, tq, hd), lambda i, j, _: (0, i, 0)),
                      pl.BlockSpec((kvh, tk, hd), keys_at),
                      pl.BlockSpec((kvh, tk, hd), keys_at),
                      pl.BlockSpec((tq, tk), mask_at)],
            out_specs=pl.BlockSpec((H, tq, hd), lambda i, j, _: (0, i, 0)),
            grid=(T // tq, S // tk),
            scratch_shapes=[pltpu.VMEM((H, tq, hd), jnp.float32),
                            pltpu.VMEM((H, tq, 1), jnp.float32),
                            pltpu.VMEM((H, tq, 1), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret, name=PREFILL_KERNEL,
    )(need.astype(jnp.int32), jnp.swapaxes(q, 0, 1), kt, vt, mask)
    return jnp.swapaxes(o, 0, 1)


def masked_attention(q, kt, vt, mask, last=None, *, scale: float):
    """q [T, H, hd]; kt, vt [kvh, S, hd] (KV heads first); mask int8
    [T, S]; last int32 [T] (a row sees no key from ``last`` on) ->
    [T, H, hd] in q's dtype: one softmax a row and head over the keys
    the mask keeps; a row that keeps none comes back as zeros. The
    kernel takes whole query blocks of 128; fewer rows (a speculative
    window, a bucket under 128) go to plain jax on every platform."""
    with jax.named_scope("rt.attn.sparse"):
        if last is None:
            last = jnp.full(q.shape[:1], kt.shape[1], jnp.int32)
        xla = functools.partial(masked_attention_xla, scale=scale)
        if q.shape[0] % _PREFILL_ROWS:
            return xla(q, kt, vt, mask, last)
        return jax.lax.platform_dependent(
            q, kt, vt, mask, last,
            tpu=functools.partial(masked_attention_tpu, scale=scale),
            default=xla)


def attend(q, k, v, qi, w, ki, lim_a, lim_b=None, start_b=None, *,
           top_k: int, scale: float, tile: int = QUERY_TILE):
    """Sparse attention where the keys lie side by side. q [B, T, H,
    hd]; k, v [B, S, kvh, hd]; qi [B, T, J, d]; w [B, T, J]; ki [B, S,
    d]; lim_a, lim_b int32 [B, T] and ``start_b``: the keys a query
    sees (``visible``; 0 and 0: a row that is no token, whose output is
    zeros) -> [B, T, H, hd] in q's dtype. A tile of ``tile`` queries at
    a time: its scores, its choice, its product; a tile without a token
    is skipped."""
    B, T = q.shape[:2]
    S = k.shape[1]
    if lim_b is None:
        lim_b, last = jnp.zeros_like(lim_a), lim_a
    else:
        last = jnp.where(lim_b > 0, start_b + lim_b, lim_a)
    tile = tile if T % tile == 0 else T
    outs = []
    for b in range(B):
        kt, vt = jnp.swapaxes(k[b], 0, 1), jnp.swapaxes(v[b], 0, 1)
        keys = ki[b][None]

        def one(rows, kt=kt, vt=vt, keys=keys):
            q_t, qi_t, w_t, la, lb, upto = rows
            scores = index_scores(qi_t[None], w_t[None], keys, upto[None])
            mask = choose(scores[0], la, lb if start_b is not None else None,
                          start_b, top_k=top_k)
            return masked_attention(q_t, kt, vt, mask, upto, scale=scale)

        rows = (q[b], qi[b], w[b], lim_a[b], lim_b[b], last[b])
        if tile == T:
            outs.append(one(rows))
            continue
        tiles = jax.tree.map(
            lambda a: a.reshape(T // tile, tile, *a.shape[1:]), rows)
        out = jax.lax.map(
            lambda t: jax.lax.cond(
                t[5].max() > 0, one,
                lambda t: jnp.zeros(t[0].shape, q.dtype), t), tiles)
        outs.append(out.reshape(T, *q.shape[2:]))
    return jnp.stack(outs)


# ------------------------------------------------------------ a decode step
_COUNT = 128        # keys a group of ``chosen_rows``' counts holds


def chosen_rows(mask, top_k: int):
    """mask int8 [B, S] with at most ``top_k`` ones a row -> (idx int32
    [B, top_k], the chosen indices in rising
    order, then anything; ok bool [B, top_k], which of them are). By
    counting, in dense products that the MXU runs exactly (0s and 1s in
    bfloat16, sums in float32): the ones a group of 128 keys holds, the
    groups' running total, the group an output slot falls in, and inside
    it the key at which the running count reaches the slot's."""
    B, S = mask.shape
    G = -(-S // _COUNT)
    m = jnp.pad(mask > 0, ((0, 0), (0, G * _COUNT - S))).reshape(
        B, G, _COUNT).astype(jnp.bfloat16)
    counts = m.astype(jnp.float32).sum(-1)                       # [B, G]
    ends = jnp.cumsum(counts, -1)
    slot = jnp.arange(top_k, dtype=jnp.float32)[None, :, None]   # [1, k, 1]
    group = (ends[:, None, :] <= slot).sum(-1)                   # [B, k]
    ok = slot[..., 0] < ends[:, -1:]
    group = jnp.minimum(group, G - 1)
    pick = jax.nn.one_hot(group, G, dtype=jnp.bfloat16)          # [B, k, G]
    inside = jnp.einsum("bkg,bgc->bkc", pick, m,
                        preferred_element_type=jnp.float32)
    lower = jnp.tril(jnp.ones((_COUNT, _COUNT), jnp.bfloat16)).T
    running = jnp.einsum("bkc,cd->bkd", inside.astype(jnp.bfloat16), lower,
                         preferred_element_type=jnp.float32)
    first = jnp.take_along_axis(ends - counts, group, axis=1)
    rank = slot[..., 0] - first                                  # 0-based
    at = (running <= rank[..., None]).sum(-1)
    idx = group * _COUNT + jnp.minimum(at, _COUNT - 1)
    return idx.astype(jnp.int32), ok


def gather_rows(pool, layer, tables, idx, page: int):
    """The rows at positions ``idx`` [B, n] of each slot's own pages:
    pool [L, P, page, ...]; tables int32 [B, pages] -> [B, n, ...]. ONE
    gather out of the pool as it lies: no layer is sliced out, no page
    copied whole."""
    L, P = pool.shape[:2]
    pages = jnp.take_along_axis(tables, idx // page, axis=1)
    # rows of the pool as one list: indexed by layer, page and place at
    # once XLA wants the layers inward and copies the whole pool there
    # (read from a compile for a v5e at 16 layers: 4 GB a pool)
    return jnp.take(pool.reshape(L * P * page, *pool.shape[3:]),
                    (layer * P + pages) * page + idx % page, axis=0)


def decode_chosen(qi, w, pool_i, layer, tables, lengths, new_rows, n_new,
                  *, top_k: int):
    """A decode step's choice, one query a slot. qi [B, J, row]; w [B,
    J]; pool_i [L, P, page, row], the indexer's pool (a key and the
    zeros behind it); tables int32 [B, n], the slots' pages that can
    hold old context; lengths int32 [B], the positions a slot has
    cached; new_rows [B, K, row], the burst's own indexer rows, of which
    the first ``n_new`` (this step's among them) are visible.

    Returns (idx int32 [B, top_k], ok bool [B, top_k]: the chosen cached
    positions; own bool [B, K]: the chosen rows of the burst)."""
    B, n = tables.shape
    page = pool_i.shape[2]
    K = new_rows.shape[1]
    L, P = pool_i.shape[:2]
    old = jnp.take(pool_i.reshape(L * P, page, -1), layer * P + tables,
                   axis=0).reshape(B, n * page, -1)
    # the heads as the ROWS of one product a block of keys (a head's
    # weight on its own row), then summed: with one query a slot, a
    # product a head would be 16 products of one row each
    s_old = index_scores(
        qi[:, :, None], w[:, :, None], old,
        jnp.broadcast_to(lengths[:, None], qi.shape[:2]),
        name=INDEX_KERNEL + DECODE).sum(1)
    pad = (-K) % _COUNT
    new = jnp.pad(new_rows, ((0, 0), (0, pad), (0, 0)))
    s_new = index_scores_xla(qi[:, None], w[:, None], new)[:, 0]
    mask = choose(jnp.concatenate([s_old, s_new], -1), lengths,
                  jnp.broadcast_to(n_new, lengths.shape), n * page,
                  top_k=top_k, name=SELECT_KERNEL + DECODE)
    idx, ok = chosen_rows(mask[:, :n * page], top_k)
    return idx, ok, mask[:, n * page:n * page + K] > 0
