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

Three steps where the keys lie side by side, each under its own
``jax.named_scope`` and, on a TPU, its own named Pallas kernel
(``harness/trace.py`` keeps those names):

  * ``index_scores`` (``rt.attn.index``; ``rt_sparse_index``): the
    scores of a tile of queries against the keys, a block at a time: the
    16 products of a block and their weighted, rectified sum stay in
    VMEM, so no [T, J, S] array exists, and a block of keys that no query
    of the tile can see is not computed.
  * ``choose`` (``rt.attn.select``; ``rt_sparse_select``): which keys a
    row keeps, as a mask. No sort: the 2,048th largest score of a row is
    found by COUNTING, a bit of its (order-preserving) integer image at a
    time, 32 counts while the row sits in VMEM, then, only where scores
    tie across the 2,048th place, the position up to which the tied ones
    are taken. A count runs over the chunks of 2,048 columns that some
    row of the block of 32 rows can SEE, not over the bucket's row (a
    tile at position 2,048 of a 24,576-column row: 0.126 ms against
    0.417, TPU v5e, PR 59), and a block that sees at most 2,048 keys
    counts nothing. ``lax.top_k`` at k = 2,048 sorts rows of up to 32k
    on a TPU; a count is a compare and an add.
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
``verify_step``.

A decode step has one query a slot and the keys in pages.
``decode_chosen`` gathers the indexer's rows of each slot's own pages
(128 bytes a position, not the 2 KB of its K and V), scores them and the
burst's own and chooses, as a mask. ``decode_attention`` (``rt.attn.
sparse``; ``rt_sparse_attend_decode``) then reads each slot's K and V
pages WHERE THEY LIE, all of them up to the slot's length, and attends
under the mask: a slot's program copies 8 pages of K and of V a step
from the pools into VMEM (the next step's on their way meanwhile), one
online softmax, and a slot that does not decode copies nothing;
``join_new_rows`` joins the burst's own chosen rows by the log-sum-exp.
Reading every page costs less than finding the 2,048 rows: until PR 44
the chosen positions were counted out of the mask and ONLY those K and V
rows gathered (16,384 rows of 1 KB a pool for 8 slots, whether they
decode or not), 0.81 to 0.91 ms a layer whatever the lengths (the
counting 0.15 to 0.20, a pool's gather 0.27 to 0.32, the product 0.06);
the kernel walks 44 to 89 MB of pages (3 to 5 slots of 3k to 22k) in
0.12 to 0.18 ms with its mask's widening, and 8 x 32k (537 MB) in 0.78
(TPU v5e, PR 44, chip calls 1 and 3: ``PERF.md`` section 6).

Where a query sees at most ``top_k`` keys every visible key is chosen
and the result is plain causal attention's.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
INT_MIN = -2 ** 31
# the kernels' names in a compiled program and a device trace; inside
# ``decode_burst`` the first two carry ``DECODE`` behind them
INDEX_KERNEL = "rt_sparse_index"
SELECT_KERNEL = "rt_sparse_select"
PREFILL_KERNEL = "flash_sparse_fwd"
# selection by blocks (``block_attend``): the scores of compressed keys,
# and the flash forward that leaves out the key blocks nobody chose
BLOCK_SCORE_KERNEL = "rt_block_score"
BLOCK_PREFILL_KERNEL = "flash_block_sparse_fwd"
DECODE = "_decode"
DECODE_KERNEL = "rt_sparse_attend_decode"
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


def _select_chunks_kernel(chunks_ref, lim_ref, s_ref, m_ref, key_ref, *, k,
                          chunk, start_b):
    """``chosen`` over the columns the block's rows can see: the first
    segment (the columns below ``start_b``, the whole row without one)
    in chunks of ``chunk``, of which the block counts over its first
    ``chunks_ref[i]``; the second segment whole. -1: no row of the block
    sees more than ``k`` keys and nothing is counted. The same counts as
    ``chosen``'s: a column no row sees has the image ``INT_MIN``, which
    no pass counts."""
    from jax.experimental import pallas as pl

    rows, S = s_ref.shape
    first = S if start_b is None else start_b
    n = chunks_ref[pl.program_id(0)]
    low = jnp.int32(INT_MIN)
    # ``visible`` as ONE bound a segment: a row sees the columns below it
    last_a = lim_ref[:, 0:1]
    last_b = jnp.maximum(last_a, first + lim_ref[:, 1:2])
    tail = [(at, min(chunk, S - at), last_b) for at in range(first, S, chunk)]

    def over(f, carry):
        """``f(at, width, last, carry)`` folded over the counted spans
        of columns [at, at + width), of which a row sees those below
        ``last``."""
        carry = jax.lax.fori_loop(
            0, n, lambda c, carry: f(pl.multiple_of(c * chunk, chunk),
                                     chunk, last_a, carry), carry)
        for span in tail:
            carry = f(*span, carry)
        return carry

    def column(at, width):
        return at + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)

    def count(m):
        """How many keys of the counted spans ``m(key, idx, last)`` holds
        for, a row: whole lanes added up a span, one sum across lanes a
        pass; exact in float32 up to 2**24 keys a row."""
        def add(at, width, last, lanes):
            ones = m(key_ref[:, pl.ds(at, width)], column(at, width),
                     last).astype(jnp.float32)
            for j in range(0, width, 128):
                lanes = lanes + ones[:, j:j + 128]
            return lanes

        return over(add, jnp.zeros((rows, 128), jnp.float32)).sum(
            -1, keepdims=True)

    @pl.when(n >= 0)
    def _():
        def image(at, width, last, _):
            key_ref[:, pl.ds(at, width)] = jnp.where(
                column(at, width) < last,
                _sort_key(s_ref[:, pl.ds(at, width)]), low)

        over(image, None)

        def bit(i, prefix):
            cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
            enough = count(lambda key, *_: key >= (cand ^ low)) >= k
            return jnp.where(enough, cand, prefix)

        threshold = jax.lax.fori_loop(
            0, 32, bit, jnp.zeros((rows, 1), jnp.int32)) ^ low
        need = k - count(lambda key, *_: key > threshold)

        def tied(upto):
            """The seen keys at the threshold up to column ``upto``."""
            return lambda key, idx, last: (key == threshold) & (
                idx < jnp.minimum(upto + 1, last))

        def halve(_, bounds):
            lo, hi = bounds
            mid = (lo + hi) >> 1
            enough = count(tied(mid)) >= need
            return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

        upto = jax.lax.cond(
            jnp.any(count(tied(S)) > need),
            lambda: jax.lax.fori_loop(
                0, max(1, (S - 1).bit_length()), halve,
                (jnp.zeros((rows, 1), jnp.int32),
                 jnp.full((rows, 1), S - 1, jnp.int32)))[0],
            lambda: jnp.full((rows, 1), S, jnp.int32))

        def write(at, width, last, _):
            # a key above the threshold is above ``INT_MIN``: a seen one
            key, idx = key_ref[:, pl.ds(at, width)], column(at, width)
            m_ref[:, pl.ds(at, width)] = (
                (key > threshold) | tied(upto)(key, idx, last)
            ).astype(jnp.int32).astype(m_ref.dtype)

        over(write, None)

    def uncounted(at, width, last):
        m_ref[:, pl.ds(at, width)] = (column(at, width) < last).astype(
            jnp.int32).astype(m_ref.dtype)

    # the chunks nobody counted over: what a row sees of them (nothing,
    # behind the counted ones)
    jax.lax.fori_loop(
        jnp.maximum(n, 0), first // chunk,
        lambda c, _: uncounted(pl.multiple_of(c * chunk, chunk), chunk,
                               last_a), None)

    @pl.when(n < 0)
    def _():
        for span in tail:
            uncounted(*span)


SELECT_ROWS = 32       # an int8 tile's rows
# columns of the first segment a block of rows counts over or leaves out
# together: it divides every prefill bucket and a decode step's spans
SELECT_CHUNK = 2048


def choose_tpu(scores, lim, *, k, start_b, name=SELECT_KERNEL,
               chunk=SELECT_CHUNK, interpret=False):
    """The kernel: grid (row blocks of 32); a step holds its rows' whole
    scores in VMEM and counts there, over the chunks of ``chunk``
    columns that some row of the block sees of the first segment (and
    the second segment whole): a scalar a block, from ``lim``. A block
    whose rows all see at most ``k`` keys counts nothing. At most 8 rows
    (a decode step's slots) are one block of 8 and their mask is int32,
    a tile of which has 8 rows: padded to 32 the step would count over
    24 rows that are nobody's. A first segment of one chunk (or of no
    whole lanes) is counted whole, a row at a time (``chosen``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, S = scores.shape
    rows, dtype = (8, jnp.int32) if R <= 8 else (SELECT_ROWS, jnp.int8)
    pad = (-R) % rows
    if pad:
        scores = jnp.pad(scores, ((0, pad), (0, 0)))
        lim = jnp.pad(lim, ((0, pad), (0, 0)))
    blocks = scores.shape[0] // rows
    first = S if start_b is None else start_b
    chunk = _block(first, chunk)
    params = dict(
        out_shape=jax.ShapeDtypeStruct(scores.shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM),
        interpret=interpret, name=name)
    if first == chunk or (S - first) % 128:
        mask = pl.pallas_call(
            functools.partial(_select_kernel, k=k, start_b=start_b),
            grid=(blocks,),
            in_specs=[pl.BlockSpec((rows, 2), lambda i: (i, 0)),
                      pl.BlockSpec((rows, S), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((rows, S), lambda i: (i, 0)),
            **params)(lim, scores)
    else:
        # a row sees at most lim_a + lim_b keys
        most, last = (x.reshape(blocks, rows).max(-1)
                      for x in (jnp.maximum(lim, 0).sum(-1), lim[:, 0]))
        chunks = jnp.where(
            most > k, jnp.clip(-(-last // chunk), 0, first // chunk), -1)
        mask = pl.pallas_call(
            functools.partial(_select_chunks_kernel, k=k, chunk=chunk,
                              start_b=start_b),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                in_specs=[pl.BlockSpec((rows, 2), lambda i, _: (i, 0)),
                          pl.BlockSpec((rows, S), lambda i, _: (i, 0))],
                out_specs=pl.BlockSpec((rows, S), lambda i, _: (i, 0)),
                grid=(blocks,),
                scratch_shapes=[pltpu.VMEM((rows, S), jnp.int32)]),
            **params)(chunks.astype(jnp.int32), lim, scores)
    return mask[:R] if pad else mask


def counted_keys(start: int, end: int, k: int, decode: bool) -> int:
    """Host arithmetic from positions: the columns a pass of
    ``choose_tpu`` counts over, summed over the queries at positions
    [start, end) of one sequence. Side by side (a prompt's rows from
    ``start``): blocks of ``SELECT_ROWS`` queries, each over the keys
    its last query sees, rounded up to ``SELECT_CHUNK``; nothing where
    that is at most ``k``. ``decode``: a burst's steps, each over the
    ``start`` cached positions rounded up and the burst's own 128 lanes
    (at least: the longest slot of a step's rows decides for all)."""
    if decode:
        steps = end - max(start, k)          # those that see more than k
        return max(steps, 0) * (-(-start // SELECT_CHUNK) * SELECT_CHUNK
                                + 128)
    first = np.arange(start, end, SELECT_ROWS)
    seen = np.minimum(first + SELECT_ROWS, end)
    return int(((seen - first) * (-(-seen // SELECT_CHUNK) * SELECT_CHUNK))[
        seen > k].sum())


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


def _prefill_kernel(need_ref, *refs, scale, kvh, group, blocks=False):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)
    wanted = j < need_ref[i]
    if blocks:
        # a second table: the key blocks in which the query block's mask
        # keeps anything at all (selection by blocks); the rest are
        # fetched and not computed
        live_ref, *refs = refs
        wanted = wanted & (live_ref[i, j] > 0)
    q_ref, k_ref, v_ref, mask_ref, o_ref, acc, m_ref, l_ref = refs

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(wanted)
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
                         blocks=False, interpret=False):
    """The kernel: grid (query blocks of 128, key blocks of 512); a step
    holds every head's queries of the block, the key block's keys and
    values of every KV head and the mask's block, and keeps an online
    softmax a head. Key blocks from ``last`` on (no row of the query
    block sees them) are neither fetched nor computed. ``blocks``: the
    mask was chosen by blocks (``block_attend``): a key block in which
    the query block keeps nothing is not computed either, under the
    kernel's other name (``BLOCK_PREFILL_KERNEL``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, hd = q.shape
    kvh, S, _ = kt.shape
    tq, tk = _PREFILL_ROWS, _block(S, 512)
    if last is None:
        need = jnp.full((T // tq,), S // tk, jnp.int32)
    else:
        need = -(-last.reshape(T // tq, tq).max(-1) // tk)
    tables = (need.astype(jnp.int32),)
    if blocks:
        tables += ((mask.reshape(T // tq, tq, S // tk, tk) > 0).any(
            (1, 3)).astype(jnp.int32),)

    def keys_at(i, j, need, *_):
        return 0, jnp.minimum(j, jnp.maximum(need[i] - 1, 0)), 0

    def mask_at(i, j, need, *_):
        return i, jnp.minimum(j, jnp.maximum(need[i] - 1, 0))

    o = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, kvh=kvh,
                          group=H // kvh, blocks=blocks),
        out_shape=jax.ShapeDtypeStruct((H, T, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            in_specs=[pl.BlockSpec((H, tq, hd), lambda i, j, *_: (0, i, 0)),
                      pl.BlockSpec((kvh, tk, hd), keys_at),
                      pl.BlockSpec((kvh, tk, hd), keys_at),
                      pl.BlockSpec((tq, tk), mask_at)],
            out_specs=pl.BlockSpec((H, tq, hd), lambda i, j, *_: (0, i, 0)),
            grid=(T // tq, S // tk),
            scratch_shapes=[pltpu.VMEM((H, tq, hd), jnp.float32),
                            pltpu.VMEM((H, tq, 1), jnp.float32),
                            pltpu.VMEM((H, tq, 1), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name=BLOCK_PREFILL_KERNEL if blocks else PREFILL_KERNEL,
    )(*tables, jnp.swapaxes(q, 0, 1), kt, vt, mask)
    return jnp.swapaxes(o, 0, 1)


def masked_attention(q, kt, vt, mask, last=None, *, scale: float,
                     blocks: bool = False):
    """q [T, H, hd]; kt, vt [kvh, S, hd] (KV heads first); mask int8
    [T, S]; last int32 [T] (a row sees no key from ``last`` on) ->
    [T, H, hd] in q's dtype: one softmax a row and head over the keys
    the mask keeps; a row that keeps none comes back as zeros. The
    kernel takes whole query blocks of 128; fewer rows (a speculative
    window, a bucket under 128) go to plain jax on every platform.
    ``blocks``: see ``masked_attention_tpu``."""
    with jax.named_scope("rt.attn.sparse"):
        if last is None:
            last = jnp.full(q.shape[:1], kt.shape[1], jnp.int32)
        xla = functools.partial(masked_attention_xla, scale=scale)
        if q.shape[0] % _PREFILL_ROWS:
            return xla(q, kt, vt, mask, last)
        return jax.lax.platform_dependent(
            q, kt, vt, mask, last,
            tpu=functools.partial(masked_attention_tpu, scale=scale,
                                  blocks=blocks),
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
def decode_attention_xla(q, pool_k, pool_v, layer, tables, lengths, mask, *,
                         scale, kvh=None):
    """The same in plain jax (the CPU's path, and the kernel's oracle):
    the table's rectangle of the layer's pages gathered, one softmax a
    slot and head over the keys the mask keeps below the slot's length."""
    B, H, hd = q.shape
    kvh = kvh or pool_k.shape[3]
    k, v = (jnp.take(jax.lax.dynamic_index_in_dim(pool, layer, 0, False),
                     tables, axis=0).reshape(B, -1, kvh, hd)
            for pool in (pool_k, pool_v))
    s = jnp.einsum("bkgd,bskd->bkgs", q.reshape(B, kvh, H // kvh, hd), k,
                   preferred_element_type=jnp.float32) * scale
    seen = ((mask > 0) & (jnp.arange(k.shape[1])[None, :]
                          < lengths[:, None]))[:, None, None, :]
    s = jnp.where(seen, s, NEG_INF)
    m = s.max(-1)
    p = jnp.where(seen, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.maximum(p.sum(-1), 1e-30)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return ((o / l[..., None]).reshape(B, H, hd),
            (m + jnp.log(l)).reshape(B, H))


def _decode_kernel(layer, tables, lengths, q_ref, seen_ref, k_hbm, v_hbm,
                   o_ref, lse_ref, k_buf, v_buf, sems, *, scale, page, pages,
                   group):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    length = lengths[b]
    rows = k_hbm.shape[2]               # a page's (position, KV head) rows
    steps = pl.cdiv(length, pages * page)
    last = jnp.maximum(length - 1, 0) // page

    def copies(j, half):
        """Step j's pages of K and V into buffer ``half``; past the
        slot's last page that page again (read, never counted)."""
        for g in range(pages):
            at = tables[b, jnp.minimum(j * pages + g, last)]
            for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                yield pltpu.make_async_copy(
                    pool.at[layer[0], at],
                    buf.at[half, pl.ds(g * rows, rows)], sems.at[half])

    @pl.when(steps > 0)
    def _():
        for copy in copies(0, 0):
            copy.start()

    q = q_ref[...]
    head = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0) // group

    def step(j, carry):
        m_prev, l, acc = carry
        half = j % 2

        @pl.when(j + 1 < steps)
        def _():
            for copy in copies(j + 1, 1 - half):
                copy.start()

        for copy in copies(j, half):
            copy.wait()
        # a page's rows are (position, KV head): EVERY query head against
        # every row in one product, and a head keeps the rows of its own
        # KV head (``seen_ref``: a chosen row's KV head, else -1)
        s = jax.lax.dot_general(q, k_buf[half], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        seen = jnp.concatenate([seen_ref[pl.ds(j * pages + g, 1), :]
                                for g in range(pages)], axis=1) == head
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        # the rows of other KV heads weigh 0: one product again
        values = v_buf[half]
        return (m_new, l * corr + p.sum(-1, keepdims=True),
                acc * corr + jax.lax.dot_general(
                    p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))

    m, l, acc = jax.lax.fori_loop(
        0, steps, step,
        (jnp.full((q.shape[0], 1), NEG_INF, jnp.float32),
         jnp.zeros((q.shape[0], 1), jnp.float32),
         jnp.zeros(o_ref.shape, jnp.float32)))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = acc / l
    lse_ref[...] = m + jnp.log(l)


# K bytes (and as many of V) a step of the decode kernel takes
_STEP_BYTES = 512 * 1024


def decode_attention_tpu(q, pool_k, pool_v, layer, tables, lengths, mask, *,
                         scale, kvh=None, interpret=False):
    """The kernel: grid (slot,); a slot's program walks the steps its
    length needs and no more. A step copies as many of the slot's pages
    of K and of V as hold ``_STEP_BYTES`` straight from the pools where
    they lie (the page's index from the block table, prefetched as
    scalars; the next step's pages are on their way while this one's are
    multiplied), reads the mask's row for them and keeps a float32
    online softmax. A slot that does not decode (length 0) copies
    nothing and computes nothing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    flat = kvh is not None
    page, kvh = (pool_k.shape[2] // kvh, kvh) if flat else pool_k.shape[2:4]
    n = tables.shape[1]
    # a power of two of pages, so that it divides the table's bucket
    most = max(1, _STEP_BYTES // (page * kvh * hd * pool_k.dtype.itemsize))
    pages = _block(n, 1 << most.bit_length() - 1, 1)
    # a chosen row's KV head beside every (position, KV head) row of the
    # pages, a page's in a row; -1 where the position is not chosen or
    # not the slot's. Spread by a product (a position's 0 or 1 times its
    # KV heads' numbers from 1, exact in any precision): as a broadcast
    # XLA interleaves lanes, 0.06 to 0.10 ms a layer where the kernel
    # takes 0.08 to 0.14 (PR 44, chip call 2)
    live = (mask > 0) & (jnp.arange(n * page)[None, :] < lengths[:, None])
    spread = np.kron(np.eye(page, dtype=np.float32),
                     np.arange(1.0, kvh + 1.0, dtype=np.float32)[None, :])
    seen = (live.astype(jnp.float32).reshape(B * n, page) @ spread).astype(
        jnp.int32).reshape(B, n, page * kvh) - 1
    return _decode_call(q, pool_k, pool_v, layer, tables, lengths, seen,
                        pages=pages, scale=scale, kvh=kvh if flat else None,
                        interpret=interpret)


def _decode_call(q, pool_k, pool_v, layer, tables, lengths, seen, *, pages,
                 scale, group=None, kvh=None, interpret=False):
    """``decode_attention_tpu``'s ``pallas_call``. seen int32 [B, n,
    page * kvh]: beside every (position, KV head) row of a listed page
    the number of the group of ``group`` query rows (``H // kvh``
    unless given) that keeps it, else -1. ``kvh``: the pools are [L, P,
    page * kvh, hd] already (a configuration with state layers keeps
    them so)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    if kvh is None:
        page, kvh = pool_k.shape[2:4]
        # (position, KV head) as the rows of one matrix a page: the same
        # bytes as they lie
        pool_k, pool_v = (pool.reshape(*pool.shape[:2], page * kvh, hd)
                          for pool in (pool_k, pool_v))
    else:
        page = pool_k.shape[2] // kvh
    o, lse = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page=page,
                          pages=pages, group=group or H // kvh),
        out_shape=[jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((None, H, hd), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((None, *seen.shape[1:]),
                                   lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((None, H, hd), lambda b, *_: (b, 0, 0)),
                       pl.BlockSpec((None, H, 1), lambda b, *_: (b, 0, 0))],
            grid=(B,),
            scratch_shapes=[
                pltpu.VMEM((2, pages * page * kvh, hd), pool_k.dtype),
                pltpu.VMEM((2, pages * page * kvh, hd), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM),
        interpret=interpret, name=DECODE_KERNEL,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tables, lengths, q, seen,
      pool_k, pool_v)
    return o, lse[..., 0]


def decode_attention(q, pool_k, pool_v, layer, tables, lengths, mask, *,
                     scale: float, kvh: Optional[int] = None):
    """One query a slot over the keys ``mask`` keeps of the slot's own
    cached pages, read where they lie.

    q [B, H, hd]; pool_k, pool_v [L, P, page, kvh, hd], the whole
    stacks, and ``layer`` (a traced index) picks its layer in the page's
    address: no layer is sliced out, no page copied; tables int32 [B, n],
    a slot's pages in order (unused entries 0: the reserved page, never
    scored); lengths int32 [B], the positions a slot has cached (0:
    nothing, and the slot's output is 0 with a log-sum-exp of about
    -1e30); mask int8 [B, n * page], 1 at the cached keys the slot
    attends over (``decode_chosen``'s). ``kvh``: the pools are [L, P,
    page * kvh, hd] already, a page ONE matrix of its (position, KV
    head) rows.

    Returns (o float32 [B, H, hd], the softmax's output over the chosen
    cached keys alone; lse float32 [B, H], its log-sum-exp, by which
    ``join_new_rows`` joins it with rows the cache does not hold yet)."""
    with jax.named_scope("rt.attn.sparse"):
        return jax.lax.platform_dependent(
            q, pool_k, pool_v, layer, tables, lengths, mask,
            tpu=functools.partial(decode_attention_tpu, scale=scale,
                                  kvh=kvh),
            default=functools.partial(decode_attention_xla, scale=scale,
                                      kvh=kvh))


def join_new_rows(o_old, lse_old, q, k, v, mask, *, scale: float):
    """One softmax over (what ``decode_attention`` saw; the rows k, v
    [B, K, kvh, hd] the cache does not hold yet, under ``mask`` [B, K]).
    Returns o float32 [B, H, hd]."""
    B, H, hd = q.shape
    kvh = k.shape[2]
    seen = mask[:, None, None, :]
    with jax.named_scope("rt.attn.sparse"):
        s = jnp.einsum("bkgd,bnkd->bkgn", q.reshape(B, kvh, H // kvh, hd),
                       k, preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen, s, NEG_INF)
        m = jnp.maximum(lse_old.reshape(B, kvh, -1), s.max(-1))
        w_old = jnp.exp(lse_old.reshape(m.shape) - m)
        p = jnp.where(seen, jnp.exp(s - m[..., None]), 0.0)
        new = jnp.einsum("bkgn,bnkd->bkgd", p, v.astype(jnp.float32))
        o = (w_old[..., None] * o_old.reshape(B, kvh, -1, hd) + new) / (
            jnp.maximum(w_old + p.sum(-1), 1e-30)[..., None])
        return o.reshape(B, H, hd)


def decode_chosen(qi, w, pool_i, layer, tables, lengths, new_rows, n_new,
                  *, top_k: int):
    """A decode step's choice, one query a slot. qi [B, J, row]; w [B,
    J]; pool_i [L, P, page, row], the indexer's pool (a key and the
    zeros behind it); tables int32 [B, n], the slots' pages that can
    hold old context; lengths int32 [B], the positions a slot has
    cached; new_rows [B, K, row], the burst's own indexer rows, of which
    the first ``n_new`` (this step's among them) are visible.

    Returns (mask int8 [B, n * page], 1 at the chosen cached positions;
    own bool [B, K]: the chosen rows of the burst)."""
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
    pad = (-K) % 128        # the burst's scores: whole lanes
    new = jnp.pad(new_rows, ((0, 0), (0, pad), (0, 0)))
    s_new = index_scores_xla(qi[:, None], w[:, None], new)[:, 0]
    mask = choose(jnp.concatenate([s_old, s_new], -1), lengths,
                  jnp.broadcast_to(n_new, lengths.shape), n * page,
                  top_k=top_k, name=SELECT_KERNEL + DECODE)
    return mask[:, :n * page], mask[:, n * page:n * page + K] > 0


# ------------------------------------------------------ selection by blocks
class BlockSizes(NamedTuple):
    """InfLLM-V2's sizes (``LlamaConfig.block_*``): a block of ``size``
    tokens; ``topk`` blocks a query; compressed keys, the mean of
    ``2 * stride`` keys every ``stride``; the first ``init`` blocks and
    the blocks of the ``window`` newest tokens always chosen; a query
    below ``dense_len`` attends over every visible key."""
    size: int
    topk: int
    stride: int
    init: int
    window: int
    dense_len: int

    @property
    def per(self) -> int:
        """Compressed keys that begin in a block."""
        return self.size // self.stride


def stride_sums(k, valid, stride: int):
    """float32 sums of the keys of every ``stride`` positions: k [...,
    S, kvh, hd], valid bool [..., S] (a row that is no token adds
    nothing) -> [..., S // stride, kvh, hd]. What the pool of compressed
    keys holds: a compressed key is the mean of two neighbours."""
    k = jnp.where(valid[..., None, None], k, 0).astype(jnp.float32)
    return k.reshape(*k.shape[:-3], k.shape[-3] // stride, stride,
                     *k.shape[-2:]).sum(-3)


def compressed_keys(sums, sizes: BlockSizes, dtype):
    """sums float32 [..., N, kvh, hd] (``stride_sums``) -> the compressed
    keys [..., N, kvh, hd] in ``dtype``: c_j the mean of the keys of
    strides j and j + 1 (the last one, which has no neighbour, is never
    valid)."""
    nxt = jnp.concatenate([sums[..., 1:, :, :],
                           jnp.zeros_like(sums[..., :1, :, :])], -3)
    return ((sums + nxt) / (2.0 * sizes.stride)).astype(dtype)


def valid_compressed(q_pos, sizes: BlockSizes):
    """How many compressed keys a query at ``q_pos`` may score: those
    whose ``2 * stride`` tokens all lie at or before it."""
    return jnp.maximum((q_pos + 1 - 2 * sizes.stride) // sizes.stride + 1, 0)


def block_scores_xla(q, c, n_valid, *, scale):
    B, H, T, hd = q.shape
    kvh = c.shape[1]
    s = jnp.einsum("bkgtd,bknd->bkgtn", q.reshape(B, kvh, H // kvh, T, hd),
                   c, preferred_element_type=jnp.float32) * scale
    seen = (jnp.arange(c.shape[2])[None, None, :]
            < n_valid[..., None])[:, None, None]
    s = jnp.where(seen, s, NEG_INF)
    p = jnp.where(seen, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    return (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).sum(2)


def _block_score_kernel(q_ref, c_ref, n_ref, o_ref, *, scale, group):
    keys = c_ref[...]
    seen = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1) < n_ref[...]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for r in range(group):
        s = jax.lax.dot_general(q_ref[r], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen, s, NEG_INF)
        p = jnp.where(seen, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        acc = acc + p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o_ref[...] = acc


def block_scores_tpu(q, c, n_valid, *, scale, interpret=False):
    """The kernel: grid (row, KV head, query block); a step holds the
    group's heads of a block of queries and ALL of the KV head's
    compressed keys (2,048 of 128 at 32k tokens: 0.5 MB), one softmax a
    head over them, and writes the heads' sum."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, hd = q.shape
    kvh, N = c.shape[1:3]
    group = H // kvh
    pad = (-T) % 8
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        n_valid = jnp.pad(n_valid, ((0, 0), (0, pad)))
    Tp = T + pad
    tq = _block(Tp, 128, 8)
    out = pl.pallas_call(
        functools.partial(_block_score_kernel, scale=scale, group=group),
        out_shape=jax.ShapeDtypeStruct((B, kvh, Tp, N), jnp.float32),
        grid=(B, kvh, Tp // tq),
        in_specs=[pl.BlockSpec((None, group, tq, hd),
                               lambda b, g, i: (b, g, i, 0)),
                  pl.BlockSpec((None, None, N, hd),
                               lambda b, g, i: (b, g, 0, 0)),
                  pl.BlockSpec((None, tq, 1), lambda b, g, i: (b, i, 0))],
        out_specs=pl.BlockSpec((None, None, tq, N),
                               lambda b, g, i: (b, g, i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret, name=BLOCK_SCORE_KERNEL,
    )(q, c, n_valid[..., None].astype(jnp.int32))
    return out[:, :, :T] if pad else out


def block_scores(q, c, q_pos, sizes: BlockSizes, *, scale: float):
    """The blocks' scores. q [B, H, T, hd] (heads first); c [B, kvh, N,
    hd], the compressed keys of the positions side by side from 0; q_pos
    int32 [B, T] (-1: no token). For every query head one softmax over
    the compressed keys it may score; summed over a KV head's group of
    heads (``rt_block_score``); a block's score is the largest among the
    compressed keys whose tokens touch it (j from ``per * b - 1`` to
    ``per * b + per - 1``). Returns float32 [B, kvh, T, N // per]; 0
    where a block has no compressed key the query may score."""
    with jax.named_scope("rt.attn.block.score"):
        n_valid = valid_compressed(q_pos, sizes)
        s = jax.lax.platform_dependent(
            q, c, n_valid,
            tpu=functools.partial(block_scores_tpu, scale=scale),
            default=functools.partial(block_scores_xla, scale=scale))
        per, N = sizes.per, c.shape[2]
        shifted = jnp.pad(s, ((0, 0),) * 3 + ((1, 0),))     # s_{j - 1} at j
        inside = shifted[..., :N].reshape(*s.shape[:3], N // per, per).max(-1)
        return jnp.maximum(inside, shifted[..., per::per])


_FORCED = 1e30


def _ranked(scores, q_pos, sizes: BlockSizes):
    """The scores as the choice ranks them: a block that is always
    chosen (the first ``init``, those of the ``window`` newest tokens;
    for a query below ``dense_len`` every visible one) above everything,
    a block the query cannot see below everything. scores [..., T, NB];
    q_pos [..., T] (broadcastable). Returns (ranked, own block + 1)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    own = (jnp.maximum(q_pos, 0) // sizes.size)[..., None]
    forced = ((idx < sizes.init) | (idx > own - sizes.window // sizes.size)
              | (q_pos < sizes.dense_len)[..., None])
    seen = (idx <= own) & (q_pos >= 0)[..., None]
    return jnp.where(seen, jnp.where(forced, _FORCED, scores),
                     -jnp.inf), jnp.where(q_pos >= 0, own[..., 0] + 1, 0)


def block_choice(scores, q_pos, sizes: BlockSizes):
    """Which blocks a query attends over, as a mask: scores float32
    [B, kvh, T, NB] (``block_scores``); q_pos [B, T] -> int8 [B, kvh, T,
    NB]. ``topk`` blocks a query and KV head, ties to the earlier block;
    every visible block where the query lies below ``dense_len`` or sees
    at most ``topk``."""
    ranked, lim = _ranked(scores, q_pos[:, None], sizes)
    lim = jnp.broadcast_to(lim, scores.shape[:-1])
    mask = choose(ranked, lim, top_k=sizes.topk, name=SELECT_KERNEL)
    idx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 3)
    dense = (q_pos < sizes.dense_len)[:, None, :, None]
    return jnp.where(dense, (idx < lim[..., None]).astype(jnp.int8), mask)


# ``block_attend``, ``block_decode_pages`` and ``block_decode_attention``
# are jitted on their own: the layers of a program whose layers run one
# after the other (weights a layer kind) call them with the same shapes,
# so a program holds ONE traced and lowered body of each however many
# layers call it (``ops/linear_attention.py`` does the same for the
# linear form). The compiler inlines the calls; the kernels, the scopes
# and every precision are what they were. The sizes shape the body, so
# they are static.
def _static_sizes(sizes, where: str) -> None:
    """Refuse, before jit hashes it or a trace holds it, ``sizes`` that
    are no ``BlockSizes`` (an array cannot key a jitted function)."""
    if not isinstance(sizes, BlockSizes):
        raise TypeError(
            f"{where}: sizes is static to the jitted function and has to "
            f"be a BlockSizes (LlamaConfig.block_sizes), not "
            f"{type(sizes).__name__}")


def block_attend(q, k, v, sums, q_pos, sizes: BlockSizes, *, scale: float,
                 tile: int = QUERY_TILE):
    """Attention by blocks where the keys lie side by side from position
    0: q [T, H, hd]; k, v [S, kvh, hd]; sums float32 [S // stride, kvh,
    hd] (``stride_sums`` of k); q_pos int32 [T], a query's position (-1:
    no token, zeros come back) -> [T, H, hd] in q's dtype. A tile of
    queries at a time: the blocks' scores, the choice, and the flash
    forward under the chosen blocks' tokens up to the query's own, a KV
    head after the other (the choice is a KV head's); a tile without a
    token is skipped."""
    _static_sizes(sizes, "sparse_attention.block_attend")
    return _block_attend(q, k, v, sums, q_pos, sizes, scale=scale, tile=tile)


@functools.partial(jax.jit, static_argnames=("sizes", "scale", "tile"))
def _block_attend(q, k, v, sums, q_pos, sizes, *, scale, tile):
    T, H, hd = q.shape
    S, kvh = k.shape[:2]
    group = H // kvh
    kt, vt = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)
    c = jnp.swapaxes(compressed_keys(sums, sizes, q.dtype), 0, 1)[None]
    key_at = jnp.arange(S, dtype=jnp.int32)

    def one(rows):
        q_t, at = rows
        scores = block_scores(jnp.swapaxes(q_t, 0, 1)[None], c, at[None],
                              sizes, scale=scale)
        chosen_blocks = block_choice(scores, at[None], sizes)[0]
        outs = []
        for g in range(kvh):
            with jax.named_scope("rt.attn.select"):
                mask = (jnp.repeat(chosen_blocks[g], sizes.size, axis=-1)
                        * (key_at[None, :] <= at[:, None])).astype(jnp.int8)
            outs.append(masked_attention(
                q_t[:, g * group:(g + 1) * group], kt[g:g + 1], vt[g:g + 1],
                mask, jnp.maximum(at + 1, 0), scale=scale, blocks=True))
        return jnp.concatenate(outs, 1)

    tile = tile if T % tile == 0 else T
    if tile == T:
        return one((q, q_pos))
    tiles = (q.reshape(T // tile, tile, H, hd), q_pos.reshape(-1, tile))
    out = jax.lax.map(
        lambda t: jax.lax.cond(t[1].max() >= 0, one,
                               lambda t: jnp.zeros(t[0].shape, q.dtype), t),
        tiles)
    return out.reshape(T, H, hd)


def block_decode_pages(q, old_sums, new_k, n_new, tables, lengths,
                       sizes: BlockSizes, *, scale: float):
    """A decode step's choice of pages, one query a slot (a page is a
    block). q [B, H, hd]; old_sums float32 [B, n * per, kvh, hd], the
    pool's sums of the slot's listed pages (``tables`` int32 [B, n]);
    zeros where a stride holds no cached position;
    lengths int32 [B], the positions a slot has cached; new_k [B, K, kvh,
    hd], the burst's own keys, of which the first ``n_new`` (this step's
    among them) are visible: the query sits at ``lengths + n_new - 1``.

    Returns, a row a (slot, KV head) pair (the choice is a KV head's),
    what ``block_decode_attention`` takes: (pages int32 [B * kvh, W], the
    chosen pages' ids; lengths int32 [B * kvh], the positions listed;
    seen int32 [B * kvh, W, size * kvh]: 0 beside a (position, KV head)
    row that is cached, the pair's own KV head's and on a chosen page,
    else -1)."""
    _static_sizes(sizes, "sparse_attention.block_decode_pages")
    return _block_decode_pages(q, old_sums, new_k, n_new, tables, lengths,
                               sizes, scale=scale)


@functools.partial(jax.jit, static_argnames=("sizes", "scale"))
def _block_decode_pages(q, old_sums, new_k, n_new, tables, lengths, sizes, *,
                        scale):
    B, H, hd = q.shape
    n = tables.shape[1]
    K, kvh = new_k.shape[1:3]
    size, stride, per = sizes.size, sizes.stride, sizes.per
    q_pos = jnp.where(lengths > 0, lengths + n_new - 1, -1)
    with jax.named_scope("rt.attn.block.score"):
        # the strides' sums as the query sees them: the pool's, where a
        # stride holds a cached position, and the burst's own rows
        at = jnp.arange(n * per)
        row_at = lengths[:, None] + jnp.arange(K)[None, :]       # [B, K]
        mine = ((row_at // stride)[..., None] == at[None, None, :]) & (
            jnp.arange(K)[None, :, None] < n_new)
        sums = old_sums + jnp.einsum("bkn,bkgd->bngd",
                                     mine.astype(jnp.float32),
                                new_k.astype(jnp.float32),
                                precision="highest")
        c = jnp.swapaxes(compressed_keys(sums, sizes, q.dtype), 1, 2)
    scores = block_scores(q[:, :, None], c, q_pos[:, None], sizes,
                          scale=scale)[:, :, 0]                 # [B, kvh, n]
    with jax.named_scope("rt.attn.select"):
        ranked, visible = _ranked(scores, q_pos[:, None], sizes)
        W = min(n, max(sizes.topk, sizes.dense_len // size))
        _, idx = jax.lax.top_k(ranked, W)                   # [B, kvh, W]
        most = jnp.where(q_pos < sizes.dense_len, W, sizes.topk)
        n_chosen = jnp.minimum(visible, most[:, None])          # [B, 1]
        kept = jnp.arange(W)[None, None, :] < n_chosen[..., None]
        pages = jnp.where(kept, jnp.take_along_axis(
            tables[:, None, :], idx, axis=2), 0)
        # a listed page's positions that are cached, and whose rows are
        # the pair's own KV head's
        cached = kept[..., None] & (
            idx[..., None] * size + jnp.arange(size) < lengths[
                :, None, None, None])                      # [B, kvh, W, size]
        head = jnp.arange(kvh)
        seen = jnp.where(
            cached[..., None] & (head[None, :, None, None, None]
                                 == head[None, None, None, None, :]), 0, -1)
        return (pages.reshape(B * kvh, W).astype(jnp.int32),
                jnp.broadcast_to(n_chosen * size, (B, kvh)).reshape(
                    -1).astype(jnp.int32),
                seen.reshape(B * kvh, W, size * kvh).astype(jnp.int32))


def block_decode_attention_xla(q, pool_k, pool_v, layer, pages, lengths,
                               seen, *, scale):
    del lengths
    R, G, hd = q.shape
    rows_k, rows_v = (
        jnp.take(jax.lax.dynamic_index_in_dim(pool, layer, 0, False), pages,
                 axis=0).reshape(R, -1, hd) for pool in (pool_k, pool_v))
    keep = (seen.reshape(R, -1) >= 0)[:, None, :]
    s = jnp.einsum("rgd,rsd->rgs", q, rows_k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(keep, s, NEG_INF)
    m = s.max(-1)
    p = jnp.where(keep, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.maximum(p.sum(-1), 1e-30)
    o = jnp.einsum("rgs,rsd->rgd", p.astype(rows_v.dtype), rows_v,
                   preferred_element_type=jnp.float32)
    return o / l[..., None], m + jnp.log(l)


def block_decode_attention_tpu(q, pool_k, pool_v, layer, pages, lengths,
                               seen, *, kvh, scale, interpret=False):
    most = max(1, _STEP_BYTES // (pool_k.shape[2] * q.shape[-1]
                                  * pool_k.dtype.itemsize))
    return _decode_call(
        q, pool_k, pool_v, layer, pages, lengths, seen,
        pages=_block(pages.shape[1], 1 << most.bit_length() - 1, 1),
        scale=scale, group=q.shape[1], kvh=kvh, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("kvh", "scale"))
def block_decode_attention(q, pool_k, pool_v, layer, pages, lengths, seen,
                           *, kvh: int, scale: float):
    """One query a slot over the cached tokens of the pages it chose, a
    KV head's group of heads a row: q [B, H, hd]; pool_k, pool_v [L, P,
    page * kvh, hd], a page ONE matrix of its (position, KV head) rows
    (llm/kinds/state.py keeps them so for these layers); ``pages``,
    ``lengths`` and ``seen`` from ``block_decode_pages``.
    ``rt_sparse_attend_decode`` walks the LISTED pages and no other: a
    page that was not chosen is not read. Returns (o float32 [B, H, hd],
    lse float32 [B, H]) for ``join_new_rows``."""
    B, H, hd = q.shape
    rows = pages.shape[0]
    with jax.named_scope("rt.attn.sparse"):
        o, lse = jax.lax.platform_dependent(
            q.reshape(rows, -1, hd), pool_k, pool_v,
            jnp.asarray(layer, jnp.int32), pages, lengths, seen,
            tpu=functools.partial(block_decode_attention_tpu, kvh=kvh,
                                  scale=scale),
            default=functools.partial(block_decode_attention_xla,
                                      scale=scale))
        return o.reshape(B, H, hd), lse.reshape(B, H)
