"""Linear attention with a decaying state (Lightning Attention-2, as
MiniCPM-SALA's ``lightning-attn`` layers use it).

A head keeps no key and no value of the past: its memory is ONE matrix
``S`` of ``hd`` x ``hd`` float32 a sequence, and a token at position t
does

    S_t = lambda S_{t-1} + k_t^T v_t          o_t = scale * q_t S_t

(the token's own key is in ``S_t``), with ``lambda = exp(-slope)`` a
head. Two forms of the same recurrence, each under the
``jax.named_scope`` ``rt.attn.linear`` and, on a TPU, its own named
Pallas kernel (``harness/trace.py`` keeps those names):

  * ``prefill`` (``rt_linear_prefill``): the chunked form, where the
    tokens lie side by side. Within a chunk of 128 the decayed
    ``(q k^T)`` under the causal mask times ``v``; between chunks ``q S``
    and the state's update. Grid (row, head, chunk): the state is the
    kernel's float32 output block, which stays in VMEM while the chunks
    of a head go by. Sums and state in float32.
  * ``decode_step`` (``rt_linear_decode``): one token a slot. The pool
    ``[layers, slots, heads, hd, hd]`` float32 is updated IN PLACE
    (aliased to the kernel's output): a slot that decodes reads its
    state once and writes it once, a slot that does not is neither read
    nor written.

Rows that are padding (a bucket's tail behind a prompt's end, an idle
slot of a burst) neither read nor change a state: a prefill is told the
rows' ``lengths`` and the state stops at the last token; a chunk wholly
behind it is skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PREFILL_KERNEL = "rt_linear_prefill"
DECODE_KERNEL = "rt_linear_decode"
CHUNK = 128
_VMEM = 64 * 1024 * 1024


# ----------------------------------------------------------- the recurrence
def recurrence(q, k, v, slopes, lengths=None, state=None, *, scale: float):
    """The definition, a token at a time (the tests' oracle, and what a
    platform without the kernels runs for a short row). q, k, v
    [B, S, H, hd]; slopes float32 [H]; lengths int32 [B] (None: every
    row a token); state float32 [B, H, hd, hd] (None: zeros).
    Returns (o float32 [B, S, H, hd], the state after the last token)."""
    B, S, H, hd = q.shape
    decay = jnp.exp(-jnp.asarray(slopes, jnp.float32))[None, :, None, None]
    if state is None:
        state = jnp.zeros((B, H, hd, hd), jnp.float32)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)

    def step(s, row):
        t, qt, kt, vt = row
        new = decay * s + jnp.einsum(
            "bhk,bhv->bhkv", kt.astype(jnp.float32), vt.astype(jnp.float32),
            precision="highest")
        s = jnp.where((t < lengths)[:, None, None, None], new, s)
        return s, scale * jnp.einsum("bhk,bhkv->bhv", qt.astype(jnp.float32),
                                     s, precision="highest")

    state, o = jax.lax.scan(
        step, state, (jnp.arange(S), *(jnp.swapaxes(a, 0, 1)
                                       for a in (q, k, v))))
    return jnp.swapaxes(o, 0, 1), state


# ------------------------------------------------------------ chunked form
def _chunks(a, chunk):
    B, S = a.shape[:2]
    return a.reshape(B, S // chunk, chunk, *a.shape[2:])


def prefill_xla(q, k, v, slopes, lengths, state, *, scale, chunk=CHUNK):
    """The chunked form in plain jax: a ``lax.scan`` over chunks, every
    head of a chunk at once. S a whole number of chunks."""
    B, S, H, hd = q.shape
    slopes = jnp.asarray(slopes, jnp.float32)
    i = jnp.arange(chunk)
    # D[h, i, j] = lambda_h ** (i - j) for i >= j
    gap = (i[:, None] - i[None, :]).astype(jnp.float32)
    within = jnp.where(gap >= 0, jnp.exp(-slopes[:, None, None]
                                         * jnp.maximum(gap, 0.0)), 0.0)

    def one(s, rows):
        c, qc, kc, vc = rows                           # [B, chunk, H, hd]
        n = jnp.clip(lengths - c * chunk, 0, chunk)    # tokens in the chunk
        tok = i[None, :] < n[:, None]                  # [B, chunk]
        kc = jnp.where(tok[..., None, None], kc, 0).astype(jnp.float32)
        qc, vc = qc.astype(jnp.float32), vc.astype(jnp.float32)
        a = jnp.einsum("bihk,bjhk->bhij", qc, kc,
                       precision="highest") * within[None]
        o = jnp.einsum("bhij,bjhv->bihv", a, vc, precision="highest")
        # what the state before the chunk adds: lambda ** (i + 1) q S
        grow = jnp.exp(-slopes[None, :] * (i[:, None] + 1.0))   # [chunk, H]
        o = o + jnp.einsum("bihk,bhkv->bihv", qc * grow[None, ..., None], s,
                           precision="highest")
        # the state after the chunk's last token
        left = jnp.exp(-slopes[None, None, :] * jnp.maximum(
            n[:, None, None] - 1 - i[None, :, None], 0).astype(jnp.float32))
        s = (jnp.exp(-slopes[None, :] * n[:, None].astype(jnp.float32)
                     )[..., None, None] * s
             + jnp.einsum("bjhk,bjhv->bhkv", kc * left[..., None], vc,
                          precision="highest"))
        return s, scale * o

    state, o = jax.lax.scan(
        one, state, (jnp.arange(S // chunk),
                     *(jnp.swapaxes(_chunks(a, chunk), 0, 1)
                       for a in (q, k, v))))
    return jnp.swapaxes(o, 0, 1).reshape(B, S, H, hd), state


def _prefill_kernel(lens_ref, slope_ref, q_ref, k_ref, v_ref, s0_ref, o_ref,
                    s_ref, *, scale, chunk):
    from jax.experimental import pallas as pl

    b, h, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_ref[...] = s0_ref[...]

    n = jnp.clip(lens_ref[b] - c * chunk, 0, chunk)

    @pl.when(n == 0)
    def _():
        # a chunk wholly behind the prompt's end: no token, no change
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _():
        slope = slope_ref[h]
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        gap = (row - col).astype(jnp.float32)
        within = jnp.where((row >= col) & (col < n),
                           jnp.exp(-slope * jnp.maximum(gap, 0.0)), 0.0)
        a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * within
        o = jax.lax.dot_general(a.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        at = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        grow = jnp.exp(-slope * (at + 1).astype(jnp.float32))
        s = s_ref[...]
        o = o + jax.lax.dot_general(
            q.astype(jnp.float32) * grow, s, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = (scale * o).astype(o_ref.dtype)
        left = jnp.where(at < n, jnp.exp(
            -slope * jnp.maximum(n - 1 - at, 0).astype(jnp.float32)), 0.0)
        s_ref[...] = jnp.exp(-slope * n.astype(jnp.float32)) * s + (
            jax.lax.dot_general(
                k.astype(jnp.float32) * left, v.astype(jnp.float32),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))


def prefill_tpu(q, k, v, slopes, lengths, state, *, scale, chunk=CHUNK,
                interpret=False):
    """The kernel: grid (row, head, chunk). A step holds a head's chunk
    of q, k and v; the head's state is the float32 output block, held in
    VMEM from the head's first chunk to its last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, hd = q.shape
    qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))   # [B, H, S, hd]

    def rows(b, h, c, *_):
        return b, h, c, 0

    def whole(b, h, c, *_):
        return b, h, 0, 0

    o, state = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, chunk=chunk),
        out_shape=[jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((None, None, chunk, hd), rows),
                      pl.BlockSpec((None, None, chunk, hd), rows),
                      pl.BlockSpec((None, None, chunk, hd), rows),
                      pl.BlockSpec((None, None, hd, hd), whole)],
            out_specs=[pl.BlockSpec((None, None, chunk, hd), rows),
                       pl.BlockSpec((None, None, hd, hd), whole)],
            grid=(B, H, S // chunk)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret, name=PREFILL_KERNEL,
    )(lengths.astype(jnp.int32), jnp.asarray(slopes, jnp.float32), qt, kt,
      vt, state)
    return jnp.swapaxes(o, 1, 2), state


# ``prefill`` and ``decode_step`` are jitted on their own: the layers of a
# program whose layers run one after the other (weights a layer kind) call
# them with the same shapes, so a program holds ONE traced and lowered body
# of each however many layers call it. The compiler inlines the calls; the
# kernels, the scope and every precision are what they were. The slopes
# are constants of the body (the kernels are handed them as an array made
# while tracing), so they are static: a tuple of floats, which is what
# ``LlamaConfig.linear_decay`` is.
def _static_slopes(slopes, where: str) -> None:
    """Refuse, before jit hashes it or a trace holds it, a ``slopes``
    that is not a tuple of floats (an array cannot key a jitted
    function, and a tracer would key a new body every call)."""
    if not (isinstance(slopes, tuple)
            and all(isinstance(s, float) for s in slopes)):
        raise TypeError(
            f"{where}: slopes is static to the jitted function and has to "
            f"be a tuple of floats (LlamaConfig.linear_decay, or "
            f"tuple(map(float, slopes_of(heads)))), not "
            f"{type(slopes).__name__}")


def prefill(q, k, v, slopes, lengths=None, state=None, *, scale: float):
    """The rows' outputs and the state behind their last token. q, k, v
    [B, S, H, hd]; slopes, a tuple of H floats (``-log`` of a head's
    decay a token; static);
    lengths int32 [B]: the rows that are tokens are the first
    ``lengths`` (None: all); state float32 [B, H, hd, hd], the state
    before the first row (None: zeros). Returns (o float32 [B, S, H,
    hd]; state float32 [B, H, hd, hd]). Rows that are no whole number of
    chunks of 128 (a short bucket, a speculative window) take the
    recurrence itself."""
    _static_slopes(slopes, "linear_attention.prefill")
    return _prefill(q, k, v, slopes, lengths, state, scale=scale)


@functools.partial(jax.jit, static_argnames=("slopes", "scale"))
def _prefill(q, k, v, slopes, lengths, state, *, scale):
    B, S, H, hd = q.shape
    with jax.named_scope("rt.attn.linear"):
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        if state is None:
            state = jnp.zeros((B, H, hd, hd), jnp.float32)
        if S % CHUNK:
            return recurrence(q, k, v, slopes, lengths, state, scale=scale)
        return jax.lax.platform_dependent(
            q, k, v, lengths, state,
            tpu=lambda *a: prefill_tpu(*a[:3], slopes, *a[3:], scale=scale),
            default=lambda *a: prefill_xla(*a[:3], slopes, *a[3:],
                                           scale=scale))


# ------------------------------------------------------------ a decode step
def decode_step_xla(q, k, v, pool, layer, active, slopes, *, scale):
    decay = jnp.exp(-jnp.asarray(slopes, jnp.float32))[None, :, None, None]
    s = pool[layer]
    new = decay * s + jnp.einsum(
        "bhk,bhv->bhkv", k.astype(jnp.float32), v.astype(jnp.float32),
        precision="highest")
    s = jnp.where(active[:, None, None, None], new, s)
    o = scale * jnp.einsum("bhk,bhkv->bhv", q.astype(jnp.float32), s,
                           precision="highest")
    return jnp.where(active[:, None, None], o, 0.0), pool.at[layer].set(s)


def _decode_kernel(order_ref, live_ref, slope_ref, layer_ref, q_ref, k_ref,
                   v_ref, s_ref, o_ref, out_ref, *, scale, heads):
    from jax.experimental import pallas as pl

    hb, g = pl.program_id(0), pl.program_id(1)
    live = live_ref[0]

    @pl.when(g < live)
    def _():
        for i in range(heads):
            slope = slope_ref[hb * heads + i]
            # q and k as columns [hd, 1], v as a row [1, hd]: the outer
            # product and the row times the state on the VPU, in float32
            s = jnp.exp(-slope) * s_ref[i] + (
                k_ref[i].astype(jnp.float32) * v_ref[i].astype(jnp.float32))
            out_ref[i] = s
            o_ref[i] = scale * (q_ref[i].astype(jnp.float32) * s).sum(
                0, keepdims=True)

    @pl.when(live == 0)
    def _():
        # no slot decodes: the one block the grid holds goes back as it
        # came (an output block is written back whatever the body did)
        out_ref[...] = s_ref[...]


_DECODE_HEADS = 8


def live_order(active):
    """(order int32 [B], live int32 [1]) of a decode step's slots: the
    numbers of the slots that decode first, in order, then the last of
    them again; how many decode. The same for every layer and step of a
    burst: ``decode_step`` takes it where the caller made it once."""
    live = active.sum().astype(jnp.int32)
    rank = jnp.argsort(~active, stable=True).astype(jnp.int32)
    return jnp.where(jnp.arange(active.shape[0]) < live, rank,
                     rank[jnp.maximum(live - 1, 0)]), live[None]


def decode_step_tpu(q, k, v, pool, layer, active, order, live, slopes, *,
                    scale, interpret=False):
    """The kernel: grid (block of heads, live slot), over the slots that
    decode, in order (``order``: their numbers first, then the last of
    them again, so that a step past the live ones asks for the block it
    already holds, fetches nothing, does nothing, and the block is
    written back once, as the last live step left it). The pool is the
    kernel's input AND output (aliased): a step reads a slot's block of
    heads and writes it back where it lay; every other block of the pool
    is left as it is."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    heads = min(_DECODE_HEADS, H)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def slot_rows(hb, g, order, live, slope, layer):
        return order[g], hb, 0, 0

    def slot_state(hb, g, order, live, slope, layer):
        return layer[0], order[g], hb, 0, 0

    o, pool = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, heads=heads),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, hd), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((None, heads, hd, 1), slot_rows),
                      pl.BlockSpec((None, heads, hd, 1), slot_rows),
                      pl.BlockSpec((None, heads, 1, hd), slot_rows),
                      pl.BlockSpec((None, None, heads, hd, hd), slot_state)],
            out_specs=[pl.BlockSpec((None, heads, 1, hd), slot_rows),
                       pl.BlockSpec((None, None, heads, hd, hd),
                                    slot_state)],
            grid=(H // heads, B)),
        # operand 7 (behind the 4 prefetched scalars and q, k, v): the pool
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret, name=DECODE_KERNEL,
    )(order, live, jnp.asarray(slopes, jnp.float32), layer,
      q[..., None], k[..., None], v[:, :, None], pool)
    # a slot that does not decode was given no output block
    return jnp.where(active[:, None, None], o[:, :, 0], 0.0), pool


def decode_step(q, k, v, pool, layer, active, slopes, *, scale: float,
                order=None):
    """One token a slot: q, k, v [B, H, hd]; pool float32 [L, B, H, hd,
    hd], the whole stack, ``layer`` picking its layer in the block's
    address; active bool [B]; ``order``: ``live_order(active)``, where
    the caller has it already. Returns (o float32 [B, H, hd], zeros for
    a slot that is not active; the pool, the active slots' states of the
    layer advanced by the token, everything else as it was). ``slopes``
    as ``prefill`` takes them."""
    _static_slopes(slopes, "linear_attention.decode_step")
    return _decode_step(q, k, v, pool, layer, active, slopes, scale=scale,
                        order=order)


@functools.partial(jax.jit, static_argnames=("slopes", "scale"))
def _decode_step(q, k, v, pool, layer, active, slopes, *, scale, order):
    with jax.named_scope("rt.attn.linear"):
        order, live = live_order(active) if order is None else order
        return jax.lax.platform_dependent(
            q, k, v, pool, jnp.asarray(layer, jnp.int32), active, order,
            live,
            tpu=lambda *a: decode_step_tpu(*a, slopes, scale=scale),
            default=lambda *a: decode_step_xla(*a[:6], slopes, scale=scale))


def slopes_of(heads: int) -> np.ndarray:
    """Lightning Attention-2's slopes: ``2 ** (-8 (h + 1) / heads)``."""
    return np.asarray([2.0 ** (-8.0 * (h + 1) / heads)
                       for h in range(heads)], np.float32)
