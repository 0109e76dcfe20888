"""The selective scan of a state-space layer (Mamba-1, arXiv:2312.00752).

A layer of ``E`` channels keeps no key and no value of the past: a
channel's memory is ``N`` float32 numbers a sequence, and a token at
position t does, for channel e and state index n,

    s[n, e] = exp(dt[t, e] a[n, e]) s[n, e] + dt[t, e] u[t, e] B[t, n]
    y[t, e] = sum_n s[n, e] C[t, n] + d[e] u[t, e]

(``a`` negative, ``dt`` positive: the decay is a channel's AND a state
index's, which ``ops/ssd.py``'s scalar decay a head cannot express).
Everything here is float32. Two forms of the one recurrence, each under
the ``jax.named_scope`` ``rt.scan`` and, on a TPU, a named Pallas kernel
of its own (``harness/trace.py`` keeps those names):

  * ``prefill`` (``rt_scan_prefill``): the tokens side by side, walked
    in chunks of ``CHUNK`` with the state carried from chunk to chunk.
    Grid (row, block of 1,024 channels, chunk): a channel block's state
    is 16 registers of [8, 128] that ride the chunk's token loop, ``B``
    and ``C`` are scalars in SMEM, so a token costs a block seven vector
    operations and one ``exp`` a state index and neither a transposition
    nor a reduction across lanes. The vector unit bounds it, not HBM.
  * ``decode_step`` (``rt_scan_decode``): one token a slot. The pool
    ``[layers, slots, N + taps - 1, E / 128, 128]`` float32 (a slot's
    state rows, then the convolution's tail: ``causal_conv``) is updated
    IN PLACE (aliased to the kernel's output): a slot that decodes reads
    its state once and writes it once, a slot that does not is neither
    read nor written.

The channel axis is split (E / 128, 128) wherever a kernel sees it: whole
lanes, and eight rows of them a register. Rows that are padding (a
bucket's tail behind a prompt's end, an idle slot of a burst) change no
state: a prefill is told the rows' ``lengths``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .linear_attention import live_order  # noqa: F401  (callers take it here)

PREFILL_KERNEL = "rt_scan_prefill"
DECODE_KERNEL = "rt_scan_decode"
CHUNK = 256
LANES = 128
_ROWS = 8                    # rows of lanes a channel block holds
_VMEM = 64 * 1024 * 1024


def _lanes(x):
    """[..., E] -> [..., E / 128, 128]: the channel axis as a kernel
    sees it."""
    return x.reshape(*x.shape[:-1], x.shape[-1] // LANES, LANES)


# ----------------------------------------------------------- the recurrence
def recurrence(u, dt, Bm, Cm, a, d, lengths=None, state=None):
    """The definition, a token at a time (the tests' oracle, and what a
    platform without the kernels runs). u, dt float32 [B, T, E]; Bm, Cm
    [B, T, N]; a [N, E]; d [E]; lengths int32 [B] (None: every row a
    token); state [B, N, E] (None: zeros). Returns (y [B, T, E], the
    state after the last token)."""
    B, T, E = u.shape
    if state is None:
        state = jnp.zeros((B, a.shape[0], E), jnp.float32)
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)

    def step(s, row):
        t, ut, dtt, bt, ct = row
        new = (jnp.exp(dtt[:, None, :] * a[None]) * s
               + (dtt * ut)[:, None, :] * bt[:, :, None])
        s = jnp.where((t < lengths)[:, None, None], new, s)
        return s, (s * ct[:, :, None]).sum(1) + d[None] * ut

    state, y = jax.lax.scan(
        step, state, (jnp.arange(T), *(jnp.swapaxes(x, 0, 1)
                                       for x in (u, dt, Bm, Cm))))
    return jnp.swapaxes(y, 0, 1), state


def causal_conv(x, tail, w, b, lengths):
    """The causal depthwise convolution in front of the scan, and what it
    leaves for the next token. x [B, T, E], the rows' inputs; tail [B,
    taps - 1, E], the inputs before them (zeros at a sequence's start); w
    [taps, E], the newest input's tap last; b [E]; lengths int32 [B].
    Returns (out float32 [B, T, E]; the last taps - 1 inputs behind row
    ``lengths - 1``, the next call's ``tail``)."""
    taps, T = w.shape[0], x.shape[1]
    ext = jnp.concatenate([tail.astype(jnp.float32),
                           x.astype(jnp.float32)], 1)
    out = b.astype(jnp.float32) + sum(
        w[j].astype(jnp.float32) * ext[:, j:j + T] for j in range(taps))
    new_tail = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
        rows, n, taps - 1, 0))(ext, lengths)
    return out, new_tail


# ------------------------------------------------------------ chunked form
def _prefill_kernel(lens_ref, bt_ref, ct_ref, u_ref, dt_ref, a_ref, d_ref,
                    s0_ref, y_ref, s_ref, *, chunk, n_state):
    from jax.experimental import pallas as pl

    b, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_ref[...] = s0_ref[...]

    n = jnp.clip(lens_ref[b] - c * chunk, 0, chunk)

    @pl.when(n == 0)
    def _():
        # a chunk wholly behind the prompt's end: no token, no change
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n > 0)
    def _():
        a = [a_ref[i] for i in range(n_state)]
        d = d_ref[...]

        def token(t, s):
            dt = jnp.where(t < n, dt_ref[t], 0.0)   # dt 0: nothing changes
            u = u_ref[t]
            dtu = dt * u
            y = d * u
            out = []
            for i in range(n_state):
                si = jnp.exp(dt * a[i]) * s[i] + dtu * bt_ref[i, t]
                y = y + si * ct_ref[i, t]
                out.append(si)
            y_ref[t] = y
            return tuple(out)

        s = jax.lax.fori_loop(
            0, chunk, token, tuple(s_ref[i] for i in range(n_state)))
        for i in range(n_state):
            s_ref[i] = s[i]


def prefill_tpu(u, dt, Bm, Cm, a, d, lengths, state, *, chunk=CHUNK,
                interpret=False):
    """The kernel: grid (row, channel block, chunk). A step holds a
    chunk's ``u`` and ``dt`` of a channel block in VMEM and the chunk's
    ``B`` and ``C`` in SMEM (state index major, a scalar a token); the
    block's state is the float32 output block, held from the block's
    first chunk to its last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, E = u.shape
    N, R = a.shape[0], E // LANES
    rows = min(_ROWS, R)

    def tokens(b, e, c, *_):
        return b, c, e, 0

    def scalars(b, e, c, *_):
        return b, 0, c

    def channels(b, e, c, *_):
        return 0, e, 0

    def carried(b, e, c, *_):
        return b, 0, e, 0

    y, state = pl.pallas_call(
        functools.partial(_prefill_kernel, chunk=chunk, n_state=N),
        out_shape=[jax.ShapeDtypeStruct((B, T, R, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, R, LANES), jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((None, N, chunk), scalars,
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((None, N, chunk), scalars,
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((None, chunk, rows, LANES), tokens),
                pl.BlockSpec((None, chunk, rows, LANES), tokens),
                pl.BlockSpec((N, rows, LANES), channels),
                pl.BlockSpec((rows, LANES), lambda b, e, c, *_: (e, 0)),
                pl.BlockSpec((None, N, rows, LANES), carried)],
            out_specs=[pl.BlockSpec((None, chunk, rows, LANES), tokens),
                       pl.BlockSpec((None, N, rows, LANES), carried)],
            grid=(B, R // rows, T // chunk)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret, name=PREFILL_KERNEL,
    )(lengths.astype(jnp.int32), jnp.swapaxes(Bm, 1, 2),
      jnp.swapaxes(Cm, 1, 2), _lanes(u), _lanes(dt), _lanes(a), _lanes(d),
      _lanes(state))
    return y.reshape(B, T, E), state.reshape(B, N, E)


@jax.jit
def prefill(u, dt, Bm, Cm, a, d, lengths=None, state=None):
    """The rows' outputs and the state behind their last token: shapes
    as ``recurrence``'s. Rows that are no whole number of chunks (a short
    bucket) take the recurrence itself. Jitted on its own, so a program
    whose layers call it with the same shapes holds one traced body."""
    B, T, E = u.shape
    with jax.named_scope("rt.scan"):
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        if state is None:
            state = jnp.zeros((B, a.shape[0], E), jnp.float32)
        if T % CHUNK:
            return recurrence(u, dt, Bm, Cm, a, d, lengths, state)
        return jax.lax.platform_dependent(
            u, dt, Bm, Cm, a, d, lengths, state,
            tpu=prefill_tpu, default=recurrence)


# ------------------------------------------------------------ a decode step
def decode_step_xla(u, dt, Bm, Cm, a, d, pool, layer, active):
    N = a.shape[0]
    s = pool[layer, :, :N].reshape(u.shape[0], N, -1)
    new = (jnp.exp(dt[:, None, :] * a[None]) * s
           + (dt * u)[:, None, :] * Bm[:, :, None])
    s = jnp.where(active[:, None, None], new, s)
    y = (s * Cm[:, :, None]).sum(1) + d[None] * u
    return jnp.where(active[:, None], y, 0.0), jax.lax.dynamic_update_slice(
        pool, s.reshape(1, s.shape[0], N, *pool.shape[3:]),
        (layer, 0, 0, 0, 0))


def _decode_kernel(order_ref, live_ref, layer_ref, bm_ref, cm_ref, u_ref,
                   dt_ref, a_ref, d_ref, s_ref, y_ref, out_ref, *, n_state):
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    live = live_ref[0]

    @pl.when(g < live)
    def _():
        slot = order_ref[g]
        dt, u = dt_ref[...], u_ref[...]
        dtu = dt * u
        y = d_ref[...] * u
        for i in range(n_state):
            si = (jnp.exp(dt * a_ref[i]) * s_ref[i]
                  + dtu * bm_ref[slot * n_state + i])
            out_ref[i] = si
            y = y + si * cm_ref[slot * n_state + i]
        y_ref[...] = y

    @pl.when(live == 0)
    def _():
        # no slot decodes: the one block the grid holds goes back as it
        # came (an output block is written back whatever the body did)
        out_ref[...] = s_ref[...]


def decode_step_tpu(u, dt, Bm, Cm, a, d, pool, layer, active, order, live,
                    *, interpret=False):
    """The kernel: a grid step a live slot, in ``order`` (their numbers
    first, then the last of them again, so that a step past the live ones
    asks for the block it already holds, fetches nothing, does nothing,
    and the block is written back once, as the last live step left it).
    The pool is the kernel's input AND output (aliased): a step reads the
    slot's state rows of the layer and writes them back where they lay;
    the tail rows and every other block are left as they are."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, E = u.shape
    N, R = a.shape[0], E // LANES
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def slot_rows(g, order, *_):
        return order[g], 0, 0

    def slot_state(g, order, live, layer, *_):
        return layer[0], order[g], 0, 0, 0

    y, pool = pl.pallas_call(
        functools.partial(_decode_kernel, n_state=N),
        out_shape=[jax.ShapeDtypeStruct((B, R, LANES), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[pl.BlockSpec((None, R, LANES), slot_rows),
                      pl.BlockSpec((None, R, LANES), slot_rows),
                      pl.BlockSpec((N, R, LANES), lambda g, *_: (0, 0, 0)),
                      pl.BlockSpec((R, LANES), lambda g, *_: (0, 0)),
                      pl.BlockSpec((None, None, N, R, LANES), slot_state)],
            out_specs=[pl.BlockSpec((None, R, LANES), slot_rows),
                       pl.BlockSpec((None, None, N, R, LANES), slot_state)],
            grid=(B,)),
        # operand 9 (behind the 5 prefetched scalars and u, dt, a, d)
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret, name=DECODE_KERNEL,
    )(order, live, layer, Bm.reshape(-1), Cm.reshape(-1), _lanes(u),
      _lanes(dt), _lanes(a), _lanes(d), pool)
    # a slot that does not decode was given no output block
    return jnp.where(active[:, None], y.reshape(B, E), 0.0), pool


@jax.jit
def decode_step(u, dt, Bm, Cm, a, d, pool, layer, active, order=None):
    """One token a slot: u, dt float32 [B, E]; Bm, Cm [B, N]; pool
    float32 [L, B, N + taps - 1, E / 128, 128], the whole stack, ``layer``
    picking its layer in the block's address; active bool [B]; ``order``:
    ``live_order(active)``, where the caller has it already. Returns (y
    float32 [B, E], zeros for a slot that is not active; the pool, the
    active slots' states of the layer advanced by the token, everything
    else as it was)."""
    with jax.named_scope("rt.scan"):
        order, live = live_order(active) if order is None else order
        return jax.lax.platform_dependent(
            u, dt, Bm, Cm, a, d, pool, jnp.asarray(layer, jnp.int32), active,
            order, live,
            tpu=decode_step_tpu,
            default=lambda *x: decode_step_xla(*x[:9]))
