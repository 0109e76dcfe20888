"""Linear attention with a decaying state (``ops/linear_attention.py``):
the chunked form against the recurrence it abbreviates, a decode step
against one step of it, and what padding may not do.

float32 on both sides: the chunked form sums the same products in
another order (a chunk's decayed ``q k^T`` times ``v``, the state's
share added behind), which reads 1e-6 to 1e-5 on outputs of deviation
about 1. The kernels run in Pallas's interpret mode here; what Mosaic
makes of them is ``tests/test_tpu_compile.py``'s and the chip's.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import linear_attention as la

H, HD = 4, 128
SCALE = HD ** -0.5
TOLERANCE = 5e-5
# the slopes as ``prefill`` and ``decode_step`` take them (static to the
# jitted functions): what ``LlamaConfig.linear_decay`` is
DECAY = tuple(map(float, la.slopes_of(H)))


def _rows(seed, batch, seq):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (0.3 * jax.random.normal(key, (batch, seq, H, HD), jnp.float32)
               for key in ks[:3])
    return q, k, v, jax.random.normal(ks[3], (batch, H, HD, HD), jnp.float32)


def _close(a, b):
    return float(jnp.abs(a - b).max())


def test_the_slopes_are_lightning_attention_2s():
    slopes = la.slopes_of(32)
    assert slopes.shape == (32,)
    np.testing.assert_allclose(slopes[0], 2.0 ** -0.25, rtol=1e-6)
    np.testing.assert_allclose(slopes[-1], 2.0 ** -8, rtol=1e-6)


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("lengths", [(256, 256), (200, 77), (128, 0)],
                         ids=["whole", "ragged", "empty-row"])
def test_chunked_form_is_the_recurrence(form, lengths):
    """Chunks of 128 that do (256) and do not (200, 77) divide the
    rows' lengths, from a state that is not zero."""
    q, k, v, s0 = _rows(1, 2, 256)
    lens = jnp.asarray(lengths, jnp.int32)
    slopes = la.slopes_of(H)
    want_o, want_s = la.recurrence(q, k, v, slopes, lens, s0, scale=SCALE)
    if form == "xla":
        o, s = la.prefill_xla(q, k, v, slopes, lens, s0, scale=SCALE)
    else:
        o, s = la.prefill_tpu(q, k, v, slopes, lens, s0, scale=SCALE,
                              interpret=True)
    token = (jnp.arange(256)[None, :] < lens[:, None])[..., None, None]
    assert _close(jnp.where(token, o, 0), jnp.where(token, want_o, 0)) \
        < TOLERANCE
    assert _close(s, want_s) < TOLERANCE
    assert s.dtype == jnp.float32 and o.dtype == jnp.float32


def test_rows_that_are_no_whole_chunks_take_the_recurrence():
    q, k, v, s0 = _rows(2, 1, 40)
    slopes = la.slopes_of(H)
    o, s = la.prefill(q, k, v, DECAY, jnp.asarray([33], jnp.int32), s0,
                      scale=SCALE)
    want_o, want_s = la.recurrence(q, k, v, slopes,
                                   jnp.asarray([33], jnp.int32), s0,
                                   scale=SCALE)
    assert _close(o, want_o) == 0.0 and _close(s, want_s) == 0.0


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_padding_rows_leave_a_state_to_the_bit(form):
    """A row with no token (length 0) keeps its state bit for bit, and a
    row's state stops at its last token whatever lies behind it."""
    q, k, v, s0 = _rows(3, 2, 256)
    slopes = la.slopes_of(H)
    run = la.prefill_xla if form == "xla" else (
        lambda *a, **kw: la.prefill_tpu(*a, **kw, interpret=True))
    _, s = run(q, k, v, slopes, jnp.asarray([0, 130], jnp.int32), s0,
               scale=SCALE)
    assert bool((s[0] == s0[0]).all())
    # other rows behind the row's end: the same state
    noise = jnp.where(jnp.arange(256)[None, :, None, None] >= 130, 7.0, 0.0)
    _, s2 = run(q + noise, k + noise, v + noise, slopes,
                jnp.asarray([0, 130], jnp.int32), s0, scale=SCALE)
    assert bool((s2 == s).all())


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_a_decode_step_is_one_step_of_the_recurrence(form):
    B = 4
    q, k, v, _ = _rows(4, B, 1)
    pool = jax.random.normal(jax.random.PRNGKey(5), (3, B, H, HD, HD),
                             jnp.float32)
    active = jnp.asarray([True, False, True, True])
    slopes = la.slopes_of(H)
    want_o, want_s = la.recurrence(q, k, v, slopes, None, pool[1],
                                   scale=SCALE)
    step = la.decode_step_xla if form == "xla" else (
        lambda *a, **kw: la.decode_step_tpu(
            *a[:6], *la.live_order(active), *a[6:], **kw, interpret=True))
    o, new = step(q[:, 0], k[:, 0], v[:, 0], pool, jnp.int32(1), active,
                  slopes, scale=SCALE)
    live = np.asarray(active)
    assert _close(o[live], want_o[live, 0]) < TOLERANCE
    assert _close(new[1][live], want_s[live]) < TOLERANCE
    assert new.dtype == jnp.float32
    # an idle slot and every other layer: to the bit; its output zeros
    assert bool((new[1, 1] == pool[1, 1]).all())
    assert bool((new[0] == pool[0]).all()) and bool((new[2] == pool[2]).all())
    assert bool((o[1] == 0).all())


def test_the_live_slots_first_then_the_last_of_them_again():
    order, live = la.live_order(jnp.asarray([False, True, False, True,
                                             False]))
    assert order.tolist() == [1, 3, 3, 3, 3] and live.tolist() == [2]
    order, live = la.live_order(jnp.zeros(3, bool))
    assert order.tolist() == [0, 0, 0] and live.tolist() == [0]


def test_a_step_with_no_live_slot_changes_nothing():
    q, k, v, _ = _rows(6, 4, 1)
    pool = jax.random.normal(jax.random.PRNGKey(7), (2, 4, H, HD, HD),
                             jnp.float32)
    idle = jnp.zeros(4, bool)
    _, new = la.decode_step_tpu(q[:, 0], k[:, 0], v[:, 0], pool,
                                jnp.int32(0), idle, *la.live_order(idle),
                                la.slopes_of(H), scale=SCALE, interpret=True)
    assert bool((new == pool).all())


def test_prefill_then_decode_is_one_recurrence():
    """A prompt through the chunked form, then tokens one at a time
    through the pool: the states and outputs of one pass over all."""
    q, k, v, _ = _rows(8, 1, 256 + 3)
    slopes = la.slopes_of(H)
    want_o, want_s = la.recurrence(q, k, v, slopes, scale=SCALE)
    _, s = la.prefill(q[:, :256], k[:, :256], v[:, :256], DECAY,
                      scale=SCALE)
    pool = jnp.zeros((1, 1, H, HD, HD), jnp.float32).at[0].set(s)
    for t in range(256, 259):
        o, pool = la.decode_step(q[:, t], k[:, t], v[:, t], pool, 0,
                                 jnp.ones(1, bool), DECAY, scale=SCALE)
        assert _close(o, want_o[:, t]) < TOLERANCE
    assert _close(pool[0], want_s) < TOLERANCE


# ------------------------------------------------- jitted on their own
def _prefill_case(slopes):
    q, k, v, s0 = _rows(9, 1, 256)
    return (lambda f, q, k, v, lens, s0: f(q, k, v, slopes, lens, s0,
                                           scale=SCALE),
            (q, k, v, jnp.asarray([200], jnp.int32), s0))


def _decode_case(slopes):
    q, k, v, _ = _rows(10, 4, 1)
    pool = jax.random.normal(jax.random.PRNGKey(11), (2, 4, H, HD, HD),
                             jnp.float32)
    return (lambda f, q, k, v, pool, active: f(
                q, k, v, pool, 1, active, slopes, scale=SCALE,
                order=la.live_order(active)),
            (q[:, 0], k[:, 0], v[:, 0], pool,
             jnp.asarray([True, False, True, True])))


CASES = {"prefill": _prefill_case, "decode_step": _decode_case}


@pytest.mark.parametrize("name", CASES)
def test_a_program_that_calls_the_jitted_function_has_its_bodys_bits(name):
    """The public function hands its arguments to a function jitted on
    its own (the layers of a program then share ONE traced body of it).
    A program that calls it computes the same bits as one that holds
    that function's undecorated body inline, as every program did, on
    the plain-jax twin the CPU takes. (Both sides are a program: run op
    by op the body's sums are not fused and differ in the last bit.)"""
    call, rows = CASES[name](DECAY)
    got = jax.jit(functools.partial(call, getattr(la, name)))(*rows)
    want = jax.jit(functools.partial(
        call, getattr(la, "_" + name).__wrapped__))(*rows)
    for a, b in zip(got, want, strict=True):
        assert a.dtype == b.dtype and bool((a == b).all())


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("kind", ["numpy", "jax", "list", "tracer"])
def test_slopes_that_are_no_tuple_of_floats_are_refused_by_name(name, kind):
    """The slopes key the jitted function: an array cannot, and a tracer
    (a program that made its slopes an operand) would key a new body
    every call. The public function refuses both by name, before the
    jitted function is asked for anything."""
    jitted, array = getattr(la, "_" + name), jnp.asarray(la.slopes_of(H))

    def call(slopes):
        run, rows = CASES[name](slopes)
        return run(getattr(la, name), *rows)

    before = jitted._cache_size()
    with pytest.raises(TypeError, match=f"{name}: slopes .* tuple of floats"):
        if kind == "tracer":
            jax.jit(call)(array)
        else:
            call({"numpy": la.slopes_of(H), "jax": array,
                  "list": list(DECAY)}[kind])
    assert jitted._cache_size() == before
