"""The selective scan (``ray_tpu/ops/selective_scan.py``): the chunked
prefill kernel and the in-place decode kernel (Pallas interpreter, CPU)
against the recurrence, chunk boundaries, rows behind a prompt's end and
a carried state and tail included."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan as ss

N = 16


def _inputs(B, T, E, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(ks[0], (B, T, E))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, E)) - 3.0)
    Bm = jax.random.normal(ks[2], (B, T, N))
    Cm = jax.random.normal(ks[3], (B, T, N))
    a = -jnp.exp(0.5 * jax.random.normal(ks[4], (N, E)))
    d = jax.random.normal(ks[5], (E,))
    s0 = jax.random.normal(ks[6], (B, N, E))
    return u, dt, Bm, Cm, a, d, s0


def _by_hand(u, dt, Bm, Cm, a, d, s):
    """The issue's equations, a token, channel and state index at a time
    in numpy: a third way of writing them."""
    u, dt, Bm, Cm, a, d = (np.asarray(x, np.float64)
                           for x in (u, dt, Bm, Cm, a, d))
    s = np.array(s, np.float64)
    y = np.zeros_like(u)
    for t in range(u.shape[0]):
        s = np.exp(dt[t][None, :] * a) * s \
            + (dt[t] * u[t])[None, :] * Bm[t][:, None]
        y[t] = (s * Cm[t][:, None]).sum(0) + d * u[t]
    return y, s


def test_the_recurrence_is_the_equations():
    u, dt, Bm, Cm, a, d, s0 = _inputs(1, 9, 128)
    y, s = ss.recurrence(u, dt, Bm, Cm, a, d, None, s0)
    want_y, want_s = _by_hand(u[0], dt[0], Bm[0], Cm[0], a, d, s0[0])
    np.testing.assert_allclose(y[0], want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s[0], want_s, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lengths", [(512, 512), (512, 300), (256, 1),
                                     (257, 511), (0, 255)])
@pytest.mark.parametrize("E", [128, 1024, 2048])
def test_the_prefill_kernel_is_the_recurrence(E, lengths):
    """Two chunks of 256, rows that end inside the first chunk, at its
    edge, one token behind it and nowhere; one block of channels
    narrower than a register's eight rows, one block, two blocks."""
    u, dt, Bm, Cm, a, d, s0 = _inputs(2, 512, E, seed=E)
    lens = jnp.asarray(lengths, jnp.int32)
    y, s = ss.prefill_tpu(u, dt, Bm, Cm, a, d, lens, s0, interpret=True)
    want_y, want_s = ss.recurrence(u, dt, Bm, Cm, a, d, lens, s0)
    tok = (jnp.arange(512)[None, :] < lens[:, None])[..., None]
    np.testing.assert_allclose(jnp.where(tok, y, 0), jnp.where(tok, want_y, 0),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s, want_s, rtol=2e-5, atol=2e-5)
    # a row with no token leaves its state as it came
    if 0 in lengths:
        np.testing.assert_array_equal(s[lengths.index(0)],
                                      s0[lengths.index(0)])


@pytest.mark.parametrize("cut", [256, 512])
def test_a_carried_state_is_one_pass(cut):
    """Rows [0, cut) and then [cut, 768) from the state the first left
    are the 768 rows in one pass: the kernel across the cut, and
    ``prefill`` itself (the recurrence on the CPU)."""
    u, dt, Bm, Cm, a, d, _ = _inputs(1, 768, 256, seed=7)
    whole_y, whole_s = ss.recurrence(u, dt, Bm, Cm, a, d)
    for run in (lambda *x: ss.prefill_tpu(*x, interpret=True), ss.prefill):
        ones = jnp.asarray([cut], jnp.int32)
        y1, s1 = run(u[:, :cut], dt[:, :cut], Bm[:, :cut], Cm[:, :cut], a,
                     d, ones, jnp.zeros((1, N, 256)))
        y2, s2 = run(u[:, cut:], dt[:, cut:], Bm[:, cut:], Cm[:, cut:], a,
                     d, jnp.asarray([768 - cut], jnp.int32), s1)
        np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), whole_y,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(s2, whole_s, rtol=2e-5, atol=2e-5)


def test_rows_that_are_no_whole_chunks_take_the_recurrence():
    u, dt, Bm, Cm, a, d, s0 = _inputs(1, 24, 128)
    lens = jnp.asarray([17], jnp.int32)
    y, s = ss.prefill(u, dt, Bm, Cm, a, d, lens, s0)
    want_y, want_s = ss.recurrence(u, dt, Bm, Cm, a, d, lens, s0)
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(s, want_s)


@pytest.mark.parametrize("taps", [2, 4])
def test_the_convolution_carries_its_tail(taps):
    """Rows in two pieces, the second handed the first's tail, are the
    rows in one; the tail behind a short row is that row's last inputs."""
    ks = jax.random.split(jax.random.PRNGKey(taps), 3)
    x = jax.random.normal(ks[0], (2, 12, 128))
    w, b = jax.random.normal(ks[1], (taps, 128)), jax.random.normal(
        ks[2], (128,))
    zero = jnp.zeros((2, taps - 1, 128))
    whole, _ = ss.causal_conv(x, zero, w, b, jnp.asarray([12, 12]))
    first, tail = ss.causal_conv(x[:, :5], zero, w, b, jnp.asarray([5, 5]))
    second, tail2 = ss.causal_conv(x[:, 5:], tail, w, b, jnp.asarray([7, 3]))
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(tail2[0], x[0, 12 - (taps - 1):])
    np.testing.assert_array_equal(tail2[1], x[1, 8 - (taps - 1):8])
    # by hand: the newest input's tap is the last
    want = b + sum(w[j] * (x[0, 6 - (taps - 1) + j]) for j in range(taps))
    np.testing.assert_allclose(whole[0, 6], want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("active", [(True, False, True, True),
                                    (False, False, False, False),
                                    (False, True, False, False),
                                    (True, True, True, True)])
@pytest.mark.parametrize("layer", [0, 2])
def test_the_decode_kernel_updates_the_live_slots_in_place(layer, active):
    E, S, L = 256, 4, 3
    u, dt, Bm, Cm, a, d, _ = _inputs(1, S, E, seed=11)
    u, dt, Bm, Cm = u[0], dt[0], Bm[0], Cm[0]
    pool = jax.random.normal(jax.random.PRNGKey(5),
                             (L, S, N + 3, E // 128, 128))
    active = jnp.asarray(active)
    want_y, want_s = ss.recurrence(
        u[:, None], dt[:, None], Bm[:, None], Cm[:, None], a, d, None,
        pool[layer, :, :N].reshape(S, N, E))
    order, live = ss.live_order(active)
    for y, out in (
            ss.decode_step_xla(u, dt, Bm, Cm, a, d, pool, layer, active),
            ss.decode_step_tpu(u, dt, Bm, Cm, a, d, pool, jnp.int32(layer),
                               active, order, live, interpret=True),
            ss.decode_step(u, dt, Bm, Cm, a, d, pool, layer, active)):
        np.testing.assert_allclose(
            y, jnp.where(active[:, None], want_y[:, 0], 0.0), rtol=2e-5,
            atol=2e-5)
        got = out[layer, :, :N].reshape(S, N, E)
        np.testing.assert_allclose(
            got, jnp.where(active[:, None, None], want_s,
                           pool[layer, :, :N].reshape(S, N, E)),
            rtol=2e-5, atol=2e-5)
        # the tail's rows, the idle slots and the other layers: untouched
        np.testing.assert_array_equal(out[layer, :, N:], pool[layer, :, N:])
        others = [i for i in range(L) if i != layer]
        np.testing.assert_array_equal(out[jnp.asarray(others)],
                                      pool[jnp.asarray(others)])
        idle = np.flatnonzero(~np.asarray(active))
        np.testing.assert_array_equal(out[layer, idle], pool[layer, idle])


def test_the_kernels_have_names_of_their_own():
    """``harness/trace.py`` keeps a kernel's events under the name its
    ``pallas_call`` was given: the two readers look for these."""
    assert (ss.PREFILL_KERNEL, ss.DECODE_KERNEL) == (
        "rt_scan_prefill", "rt_scan_decode")
