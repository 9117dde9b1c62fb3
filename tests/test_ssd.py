"""``ops/ssd.py``: the Mamba-2 recurrence with a scalar decay a head. The
chunked scan an admission runs against the token-serial recurrence (at
lengths that are and are not multiples of the chunk, at one token, from a
carried state, at the published chunk), the step kernel under the Pallas
interpreter against the ``jax.numpy`` step (a slot that is not active
keeps its state bit for bit, the other layers' slabs are untouched; at
the cell's own block of 64 heads; its read-out held to float64 where one
bfloat16 pass is not; a head's channels in the head's own row whatever
the block), and the convolution that feeds them."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchkafka_tpu.ops import ssd

H, P, N = 4, 64, 128


def _tokens(seed: int, b: int, s: int):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (b, s, H, P))
    bias = jax.random.uniform(ks[2], (H,), minval=-5.0, maxval=0.0)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, H)) + bias)
    a = -jax.random.uniform(ks[3], (H,), minval=1.0, maxval=16.0)
    bm = jax.random.normal(ks[4], (b, s, N))
    cm = jax.random.normal(ks[5], (b, s, N))
    return x, dt, a, bm, cm, jnp.linspace(0.5, 1.5, H)


def _serial(x, dt, a, bm, cm, d, state=None):
    """Token by token through ``ssd_step_xla``: (y [B, S, H, P], state)."""
    b = x.shape[0]
    if state is None:
        state = jnp.zeros((b, H, P, N), jnp.float32)

    def token(state, xs):
        y, state = ssd.ssd_step_xla(state, 0, *xs[:2], a, *xs[2:], d)
        return state, y

    state, y = jax.lax.scan(token, state[None], tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)
    ))
    return jnp.moveaxis(y, 0, 1), state[0]


def _close(got, want, tol):
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= tol * scale


@pytest.mark.parametrize("s", [1, 7, 8, 9, 16, 20])
def test_the_chunked_scan_is_the_token_serial_recurrence(s):
    """One token, a chunk less one, whole and plus one, two chunks, and a
    length that is no multiple of the chunk (chunks of 8)."""
    t = _tokens(s, 2, s)
    want_y, want_s = _serial(*t)
    got_y, got_s = jax.jit(lambda *z: ssd.ssd_chunk(*z, chunk=8))(*t)
    assert got_y.shape == want_y.shape and got_s.shape == want_s.shape
    _close(got_y, want_y, 2e-5)
    _close(got_s, want_s, 2e-5)


def test_the_published_chunk_of_256_and_a_tail():
    t = _tokens(11, 1, 300)
    want_y, want_s = _serial(*t)
    got_y, got_s = jax.jit(ssd.ssd_chunk)(*t)
    _close(got_y, want_y, 2e-5)
    _close(got_s, want_s, 2e-5)


def test_the_chunked_scan_carries_a_state_across_calls():
    t = _tokens(7, 1, 40)
    x, dt, a, bm, cm, d = t
    _y, state = ssd.ssd_chunk(x[:, :13], dt[:, :13], a, bm[:, :13], cm[:, :13],
                              d, chunk=8)
    got_y, got_s = ssd.ssd_chunk(
        x[:, 13:], dt[:, 13:], a, bm[:, 13:], cm[:, 13:], d, state=state,
        chunk=8,
    )
    want_y, want_s = _serial(*t)
    _close(got_y, want_y[:, 13:], 2e-5)
    _close(got_s, want_s, 2e-5)


def test_the_slowest_and_the_fastest_decay_stay_inside_float32():
    """A step of 20 at a rate of 16 a head decays by e^-320 a token, a
    step of 1e-4 by nothing: every exponent is formed as a difference
    under the causal mask, and the answer is the recurrence's."""
    x, dt, a, bm, cm, d = _tokens(3, 1, 24)
    dt = dt.at[:, :, 0].set(20.0).at[:, :, 1].set(1e-4)
    a = a.at[0].set(-16.0)
    want_y, want_s = _serial(x, dt, a, bm, cm, d)
    got_y, got_s = ssd.ssd_chunk(x, dt, a, bm, cm, d, chunk=8)
    assert bool(jnp.isfinite(got_y).all()) and bool(jnp.isfinite(got_s).all())
    _close(got_y, want_y, 2e-5)
    _close(got_s, want_s, 2e-5)


def test_the_step_kernel_is_the_jax_numpy_step():
    """Layer 1 of a stack of two, three slots of which the second is not
    active (dt 0): the kernel under the interpreter gives the ``jax.numpy``
    step's read-out and state, keeps the idle slot's state BIT FOR BIT and
    leaves layer 0 alone."""
    x, dt, a, bm, cm, d = _tokens(5, 3, 1)
    state = jax.random.normal(jax.random.key(9), (2, 3, H, P, N))
    dt = dt[:, 0].at[1].set(0.0)
    args = (x[:, 0], dt, a, bm[:, 0], cm[:, 0], d)
    want_y, want_s = ssd.ssd_step_xla(state, 1, *args)
    got_y, got_s = ssd.ssd_step(state, 1, *args, interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-6)
    for s in (got_s, want_s):
        assert bool((s[1, 1] == state[1, 1]).all())
        assert bool((s[0] == state[0]).all())
    # The idle slot still reads its state out: y = S C + D x.
    want = jnp.einsum("hpn,n->hp", state[1, 1], cm[1, 0]) + d[:, None] * x[1, 0]
    np.testing.assert_allclose(got_y[1], want, atol=2e-5)


def test_the_step_kernel_takes_heads_that_no_block_divides():
    """Six heads: the grid takes gcd(6, STEP_HEADS) = 2 a step."""
    ks = jax.random.split(jax.random.key(2), 5)
    h = 6
    state = jax.random.normal(ks[0], (1, 2, h, 16, 128))
    x = jax.random.normal(ks[1], (2, h, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (2, h)))
    bm, cm = jax.random.normal(ks[3], (2, 2, 128))
    a, d = -jnp.arange(1.0, h + 1), jnp.ones((h,))
    want = ssd.ssd_step_xla(state, 0, x, dt, a, bm, cm, d)
    got = ssd.ssd_step(state, 0, x, dt, a, bm, cm, d, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


@pytest.fixture(scope="module")
def real_block():
    """A grid step's block as the cell has it, 64 heads of 64 x 128 a
    slot, layer 1 of a stack of two, the second of two slots not active:
    (the state, the step's arguments, the interpreted kernel's result)."""
    slots, heads = 2, 64
    ks = jax.random.split(jax.random.key(13), 6)
    state = jax.random.normal(ks[0], (2, slots, heads, 64, 128))
    x = jax.random.normal(ks[1], (slots, heads, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, heads)) - 2.0)
    dt = dt.at[1].set(0.0)
    a = -jnp.exp(jax.random.uniform(ks[3], (heads,), maxval=2.7))
    bm, cm = jax.random.normal(ks[4], (2, slots, 128))
    d = jax.random.uniform(ks[5], (heads,), minval=0.5, maxval=1.5)
    args = (x, dt, a, bm, cm, d)
    return state, args, ssd.ssd_step(state, 1, *args, interpret=True)


def test_the_step_kernel_at_a_real_block_is_the_jax_numpy_step(real_block):
    """64 heads of 64 x 128 a grid step, eight read-out products of eight
    heads' tiles each: the ``jax.numpy`` step's read-out and state; the
    idle slot's state BIT FOR BIT, layer 0 untouched."""
    state, args, (got_y, got_s) = real_block
    want_y, want_s = ssd.ssd_step_xla(state, 1, *args)
    _close(got_y, want_y, 2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=1e-6)
    assert bool((got_s[1, 1] == state[1, 1]).all())
    assert bool((got_s[0] == state[0]).all())


# The read-out's error against float64, as a share of max|y|. The shipped
# form (C against the tiles at ``Precision.HIGHEST``) reads 3e-7 here and
# 2e-7 on the v5e (PERF.md §6, PR 46); ONE bfloat16 pass reads 2e-3.
READ_OUT_BOUND = 2e-6


@pytest.mark.parametrize("form", ["kernel", "one_bf16_pass"])
def test_the_read_out_keeps_the_reference_s_precision(real_block, form):
    """``y`` of the state the kernel wrote, against that state's read-out
    in float64: within ``READ_OUT_BOUND`` of max|y|. A read-out of one
    bfloat16 pass (a lower precision than the configuration's path
    states) must fail the bound a hundred times over."""
    _state, (x, _dt, _a, _bm, cm, d), (y, s) = real_block
    if form == "one_bf16_pass":
        y = jnp.einsum(
            "bhpn,bn->bhp", s[1].astype(jnp.bfloat16),
            cm.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        ) + d[:, None] * x
    want = np.einsum(
        "bhpn,bn->bhp", np.asarray(s[1], np.float64),
        np.asarray(cm, np.float64),
    ) + np.asarray(d, np.float64)[:, None] * np.asarray(x, np.float64)
    err = np.abs(np.asarray(y, np.float64) - want).max() / np.abs(want).max()
    if form == "kernel":
        assert err <= READ_OUT_BOUND
    else:
        assert err > 100 * READ_OUT_BOUND


@pytest.mark.parametrize(
    "slots,heads,p", [(3, 4, 64), (2, 6, 16), (1, 128, 64)]
)
def test_the_step_kernel_s_read_out_comes_back_a_head_a_row(slots, heads, p):
    """``y`` [B, H, P] float32 whatever the block (4 heads: one group of
    4; 6 heads of 16: gcd 2; 128 heads: two grid steps a slot), a head's
    channels in the head's own row: every head is given a state that
    reads out its own number."""
    state = jnp.broadcast_to(
        jnp.arange(1.0, heads * p + 1).reshape(heads, p, 1),
        (1, slots, heads, p, 128),
    )
    cm = jnp.zeros((slots, 128)).at[:, 5].set(1.0)
    zeros = jnp.zeros((slots, heads, p))
    y, s = ssd.ssd_step(
        state, 0, zeros, jnp.zeros((slots, heads)), -jnp.ones((heads,)),
        cm, cm, jnp.ones((heads,)), interpret=True,
    )
    assert y.shape == (slots, heads, p) and y.dtype == jnp.float32
    np.testing.assert_array_equal(y, state[0, ..., 5])
    np.testing.assert_array_equal(s, state)


def test_the_convolution_is_causal_and_has_a_bias():
    rows = jax.random.normal(jax.random.key(0), (2, 3 + 5, 16))
    taps = jax.random.normal(jax.random.key(1), (4, 16))
    bias = jax.random.normal(jax.random.key(2), (16,))
    got = ssd.short_conv(rows, taps, bias)
    assert got.shape == (2, 5, 16)
    want = jax.nn.silu(sum(
        rows[:, i:i + 5] * taps[i] for i in range(4)
    ) + bias)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # The last token's output reads the last four rows alone.
    moved = rows.at[:, 0].add(5.0)
    np.testing.assert_allclose(
        ssd.short_conv(moved, taps, bias)[:, 1:], got[:, 1:], atol=1e-6
    )


def test_the_step_s_convolution_over_a_tail_in_one_row():
    """``conv_step`` is ``short_conv`` for one token: the same output, and
    the tail that drops its oldest row and takes the new one behind."""
    c = 256
    tail = jax.random.normal(jax.random.key(0), (3, 3, c))
    new = jax.random.normal(jax.random.key(1), (3, c))
    taps = jax.random.normal(jax.random.key(2), (4, c))
    bias = jax.random.normal(jax.random.key(3), (c,))
    want = ssd.short_conv(
        jnp.concatenate([tail, new[:, None]], axis=1), taps, bias
    )[:, 0]
    got, fresh = ssd.conv_step(tail.reshape(3, -1), new, taps, bias)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(
        fresh.reshape(3, 3, c),
        jnp.concatenate([tail[:, 1:], new[:, None]], axis=1),
    )
