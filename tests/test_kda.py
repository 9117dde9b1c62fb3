"""``ops/kda.py``: the delta rule with a decay a channel. The chunkwise
form an admission runs against the token-serial recurrence (at chunk and
sub-chunk boundaries, at a prompt of one chunk, from a carried state, at
the fastest decay the gate allows), the step kernel under the Pallas
interpreter against the ``jax.numpy`` step, and the parts of the layer
that feed them."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchkafka_tpu.ops import kda

H, E = 2, 128


def _tokens(seed: int, b: int, s: int, fastest: bool = False):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = kda.l2_norm(jax.random.normal(ks[0], (b, s, H, E)))
    k = kda.l2_norm(jax.random.normal(ks[1], (b, s, H, E)))
    v = jax.random.normal(ks[2], (b, s, H, E))
    shift = jax.random.uniform(ks[3], (1, 1, H, E), minval=-6.0, maxval=2.0)
    g = -5.0 * jax.nn.sigmoid(shift + jax.random.normal(ks[5], (b, s, H, E)))
    if fastest:
        g = jnp.full_like(g, -4.999)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, H)))
    return q, k, v, g, beta


def _serial(q, k, v, g, beta, state=None):
    """Token by token through ``kda_step_xla``: (o [B, S, H, E], state)."""
    b, s = q.shape[:2]
    if state is None:
        state = jnp.zeros((b, H, E, E), jnp.float32)
    state, outs = state[None], []
    for t in range(s):
        o, state = kda.kda_step_xla(
            state, 0, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t]
        )
        outs.append(o)
    return jnp.stack(outs, axis=1), state[0]


def _close(got, want, tol):
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= tol * scale


@pytest.mark.parametrize("s", [1, 15, 16, 17, 63, 64, 65, 128, 150])
def test_the_chunkwise_form_is_the_token_serial_recurrence(s):
    """One token, a sub-chunk and a chunk less one, whole and plus one,
    two chunks, and a length that is no multiple of either."""
    x = _tokens(s, 2, s)
    want_o, want_s = _serial(*x)
    got_o, got_s = jax.jit(kda.kda_chunk)(*x)
    assert got_o.shape == want_o.shape and got_s.shape == want_s.shape
    _close(got_o, want_o, 2e-5)
    _close(got_s, want_s, 2e-5)


def test_the_chunkwise_form_carries_a_state_across_calls():
    x = _tokens(7, 1, 96)
    first = tuple(a[:, :40] for a in x)
    rest = tuple(a[:, 40:] for a in x)
    _o, state = kda.kda_chunk(*first)
    got_o, got_s = kda.kda_chunk(*rest, state=state)
    want_o, want_s = _serial(*x)
    _close(got_o, want_o[:, 40:], 2e-5)
    _close(got_s, want_s, 2e-5)


def test_the_fastest_decay_stays_inside_float32():
    """g = -5 at every channel of every token: over a sub-chunk of 16 the
    decay's factors reach e^80 and e^-80, over a chunk of 64 e^-320; the
    sub-chunks keep every factor finite and the answer the recurrence's."""
    x = _tokens(3, 1, 128, fastest=True)
    got_o, got_s = kda.kda_chunk(*x)
    want_o, want_s = _serial(*x)
    assert bool(jnp.isfinite(got_o).all() and jnp.isfinite(got_s).all())
    _close(got_s, want_s, 1e-5)
    _close(got_o, want_o, 1e-3)
    assert kda.SUB_CHUNK * 5 < 88 < kda.CHUNK * 5


def test_a_padding_token_leaves_the_state_as_it_was():
    """A prompt of 70 is run as two chunks of 64: the 58 padding tokens
    (g 0, beta 0, k 0) decay nothing and correct nothing."""
    x = _tokens(11, 1, 70)
    _o, state = kda.kda_chunk(*x)
    _o, want = _serial(*x)
    _close(state, want, 2e-5)


@pytest.mark.parametrize("heads,block", [(4, 16), (32, 16), (32, 8)])
def test_the_step_kernel_is_the_step(heads, block, monkeypatch):
    """``tk_kda_step`` under the Pallas interpreter against the
    ``jax.numpy`` step: the read-out, the layer's slab, and every other
    layer's slab untouched."""
    monkeypatch.setattr(kda, "STEP_HEADS", block)
    ks = jax.random.split(jax.random.key(heads), 6)
    b = 3
    state = jax.random.normal(ks[0], (3, b, heads, E, E))
    q = kda.l2_norm(jax.random.normal(ks[1], (b, heads, E)))
    k = kda.l2_norm(jax.random.normal(ks[2], (b, heads, E)))
    v = jax.random.normal(ks[3], (b, heads, E))
    g = -5.0 * jax.random.uniform(ks[4], (b, heads, E))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, heads)))
    want_o, want_s = kda.kda_step_xla(state, 1, q, k, v, g, beta)
    got_o, got_s = jax.jit(
        lambda *a: kda.kda_step(*a, interpret=True)
    )(state, jnp.int32(1), q, k, v, g, beta)
    np.testing.assert_allclose(got_o, want_o, atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    np.testing.assert_array_equal(got_s[0], state[0])
    np.testing.assert_array_equal(got_s[2], state[2])


def test_a_slot_that_decays_and_corrects_nothing_keeps_its_state():
    """g 0 and beta 0 (what an inactive slot is given): the kernel writes
    back what it read, bit for bit."""
    ks = jax.random.split(jax.random.key(5), 4)
    state = jax.random.normal(ks[0], (1, 2, 4, E, E))
    q = kda.l2_norm(jax.random.normal(ks[1], (2, 4, E)))
    k = kda.l2_norm(jax.random.normal(ks[2], (2, 4, E)))
    v = jax.random.normal(ks[3], (2, 4, E))
    zero = jnp.zeros((2, 4, E))
    for step in (kda.kda_step_xla,
                 lambda *a: kda.kda_step(*a, interpret=True)):
        _o, got = step(state, 0, q, k, v, zero, zero[..., 0])
        np.testing.assert_array_equal(got, state)


def test_the_gate_stays_inside_its_bound():
    a = jnp.linspace(-40.0, 40.0, 2 * H * E).reshape(2, H, E)
    g = kda.gate(a, jnp.log(jnp.array([1.0, 2.0])), jnp.zeros((H, E)), -5.0)
    assert g.dtype == jnp.float32
    assert float(g.max()) <= 0.0 and float(g.min()) >= -5.0
    # A channel of its own: the gate is no scalar a head.
    assert len(np.unique(np.asarray(g[0, 0]))) > E // 2
    np.testing.assert_allclose(
        g[0, 1, 5], -5.0 / (1.0 + np.exp(-2.0 * float(a[0, 1, 5]))), rtol=1e-5
    )


def test_the_short_convolution_is_causal_and_keeps_a_tail():
    ks = jax.random.split(jax.random.key(2), 2)
    x = jax.random.normal(ks[0], (1, 10, 6))
    taps = jax.random.normal(ks[1], (4, 6))
    rows = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    whole = kda.short_conv(rows, taps)
    assert whole.shape == (1, 10, 6)
    want = jax.nn.silu(sum(
        taps[i] * (x[0, 7 - 3 + i] if 7 - 3 + i >= 0 else 0.0) for i in range(4)
    ))
    np.testing.assert_allclose(whole[0, 7], want, rtol=1e-5)
    # A decode token: the three rows before it in front (the conv tail).
    one = kda.short_conv(x[:, 4:8], taps)
    np.testing.assert_allclose(one[:, 0], whole[:, 7], rtol=1e-6)
