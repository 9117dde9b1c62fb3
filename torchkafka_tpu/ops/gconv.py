"""The gated short convolution (LFM2's sequence mixer): a causal depthwise
convolution over the last few tokens, gated on both sides, that keeps NO
state.

A token's projection gives three rows of C channels, ``b``, ``c`` and
``x``. With ``u = b * x`` and T taps a channel (the last the current
token's, zeros before the first token):

    v_t = sum_i taps[i] * u_{t - (T - 1) + i}        no bias, no activation
    y_t = c_t * v_t

so all a slot keeps of the layer is its last ``T - 1`` rows of ``u``: the
TAIL, which no position indexes and nothing decays (``ops/ssd.py`` and
``ops/kda.py`` keep a float32 state beside theirs). ``u`` is rounded to
the compute dtype where it is formed, as a tail holds it, so a token
reads the same rows whether the admission or an earlier tick left them;
the taps' sum and the outer gate are float32.

Over a sequence (``gconv_seq``, an admission) the convolution is a sum of
T shifted copies: no scan, no chunks. For one token a slot
(``gconv_step``, a tick) every operand is a dense ``[slots, C]`` tile: a
slot's tail lies in ONE row ``[(T - 1) * C]``, oldest first (as ``[T - 1,
C]`` the device pads the rows and re-lays each to concatenate them:
``models/linear_attn.py::slot_shapes``). Both are left to XLA (a tick's
part is some twenty bytes a channel a slot, fused behind the projection);
``tk_gconv_seq`` and ``tk_gconv_step`` name them in a trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SEQ, STEP = "tk_gconv_seq", "tk_gconv_step"


def _gate_in(b, x):
    """``u = b * x``, rounded once to the operands' dtype."""
    return (b.astype(jnp.float32) * x.astype(jnp.float32)).astype(x.dtype)


def gconv_seq(b, c, x, taps):
    """b, c, x [B, S, C] from an empty tail; taps [T, C] → (y [B, S, C] in
    the operands' dtype, the tail after the last token [B, (T - 1) * C])."""
    with jax.named_scope(SEQ):
        t, s = taps.shape[0], x.shape[1]
        u = _gate_in(b, x)
        rows = jnp.pad(u, ((0, 0), (t - 1, 0), (0, 0)))
        taps = taps.astype(jnp.float32)
        v = sum(rows[:, i:i + s].astype(jnp.float32) * taps[i] for i in range(t))
        y = (c.astype(jnp.float32) * v).astype(x.dtype)
        return y, rows[:, s:].reshape(rows.shape[0], -1)


def gconv_step(tail, b, c, x, taps, act=None):
    """One token a slot: tail [B, (T - 1) * C], b, c, x [B, C] → (y [B, C],
    the tail with its oldest row dropped and ``u`` behind). ``act`` [B]
    bool or None: a slot that is not active keeps its tail bit for bit
    (its ``y`` is never read)."""
    with jax.named_scope(STEP):
        t, ch = taps.shape[0], x.shape[-1]
        u = _gate_in(b, x).astype(tail.dtype)
        taps = taps.astype(jnp.float32)
        rows = [tail[:, i * ch:(i + 1) * ch] for i in range(t - 1)] + [u]
        v = sum(r.astype(jnp.float32) * taps[i] for i, r in enumerate(rows))
        fresh = jnp.concatenate([tail[:, ch:], u], axis=-1)
        if act is not None:
            fresh = jnp.where(act[:, None], fresh, tail)
        return (c.astype(jnp.float32) * v).astype(x.dtype), fresh
